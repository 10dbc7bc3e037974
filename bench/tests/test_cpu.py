"""bench/cpu.py: the CPU arithmetic on samples written by hand, the
parsing of a ``stat`` line, and the table by thread on what one traced
chip run of PR 28 read from /proc (bench/testdata/)."""

import json
import os

import pytest

from bench import cpu
from bench.readers import cpu as cpu_reader

DATA = os.path.join(os.path.dirname(__file__), "..", "testdata")


def test_cpu_per_interval_is_first_to_last_over_the_intervals_between():
    # five ticks 10 s apart; only the first and the last are read
    ticks = [(100.0, 50.0), (110.0, 57.0), (120.0, 99.0), None, (140.0, 74.0)]
    assert cpu.per_interval(ticks, 10.0) == pytest.approx(6.0)
    # a sample taken late is divided by the time it spans, not by 4
    assert cpu.per_interval([(100.0, 50.0), (140.5, 74.3)], 10.0) \
        == pytest.approx(24.3 / 4.05)


@pytest.mark.parametrize("ticks", [
    [], [(100.0, 50.0)], [None, (140.0, 74.0)], [(100.0, 50.0), None],
    [(100.0, 50.0), (100.0, 50.0)],
])
def test_a_missing_sample_gives_no_reading_and_never_zero(ticks):
    assert cpu.per_interval(ticks, 10.0) is None


def stat_line(tid, comm, utime, stime, start):
    rest = ["S", "1", "1", "1", "0", "-1", "0", "0", "0", "0", "0",
            str(utime), str(stime)] + ["0"] * 6 + [str(start)] + ["0"] * 30
    return f"{tid} ({comm}) " + " ".join(rest)


def test_a_stat_line_is_parsed_whatever_its_name_holds():
    assert cpu.parse_stat(stat_line(7, "python3", 250, 50, 999)) \
        == ("python3", 300 / cpu.TICKS_PER_S, 999)
    assert cpu.parse_stat(stat_line(7, "a (b) c", 1, 2, 3))[0] == "a (b) c"
    assert cpu.parse_stat("") is None
    assert cpu.parse_stat("7 (python3) S 1") is None
    # the real thing, of this process
    me = cpu.threads_of(cpu.read_stats())
    assert any(tid == os.getpid() for tid, _ in me)


def sample(t, process, threads, python):
    return {"t": t, "process": process, "python": python,
            "threads": {(tid, start): (comm, c)
                        for tid, start, comm, c in threads}}


PY = {1: "MainThread", 2: "flush-ticker", 3: "bench-cpu"}
STAGES = {1: "import", 2: "server", 3: "warmup", 10: "backend",
          11: "connect", 12: "backend"}


def test_by_thread_groups_and_loses_nothing():
    samples = [
        sample(101.0, 50.0, [(1, 5, "python3", 4.0), (2, 6, "python3", 10.0),
                             (3, 7, "python3", 0.1), (10, 8, "tf_pool", 20.0),
                             (11, 9, "python3", 3.0),
                             (12, 8, "python3", 2.0)], PY),
        # 12 ends after this sample, having used 1.0 since the first;
        # 13 is born (by an executor of the program) and has used 0.6
        sample(111.0, 57.0, [(1, 5, "python3", 4.5), (2, 6, "python3", 11.0),
                             (3, 7, "python3", 0.1), (10, 8, "tf_pool", 21.5),
                             (11, 9, "python3", 4.0), (12, 8, "python3", 3.0),
                             (13, 30, "python3", 0.6)],
               {**PY, 13: "ThreadPoolExecutor-0_0"}),
        None,  # a sample that was not taken
        # tid 12 again, another thread (another start time): from zero
        sample(121.0, 64.0, [(1, 5, "python3", 5.0), (2, 6, "python3", 12.0),
                             (3, 7, "python3", 0.1), (10, 8, "tf_pool", 23.0),
                             (11, 9, "python3", 5.0), (13, 30, "python3", 1.0),
                             (12, 40, "python3", 0.4)],
               {**PY, 13: "ThreadPoolExecutor-0_0"}),
    ]
    t = cpu.by_thread(samples, STAGES, 10.0)
    assert t["intervals"] == pytest.approx(2.0) and t["samples"] == 3
    assert t["process_cpu_s.interval"] == pytest.approx(7.0)
    g = t["groups"]
    assert g["harness"] == pytest.approx(0.5)           # main 1.0, cpu 0
    assert g["program.python"] == pytest.approx(1.5)    # ticker 2.0 + 1.0
    assert g["program.readers"] == pytest.approx(1.0)   # tid 11, 2.0
    # pool 3.0, the thread that ended 1.0, the one on its tid 0.4
    assert g["runtime"] == pytest.approx(2.2)
    assert sum(g.values()) == pytest.approx(7.0)
    assert g["unattributed"] == pytest.approx(7.0 - 0.5 - 1.5 - 1.0 - 2.2)
    rows = t["threads"]
    assert rows["runtime/tf_pool@backend"] == {
        "cpu_s.interval": pytest.approx(1.5), "threads": 1}
    assert rows["runtime/python3@backend"]["cpu_s.interval"] \
        == pytest.approx(0.5)
    assert rows["runtime/python3@window"] == {
        "cpu_s.interval": pytest.approx(0.2), "threads": 1}
    assert rows["program.python/ThreadPoolExecutor-0_0"]["cpu_s.interval"] \
        == pytest.approx(0.5)
    assert list(rows)[0] == "runtime/tf_pool@backend"   # largest first
    assert cpu_reader.read({"cpu": {"by_thread": t}}, {"group": "runtime"}) \
        == pytest.approx(2.2)


def test_fewer_than_two_samples_give_no_table_and_the_reader_nothing():
    assert cpu.by_thread([None, sample(1.0, 1.0, [], {})], {}, 10.0) is None
    assert cpu_reader.read({"cpu": {"by_thread": None}},
                           {"group": "runtime"}) is None
    assert cpu_reader.read({}, {"group": "runtime"}) is None


def test_the_sampler_takes_both_kinds_of_sample_of_this_process():
    import time

    now = time.time()
    s = cpu.Sampler([now + 0.05, now + 0.25], after_tick=0.1).start()
    stages = cpu.Stages()
    stages.mark("import")
    burn = 0
    while time.time() < now + 0.5:
        burn += 1   # CPU for the samples to see
    s.close()
    assert all(x is not None for x in s.ticks + s.threads)
    assert s.ticks[1][1] > s.ticks[0][1]
    assert cpu.per_interval(s.ticks, 0.2) == pytest.approx(
        s.ticks[1][1] - s.ticks[0][1], rel=0.2)
    assert set(s.raw) == {0, 1}
    t = cpu.by_thread(s.threads, stages.first, 0.2)
    assert t["groups"]["harness"] > 0.0           # this test's own loop
    assert "harness/bench-cpu" in t["threads"]
    assert s.late_s < 0.1


def recorded():
    """/proc/self/task/*/stat of the serving process as the traced run of
    chip call 1 of PR 28 read it (TPU v5 lite, seed 2800000011): one
    second after the first and after the last counted tick."""
    import gzip

    with gzip.open(os.path.join(
            DATA, "local-timers.steady.pr28.proc_tasks.json.gz"), "rt") as f:
        rec = json.load(f)
    samples = [{"t": s["t"], "process": s["process"],
                "python": {int(k): v for k, v in s["python"].items()},
                "threads": cpu.threads_of(s["stat"])}
               for s in rec["samples"]]
    stages = {int(k): v for k, v in rec["stages"].items()}
    return samples, stages, rec["interval_s"]


def test_the_table_by_thread_on_a_recorded_proc_sample():
    samples, stages, interval = recorded()
    assert [len(s["threads"]) for s in samples] == [205, 198]
    t = cpu.by_thread(samples, stages, interval)
    assert t["intervals"] == pytest.approx(4.0, abs=1e-3)
    # what that run's own table read (chiprun_out/c1, PERF.md section 5)
    assert t["process_cpu_s.interval"] == pytest.approx(6.542, abs=1e-3)
    g = t["groups"]
    assert g["program.python"] == pytest.approx(2.267, abs=1e-3)
    assert g["program.readers"] == pytest.approx(0.892, abs=1e-3)
    assert g["runtime"] == pytest.approx(0.777, abs=1e-3)
    # the run's five samples saw `bench-trace` (0.22 s an interval) before
    # it ended; the two kept here do not, and what it used is not lost:
    assert g["harness"] == pytest.approx(0.790 - 0.220, abs=1e-3)
    # it is here, with the profiler's export, whose threads ended too
    assert g["unattributed"] == pytest.approx(1.815 + 0.220, abs=1e-3)
    assert sum(g.values()) == pytest.approx(t["process_cpu_s.interval"])
    rows = t["threads"]
    assert list(rows)[:3] == ["program.python/micro-fold",
                              "program.readers/python3@connect",
                              "program.python/native-pump"]
    assert rows["program.readers/python3@connect"]["threads"] == 1
    assert rows["program.python/micro-fold"]["cpu_s.interval"] \
        == pytest.approx(1.257, abs=1e-3)
    # a pool's numbered threads share a row; `python3` keeps its digit
    assert rows["runtime/llvm-worker@first_tick"]["threads"] == 13
    assert rows["runtime/python3@import"]["threads"] == 12
    assert cpu_reader.read({"cpu": {"by_thread": t}}, {"group": "runtime"}) \
        == pytest.approx(0.777, abs=1e-3)
