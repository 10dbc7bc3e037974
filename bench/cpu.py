"""What the serving process costs its host: CPU seconds (user + system)
per flush interval, and the same by thread.

Two kinds of sample, both taken by one thread of the harness
(``Sampler``) that sleeps until each is due:

* at the *scheduled* tick of every counted flush: the wall clock and the
  process's CPU time (``getrusage``: every thread, the ended ones too).
  ``per_interval`` of the first and the last of them is the end-to-end
  metric ``host_cpu_s.interval``. The sender is a child process and is
  not in it; the reference runs after the last sample.
* ``THREADS_AFTER_TICK`` seconds after each of those ticks (the flush and
  the harness's own reading of it are over by then): the process's CPU
  time again and ``/proc/self/task/*/stat`` of every thread, for the
  table by thread (``by_thread``). Not at the tick: a hundred small
  files are read with the interpreter lock held, beside the flush.

A thread is known by (tid, start time), so a reused tid is a new thread.
A thread first seen inside the window is counted from zero; one that
ends inside it keeps what its last sample read, and what it used after
that is in the table's ``unattributed`` row (process less the threads),
never lost from the process's own figure.

Which thread is whose (``group_of``): a thread Python knows
(``threading.enumerate()``, by native id) is the harness's if it is the
main thread or named ``bench-*``, else the program's, under its own
name. A thread Python does not know is native. Every native thread's OS
name is the process's (``python3``) unless its owner named it, and the
program's C++ readers are not named; so a native thread is told by when
it was first seen (``Stages``): the one that appears as the sender
connects is the program's stream reader, every other is the runtime's
(XLA, libtpu, BLAS), which the program did not start.
"""

from __future__ import annotations

import os
import re
import resource
import threading
import time

THREADS_AFTER_TICK = 1.0
TASKS = "/proc/self/task"
TICKS_PER_S = os.sysconf("SC_CLK_TCK")
HARNESS, PROGRAM, READERS, RUNTIME = (
    "harness", "program.python", "program.readers", "runtime")
READER_STAGE = "connect"


def process_cpu_s() -> float:
    """User + system CPU seconds of this process, all threads."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def parse_stat(text: str):
    """One ``/proc/<pid>/task/<tid>/stat`` line -> (comm, CPU seconds,
    start time in clock ticks), or None if it is not one. The name sits
    in parentheses and may itself hold spaces and parentheses."""
    lo, hi = text.find("("), text.rfind(")")
    if lo < 0 or hi < lo:
        return None
    rest = text[hi + 1:].split()
    # after the name: state is field 3, utime 14, stime 15, starttime 22
    if len(rest) < 20:
        return None
    try:
        return (text[lo + 1:hi], (int(rest[11]) + int(rest[12])) / TICKS_PER_S,
                int(rest[19]))
    except ValueError:
        return None


def read_stats() -> dict:
    """{tid: its ``stat`` line} of every thread alive."""
    out = {}
    for tid in os.listdir(TASKS):
        try:
            with open(os.path.join(TASKS, tid, "stat")) as f:
                out[int(tid)] = f.read()
        except OSError:  # the thread ended between the listing and here
            continue
    return out


def threads_of(stats: dict) -> dict:
    """``read_stats`` -> {(tid, start time): (comm, CPU seconds)}."""
    out = {}
    for tid, text in stats.items():
        got = parse_stat(text)
        if got is not None:
            out[(int(tid), got[2])] = (got[0], got[1])
    return out


def python_threads() -> dict:
    """{native id: name} of the threads Python started. A foreign thread
    that once called into Python shows up as ``Dummy-n``: it is native."""
    return {t.native_id: t.name for t in threading.enumerate()
            if t.native_id is not None and not t.name.startswith("Dummy-")}


class Stages:
    """When each thread was first seen: ``mark(stage)`` at points of the
    run; a thread belongs to the first stage whose mark found it."""

    def __init__(self) -> None:
        self.first: dict = {}

    def mark(self, stage: str) -> list:
        """The tids this mark saw for the first time."""
        new = [t for t in map(int, os.listdir(TASKS)) if t not in self.first]
        for t in new:
            self.first[t] = stage
        return new

    def wait_for_native(self, stage: str, timeout: float) -> list:
        """Mark until a thread that Python did not start turns up (the
        C++ reader of a connection just accepted), at most ``timeout``
        seconds. Returns those tids."""
        limit = time.time() + timeout
        while True:
            py = python_threads()
            new = [t for t in self.mark(stage) if t not in py]
            if new or time.time() > limit:
                return new
            time.sleep(0.01)


def group_of(name, comm: str, stage: str) -> tuple:
    """(group, row name) of one thread, from the name Python gave it (None
    for a native thread), its OS name and the stage at which it was
    first seen: see the module's docstring."""
    if name is not None:
        mine = name == "MainThread" or name.startswith("bench-")
        return (HARNESS if mine else PROGRAM), name
    # a pool's threads are numbered (llvm-worker-7): one row for the pool
    pool = re.sub(r"[-_]\d+$", "", comm)
    return (READERS if stage == READER_STAGE else RUNTIME), f"{pool}@{stage}"


def per_interval(samples: list, interval_s: float):
    """CPU seconds per interval between the first and the last of
    ``samples`` (each ``(wall time, process CPU seconds)`` or None), or
    None where either is missing or they are not an interval apart: a
    reading that is not there is never 0."""
    if len(samples) < 2 or samples[0] is None or samples[-1] is None:
        return None
    (t0, c0), (t1, c1) = samples[0], samples[-1]
    intervals = (t1 - t0) / interval_s
    if intervals < 0.5:
        return None
    return (c1 - c0) / intervals


def by_thread(samples: list, stages: dict, interval_s: float):
    """The table by thread from the samples taken after the ticks, each
    ``{"t", "process", "threads": {(tid, start): (comm, cpu)}, "python":
    {tid: name}}``: CPU seconds per interval of every thread between
    the first and the last sample that saw it, by group and by row, with
    what the process used and no thread shows as ``unattributed``. None
    with fewer than two samples."""
    samples = [s for s in samples if s is not None]
    if len(samples) < 2:
        return None
    first, last = samples[0], samples[-1]
    intervals = (last["t"] - first["t"]) / interval_s
    if intervals < 0.5:
        return None
    used: dict = {}   # key -> [group, row, cpu at first sight, at last]
    for i, s in enumerate(samples):
        for key, (comm, cpu) in s["threads"].items():
            if key not in used:
                # first seen after the first sample: born in the window
                # (whatever thread had its tid before), counted from 0
                group, row = group_of(
                    s["python"].get(key[0]), comm,
                    stages.get(key[0], "window") if i == 0 else "window")
                used[key] = [group, row, cpu if i == 0 else 0.0, cpu]
            else:
                used[key][3] = cpu
    rows: dict = {}
    groups = {HARNESS: 0.0, PROGRAM: 0.0, READERS: 0.0, RUNTIME: 0.0}
    for group, row, c0, c1 in used.values():
        e = rows.setdefault(f"{group}/{row}", {"cpu_s.interval": 0.0,
                                               "threads": 0})
        e["cpu_s.interval"] += (c1 - c0) / intervals
        e["threads"] += 1
        groups[group] += (c1 - c0) / intervals
    process = (last["process"] - first["process"]) / intervals
    groups["unattributed"] = process - sum(groups.values())
    return {"interval_s": interval_s, "intervals": intervals,
            "samples": len(samples), "process_cpu_s.interval": process,
            "groups": groups,
            "threads": dict(sorted(rows.items(),
                                   key=lambda kv: -kv[1]["cpu_s.interval"]))}


class Sampler:
    """The thread that takes both kinds of sample at the times given
    (``ticks``: the scheduled ticks of the counted flushes)."""

    def __init__(self, ticks: list,
                 after_tick: float = THREADS_AFTER_TICK) -> None:
        self.due = sorted([(t, "tick", i) for i, t in enumerate(ticks)]
                          + [(t + after_tick, "threads", i)
                             for i, t in enumerate(ticks)])
        self.ticks: list = [None] * len(ticks)
        self.threads: list = [None] * len(ticks)
        self.raw: dict = {}  # sample number -> {tid: stat line}
        self.late_s = 0.0   # the latest any sample was taken
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-cpu",
                                        daemon=True)

    def start(self) -> "Sampler":
        self._thread.start()
        return self

    def _run(self) -> None:
        for at, kind, i in self.due:
            if self._stop.wait(max(0.0, at - time.time())):
                return
            now = time.time()
            if kind == "tick":
                self.ticks[i] = (now, process_cpu_s())
            else:
                stats = read_stats()
                self.threads[i] = {
                    "t": now, "process": process_cpu_s(),
                    "python": python_threads(),
                    "threads": threads_of(stats)}
                if i in (0, len(self.threads) - 1):
                    # as read, for bench/testdata: the first and the last
                    self.raw[i] = stats
            self.late_s = max(self.late_s, now - at)

    def close(self) -> None:
        """Ends the thread, whatever is still due stays None."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
