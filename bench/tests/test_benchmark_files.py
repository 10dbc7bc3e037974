"""BENCHMARK.json against the files under bench/: every configuration,
cell and per-layer metric has its file and the names agree; every
end-to-end metric is one that ``bench/run.py read_metrics`` gives a value
for; and the ring that ``local-timers`` builds from a seed is, byte for
byte, the one the accepted benchmark built (PR 24), so that a correction
of the configuration's texts has provably changed no line."""

import hashlib
import importlib
import json
import os

import pytest

from bench import run, stream

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

# sha256 over (dtype, bytes) of cls, sid, val: taken from the parent
# (commit e72cffa, PR 26) before any file of the benchmark was edited
RING_SEED, RING_LINES = 2800000001, 1862493
RING_SHA256 = \
    "9db68ac74c944ff6ddcf93fba9f1129f378e96c69678aa795903d696c55baa69"


def test_the_paths_hold_the_command_and_every_file_named():
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert os.path.exists(os.path.join(ROOT, BENCH["command"][1]))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_a_configuration_has_its_file_under_its_own_name(entry):
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    config = stream.load_json("configs", entry["name"])
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    assert set(config["reduced_why"]) == set(entry["reduced"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    # the generator its lines name is there
    stream.generator(config["lines"]["generator"]).build_ring


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_cell_is_a_configuration_and_a_traffic_file(cell):
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    traffic = stream.load_json("traffic", cell["traffic"])
    assert traffic["name"] == cell["traffic"]
    stream.generator(traffic["arrival"]).due_offsets
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_metric_has_its_file_and_its_reader(m):
    spec = stream.load_json("layer_metrics", m["name"])
    for key in ("name", "unit", "layer", "moves", "source", "better"):
        assert spec[key] == m[key], key
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    assert callable(importlib.import_module(
        "bench.readers." + spec["reader"]).read)
    cells = [w["name"] for w in BENCH["workloads"]]
    assert all(w in cells for w in m.get("workloads", []))


def synthetic_run(cell: dict) -> dict:
    """Five flushes 10 s apart from t=100, five whole cycles of the
    sender, and the CPU samples at the five ticks."""
    starts = [102.0 + 10.0 * k for k in range(5)]
    return {
        "cell": cell, "window": (100.0, 141.0),
        "flushes": [{"flush_s": 0.15 + 0.01 * k} for k in range(5)],
        "sender_log": {
            "cycle_start": starts, "interval_s": 10.0,
            "lines_per_cycle": 1000, "chunks_per_cycle": 2,
            "cycle": [k for k in range(5) for _ in range(2)],
            "due": [s + 5.0 * j for s in starts for j in range(2)],
            "done": [s + 5.0 * j + 0.001 for s in starts for j in range(2)]},
        "cpu": {"ticks": [(100.0 + 10.0 * k, 50.0 + 6.5 * k)
                          for k in range(5)],
                "interval_s": 10.0, "by_thread": None}}


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_an_end_to_end_metric_is_bounded_and_read_metrics_gives_it(m):
    assert m["unit"] and m["better"] in ("lower", "higher")
    assert 0.0 < m["bound"] <= 0.25
    assert m["source"] in ("host_clock", "device_trace")
    for cell in BENCH["workloads"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        got = run.read_metrics(BENCH, cell, synthetic_run(cell), 90.0, False)
        assert got[m["name"]]["unit"] == m["unit"]
        assert got[m["name"]]["value"] > 0.0


def test_read_metrics_on_the_synthetic_run():
    cell = BENCH["workloads"][0]
    got = run.read_metrics(BENCH, cell, synthetic_run(cell), 90.0, False)
    assert set(got) == {m["name"] for m in BENCH["end_to_end"]}
    assert got["host_cpu_s.interval"]["value"] == pytest.approx(6.5)
    assert got["flush_s.mean"]["value"] == pytest.approx(0.17)
    assert got["lines_per_s"]["value"] == pytest.approx(1000 / 5.001)
    assert got["setup_s"]["value"] == 90.0
    # a run without its first CPU sample reports no host_cpu_s.interval
    broken = synthetic_run(cell)
    broken["cpu"]["ticks"][0] = None
    assert "host_cpu_s.interval" not in run.read_metrics(
        BENCH, cell, broken, 90.0, False)


def test_the_ring_of_local_timers_is_byte_for_byte_the_accepted_one():
    ring = stream.build_ring(stream.load_json("configs", "local-timers"),
                             RING_SEED)
    assert len(ring) == RING_LINES
    h = hashlib.sha256()
    for a in (ring.cls, ring.sid, ring.val):
        h.update(a.dtype.str.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == RING_SHA256
