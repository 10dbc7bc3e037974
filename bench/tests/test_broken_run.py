"""A whole run at rehearsal size (CPU, no look for a chip) with the
timed path broken underneath must end ``correct: false``: one answer
altered where the sink receives it, and one line written twice by the
sender. Needs JAX and the program; about a minute each."""

import argparse
import os

import pytest

pytest.importorskip("jax")
pytest.importorskip("veneur_tpu")

from bench import run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault,expect", [
    ("alter", ("mismatch", "no cut", "no position")),
    ("double", ("no cut", "mismatch", "lines_missing", "more lines")),
])
def test_a_broken_path_ends_incorrect(fault, expect):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = argparse.Namespace(
        workload="rehearsal-timers.steady", seed=2**31 + 11, seconds=5.0,
        trace=0, control=0, benchmark_file=os.path.join(
            HERE, "..", "testdata", "rehearsal_benchmark.json"))
    holder: dict = {}
    try:
        done = run.run_cell(args, holder, fault=fault)
    finally:
        if holder.get("server") is not None:
            holder["server"].shutdown()
    assert done["correct"] is False
    assert done["result"] is None  # off the chip: no result line
    assert any(any(e in r for e in expect) for r in done["reasons"]), \
        done["reasons"]


def test_the_hold_after_the_first_cycle_leaves_a_sound_run_correct(
        monkeypatch):
    """Every run holds its sender after the first cycle and resumes it
    once the first flushes are over: the point is kept with the sender's
    count, and the chain of cuts still closes."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    events = []
    emit = run.emit

    def listen(event, **fields):
        events.append((event, fields))
        emit(event, **fields)

    monkeypatch.setattr(run, "emit", listen)
    args = argparse.Namespace(
        workload="rehearsal-timers.steady", seed=2**31 + 13, seconds=5.0,
        trace=0, control=0, benchmark_file=os.path.join(
            HERE, "..", "testdata", "rehearsal_benchmark.json"))
    holder: dict = {}
    try:
        done = run.run_cell(args, holder)
    finally:
        if holder.get("server") is not None:
            holder["server"].shutdown()
    resumed = [f for e, f in events if e == "resume"]
    assert resumed and resumed[0]["lines"] > 0 and resumed[0]["shed"] == 0
    assert done["correct"] is True, done["reasons"]
