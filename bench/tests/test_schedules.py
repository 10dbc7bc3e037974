"""Both arrival schedules' due times, and lines_per_s, burst_send_s and
send_lag_ms.p99 on a synthetic sender log."""

import numpy as np
import pytest

from bench import senderlog, stream


def test_steady_due_times_are_even_across_the_interval():
    off = stream.due_offsets({"arrival": "steady"}, 4, 10.0)
    assert off.tolist() == [0.0, 2.5, 5.0, 7.5]


def test_burst_due_times_are_all_at_the_cycle_start():
    assert stream.due_offsets({"arrival": "burst"}, 5, 10.0).tolist() \
        == [0.0] * 5


def synthetic_log(send_s, n_cycles=5, chunks=4, lag=0.001):
    """Cycles start every 10 s from t=100; chunk j is due start + j and
    returns ``lag`` later, except the last, which returns at
    start + send_s."""
    cyc, due, done, starts = [], [], [], []
    for k in range(n_cycles):
        start = 100.0 + 10.0 * k
        starts.append(start)
        for j in range(chunks):
            cyc.append(k)
            due.append(start + j)
            done.append(start + send_s if j == chunks - 1
                        else start + j + lag)
    return {"cycle_start": starts, "interval_s": 10.0,
            "lines_per_cycle": 1000, "chunks_per_cycle": chunks,
            "cycle": cyc, "due": due, "done": done}


def test_lines_per_s_counts_only_cycles_that_start_in_the_window():
    log = synthetic_log(send_s=4.0)
    # window [108, 131): the cycles at 110, 120, 130 start in it
    assert senderlog.lines_per_s(log, 108.0, 131.0) == pytest.approx(
        3000 / 12.0)
    assert senderlog.burst_send_s(log, 108.0, 131.0) == pytest.approx(4.0)
    assert len(senderlog.cycles(log, 108.0, 131.0)) == 3


def test_an_unfinished_cycle_is_left_out():
    log = synthetic_log(send_s=4.0)
    for key in ("cycle", "due", "done"):
        log[key] = log[key][:-1]  # the last cycle lacks its last chunk
    assert [c["cycle"] for c in senderlog.cycles(log, 0.0, 1e9)] \
        == [0, 1, 2, 3]


def test_send_lag_percentile_is_taken_from_due_times():
    log = synthetic_log(send_s=3.5)  # last chunk due at +3, back at +3.5
    lags = np.array([1.0, 1.0, 1.0, 500.0] * 3)
    assert senderlog.send_lag_ms(log, 108.0, 131.0, 99) == pytest.approx(
        float(np.percentile(lags, 99)))
    assert senderlog.lines_per_s(log, 0.0, 50.0) is None
