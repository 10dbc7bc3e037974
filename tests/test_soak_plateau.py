"""RSS-plateau judgment for the topology soak (tools/soak_topology.py).

The multi-hour leak-hunt mode (--min-intervals / --min-duration) passes
only when the post-warmup rss_growth_per_interval_mb window series
falls monotonically — a process whose per-interval growth keeps rising
is leaking, however small each step. The classifier is pure, so the
tier-1 lane pins its edges on synthetic series here; the slow-marked
test drives the real soak end to end at miniature scale and checks the
artifact carries the series and verdict.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

from soak_topology import (  # noqa: E402
    attribute_tail_growth, churn_rebound_windows, classify_rss_plateau)


def test_plateau_falling_series_passes():
    out = classify_rss_plateau([2.0, 0.8, 0.3, 0.1, 0.05])
    assert out["judgeable"] and out["plateau_ok"]
    assert out["monotonic_falling"] and out["rising_at_window"] is None


def test_plateau_rising_series_fails_and_names_the_window():
    out = classify_rss_plateau([0.5, 0.2, 0.2, 0.9])
    assert out["judgeable"]
    assert not out["plateau_ok"]
    assert out["rising_at_window"] == 3


def test_plateau_noise_floor_tolerates_jitter():
    # +0.03 MB/interval window-to-window is allocator noise, not a leak
    out = classify_rss_plateau([0.50, 0.20, 0.23, 0.21])
    assert out["plateau_ok"]
    # an explicit tighter floor turns the same jitter into a failure
    out = classify_rss_plateau([0.50, 0.20, 0.23, 0.21], tol=0.01)
    assert not out["plateau_ok"]


def test_plateau_churn_rebound_is_excused_not_a_leak():
    # a real trace shape: falling, then a join at window 3 recompiles
    # the forward path (growth rebounds), then falls again to the tail
    series = [2.0, 0.8, 0.3, 1.1, 0.4, 0.1]
    out = classify_rss_plateau(series)
    assert not out["plateau_ok"] and out["rising_at_window"] == 3
    out = classify_rss_plateau(series, rebound_windows=[3])
    assert out["plateau_ok"] and out["rising_at_window"] is None
    assert out["excused_rebounds"] == 1
    assert out["monotonic_falling"]


def test_plateau_tail_must_still_fall_after_excused_rebound():
    # the excuse restarts the chain; a rise AFTER the churn window is
    # still a leak
    out = classify_rss_plateau([2.0, 0.8, 1.1, 0.4, 0.9],
                               rebound_windows=[2])
    assert not out["plateau_ok"]
    assert out["rising_at_window"] == 4
    assert out["excused_rebounds"] == 1


def test_churn_rebound_windows_maps_intervals_to_windows():
    # windows of 5 intervals closing at 15/20/25: spans (10,15], (15,20],
    # (20,25] — with the soak's close-before-churn ordering a churn at
    # interval c lands in the window with start <= c < upto
    wins = [{"upto_interval": u, "intervals": 5, "rss_mb": 0.0,
             "growth_per_interval_mb": 0.0} for u in (15, 20, 25)]
    # churn at 17 → window 1 elevated, window 2 may carry the compile tail
    assert churn_rebound_windows(wins, [17]) == [1, 2]
    # churn past the last window excuses nothing
    assert churn_rebound_windows(wins, [25]) == []
    assert churn_rebound_windows(wins, []) == []


def test_plateau_short_series_judges_nothing():
    for series in ([], [1.0], [1.0, 2.0]):
        out = classify_rss_plateau(series)
        assert not out["judgeable"]
        assert out["plateau_ok"]  # never gates with too few windows


def _win(rss, py=None):
    w = {"growth_per_interval_mb": rss}
    if py is not None:
        w["py_heap_growth_per_interval_mb"] = py
    return w


def test_tail_attribution_names_the_dominant_side():
    # the residual tail is mostly native (XLA caches / malloc arenas):
    # the python heap explains only a sliver of what RSS gained
    out = attribute_tail_growth(
        [_win(2.0, 1.5), _win(0.10, 0.01), _win(0.08, 0.01),
         _win(0.06, 0.02)])
    assert out["judgeable"] and out["windows"] == 3
    assert out["dominant"] == "native"
    assert out["py_heap_fraction"] < 0.5
    # flip it: the python heap explains the whole tail
    out = attribute_tail_growth(
        [_win(0.10, 0.09), _win(0.08, 0.08), _win(0.06, 0.06)])
    assert out["dominant"] == "python_heap"
    assert out["py_heap_fraction"] >= 0.5


def test_tail_attribution_clamps_and_degenerate_cases():
    # a SHRINKING python heap inside growing RSS: all-native, frac 0
    out = attribute_tail_growth(
        [_win(0.10, -0.50), _win(0.10, -0.40), _win(0.10, -0.30)])
    assert out["dominant"] == "native" and out["py_heap_fraction"] == 0.0
    # flat-or-falling RSS tail: nothing to attribute
    out = attribute_tail_growth(
        [_win(-0.05, 0.0), _win(0.0, 0.0), _win(-0.01, 0.0)])
    assert out["dominant"] == "none"
    # windows recorded before the tracemalloc sampling began (no
    # py_heap key) are excluded from the tail
    out = attribute_tail_growth([_win(5.0), _win(0.1, 0.05)])
    assert out["windows"] == 1
    # no instrumented windows at all: not judgeable
    assert not attribute_tail_growth([_win(5.0)])["judgeable"]


@pytest.mark.slow
def test_soak_topology_short_run_records_plateau_series(tmp_path):
    """End-to-end miniature soak: the artifact must carry the window
    series and the classifier's verdict. Tiny series counts and 14
    intervals (warmup 10 + one 2-interval window x2) keep this minutes,
    not hours — still slow-marked out of tier-1."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", VENEUR_SOAK_INTERVALS="14",
               VENEUR_SOAK_HISTO_SERIES="60",
               VENEUR_SOAK_COUNTER_SERIES="20",
               VENEUR_ARTIFACT_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "soak_topology.py"),
         "--rss-window", "2"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    art = json.load(open(tmp_path / "TOPOLOGY_SOAK.json"))
    assert art["conservation_ok"]
    assert art["rss_window_intervals"] == 2
    assert len(art["rss_windows"]) >= 2
    for w in art["rss_windows"]:
        assert set(w) == {"upto_interval", "rss_mb", "intervals",
                          "growth_per_interval_mb"}
    assert set(art["rss_plateau"]) == {"judgeable", "monotonic_falling",
                                       "rising_at_window",
                                       "excused_rebounds", "plateau_ok"}
    assert art["rss_plateau_gates"] is False  # default run records only
