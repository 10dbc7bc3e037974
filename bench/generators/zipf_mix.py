"""Line generator: services whose clients buffer, a few keys taking most
lines. ``lines_per_interval`` lines drawn i.i.d. from Zipf(``zipf_s``)
over every series of the deployment.

The types are dealt to the ranks by ``rank_pattern`` (one letter of
c/g/t/s per rank, repeated), so the hot keys are of every type and are
of the same types under every seed: the seed permutes which series of a
class gets which rank and draws the lines, it does not change the work.
Timer values lognormal on the 0.25 grid, counter increments 1-999, gauge
values on the 0.25 grid, set k draws members from a universe of U_k, U
geometric from ``set_universe_min`` to ``set_universe_max``."""

import numpy as np

ORDER = ("counter", "gauge", "timer", "set")


def build_ring(lines: dict, series: dict, rng) -> tuple:
    total = sum(series[c] for c in ORDER)
    pattern = np.array(["cgts".index(ch) for ch in lines["rank_pattern"]],
                       np.int8)
    rank_cls = np.resize(pattern, total)
    rank_sid = np.empty(total, np.int64)
    for c, name in enumerate(ORDER):
        at = np.nonzero(rank_cls == c)[0]
        if len(at) != series[name]:
            raise ValueError(f"rank_pattern deals {len(at)} {name} series, "
                             f"the config has {series[name]}")
        rank_sid[at] = rng.permutation(series[name])
    p = np.arange(1, total + 1, dtype=np.float64) ** -float(lines["zipf_s"])
    cdf = np.cumsum(p / p.sum())
    n = int(lines["lines_per_interval"])
    rank = np.minimum(np.searchsorted(cdf, rng.random(n)), total - 1)
    cls, sid = rank_cls[rank], rank_sid[rank]
    val = np.empty(n, np.float64)
    m = cls == 0
    val[m] = rng.integers(1, 1000, int(m.sum()))
    m = cls == 1
    val[m] = rng.integers(0, 1 << 20, int(m.sum())) / 4.0
    m = cls == 2
    # multiples of 0.25 below 2^17: exact in float32
    val[m] = np.clip(np.round(np.exp(rng.normal(3.0, 1.0, int(m.sum())))
                              * 4.0), 1, 400000) / 4.0
    m = cls == 3
    universe = np.round(np.geomspace(lines["set_universe_min"],
                                     lines["set_universe_max"],
                                     series["set"])).astype(np.int64)
    val[m] = np.floor(rng.random(int(m.sum())) * universe[sid[m]])
    return cls, sid, val
