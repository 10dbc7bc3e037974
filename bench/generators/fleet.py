"""Line generator: a host fleet's local agent (chip_smoke.py's lines).

Per interval: ``cold_samples`` samples to each ordinary timer,
``hot_samples`` lognormal samples to each of the first ``hot_series``
timers, ``counter_incs`` increments to each counter, ``gauge_writes``
writes to each gauge, and sets of 1 to ``set_max_members`` members. The
whole interval is then shuffled by one seeded permutation, so that every
second of it carries the same mix.
"""

import numpy as np


def set_cardinalities(n_sets: int, max_members: int) -> np.ndarray:
    """Most sets small (1..100 members), 16 from 128 members towards
    the bound where a sparse host row is promoted to a dense device row
    (2^14 / 8 = 2,048 distinct registers), 8 from a tenth of
    ``max_members`` up to it."""
    n_mid, n_large = (16, 8) if n_sets >= 64 else (2, 1)
    small = np.round(np.geomspace(1, 100, n_sets - n_mid - n_large))
    mid = np.round(np.geomspace(128, min(4096, max_members), n_mid))
    large = np.round(np.geomspace(max(1, max_members // 10), max_members,
                                  n_large))
    return np.concatenate([small, mid, large]).astype(np.int64)


def build_ring(lines: dict, series: dict, rng) -> tuple:
    n_t, n_hot = series["timer"], lines["hot_series"]
    if n_t <= n_hot:
        raise ValueError("more hot timers than timers")
    n_cold = n_t - n_hot
    # multiples of 0.25 below 2^17: exact in float32, so min and max
    # can be compared exactly
    cold = rng.integers(4, 400000, (n_cold, lines["cold_samples"])) / 4.0
    hot = np.exp(rng.normal(3.0, 1.0, (n_hot, lines["hot_samples"])))
    hot = np.clip(np.round(hot * 4.0), 1, 400000) / 4.0
    incs = rng.integers(1, 1000, (series["counter"], lines["counter_incs"]))
    gauges = rng.integers(0, 1 << 20,
                          (series["gauge"], lines["gauge_writes"])) / 4.0
    cards = set_cardinalities(series["set"], lines["set_max_members"])

    def block(c, ids, vals):
        per = vals.shape[1]
        return (np.full(vals.size, c, np.int8),
                np.repeat(ids, per), vals.reshape(-1))

    set_sid = np.repeat(np.arange(series["set"]), cards)
    set_member = np.concatenate([np.arange(k) for k in cards.tolist()])
    blocks = [
        block(2, np.arange(n_hot), hot),
        block(2, np.arange(n_hot, n_t), cold),
        block(0, np.arange(series["counter"]), incs),
        block(1, np.arange(series["gauge"]), gauges),
        (np.full(len(set_sid), 3, np.int8), set_sid, set_member),
    ]
    cls, sid, val = (np.concatenate(x) for x in zip(*blocks))
    perm = rng.permutation(len(cls))
    return cls[perm], sid[perm], val[perm].astype(np.float64)
