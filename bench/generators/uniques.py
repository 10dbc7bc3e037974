"""Line generator: an API or edge service that counts unique users.

For every request the service sends the three lines the DogStatsD client
documentation pairs: the request's timer, its counter, and a ``|s`` line
that carries the user's id into a set tagged by endpoint. Endpoint e is
timer e, counter e, set e and gauge e (its requests in flight).

``requests_per_interval`` requests over E endpoints (E = ``series.set``
= ``series.timer`` = ``series.counter``). The endpoint of rank r takes a
**fixed** count n_r proportional to r^-``zipf_s`` (floor, the remainder
to rank 1), so every seed sends the same work. A request is three
adjacent lines: timer (lognormal on the 0.25 grid, as ``zipf_mix``),
counter (+1), set (an id drawn uniformly from the endpoint's universe of
``universe_per_request`` x n_r ids: at 4 a user mostly comes once an
interval, 88.5% of an endpoint's requests are distinct ids). Each gauge
is written ``gauge_writes`` times.

The seed permutes which endpoint holds which rank, draws every request's
user and the timers' and gauges' values, and orders the interval by one
permutation, requests and gauge writes together (the reference finds its
cuts from gauges and sets).
"""

import numpy as np


def rank_counts(requests: int, endpoints: int, zipf_s: float) -> np.ndarray:
    """n_r for r = 1..endpoints: floor of the Zipf share, the remainder
    to rank 1."""
    p = np.arange(1, endpoints + 1, dtype=np.float64) ** -float(zipf_s)
    n = np.floor(requests * (p / p.sum())).astype(np.int64)
    n[0] += requests - int(n.sum())
    return n


def build_ring(lines: dict, series: dict, rng) -> tuple:
    e = series["set"]
    if not (series["timer"] == series["counter"] == e):
        raise ValueError("an endpoint is one timer, one counter and one set: "
                         "series.timer, .counter and .set must be equal")
    n_req = int(lines["requests_per_interval"])
    n_r = rank_counts(n_req, e, lines["zipf_s"])
    req_ep = np.repeat(rng.permutation(e), n_r)
    universe = np.repeat(int(lines["universe_per_request"]) * n_r, n_r)
    req_user = np.floor(rng.random(n_req) * universe)
    n_gw = series["gauge"] * int(lines["gauge_writes"])
    # one unit a request (three lines) or a gauge write (one line)
    unit_ep = np.concatenate([
        req_ep,
        np.repeat(np.arange(series["gauge"]), int(lines["gauge_writes"]))])
    order = rng.permutation(n_req + n_gw)
    unit_ep, is_req = unit_ep[order], order < n_req
    first = np.concatenate([[0], np.cumsum(np.where(is_req, 3, 1))])
    total = int(first[-1])
    first = first[:-1]
    cls = np.empty(total, np.int8)
    sid = np.empty(total, np.int64)
    val = np.empty(total, np.float64)
    at, ep = first[is_req], unit_ep[is_req]
    for k, c in enumerate((2, 0, 3)):             # timer, counter, set
        cls[at + k] = c
        sid[at + k] = ep
    # multiples of 0.25 below 2^17: exact in float32
    val[at] = np.clip(np.round(np.exp(rng.normal(3.0, 1.0, n_req)) * 4.0),
                      1, 400000) / 4.0
    val[at + 1] = 1.0
    val[at + 2] = req_user[order[is_req]]
    at = first[~is_req]
    cls[at] = 1
    sid[at] = unit_ep[~is_req]
    val[at] = rng.integers(0, 1 << 20, n_gw) / 4.0
    return cls, sid, val
