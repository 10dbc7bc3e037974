"""Arrival pattern: the cycle's chunks are due at evenly spaced times
across the interval (a fleet's many clients, each on its own clock)."""


def due_offsets(traffic: dict, n_chunks: int, interval_s: float) -> list:
    return [interval_s * j / n_chunks for j in range(n_chunks)]
