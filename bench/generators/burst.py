"""Arrival pattern: every chunk of the cycle is due at the cycle's start
(clients that buffer and flush on one timer), so the sender writes as
fast as the connection takes them and is then silent."""


def due_offsets(traffic: dict, n_chunks: int, interval_s: float) -> list:
    return [0.0] * n_chunks
