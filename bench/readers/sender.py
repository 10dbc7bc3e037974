"""The sender's own log (due time and sendall-return time per chunk).
arg: {"what": "send_lag_ms", "pct": 99} or {"what": "burst_send_s"}."""

from bench import senderlog


def read(run: dict, arg: dict):
    t0, t1 = run["window"]
    if arg["what"] == "send_lag_ms":
        return senderlog.send_lag_ms(run["sender_log"], t0, t1, arg["pct"])
    if arg["what"] == "burst_send_s":
        return senderlog.burst_send_s(run["sender_log"], t0, t1)
    raise ValueError(f"sender reader: unknown {arg['what']!r}")
