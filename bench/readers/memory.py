"""device.memory_stats() after the window: the peak on the fullest chip.
arg: {"scale": number}."""


def read(run: dict, arg: dict):
    peaks = [p for p in run["memory_peaks"] if p]
    if not peaks:
        return None
    return arg.get("scale", 1.0) * max(peaks)
