"""JAX's compile log inside the measured window (bench/run.py's
CompileClock). arg: {"what": "n"}: programs compiled or loaded from the
persistent cache; {"what": "compiled"}: real compilations only. 0 is
what a warmed-up run should read."""


def read(run: dict, arg: dict):
    inside = run.get("window_compiles")
    if inside is None:
        return None
    return float(inside[arg["what"]])
