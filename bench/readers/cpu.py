"""The table by thread (bench/cpu.py ``by_thread``, also written to
bench/out/<cell>.cpu_by_thread.json): CPU seconds per flush interval
inside the window. arg: {"group": "runtime" | "program.python" |
"program.readers" | "harness" | "unattributed"}."""


def read(run: dict, arg: dict):
    table = (run.get("cpu") or {}).get("by_thread")
    if table is None:
        return None
    return table["groups"].get(arg["group"])
