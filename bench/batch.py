#!/usr/bin/env python3
"""Several runs of bench/run.py in one call, one process after another
(a chip belongs to one process at a time), as the sets of runs behind
the bounds were made:

    chiprun -- python3 bench/batch.py local-timers.steady:41:0:101,102,103

Each argument is <workload>:<seconds>:<trace>:<seed>[,<seed>...], with
an optional fifth field ``c`` to print the lower-precision control too.
Every run's output goes to chiprun_out/<workload>.<seed>.t<trace>.log;
standard output gets one summary line a run. Imports neither JAX nor
the program."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEEP = ("window", "limits", "control", "failure", "counters", "sender",
        "cpu", "log_warnings", "trace")


def main() -> int:
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for spec in sys.argv[1:]:
        workload, seconds, trace, seeds, *rest = spec.split(":")
        for seed in seeds.split(","):
            cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
                   "--workload", workload, "--seed", seed,
                   "--seconds", seconds, "--trace", trace]
            if "c" in rest:
                cmd += ["--control", "1"]
            log = os.path.join(out_dir, f"{workload}.{seed}.t{trace}.log")
            t0 = time.time()
            with open(log, "w") as f, open(log + ".err", "w") as e:
                rc = subprocess.run(cmd, stdout=f, stderr=e, cwd=ROOT).returncode
            piece = os.path.join(ROOT, "bench", "out",
                                 f"{workload}.{seed}.slice.json.gz")
            if os.path.exists(piece):
                shutil.copy(piece, out_dir)
            with open(log) as f:
                lines = f.read().splitlines()
            flush_s = []
            for ln in lines:
                try:
                    ev = json.loads(ln)
                except ValueError:
                    continue
                if ev.get("event") == "flush":
                    flush_s.append([ev["ordinal"], round(ev["flush_s"], 3),
                                    round(ev["late_s"], 3),
                                    ev["compiled"]["n"],
                                    round(ev["compiled"]["s"], 1)])
                elif ev.get("event") in KEEP:
                    print(json.dumps(ev)[:3000], flush=True)
            print(json.dumps({"summary": spec, "seed": seed, "rc": rc,
                              "wall_s": round(time.time() - t0, 1),
                              "flushes": flush_s,
                              "last": lines[-1][:6000] if lines else ""}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
