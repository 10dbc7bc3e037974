"""Per-core parse throughput + multi-process SO_REUSEPORT scaling.

VERDICT r4 item 4: the 50M samples/s/chip north star is host-parse
bound, and round 4 only ever *extrapolated* the parse rate. This tool
measures it:

1. `native/parse_bench` (built on demand): single-core C++ phases —
   parse-only, parse+commit, and the wire-facing datagram API — with
   cycles/line from rdtsc.
2. Multi-process scaling: N copies of parse_bench run concurrently
   (processes, not threads — the SO_REUSEPORT deployment shape, one
   reader process per core, no shared GIL or allocator). On a host
   with C cores the aggregate should approach C × the single-core
   rate; on this 1-core dev rig the harness documents exactly that
   limitation instead of extrapolating silently.
3. The core-budget arithmetic for the north star: cores needed =
   50e6 / measured per-core datagram rate.

Writes PARSE_PERCORE.json at the repo root and prints one JSON line.

Usage: python tools/bench_parse_percore.py [--lines 4000000] [--procs N]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "native", "parse_bench")


def build() -> None:
    subprocess.run(["make", "-C", os.path.join(REPO, "native"),
                    "parse_bench"], check=True, capture_output=True)


def run_one(lines: int) -> dict:
    out = subprocess.run([BENCH, str(lines), "--cell", "0"], check=True,
                         capture_output=True, text=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def run_parallel(lines: int, procs: int) -> dict:
    t0 = time.time()
    children = [subprocess.Popen([BENCH, str(lines), "--cell", "0"],
                                 stdout=subprocess.PIPE, text=True)
                for _ in range(procs)]
    results = []
    for c in children:
        out, _ = c.communicate()
        if c.returncode != 0:
            raise RuntimeError("parse_bench child failed")
        results.append(json.loads(out.strip().splitlines()[-1]))
    wall = time.time() - t0
    # each child timed 3 phases over `lines` lines; aggregate rate uses
    # the children's own datagram-phase rates (per-phase wall), while
    # `wall` sanity-checks that they genuinely ran concurrently
    agg = sum(r["datagram_lines_per_s"] for r in results)
    return {"procs": procs, "aggregate_datagram_lines_per_s": agg,
            "per_child_datagram_lines_per_s": [
                r["datagram_lines_per_s"] for r in results],
            "wall_s": round(wall, 1)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=4_000_000)
    ap.add_argument("--procs", type=int, default=0,
                    help="0 = up to min(4, cores)")
    args = ap.parse_args()

    build()
    cores = len(os.sched_getaffinity(0))
    single = run_one(args.lines)

    procs = args.procs or min(4, cores)
    scaling = [run_parallel(args.lines // 2, n)
               for n in sorted({1, 2, procs}) if n >= 1]

    rate = single["datagram_lines_per_s"]
    out = {
        "host_cores": cores,
        "single_core": single,
        "reuseport_process_scaling": scaling,
        "scaling_note": (
            "1-core dev rig: concurrent processes timeslice one core, so "
            "aggregate ≈ single-core rate by construction — the scaling "
            "column demonstrates the harness, not the ceiling. On an "
            "N-core deployment each SO_REUSEPORT reader process owns a "
            "core; the C++ readers share no state until the (sharded, "
            "mutex-per-shard) directory commit." if cores == 1 else
            "multi-core host: aggregate column is the measured ceiling"),
        "north_star": {
            "target_samples_per_s": 50_000_000,
            "measured_per_core_lines_per_s": rate,
            "cores_needed": round(50e6 / rate, 1),
        },
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rev": subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              cwd=REPO, capture_output=True,
                              text=True).stdout.strip(),
    }
    tmp = os.path.join(REPO, "PARSE_PERCORE.json.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1)
    os.replace(tmp, os.path.join(REPO, "PARSE_PERCORE.json"))
    print(json.dumps({"metric": "parse_lines_per_s_per_core",
                      "value": rate, "unit": "lines/s",
                      "cycles_per_line": single[
                          "datagram_cycles_per_line"],
                      "cores_for_50M": out["north_star"]["cores_needed"]}))


if __name__ == "__main__":
    main()
