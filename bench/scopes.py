#!/usr/bin/env python3
"""Device seconds per named scope inside a program, by hand:

    python3 bench/scopes.py bench/out/<cell>.<seed>.trace --config local-timers \
        [--program jit__histo_fold_staged] [--spill 4096,32768]

The program's jitted functions name their stages with jax.named_scope
(``tdigest.compress.sort``, ``fold_staged.merge``, ...: bench/TRACING.md
lists them). On a TPU plane the profiler gives an op event the HLO
instruction as its name and three statistics of time, none of the scope
(bench/TRACING.md section 3), so the path comes from the compiled
program's own text: ``scope_map`` compiles the cell's programs at the
cell's shapes (on the chip if this process has one, else for a described
v5e) and reads each instruction's ``op_name``. The spill fold is
specialised per (active rows, samples) batch: ``--spill`` names the one
to map, and ``coverage`` says what share of a program's op seconds the
map knew.

Prints one JSON object: the statistics met on the op line (``stats``),
device seconds per program (``programs``), per scope inside each
(``scopes``: the innermost scope the program's code named, ``(unnamed)``
where the op has none or the map does not know it), ``coverage``, and
the while loops with their scope (``whiles``). A while loop is listed
beside the ops of its body, so inside one program the scopes can add up
to more than the program."""

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402

OP_LINE = "XLA Ops"
#: statistics that hold the op's framework name (the named_scope path)
#: where a plane has them (XLA:CPU's thunk events do not either)
SCOPE_STATS = ("tf_op", "long_name")
INSTR = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+) = .*?op_name="([^"]*)"', re.M)
#: the stages the program names: <module>.<stage>[.<part>]
NAMED = re.compile(
    r"(?:^|/)((?:tdigest|segments|fold_staged|ingest_step|flush_extract|"
    r"pack_extract|microfold|hll)(?:\.[a-z_]+)*)(?=/|$)")


def scope_of(path: str) -> str:
    """The innermost scope the program named on an op's path
    ``jit(f)/jit(main)/fold_staged.merge/concatenate``."""
    found = NAMED.findall(path or "")
    return found[-1] if found else "(unnamed)"


def scope_map(config: dict, spill=(4096, 32768)) -> dict:
    """{"jit_<program>": {"%instruction": op_name}} for the programs of
    the flush at the shapes the cell runs them with."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from veneur_tpu.core import worker as wk
    from veneur_tpu.core.config import Config
    from veneur_tpu.ops import microfold as mf

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        from jax.experimental import topologies

        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        jax.config.update("jax_enable_compilation_cache", False)
    sh = SingleDeviceSharding(dev)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def fields(rows):
        return [arr((rows, 128))] * 2 + [arr((rows,))] * 12

    timers = int(config["series"]["timer"])
    rows = max(1024, 1 << (timers - 1).bit_length())
    pool = 1 << timers.bit_length() if config.get("preset_histo_rows") \
        else rows
    depth = int(config["server"].get("tpu_stage_depth",
                                     Config().tpu_stage_depth))
    pcts = len(config["server"].get("percentiles", [0.5]))
    k, n = spill
    i32 = jnp.int32
    lowered = {
        "jit__histo_fold_staged": wk._histo_fold_staged.lower(
            *fields(rows), arr((rows, depth)), arr((rows, depth)),
            compression=100.0),
        "jit__histo_ingest_step": wk._histo_ingest_step.lower(
            *fields(pool), arr((k,), i32), arr((n,), i32), arr((n,)),
            arr((n,)), compression=100.0),
        "jit__histo_flush_extract": wk._histo_flush_extract.lower(
            *fields(rows), arr((pcts,))),
        "jit__pack_extract_columns": wk._pack_extract_columns.lower(
            arr((rows, pcts)), *[arr((rows,))] * 10),
        "jit__scatter_chunk": mf._scatter_chunk.lower(
            arr((pool, depth)), arr((pool, depth)),
            arr((mf.MICRO_CHUNK,), i32), arr((mf.MICRO_CHUNK,), i32),
            arr((mf.MICRO_CHUNK,)), arr((mf.MICRO_CHUNK,))),
    }
    return {name: dict(INSTR.findall(low.compile().as_text()))
            for name, low in lowered.items()}


def load(trace_dir: str):
    """[(plane, line, name, start_s, dur_s, {stat: value})] of the
    device planes' lines."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                            {k: v for k, v in ev.stats}))
    return out


def reduce(events: list, program: str = "", smap: dict = None) -> dict:
    mods = sorted((e[3], e[3] + e[4], e[2].split("(")[0]) for e in events
                  if e[1] == "XLA Modules")

    def module_at(t: float) -> str:
        for a, b, name in mods:
            if a <= t < b:
                return name
        return "(no module)"

    smap = smap or {}
    stats: dict = {}
    programs: dict = {}
    scopes: dict = {}
    known: dict = {}
    whiles: dict = {}
    for a, b, name in mods:
        programs[name] = programs.get(name, 0.0) + b - a
    for plane, line, name, start, dur, st in events:
        if line != OP_LINE:
            continue
        for k in st:
            stats[k] = stats.get(k, 0) + 1
        mod = str(st.get("hlo_module") or module_at(start))
        if program and mod != program:
            continue
        instr = name.split(" = ")[0]
        path = next((str(st[k]) for k in SCOPE_STATS if st.get(k)), None)
        if path is None:
            path = smap.get(mod, {}).get(instr)
        seen = known.setdefault(mod, [0.0, 0.0])
        seen[0] += dur if path is not None else 0.0
        seen[1] += dur
        sc = scopes.setdefault(mod, {})
        key = scope_of(path)
        sc[key] = sc.get(key, 0.0) + dur
        if " while(" in name:
            w = whiles.setdefault(f"{mod} {instr}", {
                "shape": name.split(" = ")[1][:60], "scope": key,
                "seconds": 0.0})
            w["seconds"] += dur
    return {"stats": stats, "programs": programs,
            "coverage": {m: k / n for m, (k, n) in known.items() if n},
            "scopes": {m: dict(sorted(v.items(), key=lambda kv: -kv[1]))
                       for m, v in scopes.items()},
            "whiles": whiles}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--program", default="",
                    help="only this program, e.g. jit__histo_fold_staged")
    ap.add_argument("--config", default="",
                    help="bench/configs/<name>.json: compile its programs "
                         "and map instructions to scopes")
    ap.add_argument("--spill", default="4096,32768",
                    help="active rows,samples of the spill batch to map")
    args = ap.parse_args(argv)
    smap = None
    if args.config:
        from bench import stream

        smap = scope_map(stream.load_json("configs", args.config),
                         tuple(int(x) for x in args.spill.split(",")))
    print(json.dumps(reduce(load(args.trace_dir), args.program, smap),
                     indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
