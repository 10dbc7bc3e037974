#!/usr/bin/env python3
"""The sender: a child process that imports neither JAX nor the program.

It builds the cell's ring of lines from the seed, says ``ready`` on its
standard output, reads ``{"port": ..., "s0": ...}`` from its standard
input, and then repeats the ring once per interval on its own clock:
cycle k starts at s0 + k x interval and chunk j of it is due at the
offset the traffic's arrival pattern gives. It never looks at the
server: a chunk is written when it is due or as soon after as
``sendall`` takes it (TCP blocks the writer when the reader falls
behind). For every chunk it keeps the time it was due and the time
``sendall`` returned. During warm-up the parent may hold it at a cycle
boundary and give it a new s0; ``{"stop": true}`` (or the end of input)
stops it after the chunk in hand; it then writes its log and says
``stopped``.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import stream  # noqa: E402


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--log", required=True)
    ap.add_argument("--fault", default="",
                    help="tests only: 'double' writes one counter line twice")
    args = ap.parse_args()
    config = stream.load_json("configs", args.config)
    traffic = stream.load_json("traffic", args.traffic)
    interval = float(config["interval_s"])

    t0 = time.time()
    ring = stream.build_ring(config, args.seed)
    lines = stream.format_lines(ring, config["lines"].get("tag_from", 0))
    chunks, counts = stream.chunk_lines(lines, int(traffic["chunk_bytes"]))
    offsets = stream.due_offsets(traffic, len(chunks), interval).tolist()
    n_lines, n_bytes = len(lines), sum(map(len, chunks))
    del ring, lines
    say(event="ready", lines_per_cycle=n_lines, bytes_per_cycle=n_bytes,
        chunks_per_cycle=len(chunks), build_s=time.time() - t0,
        longest_line=max(len(c) for c in chunks[0].split(b"\n")))

    # commands from the parent, one JSON object a line: {"s0": t} starts
    # (or, after a hold, resumes) the schedule with a cycle due at t;
    # {"hold": true} asks for a halt at the next cycle boundary (warm-up
    # only: a compile that outlasts the interval would otherwise pile up
    # a backlog of shapes no steady state has); anything else stops.
    inbox: queue.Queue = queue.Queue()
    stop, hold = threading.Event(), threading.Event()

    def read_commands() -> None:
        for raw in sys.stdin:
            try:
                cmd = json.loads(raw)
            except ValueError:
                break
            if not isinstance(cmd, dict):
                break
            if cmd.get("hold"):
                hold.set()
            elif "s0" in cmd:
                inbox.put(cmd)
            else:
                break
        stop.set()
        inbox.put(None)

    go = json.loads(sys.stdin.readline())
    threading.Thread(target=read_commands, daemon=True).start()
    due_log, done_log, cycle_log, starts = [], [], [], []
    written = 0
    with socket.create_connection(("127.0.0.1", int(go["port"]))) as sock:
        base, k = float(go["s0"]), 0
        while not stop.is_set():
            start = base + k * interval
            starts.append(start)
            for j, chunk in enumerate(chunks):
                due = start + offsets[j]
                wait = due - time.time()
                if (wait > 0 and stop.wait(wait)) or stop.is_set():
                    break
                sock.sendall(chunk)
                if args.fault == "double" and len(starts) == 2 and j == 0:
                    # a counter line: a doubled gauge write or set member
                    # changes nothing that any sink could see
                    sock.sendall(next(ln for ln in chunk.split(b"\n")
                                      if ln.endswith(b"|c")) + b"\n")
                done_log.append(time.time())
                due_log.append(due)
                cycle_log.append(len(starts) - 1)
                written += counts[j]
            k += 1
            if hold.is_set() and not stop.is_set():
                say(event="held", cycles=len(starts), t=time.time(),
                    lines_written=written)
                cmd = inbox.get()
                if cmd is None:
                    break
                hold.clear()
                base, k = float(cmd["s0"]), 0
        # every byte handed to the kernel before the connection closes
        sock.shutdown(socket.SHUT_WR)
    with open(args.log, "w") as f:
        json.dump({"cycle_start": starts, "interval_s": interval,
                   "lines_per_cycle": n_lines, "chunks_per_cycle": len(chunks),
                   "chunk_lines": counts, "cycle": cycle_log,
                   "due": due_log, "done": done_log}, f)
    say(event="stopped", lines_written=written, log=args.log,
        t_stopped=time.time())
    return 0


if __name__ == "__main__":
    sys.exit(main())
