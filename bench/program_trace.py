#!/usr/bin/env python3
"""The program's own spans and device programs in a profiler trace, inside the
benchmark's ``bench.window``: what ``trace_reduce.py`` does not read.

    python3 bench/program_trace.py <trace dir | .xplane.pb | .xplane.pb.gz>

reads a trace kept by ``run.py --trace 1 --trace-dir <dir>`` and prints one JSON
object:

- ``spans``: per ``ptpu.*`` host span (the loader's stages and their phases, the
  pool's items), its seconds inside the window summed over threads (``s``), the
  spans that reach into the window (``count``) and their summed ``bytes`` arg;
- ``idle_by_span``: per ``ptpu.*`` span of the loader's transfer thread (the host
  thread that holds its ``decode.dispatch`` or ``h2d.transfer`` spans), the device
  seconds idle under it, the mean over chips;
- ``programs``: device seconds per ``ptpu_*`` program, the mean over chips.

A trace of a program without such spans or programs gives empty objects.
"""
import bisect
import json
import re
import sys

import trace_reduce as tr

PROGRAM_SPAN = "ptpu."
PROGRAM = re.compile(r"ptpu_\w+")
#: a host line (thread) holding one of these is the loader's transfer thread
TRANSFER_STAGES = ("ptpu.decode.dispatch", "ptpu.h2d.transfer")


def program_spans(profile):
    """``[(line, name, start_ns, end_ns, args)]`` of the ``ptpu.*`` host spans;
    ``line`` tells the host threads apart."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_SPAN):
                        out.append(((plane.name, i), ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
    return out


def _overlap(intervals, starts, s, e):
    """Length of ``[s, e)`` inside the sorted disjoint ``intervals``."""
    total = 0
    k = max(bisect.bisect_right(starts, s) - 1, 0)
    while k < len(intervals) and intervals[k][0] < e:
        total += max(0, min(e, intervals[k][1]) - max(s, intervals[k][0]))
        k += 1
    return total


def reduce_profile(profile):
    windows = [(s, e) for n, s, e in tr.host_spans(profile) if n == tr.WINDOW]
    if not windows:
        raise ValueError("the trace holds no %s annotation" % tr.WINDOW)
    lo, hi = windows[0]
    idle, programs = [], {}  # per chip: idle intervals; program -> device seconds
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted(((tr._short(ev.name), ev.start_ns, ev.end_ns)
                          for ev in lines[tr.MODULES_LINE].events), key=lambda m: m[1]) \
            if tr.MODULES_LINE in lines else []
        starts = [s for _, s, _ in modules]
        ops, prog = [], {}
        for ev in lines[tr.OPS_LINE].events if tr.OPS_LINE in lines else ():
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            ops.append((s, e))
            named = PROGRAM.search(tr._module_of(modules, starts,
                                                 (ev.start_ns + ev.end_ns) / 2))
            if named:
                prog.setdefault(named.group(0), []).append((s, e))
        edges = [lo] + [t for iv in tr.union(ops) for t in iv] + [hi]
        idle.append([(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]])
        for name, ivs in prog.items():
            programs[name] = programs.get(name, 0.0) + tr._length(tr.union(ivs)) * 1e-9
    if not idle:
        raise ValueError("the trace holds no TPU device plane")
    pspans = program_spans(profile)
    transfer = {line for line, name, _s, _e, _a in pspans if name in TRANSFER_STAGES}
    idle_starts = [[iv[0] for iv in ivs] for ivs in idle]
    totals, idle_by = {}, {}
    for line, name, s, e, args in pspans:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        t = totals.setdefault(name, {"s": 0.0, "count": 0, "bytes": 0})
        t["s"] += (e - s) * 1e-9
        t["count"] += 1
        t["bytes"] += int(args.get("bytes", 0))
        if line in transfer:
            under = sum(_overlap(ivs, st, s, e) for ivs, st in zip(idle, idle_starts))
            idle_by[name] = idle_by.get(name, 0.0) + under * 1e-9 / len(idle)
    return {"window_s": (hi - lo) * 1e-9, "spans": dict(sorted(totals.items())),
            "idle_by_span": dict(sorted(idle_by.items())),
            "programs": {k: v / len(idle) for k, v in sorted(programs.items())}}


def load(path):
    import gzip
    import os

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.find_trace(path)
    if path.endswith(".gz"):
        with open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    return ProfileData.from_file(path)


if __name__ == "__main__":
    print(json.dumps(reduce_profile(load(sys.argv[1])), indent=1))
