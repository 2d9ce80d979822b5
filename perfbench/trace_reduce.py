"""Reduce a JAX profiler trace (.xplane.pb) to the benchmark's numbers.

- busy: the union of the intervals in which an operation ran on each
  device (the "XLA Ops" lines of the ``/device:TPU:<n>`` planes),
  averaged over the devices that ran anything;
- window: the extent of the benchmark's own ``bench:`` host annotations
  (the measured window's chunks or blocks), on the trace's clock;
- programs: device seconds per XLA module (a jitted program, named as
  JAX names it, ``jit_<function>`` without the suffix);
- idle gaps: every stretch of the window in which a device ran nothing,
  attributed to the innermost ``bench:`` span that covers its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

BENCH = "bench:"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """'jit_build_body(123)' -> 'build_body'."""
    name = _SUFFIX.sub("", name.strip())
    return name[4:] if name.startswith("jit_") else name


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(tdir: str) -> str:
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % tdir)
    return max(paths, key=os.path.getmtime)


def events(pd):
    """(device lines {device: {"ops": [...], "modules": [...]}},
    host spans [(name, start_ns, end_ns)]) from a ProfileData."""
    dev = {}
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = ("ops" if line.name == "XLA Ops" else
                       "modules" if line.name == "XLA Modules" else None)
                if key is None:
                    continue
                for e in line.events:
                    d[key].append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns))
            if d["ops"] or d["modules"]:
                dev[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(BENCH):
                        host.append((e.name[len(BENCH):], e.start_ns,
                                     e.start_ns + e.duration_ns))
    return dev, host


def reduce(pd, window_spans=("chunk", "block")) -> dict:
    dev, host = events(pd)
    if not dev:
        raise ValueError("the trace holds no device operation")
    win = [(s, e) for n, s, e in host if n in window_spans]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        every = [t for d in dev.values() for k in d.values()
                 for _n, s, e in k for t in (s, e)]
        lo, hi = min(every), max(every)
    busy, programs, ops, gaps = [], defaultdict(float), defaultdict(float), \
        defaultdict(float)
    for d in dev.values():
        src = d["ops"] or d["modules"]
        iv = clip(union([(s, e) for _n, s, e in src]), lo, hi)
        busy.append(sum(e - s for s, e in iv))
        for name, s, e in d["modules"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                programs[module_name(name)] += (e - s) / 1e9
        mods = sorted((s, e, module_name(n)) for n, s, e in d["modules"])
        starts = [m[0] for m in mods]
        for name, s, e in d["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                ops[op_label(name, s, mods, starts)] += (e - s) / 1e9
        prev = lo
        for s, e in iv + [[hi, hi]]:
            if s > prev:
                gaps[attribute(host, prev, s)] += (s - prev) / 1e9
            prev = max(prev, e)
    ndev = len(busy)
    top = lambda acc: [[k, v] for k, v in sorted(acc.items(),
                                                 key=lambda kv: -kv[1])]
    return {"busy_s": sum(busy) / ndev / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": ndev, "programs": dict(programs),
            "device_ops": top(ops),
            "idle_gaps": [[k, v / ndev] for k, v in top(gaps)]}


def op_label(name: str, start, mods, starts) -> str:
    """'<program>/<op>': the HLO instruction's name without its text,
    under the module whose interval holds the op's start."""
    op = name.split(" = ")[0].strip()
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and mods[i][0] <= start < mods[i][1]:
        return "%s/%s" % (mods[i][2], op)
    return op


def attribute(host, s, e) -> str:
    """The innermost benchmark span covering the middle of [s, e)."""
    mid = (s + e) / 2
    best = None
    for name, hs, he in host:
        if hs <= mid < he and (best is None or he - hs < best[1]):
            best = (name, he - hs)
    return best[0] if best else "outside spans"


def reduce_dir(tdir: str) -> dict:
    return reduce(load(find_xplane(tdir)))


def program_seconds(red: dict, patterns) -> float:
    """Device seconds of the programs whose names match any pattern."""
    rx = [re.compile(p) for p in patterns]
    return sum(v for k, v in red["programs"].items()
               if any(r.search(k) for r in rx))


def programs_with_op(red: dict, op_pattern: str) -> set:
    """Names of the programs that ran an operation matching the pattern
    (an op label is '<program>/<op>')."""
    rx = re.compile(op_pattern)
    return {lab.split("/", 1)[0] for lab, _s in red["device_ops"]
            if "/" in lab and rx.search(lab.split("/", 1)[1])}
