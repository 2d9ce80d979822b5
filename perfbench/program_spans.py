"""The program's own spans in a device trace, and a run of one cell that
records them.

    python3 perfbench/program_spans.py --workload <name> --seed <n>
                                       --seconds <s> [--profile 0|1]

An open span of an enabled ``presto_tpu.obs`` tracer is a
``presto:<span name>`` annotation in the JAX profiler's trace, on the
line of the thread that ran it.  ``reduce`` takes from a trace, clipped
to the window of the benchmark's ``bench:`` chunk or block spans:

- per program span name: the count, the total seconds and the self
  seconds (the span less the part its children on the same thread
  cover);
- idle gaps: every stretch of the window in which a device ran nothing,
  attributed to the innermost ``bench:`` or ``presto:`` span over its
  middle on the dispatching thread (the one that opened the window's
  chunk or block spans).  Spans of worker threads do not compete for a
  gap, whatever their length.

``QUANTITIES`` turns that into the per-trial and per-block numbers of
PERF.md section 3 (the per-layer metrics these spans are for).

The run is run.py's traced branch with the program's spans on: the
process default obs handle is enabled (the ingest spans resolve it) and
handed to the cell (the search spans take it).  ``--profile 1`` records
the window under the profiler and prints the reduction; ``--profile 0``
runs the window with the spans on and no profiler, which with run.py
``--trace 0`` gives the cost of the spans.  No correctness check.  One
JSON line on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, trace_reduce  # noqa: E402

PRESTO = "presto:"
BENCH = "bench:"
WINDOW = ("chunk", "block")


def host_spans(pd):
    """[(thread, name, start_ns, end_ns)] of every ``bench:`` and
    ``presto:`` host event; a thread is (plane, line index), the name
    keeps its prefix."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((BENCH, PRESTO)):
                    out.append(((plane.name, i), e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def covered(intervals) -> float:
    return sum(e - s for s, e in trace_reduce.union(intervals))


def self_and_total(spans, lo, hi):
    """{program span name: {count, total_s, self_s}} clipped to [lo,
    hi); a span's children are the spans of its thread inside it."""
    by_thread = defaultdict(list)
    for th, name, s, e in spans:
        by_thread[th].append((s, e, name))
    acc = defaultdict(lambda: [0, 0.0, 0.0])
    for evs in by_thread.values():
        evs.sort(key=lambda x: (x[0], -x[1]))
        for i, (s, e, name) in enumerate(evs):
            if not name.startswith(PRESTO):
                continue
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            kids = []
            for s2, e2, _n in evs[i + 1:]:
                if s2 >= e:
                    break
                if e2 <= e and (s2, -e2) != (s, -e):
                    kids.append((max(s2, cs), min(e2, ce)))
            a = acc[name[len(PRESTO):]]
            a[0] += 1
            a[1] += (ce - cs) / 1e9
            a[2] += (ce - cs - covered([k for k in kids if k[1] > k[0]])) \
                / 1e9
    return {k: {"count": v[0], "total_s": v[1], "self_s": v[2]}
            for k, v in sorted(acc.items())}


def label(name: str) -> str:
    for pre in (BENCH, PRESTO):
        if name.startswith(pre):
            return name[len(pre):]
    return name


def reduce(pd, window_spans=WINDOW) -> dict:
    """The window, the program spans and, where the trace has a device
    plane, the idle gaps by the dispatching thread's spans."""
    spans = host_spans(pd)
    win = [(th, s, e) for th, n, s, e in spans
           if n.startswith(BENCH) and label(n) in window_spans]
    if not win:
        raise ValueError("the trace holds no bench: %s span"
                         % "/".join(window_spans))
    lo, hi = min(s for _t, s, _e in win), max(e for _t, _s, e in win)
    counts = defaultdict(int)
    for th, _s, _e in win:
        counts[th] += 1
    dispatch = max(counts, key=counts.get)
    out = {"window_s": (hi - lo) / 1e9,
           "program_spans": self_and_total(spans, lo, hi)}
    dev, _bench = trace_reduce.events(pd)
    if not dev:
        return out
    mine = [(n, s, e) for th, n, s, e in spans if th == dispatch]
    gaps, busy = defaultdict(float), []
    for d in dev.values():
        src = d["ops"] or d["modules"]
        iv = trace_reduce.clip(trace_reduce.union(
            [(s, e) for _n, s, e in src]), lo, hi)
        busy.append(sum(e - s for s, e in iv))
        prev = lo
        for s, e in iv + [[hi, hi]]:
            if s > prev:
                gaps[label(trace_reduce.attribute(mine, prev, s))] += \
                    (s - prev) / 1e9
            prev = max(prev, e)
    ndev = len(busy)
    out["busy_s"] = sum(busy) / ndev / 1e9
    out["idle_gaps"] = [[k, v / ndev] for k, v in
                        sorted(gaps.items(), key=lambda kv: -kv[1])]
    return out


def _total(red, *names) -> float:
    ps = red["program_spans"]
    return sum(ps[n]["total_s"] for n in names if n in ps)


def _per_count(red, name):
    ps = red["program_spans"].get(name)
    return 1e3 * ps["total_s"] / ps["count"] if ps else None


#: metric -> (cell path, reader of (reduction, window)); ms
QUANTITIES = {
    "seam_trip_ms_per_trial": ("search", lambda red, win: 1e3 * _total(
        red, "seam:download", "seam:zap", "seam:upload") / win["trials"]),
    "accel_sync_ms_per_trial": ("search", lambda red, win: 1e3 * _total(
        red, "accel:collect") / win["trials"]),
    "refine_ms_per_trial": ("search", lambda red, win: 1e3 * _total(
        red, "accel:refine") / win["trials"]),
    "ingest_wait_ms_per_block": ("dedisp", lambda red, win: 1e3 * _total(
        red, "ingest:wait") / win["steps"]),
    "prep_ms_per_block": ("dedisp", lambda red, win: _per_count(
        red, "ingest:prep")),
}


def quantities(path: str, red: dict, win: dict) -> dict:
    return {k: f(red, win) for k, (p, f) in QUANTITIES.items() if p == path}


def parse(argv):
    p = argparse.ArgumentParser(prog="perfbench/program_spans.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--profile", type=int, default=1, choices=(0, 1))
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


def main(argv=None) -> int:
    a = parse(argv)
    if not a.rehearse:
        harness.use_checkout_cache()
    os.environ.setdefault("PRESTO_TPU_COST", "0")
    c = harness.load_cell(a.workload)
    cell, config, traffic = c["cell"], c["config"], c["traffic"]
    import jax
    try:
        device = harness.device_info(cell["chips"], a.rehearse)
    except (harness.NoDevice, RuntimeError) as e:
        harness.eprint("perfbench: %s" % e)
        return 3
    from presto_tpu.obs import ObsConfig, configure
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spans = harness.Spans(traced=bool(a.profile))
    drv = harness.path_driver(traffic["path"]).Cell(
        config, traffic, spans, rehearse=a.rehearse)
    drv.obs = configure(ObsConfig(enabled=True))
    red = None
    try:
        drv.setup(a.seed)
        if a.profile:
            tdir = harness.trace_dir()
            jax.profiler.start_trace(tdir)
        win = drv.window(a.seconds)
        if a.profile:
            jax.profiler.stop_trace()
            red = reduce(trace_reduce.load(trace_reduce.find_xplane(tdir)))
            shutil.rmtree(tdir, ignore_errors=True)
    finally:
        drv.close()
    out = {"workload": a.workload, "seed": a.seed, "profile": a.profile,
           "device": device, "window": win}
    if red is not None:
        out["quantities"] = quantities(traffic["path"], red, win)
        out["trace"] = red
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
