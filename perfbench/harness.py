"""The data-driven part of the benchmark: find a cell's files by name,
check the device, time set-up and the window, record host spans,
reduce a trace, run the per-layer readers and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own:

- ``configs/<config>.json``   sizes of one deployment
- ``traffic/<traffic>.json``  one mix; its ``path`` names the driver in
  ``paths/<path>.py`` that turns it into calls of the program
- ``metrics/<metric>.py``     one per-layer reader, ``read(ctx)``
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time
from contextlib import contextmanager

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoDevice(RuntimeError):
    """The measurement path found no accelerator, or too few chips."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry, configuration and traffic, found by name."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit("unknown workload %r (have: %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return dict(spec=spec, cell=cell, config=config, traffic=traffic)


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    """The metric entries of one kind that this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def path_driver(name: str):
    return importlib.import_module("perfbench.paths." + name)


def reader(metric: str):
    """The per-layer reader ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_checkout_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (set before JAX is imported; the program takes it from
    JAX_COMPILATION_CACHE_DIR)."""
    d = os.path.join(HERE, ".cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    return d


def device_info(chips: int, rehearse: bool) -> dict:
    """Platform, kind and count as JAX reports them; a measurement run
    without a TPU, or with fewer chips than the cell asks for, fails."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse:
        if info["platform"] != "tpu":
            raise NoDevice("no TPU: JAX found %s" % info)
        if info["count"] < chips:
            raise NoDevice("the cell needs %d chips, JAX found %d"
                           % (chips, info["count"]))
    return info


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileMeter:
    """Compile seconds and persistent-cache hits from JAX's own
    monitoring events (backend_compile_duration wraps a compile or a
    cache load).  Copied from chip_smoke.py."""

    def __init__(self):
        import jax.monitoring as mon
        self.secs = 0.0
        self.n = 0
        self.hits = 0
        self.misses = 0
        self.names = []            # function of each compile or load
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._event)

    def _dur(self, event, secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1
            self.names.append(fun_name)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.n,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Spans:
    """The benchmark's own host spans around the calls it makes into
    each layer; in a traced run they also go into the profiler's trace
    so idle gaps can be attributed to them."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.records = []          # (name, t0_ns, t1_ns)

    @contextmanager
    def __call__(self, name: str):
        ann = None
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def total_s(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name) / 1e9

    def count(self, name: str) -> int:
        return sum(1 for n, _a, _b in self.records if n == name)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return json.dumps(out)


def trace_dir() -> str:
    import tempfile
    return tempfile.mkdtemp(prefix="perfbench_trace_")


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
