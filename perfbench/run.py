"""Run one benchmark cell once and print the contract's result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

Set-up (JAX start, compile or cache load, input synthesis, warm-up of
every shape the window uses) is timed from process start to the first
timed chunk or block.  The window then runs for --seconds and ends on
a chunk or block boundary.  After the window the device peak is read,
the program's state is dropped, and the timed path's outputs are
compared with the plain reference (perfbench/reference).

Two modes the driver never uses:
  --rehearse      tiny sizes on the CPU (control flow and the result
                  line; no metric is printed)
  --readings K    K seeds in one process, each a window of --seconds,
                  printing the compared numbers of the program and of
                  the control (the reference in bfloat16): the readings
                  the limits are set from
"""

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(prog="perfbench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--readings", type=int, default=0)
    return p.parse_args(argv)


def per_layer(metrics, red, win, spans, setup_compile, drv, counters):
    """Run each per-layer reader on the traced run's context."""
    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    ctx = {"trace": red, "window": win, "spans": spans,
           "compile": setup_compile, "required": drv.required(),
           "peak": peaks[red["device_kind"]], "counters": counters}
    out = {}
    for m in metrics:
        v = harness.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def drv_counters(drv, since=None) -> dict:
    """The program's byte counters, less their values at ``since``."""
    obs = getattr(drv, "obs", None)
    if obs is None:
        return {}
    snap = obs.metrics.snapshot()
    out = {}
    for name in ("jax_device_put_bytes_total", "jax_device_get_bytes_total"):
        fam = snap.get(name) or {}
        out[name] = (float(sum(s["value"] for s in fam.get("series", [])))
                     - (since or {}).get(name, 0.0))
    return out


def main(argv=None) -> int:
    a = parse(argv)
    if not a.rehearse:
        harness.use_checkout_cache()
    os.environ.setdefault("PRESTO_TPU_COST", "0")
    c = harness.load_cell(a.workload)
    spec, cell, config, traffic = c["spec"], c["cell"], c["config"], \
        c["traffic"]
    import jax
    meter = harness.CompileMeter()
    try:
        device = harness.device_info(cell["chips"], a.rehearse)
    except (harness.NoDevice, RuntimeError) as e:
        harness.eprint("perfbench: %s" % e)
        return 3
    import presto_tpu  # noqa: F401  (sets its cache options)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spans = harness.Spans(traced=bool(a.trace))
    mod = harness.path_driver(traffic["path"])
    drv = mod.Cell(config, traffic, spans, rehearse=a.rehearse)
    if a.trace:
        from presto_tpu.obs import ObsConfig, Observability
        drv.obs = Observability(ObsConfig(enabled=True))
    if a.readings:
        return readings(a, lambda: mod.Cell(config, traffic, spans,
                                            rehearse=a.rehearse))
    try:
        drv.setup(a.seed)
        setup_s = time.perf_counter() - T_START
        setup_compile = meter.snapshot()
        tdir = None
        if a.trace:
            tdir = harness.trace_dir()
            jax.profiler.start_trace(tdir)
        at_start = drv_counters(drv)
        win = drv.window(a.seconds)
        counters = drv_counters(drv, since=at_start)
        window_compile = meter.snapshot()
        red = None
        if a.trace:
            jax.profiler.stop_trace()
            from perfbench import trace_reduce
            red = trace_reduce.reduce_dir(tdir)
            red["device_kind"] = device["kind"]
            import shutil
            shutil.rmtree(tdir, ignore_errors=True)
        device["memory_peak_bytes"] = harness.memory_peak_bytes()
        drv.release()
        t_check = time.perf_counter()
        checks, failed, _ctl = drv.check()
        t_check = time.perf_counter() - t_check
    finally:
        drv.close()
    harness.eprint("set-up compile %s; window compile %s %s; check %.1f s"
                   % (json.dumps(setup_compile), json.dumps(window_compile),
                      meter.names[setup_compile["compiles"]:
                                  window_compile["compiles"]], t_check))
    correct = failed == 0 and all(v <= lim for _n, v, lim in checks)
    metrics, breakdown = {}, None
    if a.rehearse:
        pass          # a CPU run never prints a device metric
    elif a.trace:
        metrics = per_layer(harness.metrics_for(spec, cell["name"],
                                                "per_layer"),
                            red, win, spans, setup_compile, drv,
                            counters)
        harness.eprint("programs: %s" % json.dumps(sorted(
            red["programs"].items(), key=lambda kv: -kv[1])[:25]))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
    else:
        for m in harness.metrics_for(spec, cell["name"], "end_to_end"):
            v = setup_s if m["name"] == "setup_s" else win[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    harness.eprint("window: %s" % json.dumps(
        {k: v for k, v in win.items()}))
    for n, v, lim in checks:
        harness.eprint("check %s %r limit %r" % (n, v, lim))
    print(harness.result_line(correct, win["attempted"], failed, metrics,
                              device, checks, breakdown), flush=True)
    return 0


def readings(a, make) -> int:
    """The program's and the control's compared numbers over a.readings
    seeds in one process (the limits are set from these)."""
    for k in range(a.readings):
        seed = a.seed + k
        drv = make()
        try:
            drv.setup(seed)
            drv.window(a.seconds)
            drv.release()
            checks, failed, ctl = drv.check(control=True)
        finally:
            drv.close()
        print(json.dumps({"seed": seed, "failed": failed,
                          "program": {n: v for n, v, _l in checks},
                          "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
