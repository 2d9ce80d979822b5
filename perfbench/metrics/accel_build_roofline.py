"""accel plane build (search/accel.py, build_pallas.py): share of the
least time the published peaks allow for the required work of the
F-Fdot planes of both passes (counts.accel_build; bytes-bound on a
v5e), over the device time of the build programs in the trace."""

import re

from perfbench import counts, trace_reduce

# the jitted build body: `build_body` on the XLA engines; on the v5e an
# unnamed lambda around the Pallas builder, known by its kernel, the op
# `build` (no stable program name yet; PERF.md)
NAMED = re.compile(r"build_body")
KERNEL = r"^%?build(\.\d+)?$"


def read(ctx):
    red = ctx["trace"]
    progs = ({p for p in red["programs"] if NAMED.search(p)}
             | trace_reduce.programs_with_op(red, KERNEL))
    t = sum(red["programs"].get(p, 0.0) for p in progs)
    if t <= 0:
        return None
    n = ctx["window"]["trials"]
    req = {k: v * n for k, v in ctx["required"]["accel_build"].items()}
    least, _bound = counts.least_time(req, ctx["peak"])
    return 100.0 * least / t
