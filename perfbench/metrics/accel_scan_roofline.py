"""accel scan / harmonic sum (search/accel.py, accel_pallas.py): share
of the least time the published peaks allow for reading every plane
cell of both passes once (counts.accel_scan; bytes-bound on a v5e),
over the device time of the scan programs in the trace."""

from perfbench import counts, trace_reduce

PROGRAMS = [r"^scan_many"]


def read(ctx):
    t = trace_reduce.program_seconds(ctx["trace"], PROGRAMS)
    if t <= 0:
        return None
    n = ctx["window"]["trials"]
    req = {k: v * n for k, v in ctx["required"]["accel_scan"].items()}
    least, _bound = counts.least_time(req, ctx["peak"])
    return 100.0 * least / t
