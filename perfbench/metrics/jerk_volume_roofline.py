"""jerk volume (search/jerk.py: the banded build and harmonic sum):
share of the least time the published peaks allow for the band's
required work (counts_jerk.jerk_volume: PRESTO's FFT correlation of
every (z, w) row and r-block plus the staged harmonic adds; operations-
bound on a v5e), over the device time of the jerk programs in the
trace."""

from perfbench import counts, trace_reduce

PROGRAMS = [r"^jerk_(prep|build|scan)$"]


def read(ctx):
    req = ctx["required"].get("jerk_volume")
    t = trace_reduce.program_seconds(ctx["trace"], PROGRAMS)
    if not req or t <= 0:
        return None
    n = ctx["window"]["trials"]
    least, _bound = counts.least_time({k: v * n for k, v in req.items()},
                                      ctx["peak"])
    return 100.0 * least / t
