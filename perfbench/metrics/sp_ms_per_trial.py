"""single-pulse (search/singlepulse.py through the seam): device time
of the single-pulse programs per DM trial, in ms."""

from perfbench import trace_reduce

PROGRAMS = [r"_resident_pipeline", r"_detrend_blocks"]


def read(ctx):
    t = trace_reduce.program_seconds(ctx["trace"], PROGRAMS)
    n = ctx["window"]["trials"]
    return 1e3 * t / n if t > 0 and n else None
