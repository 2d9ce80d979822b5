"""dedispersion (ops/dedispersion.make_block_step): share of the least
time the published peaks allow for one block step's required reads and
writes (counts.dedisp_step; bytes-bound on a v5e), over the device time
of the block-step program in the trace."""

from perfbench import counts, trace_reduce

PROGRAMS = [r"^step$"]


def read(ctx):
    t = trace_reduce.program_seconds(ctx["trace"], PROGRAMS)
    n = ctx["window"]["steps"]
    if t <= 0 or not n:
        return None
    req = {k: v * n for k, v in ctx["required"]["dedisp"].items()}
    least, _bound = counts.least_time(req, ctx["peak"])
    return 100.0 * least / t
