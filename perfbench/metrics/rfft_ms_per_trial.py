"""FFT seam (pipeline/fusion.fused_rfft_batch): device time of the
batched packed rFFT program per DM trial, in ms."""

from perfbench import trace_reduce

PROGRAMS = [r"realfft_packed_pairs"]


def read(ctx):
    t = trace_reduce.program_seconds(ctx["trace"], PROGRAMS)
    n = ctx["window"]["trials"]
    return 1e3 * t / n if t > 0 and n else None
