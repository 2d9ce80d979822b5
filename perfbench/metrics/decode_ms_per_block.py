"""ingest (the reader's 8-bit decode, apps/common.BlockPrep, the
transpose; on fusion.DoubleBufferedIngest's worker thread): host ms
per block, from the benchmark's own span around each decode+prep."""


def read(ctx):
    sp = ctx["spans"]
    n = sp.count("decode")
    return 1e3 * sp.total_s("decode") / n if n else None
