"""candidate collection (_seam_fft_search's download, zap, re-upload,
refine): host-to-device megabytes per DM trial, from the program's
jax_device_put_bytes_total counter (the zap round trip re-uploads the
spectra)."""


def read(ctx):
    b = ctx["counters"].get("jax_device_put_bytes_total")
    n = ctx["window"]["trials"]
    return b / 1e6 / n if b and n else None
