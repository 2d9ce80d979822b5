"""device: share of the traced search window in which the chip ran no
operation, in %."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
