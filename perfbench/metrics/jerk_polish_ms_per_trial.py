"""jerk polish (search/polish.optimize_jerk_cands through
refine_and_write): host ms per DM trial in the benchmark's own
``jerk_polish`` span around each call, in the window."""


def read(ctx):
    s = ctx["window"].get("jerk_polish_s")
    n = ctx["window"]["trials"]
    return 1e3 * s / n if s is not None and n else None
