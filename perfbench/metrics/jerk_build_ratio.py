"""jerk volume (search/jerk.py): plane cells the program built in the
window (its accel_jerk_cells_built_total counter, fundamental and
subharmonic) over the band's fundamental cells times the window's
trials.  The floor at numharm 8 is 1 + (1/2 + 1/4 + 3/4 + 1/8 + 3/8 +
5/8 + 7/8) = 4.5; block padding and rebuilds raise it."""


def read(ctx):
    built = ctx["window"].get("jerk_cells_built")
    cells = ctx["required"].get("band_cells")
    n = ctx["window"]["trials"]
    return built / (cells * n) if built and cells and n else None
