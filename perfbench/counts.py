"""Required operations and bytes of each measured layer, from the
cell's shapes alone (not from what any implementation compiles).

Each function returns {"flops": ..., "bytes": ...} for the whole call.
A roofline share divides the least time these allow at the published
peaks (peaks.json) by the layer's device time in the trace.
"""

from __future__ import annotations

import math

ACCEL_DZ = 2           # z step of the F-Fdot plane (accel.h)
ACCEL_NUMBETWEEN = 2   # interpolated columns per Fourier bin
F32 = 4
C64 = 8


def search_range(numbins: int, T: float, flo: float):
    """(rlo, rhi) in Fourier bins: accelsearch's searched range."""
    return max(flo * T, 8.0), float(numbins - 1)


def plane_cells(numbins: int, T: float, zmax: int, flo: float) -> int:
    rlo, rhi = search_range(numbins, T, flo)
    numz = (zmax // ACCEL_DZ) * 2 + 1
    return int(numz * ACCEL_NUMBETWEEN * (rhi - rlo))


def accel_build(numbins: int, T: float, zmax: int, flo: float,
                ntrials: int) -> dict:
    """The F-Fdot plane over the searched range: read the spectrum once,
    write every plane cell once as float32.  Operations: the complex
    product and power of each cell in the frequency-domain correlation
    (8 per cell); the FFTs are not counted, so the operations bound is
    a lower bound.  At the v5e peaks the bytes bound is the larger."""
    rlo, rhi = search_range(numbins, T, flo)
    cells = plane_cells(numbins, T, zmax, flo)
    return {"flops": 8.0 * cells * ntrials,
            "bytes": float((cells * F32 + (rhi - rlo) * C64) * ntrials)}


def accel_scan(numbins: int, T: float, zmax: int, numharm: int,
               flo: float, ntrials: int) -> dict:
    """Harmonic summing over the plane: read every cell once; one add
    per summed harmonic and one compare per stage for each cell."""
    cells = plane_cells(numbins, T, zmax, flo)
    stages = int(math.log2(numharm)) + 1
    return {"flops": float(cells * (numharm - 1 + stages) * ntrials),
            "bytes": float(cells * F32 * ntrials)}


def dedisp_step(nchan: int, nsub: int, ndms: int, blocklen: int) -> dict:
    """One streamed block: read the new float32 block once, write the
    DM series once; one add per channel sample into its subband and
    one per subband sample into each DM trial."""
    return {"flops": float(nchan * blocklen + ndms * nsub * blocklen),
            "bytes": float(F32 * (nchan * blocklen + ndms * blocklen))}


def least_time(req: dict, peak: dict) -> tuple:
    """(seconds, bound) at the published peaks: the larger of
    operations over the bf16 peak and bytes over HBM bandwidth."""
    t_ops = req["flops"] / peak["flops_bf16"]
    t_bytes = req["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "flops")
