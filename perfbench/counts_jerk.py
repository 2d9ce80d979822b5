"""Required operations and bytes of a band's (r, z, w) jerk volume, from
the cell's shapes alone (not from what any implementation compiles).

PRESTO's jerk search (accelsearch -wmax) correlates each r-block of the
spectrum with one kernel per (z, w) of the grid and sums harmonics over
the result.  Per trial, over the band's fundamental plane:

- for every (z, w) row and r-block of ACCEL_USELEN half bins: the
  complex product of the block's spectrum with the kernel (6 per
  point), the inverse FFT of length fftlen (5 n log2 n) and the power
  of each kept cell (3 per cell);
- the staged harmonic sum: one add per summed harmonic and one compare
  per stage for each fundamental cell;
- bytes: the band's spectrum read once (complex64).

The forward FFTs, the subharmonic planes and every byte of the planes
are left out, so the least time these allow is a lower bound for any
implementation that computes every cell of the volume.
"""

from __future__ import annotations

import math

ACCEL_USELEN = 7470    # PRESTO's half bins per r-block (accel.h)
ACCEL_DZ = 2
ACCEL_DW = 20
ACCEL_NUMBETWEEN = 2
C64 = 8


def next2_to_n(x: int) -> int:
    n = 1
    while n < x:
        n <<= 1
    return n


def _z_halfwidth(z: float) -> int:
    z = abs(z)
    m = max(int(z * (0.00089 * z + 0.3131) + 16), 16)
    if z > 100 and m > 0.6 * z:
        m = int(0.6 * z)
    return m


def _w_halfwidth(z: float, w: float) -> int:
    if abs(w) < 1e-7:
        return _z_halfwidth(z)
    nu0 = -z / 2.0 + w / 12.0
    ext = max(abs(nu0), abs(z / 2.0 + w / 12.0))
    ustar = (w / 2.0 - z) / w
    if 0.0 < ustar < 1.0:
        ext = max(ext, abs(nu0 + (z - w / 2.0) * ustar
                           + (w / 2.0) * ustar ** 2))
    return int(math.ceil(ext)) + 16


def fftlen(zmax: int, wmax: int, uselen: int = ACCEL_USELEN) -> int:
    """calc_fftlen of the fundamental (accel_utils.c) for the widest
    (zmax, wmax) kernel."""
    hw = _w_halfwidth(zmax, wmax) if wmax else _z_halfwidth(zmax)
    return next2_to_n(uselen + 2 + 2 * ACCEL_NUMBETWEEN * hw)


def band_cells(band_bins: int, zmax: int, wmax: int) -> int:
    """Fundamental plane cells of the band: numz x numw x half bins."""
    numz = (zmax // ACCEL_DZ) * 2 + 1
    numw = (wmax // ACCEL_DW) * 2 + 1
    return numz * numw * ACCEL_NUMBETWEEN * int(band_bins)


def jerk_volume(band_bins: int, zmax: int, wmax: int, numharm: int,
                ntrials: int) -> dict:
    """{"flops", "bytes"} of ``ntrials`` trials' band volumes."""
    numz = (zmax // ACCEL_DZ) * 2 + 1
    numw = (wmax // ACCEL_DW) * 2 + 1
    n = fftlen(zmax, wmax)
    nblocks = -(-ACCEL_NUMBETWEEN * int(band_bins) // ACCEL_USELEN)
    per_row_block = 6 * n + 5 * n * math.log2(n) + 3 * ACCEL_USELEN
    corr = numz * numw * nblocks * per_row_block
    stages = int(math.log2(numharm)) + 1
    harm = band_cells(band_bins, zmax, wmax) * (numharm - 1 + stages)
    return {"flops": float((corr + harm) * ntrials),
            "bytes": float(C64 * int(band_bins) * ntrials)}
