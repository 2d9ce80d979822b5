"""Fourier-domain F–Fdot acceleration search (accelsearch rebuilt TPU-first).

Reference call stack (SURVEY.md §3.2, src/accelsearch.c:134-221,
src/accel_utils.c): per r-block of ACCEL_USELEN half-bins —
  subharm_ffdot_plane  (accel_utils.c:879-1051): normalize amplitudes,
      spread ×2 interbin, FFT, per-z-row complex-multiply by conj
      z-response kernel, inverse FFT, |·|²/fftlen² into powers[z][r]
  inmem harmonic sums  (accel_utils.c:1160-1256): powers[z][r] +=
      plane[zind(frac,z)][round(r*frac)]
  search_ffdotpows     (accel_utils.c:1259-1298): threshold at
      powcut[stage], candidate_sigma, sorted insert.

TPU-first redesign (this module):
  * the whole spectrum's fundamental plane is built as ONE batched
    tensor program: [nblocks, fftlen] spread segments x [numz, fftlen]
    kernel bank -> batched IFFT -> [nblocks, numz, uselen] powers,
    assembled to P[numz, R] in HBM (the reference's `-inmem` plane,
    accel_utils.c:1651-1670, is the natural TPU layout);
  * harmonic summing is a z-row take plus a PHASE-DECOMPOSED column
    read (static strided views when slab starts are numharm-aligned —
    no minor-axis gather, the TPU scan-time hot spot), accumulated
    stage by stage;
  * thresholding is a segment-max (lossless under the r-dedup rule)
    followed by a top-k per stage (static K, the `omp critical` insert
    becomes host-side filtering), returned as ONE packed int32 tensor
    so the host pays a single D2H;
  * candidate sigma/powcut math runs on host in float64 (ops/stats).

All device entry points keep complex internal to jit (float32 pair
boundaries — see ops/fftpack note on the TPU complex-transfer limit).
"""

from __future__ import annotations

import os
from collections import Counter
from dataclasses import dataclass, replace
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from functools import lru_cache, partial

from presto_tpu.obs import maybe_span
from presto_tpu.ops import responses as resp
from presto_tpu.ops import stats as st
from presto_tpu.utils.psr import next2_to_n

# Search grid constants (include/accel.h:18-31)
ACCEL_NUMBETWEEN = 2
ACCEL_DR = 0.5
ACCEL_RDR = 2
ACCEL_DZ = 2
ACCEL_RDZ = 0.5
ACCEL_CLOSEST_R = 15.0
ACCEL_USELEN = 7470
DBLCORRECT = 1e-14

# One shared device-memory constant (the meminfo.h analog): every HBM
# budget in this module derives from it so independent sub-budgets
# cannot stack past the device.  Override (bytes) for parts with
# different headroom.
DEVICE_HBM_BYTES = int(os.environ.get("PRESTO_TPU_HBM_BYTES",
                                      str(16 * 2 ** 30)))
# the [chunk, numz, fftlen] complex plane-build intermediate budget
# (bigger was NOT better in clean A/Bs on v5e — HBM pressure beside
# the plane + stacked-ys residents); single source for every consumer
CHUNK_BUDGET_BYTES = int(os.environ.get("PRESTO_TPU_CHUNK_BUDGET",
                                        str(2 ** 30)))


def _nearest_int(x: float) -> int:
    """Round half away from zero — the reference's NEAREST_INT
    (prepfold.h:14), NOT Python's banker's rounding."""
    return int(np.ceil(x - 0.5)) if x < 0 else int(np.floor(x + 0.5))


def calc_required_z(harm_fract: float, zfull: float) -> float:
    """z of the subharmonic for fundamental z (accel_utils.c:53-59)."""
    return _nearest_int(ACCEL_RDZ * zfull * harm_fract) * ACCEL_DZ


def calc_required_r(harm_fract: float, rfull: float) -> float:
    """r of the subharmonic for fundamental r (accel_utils.c:60-66)."""
    return int(ACCEL_RDR * rfull * harm_fract + 0.5) * ACCEL_DR


def calc_required_w(harm_fract: float, wfull: float) -> float:
    """w of the subharmonic for fundamental w, rounded to the jerk
    grid (modern PRESTO's calc_required_w; the mounted reference
    predates the jerk search)."""
    return _nearest_int(wfull * harm_fract / ACCEL_DW) * ACCEL_DW


def index_from_z(z: float, loz: float) -> int:
    return int((z - loz) * ACCEL_RDZ + DBLCORRECT)


def calc_fftlen(numharm: int, harmnum: int, max_zfull: int,
                uselen: int = ACCEL_USELEN,
                max_wfull: int = 0) -> int:
    """FFT length for a subharmonic block (accel_utils.c:116-131;
    jerk-search banks size for the widest w kernel)."""
    harm_fract = harmnum / numharm
    bins_needed = uselen * harmnum // numharm + 2
    z_req = calc_required_z(harm_fract, max_zfull)
    hw = (resp.w_resp_halfwidth(z_req, max_wfull, resp.LOWACC)
          if max_wfull else resp.z_resp_halfwidth(z_req, resp.LOWACC))
    end_effects = 2 * ACCEL_NUMBETWEEN * hw
    return next2_to_n(bins_needed + end_effects)


ACCEL_DW = 20                    # w grid step of the jerk search


@dataclass
class AccelConfig:
    zmax: int = 200              # max |z| searched (fundamental)
    wmax: int = 0                # max |w| of the jerk search (0 = off)
    numharm: int = 8             # max harmonics summed (power of two)
    sigma: float = 2.0           # candidate sigma cutoff
    rlo: float = 0.0             # min Fourier freq searched (bins);
                                 # 0 -> flo * T at plan time
    rhi: float = 0.0             # 0 -> numbins - 1
    flo: float = 1.0             # min freq (Hz) if rlo not given
    uselen: int = ACCEL_USELEN   # half-bins of fundamental per block
    max_cands_per_stage: int = 2048   # static top-k size
    norm: str = "median"         # "median" (accel_utils.c:952-967) or
                                 # "prenorm" (spectrum already
                                 # normalized: -photon/-locpow modes
                                 # prescale on host)

    @property
    def numharmstages(self) -> int:
        return int(np.log2(self.numharm)) + 1

    @property
    def numz(self) -> int:
        return (self.zmax // ACCEL_DZ) * 2 + 1

    @property
    def ws(self) -> np.ndarray:
        """Jerk-search w grid (empty when wmax == 0)."""
        if not self.wmax:
            return np.zeros(1)
        nside = self.wmax // ACCEL_DW
        return (np.arange(2 * nside + 1) - nside) * float(ACCEL_DW)


@dataclass
class AccelKernels:
    """The z-response kernel bank for the fundamental (host-built).

    Kernels are stored TIME-DOMAIN, centered in a common kmax-tap
    window (kmax = 2*NUMBETWEEN*halfwidth of the widest kernel); the
    host uploads this compact bank and _fft_kernel_bank_c expands it to
    the FFT'd fftlen bank on device (a ~20x smaller upload; one bank
    per w plane in the jerk search).
    """
    fftlen: int
    halfwidth: int
    numz: int
    zlo: int
    kmax: int
    kern_pairs: np.ndarray       # [numz, kmax, 2] float32, centered

    @classmethod
    def build(cls, cfg: AccelConfig, w: float = 0.0) -> "AccelKernels":
        """Parity: init_kernel (accel_utils.c:133-151) for harm 1/1.

        One kernel per z in [-zmax, zmax] step ACCEL_DZ: the float64
        z-response (or w-response for the jerk search's w != 0 planes),
        kernels shared across all r-blocks.  All w planes of one
        search share the kmax sized for the widest kernel so the
        plane builder compiles once.
        """
        fftlen = calc_fftlen(1, 1, cfg.zmax, cfg.uselen, cfg.wmax)
        halfwidth = (resp.w_resp_halfwidth(float(cfg.zmax),
                                           float(cfg.wmax), resp.LOWACC)
                     if cfg.wmax else
                     resp.z_resp_halfwidth(float(cfg.zmax), resp.LOWACC))
        numz = cfg.numz
        kmax = 2 * ACCEL_NUMBETWEEN * halfwidth
        kerns = np.zeros((numz, kmax), dtype=np.complex128)
        zs = -cfg.zmax + np.arange(numz, dtype=np.float64) * ACCEL_DZ
        if abs(w) >= 1e-7:
            # whole-bank quadrature at full kmax taps (the centered
            # numkern sub-grids of the kmax grid coincide exactly, so
            # masking reproduces the per-z-truncated kernels); the
            # serial per-z path cost ~1-2 s/kernel — an hour per
            # wmax=300 bank set
            full = resp.gen_w_response_bank(0.0, ACCEL_NUMBETWEEN,
                                            zs, float(w), kmax)
        for i in range(numz):
            z = zs[i]
            if abs(w) < 1e-7:
                hw = resp.z_resp_halfwidth(float(z), resp.LOWACC)
                numkern = min(2 * ACCEL_NUMBETWEEN * hw, kmax)
                k = resp.gen_z_response(0.0, ACCEL_NUMBETWEEN, float(z),
                                        numkern)
                start = kmax // 2 - numkern // 2
                kerns[i, start:start + numkern] = k[:numkern]
            else:
                hw = resp.w_resp_halfwidth(float(z), float(w),
                                           resp.LOWACC)
                numkern = min(2 * ACCEL_NUMBETWEEN * hw, kmax)
                start = kmax // 2 - numkern // 2
                kerns[i, start:start + numkern] = \
                    full[i, start:start + numkern]
        pairs = np.stack([kerns.real, kerns.imag], axis=-1).astype(np.float32)
        return cls(fftlen=fftlen, halfwidth=halfwidth, numz=numz,
                   zlo=-cfg.zmax, kmax=kmax, kern_pairs=pairs)


# ----------------------------------------------------------------------
# Device: fundamental plane construction
# ----------------------------------------------------------------------

def fft_kernel_bank_np(kern: "AccelKernels") -> np.ndarray:
    """Host-side expansion of the compact time-domain bank to the
    FFT'd [numz, fftlen, 2] bank _ffdot_blocks consumes (the numpy
    twin of _fft_kernel_bank_c, for driver entry points and referee
    paths that want plain arrays).

    NOTE: this twin FFTs in complex128 then rounds, while the device's
    _fft_kernel_bank_c FFTs in complex64 — the two banks agree only to
    float32 rounding, not bit-for-bit (accel_ref's referee compares
    candidate lists, where the difference is far below threshold)."""
    kc = kern.kern_pairs[..., 0] + 1j * kern.kern_pairs[..., 1]
    half = kern.kmax // 2
    placed = np.zeros((kc.shape[0], kern.fftlen), dtype=np.complex128)
    placed[:, :half] = kc[:, half:]
    placed[:, kern.fftlen - half:] = kc[:, :half]
    k = np.fft.fft(placed, axis=-1)
    return np.stack([k.real, k.imag], axis=-1).astype(np.float32)


@partial(jax.jit, static_argnames=("fftlen",))
def _fft_kernel_bank_c(kern_tpairs, fftlen):
    """FFT'd complex64 device bank from the compact time-domain bank
    (NR wrap placement, corr_prep.c:58-80 + forward FFT) — the form
    the build hot path consumes (see the dtype note on _kern_bank_z;
    the compact time-domain bank still uploads as pairs)."""
    kc = kern_tpairs[..., 0] + 1j * kern_tpairs[..., 1]
    kmax = kc.shape[-1]
    half = kmax // 2
    numz = kc.shape[0]
    placed = jnp.zeros((numz, fftlen), dtype=jnp.complex64)
    placed = placed.at[:, :half].set(kc[:, half:])
    placed = placed.at[:, fftlen - half:].set(kc[:, :half])
    return jnp.fft.fft(placed, axis=-1)


@partial(jax.jit, static_argnames=("uselen", "fftlen", "halfwidth"))
def _ffdot_blocks(seg_pairs, kern_pairs, uselen, fftlen, halfwidth):
    """Batched f-fdot power plane for many r-blocks at once —
    the PAIRS-boundary form kept for __graft_entry__ and external
    float32-only consumers (the build hot path uses the complex
    slab engines _ffdot_slab_mxu/_ffdot_slab_fft instead).

    seg_pairs: [nblocks, fftlen//2, 2] float32 — normalized Fourier
        amplitudes for each block's read window (lobin = block_rlo -
        halfwidth, fftlen//2 whole bins).
    kern_pairs: [numz, fftlen, 2] float32 — FFT'd kernel bank as
        pairs (fft_kernel_bank_np's output).
    Returns [nblocks, numz, uselen] float32 powers.

    Parity with the per-row loop of accel_utils.c:1002-1051: spread ×2,
    forward FFT, multiply by conj(kernel), inverse FFT, take uselen
    points starting at halfwidth*NUMBETWEEN, |.|^2 / fftlen^2.
    (A direct-conv MXU formulation was benchmarked at parity with this
    on v5e at float32 precision and abandoned — batched FFTs through
    XLA already saturate the same ~25 ms/chunk.)
    """
    data = seg_pairs[..., 0] + 1j * seg_pairs[..., 1]   # [B, fftlen//2]
    kern = kern_pairs[..., 0] + 1j * kern_pairs[..., 1]  # [numz, fftlen]
    B = data.shape[0]
    spread = jnp.zeros((B, fftlen), dtype=jnp.complex64)
    spread = spread.at[:, ::ACCEL_NUMBETWEEN].set(data)
    fdata = jnp.fft.fft(spread, axis=-1)                # [B, fftlen]
    prod = fdata[:, None, :] * jnp.conj(kern)[None]     # [B, numz, fftlen]
    corr = jnp.fft.ifft(prod, axis=-1)                  # ifft = fft(-1)/n
    offset = halfwidth * ACCEL_NUMBETWEEN
    good = jax.lax.dynamic_slice_in_dim(corr, offset, uselen, axis=2)
    # reference norm: |x|^2/fftlen^2 with unnormalized inverse FFT; jnp
    # ifft divides by fftlen already, so only one factor remains... but
    # the forward FFT here is unnormalized like COMPLEXFFT, so
    # |ifft_np|^2 = |ifft_ref|^2 / fftlen^2 exactly matches ref norm.
    return (good.real ** 2 + good.imag ** 2).astype(jnp.float32)


# ----------------------------------------------------------------------
# Factored MXU-DFT correlation engine
# ----------------------------------------------------------------------
#
# XLA's TPU FFT is a multi-pass HBM-bound loop, and the correlation
# pipeline around it (spread scatter, kernel cmul, inverse FFT,
# |.|^2, then a plane-sized [B, numz, .] -> [numz, B*.] relayout)
# costs several full traversals of multi-GB complex intermediates.
# The factored engine computes the same correlation as two small DFT
# matmul stages (fftlen = n1 * 128) on the MXU, with the inverse
# written directly in z-major order ('zxic' einsum output) so the
# slab lands in plane layout with NO post-hoc transpose.  Validated
# at HIGHEST precision to the same float32 error vs a float64 FFT as
# the jnp.fft path (3.2e-7 vs 3.6e-7 max rel on the bench workload).

_DFT_N2 = 128                    # lane-width radix of stage 2

ACCEL_ENGINE = os.environ.get("PRESTO_TPU_ACCEL_ENGINE", "auto")

#: engine decisions, counted per (zmax, stage, engine) each time a
#: searcher fixes a build or scan program: stage is "build" (pallas /
#: xla-mxu / xla-fft) or "scan" (pallas, or "xla: <why the pallas
#: reducer declined>").  chip_smoke.py asserts the chip's main path
#: took the pallas engines from these counts.
ENGINES: Counter = Counter()


def _note_engine(zmax: int, stage: str, engine: str) -> None:
    ENGINES[(int(zmax), stage, engine)] += 1


def _use_mxu_engine(fftlen: int) -> bool:
    """auto: factored engine on TPU (pocketfft-backed XLA FFT wins on
    CPU), when fftlen factors as n1*128 with even n1 (the spread trick
    needs n2/2 integral)."""
    ok = fftlen % (2 * _DFT_N2) == 0
    if ACCEL_ENGINE == "mxu":
        return ok
    if ACCEL_ENGINE == "fft":
        return False
    return ok and jax.devices()[0].platform == "tpu"


@lru_cache(maxsize=8)
def _dft_consts_np(fftlen: int):
    """Pair-format (f32 [..., 2]) DFT stage constants, uploaded as
    pairs (the module's host<->device boundary, ops/fftpack.py) and
    recombined under jit.

    Factorization (time i = i1*n2 + i2, freq k = k1 + n1*k2):
      fwd   Y[k1, j] = sum_i1 D1[k1, i1] x[i1*(n2/2) + j]   (spread
            data: only even i2 = 2j are nonzero, halving stage 2)
            S[k1, k2] = (Y * T2) @ D2m, tiled 2x along k2
      inv   q = P @ C2;  corr[i1, i2] = iD1 @ (q * Tb)
    """
    n2 = _DFT_N2
    n1 = fftlen // n2
    m = n2 // 2

    def pairs(c):
        return np.stack([c.real, c.imag], -1).astype(np.float32)

    k1 = np.arange(n1)
    i1 = np.arange(n1)
    j = np.arange(m)
    k2 = np.arange(n2)
    i2 = np.arange(n2)
    D1 = np.exp(-2j * np.pi * np.outer(k1, i1) / n1)
    T2 = np.exp(-2j * np.pi * np.outer(k1, 2 * j) / fftlen)
    D2m = np.exp(-2j * np.pi * np.outer(j, np.arange(m)) / m)
    C2 = np.exp(+2j * np.pi * np.outer(k2, i2) / n2)
    Tb = np.exp(+2j * np.pi * np.outer(k1, i2) / fftlen) / fftlen
    iD1 = np.exp(+2j * np.pi * np.outer(i1, k1) / n1)
    return tuple(pairs(c) for c in (D1, T2, D2m, C2, Tb, iD1))


@partial(jax.jit, static_argnames=("fftlen",))
def _kern_bank_z(kern_c, fftlen):
    """FFT'd complex bank [numz, fftlen] -> conjugated stage-layout
    bank [numz, n1, n2] (Z[k1, k2] = Kfft[k1 + n1*k2]).

    NOTE on dtypes in this module's device path: everything internal
    is complex64, NOT float32 [..., 2] pairs — a trailing dim of 2
    lands on the TPU lane axis and is padded 2 -> 128, a 64x tax on
    every byte moved (measured: the 561 window slices alone cost
    121 ms in pair layout).  Pairs appear only at host<->device
    boundaries (ops/fftpack.py)."""
    n1 = fftlen // _DFT_N2
    return jnp.conj(kern_c).reshape(
        kern_c.shape[0], _DFT_N2, n1).transpose(0, 2, 1)


def _ffdot_slab_mxu(data, kz, consts, uselen, fftlen, halfwidth):
    """Factored-DFT twin of _ffdot_blocks, returning the slab in plane
    layout [numz, B*uselen] (z-major, blocks concatenated along
    columns) — same math, same normalization, no output transpose.

    data: [B, fftlen//2] complex64 block windows; kz: _kern_bank_z
    bank; consts: _dft_consts pair arrays."""
    n2 = _DFT_N2
    n1 = fftlen // n2
    B = data.shape[0]
    cx = lambda p: p[..., 0] + 1j * p[..., 1]
    C2, Tb, iD1 = (cx(c) for c in consts[3:])
    numz = kz.shape[0]
    prec = jax.lax.Precision.HIGHEST
    S = _fwd_stage_c(data, consts, fftlen)               # [B, n1, n2]
    Pm = S[:, None] * kz[None]                           # [B,numz,n1,n2]
    q = jnp.einsum("xzab,bc->xzac", Pm, C2, precision=prec)
    corr = jnp.einsum("ia,xzac->zxic", iD1, q * Tb[None, None],
                      precision=prec)                    # [numz,B,n1,n2]
    pw = (corr.real ** 2 + corr.imag ** 2).astype(jnp.float32)
    pw = pw.reshape(numz, B, fftlen)
    off = halfwidth * ACCEL_NUMBETWEEN
    pw = jax.lax.slice(pw, (0, 0, off), (numz, B, off + uselen))
    return pw.reshape(numz, B * uselen)


def _fwd_stage_c(data, consts, fftlen):
    """Forward half of the factored transform: block windows ->
    stage-layout spectra S [B, n1, n2] complex — ONE implementation
    shared by the XLA slab engine and the pallas builder, so the two
    engines cannot drift."""
    n2 = _DFT_N2
    n1 = fftlen // n2
    m = n2 // 2
    B = data.shape[0]
    cx = lambda p: p[..., 0] + 1j * p[..., 1]
    D1, T2, D2m = (cx(c) for c in consts[:3])
    prec = jax.lax.Precision.HIGHEST
    x2 = data.reshape(B, n1, m)
    Y = jnp.einsum("ab,xbj->xaj", D1, x2, precision=prec)
    Sm = jnp.einsum("xaj,jk->xak", Y * T2[None], D2m, precision=prec)
    return jnp.concatenate([Sm, Sm], axis=-1)


def _fwd_stage_mxu(data, consts, fftlen):
    """_fwd_stage_c as (re, im) float32 pairs (the pallas builder's
    input form)."""
    S = _fwd_stage_c(data, consts, fftlen)
    return (S.real.astype(jnp.float32), S.imag.astype(jnp.float32))


def _ffdot_slab_fft(data, kern_c, uselen, fftlen, halfwidth):
    """jnp.fft twin of _ffdot_slab_mxu (complex in, z-major slab out)
    — the engine used where the factored transform doesn't apply
    (CPU, or fftlen not a multiple of 256)."""
    B = data.shape[0]
    numz = kern_c.shape[0]
    spread = jnp.zeros((B, fftlen), dtype=jnp.complex64)
    spread = spread.at[:, ::ACCEL_NUMBETWEEN].set(data)
    fdata = jnp.fft.fft(spread, axis=-1)
    prod = fdata[:, None, :] * jnp.conj(kern_c)[None]
    corr = jnp.fft.ifft(prod, axis=-1)
    offset = halfwidth * ACCEL_NUMBETWEEN
    good = jax.lax.dynamic_slice_in_dim(corr, offset, uselen, axis=2)
    pw = (good.real ** 2 + good.imag ** 2).astype(jnp.float32)
    return jnp.moveaxis(pw, 0, 1).reshape(numz, B * uselen)


def _block_median_norms_c(data):
    """Old-style per-block median power normalization factors.

    norm = 1/sqrt(median(|amps|^2)/ln2) (accel_utils.c:952-967).
    data: [B, numdata] complex windows -> [B, 1] float32 scale (the
    reference scales data before correlating)."""
    pows = data.real ** 2 + data.imag ** 2
    med = jnp.maximum(jnp.median(pows, axis=-1), 1e-30)
    return (1.0 / jnp.sqrt(med / jnp.log(2.0))).astype(jnp.float32)[
        :, None]


# ----------------------------------------------------------------------
# Device: harmonic summing + thresholding over the full plane
# ----------------------------------------------------------------------

def _harm_fracs_and_zinds(cfg: AccelConfig, numz: int):
    """Host-precomputed per-stage harmonic fractions and z-row maps.

    For each stage s >= 1 and odd harm < 2^s: fraction harm/2^s and the
    z-row gather map zind[numz] (inmem_add_ffdotpows index math,
    accel_utils.c:1160-1207).  Column maps are computed on device from
    the fraction (round-half-up of absolute half-bin * frac).
    """
    out = []
    zlo = -cfg.zmax
    zs = zlo + np.arange(numz) * ACCEL_DZ
    for stage in range(1, cfg.numharmstages):
        harmtosum = 1 << stage
        stage_list = []
        for harm in range(1, harmtosum, 2):
            frac = harm / harmtosum
            zinds = np.array([index_from_z(calc_required_z(frac, z), zlo)
                              for z in zs], dtype=np.int32)
            stage_list.append((harm, harmtosum, zinds))
        out.append(stage_list)
    return out


SEARCH_SEG = 16     # columns per segment-max before top-k: 16 columns
                    # = 8 r-bins < ACCEL_CLOSEST_R, so candidates
                    # merged here are exactly those the r-dedup
                    # (insert_new_accelcand semantics) collapses anyway


def _make_search_scanner(numharmstages, fracs_zinds, powcuts, slab, k,
                         plane_numr, aligned=False,
                         pallas_reducer=None, numz=None,
                         plane_padded=False):
    """One jit'd function running the whole staged search as a lax.scan
    over slab start columns (a single device dispatch — per-slab
    calls would pay the dispatch latency once per slab).

    Per slab: accumulate the harmonic sums, then per stage reduce each
    column to its max over z (same-column different-z cells are exact
    duplicates under the sifter's r-dedup), segment-max groups of
    SEARCH_SEG columns (duplicates under the same rule — the
    reference's own insert-time dedup, accel_utils.c:294-382, collapses
    candidates within ACCEL_CLOSEST_R=15 bins), and top-k the segments
    above powcut (TPU top-k cost scales with the input length; the
    16x shrink is the big win).  Column gather indices use exact int32
    round-half-up of (abs_halfbin * harm / htot), equal to the
    reference's (int)(rrint*frac + 0.5) double math
    (accel_utils.c:1169-1175), and each harmonic reads only its
    contiguous source window via dynamic_slice (bounded gather
    traffic).  Returns ONE packed int32 array [3, nslabs, stages, k]
    (power bits, column, zrow) so the host pays a single D2H transfer.
    """
    powcuts = jnp.asarray(powcuts, dtype=jnp.float32)
    fz = [(harm, htot, jnp.asarray(zi)) for stage in fracs_zinds
          for (harm, htot, zi) in stage]
    nseg = -(-slab // SEARCH_SEG)
    segpad = nseg * SEARCH_SEG - slab
    kk = min(k, nseg)

    def _zi_for(zinds, nrows):
        """zinds extended to a pad_rows plane (the direct-plane pallas
        builder hands the scanner ceil(numz/8)*8 rows; pad rows are
        zero-kernel rows, mapped to themselves so they stay zero in
        every harmonic accumulator and can never beat powcut)."""
        if nrows == zinds.shape[0]:
            return zinds
        return jnp.concatenate([
            zinds, jnp.arange(zinds.shape[0], nrows, dtype=jnp.int32)])

    def slab_body(planes, start_col):
        """planes: [1 + n_harm_terms] source planes — planes[0] is the
        fundamental, planes[1 + fi] the source for harmonic term fi.
        For the z-only search every entry aliases ONE buffer (free)."""
        P = planes[0]
        cols = start_col + jnp.arange(slab, dtype=jnp.int32)
        acc = jax.lax.dynamic_slice(P, (0, start_col), (P.shape[0], slab))

        def collect(acc, stage):
            colmax = acc.max(axis=0)
            colz = acc.argmax(axis=0).astype(jnp.int32)
            masked = jnp.where(colmax > powcuts[stage], colmax, 0.0)
            segs = jnp.pad(masked, (0, segpad)).reshape(nseg,
                                                        SEARCH_SEG)
            v, si = jax.lax.top_k(segs.max(axis=1), kk)
            ci = si * SEARCH_SEG + \
                jnp.take(segs.argmax(axis=1).astype(jnp.int32), si)
            # padded-segment hits have v == 0 and are filtered on host
            return v, ci, jnp.take(colz, ci, mode="clip")

        outs = [collect(acc, 0)]
        fi = 0
        for stage in range(1, numharmstages):
            for _ in range(1 << (stage - 1)):   # odd harmonics
                harm, htot, zinds = fz[fi]
                fi += 1        # planes[fi] is now THIS term's source
                               # (planes[0] is the fundamental)
                if (aligned and slab % htot == 0
                        and (slab // htot + 1) * harm <= slab):
                    # Phase-decomposed subharmonic read — NO gather.
                    # With start_col % htot == 0 (the _slab_plan
                    # alignment contract), column j = q*htot + ph maps
                    # to source column cstart + q*harm + off(ph),
                    # off(ph) = (ph*harm + htot//2)//htot <= harm: all
                    # phases are STATIC slices of a [nq+1, harm]
                    # reshape, replacing the minor-axis gather that
                    # dominated scan time on TPU (~6x the slice cost).
                    nq = slab // htot
                    cstart = (start_col // htot) * harm
                    src = jax.lax.dynamic_slice(
                        planes[fi], (0, cstart), (P.shape[0], slab))
                    sub = jnp.take(src, _zi_for(zinds, P.shape[0]),
                                   axis=0)
                    src3 = sub[:, :(nq + 1) * harm].reshape(
                        -1, nq + 1, harm)
                    pieces = []
                    for ph in range(htot):
                        off = (ph * harm + (htot >> 1)) // htot
                        if off < harm:
                            pieces.append(src3[:, :nq, off])
                        else:            # off == harm: next q, tap 0
                            pieces.append(src3[:, 1:nq + 1, 0])
                    acc = acc + jnp.stack(pieces, axis=-1).reshape(
                        acc.shape[0], slab)
                else:
                    # round-half-up of cols*harm/htot without int32
                    # overflow (split off the quotient so the multiply
                    # stays < 2^31 even for billion-bin spectra):
                    # exact for htot = 2^s.
                    rind = ((cols // htot) * harm
                            + ((cols % htot) * harm + (htot >> 1))
                            // htot)
                    cstart = jnp.minimum(
                        (start_col // htot) * harm
                        + ((start_col % htot) * harm + (htot >> 1))
                        // htot,
                        plane_numr - slab)
                    src = jax.lax.dynamic_slice(planes[fi], (0, cstart),
                                                (P.shape[0], slab))
                    sub = jnp.take(src, _zi_for(zinds, P.shape[0]),
                                   axis=0)
                    acc = acc + jnp.take(sub, rind - cstart, axis=1)
            outs.append(collect(acc, stage))
        vals = jnp.stack([o[0] for o in outs])      # [stages, k]
        cidx = jnp.stack([o[1] for o in outs])
        zrow = jnp.stack([o[2] for o in outs])
        # one int32 tensor (power bits / column / zrow) -> one D2H
        return jnp.stack([jax.lax.bitcast_convert_type(vals, jnp.int32),
                          cidx, zrow])

    nterms = len(fz)

    def _scan_planes_py(planes, start_cols):
        def body(carry, start):
            return carry, slab_body(planes, start)
        _, packed = jax.lax.scan(body, None, start_cols)
        return jnp.moveaxis(packed, 1, 0)  # [3, nslabs, stages, k]

    def _collect_from_reduced(colmax, colz):
        """Shared threshold + segment-max + top-k over the reduced
        [nslabs, stages, slab] (colmax, colz) arrays -> packed int32
        [3, nslabs, stages, k] (same packing as slab_body)."""
        nslabs = colmax.shape[0]
        masked = jnp.where(colmax > powcuts[None, :, None], colmax,
                           0.0)
        segs = masked.reshape(nslabs, numharmstages, nseg,
                              SEARCH_SEG)
        v, si = jax.lax.top_k(segs.max(-1), kk)
        ci = si * SEARCH_SEG + jnp.take_along_axis(
            segs.argmax(-1).astype(jnp.int32), si, axis=-1)
        zrow = jnp.take_along_axis(colz, ci, axis=-1)
        return jnp.stack([jax.lax.bitcast_convert_type(v, jnp.int32),
                          ci, zrow])

    def _scan_pallas_py(P, start_cols):
        """Pallas stage-reduction path: pad the plane to the kernel's
        tiling contract, reduce on-kernel, finish in XLA.  A plane
        from the direct-plane builder (plane_padded) already has
        pad_rows rows and >= PLANE_PAD trailing zero columns — no
        multi-GB pad pass."""
        from presto_tpu.search import accel_pallas as ap
        rowpad = max(0, ap.pad_rows(numz) - P.shape[0])
        colpad = 0 if plane_padded else ap.PLANE_PAD
        Ppad = jnp.pad(P, ((0, rowpad), (0, colpad))) \
            if (rowpad or colpad) else P
        colmax, colz = pallas_reducer(Ppad, start_cols)
        return _collect_from_reduced(colmax, colz)

    def _scan_all_py(P, start_cols):
        if pallas_reducer is not None:
            return _scan_pallas_py(P, start_cols)
        # z-only search: every harmonic reads the fundamental plane
        return _scan_planes_py((P,) * (1 + nterms), start_cols)

    scan_all = jax.jit(_scan_all_py)
    scan_all.body = _scan_all_py     # unjitted, for fused build+search

    @jax.jit
    def scan_many(Ps, start_cols):
        """Batched: Ps [numdms, numz, plane_numr] -> per-DM results in
        ONE device dispatch (the DM fan-out of a survey search)."""
        def per_dm(_, P):
            return None, _scan_all_py(P, start_cols)
        _, outs = jax.lax.scan(per_dm, None, Ps)
        return jnp.moveaxis(outs, 1, 0)   # [3, numdms, nslabs, stages, k]

    from functools import partial

    @partial(jax.jit, static_argnums=2)
    def scan_many_compact(Ps, start_cols, m):
        """scan_many + per-trial top-m candidate compaction in the
        SAME dispatch: the dense [3, nd, nslabs, stages, k] tensor
        never crosses to the host (compact_scan_packed — the D2H
        shrink that made the e2e share device-bound, applied to the
        library's batched path)."""
        packed = scan_many(Ps, start_cols)
        per_dm = jnp.moveaxis(packed, 1, 0)  # [nd, 3, nsl, st, k]
        return jax.vmap(
            lambda p: compact_scan_packed(p, m))(per_dm)

    scan_all.many = scan_many
    scan_all.many_compact = scan_many_compact
    return scan_all


def _unpack_scan(packed: np.ndarray):
    """Host side of the packed scanner output: float32 powers + int32
    column/zrow indices."""
    arr = np.asarray(packed)
    return arr[0].view(np.float32), arr[1], arr[2]


# compact_scan_packed meta-word layout (low to high bits)
_CMP_ZBITS = 12          # zrow: plane row index (numz + reducer pad)
_CMP_SBITS = 3           # stage: numharmstages <= 5 in practice
COMPACT_CANDS = 2048     # default top-m budget per trial


def compact_scan_packed(packed, m: int = COMPACT_CANDS):
    """Device-side compaction of one trial's scanner output.

    The scanner's packed [3, nslabs, stages, k] tensor reserves k
    top-k slots per (slab, stage) but above-powcut survivors are
    typically a few hundred per trial — the dense D2H (tens of MB per
    DM group) dominated the whole e2e wall of a slow-transfer host
    (153.8 of 154.0 s host-side in round 4).  This selects the
    top-m slots by power across ALL (slab, stage, slot) cells in one
    device pass, so the host transfer shrinks from nslabs*stages*k to
    m words per row.  Lossless as long as the number of positive
    (above-powcut) slots is < m; collect_compacted() raises if the
    m-th value is still positive (possible truncation) — raise m.

    Pure jnp: call it inside an enclosing jit (e.g. appended to a
    fused build+scan+compact program) so no extra dispatch is paid.
    Returns int32 [3, m]: power bits (descending), within-slab column,
    and meta = zrow | stage << _CMP_ZBITS | slab << (_CMP_ZBITS+_CMP_SBITS).
    """
    valbits, cidx, zrow = packed[0], packed[1], packed[2]
    nslabs, stages, k = valbits.shape
    assert stages < (1 << _CMP_SBITS) and nslabs < (1 << 16), \
        (nslabs, stages)
    m = min(m, nslabs * stages * k)
    si = jnp.arange(nslabs, dtype=jnp.int32)[:, None, None]
    sg = jnp.arange(stages, dtype=jnp.int32)[None, :, None]
    meta = (zrow | (sg << _CMP_ZBITS)
            | (si << (_CMP_ZBITS + _CMP_SBITS)))
    vals = jax.lax.bitcast_convert_type(valbits, jnp.float32)
    v, idx = jax.lax.top_k(vals.reshape(-1), m)
    return jnp.stack([jax.lax.bitcast_convert_type(v, jnp.int32),
                      jnp.take(cidx.reshape(-1), idx),
                      jnp.take(meta.reshape(-1), idx)])


@dataclass
class AccelCand:
    """A raw search candidate (pre-sifting). Mirrors accelcand
    (accel.h:76-86) minus the optimization fields."""
    power: float
    sigma: float
    numharm: int
    r: float           # fundamental-search r / numharm (candidate freq bin)
    z: float
    w: float = 0.0     # jerk plane of origin (0 unless wmax search)

    def freq(self, T: float) -> float:
        return self.r / T


class AccelSearch:
    """In-memory accelsearch over a packed spectrum.

    Usage:
        s = AccelSearch(cfg, T=obs_seconds)
        cands = s.search(fft_pairs)   # [numbins, 2] float32 pairs
    """

    def __init__(self, cfg: AccelConfig, T: float, numbins: int):
        # spectra shorter than one ACCEL_USELEN r-block would yield an
        # empty search (the reference's block loop, accelsearch.c:167,
        # simply assumes survey-length FFTs): shrink the block to fit
        max_uselen = max(64, 2 * (numbins - 16))
        if cfg.uselen > max_uselen or cfg.uselen % 2:
            # even uselen keeps the block grid on whole bins — the
            # uniform-hop frame builder (_frames_fn) requires an
            # integer hop = uselen/2
            cfg = replace(cfg, uselen=min(cfg.uselen & ~1, max_uselen))
        # Direct-plane pallas builder (TPU): pick an ALIGNED geometry —
        # uselen a multiple of 128 columns filling the fftlen minus a
        # 128-aligned output offset — so the build kernel stores the
        # plane layout directly (build_pallas.py docstring).  Only the
        # DEFAULT uselen is retuned; an explicit cfg.uselen is the
        # caller's choice (the reference's own ACCEL_USELEN is a CPU
        # FFT tuning knob, accel.h:10-16).
        from presto_tpu.search import accel_pallas as _ap
        _plb_ok = (_ap.pallas_available()
                   and ACCEL_ENGINE in ("auto", "plb"))
        if _plb_ok and cfg.uselen == ACCEL_USELEN:
            fft0 = calc_fftlen(1, 1, cfg.zmax, cfg.uselen, cfg.wmax)
            hw0 = (resp.w_resp_halfwidth(float(cfg.zmax),
                                         float(cfg.wmax), resp.LOWACC)
                   if cfg.wmax else
                   resp.z_resp_halfwidth(float(cfg.zmax), resp.LOWACC))
            hw_eff0 = -(-hw0 // 64) * 64
            u_al = (fft0 - 4 * hw_eff0) & ~127
            if (1024 <= u_al <= max_uselen
                    and calc_fftlen(1, 1, cfg.zmax, u_al,
                                    cfg.wmax) == fft0):
                cfg = replace(cfg, uselen=u_al)
        self.cfg = cfg
        self.T = T
        self.numbins = numbins
        self.kern = AccelKernels.build(cfg)
        # plb engages when the ACTUAL kernel geometry satisfies the
        # alignment contract (kern built above)
        self._plb_hw_eff = None
        if _plb_ok:
            hw_eff = -(-self.kern.halfwidth // 64) * 64
            if (self.kern.fftlen % (2 * _DFT_N2) == 0
                    and cfg.uselen % _DFT_N2 == 0
                    and cfg.uselen + 4 * hw_eff <= self.kern.fftlen
                    and _use_mxu_engine(self.kern.fftlen)):
                self._plb_hw_eff = hw_eff
        self._fn_cache = {}   # compiled build/scan fns (avoid re-jit)
        self._kern_dev = None  # device copy of the kernel bank (lazy)
        self.rlo = cfg.rlo if cfg.rlo > 0 else max(cfg.flo * T, 8.0)
        self.rhi = cfg.rhi if cfg.rhi > 0 else numbins - 1
        # numindep & powcut per stage (accel_utils.c:1629-1641)
        self.numindep = []
        self.powcut = []
        for ii in range(cfg.numharmstages):
            harmtosum = 1 << ii
            if cfg.numz == 1:
                ni = (self.rhi - self.rlo) / harmtosum
            else:
                ni = ((self.rhi - self.rlo) * (cfg.numz + 1) *
                      (ACCEL_DZ / 6.95) / harmtosum)
            # jerk search: each w plane is (approximately) another set
            # of independent trials
            ni *= len(cfg.ws)
            self.numindep.append(ni)
            self.powcut.append(float(st.power_for_sigma(
                cfg.sigma, harmtosum, ni)))

    # -- plane ---------------------------------------------------------

    def _plan_blocks(self):
        """r-block starts (whole bins) covering [0, rhi] — the
        reference's inmem pre-population + search loops
        (accelsearch.c:143-160) start at r=8; this grid starts at r=0
        so plane columns stay tile-aligned (col0=16 puts every concat
        joint of the plane assembly at a misaligned lane offset, a
        measured ~2x write-cost tax on v5e).  Deviation: the first
        block's median-normalization window covers [0, uselen/2)
        instead of [8, 8+uselen/2) — 8 bins of content out of 4096,
        immaterial to the robust median — and columns below rlo are
        computed but filtered at collect time (_collect_slab r0min),
        exactly like any other below-rlo column of an aligned slab."""
        blocks = []
        startr = 0.0
        step = self.cfg.uselen * ACCEL_DR
        # Only full, in-spectrum blocks are built/searched — same bound
        # as the reference loop (accelsearch.c:167): a partial block at
        # the top would be median-normalized against zero padding.
        while startr + step < self.rhi:
            blocks.append(startr)
            startr += step
        return blocks

    def build_plane(self, fft_pairs: np.ndarray):
        """Fundamental F-Fdot plane P[numz, plane_numr] — a device
        array resident in HBM (host transfers of the multi-GB plane
        through the host<->TPU link would dominate the search time).

        plane column c = absolute half-bin (r = c * ACCEL_DR), starting
        at column 0 == r 0.  Block j occupies the contiguous columns
        [j*uselen, (j+1)*uselen): starts are j*uselen*DR (the r=0
        block-grid origin of _plan_blocks; columns below rlo are
        filtered at collect time), so the per-chunk slabs concatenate
        directly into the plane.
        fft_pairs: [numbins, 2] float32 (the packed .fft as pairs).
        """
        kern = self.kern
        starts = self._plan_blocks()
        if not starts:
            # spectrum too short for one full block: empty plane
            return jnp.zeros((kern.numz, 0), dtype=jnp.float32)
        yp = self._build_plan_ns()
        key = ("build",) + yp.key
        self._build_plan = key
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.jit(yp.build_body)
        return self._fn_cache[key](self._to_dev(fft_pairs),
                                   self._kern_bank_dev())

    def _kern_bank_dev(self):
        if self._kern_dev is None:   # one small upload, reused
            self._kern_dev = _fft_kernel_bank_c(
                jnp.asarray(self.kern.kern_pairs), self.kern.fftlen)
        return self._kern_dev

    @staticmethod
    def _to_dev(fft_pairs):
        if isinstance(fft_pairs, jax.Array):
            return fft_pairs             # already uploaded
        return jnp.asarray(np.ascontiguousarray(fft_pairs))

    def _plane_geom(self):
        """Block/window geometry of the plane build (host-side ints),
        cached — it depends only on (cfg, numbins)."""
        if getattr(self, "_geom", None) is not None:
            return self._geom
        cfg, kern = self.cfg, self.kern
        starts = self._plan_blocks()
        if not starts:
            self._geom = False
            return False
        numdata = kern.fftlen // 2
        # plane width padded (zero columns) to a multiple of the
        # scanner's alignment so every aligned slab fits inside the
        # plane; zero columns can never exceed powcut.  On TPU the
        # pallas stage reducer wants TILE-aligned slab starts, so the
        # plane pads to that stricter grid.
        align = max(16, cfg.numharm)
        from presto_tpu.search import accel_pallas as ap
        if ap.pallas_available():
            align = max(align, ap.TILE)
        # direct-plane builder geometry: the plane IS the kernel
        # output, [numz_pad, nb_pad*uselen] with >= 1 zero-padded
        # block on the right (covers the scan's PLANE_PAD contract);
        # the effective halfwidth rounds the window offset to a
        # 128-column boundary so the good region is whole n1-rows
        hw_eff = self._plb_hw_eff
        hw_use = hw_eff if hw_eff else kern.halfwidth
        nb_pad = None
        if hw_eff:
            from presto_tpu.search import build_pallas as bp
            nb_pad = -(-(len(starts) + 1) // bp.BB) * bp.BB
            plane_numr = nb_pad * cfg.uselen
        else:
            plane_numr = int(2 * int(starts[-1]) + cfg.uselen)
            plane_numr += (-plane_numr) % align
        # Chunk the block batch: the [chunk, numz, fftlen] complex
        # intermediate is the peak working memory, so bound it — the
        # HBM-ladder analog of meminfo.h.  Round down to the smallest
        # chunk keeping chunk*uselen a lane-tile multiple (aligned
        # concat joints / DUS offsets).
        chunk = max(1, int(CHUNK_BUDGET_BYTES
                           // (kern.numz * kern.fftlen * 8)))
        import math as _math
        almul = 128 // _math.gcd(cfg.uselen, 128)
        if chunk >= almul:
            chunk -= chunk % almul
        col0 = int(starts[0]) * ACCEL_RDR
        # Host uploads ONLY the raw spectrum; the per-block read
        # windows are gathered on device (shipping the ~10%-
        # overlapping window tensor costs more than the whole device
        # compute).  Window j = fft_pad[lobins[j] : +numdata]; padded
        # (beyond-nblocks) windows point at a zero region.
        nblocks = len(starts)
        chunk = min(chunk, nblocks)
        nsteps = (nblocks + chunk - 1) // chunk
        npad_blocks = nsteps * chunk - nblocks
        lobin0 = int(starts[0]) - hw_use
        pad_lo = max(0, -lobin0)
        # cover the last real window AND the frame builder's (F+P)*hop
        # base region (padded frames read zeros there)
        hop = int(cfg.uselen * ACCEL_DR)
        F = nsteps * chunk
        P = -(-numdata // hop)
        pad_hi = numdata + max(
            0, int(starts[-1]) - hw_use + numdata - self.numbins)
        pad_hi = max(pad_hi,
                     lobin0 + pad_lo + (F + P) * hop - self.numbins)
        lobins = np.asarray(
            [int(s0) - hw_use for s0 in starts]
            + [self.numbins] * npad_blocks, np.int32) + pad_lo
        from types import SimpleNamespace
        self._geom = SimpleNamespace(
            starts=starts, numdata=numdata, plane_numr=plane_numr,
            chunk=chunk, nsteps=nsteps, col0=col0, nblocks=nblocks,
            lobins=lobins, hw_use=hw_use, hw_eff=hw_eff,
            nb_pad=nb_pad,
            pads=((pad_lo, pad_hi), (0, 0)),
            body_numr=nsteps * chunk * cfg.uselen)
        return self._geom

    def _chunk_slab_fn(self, g):
        """Per-chunk slab computation: [chunk, numdata] complex block
        windows -> [numz, chunk*uselen] slab in plane (z-major)
        layout.  kern_use is an ARGUMENT (not a closure): it is
        the complex FFT'd bank for the fft engine and the stage-layout
        conj bank (_kern_bank_z) for the mxu engine."""
        cfg, kern = self.cfg, self.kern
        use_mxu = _use_mxu_engine(kern.fftlen)
        consts = _dft_consts_np(kern.fftlen) if use_mxu else None
        hw_use = g.hw_use     # effective halfwidth: plb geometry pads
                              # the output offset, and the window
                              # lobins shift with it — every engine
                              # must slice at the same offset

        def chunk_slab(data, kern_use):
            if cfg.norm == "median":
                data = data * _block_median_norms_c(data)
            if use_mxu:
                return _ffdot_slab_mxu(
                    data, kern_use, tuple(map(jnp.asarray, consts)),
                    cfg.uselen, kern.fftlen, hw_use)
            return _ffdot_slab_fft(data, kern_use, cfg.uselen,
                                   kern.fftlen, hw_use)

        chunk_slab.use_mxu = use_mxu
        return chunk_slab

    def _frames_fn(self, g):
        """All block read windows at once, from the uniform block grid
        (hop = uselen*ACCEL_DR bins): two reshapes + one concat
        instead of per-block slices (561 dynamic_slice ops measured
        ~100 ms on v5e; this is one pass over ~18 MB).  Returns
        f(fft_raw_pairs) -> [nframes, numdata] complex64, where frames
        past the real blocks read the zero padding (the padded-block
        contract of _plane_geom)."""
        kern = self.kern
        hop = int(self.cfg.uselen * ACCEL_DR)
        L = g.numdata
        F = g.nsteps * g.chunk
        lob0 = int(g.lobins[0])
        pad_lo, pad_hi = g.pads[0]
        P = -(-L // hop)              # rows each frame spans

        def frames(fft_raw):
            c = jnp.pad(fft_raw[:, 0] + 1j * fft_raw[:, 1],
                        (pad_lo, pad_hi))
            base = jax.lax.slice(c, (lob0,), (lob0 + (F + P) * hop,))
            A = base.reshape(F + P, hop)
            parts = [jax.lax.slice(A, (p, 0),
                                   (p + F, min(hop, L - p * hop)))
                     for p in range(P)]
            return jnp.concatenate(parts, axis=1) if P > 1 else parts[0]
        return frames

    def _pallas_build_body(self, g, frames_fn):
        """Direct-plane pallas build body (the default TPU engine when
        the aligned geometry holds — see __init__): forward spectra in
        XLA, correlation + |.|^2 in a VMEM pallas kernel
        (search/build_pallas.py) that writes the plane layout
        directly.  The output is [numz_pad, nb_pad*uselen]: pad z
        rows are zero (zero kernels) and padded blocks write zero
        columns, both handled by the scanner; the only post-op is a
        free reshape.  (The previous full-frame version lost ~290 ms
        to an XLA [off:off+uselen] relayout pass; kernel alone
        measured ~74 ms on the bench workload.)"""
        from presto_tpu.search import build_pallas as bp
        cfg, kern = self.cfg, self.kern
        fftlen, numz = kern.fftlen, kern.numz
        nblocks = g.nblocks
        uselen = cfg.uselen
        off_eff = g.hw_eff * ACCEL_NUMBETWEEN
        numz_pad = -(-numz // bp.ZT) * bp.ZT
        nb_pad = g.nb_pad
        assert nb_pad * uselen == g.plane_numr
        builder = bp.make_plane_builder(numz, nb_pad, fftlen, uselen,
                                        off_eff)
        consts = _dft_consts_np(fftlen)

        def build_body(fft_raw, kern_dev):
            fr = jax.lax.slice(frames_fn(fft_raw), (0, 0),
                               (nblocks, fftlen // 2))
            if cfg.norm == "median":
                fr = fr * _block_median_norms_c(fr)
            Sr, Si = _fwd_stage_mxu(
                fr, tuple(map(jnp.asarray, consts)), fftlen)
            bpad = ((0, nb_pad - nblocks), (0, 0), (0, 0))
            Sr, Si = jnp.pad(Sr, bpad), jnp.pad(Si, bpad)
            kz = _kern_bank_z(kern_dev, fftlen)
            Kr = jnp.pad(kz.real.astype(jnp.float32),
                         ((0, numz_pad - numz), (0, 0), (0, 0)))
            Ki = jnp.pad(kz.imag.astype(jnp.float32),
                         ((0, numz_pad - numz), (0, 0), (0, 0)))
            pw = builder(Sr, Si, Kr, Ki)
            # [numz_pad, nb_pad, uselen//128, 128] -> the plane, free
            return pw.reshape(numz_pad, nb_pad * uselen)
        return build_body

    # how many chunk bodies are unrolled for the concat assembly before
    # falling back to a scanned DUS carry (HLO size bound; planes that
    # big exceed single-chip HBM anyway and stream through oocfft)
    _UNROLL_CHUNKS = 48

    def _build_plan_ns(self):
        """Plane-build plan: unrolled per-chunk z-major slabs joined by
        ONE concatenate (the plane is written exactly once — both the
        stacked-ys moveaxis assembly (~350 ms) and a scanned
        dynamic_update_slice carry (~185 ms: XLA copies the carried
        plane each step) measured as the dominant cost of the round-2
        build on v5e).  Falls back to the DUS-carry scan when nsteps
        is too large to unroll."""
        g = self._plane_geom()
        if g is False:
            return None
        kern = self.kern
        if getattr(g, "build_body", None) is None:
            chunk_slab = self._chunk_slab_fn(g)
            plane_numr, col0, pads = g.plane_numr, g.col0, g.pads
            numz = kern.numz
            cw = g.chunk * self.cfg.uselen
            use_mxu = chunk_slab.use_mxu
            fftlen = kern.fftlen

            def prep_bank(kern_c):
                return _kern_bank_z(kern_c, fftlen) if use_mxu \
                    else kern_c

            frames_fn = self._frames_fn(g)
            chunk = g.chunk

            if ACCEL_ENGINE == "plb" and not g.hw_eff:
                print("accel: PRESTO_TPU_ACCEL_ENGINE=plb requested "
                      "but the aligned geometry does not hold "
                      "(explicit uselen or halfwidth too wide) — "
                      "using the default engine")
            if use_mxu and g.hw_eff:
                g.build_body = self._pallas_build_body(g, frames_fn)
                g.key = (g.chunk, g.nsteps, g.plane_numr, "plb")
                _note_engine(self.cfg.zmax, "build", "pallas")
                return g
            _note_engine(self.cfg.zmax, "build",
                         "xla-mxu" if use_mxu else "xla-fft")

            # the unrolled concat holds all slabs (~1x plane) PLUS the
            # concat output plane; when 2x plane + the chunk
            # intermediate would crowd HBM, stream through the 1x-plane
            # DUS carry instead (slower, but it fits)
            fits = (numz * (plane_numr + g.nsteps * cw) * 4
                    + CHUNK_BUDGET_BYTES) < (DEVICE_HBM_BYTES * 9) // 16

            if g.nsteps <= self._UNROLL_CHUNKS and fits:
                def build_body(fft_raw, kern_dev):
                    fr = frames_fn(fft_raw)
                    kern_use = prep_bank(kern_dev)
                    # optimization_barrier chain: unrolled chunks have
                    # no data deps between them, and XLA's scheduler
                    # will happily keep every chunk's multi-GB complex
                    # intermediates alive at once (OOM on v5e); the
                    # chain forces chunk i+1 to start after slab i
                    slabs = []
                    for i in range(g.nsteps):
                        data = jax.lax.slice(
                            fr, (i * chunk, 0),
                            ((i + 1) * chunk, fr.shape[1]))
                        slab = chunk_slab(data, kern_use)
                        if i + 1 < g.nsteps:
                            fr, slab = jax.lax.optimization_barrier(
                                (fr, slab))
                        slabs.append(slab)
                    # keep only REAL blocks' columns (a padded frame
                    # reads the spectrum tail + zero padding, so its
                    # ~zero median blows the normalization up — its
                    # output must never reach the plane), zero-fill
                    # the alignment padding, and write everything with
                    # one concatenate
                    keep = min(plane_numr - col0,
                               g.nblocks * self.cfg.uselen)
                    over = g.nsteps * cw - keep
                    if over > 0:
                        slabs[-1] = jax.lax.slice(
                            slabs[-1], (0, 0), (numz, cw - over))
                    parts = [jnp.zeros((numz, col0), jnp.float32)] \
                        if col0 else []
                    parts += slabs
                    right = plane_numr - col0 - sum(
                        s.shape[1] for s in slabs)
                    if right > 0:
                        parts.append(jnp.zeros((numz, right),
                                               jnp.float32))
                    return jnp.concatenate(parts, axis=1)
            else:
                # DUS-carry fallback: chunks of REAL blocks only, the
                # final chunk overlapping backwards (rewrites the same
                # values) so padded-frame output never lands in the
                # plane and every dispatch shares one shape
                bstarts = [min(i * chunk, g.nblocks - chunk)
                           for i in range(g.nsteps)]
                start_cols = np.asarray(
                    [col0 + b * self.cfg.uselen for b in bstarts],
                    np.int32)
                bstarts = np.asarray(bstarts, np.int32)

                def build_body(fft_raw, kern_dev):
                    fr = frames_fn(fft_raw)
                    kern_use = prep_bank(kern_dev)
                    pl = jnp.zeros((numz, plane_numr), jnp.float32)

                    def body(pl, xs):
                        b0, start_col = xs
                        data = jax.lax.dynamic_slice(
                            fr, (b0, 0), (chunk, fr.shape[1]))
                        slabv = chunk_slab(data, kern_use)
                        return jax.lax.dynamic_update_slice(
                            pl, slabv, (0, start_col)), None
                    pl, _ = jax.lax.scan(
                        body, pl, (jnp.asarray(bstarts),
                                   jnp.asarray(start_cols)))
                    return pl

            g.build_body = build_body
            g.key = (g.chunk, g.nsteps, g.plane_numr, use_mxu)
        return g

    # -- search --------------------------------------------------------

    def search(self, fft_pairs: np.ndarray,
               plane: Optional[np.ndarray] = None,
               slab: int = 1 << 20) -> List[AccelCand]:
        """Run the full staged harmonic-summing search.

        With cfg.wmax set this is the JERK search over the (r, z, w)
        volume of the ACCEL_DW w grid, built band by band with each
        subharmonic read from its own w plane (search/jerk.py).

        The plane stays resident in HBM; the search region is processed
        in `slab`-column accumulator slabs (peak extra memory ~
        numz*slab floats per gather), each slab thresholded+top-k'd per
        stage on device with candidates collected on host — bounding
        memory for arbitrarily long spectra.

        Returned candidates are PRE-COLLAPSED to at most one per ~8
        r-bins (the segment-max reduction; lossless w.r.t. the final
        list because remove_duplicates' ACCEL_CLOSEST_R=15-bin rule —
        insert_new_accelcand semantics — collapses anything closer
        anyway).  Library callers should not expect sub-segment
        multiplicity; apply remove_duplicates/eliminate_harmonics for
        the reference's final-list semantics.
        """
        cfg = self.cfg
        if plane is None and cfg.wmax:
            from presto_tpu.search import jerk
            return jerk.volume(self).search_many(fft_pairs[None])[0]
        if plane is None:
            cs = self._search_fused(fft_pairs, slab,
                                    self._kern_bank_dev())
            if cs is not None:
                return cs
            plane = self.build_plane(fft_pairs)
        return self._search_plane(plane, slab)

    def _collect_packed(self, packed, start_cols) -> List[AccelCand]:
        vals, cidx, zrow = _unpack_scan(packed)
        return self._dedup_sort(
            self._collect_group(vals, cidx, zrow, start_cols))

    def _search_fused(self, fft_pairs, slab: int,
                      kern_dev) -> Optional[List[AccelCand]]:
        """Plane build + staged search in ONE device dispatch (the
        plane never surfaces; saves a host<->device round trip).
        Returns None
        when there is no build plan (too-short spectra) — callers then
        take the two-dispatch path."""
        yp = self._build_plan_ns()
        if yp is None:
            return None
        splan = self._slab_plan(yp.plane_numr, slab)
        if splan is None:
            return []
        slab_, k, scanner, start_cols = splan
        key = ("fused",) + yp.key + (slab_, k)
        if key not in self._fn_cache:
            build_body, scan_body = yp.build_body, scanner.body

            @jax.jit
            def fused(fft_raw, kern_dev, scols):
                return scan_body(build_body(fft_raw, kern_dev), scols)
            self._fn_cache[key] = fused
        packed = self._fn_cache[key](
            self._to_dev(fft_pairs), kern_dev,
            jnp.asarray(start_cols, dtype=jnp.int32))
        return self._collect_packed(packed, start_cols)

    def _slab_plan(self, plane_numr: int, slab: int):
        """(slab, k, scanner, start_cols) for a plane width — the ONE
        source of the slab/top-k layout for single and batched paths
        (the overlap-last-slab trick keeps one jit shape)."""
        cfg = self.cfg
        r0 = int(self.rlo) * ACCEL_RDR
        self._r0min = r0          # candidates below rlo are filtered
        numr = min(int(self.rhi) * ACCEL_RDR, plane_numr) - r0
        if numr <= 0:
            return None
        top = r0 + numr
        self._rtop = top          # ... and at/above rhi (alignment
                                  # may scan a few columns past top)
        slab = min(slab, numr)
        # Alignment contract for the scanner's phase-decomposed
        # harmonic reads: every slab start (and the slab length) is a
        # multiple of numharm, so each subharmonic read is a static
        # strided view.  Aligning r0 down (and the top slab up, within
        # the align-padded plane) scans a few out-of-range columns,
        # filtered in _collect_slab via _r0min/_rtop.
        align = cfg.numharm
        # the pallas stage reducer (TPU) wants TILE-aligned starts
        # and a TILE-multiple slab; fall back to the XLA scanner when
        # the geometry is too small to align
        use_pallas = False
        ptile = None
        from presto_tpu.search import accel_pallas as ap
        decline = None
        if not ap.pallas_available():
            decline = "no TPU"
        elif cfg.numharm > 16:
            decline = "numharm > 16"
        elif plane_numr % ap.TILE:
            decline = "plane not TILE-aligned"
        else:
            # plane is aligned to the MAX tile, so any smaller
            # power-of-two tile the VMEM budget picks also divides it
            fz_probe = _harm_fracs_and_zinds(cfg, self.cfg.numz)
            ptile = ap.pick_tile(fz_probe, self.cfg.numz, slab)
            if not ptile:
                decline = "no tile fits VMEM"
        if ptile:
            # tuned engine choice: a measured harmonic_sum_layout
            # entry may prefer the XLA staged scan for this
            # geometry (candidate lists are engine-identical, so
            # this is performance-only)
            from presto_tpu import tune
            if tune.enabled():
                lay = tune.best(
                    "harmonic_sum_layout",
                    tune.key_harm_layout(self.cfg.numz,
                                         cfg.numharm))
                if lay and lay.get("engine") == "xla":
                    ptile = None
                    decline = "tuned to xla"
        if ptile:
            align = max(align, ptile)
            use_pallas = True
        aligned = (slab % align == 0 or slab > 4 * align) \
            and plane_numr % align == 0
        if aligned and slab % align:
            slab -= slab % align
        if use_pallas and not (aligned and slab % align == 0):
            use_pallas = False
            decline = "slab not aligned"
        r0a = r0 - (r0 % align) if aligned else r0
        top_a = min(top + ((-top) % align), plane_numr) if aligned \
            else top
        k = min(cfg.max_cands_per_stage, slab)
        # a direct-plane build already carries the reducer's row pad
        # and >= PLANE_PAD trailing zero columns: skip the 3.4 GB pad
        plane_padded = bool(
            use_pallas and self._plb_hw_eff
            and plane_numr >= top_a + ap.PLANE_PAD)
        skey = ("scan", slab, k, plane_numr, aligned, use_pallas,
                plane_padded)
        if skey not in self._fn_cache:
            fz = _harm_fracs_and_zinds(cfg, self.cfg.numz)
            reducer = None
            if use_pallas:
                reducer = ap.make_stage_reducer(
                    cfg.numharmstages, fz, slab, self.cfg.numz,
                    plane_numr, tile=ptile)
            _note_engine(cfg.zmax, "scan", "pallas" if use_pallas
                         else "xla: " + decline)
            self._fn_cache[skey] = _make_search_scanner(
                cfg.numharmstages, fz, self.powcut, slab, k,
                plane_numr, aligned=aligned,
                pallas_reducer=reducer, numz=self.cfg.numz,
                plane_padded=plane_padded)
        start_cols = []
        off = r0a
        while True:
            if off + slab >= top_a:             # keep one jit shape:
                start_cols.append(max(top_a - slab, 0))  # overlap last
                break
            start_cols.append(off)
            off += slab
        return slab, k, self._fn_cache[skey], start_cols

    def _search_plane(self, plane, slab: int) -> List[AccelCand]:
        # top-k cost grows steeply with k on TPU: keep k fixed and
        # scale the number of slabs instead (per-slab top-k truncates
        # only the weakest noise candidates)
        numz, plane_numr = plane.shape
        plan = self._slab_plan(plane_numr, slab)
        if plan is None:
            return []
        slab, k, scanner, start_cols = plan
        dplane = jnp.asarray(plane)
        packed = scanner(dplane, jnp.asarray(start_cols,
                                             dtype=jnp.int32))
        return self._collect_packed(packed, start_cols)

    @staticmethod
    def _dedup_sort(cands: List[AccelCand]) -> List[AccelCand]:
        # overlapping the final slab can duplicate candidates: dedup on
        # exact (numharm, r, z)
        seen = set()
        uniq = []
        for c in cands:
            key = (c.numharm, c.r, c.z)
            if key not in seen:
                seen.add(key)
                uniq.append(c)
        return sorted(uniq, key=lambda c: (-c.sigma, c.r))

    def search_many(self, pairs_batch: np.ndarray,
                    slab: int = 1 << 20,
                    compact_m: int = COMPACT_CANDS,
                    mesh=None, obs=None) -> List[List[AccelCand]]:
        """Batched search over many same-length spectra — the survey's
        DM fan-out (one plane build + one scanned search dispatch per
        memory-budgeted DM group instead of per-trial dispatch storms;
        the mpiprepsubband-scale path of SURVEY §2.5).

        pairs_batch: [numdms, numbins, 2] float32 — a NumPy array or a
        DEVICE array (jax.Array): the survey's fused realfft->search
        path keeps spectra resident in HBM, skipping a host download +
        re-upload per DM trial).  Returns per-DM candidate lists
        (same semantics as search() per spectrum).

        ``mesh``: a jax Mesh whose first axis shards the DM trials —
        the sharded seam's per-device spectra search in place via
        parallel/sharded.sharded_accel_search_many (candidate lists
        are test-pinned equal to this method's); None keeps the
        single-device grouped path.

        ``obs``: an Observability handle or None — when enabled, the
        scan program's per-dispatch FLOP/byte unit cost is harvested
        once per geometry (obs/costmodel.probe, kind "accel_search") so
        the survey's dispatch accounting carries silicon cost, and each
        group's host sync (the wait on its build and scan, then the
        candidate decode) is an ``accel:collect`` span.
        """
        cfg = self.cfg
        if mesh is not None and len(list(mesh.devices.flat)) > 1:
            from presto_tpu.parallel.sharded import (
                sharded_accel_search_many)
            return sharded_accel_search_many(self, pairs_batch, mesh,
                                             slab=slab,
                                             compact_m=compact_m,
                                             obs=obs)
        if isinstance(pairs_batch, jax.Array):
            batch = pairs_batch
            if batch.dtype != jnp.float32:    # same boundary cast the
                batch = batch.astype(jnp.float32)   # NumPy path gets
        else:
            batch = np.ascontiguousarray(np.asarray(pairs_batch,
                                                    np.float32))
        nd = batch.shape[0]
        if nd == 0:
            return []
        if cfg.wmax:
            # the banded (r, z, w) volume, a chunk's trials at a time
            from presto_tpu.search import jerk
            return jerk.volume(self).search_many(batch, obs=obs)
        # the plane geometry comes from the build program's shape
        # alone: every DM (the first included) runs through the
        # grouped build+scan programs, so a batch compiles those two
        # and no single-spectrum pair besides
        if not self._plan_blocks():
            return [[] for _ in range(nd)]
        kern_dev = self._kern_bank_dev()
        yp = self._build_plan_ns()
        key = ("build",) + yp.key
        self._build_plan = key
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.jit(yp.build_body)
        build_one = self._fn_cache[key]
        p0 = jax.eval_shape(build_one, jax.ShapeDtypeStruct(
            batch.shape[1:], jnp.float32), kern_dev)
        numz, plane_numr = p0.shape
        if plane_numr == 0:
            return [[] for _ in range(nd)]
        mkey = ("build_many",) + key[1:]
        if mkey not in self._fn_cache:
            if "plb" in key:
                # pallas_call + vmap is unsupported; sequential map is
                # fine (each build saturates the chip on its own).  The
                # named function names the program in device traces.
                def build_planes(batch, kd):
                    return jax.lax.map(lambda b: build_one(b, kd), batch)

                self._fn_cache[mkey] = jax.jit(build_planes)
            else:
                self._fn_cache[mkey] = jax.jit(
                    jax.vmap(build_one, in_axes=(0, None)))
        build_many = self._fn_cache[mkey]

        splan = self._slab_plan(plane_numr, slab)
        if splan is None:
            return [[] for _ in range(nd)]
        slab, k, scanner, start_cols = splan
        scols = jnp.asarray(start_cols, dtype=jnp.int32)
        if obs is not None:
            from presto_tpu.obs import costmodel
            costmodel.probe(obs, "accel_search", scanner, p0, scols)

        def collect_dm(vals, cidx, zrow):
            return self._dedup_sort(
                self._collect_group(vals, cidx, zrow, start_cols))

        out: List[List[AccelCand]] = []
        # per-spectrum footprint in the vmapped build: plane + stacked
        # ys + the [chunk, numz, fftlen] complex FFT intermediate
        # (vmap multiplies ALL of them by the group size).  The group
        # budget is HALF the old 6 GB because up to TWO groups are now
        # in flight (the window below) — same peak residency.
        g = self._plane_geom()
        plane_bytes = numz * plane_numr * 4
        per_bytes = plane_bytes * 2 + (
            g.chunk * numz * self.kern.fftlen * 8 if g else 0)
        group = max(1, int(3 * 2 ** 30 // max(per_bytes, 1)))
        group = min(group, nd)
        # back-overlap the final group so every dispatch shares ONE jit
        # shape (the tail would otherwise retrace the two heaviest
        # compiled programs); overlapped DMs are recomputed and their
        # duplicate results skipped
        starts = list(range(0, nd, group))
        if starts[-1] + group > nd:
            starts[-1] = nd - group
        done = 0

        def collect_group(ent):
            """The host sync for one dispatched group."""
            nonlocal done
            g0, planes, comp_dev = ent
            with maybe_span(obs, "accel:collect"):
                comp = np.asarray(comp_dev)
                dense = None
                for d in range(comp.shape[0]):
                    if g0 + d < done:
                        continue           # overlap: already collected
                    try:
                        cands = self.collect_compacted(
                            comp[d], start_cols, requested_m=compact_m)
                    except ValueError:
                        if dense is None:
                            dense = _unpack_scan(
                                scanner.many(planes, scols))
                        vals, cidx, zrow = dense
                        cands = collect_dm(vals[d], cidx[d], zrow[d])
                    out.append(cands)
                    done = g0 + d + 1

        # 2-deep in-flight window (the jerk ladder's pattern, see
        # pipeline/fusion.InflightWindow): group i+1's build+scan is
        # queued on the device before group i's host collection syncs,
        # so candidate decoding overlaps device work instead of
        # paying the dispatch+sync floor once per group.
        # `planes` rides in the window entry because the pathological
        # dense fallback needs it alive until its group is collected.
        pend: list = []
        for g0 in starts:
            sub = jnp.asarray(batch[g0:g0 + group])
            planes = build_many(sub, self._kern_dev)
            # per-trial top-m compaction rides the scan dispatch: the
            # dense top-k tensor stays on device (compact_m slots per
            # trial cross instead — the D2H that dominated slow-link
            # surveys).  A trial overflowing the budget (pathological
            # RFI forest) falls back to the lossless dense fetch for
            # its group.
            pend.append((g0, planes,
                         scanner.many_compact(planes, scols,
                                              compact_m)))
            if len(pend) >= 2:
                collect_group(pend.pop(0))
        while pend:
            collect_group(pend.pop(0))
        return out

    def _collect_group(self, vals: np.ndarray, cidx: np.ndarray,
                       zrow: np.ndarray, start_cols) -> List[AccelCand]:
        """Vectorized host collection over [nslabs, stages, k] scanner
        output: one numpy pass for the bounds filtering and one
        batched candidate_sigma per stage, instead of a Python loop
        per (slab, stage) — the survey e2e share collects thousands of
        slabs and was host-bound on the loop (VERDICT r4 weak #1).
        Parity: search_ffdotpows (accel_utils.c:1259-1298); each
        column contributes its max-over-z cell (same-column lower-z
        cells are duplicates under the sifter's r-dedup).  Same math
        and candidate order-class as the historical per-slab loop
        (exact float op order preserved); callers dedup/sort."""
        cfg = self.cfg
        r0min = getattr(self, "_r0min", 0)
        rtop = getattr(self, "_rtop", None)
        sc = np.asarray(start_cols, dtype=np.int64)[:, None, None]
        absc = sc + cidx
        good = (vals > 0.0) & (zrow < cfg.numz)  # pad rows are zeros
        good &= absc >= r0min     # alignment searched below rlo ...
        if rtop is not None:      # ... or a few columns past rhi
            good &= absc < rtop
        stg = np.broadcast_to(
            np.arange(vals.shape[1], dtype=np.int32)[None, :, None],
            vals.shape)
        g = good.ravel()
        return self._cands_from_flat(
            vals.ravel()[g], absc.ravel()[g], zrow.ravel()[g],
            stg.ravel()[g])

    def collect_compacted(self, comp: np.ndarray, start_cols,
                          requested_m: int = None,
                          allow_truncated: bool = False
                          ) -> List[AccelCand]:
        """Host decode of compact_scan_packed output [3, m] -> the
        same candidate list _collect_packed builds from the dense
        tensor (bounds filter + sigma + dedup/sort).

        requested_m: the m the producer passed to
        compact_scan_packed, if known — an output NARROWER than the
        request means m was clamped to the dense tensor's full slot
        count (truncation impossible), so an all-positive output is
        legitimate and the budget guard is skipped.

        allow_truncated: decode a budget-exhausted output anyway
        (keeping the strongest m candidates) instead of raising —
        ONLY for consumers that explicitly tolerate a truncated tail
        (e.g. timing replays of recorded outputs where the canonical
        results came from a lossless path)."""
        cfg = self.cfg
        assert cfg.numz < (1 << _CMP_ZBITS), cfg.numz
        comp = np.asarray(comp)
        v = comp[0].view(np.float32)
        if (v.size and v[-1] > 0.0 and not allow_truncated
                and (requested_m is None or v.size >= requested_m)):
            raise ValueError(
                "compact_scan_packed budget exhausted (m=%d slots all "
                "positive): candidates may have been dropped — raise m"
                % v.size)
        cidx = comp[1]
        zrow = comp[2] & ((1 << _CMP_ZBITS) - 1)
        stg = (comp[2] >> _CMP_ZBITS) & ((1 << _CMP_SBITS) - 1)
        si = comp[2] >> (_CMP_ZBITS + _CMP_SBITS)
        absc = np.asarray(start_cols, dtype=np.int64)[si] + cidx
        r0min = getattr(self, "_r0min", 0)
        rtop = getattr(self, "_rtop", None)
        good = (v > 0.0) & (zrow < cfg.numz) & (absc >= r0min)
        if rtop is not None:
            good &= absc < rtop
        return self._dedup_sort(self._cands_from_flat(
            v[good], absc[good], zrow[good], stg[good]))

    def _cands_from_flat(self, v: np.ndarray, absc: np.ndarray,
                         zrow: np.ndarray,
                         stg: np.ndarray) -> List[AccelCand]:
        """Filtered flat hits -> AccelCands, sigma batched per stage.
        Float op order matches the historical per-slab loop:
        (col * ACCEL_DR) / numharm and (-zmax + z * ACCEL_DZ) /
        numharm in float64."""
        cfg = self.cfg
        out: List[AccelCand] = []
        for stage in np.unique(stg).tolist():
            m = stg == stage
            numharm = 1 << int(stage)
            sigmas = np.atleast_1d(st.candidate_sigma(
                v[m], numharm, self.numindep[stage]))
            rr = (absc[m] * ACCEL_DR) / numharm
            zz = (-cfg.zmax + zrow[m] * ACCEL_DZ) / numharm
            for p, s, r_, z_ in zip(v[m].tolist(), sigmas.tolist(),
                                    rr.tolist(), zz.tolist()):
                out.append(AccelCand(power=p, sigma=s,
                                     numharm=numharm, r=r_, z=z_))
        return out


# ----------------------------------------------------------------------
# Candidate post-processing (host)
# ----------------------------------------------------------------------

# The reference's fixed list of "other common harmonic ratios"
# (accel_utils.c:415-439) in addition to r*ii and r/ii, ii = 1..16.
_HARM_RATIOS = [3 / 2, 5 / 2, 2 / 3, 4 / 3, 5 / 3, 3 / 4, 5 / 4, 2 / 5,
                3 / 5, 4 / 5, 5 / 6, 2 / 7, 3 / 7, 4 / 7, 3 / 8, 5 / 8,
                2 / 9, 3 / 10, 2 / 11, 3 / 11, 2 / 13, 3 / 13, 2 / 15]


def eliminate_harmonics(cands: List[AccelCand],
                        tooclose: float = 1.5,
                        maxharm: int = 16) -> List[AccelCand]:
    """Remove less-significant harmonically-related candidates.

    Parity: eliminate_harmonics (accel_utils.c:384-460): walking the
    sigma-sorted list, a later candidate is dropped when its r lies
    within `tooclose` bins of r_strong*ii, r_strong/ii (ii<=16), or
    r_strong*ratio for the fixed rational-ratio list.
    """
    if not cands:
        return []
    cands = sorted(cands, key=lambda c: (-c.sigma, c.r))
    kept: List[AccelCand] = []
    for c in cands:
        is_harm = False
        for k in kept:
            rk, rc = k.r, c.r
            if any(abs(rk / ii - rc) < tooclose or
                   abs(rk * ii - rc) < tooclose
                   for ii in range(1, maxharm + 1)):
                is_harm = True
            elif any(abs(rk * ratio - rc) < tooclose
                     for ratio in _HARM_RATIOS):
                is_harm = True
            if is_harm:
                break
        if not is_harm:
            kept.append(c)
    return kept


def remove_duplicates(cands: List[AccelCand]) -> List[AccelCand]:
    """Collapse candidates within ACCEL_CLOSEST_R bins of a stronger one
    to the strongest, regardless of z — the exact dedup rule of
    insert_new_accelcand (accel_utils.c:294-382), which keys on r alone.
    This also makes the device search's per-column max-over-z reduction
    lossless with respect to the final candidate list."""
    kept: List[AccelCand] = []
    for c in sorted(cands, key=lambda c: (-c.sigma, c.r)):
        if all(abs(c.r - k.r) >= ACCEL_CLOSEST_R for k in kept):
            kept.append(c)
    return kept
