"""Pallas TPU kernel for the accelsearch harmonic-sum stage scan.

The staged harmonic summing (SURVEY §7.2 step 9a: "Pallas kernels for
the harmonic-sum gather") is HBM-bandwidth-bound in the XLA
formulation: every subharmonic add materializes plane-sized
intermediates (z-permuted copy, phase-stacked copy, accumulator
update).  This kernel keeps one column tile of the accumulator in
VMEM, DMAs exactly the source window each harmonic needs from the
HBM-resident plane (only the z rows the term's zinds map can touch —
~frac*numz of them), applies the fractional-stride column mapping as
single-vreg lane gathers (tpu.dynamic_gather, decomposed over 128-lane
source/output chunks; the dynamic DMA-alignment residual folds into
the gather indices, so no vector rolls at all), applies the z-row
mapping as ONE exact bf16x3 one-hot matmul (hi/mid/lo split of the
f32 values stacked along the contraction — each output element is a
single selected bf16 triplet, reconstructing the float32 bit-for-bit
at full-bf16 MXU rate instead of a 6-pass HIGHEST f32 matmul), and
reduces each stage to per-column (max over z, argmax) on the spot —
the only HBM writes are the [stages, slab] reduction outputs.

v1 of this kernel (one fixed-size window per term + pltpu.roll + two
HIGHEST-precision one-hot matmuls) measured 336 ms on the bench
workload; the selection matmuls were ~200 ms of it and the
DMA+collect floor 135 ms.  v2 cuts both: ~45% less DMA (row-shrunk
windows), no rolls, and ~3x cheaper exact selection.

Thresholding / segment-max / top-k stay in XLA outside the kernel
(they operate on the reduced [stages, slab] arrays, which are cheap).

Alignment contract (enforced by the caller): slab starts and the slab
length are multiples of TILE, so every tile start j0 is divisible by
every htot <= 16; DMA starts are floored to 128-lane multiples with
the residual added to the gather indices.  The plane must be padded
to ceil(numz/8)*8 rows and carry >= PLANE_PAD columns of zero padding
at the right edge so subharmonic window DMAs never run off the array
(search/accel.py's _scan_pallas_py applies both pads).

Hardware notes (discovered building v1/v2): grid-pipelined manual
DMAs into one scratch get reordered across grid steps (hence the
per-term x2-parity window banks); tpu.dynamic_gather handles ONE
source vreg along the gathered dim, so lane gathers decompose into
128-lane chunks combined with predicated selects.
"""

from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp

TILE = 1024                  # columns per grid tile (lanes): fewer
                             # per-tile DMAs/collects win — 256/512/
                             # 1024 measured 194/164/151 ms on the
                             # bench workload (VMEM bounds going
                             # further)
PLANE_PAD = 1152             # right-edge zero padding the plane needs
                             # (largest per-term DMA window)


def _stage_terms(fracs_zinds):
    """Flatten the per-stage (harm, htot, zinds) lists, keeping the
    stage boundaries: returns (terms, stage_term_counts)."""
    terms = []
    counts = []
    for stage in fracs_zinds:
        counts.append(len(stage))
        for harm, htot, zinds in stage:
            terms.append((harm, htot, np.asarray(zinds)))
    return terms, counts


def _term_geom(harm: int, htot: int, zinds: np.ndarray,
               tile: int = None):
    """Static per-term window geometry: rows the zinds map can touch
    (8-padded) and the 128-multiple DMA window width covering the
    column map's span from any 128-aligned floor.  The residual
    off = ((j0//htot)*harm) % 128 is a multiple of (TILE*harm/htot)
    mod 128 — at TILE=1024 only {0, 64}, but the sizing keeps the
    worst case over ANY TILE >= 128 (112, reached at TILE=256 for
    htot=16; an earlier 96-based window undersized that term by one
    lane chunk and silently zeroed 8 of every 2048 columns)."""
    tile = tile or TILE
    rows = -(-(int(zinds.max()) + 1) // 8) * 8
    cspan = ((tile - 1) * harm + (htot >> 1)) // htot + 2
    win = -(-(112 + cspan) // 128) * 128
    return rows, win


def make_stage_reducer(numharmstages, fracs_zinds, slab: int,
                       numz: int, plane_numr: int,
                       interpret: bool = False, tile: int = None,
                       shifted: bool = False):
    """Build the pallas stage reducer.

    Returns f(P, start_cols) -> (colmax f32, colz i32), each
    [nslabs, numharmstages, slab]: per search column, the max over z
    of the stage-summed powers and its z row — the kernel half of the
    staged search (thresholding/top-k are done by the caller).

    Requires slab % tile == 0, start_cols % tile == 0, and P padded
    to ceil(numz/8)*8 rows (zero rows below; `pad_rows` below).

    ``shifted`` (the banded jerk volume, search/jerk.py): f(P, subs,
    start_cols, shifts) reads harmonic term fi from its own plane
    subs[fi].  Plane columns are absolute columns less an origin:
    shifts[0] for P, shifts[1 + fi] for subs[fi] (multiples of 128);
    then start_cols + shifts[0] must be multiples of tile, and each
    subs[fi] must hold its term's source columns plus PLANE_PAD.

    `tile` (default TILE) is threaded explicitly through the whole
    build — module state is never consulted or mutated, so concurrent
    plans with different tiles cannot race.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = int(tile or TILE)
    if tile < 128 or tile % 128 or slab % tile:
        raise ValueError("tile must be a 128-multiple dividing the "
                         "slab (tile=%d, slab=%d)" % (tile, slab))
    terms, counts = _stage_terms(fracs_zinds)
    nterms = len(terms)
    ntiles = slab // tile
    nstages = numharmstages
    numz_pad = -(-numz // 8) * 8
    geom = [_term_geom(h, t, zi, tile) for (h, t, zi) in terms]

    # bf16x3 stacked one-hot z-permutation: oh3[t] is [numz_pad,
    # 3*rows] with the same one-hot block repeated for the hi/mid/lo
    # value planes — (oh3 @ [hi;mid;lo]) selects and reconstructs each
    # float32 exactly in ONE bf16 matmul (see module docstring)
    onehots = []
    for i, (_h, _t, zinds) in enumerate(terms):
        rows = geom[i][0]
        oh = np.zeros((numz_pad, rows), np.float32)
        oh[np.arange(numz), zinds] = 1.0
        onehots.append(jnp.asarray(
            np.concatenate([oh, oh, oh], axis=1).astype(jnp.bfloat16)))

    def kernel(start_cols_ref, *refs):
        if shifted:
            shifts_ref, P_ref = refs[0], refs[1]
            Q_refs = refs[2:2 + nterms]
            refs = refs[2 + nterms:]
        else:
            P_ref = refs[0]
            refs = refs[1:]
        oh_refs = refs[:nterms]
        colmax_ref, colz_ref = refs[nterms], refs[nterms + 1]
        acc_ref = refs[nterms + 2]
        win_refs = refs[nterms + 3:nterms + 3 + (1 + nterms)]
        sems = refs[-1]

        s = pl.program_id(0)
        t = pl.program_id(1)
        j0 = start_cols_ref[s] + t * tile

        # x2 grid-step parity banks: Mosaic pipelines grid iterations,
        # so the next step's DMAs race this step's reads unless they
        # land in the other bank; the fan-out also overlaps fetches
        # with compute.
        bank = (s * ntiles + t) % 2

        def fund_dma():
            return pltpu.make_async_copy(
                P_ref.at[:, pl.ds(pl.multiple_of(j0, 128), tile)],
                win_refs[0].at[bank], sems.at[0, bank])

        def term_dma(fi):
            harm, htot, _z = terms[fi]
            rows, win = geom[fi]
            if shifted:
                cs = ((j0 + shifts_ref[0]) // htot) * harm \
                    - shifts_ref[1 + fi]
                src = Q_refs[fi]
            else:
                cs = (j0 // htot) * harm
                src = P_ref
            off = cs % 128
            return pltpu.make_async_copy(
                src.at[pl.ds(0, rows),
                         pl.ds(pl.multiple_of(cs - off, 128), win)],
                win_refs[1 + fi].at[bank], sems.at[1 + fi, bank]), off

        fund_dma().start()
        for fi in range(nterms):
            term_dma(fi)[0].start()

        fund_dma().wait()
        acc_ref[:, :] = win_refs[0][bank]

        def collect(stage):
            a = acc_ref[:, :]
            m = jnp.max(a, axis=0)
            iota = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
            z = jnp.min(jnp.where(a == m[None, :], iota, numz_pad),
                        axis=0).astype(jnp.int32)
            colmax_ref[0, stage, :] = m
            colz_ref[0, stage, :] = z

        collect(0)
        fi = 0
        for stage in range(1, nstages):
            for _ in range(counts[stage - 1]):
                harm, htot, _z = terms[fi]
                rows, win = geom[fi]
                dma, off = term_dma(fi)
                dma.wait()
                src = win_refs[1 + fi][bank]      # [rows, win]
                # fractional-stride column map as chunked lane
                # gathers; the DMA-floor residual `off` rides in the
                # indices (no roll)
                sel_cols = []
                nchunks = win // 128
                for c2 in range(tile // 128):
                    jj = jax.lax.broadcasted_iota(
                        jnp.int32, (rows, 128), 1) + c2 * 128
                    idx = off + (jj * harm + (htot >> 1)) // htot
                    out = jnp.zeros((rows, 128), jnp.float32)
                    for c in range(nchunks):
                        g = jnp.take_along_axis(
                            src[:, c * 128:(c + 1) * 128],
                            jnp.clip(idx - c * 128, 0, 127), axis=1)
                        out = jnp.where(idx // 128 == c, g, out)
                    sel_cols.append(out)
                sel = jnp.concatenate(sel_cols, axis=1)  # [rows, tile]
                # exact bf16x3 split: hi+mid+lo == x bit-for-bit
                hi = sel.astype(jnp.bfloat16)
                r1 = sel - hi.astype(jnp.float32)
                mid = r1.astype(jnp.bfloat16)
                lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
                stacked = jnp.concatenate([hi, mid, lo], axis=0)
                add = jax.lax.dot_general(
                    oh_refs[fi][...], stacked,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_ref[:, :] = acc_ref[:, :] + add
                fi += 1
            collect(stage)

    nplanes = 1 + nterms if shifted else 1

    def call(P, start_cols, subs=(), shifts=None):
        nslabs = start_cols.shape[0]
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 if shifted else 1,
            grid=(nslabs, ntiles),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nplanes +
                     [pl.BlockSpec(memory_space=pltpu.VMEM)] * nterms,
            out_specs=[
                pl.BlockSpec((1, nstages, tile),
                             lambda s, t, *_: (s, 0, t)),
                pl.BlockSpec((1, nstages, tile),
                             lambda s, t, *_: (s, 0, t)),
            ],
            scratch_shapes=[
                pltpu.VMEM((numz_pad, tile), jnp.float32),       # acc
                pltpu.VMEM((2, numz_pad, tile), jnp.float32),    # fund
            ] + [
                pltpu.VMEM((2, geom[i][0], geom[i][1]), jnp.float32)
                for i in range(nterms)
            ] + [
                pltpu.SemaphoreType.DMA((1 + nterms, 2)),
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=gs,
            out_shape=[
                jax.ShapeDtypeStruct((nslabs, nstages, slab),
                                     jnp.float32),
                jax.ShapeDtypeStruct((nslabs, nstages, slab),
                                     jnp.int32),
            ],
            interpret=interpret,
        )(*((start_cols, shifts, P) + tuple(subs) if shifted
            else (start_cols, P)), *onehots)

    if shifted:
        @jax.jit
        def reduce_shifted(P, subs, start_cols, shifts):
            return call(P, start_cols, subs, shifts)
        return reduce_shifted

    @jax.jit
    def reduce_stages(P, start_cols):
        return call(P, start_cols)

    return reduce_stages


def pad_rows(numz: int) -> int:
    """Rows the kernel-ready plane must have (8-sublane tiling)."""
    return -(-numz // 8) * 8


def scratch_bytes(fracs_zinds, numz: int, tile: int = None) -> int:
    """Static VMEM scratch estimate for make_stage_reducer (acc + the
    x2-parity window banks + the bf16 one-hot inputs) — callers gate
    on this instead of discovering a Mosaic scratch-allocation error
    at dispatch time (scratch scales with TILE and numz)."""
    tile = tile or TILE
    terms, _ = _stage_terms(fracs_zinds)
    numz_pad = pad_rows(numz)
    total = numz_pad * tile * 4                 # acc
    total += 2 * numz_pad * tile * 4            # fundamental banks
    for (h, t, zi) in terms:
        rows, win = _term_geom(h, t, zi, tile)
        total += 2 * rows * win * 4             # term window banks
        total += numz_pad * 3 * rows * 2        # oh3 (bf16, VMEM in)
    return total


# the TPU's scoped-vmem stack limit is 16 MB (measured: a 19.6 MB
# scratch set fails kernel compile); leave spill headroom
VMEM_BUDGET = 14 * 2 ** 20


def _tile_ok(fracs_zinds, numz: int, slab: int, t: int) -> bool:
    return (128 <= t <= slab and t % 128 == 0 and slab % t == 0
            and scratch_bytes(fracs_zinds, numz, t) <= VMEM_BUDGET)


def pick_tile(fracs_zinds, numz: int, slab: int):
    """The column tile for this kernel geometry.

    When tuning is active (SurveyConfig.tune / PRESTO_TPU_TUNE=1) a
    measured tile from the tuning DB wins, provided it still honors
    the alignment and scoped-VMEM contracts — a stale DB entry (new
    kernel source changes the fingerprint, but defend anyway) can
    degrade performance, never correctness.  Otherwise: the largest
    default tile whose scratch fits the budget (None when even the
    smallest doesn't — caller falls back to XLA)."""
    from presto_tpu import tune
    if tune.enabled():
        numharm = 1 << len(fracs_zinds)
        cfg = tune.best("accel_pallas_tile",
                        tune.key_accel_tile(numz, numharm, slab))
        if cfg:
            try:
                t = int(cfg.get("tile", 0))
            except (TypeError, ValueError):
                t = 0
            if _tile_ok(fracs_zinds, numz, slab, t):
                return t
    for t in (TILE, 512, 256):
        if _tile_ok(fracs_zinds, numz, slab, t):
            return t
    return None


def pallas_available() -> bool:
    """True when the default jax backend can run the TPU kernel."""
    return jax.devices()[0].platform == "tpu"
