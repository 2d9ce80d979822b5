"""Pallas harmonic-sum stage reducer vs a direct numpy reference.

Runs the kernel in interpreter mode (no TPU needed); the numbers must
match the staged-sum semantics of search/accel exactly.
"""

import numpy as np
import pytest

from presto_tpu.search.accel import (ACCEL_DZ, _harm_fracs_and_zinds,
                                     AccelConfig)
from presto_tpu.search.accel_pallas import (PLANE_PAD, TILE,
                                            make_stage_reducer,
                                            pad_rows)


def _numpy_stage_reduce(P, start_cols, slab, fracs_zinds, nstages):
    """Direct (slow) reference: staged sums + per-column max/argmax."""
    numz, R = P.shape
    nslabs = len(start_cols)
    colmax = np.zeros((nslabs, nstages, slab), np.float32)
    colz = np.zeros((nslabs, nstages, slab), np.int32)
    for si, s0 in enumerate(start_cols):
        cols = s0 + np.arange(slab)
        acc = P[:, cols].copy()
        colmax[si, 0] = acc.max(0)
        colz[si, 0] = acc.argmax(0)
        for stage in range(1, nstages):
            for harm, htot, zinds in fracs_zinds[stage - 1]:
                rind = ((cols // htot) * harm
                        + ((cols % htot) * harm + (htot >> 1)) // htot)
                acc += P[np.asarray(zinds)[:, None],
                         rind[None, :]]
            colmax[si, stage] = acc.max(0)
            colz[si, stage] = acc.argmax(0)
    return colmax, colz


@pytest.mark.parametrize("numharm", [4, 8, 16])
def test_pallas_reducer_matches_numpy(numharm):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    cfg = AccelConfig(zmax=20, numharm=numharm)
    numz = cfg.numz                      # 21
    nstages = cfg.numharmstages
    slab = 2 * TILE
    # slabs at several TILE-aligned starts: at TILE=1024 the htot=16
    # DMA-floor residual takes its full reachable set {0, 64} (the
    # historical off=112 undersize case is unreachable at this TILE;
    # _term_geom sizes for the worst case over any TILE >= 128)
    R = 10 * TILE + PLANE_PAD
    P = rng.random((numz, R)).astype(np.float32)
    P[:, -PLANE_PAD:] = 0.0              # the padding contract
    start_cols = np.asarray([0, TILE, 2 * TILE, 7 * TILE], np.int32)

    fz = _harm_fracs_and_zinds(cfg, numz)
    reducer = make_stage_reducer(nstages, fz, slab, numz, R,
                                 interpret=True)
    Ppad = np.pad(P, ((0, pad_rows(numz) - numz), (0, 0)))
    got_max, got_z = (np.asarray(a) for a in
                      reducer(jnp.asarray(Ppad),
                              jnp.asarray(start_cols)))
    want_max, want_z = _numpy_stage_reduce(P, start_cols, slab, fz,
                                           nstages)
    np.testing.assert_allclose(got_max, want_max, rtol=1e-6)
    np.testing.assert_array_equal(got_z, want_z)


@pytest.mark.parametrize("numharm", [4, 8])
def test_shifted_reducer_reads_each_term_from_its_own_plane(numharm):
    """The banded jerk volume's form (search/jerk.py): harmonic term
    fi read from its own plane subs[fi], every plane's columns being
    absolute columns less its origin."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    cfg = AccelConfig(zmax=20, numharm=numharm)
    numz, nstages = cfg.numz, cfg.numharmstages
    fz = _harm_fracs_and_zinds(cfg, numz)
    terms = [(h, t, np.asarray(zi)) for st in fz for (h, t, zi) in st]
    slab, p0 = 2 * TILE, 37 * TILE          # absolute start of the slab
    o0 = p0 - 3 * 128                       # the fundamental's origin
    rows = pad_rows(numz)
    P = rng.random((rows, slab + 3 * 128)).astype(np.float32)
    P[numz:] = 0.0
    subs, origins = [], [o0]
    for h, t, _zi in terms:
        lo = (p0 * h // t) // 256 * 256 - 128   # 128-multiple origin
        Q = rng.random((rows, slab * h // t + 384 + PLANE_PAD)
                       ).astype(np.float32)
        Q[numz:] = 0.0
        subs.append(Q)
        origins.append(lo)
    reducer = make_stage_reducer(nstages, fz, slab, numz, 0,
                                 interpret=True, shifted=True)
    got_max, got_z = (np.asarray(a) for a in reducer(
        jnp.asarray(P), tuple(jnp.asarray(q) for q in subs),
        jnp.asarray([p0 - o0], np.int32),
        jnp.asarray(origins, np.int32)))
    cols = p0 + np.arange(slab)
    acc = P[:numz, cols - o0].astype(np.float64)
    want_max = [acc.max(0)]
    want_z = [acc.argmax(0)]
    fi = 0
    for stage in range(1, nstages):
        for _ in range(1 << (stage - 1)):
            h, t, zi = terms[fi]
            src = (cols // t) * h + ((cols % t) * h + (t >> 1)) // t
            acc = acc + subs[fi][zi[:, None], src[None] - origins[1 + fi]]
            fi += 1
        want_max.append(acc.max(0))
        want_z.append(acc.argmax(0))
    np.testing.assert_allclose(got_max[0], np.stack(want_max), rtol=1e-6)
    np.testing.assert_array_equal(got_z[0], np.stack(want_z))


def test_plane_builder_matches_mxu_engine():
    """search/build_pallas.py (the direct-plane build kernel) must
    agree with the XLA factored-DFT engine it mirrors (interpret
    mode), writing the aligned [off_eff : off_eff+uselen] window of
    each block straight into plane layout."""
    import jax.numpy as jnp
    from presto_tpu.search.accel import (
        AccelConfig, AccelKernels, _dft_consts_np, _ffdot_slab_mxu,
        _kern_bank_z, _fft_kernel_bank_c, _fwd_stage_mxu)
    from presto_tpu.search import build_pallas as bp
    cfg = AccelConfig(zmax=20, numharm=2, uselen=1024)
    kern = AccelKernels.build(cfg)
    fftlen, numz = kern.fftlen, cfg.numz
    hw_eff = -(-kern.halfwidth // 64) * 64
    off_eff = 2 * hw_eff
    assert cfg.uselen + 2 * off_eff <= fftlen
    rng = np.random.default_rng(3)
    B = 9                                 # exercises block padding
    data = (rng.normal(size=(B, fftlen // 2))
            + 1j * rng.normal(size=(B, fftlen // 2))
            ).astype(np.complex64)
    kc = _fft_kernel_bank_c(jnp.asarray(kern.kern_pairs), fftlen)
    kz = _kern_bank_z(kc, fftlen)
    consts = tuple(map(jnp.asarray, _dft_consts_np(fftlen)))
    # the XLA engine slicing at the SAME aligned offset is the oracle
    want = np.asarray(_ffdot_slab_mxu(jnp.asarray(data), kz, consts,
                                      cfg.uselen, fftlen, hw_eff))
    Sr, Si = _fwd_stage_mxu(jnp.asarray(data), consts, fftlen)
    nb_pad = -(-B // bp.BB) * bp.BB
    numz_pad = -(-numz // bp.ZT) * bp.ZT
    bpad = ((0, nb_pad - B), (0, 0), (0, 0))
    zpad = ((0, numz_pad - numz), (0, 0), (0, 0))
    build = bp.make_plane_builder(numz, B, fftlen, cfg.uselen,
                                  off_eff, interpret=True)
    pw = np.asarray(build(
        jnp.pad(Sr, bpad), jnp.pad(Si, bpad),
        jnp.pad(kz.real.astype(jnp.float32), zpad),
        jnp.pad(kz.imag.astype(jnp.float32), zpad)))
    plane = pw.reshape(numz_pad, nb_pad * cfg.uselen)
    got = plane[:numz, :B * cfg.uselen]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # padded blocks and pad z rows write zeros
    assert not plane[:, B * cfg.uselen:].any()
    assert not plane[numz:].any()


def test_pick_tile_vmem_gate():
    """Tile selection honors the measured 16 MB scoped-vmem stack:
    big-numz searches step down tiles and eventually decline the
    kernel instead of failing at dispatch."""
    from presto_tpu.search.accel import (AccelConfig,
                                         _harm_fracs_and_zinds)
    from presto_tpu.search.accel_pallas import (pick_tile,
                                                scratch_bytes,
                                                VMEM_BUDGET, TILE)
    slab = 1 << 20
    picks = {}
    for zmax in (200, 400, 800):
        cfg = AccelConfig(zmax=zmax, numharm=8)
        fz = _harm_fracs_and_zinds(cfg, cfg.numz)
        t = pick_tile(fz, cfg.numz, slab)
        picks[zmax] = t
        if t is not None:
            assert scratch_bytes(fz, cfg.numz, t) <= VMEM_BUDGET
            assert slab % t == 0
    assert picks[200] == TILE          # bench config keeps the max
    assert picks[400] is not None and picks[400] < TILE
    assert picks[800] is None          # graceful XLA fallback
    # tiny slabs never get a tile bigger than themselves
    assert pick_tile(_harm_fracs_and_zinds(
        AccelConfig(zmax=20, numharm=2), 21), 21, 128) is None
