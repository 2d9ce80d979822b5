"""Compile the main path's kernels for a TPU v5e that is described,
not attached (no chip needed): the chip's compiler refuses here what
interpret mode on the CPU cannot see — VMEM overruns, unaligned
slices, programs that do not fit.  Nothing runs, so nothing here is a
result or a time.

The topology is described inside a module fixture (never at import:
only one process may load libtpu, and xdist workers import every test
file), and every test compiles in the test's own process.  The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back here.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

NCHAN, NSUB, NDMS = 256, 32, 512          # chip_smoke geometry
BLOCKLEN = 1 << 17                         # stream_blocklen(256, ...)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("zmax,numharm", [(200, 8), (50, 8), (0, 16)])
def test_stage_reducer_compiles(one_chip, zmax, numharm):
    from presto_tpu.search import accel, accel_pallas as ap
    cfg = accel.AccelConfig(zmax=zmax, numharm=numharm)
    fz = accel._harm_fracs_and_zinds(cfg, cfg.numz)
    slab, nslabs = 1 << 20, 1
    numr = slab * nslabs + ap.PLANE_PAD
    numr += -numr % ap.TILE
    tile = ap.pick_tile(fz, cfg.numz, slab)
    assert tile, "no reducer tile fits VMEM at zmax=%d" % zmax
    reducer = ap.make_stage_reducer(cfg.numharmstages, fz, slab,
                                    cfg.numz, numr, interpret=False,
                                    tile=tile)
    numz_pad = -(-cfg.numz // 8) * 8
    compiled = jax.jit(reducer).lower(
        _spec((numz_pad, numr), jnp.float32, one_chip),
        _spec((nslabs,), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_shifted_stage_reducer_compiles(one_chip):
    # the banded jerk volume's scan at the ter5 cell's width: zmax 200,
    # numharm 8, one 2^19-column piece, each term from its own plane
    from presto_tpu.search import accel, accel_pallas as ap
    cfg = accel.AccelConfig(zmax=200, numharm=8)
    fz = accel._harm_fracs_and_zinds(cfg, cfg.numz)
    slab = 1 << 19
    tile = ap.pick_tile(fz, cfg.numz, slab)
    assert tile, "no reducer tile fits VMEM at zmax=200"
    reducer = ap.make_stage_reducer(cfg.numharmstages, fz, slab,
                                    cfg.numz, 0, tile=tile, shifted=True)
    numz_pad = ap.pad_rows(cfg.numz)
    terms = [(h, t) for st in fz for (h, t, _zi) in st]
    subs = tuple(_spec((numz_pad, slab * h // t + 2 * 7424), jnp.float32,
                       one_chip) for h, t in terms)
    compiled = reducer.lower(
        _spec((numz_pad, slab + 2 * 7424), jnp.float32, one_chip), subs,
        _spec((1,), jnp.int32, one_chip),
        _spec((1 + len(terms),), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("zmax", [200, 50])
def test_plane_builder_compiles(one_chip, zmax):
    from presto_tpu.search import build_pallas as bp
    from presto_tpu.search import accel
    cfg = accel.AccelConfig(zmax=zmax, numharm=8)
    fftlen, uselen, off = 8192, 7936, 128      # the survey's geometry
    numz = cfg.numz
    nblocks = bp.BB * 4
    builder = bp.make_plane_builder(numz, nblocks, fftlen, uselen, off,
                                    interpret=False)
    n1, n2 = fftlen // 128, 128
    numz_pad = -(-numz // bp.ZT) * bp.ZT
    s = _spec((nblocks, n1, n2), jnp.float32, one_chip)
    k = _spec((numz_pad, n1, n2), jnp.float32, one_chip)
    compiled = jax.jit(builder).lower(s, s, k, k).compile()
    assert _has_kernel(compiled)


def test_dedisp_block_step_compiles(one_chip):
    # delays as a traced argument (the mesh step's per-device program);
    # a HOST delay plan unrolls numdms*nsub static slices instead, and
    # its compile for the chip takes minutes at this width (PERF.md)
    from presto_tpu.ops import dedispersion as dd

    def step(prev_raw, cur, prev_sub, chan, dms):
        sub = dd.dedisp_subbands_block(prev_raw, cur, chan, NSUB)
        return sub, dd.float_dedisp_many_block(prev_sub, sub, dms)

    raw = _spec((NCHAN, BLOCKLEN), jnp.float32, one_chip)
    compiled = jax.jit(step).lower(
        raw, raw, _spec((NSUB, BLOCKLEN), jnp.float32, one_chip),
        _spec((NCHAN,), jnp.int32, one_chip),
        _spec((NDMS, NSUB), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= NDMS * BLOCKLEN * 4


def test_sharded_dedisp_step_compiles(topo):
    from jax.sharding import Mesh
    from presto_tpu.parallel.sharded import make_sharded_dedisperse_step
    mesh = Mesh(np.array(topo.devices[:4]), ("dm",))
    rep = NamedSharding(mesh, P())
    step = make_sharded_dedisperse_step(mesh, NSUB)
    compiled = step.lower(
        _spec((NCHAN, BLOCKLEN), jnp.float32, rep),
        _spec((NCHAN, BLOCKLEN), jnp.float32, rep),
        _spec((NSUB, BLOCKLEN), jnp.float32, rep),
        _spec((NCHAN,), jnp.int32, rep),
        _spec((NDMS, NSUB), jnp.int32,
              NamedSharding(mesh, P("dm", None)))).compile()
    series = compiled.output_shardings[1]
    assert series.spec[0] == "dm" and len(mesh.devices.flat) == 4


@pytest.mark.parametrize("zmax,numharm", [(0, 16), (50, 8)])
def test_sharded_accel_search_lowers(topo, monkeypatch, zmax, numharm):
    # the DM-sharded survey's search program at the smoke's width: the
    # Pallas plane builder and stage reducer under shard_map on a
    # 4-device mesh (steered onto the chip's engines here, where
    # jax.devices() is the CPU).  Lowered, not compiled: shard_map
    # refuses a kernel at trace time, and the whole program's compile
    # takes ~45 s here (PERF.md)
    from jax.sharding import Mesh
    from presto_tpu.parallel import sharded
    from presto_tpu.search import accel, accel_pallas as ap
    monkeypatch.setattr(ap, "pallas_available", lambda: True)
    monkeypatch.setattr(accel, "_use_mxu_engine",
                        lambda n: n % (2 * accel._DFT_N2) == 0)
    mesh = Mesh(np.array(topo.devices[:4]), ("dm",))
    numbins, nd = 1 << 22, 8
    s = accel.AccelSearch(accel.AccelConfig(zmax=zmax, numharm=numharm),
                          T=numbins * 2 * 64e-6, numbins=numbins)
    g = s._build_plan_ns()
    _slab, _k, scanner, start_cols = s._slab_plan(g.plane_numr, 1 << 20)
    kern = jax.eval_shape(
        lambda kp: accel._fft_kernel_bank_c(kp, s.kern.fftlen),
        jax.ShapeDtypeStruct(s.kern.kern_pairs.shape, jnp.float32))
    fn = sharded.compact_search_fn(s, mesh, g, scanner, (nd, numbins, 2),
                                   accel.COMPACT_CANDS)
    rep = NamedSharding(mesh, P())
    lowered = fn.lower(
        _spec((nd, numbins, 2), jnp.float32,
              NamedSharding(mesh, P("dm"))),
        _spec(kern.shape, kern.dtype, rep),
        _spec((len(start_cols),), jnp.int32, rep))
    assert lowered.as_text().count("tpu_custom_call") == 2
