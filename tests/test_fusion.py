"""pipeline/fusion.py unit + integration tests (ISSUE 7 tentpole).

The fusion contract: stages hand device arrays across an in-memory
seam instead of disk; durability is a tier, not the data path; and
NOTHING about fusion may change artifact bytes — the seam's device
series equal the staged .dat bytes, spills are journaled exactly like
staged writes, and the overlap knobs (in-flight window, ingest
double-buffer) change wall clock only.
"""

import os
import time

import numpy as np
import pytest

from presto_tpu.pipeline import fusion
from presto_tpu.pipeline.fusion import (DoubleBufferedIngest,
                                        InflightWindow, SeamBlock,
                                        StageSeam)


# ----------------------------------------------------------------------
# InflightWindow
# ----------------------------------------------------------------------

def test_inflight_window_bounds_pending():
    w = InflightWindow(depth=2)
    for i in range(5):
        w.admit(np.full(4, i, np.float32))
        assert len(w._pending) <= 2
    w.drain()
    assert not w._pending


def test_inflight_window_forces_oldest_first():
    import jax.numpy as jnp
    w = InflightWindow(depth=1)
    a = jnp.arange(8.0)
    b = jnp.arange(8.0) * 2
    w.admit(a)
    w.admit(b)              # depth 1: a must have been forced out
    assert len(w._pending) == 1
    assert w._pending[0] is b


def test_inflight_window_depth_clamped():
    assert InflightWindow(0).depth == 1
    assert InflightWindow(-3).depth == 1


# ----------------------------------------------------------------------
# DoubleBufferedIngest
# ----------------------------------------------------------------------

def test_ingest_preserves_order_and_values():
    blocks = [np.full(16, i, np.float32) for i in range(20)]
    with DoubleBufferedIngest(iter(blocks), depth=3) as ing:
        got = list(ing)
    assert len(got) == 20
    for i, b in enumerate(got):
        assert np.array_equal(b, blocks[i])


def test_ingest_relays_producer_exception():
    def produce():
        yield np.zeros(4)
        raise RuntimeError("decode failed mid-stream")

    ing = DoubleBufferedIngest(produce(), depth=2)
    assert np.array_equal(next(ing), np.zeros(4))
    with pytest.raises(RuntimeError, match="decode failed"):
        next(ing)
    ing.close()


def test_ingest_close_unblocks_full_producer():
    def produce():
        for i in range(1000):
            yield np.full(8, i)

    ing = DoubleBufferedIngest(produce(), depth=1)
    next(ing)               # producer now blocked on the full queue
    t0 = time.time()
    ing.close()
    assert time.time() - t0 < 5.0
    assert not ing._thread.is_alive()


def test_ingest_overlaps_producer_with_consumer():
    """The point of the double buffer: producer work for item k+1
    happens while the consumer holds item k."""
    seen = []

    def produce():
        for i in range(4):
            seen.append(i)
            yield i

    with DoubleBufferedIngest(produce(), depth=2) as ing:
        it = iter(ing)
        first = next(it)
        time.sleep(0.2)     # consumer dwells on item 0...
        assert first == 0
        # ...while the producer ran ahead (bounded by the queue)
        assert len(seen) >= 2
        assert list(it) == [1, 2, 3]


# ----------------------------------------------------------------------
# inf_float
# ----------------------------------------------------------------------

def test_inf_float_matches_sidecar_roundtrip(tmp_path):
    """inf_float must reproduce exactly what a consumer reads back
    from the .inf text — the staged/seam byte-identity hinge."""
    from presto_tpu.io.infodata import (InfoData, read_inf, write_inf,
                                        _RADIO)
    dt = 8.192e-5 * (1 + 1e-13)     # not exactly representable
    dm = 12.345678901234
    info = InfoData(name="x", N=4096, dt=dt, dm=dm, band=_RADIO,
                    telescope="GBT")
    p = str(tmp_path / "x.inf")
    write_inf(info, p)
    back = read_inf(str(tmp_path / "x"))
    assert fusion.inf_float(dt) == back.dt
    assert fusion.inf_float(dm, 12) == back.dm


# ----------------------------------------------------------------------
# fused_rfft_batch
# ----------------------------------------------------------------------

def test_fused_rfft_matches_staged_fft():
    import jax
    import jax.numpy as jnp
    from presto_tpu.ops import fftpack
    rng = np.random.default_rng(3)
    batch = rng.normal(size=(3, 256)).astype(np.float32)
    got = np.asarray(fusion.fused_rfft_batch(jnp.asarray(batch)))
    ref = np.asarray(jax.jit(jax.vmap(
        fftpack.realfft_packed_pairs))(jnp.asarray(batch)))
    assert np.array_equal(got, ref)


# ----------------------------------------------------------------------
# StageSeam
# ----------------------------------------------------------------------

def _mk_block(workdir, ntrials=3, n=512, dt=2e-4, prefix="t"):
    import jax.numpy as jnp
    from presto_tpu.io.infodata import InfoData
    rng = np.random.default_rng(11)
    host = rng.normal(size=(ntrials, n)).astype(np.float32)
    names = [os.path.join(workdir, "%s_DM%.2f" % (prefix, float(i)))
             for i in range(ntrials)]
    infos = [InfoData(name=names[i], N=n, dt=dt, dm=float(i))
             for i in range(ntrials)]
    return SeamBlock(names=names, infos=infos,
                     dms=[float(i) for i in range(ntrials)],
                     series_dev=jnp.asarray(host), series_host=host,
                     valid=n, numout=n, dt=dt)


def test_seam_nondurable_holds_data_writes_only_inf(tmp_path):
    seam = StageSeam(str(tmp_path), durable=False)
    seam.add_block(_mk_block(str(tmp_path)))
    assert len(seam) == 3
    for p in seam.dat_paths():
        assert not os.path.exists(p)                 # no .dat spilled
        assert os.path.exists(p[:-4] + ".inf")       # metadata always


def test_seam_durable_spills_journaled(tmp_path):
    from presto_tpu.pipeline.manifest import SurveyManifest
    m = SurveyManifest.load(str(tmp_path))
    seam = StageSeam(str(tmp_path), durable=True, manifest=m)
    block = _mk_block(str(tmp_path))
    seam.add_block(block)
    for row, p in enumerate(sorted(seam.dat_paths())):
        assert os.path.exists(p)
        assert m.valid(p), p
        assert m.stage_of(p) == "prepsubband"
    # spilled bytes equal the host copy exactly
    from presto_tpu.io.datfft import read_dat
    for row, name in enumerate(block.names):
        assert np.array_equal(read_dat(name + ".dat"),
                              block.series_host[row])


def test_seam_ensure_dat_on_demand(tmp_path):
    seam = StageSeam(str(tmp_path), durable=False)
    block = _mk_block(str(tmp_path))
    seam.add_block(block)
    target = block.names[1] + ".dat"
    assert not os.path.exists(target)
    assert seam.ensure_dat(target)
    assert os.path.exists(target)
    # only the requested trial spilled
    assert not os.path.exists(block.names[0] + ".dat")
    # unknown paths report plain existence
    assert not seam.ensure_dat(str(tmp_path / "other.dat"))


def test_seam_spill_counts_bytes(tmp_path):
    from presto_tpu.obs import Observability, ObsConfig
    obs = Observability(ObsConfig(enabled=True))
    seam = StageSeam(str(tmp_path), durable=False, obs=obs)
    block = _mk_block(str(tmp_path))
    seam.add_block(block)
    seam.spill()
    c = obs.metrics.counter(
        "survey_fused_bytes_spilled_total",
        "Seam-held artifact bytes spilled to the durable tier")
    assert c.value == block.series_host.nbytes
    t = obs.metrics.counter(
        "survey_fused_trials_total",
        "DM trials handed across the in-memory stage seam")
    assert t.value == 3


def test_seam_release_drops_device_reference(tmp_path):
    seam = StageSeam(str(tmp_path), durable=False)
    block = _mk_block(str(tmp_path))
    seam.add_block(block)
    seam.release(block)
    assert block.series_dev is None
    # host copy still serves spills after release
    assert seam.ensure_dat(block.names[0] + ".dat")


def test_seam_fft_search_one_searcher_per_pass(tmp_path, monkeypatch):
    # seam chunks of one shape share a pass's searcher, and with it
    # the compiled build and scan programs
    from presto_tpu.pipeline import survey
    made = []
    real = survey._searcher_for

    def counting(cfg, T, nbins, *jerk):
        made.append(cfg.zmax)
        return real(cfg, T, nbins, *jerk)

    monkeypatch.setattr(survey, "_searcher_for", counting)
    seam = StageSeam(str(tmp_path), durable=False)
    for prefix in ("a", "b"):
        seam.add_block(_mk_block(str(tmp_path), n=4096, prefix=prefix))
    passes = [(0, 2, 2.0, 1.0), (4, 2, 2.0, 1.0)]
    survey._seam_fft_search(seam, survey.SurveyConfig(), passes)
    assert sorted(made) == [0, 4]
    for prefix in ("a", "b"):
        for z in (0, 4):
            assert os.path.exists(str(tmp_path / (
                "%s_DM1.00_ACCEL_%d" % (prefix, z))))


# ----------------------------------------------------------------------
# resolve_depths / tune wiring
# ----------------------------------------------------------------------

def test_resolve_depths_defaults():
    d = fusion.resolve_depths()
    assert d == {"window": fusion.DEFAULT_WINDOW_DEPTH,
                 "ingest_depth": fusion.DEFAULT_INGEST_DEPTH,
                 "shard_window": fusion.DEFAULT_WINDOW_DEPTH}


def test_resolve_depths_explicit_and_clamped():
    assert fusion.resolve_depths(4)["window"] == 4
    assert fusion.resolve_depths(4)["shard_window"] == 4
    assert fusion.resolve_depths(100)["window"] == 8
    assert fusion.resolve_depths(0)["window"] == 1


def test_resolve_depths_consults_tune_db(tmp_path, monkeypatch):
    from presto_tpu import tune
    monkeypatch.setenv("PRESTO_TPU_TUNE", "1")
    monkeypatch.setenv("PRESTO_TPU_TUNE_DB",
                       str(tmp_path / "tune.json"))
    tune.reset()
    db = tune.TuneDB()
    db.record(tune.fingerprint_key(), "pipeline_inflight_depth",
              tune.GLOBAL_KEY, {"window": 3, "ingest_depth": 4},
              median_s=0.01)
    db.save(str(tmp_path / "tune.json"))
    tune.reset()
    try:
        d = fusion.resolve_depths()
        # shard_window falls back to the tuned single-device window
        # when the sharded family has no measurement
        assert d == {"window": 3, "ingest_depth": 4,
                     "shard_window": 3}
    finally:
        monkeypatch.delenv("PRESTO_TPU_TUNE")
        tune.reset()


# ----------------------------------------------------------------------
# native feeder stats (csrc pt_feeder_stats binding)
# ----------------------------------------------------------------------

def test_feeder_stats_counts_blocks(tmp_path):
    from presto_tpu.io import native
    if not native.available():
        pytest.skip("native IO library unavailable")
    p = str(tmp_path / "raw.bin")
    with open(p, "wb") as f:
        f.write(os.urandom(1 << 14))
    fd = native.BlockFeeder(p, 0, 1024, nbuf=4)
    n = sum(len(b) for b in fd)
    st = fd.stats()
    fd.close()
    assert n == 1 << 14
    if st is None:          # stale .so without the symbol
        pytest.skip("pt_feeder_stats not in the loaded library")
    assert st["blocks"] >= 16
    assert st["consumer_waits"] >= 0
    assert st["producer_waits"] >= 0
