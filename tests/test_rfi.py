"""RFI stack: clipping, zero-DM, mask IO round trips, rfifind detection."""

import numpy as np
import pytest

from presto_tpu.ops.clipping import clip_times, remove_zerodm, ClipState
from presto_tpu.io import maskfile as mf
from presto_tpu.search.rfifind import rfifind, calc_avgmedstd


class TestClipping:
    def test_clean_data_unclipped(self):
        rng = np.random.default_rng(0)
        block = rng.normal(10, 1, (512, 16)).astype(np.float32)
        out, nclip, state = clip_times(block, 6.0)
        assert nclip == 0
        np.testing.assert_array_equal(out, block)

    def test_strong_rfi_clipped_and_replaced(self):
        rng = np.random.default_rng(1)
        block = rng.normal(10, 1, (512, 16)).astype(np.float32)
        block[100] += 500.0       # one huge broadband spike
        block[101] += 400.0
        out, nclip, state = clip_times(block, 6.0)
        assert nclip == 2
        # replaced samples near the channel means, not the spike
        assert np.all(out[100] < 20)
        # other samples untouched
        np.testing.assert_array_equal(out[50], block[50])

    def test_state_carries_across_blocks(self):
        rng = np.random.default_rng(2)
        state = None
        for i in range(5):
            block = rng.normal(10, 1, (256, 8)).astype(np.float32)
            _, _, state = clip_times(block, 6.0, state)
        assert state.blocksread == 5
        assert 9 < state.running_avg / 8 < 11  # band sum of 8 chans


class TestZeroDM:
    def test_removes_broadband_transient(self):
        rng = np.random.default_rng(3)
        block = rng.normal(10, 0.1, (256, 8)).astype(np.float32)
        block[77] += 50.0          # broadband impulse (e.g. lightning)
        out = remove_zerodm(block)
        # the impulse is suppressed to near the local level
        assert abs(out[77].mean() - out[50].mean()) < 1.0
        # bandpass shape preserved on average
        assert abs(out.mean() - block[:70].mean()) < 1.0


class TestMaskIO:
    def test_roundtrip(self, tmp_path):
        bytemask = np.zeros((10, 16), dtype=np.uint8)
        bytemask[3, 5] |= mf.BAD_POW
        bytemask[7, :] |= mf.USERINTS
        bytemask[:, 2] |= mf.USERCHAN
        m = mf.fill_mask(10.0, 4.0, 59000.5, 30.0, 1300.0, 1.0, 16, 10,
                         3000, [2], [7], bytemask)
        p = str(tmp_path / "t.mask")
        mf.write_mask(p, m)
        back = mf.read_mask(p)
        assert back.numchan == 16 and back.numint == 10
        assert back.ptsperint == 3000
        assert list(back.zap_chans) == [2]
        assert list(back.zap_ints) == [7]
        # interval 3 masks channels {2 (userchan), 5 (bad pow)}
        assert set(back.chans_per_int[3].tolist()) == {2, 5}
        # interval 7 masks everything
        assert len(back.chans_per_int[7]) == 16

    def test_check_mask(self):
        bytemask = np.zeros((10, 4), dtype=np.uint8)
        bytemask[2, 1] |= mf.BAD_AVG
        m = mf.fill_mask(10, 4, 0.0, 10.0, 400.0, 1.0, 4, 10, 100,
                         [], [5], bytemask)
        n, chans = m.check_mask(20.0, 5.0)   # interval 2
        assert n == 1 and list(chans) == [1]
        n, chans = m.check_mask(50.0, 5.0)   # interval 5 is zapped
        assert n == -1
        n, chans = m.check_mask(0.0, 5.0)
        assert n == 0

    def test_stats_roundtrip_and_padvals(self, tmp_path):
        rng = np.random.default_rng(4)
        numint, numchan = 20, 8
        avg = rng.normal(100, 5, (numint, numchan)).astype(np.float32)
        std = rng.normal(10, 1, (numint, numchan)).astype(np.float32)
        pw = rng.normal(3, 1, (numint, numchan)).astype(np.float32)
        p = str(tmp_path / "t.stats")
        mf.write_statsfile(p, pw, avg, std, 3000)
        st = mf.read_statsfile(p)
        np.testing.assert_array_equal(st["dataavg"], avg)
        pv = mf.determine_padvals(p)
        assert pv.shape == (numchan,)
        np.testing.assert_allclose(pv, avg.mean(axis=0), atol=3.0)


class TestRfifind:
    def _make_data(self, N=1 << 15, numchan=16, seed=0):
        rng = np.random.default_rng(seed)
        return rng.normal(100, 10, (N, numchan)).astype(np.float32)

    def test_clean_data_mostly_unmasked(self):
        data = self._make_data()
        res = rfifind(data, dt=1e-3, lofreq=1300.0, chanwidth=1.0,
                      time_sec=2.0)
        assert res.masked_fraction() < 0.15

    def test_bad_channel_detected(self):
        data = self._make_data()
        data[:, 5] += (np.arange(data.shape[0]) % 100 < 50) * 200.0
        res = rfifind(data, dt=1e-3, lofreq=1300.0, chanwidth=1.0,
                      time_sec=2.0)
        # channel 5 fully masked (std and/or periodic power)
        assert all(5 in res.mask.chans_per_int[i].tolist()
                   for i in range(res.mask.numint))

    def test_periodic_rfi_flagged_by_power(self):
        data = self._make_data(seed=1)
        t = np.arange(data.shape[0]) * 1e-3
        data[:, 3] += 30.0 * np.sin(2 * np.pi * 60.0 * t)  # 60 Hz mains
        res = rfifind(data, dt=1e-3, lofreq=1300.0, chanwidth=1.0,
                      time_sec=2.0)
        assert (res.bytemask[:, 3] & mf.BAD_POW).all()

    def test_bad_interval_detected(self):
        data = self._make_data(seed=2)
        i0 = 4 * 2000  # interval 4 at time_sec=2.0/dt=1e-3
        data[i0:i0 + 2000] += 300.0
        res = rfifind(data, dt=1e-3, lofreq=1300.0, chanwidth=1.0,
                      time_sec=2.0)
        assert (res.bytemask[4] & mf.USERINTS).all()

    def test_products_written(self, tmp_path):
        from presto_tpu.search.rfifind import write_rfifind_products
        data = self._make_data(N=1 << 13)
        res = rfifind(data, dt=1e-3, lofreq=1300.0, chanwidth=1.0,
                      time_sec=1.0)
        root = str(tmp_path / "obs")
        write_rfifind_products(res, root)
        m = mf.read_mask(root + "_rfifind.mask")
        assert m.numchan == 16
        st = mf.read_statsfile(root + "_rfifind.stats")
        assert st["numint"] == res.mask.numint


def test_calc_avgmedstd_matches_definition():
    rng = np.random.default_rng(5)
    x = rng.normal(0, 1, 101)
    avg, med, std = calc_avgmedstd(x, 0.5)
    s = np.sort(x)
    length = int(101 * 0.5 + 0.5)
    start = (101 - length) // 2
    mid = s[start:start + length]
    assert np.isclose(avg, mid.mean())
    assert np.isclose(med, s[50])
    assert np.isclose(std, mid.std())


# ----------------------------------------------------------------------
# the one-pass clip: in place, on a copy, against the gather algorithm
# ----------------------------------------------------------------------

def _clip_times_gather(block, clip_sigma, state=None):
    """The gather form of the clipper, the reference for the one-pass
    form: a copy of the block, the good rows gathered for their channel
    means."""
    if state is None:
        state = ClipState()
    zero_dm = block.sum(axis=1).astype(np.float64)
    med, std = float(np.median(zero_dm)), float(zero_dm.std())
    good = (zero_dm > med - 3.0 * std) & (zero_dm < med + 3.0 * std)
    if good.sum() < 1:
        avg, std = state.running_avg, state.running_std
        chan_avg = (state.chan_running_avg if state.chan_running_avg
                    is not None else block.mean(axis=0))
    else:
        avg, std = float(zero_dm[good].mean()), float(zero_dm[good].std())
        chan_avg = block[good].mean(axis=0)
    if state.blocksread:
        running_avg = 0.9 * state.running_avg + 0.1 * avg
        running_std = 0.9 * state.running_std + 0.1 * std
        chan_running = 0.9 * state.chan_running_avg + 0.1 * chan_avg
    else:
        running_avg, running_std = avg, std
        chan_running = chan_avg.astype(np.float64)
    bad = np.abs(zero_dm - running_avg) > clip_sigma * running_std
    out = block.copy()
    out[bad] = chan_running.astype(np.float32)
    return out, bad, ClipState(chan_running, running_avg, running_std,
                               state.blocksread + 1)


def _bursty_blocks(seed, nblocks, ptsperblk, numchan):
    """Float blocks over a sloped bandpass with 1-3 broadband bursts of
    1-24 samples each."""
    rng = np.random.default_rng(seed)
    bandpass = np.linspace(80.0, 110.0, numchan)
    for _ in range(nblocks):
        block = (bandpass + rng.normal(0, 12.0, (ptsperblk, numchan))
                 ).astype(np.float32)
        for _ in range(rng.integers(1, 4)):
            n = int(rng.integers(1, 25))
            t0 = int(rng.integers(0, ptsperblk - n))
            block[t0:t0 + n] += rng.uniform(20.0, 100.0)
        yield block


@pytest.mark.parametrize("seed,ptsperblk,numchan", [
    (0, 512, 16), (1, 512, 16), (2, 1024, 64), (3, 1024, 64),
    (4, 2048, 96), (5, 4096, 32)])
@pytest.mark.parametrize("path", ["inplace", "copy", "out"])
def test_one_pass_clip_matches_gather(seed, ptsperblk, numchan, path):
    ref_state = state = None
    nclipped = 0
    for block in _bursty_blocks(seed, 4, ptsperblk, numchan):
        want, bad, ref_state = _clip_times_gather(block, 6.0, ref_state)
        if path == "inplace":
            got, nclip, state = clip_times(block, 6.0, state, out=block)
            assert got is block
        elif path == "copy":
            got, nclip, state = clip_times(block, 6.0, state)
        else:
            dest = np.full_like(block, np.nan)
            got, nclip, state = clip_times(block, 6.0, state, out=dest)
            assert got is dest
        assert nclip == int(bad.sum())
        nclipped += nclip
        assert state.blocksread == ref_state.blocksread
        assert state.running_avg == ref_state.running_avg
        assert state.running_std == ref_state.running_std
        np.testing.assert_allclose(state.chan_running_avg,
                                   ref_state.chan_running_avg, rtol=1e-5)
        np.testing.assert_array_equal(got[~bad], want[~bad])
        np.testing.assert_allclose(got[bad], want[bad], rtol=1e-5)
    assert nclipped > 0


def test_clip_without_out_leaves_block_unchanged():
    block = next(_bursty_blocks(7, 1, 512, 16))
    before = block.copy()
    out, nclip, _ = clip_times(block, 6.0)
    assert nclip > 0 and out is not block
    np.testing.assert_array_equal(block, before)


@pytest.fixture
def default_obs():
    """An enabled process-default handle, restored afterwards."""
    from presto_tpu import obs as obsmod
    saved = obsmod._default
    obs = obsmod.configure(obsmod.ObsConfig(enabled=True))
    yield obs
    obsmod._default = saved


def _clip_counts(obs):
    blocks = obs.metrics.counter("ingest_clip_blocks_total",
                                 labelnames=("path",))
    return ({p: blocks.labels(path=p).value for p in ("inplace", "copy")},
            obs.metrics.counter("ingest_clipped_rows_total").value)


def _prep(invert=False):
    from types import SimpleNamespace
    from presto_tpu.apps.common import BlockPrep
    args = SimpleNamespace(clip=6.0, noclip=False, invert=invert,
                           zerodm=False, runavg=False)
    return BlockPrep(16, 1e-3, args)


def test_blockprep_clips_a_decoded_block_in_place(default_obs):
    from presto_tpu.io.sigproc import FilterbankHeader, decode_spectra_block
    hdr = FilterbankHeader(fch1=1500.0, foff=-1.0, nchans=16, nbits=8,
                           tsamp=1e-3, nifs=1, N=512)
    raw = np.random.default_rng(8).integers(90, 110, 512 * 16,
                                            dtype=np.uint8)
    raw.reshape(512, 16)[200:203] = 250           # a broadband burst
    block = decode_spectra_block(hdr, raw, 512)
    assert block.flags.owndata and block.flags.writeable
    want, nclip, _ = clip_times(block, 6.0)
    got = _prep()(block, 0)
    assert got is block and nclip > 0
    np.testing.assert_array_equal(got, want)
    assert _clip_counts(default_obs) == ({"inplace": 1, "copy": 0}, nclip)


@pytest.mark.parametrize("kind", ["readonly", "view"])
def test_blockprep_copies_what_it_does_not_own(default_obs, kind):
    block = next(_bursty_blocks(9, 1, 512, 16))
    if kind == "readonly":
        data = np.frombuffer(block.tobytes(), np.float32).reshape(512, 16)
        assert not data.flags.writeable
        want, nclip, _ = clip_times(block, 6.0)
    else:
        data = block
        want, nclip, _ = clip_times(block[:, ::-1].copy(), 6.0)
    before = data.copy()
    got = _prep(invert=(kind == "view"))(data, 0)
    assert nclip > 0
    np.testing.assert_array_equal(data, before)
    np.testing.assert_array_equal(got, want)
    assert _clip_counts(default_obs) == ({"inplace": 0, "copy": 1}, nclip)
