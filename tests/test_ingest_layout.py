"""The ingest path's memory layout: filterbank blocks are decoded into
channel-major memory and keep it through BlockPrep, so the dedispersion
feed's ``np.ascontiguousarray(block.T)`` is a view, not a 128 MiB copy.

Every case also checks the values against the time-major path
(``chan_major=False``), which is the layout the reader handed out
before.
"""

from types import SimpleNamespace

import numpy as np
import pytest

from presto_tpu.apps.common import BlockPrep
from presto_tpu.io import sigproc
from presto_tpu.io.sigproc import FilterbankHeader, decode_spectra_block
from presto_tpu.ops.clipping import clip_times

NCHAN, L, DT = 64, 512, 1e-3


@pytest.fixture
def default_obs():
    """An enabled process-default handle, restored afterwards."""
    from presto_tpu import obs as obsmod
    saved = obsmod._default
    obs = obsmod.configure(obsmod.ObsConfig(enabled=True))
    yield obs
    obsmod._default = saved


def _counts(obs, name, label, values):
    c = obs.metrics.counter(name, labelnames=(label,))
    return {v: c.labels(**{label: v}).value for v in values}


def _header(nchan=NCHAN):
    # 8-bit and descending, like GBNCC's GUPPI data
    return FilterbankHeader(fch1=400.0, foff=-0.5, nchans=nchan, nbits=8,
                            tsamp=DT, nifs=1, N=4 * L)


def _raw_blocks(seed, nblocks, nspec=L, nchan=NCHAN):
    """8-bit blocks around a sloped bandpass with broadband bursts."""
    rng = np.random.default_rng(seed)
    slope = np.linspace(80, 110, nchan).astype(np.int64)
    for _ in range(nblocks):
        vals = slope + rng.integers(-12, 13, (nspec, nchan))
        for _ in range(rng.integers(1, 4)):
            n = int(rng.integers(1, 25))
            t0 = int(rng.integers(0, nspec - n))
            vals[t0:t0 + n] += int(rng.integers(40, 120))
        yield np.clip(vals, 0, 255).astype(np.uint8).reshape(-1)


def _args(**kw):
    base = dict(clip=6.0, noclip=False, invert=False, zerodm=False,
                runavg=False)
    base.update(kw)
    return SimpleNamespace(**base)


def _chan_major(a):
    return a.flags.f_contiguous and not a.flags.c_contiguous


def test_cell_produce_sequence_transposes_for_free(default_obs):
    """decode -> BlockPrep (clip) -> np.ascontiguousarray(block.T), as
    the dedispersion feed runs it: the [C, T] array is the decoded
    block's own memory, and each block is clipped in place."""
    hdr = _header()
    prep, prep_time = BlockPrep(NCHAN, DT, _args()), \
        BlockPrep(NCHAN, DT, _args())
    for j, raw in enumerate(_raw_blocks(1, 3)):
        block = decode_spectra_block(hdr, raw, L)
        out = prep(block, j * L)
        blockT = np.ascontiguousarray(out.T)
        assert out is block and np.shares_memory(blockT, block)
        assert blockT.flags.c_contiguous and blockT.shape == (NCHAN, L)
        # the time-major path gives the same [C, T] values
        want = prep_time(decode_spectra_block(hdr, raw, L,
                                              chan_major=False), j * L)
        np.testing.assert_array_equal(blockT, np.ascontiguousarray(want.T))
    assert default_obs.metrics.counter("ingest_clipped_rows_total").value
    assert _counts(default_obs, "ingest_decode_blocks_total", "layout",
                   ("chan", "time")) == {"chan": 3, "time": 3}
    # the time-major blocks own their data too: every block in place
    assert _counts(default_obs, "ingest_clip_blocks_total", "path",
                   ("inplace", "copy")) == {"inplace": 6, "copy": 0}


class _Mask:
    """check_mask's contract (io/maskfile.Mask): two zapped channels,
    or the whole block for the third block."""

    def check_mask(self, starttime, duration):
        if starttime >= 2 * L * DT:
            return -1, None
        return 2, np.array([3, 40], np.int32)


@pytest.mark.parametrize("option", ["invert", "mask", "zerodm", "runavg",
                                    "ignore"])
def test_blockprep_options_keep_channel_major(option):
    """Each BlockPrep option, after the clip, hands back channel-major
    memory with the values it gives a time-major block."""
    hdr = _header()
    kw = {"invert": dict(invert=True), "zerodm": dict(zerodm=True),
          "runavg": dict(runavg=True)}.get(option, {})

    def make():
        extra = {}
        if option == "mask":
            extra = dict(mask=_Mask(),
                         padvals=np.linspace(90, 100, NCHAN,
                                             dtype=np.float32))
        if option == "ignore":
            extra = dict(ignore=np.array([0, 17, 63]))
        return BlockPrep(NCHAN, DT, _args(**kw), **extra)

    prep, prep_time = make(), make()
    for j, raw in enumerate(_raw_blocks(2, 3)):
        got = prep(decode_spectra_block(hdr, raw, L), j * L)
        want = prep_time(decode_spectra_block(hdr, raw, L,
                                              chan_major=False), j * L)
        assert _chan_major(got) and got.dtype == np.float32
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("path", ["inplace", "copy", "out"])
def test_clip_times_same_for_either_layout(path):
    """clip_times on the C and the F copy of each block: identical
    output, clipped rows and carried state; the output keeps the
    input's layout."""
    state = {"C": None, "F": None}
    nclipped = 0
    for raw in _raw_blocks(3, 4):
        vals = raw.reshape(L, NCHAN).astype(np.float32)
        res = {}
        for order in ("C", "F"):
            block = np.array(vals, order=order)
            if path == "inplace":
                out = block
            elif path == "out":
                out = np.full_like(block, np.nan)
            else:
                out = None
            got, nclip, state[order] = clip_times(block, 6.0, state[order],
                                                  out=out)
            assert got.flags["%s_CONTIGUOUS" % order]
            res[order] = (got, nclip)
        np.testing.assert_array_equal(res["C"][0], res["F"][0])
        assert res["C"][1] == res["F"][1]
        nclipped += res["C"][1]
        sc, sf = state["C"], state["F"]
        assert (sc.running_avg, sc.running_std, sc.blocksread) == \
            (sf.running_avg, sf.running_std, sf.blocksread)
        np.testing.assert_array_equal(sc.chan_running_avg,
                                      sf.chan_running_avg)
    assert nclipped > 0


def _write_fil(path, nspec, seed=4):
    hdr = _header()
    raw = np.concatenate(list(_raw_blocks(seed, 1, nspec)))
    data = decode_spectra_block(hdr, raw, nspec)   # ascending
    sigproc.write_filterbank(path, hdr, data)
    return data


@pytest.mark.parametrize("reader", ["file", "set"])
def test_read_spectra_keeps_channel_major(tmp_path, reader):
    """Whole reads, reads past EOF (zero-padded) and, for one file, a
    short read after the file shrank: channel-major by default, the
    same values as the time-major read."""
    nspec = 700
    paths = [str(tmp_path / "a.fil")]
    data = _write_fil(paths[0], nspec)
    if reader == "set":
        paths.append(str(tmp_path / "b.fil"))
        data = np.concatenate([data, _write_fil(paths[1], nspec, seed=5)])
        fb = sigproc.FilterbankSet(paths)    # equal tstarts: a, then b
    else:
        fb = sigproc.FilterbankFile(paths[0])
    with fb:
        n = fb.nspectra
        for start, count in ((0, n), (n - 100, 300), (50, 256)):
            got = fb.read_spectra(start, count)
            want = fb.read_spectra(start, count, chan_major=False)
            assert _chan_major(got) and want.flags.c_contiguous
            np.testing.assert_array_equal(got, want)
            real = min(count, n - start)
            np.testing.assert_array_equal(got[:real],
                                          data[start:start + real])
            assert not got[real:].any()
        if reader == "file":
            with open(paths[0], "r+b") as f:     # the writer died
                f.truncate(fb.header.headerlen + 600 * NCHAN)
            got = fb.read_spectra(500, 200)
            assert _chan_major(got)
            np.testing.assert_array_equal(got[:100], data[500:600])
            assert not got[100:].any()
            assert any(iv.reason == "short-read"
                       for iv in fb.quality.intervals)


def test_stream_blocks_keep_channel_major(tmp_path):
    """The prefetched stream (native feeder) and its zero-padded last
    block: channel-major, the values of time-major block reads."""
    path = str(tmp_path / "s.fil")
    data = _write_fil(path, 1000)
    with sigproc.FilterbankFile(path) as fb:
        got = list(fb.stream_blocks(256))
        want = [fb.read_spectra(s, 256, chan_major=False)
                for s in range(0, 1000, 256)]
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        assert _chan_major(g) and w.flags.c_contiguous
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(np.concatenate(got)[:1000], data)
    assert not got[-1][1000 - 768:].any()


@pytest.fixture(scope="module")
def bursty_fil(tmp_path_factory):
    """A small 8-bit descending-band observation with a dispersed
    pulsar and broadband bursts for the clipper."""
    from presto_tpu.models.synth import FakeSignal, fake_filterbank_file
    d = tmp_path_factory.mktemp("layout")
    path = str(d / "obs.fil")
    fake_filterbank_file(path, N=1 << 13, dt=5e-4, nchan=32,
                         lofreq=1350.0, chanwidth=3.0,
                         signal=FakeSignal(f=7.8125, dm=60.0, amp=1.2),
                         noise_sigma=3.0, nbits=8)
    with sigproc.FilterbankFile(path) as fb:
        hdr = fb.header
    with open(path, "r+b") as f:          # bursts in the raw bytes
        for t0 in (1000, 5000, 7000):
            f.seek(hdr.headerlen + t0 * 32)
            f.write(bytes([250]) * (32 * 6))
    return path


@pytest.mark.parametrize("app,argv", [
    ("prepsubband", ["-lodm", "40.0", "-dmstep", "10.0", "-numdms", "3",
                     "-nsub", "8"]),
    ("prepdata", ["-dm", "60.0"]),
    ("prepdata", ["-dm", "0.0", "-zerodm"]),
])
def test_prep_outputs_match_time_major_decode(bursty_fil, tmp_path,
                                              monkeypatch, app, argv):
    """prepsubband's and prepdata's .dat files are byte-identical to
    those of a run whose reader decodes time-major blocks."""
    import importlib
    mod = importlib.import_module("presto_tpu.apps." + app)
    path = bursty_fil
    outs = {}
    for layout in ("chan", "time"):
        if layout == "time":
            monkeypatch.setattr(
                sigproc.FilterbankFile, "_decode_raw",
                lambda self, raw, nspec, chan_major=True:
                decode_spectra_block(self.header, raw, nspec, False))
        mod.run(mod.build_parser().parse_args(
            argv + ["-o", str(tmp_path / layout), path]))
        outs[layout] = sorted(tmp_path.glob(layout + "*.dat"))
    assert len(outs["chan"]) == len(outs["time"]) >= 1
    for a, b in zip(outs["chan"], outs["time"]):
        assert a.read_bytes() == b.read_bytes(), (a, b)


U32 = 2.0 ** -24


def _gamma(n):
    """Higham's bound on the relative error of a float32 sum of n terms
    of one sign, whatever the order of summation."""
    return n * U32 / (1 - n * U32)


def _time_major_clip(block, sigma, state):
    """The clipper's float32 arithmetic on a C-order block, as it ran on
    the reader's time-major blocks: row sums and the good rows' channel
    means (a matvec) over time-major memory.  state: (running avg,
    running std, channel running averages) or None."""
    zero_dm = block.sum(axis=1).astype(np.float64)
    med, std = float(np.median(zero_dm)), float(zero_dm.std())
    good = (zero_dm > med - 3.0 * std) & (zero_dm < med + 3.0 * std)
    avg, std = float(zero_dm[good].mean()), float(zero_dm[good].std())
    chan_avg = (good.astype(np.float32) @ block) / int(good.sum())
    if state is None:
        chan_avg = chan_avg.astype(np.float64)
    else:
        avg = 0.9 * state[0] + 0.1 * avg
        std = 0.9 * state[1] + 0.1 * std
        chan_avg = 0.9 * state[2] + 0.1 * chan_avg
    bad = np.abs(zero_dm - avg) > sigma * std
    out = block.copy()
    out[bad] = chan_avg.astype(np.float32)
    return out, bad, (avg, std, chan_avg)


def _time_major_zerodm(block):
    """remove_zerodm's float32 arithmetic on a C-order block (no
    bandpass given: the block's channel means)."""
    bandpass = block.mean(axis=0)
    wts = bandpass / bandpass.sum()
    zerodm = block.sum(axis=1, keepdims=True)
    return (block - wts[None, :] * zerodm
            + bandpass[None, :]).astype(np.float32)


def _positive_samples(nbits, seed=6):
    """[4 L, NCHAN] ascending samples with 1-3 bursts a block: 8-bit
    integers, or positive floats for a 32-bit file."""
    if nbits == 8:
        raw = np.concatenate(list(_raw_blocks(seed, 4)))
        return decode_spectra_block(_header(), raw, 4 * L)
    rng = np.random.default_rng(seed)
    vals = (np.linspace(80.0, 110.0, NCHAN)
            + rng.normal(0.0, 12.0, (4 * L, NCHAN)))
    for j in range(4):
        for _ in range(rng.integers(1, 4)):
            n = int(rng.integers(1, 25))
            t0 = j * L + int(rng.integers(0, L - n))
            vals[t0:t0 + n] += rng.uniform(60.0, 150.0)
    return np.maximum(vals, 1.0).astype(np.float32)


@pytest.mark.parametrize("ops", ["clip", "zerodm", "runavg",
                                 "zerodm+runavg"])
@pytest.mark.parametrize("nbits", [8, 32])
def test_prep_holds_time_major_float32_arithmetic(tmp_path, default_obs,
                                                  nbits, ops):
    """BlockPrep's clip, -zerodm and -runavg on the reader's
    channel-major blocks, against the float32 arithmetic they had on
    time-major blocks, with clipped rows in every block.

    The same rows are clipped and the others pass through unchanged.
    An 8-bit clip is bit-identical: its sums are of integers, exact in
    float32 in any order.  Otherwise a sum may round differently in
    another order (and -zerodm, -runavg now accumulate in float64), so
    each sample lies within 8 (γ_T + γ_C) + 16 u of the magnitude of
    the terms it is computed from: the worst-case error of the float32
    sums of T and C terms that the two sides compute, chained through
    clip, -zerodm and -runavg (u = 2^-24).  The channel-major side is
    also held to the float64 value of the same formulas on its own
    clipped block, within 2 γ_C + 16 u: of its sums only the bandpass
    total (C terms) stays float32."""
    hdr = FilterbankHeader(fch1=400.0, foff=-0.5, nchans=NCHAN,
                           nbits=nbits, tsamp=DT, nifs=1, N=4 * L)
    path = str(tmp_path / "f.fil")
    sigproc.write_filterbank(path, hdr, _positive_samples(nbits))
    zerodm, runavg = "zerodm" in ops, "runavg" in ops
    prep = BlockPrep(NCHAN, DT, _args(zerodm=zerodm, runavg=runavg))
    tol = 8 * (_gamma(L) + _gamma(NCHAN)) + 16 * U32
    tol_own = 2 * _gamma(NCHAN) + 16 * U32
    state = own_state = None
    nbad = 0
    with sigproc.FilterbankFile(path) as fb:
        for j in range(4):
            block = fb.read_spectra(j * L, L)
            assert _chan_major(block)
            own, _, own_state = clip_times(block, 6.0, own_state)
            got = prep(block, j * L)
            assert _chan_major(got) and got.dtype == np.float32
            raw = fb.read_spectra(j * L, L, chan_major=False)
            assert raw.flags.c_contiguous
            x, bad, state = _time_major_clip(raw, 6.0, state)
            assert bad.any()
            nbad += int(bad.sum())
            want = _time_major_zerodm(x) if zerodm else x
            if runavg:
                want = want - want.mean(axis=0, keepdims=True)
            if ops == "clip":
                np.testing.assert_array_equal(got[~bad], raw[~bad])
                if nbits == 8:
                    np.testing.assert_array_equal(got, want)
            a = np.abs(x.astype(np.float64))
            m = a.mean(axis=0)
            mag = a + m + (m / m.sum()) * a.sum(axis=1, keepdims=True)
            assert np.all(np.abs(got.astype(np.float64) - want)
                          <= tol * mag)
            exact = own.astype(np.float64)
            if zerodm:
                bp = exact.mean(axis=0)
                exact = (exact - bp / bp.sum()
                         * exact.sum(axis=1, keepdims=True) + bp)
            if runavg:
                exact = exact - exact.mean(axis=0)
            assert np.all(np.abs(got - exact) <= tol_own * mag)
    assert default_obs.metrics.counter(
        "ingest_clipped_rows_total").value == nbad
