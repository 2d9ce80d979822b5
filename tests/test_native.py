"""Native C++ IO runtime vs the pure-NumPy reference path.

Every native kernel (csrc/native_io.cpp) must agree bit-for-bit with
the Python implementation it accelerates — the same invariant the
reference holds between its C readers and lib/python pure-py readers
(SURVEY.md §4 item 8).
"""

import os

import numpy as np
import pytest

from presto_tpu.io import native
from presto_tpu.io import sigproc
from presto_tpu.io import psrfits as pf

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library not built")

RNG = np.random.default_rng(1234)


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
def test_unpack_bits_parity(nbits):
    raw = RNG.integers(0, 256, size=4096).astype(np.uint8)
    got = native.unpack_bits(raw, nbits)
    want = sigproc.unpack_bits(raw, nbits)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
@pytest.mark.parametrize("nifs", [1, 2])
@pytest.mark.parametrize("flip", [False, True])
def test_decode_spectra_parity(nbits, nifs, flip, monkeypatch):
    """Native and NumPy-fallback decodes equal the reference values:
    channel-major memory that owns its data by default, the C-order
    block with chan_major=False.  The shape spans more than one of the
    native decoder's tiles, with ragged edges on both axes."""
    nspec, nchan = 300, 40
    nvals = nspec * nifs * nchan
    raw = RNG.integers(0, 256, size=nvals * nbits // 8).astype(np.uint8)
    vals = sigproc.unpack_bits(raw, nbits)
    want = vals.astype(np.float32).reshape(nspec, nifs, nchan)
    want = want.sum(axis=1) if nifs > 1 else want[:, 0, :]
    if flip:
        want = want[:, ::-1]
    hdr = sigproc.FilterbankHeader(nchans=nchan, nifs=nifs, nbits=nbits,
                                   fch1=1500.0, foff=-1.0 if flip else 1.0)
    chan = [native.decode_spectra(raw, nspec, nifs, nchan, nbits, flip)]
    time = [native.decode_spectra(raw, nspec, nifs, nchan, nbits, flip,
                                  chan_major=False)]
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setenv("PRESTO_TPU_NO_NATIVE", "1")
    chan.append(sigproc.decode_spectra_block(hdr, raw, nspec))
    time.append(sigproc.decode_spectra_block(hdr, raw, nspec,
                                             chan_major=False))
    for got in chan + time:
        assert got.dtype == np.float32 and got.flags.owndata
        assert np.array_equal(got, want)
    for got in chan:
        assert got.flags.f_contiguous and not got.flags.c_contiguous
    for got in time:
        assert got.flags.c_contiguous and not got.flags.f_contiguous


@pytest.mark.parametrize("nbits", [2, 4, 8])
@pytest.mark.parametrize("npol,pol_mode", [(1, 0), (2, -2), (4, 1)])
def test_decode_subint_parity(nbits, npol, pol_mode):
    nspec, nchan = 11, 24
    raw = RNG.integers(0, 256,
                       size=nspec * npol * nchan * nbits // 8
                       ).astype(np.uint8)
    scl = RNG.uniform(0.5, 2.0, npol * nchan).astype(np.float32)
    offs = RNG.uniform(-3, 3, npol * nchan).astype(np.float32)
    wts = RNG.uniform(0, 1, nchan).astype(np.float32)
    zero_off = 1.5
    got = native.decode_subint(raw, nspec, npol, nchan, nbits, zero_off,
                               scl, offs, wts, pol_mode, True)
    # NumPy reference, same op order as PsrfitsSet._decode_row
    vals = pf.unpack_samples(raw, nbits).astype(np.float32)
    data = vals.reshape(nspec, npol, nchan) - zero_off
    data = data * scl.reshape(npol, nchan)[None] \
        + offs.reshape(npol, nchan)[None]
    if pol_mode == -2:
        data = data[:, 0, :] + data[:, 1, :]
    else:
        data = data[:, pol_mode, :]
    data = data * wts[None, :]
    data = data[:, ::-1]
    np.testing.assert_allclose(got, data, rtol=1e-6, atol=1e-5)


def test_filterbank_read_native_vs_python(tmp_path):
    """End-to-end: FilterbankFile.read_spectra with and without the
    native path must return identical blocks."""
    nchan, nspec = 16, 200
    hdr = sigproc.FilterbankHeader(
        nchans=nchan, nifs=1, nbits=4, tsamp=1e-4,
        fch1=1500.0, foff=-1.0, tstart=55000.0,
        source_name="synthetic")
    data = RNG.integers(0, 16, size=(nspec, nchan)).astype(np.float32)
    path = str(tmp_path / "t.fil")
    sigproc.write_filterbank(path, hdr, data)

    with sigproc.FilterbankFile(path) as f:
        blk_native = f.read_spectra(3, 50)
    os.environ["PRESTO_TPU_NO_NATIVE"] = "1"
    saved, native._lib = native._lib, None
    try:
        with sigproc.FilterbankFile(path) as f:
            blk_py = f.read_spectra(3, 50)
    finally:
        del os.environ["PRESTO_TPU_NO_NATIVE"]
        native._lib = saved
    assert np.array_equal(blk_native, blk_py)


def test_psrfits_read_native_vs_python(tmp_path):
    """PsrfitsSet.read_spectra native vs python decode parity."""
    nchan, nspec = 8, 128
    data = RNG.uniform(0, 100, size=(nspec, nchan)).astype(np.float32)
    path = str(tmp_path / "t.fits")
    freqs = 1400.0 - np.arange(nchan)
    pf.write_psrfits(path, data, dt=1e-4, freqs=freqs,
                     nsblk=32, nbits=8, start_mjd=55000.0)

    with pf.PsrfitsFile([path]) as s:
        blk_native = s.read_spectra(5, 60)
    os.environ["PRESTO_TPU_NO_NATIVE"] = "1"
    saved, native._lib = native._lib, None
    try:
        with pf.PsrfitsFile([path]) as s:
            blk_py = s.read_spectra(5, 60)
    finally:
        del os.environ["PRESTO_TPU_NO_NATIVE"]
        native._lib = saved
    np.testing.assert_allclose(blk_native, blk_py, rtol=1e-6)


def test_block_feeder_reads_whole_file(tmp_path):
    """BlockFeeder must deliver the exact file bytes, in order, with a
    short final block, regardless of prefetch buffering."""
    payload = RNG.integers(0, 256, size=10_000).astype(np.uint8)
    path = str(tmp_path / "raw.bin")
    header = b"HDRHDR"
    with open(path, "wb") as f:
        f.write(header)
        f.write(payload.tobytes())
    got = []
    with native.BlockFeeder(path, len(header), 1024, nbuf=3) as feeder:
        for blk in feeder:
            got.append(blk.copy())
    assert sum(len(b) for b in got) == payload.size
    assert len(got[-1]) == payload.size % 1024
    assert np.array_equal(np.concatenate(got), payload)


def test_stream_blocks_matches_read_spectra(tmp_path):
    """The prefetched stream must deliver exactly what blockwise
    read_spectra delivers, including the zero-padded tail."""
    nchan, nspec = 16, 5000
    hdr = sigproc.FilterbankHeader(
        nchans=nchan, nifs=1, nbits=8, tsamp=1e-4,
        fch1=1500.0, foff=-1.0, tstart=55000.0, source_name="s")
    data = RNG.integers(0, 255, size=(nspec, nchan)).astype(np.float32)
    path = str(tmp_path / "s.fil")
    sigproc.write_filterbank(path, hdr, data)
    blocklen = 1024
    with sigproc.FilterbankFile(path) as f:
        streamed = list(f.stream_blocks(blocklen))
        direct = list(f.iter_blocks(blocklen))
    assert len(streamed) == len(direct)
    for a, b in zip(streamed, direct):
        assert a.shape == b.shape == (blocklen, nchan)
        assert np.array_equal(a, b)
