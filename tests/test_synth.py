"""Synthetic generator sanity: injected tone appears at the right
frequency; dispersed filterbank dedisperses back to aligned pulses."""

import numpy as np
import jax.numpy as jnp

from presto_tpu.models.synth import (FakeSignal, fake_timeseries,
                                     fake_filterbank_data)
from presto_tpu.ops import fftpack
from presto_tpu.ops import dedispersion as dd
from presto_tpu.utils import psr


def test_fake_timeseries_tone_frequency():
    N, dt = 1 << 16, 1e-3
    f0 = 12.5
    sig = FakeSignal(f=f0, shape="sine", amp=2.0)
    x = fake_timeseries(N, dt, sig, noise_sigma=0.0)
    packed = np.asarray(fftpack.realfft_packed(jnp.asarray(x - x.mean())))
    powers = np.abs(packed) ** 2
    kmax = np.argmax(powers[1:]) + 1
    assert np.isclose(kmax / (N * dt), f0, atol=1.0 / (N * dt))


def test_choose_N():
    assert psr.choose_N(5000) == 0
    n = psr.choose_N(1000000)
    assert n >= 1000000
    assert n % 16 == 0


def test_fake_filterbank_dedisperses():
    """After dedispersing at the injection DM, folded S/N must beat the
    dispersed version by a wide margin."""
    N, nchan = 8192, 32
    dt, lofreq, cw = 1e-3, 400.0, 2.0  # low band: sweep spans ~2.7 periods
    dm = 200.0
    sig = FakeSignal(f=2.0, dm=dm, shape="gauss", width=0.05, amp=5.0)
    data = fake_filterbank_data(N, dt, nchan, lofreq, cw, sig,
                                noise_sigma=1.0, baseline=0.0)
    x = jnp.asarray(data.T)  # [nchan, N] channel-major

    delays = dd.dedisp_delays(nchan, dm, lofreq, cw)
    delays -= delays.min()   # reference to highest channel
    bins = dd.delays_to_bins(delays, dt)
    dedisp = np.asarray(dd.dedisperse_series(x, bins))
    nodisp = np.asarray(dd.dedisperse_series(x, np.zeros(nchan, np.int32)))

    def peakiness(series):
        nbins = 50
        valid = series[:N - int(bins.max())]
        phases = ((np.arange(valid.size) + 0.5) * dt * sig.f) % 1.0
        prof = np.bincount((phases * nbins).astype(int), weights=valid,
                           minlength=nbins)
        return (prof.max() - np.median(prof)) / (np.std(prof) + 1e-9)

    assert peakiness(dedisp) > 1.5 * peakiness(nodisp)


# write_beam: a beam like fake_filterbank_file's in constant memory.
# Its noise comes from one stream per block (spawned from the seed) so
# blocks are made in parallel; a byte match with fake_filterbank_file's
# single whole-array stream is impossible by that layout, so we pin
# determinism and that both files carry the same pulsar.
import pytest  # noqa: E402


@pytest.mark.parametrize("block", [777, 4096, 1 << 15])
def test_write_beam_is_seeded(tmp_path, block):
    from presto_tpu.models.synth import write_beam
    sig = FakeSignal(f=5.3, dm=40.0, amp=0.5)
    paths = []
    for d in "abc":
        (tmp_path / d).mkdir()
        paths.append(str(tmp_path / d / "beam.fil"))
    for path, seed in zip(paths, (7, 7, 8)):
        write_beam(path, 5000, 1e-3, 16, 1400.0, 2.0, signal=sig,
                   noise_sigma=2.0, seed=seed, block=block)
    a, b, c = (open(p, "rb").read() for p in paths)
    assert a == b and a != c
    assert len(a) == len(c) > 5000 * 16


def test_write_beam_carries_fake_filterbank_file_pulsar(tmp_path):
    """Same signal, same header and quantization: after dedispersion
    at the pulsar's DM both files carry the pulsar's fundamental."""
    from presto_tpu.io.sigproc import FilterbankFile
    from presto_tpu.models.synth import fake_filterbank_file, write_beam
    N, dt, nchan, lo, cw = 1 << 14, 5e-4, 32, 400.0, 2.0
    sig = FakeSignal(f=7.3, dm=80.0, amp=0.6, width=0.05)
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    whole = str(tmp_path / "a" / "beam.fil")
    chunked = str(tmp_path / "b" / "beam.fil")
    fake_filterbank_file(whole, N, dt, nchan, lo, cw, sig,
                         noise_sigma=2.0, seed=3)
    write_beam(chunked, N, dt, nchan, lo, cw, signal=sig,
               noise_sigma=2.0, seed=3, block=3000)
    heads = [open(p, "rb").read(4096) for p in (whole, chunked)]
    end = heads[0].index(b"HEADER_END") + len(b"HEADER_END")
    assert heads[0][:end] == heads[1][:end]
    d = dd.dedisp_delays(nchan, 80.0, lo, cw)
    bins = dd.delays_to_bins(d - d.min(), dt)
    k0 = int(round(7.3 * (N - 2048) * dt))
    for path in (whole, chunked):
        with FilterbankFile(path) as fb:
            x = jnp.asarray(fb.read_spectra(0, N).T)
        s = np.asarray(dd.dedisperse_series(x, bins))[:N - 2048]
        pw = np.abs(np.fft.rfft(s - s.mean())) ** 2
        assert pw[k0 - 1:k0 + 2].max() / np.median(pw[1:]) > 30


def test_write_beam_injected_pulsar_dedisperses(tmp_path):
    """The inject= path (models/inject per block) puts the pulsar at
    its DM: dedispersing at the injected DM beats DM 0."""
    from presto_tpu.io.sigproc import FilterbankFile
    from presto_tpu.models.inject import InjectParams, amp_for_snr
    from presto_tpu.models.synth import write_beam
    N, dt, nchan, lo, cw = 1 << 14, 5e-4, 32, 400.0, 2.0
    p = InjectParams(f=7.3, dm=80.0, width=0.05)
    p.amp = amp_for_snr(40.0, p, N, 2.0, nchan)
    path = str(tmp_path / "psr.fil")
    write_beam(path, N, dt, nchan, lo, cw, noise_sigma=2.0, seed=3,
               inject=p, block=3000)
    with FilterbankFile(path) as fb:
        x = jnp.asarray(fb.read_spectra(0, N).T)
    peaks = []
    for dm in (0.0, 80.0):
        d = dd.dedisp_delays(nchan, dm, lo, cw)
        series = np.asarray(dd.dedisperse_series(
            x, dd.delays_to_bins(d - d.min(), dt)))[:N - 2048]
        s = series - series.mean()
        pw = np.abs(np.fft.rfft(s)) ** 2
        k = int(round(7.3 * len(s) * dt))
        peaks.append(pw[k - 2:k + 3].max() / np.median(pw[1:]))
    assert peaks[1] > 2 * peaks[0] and peaks[1] > 50
