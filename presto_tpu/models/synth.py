"""Synthetic pulsar data generation — the makedata/injectpsr analog.

The reference's makedata (src/makedata.c + src/com.c) generates .dat
time series from closed-form signal parameters (pulse shape, f/fdot/
fdotdot, amplitude, phase, binary orbit, noise) described by .mak files;
its test suite builds on exact knowledge of the injected signal
(SURVEY.md §4.2).  This module provides the same ground-truth role:
every search stage is validated against data whose answer is known in
closed form.

All generation is float64 numpy on the host (it is setup/test code, not
a hot path).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

import numpy as np

from presto_tpu.io.infodata import InfoData, ARTIFICIAL_TELESCOPE
from presto_tpu.io.sigproc import FilterbankHeader, write_filterbank
from presto_tpu.ops.dedispersion import delay_from_dm


def pulse_shape(phases: np.ndarray, shape: str = "sine",
                width: float = 0.1) -> np.ndarray:
    """Pulse amplitude at fractional phases in [0,1).

    Shapes follow makedata's menu (src/com.c): 'sine', 'gauss' (fwhm =
    `width` in phase units), 'crab' (fast-rise exponential-decay-ish).
    All normalized to peak 1.
    """
    ph = np.mod(phases, 1.0)
    if shape == "sine":
        return 0.5 * (1.0 + np.sin(2 * np.pi * ph))
    if shape == "gauss":
        sigma = width / 2.35482
        return np.exp(-0.5 * ((ph - 0.5) / sigma) ** 2)
    if shape == "crab":
        return np.exp(-np.minimum(ph, 1 - ph) / width)
    raise ValueError("unknown pulse shape %r" % shape)


@dataclass
class FakeSignal:
    """Closed-form signal description (the .mak analog)."""
    f: float = 1.0               # Hz at t=0
    fdot: float = 0.0            # Hz/s
    fdotdot: float = 0.0         # Hz/s^2
    amp: float = 1.0
    phase0: float = 0.0          # turns
    shape: str = "gauss"
    width: float = 0.1           # fractional pulse width (gauss fwhm)
    dm: float = 0.0

    def phase(self, t: np.ndarray) -> np.ndarray:
        """Integrated phase in turns at times t (s): f t + fd t²/2 + fdd t³/6."""
        return (self.phase0 + self.f * t + 0.5 * self.fdot * t * t
                + self.fdotdot * t ** 3 / 6.0)


def fake_timeseries(N: int, dt: float, signal: FakeSignal,
                    noise_sigma: float = 0.0,
                    seed: Optional[int] = 42) -> np.ndarray:
    """Noise + pulsed signal sampled at bin centers."""
    t = (np.arange(N) + 0.5) * dt
    data = signal.amp * pulse_shape(signal.phase(t), signal.shape,
                                    signal.width)
    if noise_sigma > 0:
        rng = np.random.default_rng(seed)
        data = data + rng.normal(0.0, noise_sigma, N)
    return data.astype(np.float32)


def _channel_delays(nchan: int, lofreq: float, chanwidth: float,
                    dm: float) -> np.ndarray:
    """Per-channel dispersive delays (s), ascending channels, the
    highest channel at zero — the dedispersion ops' reference."""
    freqs = lofreq + np.arange(nchan) * chanwidth
    delays = delay_from_dm(dm, freqs)
    return delays - delays.min()


def _signal_into(out: np.ndarray, t: np.ndarray, delays: np.ndarray,
                 signal: FakeSignal) -> None:
    """out[:, c] = the pulsar's amplitude at t - delays[c]."""
    for c in range(out.shape[1]):
        ph = signal.phase(t - delays[c])
        out[:, c] = signal.amp * pulse_shape(ph, signal.shape,
                                             signal.width)


def fake_filterbank_data(N: int, dt: float, nchan: int, lofreq: float,
                         chanwidth: float, signal: FakeSignal,
                         noise_sigma: float = 0.0,
                         baseline: float = 10.0,
                         seed: Optional[int] = 42) -> np.ndarray:
    """[N, nchan] float32, ascending frequency, with the pulsar's pulses
    arriving later in lower-frequency channels per the cold-plasma delay
    (delay_from_dm).  The highest channel has zero extra delay offset —
    matching how dedispersion references delays to the band."""
    delays = _channel_delays(nchan, lofreq, chanwidth, signal.dm)
    t = (np.arange(N) + 0.5) * dt
    out = np.empty((N, nchan), dtype=np.float32)
    _signal_into(out, t, delays, signal)
    out += baseline
    if noise_sigma > 0:
        rng = np.random.default_rng(seed)
        out += rng.normal(0.0, noise_sigma, out.shape).astype(np.float32)
    return out


def _beam_header(path: str, dt: float, nchan: int, lofreq: float,
                 chanwidth: float, nbits: int,
                 tstart_mjd: float) -> FilterbankHeader:
    return FilterbankHeader(
        # GBT + a real sky position (the Crab) so the default
        # barycentering path in the prep tools is exercised end-to-end
        source_name="FAKEPSR", machine_id=10, telescope_id=6,
        src_raj=53431.97, src_dej=220052.1,
        fch1=lofreq + (nchan - 1) * chanwidth, foff=-chanwidth,
        nchans=nchan, nbits=nbits, tstart=tstart_mjd, tsamp=dt, nifs=1,
        rawdatafile=path.split("/")[-1])


def _quantize8(data: np.ndarray) -> np.ndarray:
    return np.clip(np.round(data * 4.0), 0, 255).astype(np.uint8)


def fake_filterbank_file(path: str, N: int, dt: float, nchan: int,
                         lofreq: float, chanwidth: float,
                         signal: FakeSignal, noise_sigma: float = 0.0,
                         nbits: int = 8, tstart_mjd: float = 59000.0,
                         seed: Optional[int] = 42) -> FilterbankHeader:
    """Write a synthetic 8-bit .fil with an injected pulsar."""
    data = fake_filterbank_data(N, dt, nchan, lofreq, chanwidth, signal,
                                noise_sigma, baseline=32.0, seed=seed)
    if nbits == 8:
        q = _quantize8(data)
    elif nbits == 32:
        q = data
    else:
        maxv = (1 << nbits) - 1
        q = np.clip(np.round(data * maxv / data.max()), 0, maxv).astype(
            np.uint16 if nbits == 16 else np.uint8)
    hdr = _beam_header(path, dt, nchan, lofreq, chanwidth, nbits,
                       tstart_mjd)
    write_filterbank(path, hdr, q)
    return hdr


def write_beam(path: str, N: int, dt: float, nchan: int,
               lofreq: float, chanwidth: float,
               signal: Optional[FakeSignal] = None,
               noise_sigma: float = 0.0, tstart_mjd: float = 59000.0,
               seed: Optional[int] = 42, inject=None,
               block: int = 1 << 15) -> FilterbankHeader:
    """An 8-bit beam like fake_filterbank_file's (same header, baseline
    and quantization) in constant memory, ``block`` spectra at a time
    (~block * nchan * 16 bytes of host memory for any length).

    Each block draws its noise from its own stream, spawned from
    ``seed`` (numpy SeedSequence), so blocks are made in parallel on
    every core — a 2 GiB beam in seconds rather than minutes.  The
    bytes are therefore a function of (seed, block), and differ from
    fake_filterbank_file's single whole-array stream.

    ``inject``: models/inject.InjectParams of one pulsar added to every
    block by inject.inject_pulsar (its smeared, per-channel model) after
    the noise and before quantization; ``signal`` is synth's own closed
    form (None: noise only)."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from presto_tpu.io.atomic import atomic_open
    from presto_tpu.io.sigproc import write_filterbank_header
    from presto_tpu.models.inject import inject_pulsar
    hdr = _beam_header(path, dt, nchan, lofreq, chanwidth, 8, tstart_mjd)
    starts = range(0, N, block)
    streams = np.random.SeedSequence(seed).spawn(len(starts))
    delays = (_channel_delays(nchan, lofreq, chanwidth, signal.dm)
              if signal is not None else None)
    freqs = lofreq + np.arange(nchan) * chanwidth

    def make(s0: int, stream) -> bytes:
        n = min(block, N - s0)
        out = np.full((n, nchan), 32.0, np.float32)
        if signal is not None:
            sig = np.empty_like(out)
            _signal_into(sig, (np.arange(s0, s0 + n) + 0.5) * dt,
                         delays, signal)
            out += sig
        if noise_sigma > 0:
            noise = np.random.default_rng(stream).standard_normal(
                (n, nchan), dtype=np.float32)
            noise *= np.float32(noise_sigma)
            out += noise
        if inject is not None:
            out = inject_pulsar(out, dt, freqs, inject,
                                start_sec=s0 * dt)
        return _quantize8(out)[:, ::-1].tobytes()   # foff < 0 on disk

    nwork = os.cpu_count() or 1
    with atomic_open(path, "wb") as f, \
            ThreadPoolExecutor(nwork) as pool:
        write_filterbank_header(hdr, f)
        pending = deque()
        for s0, stream in zip(starts, streams):
            pending.append(pool.submit(make, s0, stream))
            if len(pending) > nwork + 1:
                f.write(pending.popleft().result())
        while pending:
            f.write(pending.popleft().result())
    return hdr


def artificial_inf(name: str, N: int, dt: float, dm: float = 0.0,
                   **kw) -> InfoData:
    return InfoData(name=name, telescope=ARTIFICIAL_TELESCOPE,
                    N=float(N), dt=dt, dm=dm, **kw)
