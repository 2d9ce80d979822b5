"""rfifind: RFI detection over (interval × channel) cells, TPU-batched.

Reference call stack (src/rfifind.c:300-470 + src/rfifind_plot.c:69-280):
for each interval × channel: time-domain avg/std + max FFT power of the
interval's channel series; thresholds from robust (middle-fraction)
statistics; bytemask bits BAD_POW/BAD_AVG/BAD_STD; whole-row/column
rejection above trigger fractions; fill_mask -> .mask/.stats artifacts.

TPU-first: the per-(int,chan) stats are one batched device program —
[numint*numchan, ptsperint] real FFTs + reductions — instead of the
reference's nested loop around a scalar FFT.  Thresholding and mask
assembly are host-side float64 numpy (tiny data).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from presto_tpu.io.maskfile import (Mask, fill_mask, write_mask,
                                    write_statsfile, BAD_POW, BAD_AVG,
                                    BAD_STD, BADDATA, USERCHAN, USERINTS,
                                    PADDING)
from presto_tpu.ops.stats import power_for_sigma


def calc_avgmedstd(arr: np.ndarray, fraction: float,
                   axis: Optional[int] = None):
    """avg/median/std of the middle `fraction` of the sorted values.
    Parity: calc_avgmedstd (mask.c:149-174).  Vectorized over `axis`."""
    a = np.sort(np.asarray(arr, dtype=np.float64), axis=axis)
    if axis is None:
        a = a.ravel()
        n = a.size
        length = int(n * fraction + 0.5)
        start = (n - length) // 2
        mid = a[start:start + length]
        return float(mid.mean()), float(a[n // 2]), float(mid.std())
    n = a.shape[axis]
    length = int(n * fraction + 0.5)
    start = (n - length) // 2
    sl = [slice(None)] * a.ndim
    sl[axis] = slice(start, start + length)
    mid = a[tuple(sl)]
    med_sl = [slice(None)] * a.ndim
    med_sl[axis] = n // 2
    return (mid.mean(axis=axis), a[tuple(med_sl)], mid.std(axis=axis))


@partial(jax.jit, static_argnames=("ptsperint",))
def _interval_stats(chunk, ptsperint):
    """Batched per-cell statistics.

    chunk: [ncells, ptsperint] float32 (each row = one interval×channel
    series).  Returns (avg[ncells], std[ncells], maxpow[ncells]) where
    maxpow is the max normalized spectral power over bins 1..n/2-1,
    normalization = var * ptsperint (rfifind.c:370-377).
    """
    avg = chunk.mean(axis=-1)
    var = chunk.var(axis=-1)
    spec = jnp.fft.rfft(chunk, axis=-1)
    pows = jnp.abs(spec[..., 1:-1]) ** 2
    norm = jnp.where(var == 0.0, 1.0, var * ptsperint)
    maxpow = pows.max(axis=-1) / norm
    return avg, jnp.sqrt(var), maxpow


@dataclass
class RfifindResult:
    dataavg: np.ndarray       # [numint, numchan]
    datastd: np.ndarray
    datapow: np.ndarray
    bytemask: np.ndarray      # [numint, numchan] uint8
    mask: Mask
    ptsperint: int

    def masked_fraction(self) -> float:
        return float(((self.bytemask & (BADDATA | USERCHAN | USERINTS))
                      != 0).mean())


def rfifind(data: np.ndarray, dt: float, lofreq: float, chanwidth: float,
            time_sec: float = 30.0, timesigma: float = 10.0,
            freqsigma: float = 4.0, chantrigfrac: float = 0.7,
            inttrigfrac: float = 0.3, mjd: float = 0.0,
            zap_chans=(), zap_ints=(),
            ptsperint: Optional[int] = None) -> RfifindResult:
    """Run the rfifind analysis over [N, numchan] time-major data.

    time_sec: integration time per interval (the -time flag, default
    rfifind.c's 30 s).  Returns stats + bytemask + Mask.
    """
    N, numchan = data.shape
    if ptsperint is None:
        ptsperint = max(1, int(time_sec / dt + 0.5))
    numint = N // ptsperint
    if numint < 1:
        raise ValueError("data shorter than one rfifind interval")

    def intervals():
        for i in range(numint):
            yield data[i * ptsperint:(i + 1) * ptsperint]

    return rfifind_stream(intervals(), numchan, ptsperint, dt, lofreq,
                          chanwidth, timesigma, freqsigma, chantrigfrac,
                          inttrigfrac, mjd, zap_chans, zap_ints)


def rfifind_stream(intervals, numchan: int, ptsperint: int, dt: float,
                   lofreq: float, chanwidth: float,
                   timesigma: float = 10.0, freqsigma: float = 4.0,
                   chantrigfrac: float = 0.7, inttrigfrac: float = 0.3,
                   mjd: float = 0.0, zap_chans=(), zap_ints=()
                   ) -> RfifindResult:
    """Streaming rfifind: one [ptsperint, numchan] block at a time, so
    the whole observation is never resident on the host (the reference
    also reads interval-by-interval via get_channel, rfifind.c:323-403).
    """
    avgs, stds, pows = [], [], []
    for block in intervals:
        cells = np.ascontiguousarray(
            block.T, dtype=np.float32)           # [numchan, ptsperint]
        a, s, p = _interval_stats(jnp.asarray(cells), ptsperint)
        avgs.append(np.asarray(a))
        stds.append(np.asarray(s))
        pows.append(np.asarray(p))
    numint = len(avgs)
    if numint < 1:
        raise ValueError("data shorter than one rfifind interval")
    dataavg = np.stack(avgs)
    datastd = np.stack(stds)
    datapow = np.stack(pows)

    bytemask = _threshold(dataavg, datastd, datapow, ptsperint,
                          timesigma, freqsigma, chantrigfrac, inttrigfrac,
                          list(zap_chans), list(zap_ints))
    userchan = sorted({c for c in range(numchan)
                       if (bytemask[:, c] & USERCHAN).all()})
    userints = sorted({i for i in range(numint)
                       if (bytemask[i] & USERINTS).all()})
    m = fill_mask(timesigma, freqsigma, mjd, ptsperint * dt, lofreq,
                  chanwidth, numchan, numint, ptsperint, userchan,
                  userints, bytemask)
    return RfifindResult(dataavg=dataavg, datastd=datastd,
                         datapow=datapow, bytemask=bytemask, mask=m,
                         ptsperint=ptsperint)


def _threshold(dataavg, datastd, datapow, ptsperint, timesigma, freqsigma,
               chantrigfrac, inttrigfrac, zap_chans, zap_ints):
    """Bytemask generation. Parity: rfifind_plot.c:126-268."""
    numint, numchan = dataavg.shape
    bytemask = np.zeros((numint, numchan), dtype=np.uint8)

    # global robust stats (rfifind_plot.c:131-136)
    _, dataavg_med, dataavg_std = calc_avgmedstd(dataavg, 0.8)
    _, datastd_med, datastd_std = calc_avgmedstd(datastd, 0.8)
    avg_reject = timesigma * dataavg_std
    std_reject = timesigma * datastd_std
    pow_reject = power_for_sigma(freqsigma, 1, ptsperint / 2)

    # per-interval and per-channel medians (rfifind_plot.c:139-155)
    _, avg_int_med, _ = calc_avgmedstd(dataavg, 0.8, axis=1)
    _, std_int_med, _ = calc_avgmedstd(datastd, 0.8, axis=1)
    _, avg_chan_med, _ = calc_avgmedstd(dataavg, 0.8, axis=0)
    _, std_chan_med, _ = calc_avgmedstd(datastd, 0.8, axis=0)

    # user zaps
    for i in zap_ints:
        if 0 <= i < numint:
            bytemask[i, :] |= USERINTS
    for c in zap_chans:
        if 0 <= c < numchan:
            bytemask[:, c] |= USERCHAN

    # powers (rfifind_plot.c:186-191)
    bytemask[datapow > pow_reject] |= BAD_POW

    # averages: deviation from interval/channel median, with medians
    # snapped to the global when themselves outlying (:192-208)
    int_med = np.where(np.abs(avg_int_med - dataavg_med)
                       > timesigma * dataavg_std, dataavg_med, avg_int_med)
    chan_med = np.where(np.abs(avg_chan_med - dataavg_med)
                        > timesigma * dataavg_std, dataavg_med,
                        avg_chan_med)
    bad_avg = (np.abs(dataavg - int_med[:, None]) > avg_reject) | \
              (np.abs(dataavg - chan_med[None, :]) > avg_reject)
    bytemask[bad_avg] |= BAD_AVG

    # standard deviations (:209-224)
    int_med = np.where(np.abs(std_int_med - datastd_med)
                       > timesigma * datastd_std, datastd_med, std_int_med)
    chan_med = np.where(np.abs(std_chan_med - datastd_med)
                        > timesigma * datastd_std, datastd_med,
                        std_chan_med)
    bad_std = (np.abs(datastd - int_med[:, None]) > std_reject) | \
              (np.abs(datastd - chan_med[None, :]) > std_reject)
    bytemask[bad_std] |= BAD_STD

    # whole-interval / whole-channel triggers (:230-268)
    bad = (bytemask & BADDATA) != 0
    int_trig = int(numchan * chantrigfrac)
    for i in np.flatnonzero(bad.sum(axis=1) > int_trig):
        bytemask[i, :] |= USERINTS
    chan_trig = int(numint * inttrigfrac)
    for c in np.flatnonzero(bad.sum(axis=0) > chan_trig):
        bytemask[:, c] |= USERCHAN
    return bytemask


def rfifind_from_stats(stats: dict, dt: float, lofreq: float,
                       chanwidth: float, timesigma: float = 10.0,
                       freqsigma: float = 4.0,
                       chantrigfrac: float = 0.7,
                       inttrigfrac: float = 0.3, mjd: float = 0.0,
                       zap_chans=(), zap_ints=()) -> RfifindResult:
    """Re-threshold previously computed statistics (the -nocompute
    path, rfifind.c:414-429: re-plot and remake the mask from the
    .stats file without touching the raw data).  `stats` is the dict
    from io.maskfile.read_statsfile."""
    dataavg = stats["dataavg"]
    datastd = stats["datastd"]
    datapow = stats["datapow"]
    ptsperint = int(stats["ptsperint"])
    numint, numchan = dataavg.shape
    bytemask = _threshold(dataavg, datastd, datapow, ptsperint,
                          timesigma, freqsigma, chantrigfrac,
                          inttrigfrac, list(zap_chans), list(zap_ints))
    userchan = sorted({c for c in range(numchan)
                       if (bytemask[:, c] & USERCHAN).all()})
    userints = sorted({i for i in range(numint)
                       if (bytemask[i] & USERINTS).all()})
    m = fill_mask(timesigma, freqsigma, mjd, ptsperint * dt, lofreq,
                  chanwidth, numchan, numint, ptsperint, userchan,
                  userints, bytemask)
    return RfifindResult(dataavg=dataavg, datastd=datastd,
                         datapow=datapow, bytemask=bytemask, mask=m,
                         ptsperint=ptsperint)


def write_rfifind_products(result: RfifindResult, rootname: str,
                           lobin: int = 0, numbetween: int = 2) -> None:
    """Write rootname_rfifind.mask and rootname_rfifind.stats."""
    write_mask(rootname + "_rfifind.mask", result.mask)
    write_statsfile(rootname + "_rfifind.stats", result.datapow,
                    result.dataavg, result.datastd, result.ptsperint,
                    lobin, numbetween)
