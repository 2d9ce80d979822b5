"""Plain reference of prepsubband's streaming path, in float64 NumPy.

Imports nothing of the program.  Semantics (PRESTO prepsubband,
dispersion.c, clipping.c):

- 8-bit spectra decoded to floats, channels flipped to ascending
  frequency when the band is recorded descending;
- clip_times: the zero-DM series of each block, its median and std,
  then mean/std and per-channel means over samples within 3 std of the
  median, folded into running values (0.9 old + 0.1 new after the
  first block); samples whose zero-DM value lies more than
  clip_sigma * running std from the running mean are replaced by the
  running channel means;
- delays: delay = dm / (0.000241 f^2) s; channel delays at the centre
  DM relative to the highest channel of their subband, subband delays
  (highest channel of each subband) per DM relative to the smallest,
  both rounded half up to samples;
- y[dm, t] = sum_s sum_{c in s} x[c, t + d_c + d_{dm,s}].

``lowp=True`` is the control: both sums accumulated in bfloat16.
"""

from __future__ import annotations

from collections import deque

import numpy as np

from perfbench.reference.search_ref import bf16


def delay_s(dm, freqs):
    f = np.asarray(freqs, np.float64)
    return dm / (0.000241 * f * f)


def plan(nchan, nsub, lofreq, chanwidth, lodm, numdms, dmstep, dt):
    """(chan_bins [nchan], dm_bins [ndms, nsub]) for ascending channels
    starting at lofreq (centre of the lowest channel)."""
    dms = lodm + np.arange(numdms) * dmstep
    per = nchan // nsub
    center = lodm + 0.5 * (numdms - 1) * dmstep
    freqs = lofreq + np.arange(nchan) * chanwidth
    subw = chanwidth * per
    subhi = lofreq + subw - chanwidth + np.arange(nsub) * subw
    chan = delay_s(center, freqs) - np.repeat(delay_s(center, subhi), per)
    sub = np.stack([delay_s(dm, subhi) for dm in dms])
    sub -= sub.min()
    tobins = lambda d: np.floor(d / dt + 0.5).astype(np.int64)
    return tobins(chan), tobins(sub)


class Clipper:
    """clip_times with its running state."""

    def __init__(self, sigma: float):
        self.sigma = sigma
        self.n = 0
        self.avg = self.std = 0.0
        self.chan = None

    def update(self, block: np.ndarray) -> np.ndarray:
        """Advance the state over one block; returns the clipped rows."""
        z = block.sum(axis=1, dtype=np.float64)
        med, std = float(np.median(z)), float(z.std())
        good = (z > med - 3 * std) & (z < med + 3 * std)
        if good.any():
            cavg, cstd = float(z[good].mean()), float(z[good].std())
            chan = good.astype(np.float64) @ block / good.sum()
        else:
            cavg, cstd = self.avg, self.std
            chan = (self.chan if self.chan is not None
                    else block.mean(axis=0, dtype=np.float64))
        if self.n:
            self.avg = 0.9 * self.avg + 0.1 * cavg
            self.std = 0.9 * self.std + 0.1 * cstd
            self.chan = 0.9 * self.chan + 0.1 * chan
        else:
            self.avg, self.std, self.chan = cavg, cstd, chan.copy()
        self.n += 1
        return np.abs(z - self.avg) > self.sigma * self.std

    def __call__(self, block: np.ndarray) -> np.ndarray:
        bad = self.update(block)
        out = np.array(block, np.float64)
        out[bad] = self.chan
        return out


def decode(raw: np.ndarray, blocklen: int, nchan: int,
           descending: bool) -> np.ndarray:
    """Exact float32 values of the 8-bit samples, ascending channels."""
    x = raw.reshape(blocklen, nchan).astype(np.float32)
    return x[:, ::-1] if descending else x


def outputs(raw_of, nblocks_needed, steps, rows, blocklen, nchan, nsub,
            chan_bins, dm_bins, clip_sigma, descending, lowp=False):
    """Reference series for the sampled steps: {step: [len(rows), L]}.
    raw_of(j) gives the raw bytes of stream block j; the clip state is
    replayed from block 0 (blocks no sampled step reads only advance
    it)."""
    clip = Clipper(clip_sigma)
    window = deque(maxlen=3)
    want = set(steps)
    keep = {j - k for j in want for k in range(3)}
    out = {}
    L = blocklen
    per = nchan // nsub
    for j in range(nblocks_needed):
        x = decode(raw_of(j), blocklen, nchan, descending)
        if j not in keep:
            clip.update(x)
            window.append(None)
            continue
        window.append(clip(x).T)                # [nchan, L]
        if j not in want:
            continue
        w3 = np.concatenate(list(window), axis=1)   # [nchan, 3L]
        sub = np.zeros((nsub, 2 * L))
        for c in range(nchan):
            v = w3[c, chan_bins[c]:chan_bins[c] + 2 * L]
            s = c // per
            sub[s] = bf16(sub[s] + bf16(v)) if lowp else sub[s] + v
        ys = np.zeros((len(rows), L))
        for k, dm in enumerate(rows):
            acc = np.zeros(L)
            for s in range(nsub):
                v = sub[s, dm_bins[dm, s]:dm_bins[dm, s] + L]
                acc = bf16(acc + v) if lowp else acc + v
            ys[k] = acc
        out[j] = ys
    return out


def gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap as a share of the reference series' noise (its std
    over the compared samples)."""
    scale = float(np.std(ref)) or 1.0
    return float(np.max(np.abs(np.asarray(prog, np.float64) - ref)) / scale)
