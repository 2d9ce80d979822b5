"""Single-pulse (matched-filter) search, TPU-batched.

Reference algorithm (bin/single_pulse_search.py:252-516): per .dat file,
linear-detrend 1000-sample blocks, robust per-block stds with a
4-sigma bad-block cut, normalize to RMS=1, then slide fftlen=8192
chunks (chunklen=8000 + overlap) over the series convolving each with
boxcar kernels of widths [1,2,3,4,6,9,14,20,30,...] via rfft
multiply (make_fftd_kerns / fft_convolve, :29-61), threshold > sigma,
and greedily prune nearby weaker events (prune_related1/2 :63-117).

TPU-first redesign: the per-chunk, per-width Python loop becomes ONE
batched device program — [nchunks, fftlen] rfft, broadcast multiply
against the [nwidths, nf] kernel bank, batched irfft, and a
lax.top_k per (chunk, width) row so only O(k) candidates ever cross
the device->host boundary (the reference's flatnonzero pulls the full
smoothed series to host).  Detrending is a closed-form batched
least-squares over [nblocks, detrendlen] instead of a per-block
scipy.signal.detrend loop.  Candidate pruning (tiny lists) stays on
host, matching the reference's semantics exactly.

Unlike PRESTO's packed-format rfft, numpy/jax rfft keeps the Nyquist
bin separate, so fft_convolve's real[0]/imag[0] patch
(single_pulse_search.py:40-42) is unnecessary here.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

DEFAULT_DOWNFACTS = (2, 3, 4, 6, 9, 14, 20, 30, 45, 70, 100, 150, 220, 300)
MAX_DOWNFACT = 30


@dataclass(order=True)
class SPCandidate:
    """One single-pulse event (sorted by sample bin, like the reference)."""
    bin: int
    sigma: float = field(compare=False)
    time: float = field(compare=False)
    downfact: int = field(compare=False)
    dm: float = field(compare=False, default=0.0)

    def __str__(self) -> str:
        return "%7.2f %7.2f %13.6f %10d     %3d\n" % (
            self.dm, self.sigma, self.time, self.bin, self.downfact)


def boxcar_kernels(downfacts: Sequence[int], fftlen: int) -> np.ndarray:
    """Circular centered boxcar kernels, RMS-preserving 1/sqrt(w) norm.

    Parity: make_fftd_kerns (bin/single_pulse_search.py:45-61); the
    tap layout reproduces scipy.signal.convolve centering.  Width 1 is
    the identity (raw, un-smoothed search path).
    """
    kerns = np.zeros((len(downfacts), fftlen), dtype=np.float32)
    for i, df in enumerate(downfacts):
        if df == 1:
            kerns[i, 0] = 1.0
            continue
        if df % 2:
            kerns[i, :df // 2 + 1] = 1.0
            kerns[i, -(df // 2):] = 1.0
        else:
            kerns[i, :df // 2 + 1] = 1.0
            if df > 2:
                kerns[i, -(df // 2 - 1):] = 1.0
        kerns[i] /= np.sqrt(df)
    return kerns


@partial(jax.jit, static_argnames=("detrendlen", "fast"))
def _detrend_blocks(blocks, detrendlen, fast):
    """Batched per-block detrend + robust std.

    blocks: [nblocks, detrendlen] float32.
    fast=False: remove per-block linear least-squares fit (reference's
    scipy.signal.detrend(type='linear') loop).  fast=True: remove the
    per-block median only (the -f/--fast path).
    Robust std: central 95% of the sorted residuals, with the 1.148
    clipped-Gaussian correction (single_pulse_search.py:380-393).
    """
    n = detrendlen
    if fast:
        med = jnp.median(blocks, axis=-1, keepdims=True)
        resid = blocks - med
    else:
        t = jnp.arange(n, dtype=jnp.float32)
        tbar = (n - 1) / 2.0
        tvar = jnp.sum((t - tbar) ** 2)
        xbar = blocks.mean(axis=-1, keepdims=True)
        slope = ((blocks - xbar) @ (t - tbar)) / tvar
        resid = blocks - xbar - slope[:, None] * (t - tbar)
    s = jnp.sort(resid, axis=-1)
    inner = s[:, n // 40: n - n // 40]
    stds = jnp.sqrt((inner ** 2).sum(axis=-1) / (0.95 * n)) * 1.148
    return resid, stds


def flag_bad_blocks(stds: np.ndarray) -> Tuple[np.ndarray, float, float]:
    """Identify blocks with outlying stds (dropouts / bursts of RFI).

    Parity: the locut/hicut split-off of the sorted stds and the
    +/-4 sigma cut (single_pulse_search.py:395-416).  Returns
    (bad_block_indices, median_stds, std_stds).
    """
    nb = len(stds)
    if nb < 4:
        return np.empty(0, dtype=np.int64), float(np.median(stds)), 0.0
    ss = np.sort(stds.astype(np.float64))
    locut = int(np.argmax(ss[1:nb // 2 + 1] - ss[:nb // 2])) + 1
    hicut = int(np.argmax(ss[nb // 2 + 1:] - ss[nb // 2:-1])) + nb // 2 - 2
    if hicut <= locut:
        locut, hicut = 0, nb
    std_stds = float(np.std(ss[locut:hicut]))
    median_stds = float(ss[(locut + hicut) // 2])
    lo, hi = median_stds - 4.0 * std_stds, median_stds + 4.0 * std_stds
    bad = np.flatnonzero((stds < lo) | (stds > hi))
    return bad, median_stds, std_stds


@partial(jax.jit, static_argnames=("fftlen", "overlap", "k"))
def _convolve_topk(chunks, kern_pairs, threshold, fftlen, overlap, k):
    """Batched boxcar matched filter + per-row candidate extraction.

    chunks: [B, fftlen] normalized data; kern_pairs: [W, nf, 2] float32
    (re/im pairs — the float32 host<->device boundary shared with
    search/accel.py and ops/fftpack.py).
    Returns (vals[B,W,k], idx[B,W,k], counts[B,W]) where (vals, idx)
    are the top-k smoothed samples of the central chunklen window and
    counts is the exact number above threshold (overflow detector for
    the fixed-capacity extraction).
    """
    kern_rfft = jax.lax.complex(kern_pairs[..., 0], kern_pairs[..., 1])
    cf = jnp.fft.rfft(chunks, axis=-1)
    prod = cf[:, None, :] * kern_rfft[None, :, :]
    sm = jnp.fft.irfft(prod, n=fftlen, axis=-1)
    good = sm[..., overlap:fftlen - overlap]
    vals, idx = jax.lax.top_k(good, k)
    counts = (good > threshold).sum(axis=-1)
    return vals, idx, counts


def prune_related1(bins: List[int], vals: List[float],
                   downfact: int) -> Tuple[List[int], List[float]]:
    """Drop weaker events within downfact/2 bins of a stronger one
    (same width).  Parity: prune_related1
    (bin/single_pulse_search.py:63-88)."""
    toremove = set()
    for i in range(len(bins) - 1):
        if i in toremove:
            continue
        for j in range(i + 1, len(bins)):
            if abs(bins[j] - bins[i]) > downfact // 2:
                break
            if j in toremove:
                continue
            if vals[i] > vals[j]:
                toremove.add(j)
            else:
                toremove.add(i)
    keepb = [b for i, b in enumerate(bins) if i not in toremove]
    keepv = [v for i, v in enumerate(vals) if i not in toremove]
    return keepb, keepv


def prune_related2(cands: List[SPCandidate],
                   downfacts: Sequence[int]) -> List[SPCandidate]:
    """Cross-width pruning over the merged, bin-sorted candidate list.
    Parity: prune_related2 (bin/single_pulse_search.py:90-117)."""
    maxdf = max(downfacts) if downfacts else 1
    toremove = set()
    for i in range(len(cands) - 1):
        if i in toremove:
            continue
        x = cands[i]
        for j in range(i + 1, len(cands)):
            y = cands[j]
            if abs(y.bin - x.bin) > maxdf // 2:
                break
            if j in toremove:
                continue
            prox = max(x.downfact // 2, y.downfact // 2, 1)
            if abs(y.bin - x.bin) <= prox:
                if x.sigma > y.sigma:
                    toremove.add(j)
                else:
                    toremove.add(i)
    return [c for i, c in enumerate(cands) if i not in toremove]


def prune_border_cases(cands: List[SPCandidate],
                       offregions: Sequence[Tuple[int, int]]
                       ) -> List[SPCandidate]:
    """Drop events within a half-width of a data/padding boundary.
    Parity: prune_border_cases (bin/single_pulse_search.py:119-136)."""
    out = []
    for c in cands:
        lo = c.bin - c.downfact // 2
        hi = c.bin + c.downfact // 2
        clipped = any(hi > off and lo < on for off, on in offregions)
        if not clipped:
            out.append(c)
    return out


@dataclass
class SinglePulseSearch:
    """Configured matched-filter search over one normalized series."""
    threshold: float = 5.0
    maxwidth: float = 0.0          # seconds; 0 => bin cap MAX_DOWNFACT
    detrendlen: int = 1000
    fast_detrend: bool = False
    badblocks: bool = True
    chunklen: int = 8000
    fftlen: int = 8192
    topk: int = 256
    batch_chunks: int = 64

    def downfacts_for(self, dt: float) -> List[int]:
        if self.maxwidth > 0.0:
            dfs = [x for x in DEFAULT_DOWNFACTS if x * dt <= self.maxwidth]
        else:
            dfs = [x for x in DEFAULT_DOWNFACTS if x <= MAX_DOWNFACT]
        return dfs or [DEFAULT_DOWNFACTS[0]]

    def _blocks_for(self, ts: np.ndarray) -> np.ndarray:
        dlen = self.detrendlen
        roundN = (len(ts) // dlen) * dlen
        return np.asarray(ts[:roundN], np.float32).reshape(-1, dlen)

    def _finish_normalize(self, resid: np.ndarray, stds: np.ndarray):
        """Host-side half of normalize: bad-block logic + scaling."""
        if stds.size == 0:
            return (np.zeros(0, np.float32), stds,
                    np.empty(0, dtype=np.int64))
        # Constant (zero-variance) blocks — padding, dropouts — are
        # always bad: without the guard 0/0 NaNs (or huge roundoff
        # amplification) would poison every chunk whose convolution
        # window overlaps them.  Detrend roundoff leaves std ~1e-7
        # rather than exact 0, so the cut is relative to the median.
        medstd = float(np.median(stds))
        zerostd = np.flatnonzero(stds <= 1e-4 * medstd)
        if self.badblocks:
            bad, med, _ = flag_bad_blocks(stds)
            bad = np.union1d(bad, zerostd)
            stds = stds.copy()
            stds[bad] = med if med > 0.0 else 1.0
        else:
            bad = zerostd
            stds = np.where(stds <= 0.0, 1.0, stds)
        normed = resid / stds[:, None]
        normed[bad] = 0.0
        return normed.reshape(-1), stds, bad

    def normalize(self, ts: np.ndarray):
        """Detrend + normalize; returns (normed series, stds, bad_blocks).
        Bad blocks are zeroed (they still participate in convolution
        overlaps, matching single_pulse_search.py:425-430)."""
        blocks = self._blocks_for(ts)
        resid, stds = _detrend_blocks(jnp.asarray(blocks),
                                      self.detrendlen,
                                      self.fast_detrend)
        return self._finish_normalize(np.asarray(resid),
                                      np.asarray(stds))

    def normalize_many(self, series_list):
        """normalize() for many series in ONE detrend dispatch (blocks
        are independent, so all files' blocks stack along axis 0 —
        the per-file dispatch otherwise dominates a survey fan-out)."""
        blist = [self._blocks_for(ts) for ts in series_list]
        counts = [b.shape[0] for b in blist]
        if sum(counts) == 0:
            return [self._finish_normalize(
                np.zeros((0, self.detrendlen), np.float32),
                np.zeros(0, np.float32)) for _ in blist]
        resid, stds = _detrend_blocks(
            jnp.asarray(np.concatenate(blist, axis=0)),
            self.detrendlen, self.fast_detrend)
        resid = np.asarray(resid)
        stds = np.asarray(stds)
        out, o = [], 0
        for c in counts:
            out.append(self._finish_normalize(resid[o:o + c],
                                              stds[o:o + c]))
            o += c
        return out

    def _chunk_geometry(self, widths):
        """(widths, chunklen, fftlen, overlap, kern_pairs) — the one
        source of chunk layout for the single and batched paths."""
        chunklen, fftlen = self.chunklen, self.fftlen
        if self.detrendlen > chunklen:
            chunklen = self.detrendlen
            fftlen = int(2 ** np.ceil(np.log2(chunklen)))
        overlap = (fftlen - chunklen) // 2
        kf = np.fft.rfft(boxcar_kernels(widths, fftlen))
        kern_pairs = np.stack([kf.real, kf.imag],
                              -1).astype(np.float32)
        return widths, chunklen, fftlen, overlap, kern_pairs

    @staticmethod
    def _padded_chunks(normed, numchunks, chunklen, overlap):
        """Overlap-padded copy of the series for chunk extraction."""
        N = len(normed)
        padded = np.zeros(overlap + numchunks * chunklen + overlap,
                          dtype=np.float32)
        padded[overlap:overlap + min(N, numchunks * chunklen)] = \
            normed[:numchunks * chunklen]
        return padded

    def search_normalized(self, normed: np.ndarray, dt: float,
                          dm: float = 0.0,
                          downfacts: Optional[Sequence[int]] = None
                          ) -> List[SPCandidate]:
        """Run the batched matched filter over an RMS=1 series."""
        if downfacts is None:
            downfacts = self.downfacts_for(dt)
        widths, chunklen, fftlen, overlap, kern_pairs = \
            self._chunk_geometry(widths=[1] + list(downfacts))
        N = len(normed)
        numchunks = max(N // chunklen, 1)
        padded = self._padded_chunks(normed, numchunks, chunklen,
                                     overlap)
        cands: List[SPCandidate] = []
        # numpy scalar: no device put for a constant
        thr = np.float32(self.threshold)
        for c0 in range(0, numchunks, self.batch_chunks):
            c1 = min(c0 + self.batch_chunks, numchunks)
            rows = np.stack([padded[c * chunklen:c * chunklen + fftlen]
                             for c in range(c0, c1)])
            vals, idx, counts = _convolve_topk(
                rows, kern_pairs, thr, fftlen, overlap,
                min(self.topk, chunklen))
            vals = np.asarray(vals)
            idx = np.asarray(idx)
            counts = np.asarray(counts)
            for ci in range(c1 - c0):
                _collect_chunk_hits(vals[ci], idx[ci], counts[ci],
                                    c0 + ci, widths, chunklen, N, dt,
                                    dm, cands)
        cands.sort()
        cands = prune_related2(cands, widths)
        return cands

    def search_many(self, series_list, dt: float,
                    dms: Sequence[float],
                    offregions_list=None):
        """Batched matched filter over MANY series (the survey's DM
        fan-out): the overlapped chunks of every file share the device
        dispatches, so per-file dispatch latency is paid once per chunk
        GROUP instead of once per file.  Per-file results match
        search() exactly (same chunking, pruning, bad-block cuts).

        Returns a list of (cands, stds, bad) triples.
        """
        nf = len(series_list)
        if offregions_list is None:
            offregions_list = [()] * nf
        preps = self.normalize_many([np.asarray(ts, np.float32)
                                     for ts in series_list])
        widths, chunklen, fftlen, overlap, kern_pairs = \
            self._chunk_geometry(
                widths=[1] + list(self.downfacts_for(dt)))

        rows = []
        owners = []                       # (file_idx, chunknum)
        Ns = []
        for fi, (normed, stds, bad) in enumerate(preps):
            N = len(normed)
            Ns.append(N)
            numchunks = max(N // chunklen, 1)
            padded = self._padded_chunks(normed, numchunks, chunklen,
                                         overlap)
            for c in range(numchunks):
                rows.append(padded[c * chunklen:c * chunklen + fftlen])
                owners.append((fi, c))

        per_file: List[List[SPCandidate]] = [[] for _ in range(nf)]
        thr = np.float32(self.threshold)
        k = min(self.topk, chunklen)
        B = self.batch_chunks
        for g0 in range(0, len(rows), B):
            group = rows[g0:g0 + B]
            npad = B - len(group)
            if npad:                      # keep ONE jit shape
                group = group + [np.zeros(fftlen, np.float32)] * npad
            vals, idx, counts = _convolve_topk(
                np.stack(group), kern_pairs, thr, fftlen, overlap, k)
            vals = np.asarray(vals)
            idx = np.asarray(idx)
            counts = np.asarray(counts)
            for ri in range(len(group) - npad):
                fi, chunknum = owners[g0 + ri]
                _collect_chunk_hits(vals[ri], idx[ri], counts[ri],
                                    chunknum, widths, chunklen,
                                    Ns[fi], dt, dms[fi], per_file[fi])

        out = []
        for fi, (normed, stds, bad) in enumerate(preps):
            cands = sorted(per_file[fi])
            cands = prune_related2(cands, widths)
            cands = self._post_filter(cands, bad, offregions_list[fi])
            out.append((cands, stds, bad))
        return out

    def search_many_resident(self, series, dt: float,
                             dms: Sequence[float],
                             offregions_list=None, G: int = 2048,
                             obs=None):
        """search_many with the series DEVICE-RESIDENT end to end —
        the survey's fused regime (dedispersed series stay in HBM;
        feeding them back through the host link costs more than the
        whole search on slow links).  Only small arrays cross the
        boundary: per-block stds down, normalization scales up, and
        the compacted top-G above-threshold hits down.

        series: [nf, N] float32 (jax array, or numpy uploaded once).
        Results match search_many exactly (same chunking, pruning,
        bad-block cuts) unless a file has more than G above-threshold
        top-k samples (heavy RFI) — those fall back to the host path.
        """
        import jax as _jax
        nf = int(series.shape[0])
        N = int(series.shape[1])
        if offregions_list is None:
            offregions_list = [()] * nf
        dev = series if isinstance(series, _jax.Array) \
            else jnp.asarray(np.asarray(series, np.float32))
        dlen = self.detrendlen
        nblk = N // dlen
        widths, chunklen, fftlen, overlap, kern_pairs = \
            self._chunk_geometry(widths=[1] + list(self.downfacts_for(dt)))
        # pass 1: detrend once; residuals stay RESIDENT for pass 2,
        # only the tiny stds cross to the host
        roundN = nblk * dlen
        resid, stds_dev = _detrend_blocks(
            dev[:, :roundN].reshape(nf * nblk, dlen), dlen,
            self.fast_detrend)
        stds_all = np.asarray(stds_dev).reshape(nf, nblk)
        scales = np.empty((nf, nblk), np.float32)
        masks = np.ones((nf, nblk), np.float32)
        bads = []
        for fi in range(nf):
            stds = stds_all[fi]
            medstd = float(np.median(stds)) if nblk else 0.0
            zerostd = np.flatnonzero(stds <= 1e-4 * medstd)
            if self.badblocks:
                bad, med, _ = flag_bad_blocks(stds)
                bad = np.union1d(bad, zerostd)
                stds = stds.copy()
                stds[bad] = med if med > 0.0 else 1.0
            else:
                bad = zerostd
                stds = np.where(stds <= 0.0, 1.0, stds)
            scales[fi] = 1.0 / stds
            masks[fi, bad] = 0.0
            bads.append(bad)
        # pass 2: normalize + frames + convolve + compact, on device
        if obs is not None:
            # unit cost of the stage's dominant program (kind
            # "sp_search"), harvested once per geometry
            from presto_tpu.obs import costmodel
            costmodel.probe(
                obs, "sp_search", _resident_pipeline,
                resid, jnp.asarray(scales), jnp.asarray(masks),
                kern_pairs, np.float32(self.threshold), dlen,
                nblk, chunklen, fftlen, overlap,
                min(self.topk, chunklen), G)
        tv, ti, tb, counts = _resident_pipeline(
            resid, jnp.asarray(scales), jnp.asarray(masks), kern_pairs,
            np.float32(self.threshold), dlen,
            nblk, chunklen, fftlen, overlap,
            min(self.topk, chunklen), G)
        tv = np.asarray(tv)
        ti = np.asarray(ti)
        tb = np.asarray(tb)
        counts = np.asarray(counts)      # [nf, F, W]
        k = min(self.topk, chunklen)
        W = len(widths)
        out = []
        for fi in range(nf):
            capped = np.minimum(counts[fi], k).sum()
            if capped > G:
                # compaction overflow (pathological RFI): host path
                row = np.asarray(dev[fi])
                res = self.search_many([row], dt, [dms[fi]],
                                       [offregions_list[fi]])[0]
                out.append(res)
                continue
            good = tv[fi] > self.threshold
            chunk = ti[fi][good] // (W * k)
            wi = (ti[fi][good] // k) % W
            vals = tv[fi][good]
            bins = tb[fi][good] + chunk * chunklen
            cands: List[SPCandidate] = []
            for c, w in set(zip(chunk.tolist(), wi.tolist())):
                sel = (chunk == c) & (wi == w)
                df = widths[w]
                b = bins[sel]
                v = vals[sel]
                order = np.argsort(b)
                bl, vl = prune_related1([int(x) for x in b[order]],
                                        [float(x) for x in v[order]],
                                        df)
                for bb, vv in zip(bl, vl):
                    # host path bounds bins by the detrend-truncated
                    # normed length, not the raw N
                    if bb < nblk * dlen:
                        cands.append(SPCandidate(
                            bin=bb, sigma=vv, time=bb * dt,
                            downfact=df, dm=dms[fi]))
            cands.sort()
            cands = prune_related2(cands, widths)
            cands = self._post_filter(cands, bads[fi],
                                      offregions_list[fi])
            # adjusted stds, matching _finish_normalize's return
            out.append((cands, 1.0 / scales[fi], bads[fi]))
        return out

    def _post_filter(self, cands, bad, offregions):
        """Bad-block cut + off-region border pruning (shared by the
        single and batched search paths)."""
        if len(bad):
            badset = set(int(b) for b in bad)
            dlen = self.detrendlen
            cands = [c for c in cands if (c.bin // dlen) not in badset]
        if offregions:
            cands = prune_border_cases(cands, offregions)
        return cands

    def search(self, ts: np.ndarray, dt: float, dm: float = 0.0,
               offregions: Sequence[Tuple[int, int]] = ()
               ) -> Tuple[List[SPCandidate], np.ndarray, np.ndarray]:
        """Full pipeline: detrend/normalize -> matched filter -> prune.
        Returns (candidates, per-block stds, bad block indices)."""
        normed, stds, bad = self.normalize(ts)
        cands = self.search_normalized(normed, dt, dm=dm)
        return self._post_filter(cands, bad, offregions), stds, bad


@partial(jax.jit, static_argnames=("detrendlen", "nblk", "chunklen",
                                   "fftlen", "overlap", "k", "G"))
def _resident_pipeline(resid, scales, badmask, kern_pairs, threshold,
                       detrendlen, nblk, chunklen, fftlen,
                       overlap, k, G):
    """Device half of search_many_resident for ONE file batch:
    detrend RESIDUALS [nf*nblk, detrendlen] (kept resident from the
    stds pass — re-detrending would double the sort-heavy device
    work) -> per-file compacted hits.

    scales [nf, nblk] (1/std per detrend block, host-computed from the
    stds pass), badmask [nf, nblk] (0 for bad blocks).  Returns
    (tv [nf, G], ti [nf, G], tb [nf, G], counts [nf, F, W]):
    the global top-G above-threshold smoothed samples per file with
    their flat (chunk, width) encoding and matched-filter bin, plus
    exact per-(chunk, width) hit counts (capacity/overflow checks).
    """
    nf = scales.shape[0]
    roundN = nblk * detrendlen
    normed = (resid.reshape(nf, nblk, detrendlen)
              * (scales * badmask)[:, :, None]).reshape(nf, roundN)
    F = max(roundN // chunklen, 1)
    # the host path copies only F*chunklen samples into its padded
    # buffer (zeros beyond) — zero the tail so the last chunk's right
    # overlap matches exactly (no-op when one chunk spans everything)
    keep = min(F * chunklen, roundN)
    if keep < roundN:
        normed = jnp.concatenate(
            [normed[:, :keep],
             jnp.zeros((nf, roundN - keep), jnp.float32)], axis=1)
    # overlap-padded frames via two reshapes (no per-chunk slices)
    P = -(-fftlen // chunklen)
    pad_hi = (F + P) * chunklen - roundN
    padded = jnp.pad(normed, ((0, 0), (overlap, overlap + pad_hi)))
    A = padded[:, :(F + P) * chunklen].reshape(nf, F + P, chunklen)
    parts = [jax.lax.slice(A, (0, p, 0),
                           (nf, p + F, min(chunklen, fftlen - p *
                                           chunklen)))
             for p in range(P)]
    frames = jnp.concatenate(parts, axis=2)      # [nf, F, fftlen]

    def per_file(fr):
        vals, idx, counts = _convolve_topk(fr, kern_pairs, threshold,
                                           fftlen, overlap, k)
        flatv = jnp.where(vals > threshold, vals, -1.0).reshape(-1)
        g = min(G, flatv.shape[0])
        tv, ti = jax.lax.top_k(flatv, g)
        tb = jnp.take(idx.reshape(-1), ti)
        if g < G:
            tv = jnp.pad(tv, (0, G - g), constant_values=-1.0)
            ti = jnp.pad(ti, (0, G - g))
            tb = jnp.pad(tb, (0, G - g))
        return tv, ti, tb, counts

    return jax.lax.map(per_file, frames)


def _collect_chunk_hits(vals_c, idx_c, counts_c, chunknum, widths,
                        chunklen, N, dt, dm, cands):
    """Turn one chunk's top-k device results into pruned candidates
    (shared by the single and batched search paths)."""
    for wi, df in enumerate(widths):
        nhit = int(counts_c[wi])
        if nhit == 0:
            continue
        if nhit > vals_c.shape[-1]:
            # Capacity overflow: pathological chunk (heavy RFI).
            # Keep the top-k strongest; the bad-block cut should
            # normally have zeroed such data.
            nhit = vals_c.shape[-1]
        v = vals_c[wi, :nhit]
        b = idx_c[wi, :nhit] + chunknum * chunklen
        order = np.argsort(b)
        bl, vl = prune_related1([int(x) for x in b[order]],
                                [float(x) for x in v[order]], df)
        for bb, vv in zip(bl, vl):
            if bb >= N:
                continue
            cands.append(SPCandidate(bin=bb, sigma=vv, time=bb * dt,
                                     downfact=df, dm=dm))


class SinglePulseStream:
    """Incremental (online) single-pulse search over a growing series.

    The explicit-carry counterpart of :meth:`SinglePulseSearch.search`:
    feed dedispersed samples as they arrive and get back candidates as
    soon as they are *final* — i.e. no future sample can change them —
    instead of waiting for the whole observation.  This is the state
    the streaming trigger path (presto_tpu/stream/rolling.py) and any
    future drift-scan search share; the batch path stays the reference
    implementation.

    Equivalence contract: fed the same samples (in any chunking) as a
    batch ``search.search(ts, dt, dm)`` sees, the concatenation of
    every ``feed()`` result plus ``flush()`` is the same candidate set,
    PROVIDED ``search.badblocks`` is False (the batch bad-block cut
    ranks every block's std against the *whole observation's*
    distribution, which no online pass can know; construct the search
    with ``badblocks=False``) and no detrend block has near-zero
    variance (the batch zero-variance guard compares against the
    global median std — here the cut uses the *running* median, see
    ``_absorb_detrended``).  The carry reproduces the batch path's
    exact geometry: detrend blocks of ``detrendlen``, matched-filter
    chunks of ``chunklen`` with ``overlap`` margins, per-(chunk,width)
    ``prune_related1``, and ``prune_related2`` over bin-sorted
    candidates — made incremental by the chain-segment argument: the
    greedy cross-width prune only couples candidates through adjacent
    (sorted) pairs within ``maxdf//2`` bins, so a run of candidates
    separated from everything later by a larger gap is final.

    Dedup across block seams: a chunk is only searched once the NEXT
    chunk's samples exist (so its right overlap holds real data exactly
    like the batch padded buffer), and candidates within ``maxdf//2``
    bins of un-searched territory are held pending — no candidate is
    ever emitted twice or differently from the batch path.
    """

    def __init__(self, search: SinglePulseSearch, dt: float,
                 dm: float = 0.0,
                 downfacts: Optional[Sequence[int]] = None):
        if search.badblocks:
            raise ValueError(
                "SinglePulseStream requires badblocks=False: the batch "
                "bad-block cut needs the whole observation's std "
                "distribution (see class docstring)")
        self.search = search
        self.dt = float(dt)
        self.dm = float(dm)
        if downfacts is None:
            downfacts = search.downfacts_for(dt)
        (self.widths, self.chunklen, self.fftlen, self.overlap,
         self._kern_pairs) = search._chunk_geometry(
            widths=[1] + list(downfacts))
        self.maxdf = max(self.widths)
        self.dlen = search.detrendlen
        self._k = min(search.topk, self.chunklen)
        self._tail = np.zeros(0, np.float32)    # raw, < detrendlen
        self._nfed = 0                          # raw samples fed
        self._nnormed = 0                       # normalized samples
        self._nbuf = np.zeros(0, np.float32)    # normalized suffix
        self._nbuf_start = 0                    # abs index of _nbuf[0]
        self._next_chunk = 0
        self._pending: List[SPCandidate] = []
        self._stds: List[float] = []
        self._bad: set = set()                  # bad detrend blocks
        self._offregions: List[Tuple[int, int]] = []
        self._flushed = False

    # -- carry state views --------------------------------------------
    @property
    def stds(self) -> np.ndarray:
        """Per-detrend-block stds seen so far (the running carry the
        batch path returns all at once)."""
        return np.asarray(self._stds, np.float32)

    @property
    def bad_blocks(self) -> np.ndarray:
        return np.asarray(sorted(self._bad), np.int64)

    @property
    def samples_fed(self) -> int:
        return self._nfed

    @property
    def pending(self) -> int:
        """Candidates held back pending cross-seam dedup."""
        return len(self._pending)

    def emission_floor(self) -> int:
        """Lower bound (bin) on every candidate this stream can still
        emit: future chunks produce bins >= next_chunk*chunklen, the
        chain guard can reach maxdf//2 below that, and held pending
        candidates may sit lower still.  Consumers clustering across
        streams (stream/rolling's trigger dedup) emit a cluster only
        once every contributing stream's floor has passed it."""
        floor = self._next_chunk * self.chunklen - self.maxdf // 2
        if self._pending:
            floor = min(floor, min(c.bin for c in self._pending))
        return floor

    def add_offregion(self, lo: int, hi: int) -> None:
        """Register a data/padding boundary region (normalized-series
        bins) for border pruning; must be added before the region's
        candidates finalize (the streaming caller learns of dropouts
        while the affected samples are still upstream of the search
        frontier, so this holds by construction)."""
        self._offregions.append((int(lo), int(hi)))

    # -- feeding ------------------------------------------------------
    def feed(self, x: np.ndarray) -> List[SPCandidate]:
        """Append raw dedispersed samples; returns newly-final
        candidates (bin-sorted, pruned exactly like the batch path)."""
        if self._flushed:
            raise RuntimeError("stream already flushed")
        x = np.asarray(x, np.float32).ravel()
        buf = np.concatenate([self._tail, x]) if self._tail.size else x
        nblk = buf.size // self.dlen
        if nblk:
            blocks = buf[:nblk * self.dlen].reshape(nblk, self.dlen)
            resid, stds = _detrend_blocks(jnp.asarray(blocks),
                                          self.dlen,
                                          self.search.fast_detrend)
            self._absorb_detrended(np.asarray(resid), np.asarray(stds))
        self._tail = buf[nblk * self.dlen:]
        self._nfed += x.size
        ready = []
        while self._nnormed >= (self._next_chunk + 2) * self.chunklen:
            ready.append(self._next_chunk)
            self._next_chunk += 1
        if ready:
            # mid-stream a chunk is searched only when the next chunk's
            # samples exist, so its window is all real data — exactly
            # what the batch padded buffer holds for a non-final chunk
            self._search_chunks(ready, limit=self._nnormed,
                                ncut=None)
        return self._finalize(final=False)

    def flush(self) -> List[SPCandidate]:
        """End of stream: search the remaining chunks with the batch
        path's zero padding, emit everything still pending.  The raw
        tail below one detrend block is dropped, matching the batch
        truncation to a whole number of detrend blocks."""
        if self._flushed:
            return []
        self._flushed = True
        self._tail = np.zeros(0, np.float32)
        N = self._nnormed
        if N == 0:
            self._pending = []
            return []
        numchunks = max(N // self.chunklen, 1)
        ready = list(range(self._next_chunk, numchunks))
        self._next_chunk = numchunks
        if ready:
            self._search_chunks(
                ready, limit=min(N, numchunks * self.chunklen), ncut=N)
        return self._finalize(final=True)

    # -- internals ----------------------------------------------------
    def _absorb_detrended(self, resid: np.ndarray,
                          stds: np.ndarray) -> None:
        """Normalize freshly-detrended blocks.  Zero-variance guard:
        the batch path cuts stds <= 1e-4 x the observation-wide median
        — online, the median of every block seen so far stands in (the
        only divergence from batch, and only for degenerate blocks)."""
        base = len(self._stds)
        self._stds.extend(float(s) for s in stds)
        medstd = float(np.median(np.asarray(self._stds)))
        bad = np.flatnonzero(stds <= 1e-4 * medstd)
        adj = np.where(stds <= 0.0, 1.0, stds)
        normed = resid / adj[:, None]
        normed[bad] = 0.0
        for r in bad:
            self._bad.add(base + int(r))
        self._nbuf = (np.concatenate([self._nbuf, normed.reshape(-1)])
                      if self._nbuf.size else normed.reshape(-1))
        self._nnormed += normed.size

    def _chunk_row(self, c: int, limit: int) -> np.ndarray:
        """The batch padded-buffer window for chunk `c`: normalized
        samples [c*chunklen - overlap, +fftlen), zeros outside
        [0, limit)."""
        row = np.zeros(self.fftlen, np.float32)
        lo = c * self.chunklen - self.overlap
        a = max(lo, 0)
        b = min(lo + self.fftlen, limit)
        if b > a:
            row[a - lo:b - lo] = \
                self._nbuf[a - self._nbuf_start:b - self._nbuf_start]
        return row

    def _search_chunks(self, chunks: List[int], limit: int,
                       ncut: Optional[int]) -> None:
        rows = [self._chunk_row(c, limit) for c in chunks]
        # pad the group to a power-of-two row count: one jit shape per
        # bucket instead of one per distinct ready-chunk count
        B = 1
        while B < len(rows):
            B *= 2
        rows += [np.zeros(self.fftlen, np.float32)] * (B - len(rows))
        vals, idx, counts = _convolve_topk(
            np.stack(rows), self._kern_pairs,
            np.float32(self.search.threshold), self.fftlen,
            self.overlap, self._k)
        vals = np.asarray(vals)
        idx = np.asarray(idx)
        counts = np.asarray(counts)
        # ncut None: mid-stream no bin can reach the eventual N (bins
        # are < (c+1)*chunklen <= nnormed at search time, and N only
        # grows) — the batch bb >= N guard cannot fire, skip it
        N = (1 << 62) if ncut is None else ncut
        for ri, c in enumerate(chunks):
            _collect_chunk_hits(vals[ri], idx[ri], counts[ri], c,
                                self.widths, self.chunklen, N,
                                self.dt, self.dm, self._pending)
        # drop normalized samples no chunk will need again
        keep_from = max(self._next_chunk * self.chunklen - self.overlap,
                        0)
        if keep_from > self._nbuf_start:
            self._nbuf = self._nbuf[keep_from - self._nbuf_start:]
            self._nbuf_start = keep_from

    def _finalize(self, final: bool) -> List[SPCandidate]:
        """Emit candidates no future sample can affect.  Future
        candidates all land at bins >= next_chunk*chunklen, and the
        greedy cross-width prune couples candidates only through
        adjacent sorted pairs within maxdf//2 bins — so chain segments
        ending before that frontier minus maxdf//2 prune identically
        to the batch path's single global pass."""
        if not self._pending:
            return []
        self._pending.sort()
        frontier = self._next_chunk * self.chunklen
        guard = self.maxdf // 2
        out: List[SPCandidate] = []
        keep: List[SPCandidate] = []
        seg: List[SPCandidate] = []
        for c in self._pending + [None]:
            if c is not None and (not seg
                                  or c.bin - seg[-1].bin <= guard):
                seg.append(c)
                continue
            if seg:
                if final or seg[-1].bin < frontier - guard:
                    out.extend(prune_related2(seg, self.widths))
                else:
                    keep.extend(seg)
            seg = [c] if c is not None else []
        self._pending = keep
        return self.search._post_filter(out, self.bad_blocks,
                                        tuple(self._offregions))


def write_singlepulse(path: str, cands: Sequence[SPCandidate]) -> None:
    """Write the .singlepulse ASCII artifact (reference column format,
    atomic on disk)."""
    from presto_tpu.io.atomic import atomic_open
    with atomic_open(path, "w") as f:
        if cands:
            f.write("# DM      Sigma      Time (s)     Sample    Downfact\n")
            for c in cands:
                f.write(str(c))


def read_singlepulse(path: str, dm: float = 0.0) -> List[SPCandidate]:
    cands = []
    with open(path) as f:
        for line in f:
            if line.startswith("#") or not line.strip():
                continue
            parts = line.split()
            cands.append(SPCandidate(
                dm=float(parts[0]), sigma=float(parts[1]),
                time=float(parts[2]), bin=int(parts[3]),
                downfact=int(parts[4])))
    return cands
