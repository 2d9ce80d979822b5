"""The (r, z, w) jerk-search volume, built band by band.

accelsearch -wmax (Andersen & Ransom 2018, ApJL 863, L13; PRESTO's
accel_utils.c jerk path) sums harmonics over one F-Fdot plane per w on
the ACCEL_DW grid, reading harmonic term f = harm/htot from the plane
at w_sub = calc_required_w(f, w).  For a piece of fundamental columns
[P0, P0 + Wp) this module builds, for each w of the grid:

  * the fundamental plane over the r-blocks holding [P0, P0 + Wp);
  * for each harmonic term f, the plane at w_sub over the r-blocks
    holding the columns that term reads, [f P0, f (P0 + Wp)) —

never a plane from r = 0 — and harmonic-sums them with the staged
power cuts of the z-only scanner (search/accel.py).  r-blocks lie on
one global grid: block j holds half-bin columns [j uselen, (j + 1)
uselen) and is median-normalised over its own read window, so a band's
plane cells equal those of the whole-spectrum search and the
candidates of bands add up to those of the whole band.

A searcher's band is its [rlo, rhi) (accelsearch's -flo/-fhi or
-rlo/-rhi): the full-band -wmax search is the band [flo T, Nyquist).
Bands wider than PIECE_COLS fundamental columns are searched piece by
piece, so device memory stays bounded: the planes of one (trial, w,
piece) take (1 + sum f) x numz x Wp x 4 bytes (2.1 GB at zmax 200,
numharm 8 and Wp = 2^19), and two are in flight.  The w kernel banks
of the whole grid stay on the device (13.6 MB each at zmax 200).

Device programs (named for device traces): ``jerk_prep`` (a trial's
spectrum, padded), ``jerk_build`` (every plane of one (trial, w,
piece)) and ``jerk_scan`` (harmonic sum, thresholds, top-k and the
candidate compaction of one (trial, w, piece)).
"""

from __future__ import annotations

from typing import List

import numpy as np
import jax
import jax.numpy as jnp

from presto_tpu.obs import maybe_span

ALIGN = 1024          # piece starts and widths, in absolute columns: a
                      # multiple of every pallas tile, of SEARCH_SEG
                      # and of every htot up to 1024
PIECE_COLS = 1 << 19  # fundamental columns per piece
COMPACT = 4096        # candidate slots one (trial, w, piece) returns


def volume(searcher) -> "JerkVolume":
    """The searcher's volume plan (built once, kept on the searcher)."""
    v = getattr(searcher, "_jerk", None)
    if v is None:
        v = searcher._jerk = JerkVolume(searcher)
    return v


def merge_w_cands(cands):
    """Same (numharm, r) found in several w planes or pieces: keep the
    strongest (the volume's local maximum)."""
    best = {}
    for c in sorted(cands, key=lambda c: -c.sigma):
        best.setdefault((c.numharm, c.r), c)
    return sorted(best.values(), key=lambda c: (-c.sigma, c.r))


class JerkVolume:
    """The banded plan of one AccelSearch with ``cfg.wmax`` set."""

    def __init__(self, s):
        from presto_tpu.search import accel as ac
        from presto_tpu.search import accel_pallas as ap

        cfg, kern = s.cfg, s.kern
        self.s, self.cfg = s, cfg
        self.numz, self.fftlen = kern.numz, kern.fftlen
        self.uselen = cfg.uselen
        self.hop = cfg.uselen // 2
        self.hw = s._plb_hw_eff or kern.halfwidth
        self.numdata = kern.fftlen // 2
        self.plb = bool(s._plb_hw_eff)
        self.use_mxu = ac._use_mxu_engine(kern.fftlen)
        fz = ac._harm_fracs_and_zinds(cfg, self.numz)
        self.terms = [(h, t, np.asarray(zi)) for st in fz
                      for (h, t, zi) in st]
        self.ws = [float(w) for w in cfg.ws]
        # whole blocks below the spectrum's top bin (accelsearch.c:167):
        # block j is built when (j + 1) hop < numbins - 1
        self.nvalid = max(0, (int(s.numbins) - 2) // self.hop)
        self.c_lo = int(s.rlo) * ac.ACCEL_RDR
        self.c_hi = min(int(s.rhi) * ac.ACCEL_RDR,
                        self.nvalid * self.uselen)
        self.pieces = []
        if self.c_hi <= self.c_lo:
            return
        a0 = self.c_lo // ALIGN * ALIGN
        a1 = -(-self.c_hi // ALIGN) * ALIGN
        self.wp = wp = min(PIECE_COLS, a1 - a0)
        # the last piece overlaps back so every piece shares one shape;
        # the overlap's candidates are found twice and merged
        self.pieces = list(range(a0, a1 - wp, wp)) + [a1 - wp]
        # blocks per plane: the fundamental's columns, and each term's
        # source columns plus the scan's right-edge DMA margin
        pad = ap.PLANE_PAD
        self.nbs = [wp // self.uselen + 2] + [
            -(-(wp * h // t + pad) // self.uselen) + 1
            for (h, t, _z) in self.terms]
        self.fracs = [(1, 1)] + [(h, t) for (h, t, _z) in self.terms]
        nframe = -(-self.numdata // self.hop)
        top = max(self._jbs(p)[i] + nb for p in self.pieces
                  for i, nb in enumerate(self.nbs))
        self.pad_lo = self.hw
        self.pad_hi = max(0, (top + nframe) * self.hop - self.pad_lo
                          - int(s.numbins))
        self.k = min(cfg.max_cands_per_stage, wp)
        self.reducer = None
        if self.plb and cfg.numharm <= 16:
            tile = ap.pick_tile(fz, self.numz, wp)
            if tile:
                self.reducer = ap.make_stage_reducer(
                    cfg.numharmstages, fz, wp, self.numz, 0, tile=tile,
                    shifted=True)
        self.banks = None
        self._build = jax.jit(self._build_fn())
        self._scan = jax.jit(self._scan_fn(), static_argnums=3)
        self._prep = jax.jit(self._prep_fn())

    # -- geometry ----------------------------------------------------

    def _jbs(self, p0: int):
        """First block of each plane of the piece at column p0."""
        return [(p0 * h // t) // self.uselen for (h, t) in self.fracs]

    def cells_built(self):
        """(fundamental, subharmonic) plane cells one (trial, w, piece)
        computes."""
        per = self.numz * self.uselen
        return per * self.nbs[0], per * sum(self.nbs[1:])

    # -- device programs ---------------------------------------------

    def _prep_fn(self):
        pad = (self.pad_lo, self.pad_hi)

        def jerk_prep(pairs):
            return jnp.pad(pairs[:, 0] + 1j * pairs[:, 1], pad)
        return jerk_prep

    def _frames(self, cpad, jb, nb):
        """[nb, numdata] read windows of blocks jb .. jb + nb - 1 (block
        j reads bins [j hop - hw, j hop - hw + numdata)); blocks past
        the last whole one read zeros."""
        hop, L = self.hop, self.numdata
        nf = -(-L // hop)
        base = jax.lax.dynamic_slice(cpad, (jb * hop,), ((nb + nf) * hop,))
        A = base.reshape(nb + nf, hop)
        fr = jnp.concatenate([A[p:p + nb, :min(hop, L - p * hop)]
                              for p in range(nf)], axis=1)
        ok = (jb + jnp.arange(nb)) < self.nvalid
        return jnp.where(ok[:, None], fr, 0.0)

    def _plane(self, fr, bank):
        """One plane [rows, nb * uselen] from its blocks' windows."""
        from presto_tpu.search import accel as ac
        cfg, fftlen = self.cfg, self.fftlen
        if cfg.norm == "median":
            fr = fr * ac._block_median_norms_c(fr)
        nb = fr.shape[0]
        if self.plb:
            from presto_tpu.search import build_pallas as bp
            consts = tuple(map(jnp.asarray, ac._dft_consts_np(fftlen)))
            Sr, Si = ac._fwd_stage_mxu(fr, consts, fftlen)
            nb_pad = -(-nb // bp.BB) * bp.BB
            bpad = ((0, nb_pad - nb), (0, 0), (0, 0))
            builder = bp.make_plane_builder(
                self.numz, nb, fftlen, self.uselen,
                self.hw * ac.ACCEL_NUMBETWEEN)
            Kr, Ki = bank
            pw = builder(jnp.pad(Sr, bpad), jnp.pad(Si, bpad), Kr, Ki)
            return pw.reshape(pw.shape[0], nb_pad * self.uselen)
        chunk = max(1, int(ac.CHUNK_BUDGET_BYTES
                           // (self.numz * fftlen * 8)))
        slabs = []
        for i in range(0, nb, chunk):
            data = fr[i:i + chunk]
            if self.use_mxu:
                consts = tuple(map(jnp.asarray, ac._dft_consts_np(fftlen)))
                slabs.append(ac._ffdot_slab_mxu(data, bank, consts,
                                                self.uselen, fftlen,
                                                self.hw))
            else:
                slabs.append(ac._ffdot_slab_fft(data, bank, self.uselen,
                                                fftlen, self.hw))
        return jnp.concatenate(slabs, axis=1) if len(slabs) > 1 \
            else slabs[0]

    def _build_fn(self):
        nbs = self.nbs

        def jerk_build(cpad, jbs, banks):
            return tuple(self._plane(self._frames(cpad, jbs[i], nb),
                                     banks[i])
                         for i, nb in enumerate(nbs))
        return jerk_build

    def _reduce_xla(self, planes, start_cols, shifts):
        """Per column of each slab: the max over z of the stage-summed
        powers and its z row ([nslabs, stages, wp] each)."""
        numz, wp = self.numz, self.wp

        def one(start):
            cols = shifts[0] + start + jnp.arange(wp, dtype=jnp.int32)
            P = planes[0]
            acc = jax.lax.dynamic_slice(P, (0, start), (P.shape[0], wp))
            acc = acc[:numz]
            outs = [(acc.max(axis=0), acc.argmax(axis=0))]
            fi = 0
            for stage in range(1, self.cfg.numharmstages):
                for _ in range(1 << (stage - 1)):
                    h, t, zi = self.terms[fi]
                    src = ((cols // t) * h + ((cols % t) * h + (t >> 1))
                           // t) - shifts[1 + fi]
                    Q = jnp.take(planes[1 + fi], jnp.asarray(zi), axis=0)
                    acc = acc + jnp.take(Q, src, axis=1)
                    fi += 1
                outs.append((acc.max(axis=0), acc.argmax(axis=0)))
            return (jnp.stack([o[0] for o in outs]),
                    jnp.stack([o[1] for o in outs]).astype(jnp.int32))
        return jax.lax.map(one, start_cols)

    def _scan_fn(self):
        from presto_tpu.search import accel as ac
        powcuts = jnp.asarray(self.s.powcut, dtype=jnp.float32)
        stages = self.cfg.numharmstages
        seg = ac.SEARCH_SEG
        nseg = self.wp // seg
        kk = min(self.k, nseg)

        def jerk_scan(planes, start_cols, shifts, m):
            if self.reducer is not None:
                colmax, colz = self.reducer(planes[0], planes[1:],
                                            start_cols, shifts)
            else:
                colmax, colz = self._reduce_xla(planes, start_cols, shifts)
            nslabs = colmax.shape[0]
            masked = jnp.where(colmax > powcuts[None, :, None], colmax,
                               0.0)
            segs = masked.reshape(nslabs, stages, nseg, seg)
            v, si = jax.lax.top_k(segs.max(-1), kk)
            ci = si * seg + jnp.take_along_axis(
                segs.argmax(-1).astype(jnp.int32), si, axis=-1)
            zrow = jnp.take_along_axis(colz, ci, axis=-1)
            packed = jnp.stack([jax.lax.bitcast_convert_type(v, jnp.int32),
                                ci, zrow])
            return ac.compact_scan_packed(packed, m) if m else packed
        return jerk_scan

    # -- kernel banks --------------------------------------------------

    def _ensure_banks(self, obs) -> None:
        """Device kernel banks of every w on the grid (host quadrature,
        then one upload each), in the form the build engine reads."""
        if self.banks is not None:
            return
        from presto_tpu.search import accel as ac
        from presto_tpu.search import build_pallas as bp
        s, fftlen = self.s, self.fftlen
        banks = {}
        with maybe_span(obs, "accel:wbank", nbanks=len(self.ws)):
            for w in self.ws:
                kern = s.kern if w == 0.0 else ac.AccelKernels.build(
                    self.cfg, w)
                kc = ac._fft_kernel_bank_c(jnp.asarray(kern.kern_pairs),
                                           fftlen)
                if self.plb:
                    kz = ac._kern_bank_z(kc, fftlen)
                    zp = ((0, -(-self.numz // bp.ZT) * bp.ZT - self.numz),
                          (0, 0), (0, 0))
                    banks[w] = (jnp.pad(kz.real.astype(jnp.float32), zp),
                                jnp.pad(kz.imag.astype(jnp.float32), zp))
                elif self.use_mxu:
                    banks[w] = ac._kern_bank_z(kc, fftlen)
                else:
                    banks[w] = kc
                _note_wbank(obs)
            jax.block_until_ready(banks)
        self.banks = banks

    # -- the search -------------------------------------------------------

    def search_many(self, batch, obs=None) -> List[list]:
        """Candidate lists of each spectrum of ``batch`` ([nd, numbins,
        2] float32 pairs, host or device)."""
        from presto_tpu.search.accel import calc_required_w
        nd = int(batch.shape[0])
        if not self.pieces:
            return [[] for _ in range(nd)]
        self._ensure_banks(obs)
        fracs = [h / t for (h, t) in self.fracs[1:]]
        wbanks = [(w, tuple(self.banks[x] for x in
                            [w] + [calc_required_w(f, w) for f in fracs]))
                  for w in sorted(self.ws, key=abs)]
        geo = []
        for p0 in self.pieces:
            jbs = self._jbs(p0)
            origins = [j * self.uselen for j in jbs]
            geo.append((p0, jnp.asarray(jbs, jnp.int32),
                        jnp.asarray([p0 - origins[0]], jnp.int32),
                        jnp.asarray(origins, jnp.int32)))
        fund, sub = self.cells_built()
        out = []
        for i in range(nd):
            cands = []
            with maybe_span(obs, "accel:jerk", pieces=len(geo),
                            ws=len(wbanks)):
                row = batch[i]
                if not isinstance(row, jax.Array):
                    row = jnp.asarray(np.ascontiguousarray(row, np.float32))
                cpad = self._prep(row.astype(jnp.float32))
                pend = []
                for p0, jbs, scols, shifts in geo:
                    for w, banks in wbanks:
                        planes = self._build(cpad, jbs, banks)
                        comp = self._scan(planes, scols, shifts, COMPACT)
                        _note_cells(obs, fund, sub)
                        pend.append((p0, w, comp, (cpad, jbs, banks, scols,
                                                   shifts)))
                        if len(pend) >= 2:
                            cands += self._collect(*pend.pop(0))
                while pend:
                    cands += self._collect(*pend.pop(0))
            out.append(merge_w_cands(cands))
        return out

    def _collect(self, p0, w, comp, args):
        """Host decode of one (trial, w, piece); a compaction that ran
        out of slots is redone with the dense scan output."""
        comp = np.asarray(comp)
        v = comp[0].view(np.float32)
        if v.size >= COMPACT and v[-1] > 0.0:
            cpad, jbs, banks, scols, shifts = args
            dense = np.asarray(self._scan(self._build(cpad, jbs, banks),
                                          scols, shifts, 0))
            v = dense[0].view(np.float32).ravel()
            cidx = dense[1].ravel()
            stg = np.broadcast_to(
                np.arange(dense.shape[2])[None, :, None],
                dense.shape[1:]).ravel()
            zrow = dense[2].ravel()
        else:
            from presto_tpu.search import accel as ac
            cidx = comp[1]
            zrow = comp[2] & ((1 << ac._CMP_ZBITS) - 1)
            stg = (comp[2] >> ac._CMP_ZBITS) & ((1 << ac._CMP_SBITS) - 1)
        absc = p0 + cidx.astype(np.int64)
        good = ((v > 0.0) & (zrow < self.numz) & (absc >= self.c_lo)
                & (absc < self.c_hi))
        cands = self.s._cands_from_flat(v[good], absc[good], zrow[good],
                                        stg[good])
        for c in cands:
            # the plane cell is the numharm-th harmonic: its (r, z, w)
            # all scale down to the fundamental
            c.w = w / c.numharm
        return cands


def _note_cells(obs, fund: int, sub: int) -> None:
    """Plane cells one (trial, w, piece) built, by kind."""
    if obs is None or not obs.enabled:
        return
    fam = obs.metrics.counter(
        "accel_jerk_cells_built_total",
        "Plane cells the jerk volume built", ("kind",))
    fam.labels(kind="fundamental").inc(fund)
    fam.labels(kind="subharmonic").inc(sub)


def _note_wbank(obs) -> None:
    """One w kernel bank built (host quadrature and upload)."""
    if obs is None or not obs.enabled:
        return
    obs.metrics.counter(
        "accel_wbank_builds_total",
        "w kernel banks built for the jerk search").inc()
