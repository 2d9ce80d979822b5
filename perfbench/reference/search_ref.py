"""Plain reference of the per-trial search path, in float64 NumPy.

It follows the published PRESTO semantics that the program implements
and imports nothing of the program:

- packed real FFT (realfft, e^{-2 pi i}, bin 0 = DC + i Nyquist);
- zapbirds: each (freq, width) of the zaplist becomes the Fourier-bin
  range ((f - w/2) T, (f + w/2) T); the bins are replaced by the local
  median amplitude sqrt(median |X|^2 / 2) of 20 bins either side, with
  their own phases (zapping.c);
- the candidate power a polish reports: for each harmonic h of a
  candidate (r, z), the interpolated amplitude
  A = integral_0^1 w(u) exp(-2 pi i (fr u + z h (u^2 - u) / 2)) du with
  w(u) = sum_d X[rint + d] exp(2 pi i d u) over a window of W bins,
  normalised by the mean power at +-(5..14) bins (DELTAAVGBINS,
  NUMLOCPOWAVG), summed over harmonics (maximize_rz / get_localpower);
  W and the quadrature follow the kernel half-width rule of
  responses.c (HIGHACC) for the largest |z h| of the candidate batch;
- the F-Fdot plane and harmonic sum a raw candidate reports
  (accelsearch's in-memory search): the plane at (r, z) on the grid of
  half bins and z steps of 2 is |sum_k norm_b X[k] conj(R(k))|^2 over
  k in [r - m, r + m), R(k) = integral_0^1 exp(2 pi i ((r - z/2 - k) u
  + z u^2 / 2)) du (Gauss-Legendre), m the LOWACC kernel half-width
  of responses.c; norm_b^2 = ln 2 / median |X|^2 over the read window
  of the r-block holding the column (accel_utils.c:952-967, zeros past
  either end); a candidate (r, z, numharm) sums harmonic h at column
  floor(2 r numharm h / numharm + 1/2) and z = 2 NEAREST_INT(z numharm
  h / numharm / 2) (accel_utils.c:53-66); the plane is zero past the
  last whole r-block below the top bin (accelsearch.c:167).  The block
  geometry (r-block
  length, read-window offset and length) is the searcher's plan, given
  as three integers;
- single_pulse_search: 1000-sample blocks linearly detrended, robust
  std of the central 95 % times 1.148, blocks with outlying std set to
  zero, boxcar sums of width w over sqrt(w) on the 8000-sample chunks
  with 96-sample overlaps of an 8192-point circular convolution.

``lowp=True`` is the control: the same arithmetic with every stored
value rounded to bfloat16, the precision below the float32 the
configuration states.
"""

from __future__ import annotations

import numpy as np

NUMLOCPOWAVG = 20
DELTAAVGBINS = 5
NUMFINTBINS = 16
STEP0_Z = 0.5            # polish grid: z step and half-extent
GRID_G = 3


def bf16(a):
    import ml_dtypes
    a = np.asarray(a)
    if np.iscomplexobj(a):
        return (bf16(a.real) + 1j * bf16(a.imag))
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def packed_rfft(x: np.ndarray, lowp: bool = False) -> np.ndarray:
    x = np.asarray(x, np.float64)
    if lowp:
        full = np.fft.rfft(bf16(x).astype(np.float32))
    else:
        full = np.fft.rfft(x)
    out = np.concatenate([[full[0].real + 1j * full[-1].real], full[1:-1]])
    return bf16(out) if lowp else out


def read_birds(path: str):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if line.startswith("B"):
                line = line[1:]
            parts = line.split()
            out.append((float(parts[0]),
                        float(parts[1]) if len(parts) > 1 else 0.0))
    return out


def zap_ranges(birds, T: float, N: int):
    ranges = sorted(((f - max(w / 2, 0.0)) * T, (f + max(w / 2, 0.0)) * T)
                    for f, w in birds)
    hibin = N / 2
    kept = []
    for lob, hib in ranges:
        if lob >= hibin - 1:
            break
        kept.append((lob, min(hib, hibin - 1)))
    return kept


def zap(X: np.ndarray, birds, T: float, N: int, localwidth: int = 20):
    """Zapped copy of X and a boolean mask of the replaced bins."""
    out = np.array(X, np.complex128)
    n = out.size
    mask = np.zeros(n, bool)
    for lob, hib in zap_ranges(birds, T, N):
        lo = max(1, int(np.floor(lob)))
        hi = min(n - 1, int(np.ceil(hib)))
        if hi < lo:
            continue
        c0 = max(1, lo - localwidth)
        c1 = min(n, hi + 1 + localwidth)
        ctx = np.concatenate([out[c0:lo], out[hi + 1:c1]])
        level = (np.sqrt(np.median(np.abs(ctx) ** 2) / 2.0)
                 if ctx.size else 0.0)
        out[lo:hi + 1] = level * np.exp(1j * np.angle(out[lo:hi + 1]))
        mask[lo:hi + 1] = True
    return out, mask


def spectrum_gap(prog: np.ndarray, ref: np.ndarray, mask: np.ndarray):
    """Widest gap between two spectra as a share of the reference's
    rms amplitude: complex values outside the zapped bins, amplitudes
    inside them (their phase is that of a near-zero bin)."""
    prog = np.asarray(prog, np.complex128)
    rms = np.sqrt(np.mean(np.abs(ref[1:]) ** 2))
    d = np.abs(prog - ref)
    d[mask] = np.abs(np.abs(prog[mask]) - np.abs(ref[mask]))
    d[0] = 0.0        # DC + i Nyquist: the search never reads bin 0
    return float(d.max() / rms)


def z_halfwidth_high(z: float) -> int:
    z = abs(z)
    m = int(z * (0.002057 * z + 0.0377) + NUMFINTBINS * 3)
    m += NUMLOCPOWAVG // 2 + DELTAAVGBINS
    if z > 100 and m > 1.2 * z:
        m = int(1.2 * z)
    return m


def polish_geometry(zmax_pairs: float):
    hw = z_halfwidth_high(zmax_pairs)
    W = -(-(2 * hw + 2 * (DELTAAVGBINS + NUMLOCPOWAVG // 2) + 16) // 128) * 128
    need = W // 2 + zmax_pairs / 2 + 2
    npts = 128
    while npts < 2 * need:
        npts *= 2
    return W, npts


def candidate_powers(X: np.ndarray, seeds, outs, pick, lowp=False):
    """Reference summed power of the polished candidates ``pick``
    (indices into outs), at the (r, z) the program reported, with the
    window of the program's seed (rint of the seed's harmonic) and the
    batch geometry of all seeds."""
    seed_r = np.array([s[0] for s in seeds], np.float64)
    seed_z = np.array([s[1] for s in seeds], np.float64)
    nh = np.array([s[2] for s in seeds], np.int64)
    zb = max(float(np.max(np.abs(seed_z[i]) * np.arange(1, nh[i] + 1)))
             for i in range(len(seeds))) + STEP0_Z * GRID_G + 1.0
    W, npts = polish_geometry(zb)
    n = X.size
    Xw = bf16(X) if lowp else np.asarray(X, np.complex128)
    u = (np.arange(npts) + 0.5) / npts
    dl = np.arange(W) - W // 2
    F = np.exp(2j * np.pi * np.outer(dl, u))                # [W, npts]
    offs = np.concatenate([-(DELTAAVGBINS + np.arange(NUMLOCPOWAVG // 2)),
                           DELTAAVGBINS + np.arange(NUMLOCPOWAVG // 2)])
    res = []
    for i in pick:
        r, z = outs[i][0], outs[i][1]
        hs = np.arange(1, nh[i] + 1)
        rint = np.floor(seed_r[i] * hs).astype(np.int64)
        idx = rint[:, None] + dl[None]
        ok = (idx >= 0) & (idx < n)
        seg = np.where(ok, Xw[np.clip(idx, 0, n - 1)], 0.0)
        wmat = seg @ F                                      # [H, npts]
        if lowp:
            wmat = bf16(wmat)
        fr = r * hs - rint
        zh = z * hs
        ev = np.concatenate([fr[:, None], fr[:, None] + offs[None]], 1)
        ph = np.exp(-2j * np.pi * (ev[..., None] * u +
                                   zh[:, None, None] * 0.5 * (u * u - u)))
        A = np.mean(wmat[:, None, :] * ph, axis=-1)         # [H, 1+2L]
        if lowp:
            A = bf16(A)
        p = np.abs(A) ** 2
        locpow = np.maximum(p[:, 1:].mean(axis=1), 1e-30)
        res.append(float(np.sum(p[:, 0] / locpow)))
    return np.array(res)


# ----------------------------------------------------------------------
# F-Fdot plane and harmonic sum
# ----------------------------------------------------------------------

_GL_U, _GL_W = np.polynomial.legendre.leggauss(1024)
_GL_U, _GL_W = (_GL_U + 1.0) / 2.0, _GL_W / 2.0


def z_halfwidth_low(z: float) -> int:
    """Kernel half-width in bins of the search plane (LOWACC)."""
    z = abs(z)
    m = max(int(z * (0.00089 * z + 0.3131) + NUMFINTBINS), NUMFINTBINS)
    if z > 100 and m > 0.6 * z:
        m = int(0.6 * z)
    return m


def _nearest_int(x: float) -> int:
    return int(np.ceil(x - 0.5)) if x < 0 else int(np.floor(x + 0.5))


def harmonics(r: float, z: float, numharm: int):
    """[(r_h, z_h)] of the harmonics a candidate at fundamental (r, z)
    sums, h = 1..numharm, on the plane's grid."""
    col = int(round(2 * r * numharm))
    zfull = int(round(z * numharm))
    return [((2 * col * h + numharm) // (2 * numharm) / 2.0,
             2 * _nearest_int(zfull * h / numharm / 2.0))
            for h in range(1, numharm + 1)]


class Plane:
    """Reference F-Fdot plane values of one spectrum.  ``geom`` is
    (uselen half bins per r-block, read-window offset in bins, read-
    window length in bins); blocks start at r = 0.  ``lowp`` is the
    control: values rounded to bfloat16 where they are stored, the
    harmonic sum accumulated in bfloat16."""

    def __init__(self, X: np.ndarray, geom, lowp: bool = False):
        self.X = bf16(X) if lowp else np.asarray(X, np.complex128)
        self.uselen, self.hw, self.numdata = (int(v) for v in geom)
        self.lowp = lowp
        self._norm2 = {}
        self._kern = {}

    def norm2(self, col: int) -> float:
        j = col // self.uselen
        if j not in self._norm2:
            lo = j * (self.uselen // 2) - self.hw
            idx = np.arange(lo, lo + self.numdata)
            ok = (idx >= 0) & (idx < self.X.size)
            w = np.where(ok, self.X[np.clip(idx, 0, self.X.size - 1)], 0)
            self._norm2[j] = np.log(2.0) / max(
                float(np.median(np.abs(w) ** 2)), 1e-30)
        return self._norm2[j]

    def kernel(self, z: float, frac: float, m: int):
        """R over the taps k - floor(r) in [-m, m] for r = floor(r) +
        frac (the support is cut at the caller)."""
        key = (z, frac)
        if key not in self._kern:
            d = frac - z / 2.0 - np.arange(-m - 1, m + 1)
            ph = np.exp(2j * np.pi * (d[:, None] * _GL_U
                                      + z * _GL_U * _GL_U / 2.0))
            R = ph @ _GL_W
            self._kern[key] = bf16(R) if self.lowp else R
        return self._kern[key]

    def power(self, r: float, z: float) -> float:
        # only whole r-blocks below the top bin are built (accelsearch.c
        # :167); the plane is zero past the last one
        step = self.uselen // 2
        if (int(round(2 * r)) // self.uselen + 1) * step >= self.X.size - 1:
            return 0.0
        m = z_halfwidth_low(z)
        r0 = int(np.floor(r))
        k = np.arange(int(np.ceil(r - m)), int(np.ceil(r + m)))
        R = self.kernel(z, r - r0, m)[k - r0 + m + 1]
        ok = (k >= 0) & (k < self.X.size)
        x = np.where(ok, self.X[np.clip(k, 0, self.X.size - 1)], 0)
        n2 = self.norm2(int(round(2 * r)))
        if self.lowp:
            x = bf16(x * np.sqrt(n2))
            A = bf16(np.sum(x * np.conj(R)))
            return float(bf16(np.abs(A) ** 2))
        return n2 * float(np.abs(np.sum(x * np.conj(R))) ** 2)

    def summed(self, r: float, z: float, numharm: int) -> float:
        tot = 0.0
        for rh, zh in harmonics(r, z, numharm):
            tot += self.power(rh, zh)
            if self.lowp:
                tot = float(bf16(tot))
        return tot


# ----------------------------------------------------------------------
# single-pulse
# ----------------------------------------------------------------------

DETRENDLEN = 1000
CHUNKLEN = 8000
FFTLEN = 8192


def _bad_blocks(stds: np.ndarray):
    nb = len(stds)
    if nb < 4:
        return np.empty(0, np.int64), float(np.median(stds))
    ss = np.sort(stds)
    locut = int(np.argmax(ss[1:nb // 2 + 1] - ss[:nb // 2])) + 1
    hicut = int(np.argmax(ss[nb // 2 + 1:] - ss[nb // 2:-1])) + nb // 2 - 2
    if hicut <= locut:
        locut, hicut = 0, nb
    sstd = float(np.std(ss[locut:hicut]))
    med = float(ss[(locut + hicut) // 2])
    bad = np.flatnonzero((stds < med - 4 * sstd) | (stds > med + 4 * sstd))
    return bad, med


def sp_normalized(x: np.ndarray, nuse: int, lowp: bool = False):
    """(normalised series, bad block indices): detrended, bad blocks
    zeroed, length nblk * 1000, samples past the last whole chunk
    zeroed."""
    nblk = nuse // DETRENDLEN
    b = np.asarray(x[:nblk * DETRENDLEN], np.float64).reshape(
        nblk, DETRENDLEN)
    t = np.arange(DETRENDLEN, dtype=np.float64)
    tc = t - (DETRENDLEN - 1) / 2.0
    xbar = b.mean(axis=1, keepdims=True)
    slope = ((b - xbar) @ tc) / np.sum(tc * tc)
    resid = b - xbar - slope[:, None] * tc
    s = np.sort(resid, axis=1)
    k = DETRENDLEN // 40
    inner = s[:, k:DETRENDLEN - k]
    stds = np.sqrt((inner ** 2).sum(axis=1) / (0.95 * DETRENDLEN)) * 1.148
    medstd = float(np.median(stds))
    zero = np.flatnonzero(stds <= 1e-4 * medstd)
    bad, med = _bad_blocks(stds)
    bad = np.union1d(bad, zero)
    stds = stds.copy()
    stds[bad] = med if med > 0 else 1.0
    normed = resid / stds[:, None]
    normed[bad] = 0.0
    normed = normed.reshape(-1)
    F = max(normed.size // CHUNKLEN, 1)
    normed[F * CHUNKLEN:] = 0.0
    return (bf16(normed) if lowp else normed), bad


def sp_sigmas(normed: np.ndarray, events, lowp: bool = False):
    """Boxcar S/N at each (bin, downfact) event, on the chunk's
    circular convolution window."""
    overlap = (FFTLEN - CHUNKLEN) // 2
    n = normed.size
    out = []
    for b, df in events:
        f = b // CHUNKLEN
        p = b - f * CHUNKLEN + overlap
        R = df // 2 if df % 2 else max(df // 2 - 1, 0)
        if df == 1:
            ms = np.array([0])
        else:
            ms = np.arange(-R, df // 2 + 1)
        j = (p - ms) % FFTLEN                       # frame positions
        src = f * CHUNKLEN - overlap + j            # series samples
        ok = (src >= 0) & (src < n)
        v = np.where(ok, normed[np.clip(src, 0, n - 1)], 0.0)
        s = np.sum(v.astype(np.float32) if lowp else v) / np.sqrt(df)
        out.append(float(bf16(s)) if lowp else float(s))
    return np.array(out)
