"""Plain reference of the banded jerk search, in float64 NumPy.

It follows Andersen & Ransom 2018 (ApJL 863, L13) and PRESTO's
accelsearch -wmax, imports nothing of the program, and computes:

- the w response by direct quadrature: for a signal whose phase over
  the segment (u = t / T in [0, 1]) is r u + z u^2 / 2 + w u^3 / 6 about
  its mean frequency, the response at Fourier offset nu is
  R(nu; z, w) = integral_0^1 exp(2 pi i (phi(u) - nu u)) du with
  phi(u) = (-z/2 + w/12) u + (z/2 - w/4) u^2 + (w/6) u^3, the cubic
  above re-referenced so that z is the MEAN fdot x T^2 and r the mean
  frequency x T (gen_w_response's model; tests/test_jerk.py states the
  convention);
- one plane cell at (r, z, w), as the correlation at the program's
  block geometry: |sum_k n_b X[k] conj(R(k - r; z, w))|^2 over the
  taps 2k - 2r in [-m, m), m = min(2 * 2 * halfwidth(z, w), kmax)
  (responses.c LOWACC half-widths), with n_b^2 = ln 2 / median |X|^2
  over the read window of the r-block b holding column 2r (blocks of
  uselen half bins on one grid from r = 0; zeros past either end of
  the spectrum); the plane is zero in blocks that are not whole below
  the top bin (accelsearch.c:167);
- the harmonic sum of a raw candidate at (r, z, w, numharm): harmonic
  h reads column round-half-up(2 r numharm h / numharm), z
  2 NEAREST_INT(z numharm h / numharm / 2) and w 20 NEAREST_INT(w
  numharm h / numharm / 20) (accel_utils.c calc_required_{r,z,w});
- the power a polished candidate reports: per harmonic h the
  interpolated amplitude A = integral_0^1 v(u) exp(-2 pi i (f u +
  z h (u^2 - u) / 2 + w h (u^3/6 - u^2/4 + u/12))) du with v(u) =
  sum_d X[rint + d] exp(2 pi i d u) over the W-bin window at the
  seed's rint, over the mean power at +-(5..14) bins at w = 0.

Departures from the paper and from PRESTO, each also the program's:

- PRESTO builds each subharmonic's plane with its own fftlen and
  zmax; here (as in the program) every plane has the fundamental's
  block geometry and z range, so a subharmonic cell is the same
  correlation at a wider kernel window (kmax), which changes nothing
  beyond rounding;
- the quadrature: the reference integrates R by composite
  Gauss-Legendre, the program by the midpoint rule on 2^14 or more
  points; both converge to the same integral;
- the polish's interpolation is the window-and-midpoint-rule
  definition above (search/polish.py), not PRESTO's rzw_interp
  kernel sum; the local power is measured at w = 0, PRESTO's
  acceptance convention for -wmax.

``lowp=True`` is the control: every stored value rounded to bfloat16,
the precision below the float32 the configuration states.
"""

from __future__ import annotations

import numpy as np

NUMBETWEEN = 2
ACCEL_DW = 20
NUMFINTBINS = 16
NUMLOCPOWAVG = 20
DELTAAVGBINS = 5
STEP0_Z, STEP0_W = 0.5, 5.0     # polish stage-0 steps and half-extents
GRID_G, GRID_GW = 3, 2


def bf16(a):
    import ml_dtypes
    a = np.asarray(a)
    if np.iscomplexobj(a):
        return bf16(a.real) + 1j * bf16(a.imag)
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def nearest_int(x: float) -> int:
    return int(np.ceil(x - 0.5)) if x < 0 else int(np.floor(x + 0.5))


def z_halfwidth(z: float, high: bool = False) -> int:
    z = abs(z)
    if high:
        m = int(z * (0.002057 * z + 0.0377) + NUMFINTBINS * 3)
        m += NUMLOCPOWAVG // 2 + DELTAAVGBINS
        if z > 100 and m > 1.2 * z:
            m = int(1.2 * z)
        return m
    m = max(int(z * (0.00089 * z + 0.3131) + NUMFINTBINS), NUMFINTBINS)
    if z > 100 and m > 0.6 * z:
        m = int(0.6 * z)
    return m


def w_halfwidth(z: float, w: float, high: bool = False) -> int:
    """Half-width in bins of the (z, w) response: the excursion of the
    instantaneous frequency of phi over [0, 1] plus the interpolation
    wings (responses.c)."""
    if abs(w) < 1e-7:
        return z_halfwidth(z, high)
    nu0 = -z / 2.0 + w / 12.0
    ext = max(abs(nu0), abs(z / 2.0 + w / 12.0))
    ustar = (w / 2.0 - z) / w
    if 0.0 < ustar < 1.0:
        ext = max(ext, abs(nu0 + (z - w / 2.0) * ustar
                           + (w / 2.0) * ustar ** 2))
    wing = (NUMFINTBINS * 3 + NUMLOCPOWAVG // 2 + DELTAAVGBINS if high
            else NUMFINTBINS)
    return int(np.ceil(ext)) + wing


def calc_z(frac: float, zfull: float) -> float:
    return nearest_int(0.5 * zfull * frac) * 2.0


def calc_w(frac: float, wfull: float) -> float:
    return nearest_int(wfull * frac / ACCEL_DW) * float(ACCEL_DW)


def harmonics(r: float, z: float, w: float, numharm: int):
    """[(column, z_h, w_h)] of each harmonic of a raw candidate at
    fundamental (r, z, w), h = 1..numharm."""
    col = int(round(2 * r * numharm))
    zf, wf = round(z * numharm), round(w * numharm)
    return [((col * h + numharm // 2) // numharm,
             calc_z(h / numharm, zf), calc_w(h / numharm, wf))
            for h in range(1, numharm + 1)]


def _gl(nseg: int, order: int = 64):
    x, wt = np.polynomial.legendre.leggauss(order)
    a = np.arange(nseg)[:, None]
    u = ((a + (x[None] + 1.0) / 2.0) / nseg).ravel()
    return u, np.tile(wt / 2.0 / nseg, nseg)


def response(nu: np.ndarray, z: float, w: float) -> np.ndarray:
    """R(nu; z, w) by composite Gauss-Legendre quadrature: 64 nodes
    for every 4 cycles of the integrand (its frequency phi'(u) - nu
    stays within the response's excursion plus |nu|)."""
    nu = np.asarray(nu, np.float64)
    span = (float(np.max(np.abs(nu))) + w_halfwidth(z, w) - NUMFINTBINS
            + 8.0)
    u, wt = _gl(int(np.ceil(span / 4.0)))
    phi = ((-z / 2 + w / 12) * u + (z / 2 - w / 4) * u * u
           + (w / 6) * u ** 3)
    return np.exp(2j * np.pi * (phi[None] - nu[:, None] * u[None])) @ wt


class Volume:
    """Reference plane cells of one spectrum.  ``geom`` is the
    program's block geometry: (uselen half bins per r-block, read-window
    offset in bins, read-window length in bins, kernel taps kmax)."""

    def __init__(self, X: np.ndarray, geom, lowp: bool = False):
        self.X = bf16(X) if lowp else np.asarray(X, np.complex128)
        self.uselen, self.hw, self.numdata, self.kmax = (int(v)
                                                         for v in geom)
        self.lowp = lowp
        self._norm2 = {}
        self._kern = {}

    def norm2(self, col: int) -> float:
        j = col // self.uselen
        if j not in self._norm2:
            lo = j * (self.uselen // 2) - self.hw
            idx = np.arange(lo, lo + self.numdata)
            ok = (idx >= 0) & (idx < self.X.size)
            v = np.where(ok, self.X[np.clip(idx, 0, self.X.size - 1)], 0)
            self._norm2[j] = np.log(2.0) / max(
                float(np.median(np.abs(v) ** 2)), 1e-30)
        return self._norm2[j]

    def power(self, col: int, z: float, w: float) -> float:
        """The plane cell at half-bin column col (r = col / 2)."""
        n = self.X.size
        if (col // self.uselen + 1) * (self.uselen // 2) >= n - 1:
            return 0.0
        m = min(2 * NUMBETWEEN * w_halfwidth(z, w), self.kmax) // 2
        t = np.arange(-m, m)
        t = t[(t + col) % 2 == 0]          # taps on whole bins k
        k = (t + col) // 2
        key = (z, w, m, col % 2)           # nu = k - r = t / 2
        if key not in self._kern:
            self._kern[key] = response(t / 2.0, z, w)
        R = self._kern[key]
        ok = (k >= 0) & (k < n)
        x = np.where(ok, self.X[np.clip(k, 0, n - 1)], 0)
        n2 = self.norm2(col)
        if self.lowp:
            x = bf16(x * np.sqrt(n2))
            A = bf16(np.sum(x * np.conj(bf16(R))))
            return float(bf16(np.abs(A) ** 2))
        return n2 * float(np.abs(np.sum(x * np.conj(R))) ** 2)

    def summed(self, r: float, z: float, w: float, numharm: int) -> float:
        tot = 0.0
        for col, zh, wh in harmonics(r, z, w, numharm):
            tot += self.power(col, zh, wh)
            if self.lowp:
                tot = float(bf16(tot))
        return tot


def polish_geometry(seeds):
    """(W, npts) of a polish batch: the window covers the widest (z, w)
    kernel of its seeds [(r, z, w, numharm)]."""
    zb = max(abs(z) * nh for _r, z, _w, nh in seeds) + STEP0_Z * GRID_G + 1
    wb = max(abs(w) * nh for _r, _z, w, nh in seeds) + STEP0_W * GRID_GW + 1
    hw = w_halfwidth(zb, wb, high=True)
    W = -(-(2 * hw + 2 * (DELTAAVGBINS + NUMLOCPOWAVG // 2) + 16)
          // 128) * 128
    need = W // 2 + zb / 2 + wb / 12.0 + 2
    npts = 128
    while npts < 2 * need:
        npts *= 2
    return W, npts


def polished_powers(X: np.ndarray, seeds, outs, pick, lowp=False):
    """Reference summed power of the polished candidates ``pick``
    (indices into outs [(r, z, w, power)]) at the (r, z, w) the program
    reported, with the window of the seed (rint of the seed's harmonic)
    and the geometry of the whole batch of seeds [(r, z, w, numharm)]."""
    W, npts = polish_geometry(seeds)
    n = X.size
    Xw = bf16(X) if lowp else np.asarray(X, np.complex128)
    u = (np.arange(npts) + 0.5) / npts
    dl = np.arange(W) - W // 2
    F = np.exp(2j * np.pi * np.outer(dl, u))
    offs = np.concatenate([-(DELTAAVGBINS + np.arange(NUMLOCPOWAVG // 2)),
                           DELTAAVGBINS + np.arange(NUMLOCPOWAVG // 2)])
    cu = 0.5 * (u * u - u)
    p3 = u ** 3 / 6.0 - u * u / 4.0 + u / 12.0
    res = []
    for i in pick:
        r, z, w = outs[i][:3]
        nh = seeds[i][3]
        hs = np.arange(1, nh + 1)
        rint = np.floor(seeds[i][0] * hs).astype(np.int64)
        idx = rint[:, None] + dl[None]
        ok = (idx >= 0) & (idx < n)
        v = np.where(ok, Xw[np.clip(idx, 0, n - 1)], 0.0) @ F
        if lowp:
            v = bf16(v)
        fr = r * hs - rint
        A0 = np.mean(v * np.exp(-2j * np.pi * (
            fr[:, None] * u + (z * hs)[:, None] * cu
            + (w * hs)[:, None] * p3)), axis=-1)
        ev = fr[:, None] + offs[None]
        Al = np.mean(v[:, None, :] * np.exp(-2j * np.pi * (
            ev[..., None] * u + (z * hs)[:, None, None] * cu)), axis=-1)
        if lowp:
            A0, Al = bf16(A0), bf16(Al)
        locpow = np.maximum(np.mean(np.abs(Al) ** 2, axis=1), 1e-30)
        res.append(float(np.sum(np.abs(A0) ** 2 / locpow)))
    return np.array(res)
