"""Real/complex FFT layer with PRESTO packed-format parity.

The reference dispatches every FFT through the COMPLEXFFT macro
(include/ransomfft.h:34-47) and implements the packed real FFT in
realfft (src/fastffts.c:198-270): forward (isign=-1) matches numpy's
e^{-2πi} convention, unnormalized; the half-complex result is stored as
n/2 complex values with X[0] = (DC, Nyquist).

On TPU everything maps to jnp.fft (XLA custom FFT): the plan caching,
six-step >2e8-point path and out-of-core two-pass path of the reference
(fftcalls.c:53-152, fastffts.c:38-195, twopass*.c) are replaced by
XLA's native FFT plus, for sizes beyond one device's HBM, the sharded
six-step FFT in presto_tpu.parallel.distfft.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Every public device function here exposes a float32 boundary —
# packed spectra travel as [..., n//2, 2] float32 "pairs" — and
# complex dtype exists only inside jit.  (The rule was born on a
# retired remote-chip link that could not move complex arrays; on a
# TPU v5e with jax 0.9 / libtpu 0.0.34 complex64 crosses host<->device
# both ways (chip_smoke.py, PR 21).  The pair API stays: the .fft
# artifacts and every stage are written against it.)  The *_pairs
# functions are the canonical TPU API; the complex-returning variants
# are conveniences for host callers (tests, host tooling).


def realfft_packed(x):
    """Forward packed real FFT of a float32 series (length even).

    Returns complex64 [n//2]: out[0] = DC + 1j*Nyquist (both real),
    out[k] = rfft(x)[k] for 1 <= k < n/2.  Unnormalized, e^{-2πi}
    convention — bit-parity with realfft(data, n, -1).
    """
    n = x.shape[-1]
    full = jnp.fft.rfft(x)                       # [..., n//2 + 1]
    dc = full[..., 0].real
    nyq = full[..., -1].real
    packed0 = (dc + 1j * nyq)[..., None]
    return jnp.concatenate([packed0, full[..., 1:-1]],
                           axis=-1).astype(jnp.complex64)


def irealfft_packed(packed, scale=True):
    """Inverse of realfft_packed.  If `scale`, divides by n/2 like the
    reference's isign=+1 path (which multiplies by 2/n after an
    unnormalized half-length inverse; net effect: x = irfft(full)*n * 2/n
    ... i.e. the reference returns 2/n times the unnormalized inverse).
    """
    n2 = packed.shape[-1]
    dc = packed[..., 0].real
    nyq = packed[..., 0].imag
    full = jnp.concatenate(
        [dc[..., None].astype(jnp.complex64),
         packed[..., 1:],
         nyq[..., None].astype(jnp.complex64)], axis=-1)
    x = jnp.fft.irfft(full, n=2 * n2)
    if scale:
        return x.astype(jnp.float32)
    return (x * (2 * n2)).astype(jnp.float32)


def complex_to_pairs(z):
    """[...,] complex -> [..., 2] float32 (inside-jit helper)."""
    return jnp.stack([z.real, z.imag], axis=-1).astype(jnp.float32)


def pairs_to_complex(p):
    """[..., 2] float32 -> [...] complex64 (inside-jit helper)."""
    return (p[..., 0] + 1j * p[..., 1]).astype(jnp.complex64)


@jax.jit
def realfft_packed_pairs(x):
    """Forward packed real FFT with a float32 boundary.

    Returns [..., n//2, 2] float32 where [..., k, :] = (Re, Im) of the
    packed bin k.  This is the canonical device API (see NOTE above).
    """
    return complex_to_pairs(realfft_packed(x))


@jax.jit
def irealfft_packed_pairs(p):
    """Inverse of realfft_packed_pairs ([..., n//2, 2] float32 -> x)."""
    return irealfft_packed(pairs_to_complex(p))


def np_pairs_to_complex64(p: np.ndarray) -> np.ndarray:
    """Host-side: [..., n, 2] float32 -> complex64 (for .fft files)."""
    return np.ascontiguousarray(p[..., 0] + 1j * p[..., 1]).astype(np.complex64)


def np_complex64_to_pairs(z: np.ndarray) -> np.ndarray:
    """Host-side inverse of np_pairs_to_complex64."""
    return np.stack([z.real, z.imag], axis=-1).astype(np.float32)


def spectral_power(packed):
    """|X_k|^2 for a packed spectrum, k = 0..n/2-1 (DC power at k=0 uses
    only the DC part, matching PRESTO's power spectra over .fft files)."""
    p = jnp.abs(packed) ** 2
    dc = packed[..., 0].real ** 2
    return jnp.concatenate([dc[..., None], p[..., 1:]], axis=-1)


def fourier_freqs(n, dt):
    """Frequencies (Hz) of packed bins 0..n/2-1."""
    return np.arange(n // 2) / (n * dt)


def next_good_fftlen(n: int) -> int:
    """Smallest 7-smooth length >= n (XLA FFT is efficient for
    2/3/5/7-smooth sizes)."""
    from presto_tpu.utils.psr import good_fft_size
    return good_fft_size(n, multiple_of=2)
