"""presto_tpu — a TPU-native pulsar search & analysis framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of PRESTO
(reference: /root/reference): RFI excision, dedispersion, FFT,
Fourier-domain acceleration search, phase-modulation (miniFFT) search,
single-pulse matched filtering, candidate sifting, and folding —
expressed as pure, jit-compiled, shardable tensor programs over
`jax.sharding.Mesh` device meshes.

Layering (bottom-up):
  utils/    — constants, unit conversions, smooth-length selection
  io/       — .inf sidecars, SIGPROC filterbank, PSRFITS, .dat/.fft, masks
  ops/      — device ops: dedispersion, packed real FFT, Fourier response
              kernels, correlation, statistics, folding, clipping
  models/   — synthetic signal generation (makedata parity), orbits
  search/   — accelsearch, single-pulse, phase-modulation, sifting, DDplan
  parallel/ — mesh construction, DM-sharded plans, sequence-sharded FFT
  apps/     — CLI entry points with PRESTO flag parity
"""

import os

__version__ = "0.2.0"

#: the compile cache's home when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def _enable_compilation_cache():
    """Persist XLA compilations across processes.

    The reference amortizes FFTW planning cost with a wisdom file
    (src/fftcalls.c:19 reads $PRESTO/lib/fftw_wisdom.txt); the XLA-era
    equivalent is the persistent compilation cache, which turns the
    cold-start of a full accelsearch program into a cache load on
    every later process.  The cache is placed from outside: where
    JAX_COMPILATION_CACHE_DIR is set, JAX reads it and that is the
    only cache directory; otherwise a run on a non-CPU platform keeps
    it in the fixed ``<checkout>/.jax_cache``.
    """
    env_dir = "JAX_COMPILATION_CACHE_DIR" in os.environ
    if not env_dir and not _accelerator_run():
        return
    import jax

    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _accelerator_run() -> bool:
    """Will this process compile for a platform other than the CPU?
    Decided without starting a backend: JAX_PLATFORMS names the first
    platform; unset, JAX picks the TPU when a TPU runtime is
    installed.  CPU runs keep no cache of their own: XLA:CPU entries
    are pinned to the host's CPU features, and loading one on another
    host risks SIGILL."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    first = first.strip().lower()
    if first:
        return first != "cpu"
    import importlib.util
    return importlib.util.find_spec("libtpu") is not None


_enable_compilation_cache()

from presto_tpu.utils import psr  # noqa: F401
