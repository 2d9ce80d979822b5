"""ctypes bindings for the C++ native IO runtime (csrc/native_io.cpp).

The reference keeps its raw-data path in C (INSTRUMENTOBJS: bit-unpack
psrfits.c:828-866, scale/offset/weight psrfits.c:805-814, the
get_rawblock readers behind backend_common.h:86-87).  This module loads
the TPU-era equivalent — fused decode kernels + a pthread prefetching
block feeder — and falls back to pure NumPy when the shared
library cannot be built or `PRESTO_TPU_NO_NATIVE=1`.  A failed build
warns once and leaves its reason in ``build_error``.

The library is built with `make -C csrc` on first use, into a file
named by a hash of the committed sources (``library_path``); every
entry point here is exercised against the NumPy reference path in
tests/test_native.py.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import warnings
from typing import Iterator, Optional

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SOURCES = ("native_io.cpp", "Makefile")

_lib = None
#: why the native library is unavailable (None while it loads or
#: before the first try); callers that must not run on the NumPy
#: fallback (chip_smoke.py) raise with it
build_error: Optional[str] = None


def library_path() -> str:
    """The build product for the committed sources: keyed on their
    CONTENT, so a library copied along with a tree (or left from an
    older source) is never loaded in place of what git holds."""
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_CSRC, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_CSRC, "libpresto_tpu_io.%s.so"
                        % h.hexdigest()[:16])


def _build(so: str) -> None:
    """make into a private name, then rename: concurrent builders
    (xdist workers, replicas) never load a half-written library."""
    tmp = "%s.%d.tmp" % (so, os.getpid())
    subprocess.run(["make", "-C", _CSRC, "TARGET=" + os.path.basename(tmp)],
                   check=True, capture_output=True, text=True,
                   timeout=300)
    os.replace(tmp, so)


def _load():
    global _lib, build_error
    if _lib is not None:
        return _lib
    if build_error is not None or os.environ.get("PRESTO_TPU_NO_NATIVE"):
        return None
    try:
        so = library_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
    except (OSError, subprocess.SubprocessError) as e:
        err = getattr(e, "stderr", None) or ""
        build_error = ("%s: %s %s" % (type(e).__name__, e, err)).strip()
        warnings.warn("native IO library unavailable, decoding with "
                      "NumPy: " + build_error, RuntimeWarning,
                      stacklevel=3)
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64 = ctypes.c_int64
    i32 = ctypes.c_int
    lib.pt_unpack_bits.argtypes = [u8p, i64, i32, u8p]
    lib.pt_unpack_to_float.argtypes = [u8p, i64, i32, f32p]
    lib.pt_decode_spectra.argtypes = [u8p, i64, i32, i32, i32, i32, f32p]
    lib.pt_decode_spectra_chan.argtypes = [u8p, i64, i32, i32, i32, i32,
                                           f32p]
    lib.pt_decode_subint.argtypes = [u8p, i64, i32, i32, i32,
                                     ctypes.c_float, f32p, f32p, f32p,
                                     i32, i32, f32p]
    lib.pt_feeder_open.argtypes = [ctypes.c_char_p, i64, i64, i32]
    lib.pt_feeder_open.restype = ctypes.c_void_p
    lib.pt_feeder_next.argtypes = [ctypes.c_void_p, u8p]
    lib.pt_feeder_next.restype = i64
    lib.pt_feeder_close.argtypes = [ctypes.c_void_p]
    lib.pt_feeder_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(i64)]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def _u8ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _f32ptr(a: Optional[np.ndarray]):
    if a is None:
        return None
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def unpack_bits(raw: np.ndarray, nbits: int) -> Optional[np.ndarray]:
    """1/2/4-bit -> uint8, MSB-first. None if native path unavailable."""
    lib = _load()
    if lib is None or nbits not in (1, 2, 4, 8):
        return None
    raw = np.ascontiguousarray(raw, np.uint8)
    out = np.empty(raw.size * 8 // nbits, np.uint8)
    lib.pt_unpack_bits(_u8ptr(raw), raw.size, nbits, _u8ptr(out))
    return out


def decode_spectra(raw: np.ndarray, nspec: int, nifs: int, nchan: int,
                   nbits: int, flip: bool,
                   chan_major: bool = True) -> Optional[np.ndarray]:
    """Fused filterbank block decode -> float32 [nspec, nchan].

    chan_major: the block's memory is channel-major (Fortran order, so
    its ``.T`` is a C-contiguous [nchan, nspec]); False gives the
    time-major (C order) block.  Either way the array owns its data."""
    lib = _load()
    if lib is None or nbits not in (1, 2, 4, 8):
        return None
    raw = np.ascontiguousarray(raw, np.uint8)
    if raw.size * 8 != nspec * nifs * nchan * nbits:
        return None
    if (nifs * nchan * nbits) % 8 != 0:
        return None      # spectra not byte-aligned; NumPy path handles
    if chan_major:
        out = np.empty((nspec, nchan), np.float32, order="F")
        decode = lib.pt_decode_spectra_chan
    else:
        out = np.empty((nspec, nchan), np.float32)
        decode = lib.pt_decode_spectra
    decode(_u8ptr(raw), nspec, nifs, nchan, nbits, int(flip),
           _f32ptr(out))
    return out


def can_decode_subint(npol: int, nchan: int, nbits: int) -> bool:
    """Cheap predicate: native decode_subint supports this geometry.
    Lets callers skip gathering scale/offset/weight columns when the
    NumPy fallback would be used anyway."""
    return (_load() is not None and nbits in (1, 2, 4, 8)
            and (npol * nchan * nbits) % 8 == 0)


def decode_subint(raw: np.ndarray, nspec: int, npol: int, nchan: int,
                  nbits: int, zero_off: float,
                  scl: Optional[np.ndarray], offs: Optional[np.ndarray],
                  wts: Optional[np.ndarray], pol_mode: int,
                  flip: bool) -> Optional[np.ndarray]:
    """Fused PSRFITS subint decode (psrfits.c:789-920 analog).

    pol_mode: >=0 select that pol, -2 sum the first two pols.
    scl/offs are [npol*nchan]; wts is [nchan]; any may be None.
    """
    lib = _load()
    if lib is None or nbits not in (1, 2, 4, 8):
        return None
    raw = np.ascontiguousarray(raw, np.uint8)
    if raw.size * 8 != nspec * npol * nchan * nbits:
        return None
    if (npol * nchan * nbits) % 8 != 0:
        return None      # spectra not byte-aligned; NumPy path handles
    scl = None if scl is None else np.ascontiguousarray(scl, np.float32)
    offs = None if offs is None else np.ascontiguousarray(offs, np.float32)
    wts = None if wts is None else np.ascontiguousarray(wts, np.float32)
    # C reads scl/offs[0:npol*nchan] and wts[0:nchan]: short arrays
    # (malformed TFORM repeat counts) must fall back to the NumPy path,
    # which raises loudly instead of reading out of bounds
    if any(a is not None and a.size < npol * nchan for a in (scl, offs)):
        return None
    if wts is not None and wts.size < nchan:
        return None
    out = np.empty((nspec, nchan), np.float32)
    lib.pt_decode_subint(_u8ptr(raw), nspec, npol, nchan, nbits,
                         float(zero_off), _f32ptr(scl), _f32ptr(offs),
                         _f32ptr(wts), pol_mode, int(flip), _f32ptr(out))
    return out


class BlockFeeder:
    """Background-prefetching sequential block reader over one file.

    Wraps the pthread ring-buffer feeder: the read of block k+1..k+nbuf
    overlaps the consumer's processing of block k, hiding disk latency
    from the device-feed loop (the role the reference's streaming
    double-buffer plays, prepsubband.c:930-942).
    """

    def __init__(self, path: str, start_offset: int, block_bytes: int,
                 nbuf: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self.block_bytes = int(block_bytes)
        self._h = lib.pt_feeder_open(path.encode(), int(start_offset),
                                     self.block_bytes, int(nbuf))
        if not self._h:
            raise OSError("pt_feeder_open failed for %s" % path)

    def __iter__(self) -> Iterator[np.ndarray]:
        while True:
            buf = np.empty(self.block_bytes, np.uint8)
            n = self._lib.pt_feeder_next(self._h, _u8ptr(buf))
            if n < 0:
                raise IOError("I/O error while prefetching blocks")
            if n == 0:
                return
            yield buf[:n]

    def stats(self) -> Optional[dict]:
        """Ingest-overlap attribution: blocks delivered plus how often
        each side of the ring waited on the other (consumer_waits ->
        disk-bound, producer_waits -> compute-bound).  None when the
        loaded library predates the symbol."""
        if not self._h:
            return None
        out = (ctypes.c_int64 * 3)()
        self._lib.pt_feeder_stats(self._h, out)
        return {"blocks": int(out[0]),
                "consumer_waits": int(out[1]),
                "producer_waits": int(out[2])}

    def close(self) -> None:
        if self._h:
            self._lib.pt_feeder_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
