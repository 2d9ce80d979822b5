"""realfft: forward/inverse packed real FFT of .dat/.fft files.

CLI parity with the reference realfft (src/realfft.c:32-): positional
data files, -fwd/-inv to force direction (default: .dat -> forward,
.fft -> inverse), -del to remove the input after success, -disk/-mem
to force the out-of-core vs in-core path.  Like the reference
(src/realfft.c:179, include/meminfo.h:4), series longer than a
MAXREALFFT-analog threshold automatically divert to the two-pass disk
FFT (ops/oocfft); multi-device scale goes through the sharded
six-step path in parallel.sharded instead.
"""

from __future__ import annotations

import argparse
import os

import numpy as np
import jax.numpy as jnp

from presto_tpu.io import datfft
from presto_tpu.io.infodata import read_inf, write_inf
from presto_tpu.ops import fftpack


def build_parser():
    p = argparse.ArgumentParser(prog="realfft")
    p.add_argument("-fwd", action="store_true")
    p.add_argument("-inv", action="store_true")
    p.add_argument("-del", dest="delete", action="store_true",
                   help="Remove the input file on success")
    p.add_argument("-disk", action="store_true",
                   help="Force the out-of-core two-pass disk FFT")
    p.add_argument("-mem", action="store_true",
                   help="Force the in-core FFT regardless of size")
    p.add_argument("-tmpdir", type=str, default=None,
                   help="Scratch directory for out-of-core temp files")
    p.add_argument("-outdir", type=str, default=None,
                   help="Directory where result files will reside")
    p.add_argument("datafiles", nargs="+")
    return p


def _xla_friendly(n: int) -> bool:
    """XLA's FFT is fast for 7-smooth lengths; a larger prime factor
    can make it materialize a dense DFT matrix (O(n^2) HBM — observed
    OOM at ~5e5 points).  Such lengths go through host pocketfft,
    which like the reference's FFTW handles any n."""
    from presto_tpu.utils.psr import _is_smooth
    return _is_smooth(n)


def _host_realfft_packed(x: np.ndarray) -> np.ndarray:
    full = np.fft.rfft(x.astype(np.float64))
    return np.concatenate(
        [[full[0].real + 1j * full[-1].real], full[1:-1]]
    ).astype(np.complex64)


def _host_irealfft_packed(amps: np.ndarray) -> np.ndarray:
    full = np.concatenate([[amps[0].real], amps[1:],
                           [amps[0].imag]]).astype(np.complex128)
    return np.fft.irfft(full, n=2 * amps.size).astype(np.float32)


def run_one(path: str, forward: bool, delete: bool,
            disk: bool = False, mem: bool = False,
            tmpdir: str | None = None,
            outdir: str | None = None) -> str:
    from presto_tpu.ops import oocfft
    base, ext = os.path.splitext(path)
    info = read_inf(base)
    obase = (os.path.join(outdir, os.path.basename(base)) if outdir
             else base)
    if forward:
        src = base + ".dat"
        out = obase + ".fft"
        nfloats = os.path.getsize(src) // 4
        if not mem and nfloats >= 8 and (disk or
                                         nfloats > oocfft.MAXREALFFT):
            oocfft.realfft_ooc(src, out, forward=True, tmpdir=tmpdir)
        else:
            data = datfft.read_dat(src)
            n = data.size & ~1
            if _xla_friendly(n):
                pairs = np.asarray(fftpack.realfft_packed_pairs(
                    jnp.asarray(data[:n])))
                packed = fftpack.np_pairs_to_complex64(pairs)
            else:
                packed = _host_realfft_packed(data[:n])
            datfft.write_fft(out, packed)
        write_inf(info, obase + ".inf")
        if delete:
            os.remove(src)
    else:
        src = base + ".fft"
        out = obase + ".dat"
        namps = os.path.getsize(src) // 8
        if not mem and namps >= 4 and (disk or
                                       2 * namps > oocfft.MAXREALFFT):
            oocfft.realfft_ooc(src, out, forward=False, tmpdir=tmpdir)
        else:
            amps = datfft.read_fft(src)
            if _xla_friendly(2 * amps.size):
                pairs = fftpack.np_complex64_to_pairs(amps)
                data = np.asarray(fftpack.irealfft_packed_pairs(
                    jnp.asarray(pairs)))
            else:
                data = _host_irealfft_packed(amps)
            datfft.write_dat(out, data)
        write_inf(info, obase + ".inf")
        if delete:
            os.remove(src)
    print("realfft: wrote %s" % out)
    return out


def main(argv=None):
    args = build_parser().parse_args(argv)
    for path in args.datafiles:
        ext = os.path.splitext(path)[1]
        forward = args.fwd or (ext == ".dat" and not args.inv)
        run_one(path, forward, args.delete, disk=args.disk,
                mem=args.mem, tmpdir=args.tmpdir, outdir=args.outdir)


if __name__ == "__main__":
    main()
