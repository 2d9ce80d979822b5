"""prepdata: raw data -> single-DM dedispersed time series (.dat+.inf).

CLI parity with the reference prepdata (clig/prepdata_cmd.cli;
src/prepdata.c:34-): -o, -dm, -downsamp, -nobary, -mask, -clip,
-zerodm, -ignorechan.  Barycentering is on by default and uses the
built-in analytic ephemeris (presto_tpu.astro replaces the reference's
TEMPO subprocess, barycenter.c:156): dispersion delays are computed at
Doppler-shifted frequencies and single bins are added/removed on the
diffbins schedule (prepdata.c:469-505) so the output is uniformly
sampled in barycentric time, epoch = bary MJD of the first sample.

Pipeline (reference read_psrdata, backend_common.c:505-604):
  read block -> [mask] -> [clip] -> [zerodm] -> dedisperse at -dm ->
  downsample -> append to .dat
"""

from __future__ import annotations

import argparse

import numpy as np
import jax.numpy as jnp

from presto_tpu.apps.common import (add_common_flags, add_raw_flags,
                                    open_raw_args, BlockPrep,
                                    fil_to_inf,
                                    pad_to_good_N, set_onoff,
                                    make_bary_plan, set_bary_epoch,
                                    start_skip_spectra, stream_blocklen)
from presto_tpu.io.datfft import write_dat, write_sdat
from presto_tpu.io.maskfile import read_mask, determine_padvals
from presto_tpu.ops import dedispersion as dd
from presto_tpu.utils.ranges import parse_ranges


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="prepdata",
        description="Prepare (dedisperse) raw data into a .dat series")
    add_common_flags(p)
    p.add_argument("-dm", type=float, default=0.0,
                   help="Dispersion measure (cm-3 pc)")
    p.add_argument("-downsamp", type=int, default=1)
    p.add_argument("-nobary", action="store_true",
                   help="Do not barycenter the output (default is to "
                        "barycenter via the built-in ephemeris)")
    p.add_argument("-ephem", type=str, default="DE405",
                   help="Ephemeris: DE200/DE405 (analytic model) or a "
                        "path to a tabulated .npz ephemeris")
    p.add_argument("-mask", type=str, default=None,
                   help="rfifind .mask file to apply")
    p.add_argument("-clip", type=float, default=6.0,
                   help="Time-domain clip sigma (0=no clipping)")
    p.add_argument("-zerodm", action="store_true")
    p.add_argument("-numout", type=int, default=0,
                   help="Output exactly this many samples (pad/truncate)")
    p.add_argument("-ignorechan", type=str, default=None,
                   help="Channels to zero out, e.g. '0:5,34'")
    p.add_argument("-shorts", action="store_true",
                   help="Write short ints (.sdat) instead of floats")
    p.add_argument("-resume", action="store_true",
                   help="Verify-not-trust resume: skip the run when "
                        "the outputs exist AND match the manifest.json "
                        "journal next to them; journal them on "
                        "completion")
    add_raw_flags(p)
    p.add_argument("rawfiles", nargs="+")
    return p


def run(args) -> str:
    outbase_early = args.outfile or "prepdata_out"
    resume = None
    if getattr(args, "resume", False):
        from presto_tpu.apps.common import CLIResume
        resume = CLIResume(outbase_early, "prepdata-cli")
        suffix = ".sdat" if args.shorts else ".dat"
        expected = [outbase_early + suffix, outbase_early + ".inf"]
        if resume.complete(expected):
            print("prepdata: -resume verified %s%s + .inf against the "
                  "journal — skipping" % (outbase_early, suffix))
            return outbase_early
        resume.invalidate_stale(expected)
    fb = open_raw_args(args.rawfiles, args)
    hdr = fb.header
    nchan = hdr.nchans
    dt = hdr.tsamp
    skip = start_skip_spectra(args, int(hdr.N))
    Ntot = int(hdr.N) - skip

    plan = (make_bary_plan(fb, dt * args.downsamp, args.ephem,
                           skip_spectra=skip)
            if not args.nobary else None)
    avgvoverc = plan.avgvoverc if plan is not None else 0.0
    delays = dd.dedisp_delays(nchan, args.dm, hdr.lofreq, abs(hdr.foff),
                              voverc=avgvoverc)
    bins = dd.delays_to_bins(delays - delays.min(), dt)
    maxd = int(bins.max())

    mask = read_mask(args.mask) if args.mask else None
    padvals = np.zeros(nchan, dtype=np.float32)
    if args.mask:
        try:
            padvals = determine_padvals(
                args.mask.replace(".mask", ".stats"))
        except OSError:
            pass
    ignore = (np.asarray(parse_ranges(args.ignorechan), dtype=np.int64)
              if args.ignorechan else None)
    prep = BlockPrep(nchan, dt, args, mask=mask,
                     padvals=padvals if args.mask else None,
                     ignore=ignore)

    blocklen = stream_blocklen(nchan, maxd, nspec=int(hdr.N) - skip)
    out = []
    bins_d = jnp.asarray(bins)
    prev = jnp.zeros((nchan, blocklen), dtype=jnp.float32)

    def _produce_blocks():
        """Decoded+preprocessed channel-major blocks (ingest worker
        thread: block k+1's decode/mask/clip/transpose overlaps the
        device dedispersion of block k, pipeline/fusion.py).  The
        native feeder already prefetches the raw reads underneath."""
        block_iter = (fb.stream_blocks(blocklen)
                      if skip == 0 and hasattr(fb, "stream_blocks")
                      else None)
        nread = skip
        while nread < hdr.N:
            block = (next(block_iter) if block_iter is not None
                     else fb.read_spectra(nread, blocklen))  # [T, C]
            block = prep(block, nread)
            yield np.ascontiguousarray(block.T)              # [C, T]
            nread += blocklen

    from presto_tpu.pipeline import fusion
    first = True
    with fusion.DoubleBufferedIngest(_produce_blocks()) as ingest:
        for blockT in ingest:
            # upload each block ONCE and carry the device array as
            # prev (re-uploading prev doubled the host->device
            # traffic); results stay on device and download once at
            # the end
            cur = jnp.asarray(blockT)
            series = dd.float_dedisp_block(prev, cur, bins_d)
            if not first:
                out.append(series)
            first = False
            prev = cur
    # flush the final window with a zero block
    series = dd.float_dedisp_block(prev, jnp.zeros_like(prev), bins_d)
    out.append(series[:blocklen - maxd] if maxd else series)

    result = np.asarray(jnp.concatenate(out))
    # trim zero-padded tail: only N - maxd samples are fully dedispersed
    # (the prepsubband `valid` truncation, prepsubband.c:703-735 stats)
    result = result[:max(Ntot - maxd, 0)]
    if args.downsamp > 1:
        n = result.size // args.downsamp * args.downsamp
        result = result[:n].reshape(-1, args.downsamp).mean(axis=1)
    if plan is not None:
        result = plan.apply(result)
    result, valid, numout = pad_to_good_N(result, args.numout)

    outbase = args.outfile or "prepdata_out"
    info = fil_to_inf(fb, outbase, result.size, dm=args.dm)
    if plan is not None:
        set_bary_epoch(info, plan)
    elif skip:
        info.mjd_f += skip * dt / 86400.0
        info.mjd_i += int(info.mjd_f)
        info.mjd_f %= 1.0
    info.dt = dt * args.downsamp
    set_onoff(info, valid, numout)
    suffix = ".dat"
    if args.shorts:
        off = write_sdat(outbase + ".sdat", result.astype(np.float32),
                         info)
        if off is None:
            print("Error: way too much dynamic range for shorts; "
                  "writing floats instead.")
            write_dat(outbase + ".dat", result.astype(np.float32), info)
        else:
            suffix = ".sdat"
            if off:
                print("          Offset applied to data:  %d" % -int(off))
    else:
        write_dat(outbase + ".dat", result.astype(np.float32), info)
    fb.close()
    if resume is not None:
        resume.record([outbase + suffix, outbase + ".inf"])
    print("Wrote %d samples to %s%s (DM=%g, downsamp=%d)"
          % (result.size, outbase, suffix, args.dm, args.downsamp))
    return outbase


def main(argv=None):
    from presto_tpu.utils.timing import app_timer
    args = build_parser().parse_args(argv)
    with app_timer("prepdata"):
        run(args)


if __name__ == "__main__":
    main()
