"""rfifind CLI: RFI statistics + mask generation from raw data.

CLI parity with the reference rfifind (clig/rfifind_cmd.cli;
src/rfifind.c:53-): -time/-blocks, -timesig, -freqsig, -chanfrac,
-intfrac, -zapchan, -zapints, -zerodm, -mask, -ignorechan,
-nocompute (re-threshold/replot from existing .stats), the shared raw
flags (-filterbank/-psrfits/-no{weights,scales,offsets}/-invert/
-noclip), and the plot toggles (-xwin, -rfips, -rfixwin).  Writes
<o>_rfifind.mask and <o>_rfifind.stats (binary parity) plus
<o>_rfifind.inf and a summary plot.
"""

from __future__ import annotations

import argparse
import os

import numpy as np

from presto_tpu.apps.common import (add_common_flags, add_raw_flags,
                                    open_raw_args, BlockPrep,
                                    fil_to_inf, obs_metadata)
from presto_tpu.io.infodata import write_inf, read_inf
from presto_tpu.io.maskfile import (read_mask, read_statsfile,
                                    determine_padvals)
from presto_tpu.search.rfifind import (rfifind_stream,
                                       rfifind_from_stats,
                                       write_rfifind_products)
from presto_tpu.utils.ranges import parse_ranges


def build_parser():
    p = argparse.ArgumentParser(prog="rfifind")
    add_common_flags(p)
    p.add_argument("-time", type=float, default=30.0,
                   help="Seconds per interval (use this or -blocks)")
    p.add_argument("-blocks", type=int, default=0,
                   help="Raw-data blocks per interval (beats -time; a "
                        "block is the format's natural read unit: a "
                        "PSRFITS subint or a SUBSBLOCKLEN=1024-sample "
                        "section, presto.h:59)")
    p.add_argument("-timesig", type=float, default=10.0)
    p.add_argument("-freqsig", type=float, default=4.0)
    p.add_argument("-chanfrac", type=float, default=0.7)
    p.add_argument("-intfrac", type=float, default=0.3)
    p.add_argument("-zapchan", type=str, default=None,
                   help="Channels to zap, e.g. '0:3,45'")
    p.add_argument("-zapints", type=str, default=None)
    p.add_argument("-ignorechan", type=str, default=None,
                   help="Channels to ignore (zapped from the start)")
    p.add_argument("-clip", type=float, default=6.0)
    p.add_argument("-zerodm", action="store_true",
                   help="Subtract the per-sample band mean before "
                        "computing statistics")
    p.add_argument("-mask", type=str, default=None,
                   help="Existing .mask to apply while computing")
    p.add_argument("-nocompute", action="store_true",
                   help="Re-threshold and re-plot from the existing "
                        "_rfifind.stats/.inf (no raw data read)")
    p.add_argument("-noplot", action="store_true",
                   help="Skip the mask summary plot")
    p.add_argument("-xwin", action="store_true",
                   help="Also draw plots to the screen")
    p.add_argument("-rfips", action="store_true",
                   help="Also write the summary plot as PostScript")
    p.add_argument("-rfixwin", action="store_true",
                   help="Show RFI instances on screen (with -xwin)")
    add_raw_flags(p, start_flags=False)
    p.add_argument("rawfiles", nargs="*")
    return p


def _plots(args, res, outbase):
    if getattr(args, "noplot", False):
        return
    from presto_tpu.plotting import plot_rfifind
    plot_rfifind(res, outbase + "_rfifind.png")
    print("rfifind: mask plot -> %s_rfifind.png" % outbase)
    if args.rfips:
        plot_rfifind(res, outbase + "_rfifind.ps")
        print("rfifind: mask plot -> %s_rfifind.ps" % outbase)
    if args.xwin or args.rfixwin:
        if os.environ.get("DISPLAY") or os.environ.get("MPLBACKEND"):
            import matplotlib.pyplot as plt
            plt.show()
        else:
            print("rfifind: no display available for -xwin/-rfixwin "
                  "(plots were written to files)")


def _run_nocompute(args):
    outbase = args.outfile or "rfifind_out"
    stats = read_statsfile(outbase + "_rfifind.stats")
    info = read_inf(outbase + "_rfifind")
    zap_chans = parse_ranges(args.zapchan) if args.zapchan else []
    if args.ignorechan:
        zap_chans = sorted(set(zap_chans)
                           | set(parse_ranges(args.ignorechan)))
    zap_ints = parse_ranges(args.zapints) if args.zapints else []
    res = rfifind_from_stats(
        stats, dt=info.dt, lofreq=info.freq, chanwidth=info.chan_wid,
        timesigma=args.timesig, freqsigma=args.freqsig,
        chantrigfrac=args.chanfrac, inttrigfrac=args.intfrac,
        mjd=info.mjd_i + info.mjd_f, zap_chans=zap_chans,
        zap_ints=zap_ints)
    res.info = {"filenm": getattr(info, "name", "") or "-",
                "telescope": info.telescope, "ra": info.ra_str,
                "dec": info.dec_str, "chanfrac": args.chanfrac,
                "intfrac": args.intfrac}
    write_rfifind_products(res, outbase)
    print("rfifind -nocompute: re-thresholded %d ints x %d chans, "
          "%.1f%% masked -> %s_rfifind.mask"
          % (res.mask.numint, res.mask.numchan,
             100 * res.masked_fraction(), outbase))
    _plots(args, res, outbase)
    return res


def run(args):
    if args.nocompute:
        return _run_nocompute(args)
    if not args.rawfiles:
        raise SystemExit("rfifind: no raw files given")
    fb = open_raw_args(args.rawfiles, args)
    hdr = fb.header
    zap_chans = parse_ranges(args.zapchan) if args.zapchan else []
    ignore = None
    if args.ignorechan:
        ignore = np.asarray(parse_ranges(args.ignorechan), np.int64)
        zap_chans = sorted(set(zap_chans) | set(ignore.tolist()))
    zap_ints = parse_ranges(args.zapints) if args.zapints else []
    if args.blocks > 0:
        # spectra_per_subint analog: NSBLK for PSRFITS, 2400 for
        # SIGPROC (rfifind.c:214, sigproc_fb.c:388)
        blk = getattr(fb, "ptsperblk", 0) or 1024
        ptsperint = args.blocks * int(blk)
    else:
        ptsperint = max(1, int(args.time / hdr.tsamp + 0.5))
    numint = hdr.N // ptsperint

    mask = read_mask(args.mask) if args.mask else None
    padvals = np.zeros(hdr.nchans, np.float32)
    if args.mask:
        try:
            padvals = determine_padvals(
                args.mask.replace(".mask", ".stats"))
        except OSError:
            pass
    prep = BlockPrep(hdr.nchans, hdr.tsamp, args, mask=mask,
                     padvals=padvals if args.mask else None,
                     ignore=ignore)

    def intervals():
        # stream one interval at a time: never the whole file in RAM
        for i in range(numint):
            blk = fb.read_spectra(i * ptsperint, ptsperint)
            yield prep(blk, i * ptsperint)

    res = rfifind_stream(intervals(), hdr.nchans, ptsperint,
                         dt=hdr.tsamp, lofreq=hdr.lofreq,
                         chanwidth=abs(hdr.foff),
                         timesigma=args.timesig, freqsigma=args.freqsig,
                         chantrigfrac=args.chanfrac,
                         inttrigfrac=args.intfrac,
                         mjd=hdr.tstart, zap_chans=zap_chans,
                         zap_ints=zap_ints)
    outbase = args.outfile or "rfifind_out"
    # ingest quarantine -> mask integration: stretches the reader
    # quarantined while streaming (NaN/Inf scrubs, zero-fill runs,
    # short reads, dropped PSRFITS rows) become zapped intervals
    # exactly like statistical RFI, and the DataQualityReport itself
    # is written as a durable artifact next to the mask.
    quality = getattr(fb, "quality", None)
    if quality is not None:
        extra = quality.zap_intervals(ptsperint, res.mask.numint)
        if extra:
            res.mask.zap_ints = np.asarray(
                sorted(set(res.mask.zap_ints.tolist()) | set(extra)),
                np.int32)
        if not quality.clean:
            print("rfifind: %s" % quality.summary())
        quality.write(outbase + "_rfifind_quality.json")
        from presto_tpu.obs import get_obs
        obs = get_obs()
        if obs.enabled:            # standalone-CLI ingest telemetry
            quality.publish(obs.metrics)
    write_rfifind_products(res, outbase)
    info = fil_to_inf(fb, outbase + "_rfifind", hdr.N)
    write_inf(info, outbase + "_rfifind.inf")
    tel, ra, dec = obs_metadata(fb)
    res.info = {"filenm": args.rawfiles[0], "telescope": tel,
                "ra": ra, "dec": dec, "chanfrac": args.chanfrac,
                "intfrac": args.intfrac}    # plot info block
    fb.close()
    print("rfifind: %d ints x %d chans, %.1f%% masked -> %s_rfifind.mask"
          % (res.mask.numint, res.mask.numchan,
             100 * res.masked_fraction(), outbase))
    _plots(args, res, outbase)
    return res


def main(argv=None):
    from presto_tpu.utils.timing import app_timer
    args = build_parser().parse_args(argv)
    with app_timer("rfifind"):
        run(args)


if __name__ == "__main__":
    main()
