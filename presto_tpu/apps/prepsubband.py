"""prepsubband: raw data -> numdms dedispersed .dat series in one pass.

CLI parity with the reference prepsubband (clig/prepsubband_cmd.cli;
src/prepsubband.c:51-): -lodm, -dmstep, -numdms, -nsub, -downsamp, -o,
-mask, -clip, -zerodm.  The two-level subband
delay scheme follows dispersion.c:103-162; the DM fan-out runs as one
batched device program, sharded over the DM axis when multiple devices
are present (the mpiprepsubband analog, SURVEY.md §2.5).
"""

from __future__ import annotations

import argparse
import os

import numpy as np
import jax
import jax.numpy as jnp

from presto_tpu.apps.common import (add_common_flags, add_raw_flags,
                                    open_raw_args, BlockPrep,
                                    fil_to_inf,
                                    pad_to_good_N, set_onoff,
                                    make_bary_plan, set_bary_epoch,
                                    start_skip_spectra, stream_blocklen)
from presto_tpu.io.datfft import write_dat
from presto_tpu.io.maskfile import read_mask, determine_padvals
from presto_tpu.ops import dedispersion as dd
from presto_tpu.utils.ranges import parse_ranges


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="prepsubband",
        description="De-disperse raw data into many DM trials")
    add_common_flags(p)
    p.add_argument("-lodm", type=float, default=0.0)
    p.add_argument("-dmstep", type=float, default=1.0)
    p.add_argument("-numdms", type=int, default=10)
    p.add_argument("-nsub", type=int, default=32)
    p.add_argument("-downsamp", type=int, default=1)
    p.add_argument("-mask", type=str, default=None)
    p.add_argument("-clip", type=float, default=6.0)
    p.add_argument("-zerodm", action="store_true")
    p.add_argument("-nobary", action="store_true")
    p.add_argument("-ephem", type=str, default="DE405")
    p.add_argument("-numout", type=int, default=0,
                   help="Output exactly this many samples per DM "
                        "(default: pad to a highly-factorable length)")
    p.add_argument("-runavg", action="store_true",
                   help="Running mean subtraction from the input data")
    p.add_argument("-sub", action="store_true",
                   help="Write subbands instead of de-dispersed data")
    p.add_argument("-subdm", type=float, default=None,
                   help="The DM to use when de-dispersing subbands "
                        "for -sub (default: center of the DM range)")
    p.add_argument("-dmprec", type=int, default=2,
                   help="Decimals of DM precision in output filenames")
    p.add_argument("-ignorechan", type=str, default=None,
                   help="Channels to zero out, e.g. '0:5,34'")
    # mpiprepsubband-equivalent launch (SURVEY s2.5): with multiple
    # devices the DM fan-out shards over a jax mesh automatically; on
    # a manual multi-host cluster pass the coordinator grid (the
    # mpirun analog; mpiprepsubband.c:81-83)
    p.add_argument("-coordinator", type=str, default=None,
                   help="host:port of the jax.distributed coordinator "
                        "(multi-host runs; give -nproc and -procid)")
    p.add_argument("-nproc", type=int, default=None,
                   help="Total process count of the multi-host run")
    p.add_argument("-procid", type=int, default=None,
                   help="This process's id (0-based)")
    # elastic worker-loss recovery (parallel/elastic.py): the DM axis
    # becomes leased shard rows in a per-survey ledger; a dead member's
    # shards are re-admitted to the survivors instead of stalling the
    # collective
    p.add_argument("-elastic", action="store_true",
                   help="Run the DM fan-out as leased shards from a "
                        "crash-safe shard ledger (worker-loss "
                        "recovery for -coordinator clusters; also "
                        "valid single-host)")
    p.add_argument("-shard-rows", dest="shard_rows", type=int,
                   default=0,
                   help="DM rows per elastic shard (0 = auto)")
    p.add_argument("-lease-ttl", dest="lease_ttl", type=float,
                   default=120.0,
                   help="Elastic shard lease TTL in seconds")
    p.add_argument("-barrier-timeout", dest="barrier_timeout",
                   type=float, default=60.0,
                   help="Max seconds any cross-host collective may "
                        "stall before the survivors reform")
    p.add_argument("-heartbeat-interval", dest="heartbeat_interval",
                   type=float, default=2.0,
                   help="Elastic heartbeat cadence in seconds")
    p.add_argument("-resume", action="store_true",
                   help="Verify-not-trust resume: skip DMs whose "
                        ".dat outputs match the manifest.json journal "
                        "next to them; journal outputs on completion")
    add_raw_flags(p)
    p.add_argument("rawfiles", nargs="+")
    return p


def plan_delays(hdr, args, avgvoverc=0.0):
    """Two-level delays: channel->subband at the center DM (or -subdm
    when given), then per-DM subband offsets (prepsubband.c:353-372;
    the barycentric branch computes the same delays at Doppler-shifted
    frequencies, prepsubband.c:477-498)."""
    nchan, dt = hdr.nchans, hdr.tsamp
    dms = args.lodm + np.arange(args.numdms) * args.dmstep
    center_dm = args.lodm + 0.5 * (args.numdms - 1) * args.dmstep
    if getattr(args, "subdm", None) is not None:
        center_dm = args.subdm
    chan_del = dd.subband_search_delays(nchan, args.nsub, center_dm,
                                        hdr.lofreq, abs(hdr.foff),
                                        voverc=avgvoverc)
    chan_bins = dd.delays_to_bins(chan_del, dt)
    sub_del = np.stack([dd.subband_delays(nchan, args.nsub, dm,
                                          hdr.lofreq, abs(hdr.foff),
                                          voverc=avgvoverc)
                        for dm in dms])
    sub_del -= sub_del.min()
    dm_bins = dd.delays_to_bins(sub_del, dt)
    return dms, chan_bins, dm_bins


class _Setup:
    """Everything both execution paths (streaming mesh run and the
    elastic shard loop) derive from the args + raw header: the open
    reader, the FULL-range delay plan, preprocessing inputs, and the
    streaming geometry.  The elastic path computing a shard MUST use
    the full-range plan (center DM, delay normalization, blocklen,
    valid length) or its rows would not be byte-equal to an unsharded
    run's."""

    def __init__(self, args):
        self.fb = open_raw_args(args.rawfiles, args)
        hdr = self.fb.header
        self.hdr = hdr
        self.nchan, self.dt = hdr.nchans, hdr.tsamp
        self.skip = start_skip_spectra(args, int(hdr.N))
        self.Neff = int(hdr.N) - self.skip
        self.plan = (make_bary_plan(self.fb, self.dt * args.downsamp,
                                    args.ephem,
                                    skip_spectra=self.skip)
                     if not args.nobary else None)
        avgvoverc = (self.plan.avgvoverc if self.plan is not None
                     else 0.0)
        self.dms, self.chan_bins, self.dm_bins = plan_delays(
            hdr, args, avgvoverc)
        self.maxd = int(self.chan_bins.max()) + int(self.dm_bins.max())
        self.mask = read_mask(args.mask) if args.mask else None
        self.padvals = np.zeros(self.nchan, dtype=np.float32)
        if args.mask:
            try:
                self.padvals = determine_padvals(
                    args.mask.replace(".mask", ".stats"))
            except OSError:
                pass
        self.ignore = (np.asarray(parse_ranges(args.ignorechan),
                                  dtype=np.int64)
                       if args.ignorechan else None)
        blocklen = stream_blocklen(
            self.nchan, max(int(self.chan_bins.max()),
                            int(self.dm_bins.max())), nspec=self.Neff)
        # the per-block downsampler reshapes [.., blocklen/downsamp,
        # downsamp]: round blocklen up to a multiple of the factor
        if blocklen % args.downsamp:
            blocklen += args.downsamp - blocklen % args.downsamp
        self.blocklen = blocklen

    def block_prep(self, args) -> BlockPrep:
        """Fresh per-stream preprocessing (the clipper carries state
        across blocks, so each full pass over the file needs its own
        instance)."""
        return BlockPrep(self.nchan, self.dt, args, mask=self.mask,
                         padvals=self.padvals if args.mask else None,
                         ignore=self.ignore)


def _expected_outputs(args):
    """The final artifact paths a (non--sub) run will write — known
    from the args alone, so -resume can verify before any compute."""
    outbase = args.outfile or "prepsubband_out"
    dms = args.lodm + np.arange(args.numdms) * args.dmstep
    names = ["%s_DM%.*f" % (outbase, args.dmprec, dm) for dm in dms]
    return outbase, names


def run(args):
    if getattr(args, "elastic", False):
        return _elastic_run(args)
    if args.coordinator or args.nproc is not None:
        from presto_tpu.parallel.mesh import init_distributed
        nproc = init_distributed(args.coordinator, args.nproc,
                                 args.procid)
        print("prepsubband: joined a %d-process cluster" % nproc)
    if args.downsamp < 1:
        raise SystemExit("prepsubband: -downsamp must be >= 1")
    resume = None
    if getattr(args, "resume", False) and not args.sub \
            and jax.process_count() == 1:
        from presto_tpu.apps.common import CLIResume
        outbase_r, names = _expected_outputs(args)
        expected = [n + s for n in names for s in (".dat", ".inf")]
        resume = CLIResume(outbase_r, "prepsubband-cli")
        if resume.complete(expected):
            print("prepsubband: -resume verified %d DM outputs "
                  "against the journal — skipping" % len(names))
            return outbase_r, args.lodm + np.arange(args.numdms) \
                * args.dmstep
        resume.invalidate_stale(expected)
    s = _Setup(args)
    fb, hdr = s.fb, s.hdr
    nchan, dt = s.nchan, s.dt
    skip, Neff = s.skip, s.Neff
    plan, dms = s.plan, s.dms
    chan_bins, dm_bins, maxd = s.chan_bins, s.dm_bins, s.maxd
    prep = s.block_prep(args)
    blocklen = s.blocklen
    chan_bins_d = jnp.asarray(chan_bins)
    # host np for the unsharded loop: float_dedisp_many_block's
    # static-slice fast path dispatches on the host array
    dm_bins_d = np.asarray(dm_bins)
    # DM-sharded mesh path (the mpiprepsubband analog): used whenever
    # more than one device is visible — a chip pod or a -coordinator
    # cluster — and the DM count divides the device count's grid
    ndev = len(jax.devices())
    use_mesh = (ndev > 1 and not args.sub
                and args.numdms % ndev == 0
                and not os.environ.get("PRESTO_TPU_DISABLE_MESH"))
    sh_step = None
    if not use_mesh and jax.process_count() > 1:
        # a cluster run MUST take the mesh path: the single-device
        # fallback would make every process compute the full job and
        # race on the same output files
        raise SystemExit(
            "prepsubband: multi-host run requires the DM-sharded path "
            "— numdms (%d) must divide the global device count (%d), "
            "-sub is single-host only, and PRESTO_TPU_DISABLE_MESH "
            "must be unset" % (args.numdms, ndev))
    mesh = None
    sh_plan = None
    if use_mesh:
        from presto_tpu.parallel.mesh import make_mesh
        mesh = make_mesh()
        if jax.process_count() == 1:
            # static per-device delay plans (parallel/sharded.
            # ShardedDedispPlan): each device compiles its DM
            # sub-range's delays as constants, so the static-slice
            # fast path and its dedisp_dm_batch tuning bound drive
            # the multi-device loop too — and the per-device outputs
            # assemble into one dm-sharded global array the fused
            # seam consumes in place
            from presto_tpu.parallel.sharded import ShardedDedispPlan
            sh_plan = ShardedDedispPlan(mesh, args.nsub,
                                        args.downsamp, chan_bins,
                                        np.asarray(dm_bins))
            sh_step = sh_plan
            print("prepsubband: DM fan-out sharded over %d devices "
                  "(static per-device delay plans)" % ndev)
        else:
            # multi-host keeps the traced shard_map step: the MPMD
            # per-device dispatch model has no cross-process story
            from presto_tpu.parallel.sharded import (
                make_sharded_dedisperse_step, shard_dm_array)
            sh_step = make_sharded_dedisperse_step(mesh, args.nsub,
                                                   args.downsamp)
            dm_bins_d = shard_dm_array(dm_bins_d, mesh)
            print("prepsubband: DM fan-out sharded over %d devices"
                  % ndev)
    elif ndev > 1 and not args.sub:
        why = ("PRESTO_TPU_DISABLE_MESH is set"
               if os.environ.get("PRESTO_TPU_DISABLE_MESH")
               else "numdms=%d is not divisible by %d"
               % (args.numdms, ndev))
        print("prepsubband: %d devices visible but %s — running "
              "single-device" % (ndev, why))
    block_step = (dd.make_block_step(chan_bins, dm_bins_d, args.nsub,
                                     args.downsamp)
                  if sh_step is None and not args.sub else None)
    prev_raw = None
    prev_sub = None
    outs = []
    subouts = []
    # in-memory stage seam (pipeline/fusion.py): when the survey
    # driver installed a process seam and this run's path is
    # seam-compatible, the DM fan-out is handed over device-resident
    # instead of (only) being written to .dat files.  Sharded mesh
    # runs deposit a ShardedSeamBlock (one DM sub-range per device);
    # barycentred runs resample on host and re-deposit.  Only
    # multi-process (-coordinator) and -sub runs keep the staged
    # contract.
    from presto_tpu.pipeline import fusion
    seam = fusion.current_process_seam()
    use_seam = (seam is not None and not args.sub
                and jax.process_count() == 1)
    if use_mesh:
        print("prepsubband: sharded routing = %s"
              % ("fused-seam" if use_seam else "staged"))
    ingest_depth = (seam.depths["ingest_depth"] if use_seam
                    else fusion.DEFAULT_INGEST_DEPTH)

    def _produce_blocks():
        """Decoded+preprocessed channel-major blocks, in stream order
        (runs on the ingest worker thread: the decode/mask/clip/
        transpose of block k+1 overlaps the device compute of block
        k, generalizing the native feeder's raw-read prefetch)."""
        # prefetched sequential reads where the reader supports it
        # (the native feeder overlaps disk IO with this decode)
        block_iter = (fb.stream_blocks(blocklen)
                      if skip == 0 and hasattr(fb, "stream_blocks")
                      else None)
        nread = skip
        while nread < hdr.N + 2 * blocklen:   # two extra flush blocks
            if nread < hdr.N:
                block = (next(block_iter) if block_iter is not None
                         else fb.read_spectra(nread, blocklen))
                block = prep(block, nread)
            else:
                block = np.zeros((blocklen, nchan), dtype=np.float32,
                                 order="F")
            yield nread, np.ascontiguousarray(block.T)
            nread += blocklen

    from presto_tpu.utils.timing import print_percent_complete
    from presto_tpu.obs import costmodel, jaxtel
    # kernel-cost accounting rides the survey's obs handle (threaded
    # through the process seam); a bare CLI run has no handle and
    # every call below is one branch
    tel_obs = getattr(seam, "obs", None) if use_seam else None
    nblocks = 0
    pct = -1
    ingest = fusion.DoubleBufferedIngest(_produce_blocks(),
                                         depth=ingest_depth)
    try:
        for nread, blockT in ingest:
            pct = print_percent_complete(min(nread - skip, Neff),
                                         Neff, pct)
            cur = (sh_plan.put_block(blockT) if sh_plan is not None
                   else jnp.asarray(blockT))
            if prev_raw is not None:
                if sh_plan is not None:
                    # static per-device sharded step: replicated raw
                    # blocks, each device running its own compiled
                    # DM-sub-range program (mpiprepsubband's
                    # compute-everywhere/Bcast pattern, SURVEY s2.5)
                    if prev_sub is None:
                        sub = sh_plan.prime(prev_raw, cur)
                    else:
                        # unit cost of ONE device's program; the
                        # dispatch count carries the fan-out width
                        costmodel.probe(tel_obs, "dedisp",
                                        sh_plan.steps[0], prev_raw[0],
                                        cur[0], prev_sub[0])
                        jaxtel.note_dispatch(tel_obs, "dedisp",
                                             len(sh_plan.steps))
                        sub, series = sh_plan.step(prev_raw, cur,
                                                   prev_sub)
                        outs.append(series)
                elif sh_step is not None and prev_sub is not None:
                    # traced sharded step (multi-host): subbands on
                    # replicated data, the DM fan-out split over the
                    # mesh
                    sub, series = sh_step(prev_raw, cur, prev_sub,
                                          chan_bins_d, dm_bins_d)
                    outs.append(series)
                elif args.sub or prev_sub is None:
                    sub = dd.dedisp_subbands_block(prev_raw, cur,
                                                   chan_bins_d,
                                                   args.nsub)
                    if args.sub:
                        subouts.append(sub)
                else:
                    # steady state: ONE composed dispatch per block
                    # (subbands + DM fan-out + downsample) instead of
                    # three — the dispatch floor bounds the
                    # single-DM regime
                    costmodel.probe(tel_obs, "dedisp", block_step,
                                    prev_raw, cur, prev_sub)
                    jaxtel.note_dispatch(tel_obs, "dedisp")
                    sub, series = block_step(prev_raw, cur, prev_sub)
                    # stays on device: one download at the end
                    outs.append(series)
                prev_sub = sub
            prev_raw = cur
            nblocks += 1
    finally:
        ingest.close()

    if args.sub:
        return _write_subbands(args, fb, plan, subouts, dms, dt,
                               int(chan_bins.max()), Neff, skip)

    # [numdms, T] — ONE dm-sharded global array on the mesh path
    cat = (sh_plan.concat(outs) if sh_plan is not None
           else jnp.concatenate(outs, axis=1))
    if use_seam:
        return _seam_handoff(args, fb, seam, cat, dms, dt, Neff, maxd,
                             skip, plan=plan, mesh=mesh)
    if jax.process_count() > 1:
        # multi-host: each process materializes and writes ONLY its
        # own DM rows — the reference's workers write their own .dat
        # files (mpiprepsubband.c:1057-1060); nothing large crosses
        # the DCN
        local = {}
        for sh in cat.addressable_shards:
            lo = sh.index[0].start or 0
            for k, row in enumerate(np.asarray(sh.data)):
                local[lo + k] = row
        local_ids = sorted(local)
        result = np.stack([local[i] for i in local_ids])
    else:
        local_ids = list(range(args.numdms))
        result = np.asarray(cat)
    valid = (Neff - maxd) // args.downsamp
    result = result[:, :valid]
    if plan is not None and plan.diffbins.size:
        # same diffbin schedule applies to every DM series
        result = np.stack([plan.apply(result[i])
                           for i in range(result.shape[0])])
    result, valid, numout = pad_to_good_N(result, args.numout)

    outbase = args.outfile or "prepsubband_out"
    for row, i in enumerate(local_ids):
        dmval = dms[i]
        name = "%s_DM%.*f" % (outbase, args.dmprec, dmval)
        info = fil_to_inf(fb, name, result.shape[1], dm=float(dmval))
        if plan is not None:
            set_bary_epoch(info, plan)
        elif skip:
            info.mjd_f += skip * dt / 86400.0
            info.mjd_i += int(info.mjd_f)
            info.mjd_f %= 1.0
        info.dt = dt * args.downsamp
        set_onoff(info, valid, numout)
        write_dat(name + ".dat", result[row], info)
    fb.close()
    if resume is not None:
        resume.record(["%s_DM%.*f%s" % (outbase, args.dmprec, dms[i],
                                        suf)
                       for i in local_ids for suf in (".dat", ".inf")])
    print("Wrote %d DMs x %d samples (lodm=%g dmstep=%g nsub=%d)"
          % (len(local_ids), result.shape[1], args.lodm, args.dmstep,
             args.nsub))
    return outbase, dms


def _seam_handoff(args, fb, seam, cat, dms, dt, Neff, maxd, skip,
                  plan=None, mesh=None):
    """Deposit the DM fan-out at the survey's in-memory stage seam
    (pipeline/fusion.py) instead of round-tripping it through .dat
    files: the device block stays resident for the FFT/search stages,
    and ONE host download (the same single download the staged path
    pays before writing .dat) provides the bit-identical artifact
    bytes for spills, prepfold, and the pad computation.

    Byte-identity: the pad tail is computed on HOST with
    pad_to_good_N's exact NumPy semantics and uploaded, so the device
    series equals the staged .dat bytes bit-for-bit.

    Sharded (``mesh``): ``cat`` is one global dm-sharded array; the
    download is per-shard (fusion.gather_shards — parallel D2H, no
    single-device gather), only the pad TAIL is re-uploaded (sharded),
    and the deposit is a ShardedSeamBlock whose consumers stay on the
    shards.  Barycentred (``plan``): the diffbin resampling runs on
    the downloaded series with the staged path's exact host semantics,
    then the resampled+padded series is RE-DEPOSITED to the device(s)
    — one download + one upload, versus the staged download + .dat
    write + read + re-upload."""
    from presto_tpu.pipeline import fusion
    from presto_tpu.pipeline.fusion import SeamBlock, ShardedSeamBlock
    from presto_tpu.obs import jaxtel

    valid = (Neff - maxd) // args.downsamp
    trimmed = cat[:, :max(valid, 0)]
    obs = getattr(seam, "obs", None)
    if mesh is not None:
        host = fusion.gather_shards(trimmed, obs=obs)  # per-shard D2H
    else:
        host = np.asarray(trimmed)              # the one download
        jaxtel.note_get(obs, host.nbytes)
    resampled = plan is not None and plan.diffbins.size
    if resampled:
        # same diffbin schedule applies to every DM series (exact
        # staged semantics: resample the trimmed series, then pad)
        host = np.stack([plan.apply(host[i])
                         for i in range(host.shape[0])])
    host, valid, numout = pad_to_good_N(host, args.numout)

    from presto_tpu.parallel.mesh import dm_sharding
    if resampled:
        # the bary resampling changed the sample schedule on host:
        # re-deposit the full padded series (sharded when on a mesh)
        if mesh is not None:
            dev = jax.device_put(host, dm_sharding(mesh, 2))
        else:
            dev = jnp.asarray(host)
        jaxtel.note_put(obs, host.nbytes)
    elif numout > trimmed.shape[1]:
        tail = host[:, trimmed.shape[1]:]
        tail_dev = (jax.device_put(tail, dm_sharding(mesh, 2))
                    if mesh is not None else jnp.asarray(tail))
        jaxtel.note_put(obs, tail.nbytes)
        dev = jnp.concatenate([trimmed, tail_dev], axis=1)
    else:
        dev = trimmed[:, :numout]

    outbase = args.outfile or "prepsubband_out"
    names, infos = [], []
    for i, dmval in enumerate(dms):
        name = "%s_DM%.*f" % (outbase, args.dmprec, dmval)
        info = fil_to_inf(fb, name, numout, dm=float(dmval))
        if plan is not None:
            set_bary_epoch(info, plan)
        elif skip:
            info.mjd_f += skip * dt / 86400.0
            info.mjd_i += int(info.mjd_f)
            info.mjd_f %= 1.0
        info.dt = dt * args.downsamp
        set_onoff(info, valid, numout)
        info.name = name
        info.N = numout
        names.append(name)
        infos.append(info)
    kw = dict(names=names, infos=infos,
              dms=[float(d) for d in dms], series_dev=dev,
              series_host=host, valid=valid, numout=numout,
              dt=dt * args.downsamp)
    if mesh is not None:
        seam.add_block(ShardedSeamBlock(mesh=mesh, **kw))
    else:
        seam.add_block(SeamBlock(**kw))
    fb.close()
    print("Handed %d DMs x %d samples across the stage seam "
          "(lodm=%g dmstep=%g nsub=%d, durable=%s%s%s)"
          % (len(names), numout, args.lodm, args.dmstep, args.nsub,
             seam.durable,
             ", sharded" if mesh is not None else "",
             ", bary" if plan is not None else ""))
    return outbase, dms


def _dedisperse_rows(s: _Setup, args, rows):
    """One elastic shard: dedisperse DM rows [lo, hi) of the FULL
    plan.  Mirrors run()'s unsharded streaming loop exactly — same
    full-range delays and blocklen, same flush blocks, same valid trim
    and padding — so each row is byte-equal to the same row of a
    never-sharded run (the recovery invariant the chaos tests pin)."""
    lo, hi = rows
    fb, hdr = s.fb, s.hdr
    prep = s.block_prep(args)
    chan_bins_d = jnp.asarray(s.chan_bins)
    dm_bins_sel = np.asarray(s.dm_bins)[lo:hi]
    # same one-dispatch composed step as the unsharded loop (a shard
    # row must be byte-equal to the same row of a never-sharded run)
    block_step = dd.make_block_step(s.chan_bins, dm_bins_sel,
                                    args.nsub, args.downsamp)
    blocklen = s.blocklen
    prev_raw = None
    prev_sub = None
    outs = []
    nread = s.skip
    while nread < hdr.N + 2 * blocklen:   # two extra flush blocks
        if nread < hdr.N:
            block = fb.read_spectra(nread, blocklen)
            block = prep(block, nread)
        else:
            block = np.zeros((blocklen, s.nchan), dtype=np.float32,
                             order="F")
        cur = jnp.asarray(np.ascontiguousarray(block.T))
        if prev_raw is not None:
            if prev_sub is None:
                sub = dd.dedisp_subbands_block(prev_raw, cur,
                                               chan_bins_d, args.nsub)
            else:
                sub, series = block_step(prev_raw, cur, prev_sub)
                outs.append(series)
            prev_sub = sub
        prev_raw = cur
        nread += blocklen
    cat = jnp.concatenate(outs, axis=1)         # [hi-lo, T]
    valid = (s.Neff - s.maxd) // args.downsamp
    result = np.asarray(cat)[:, :valid]
    if s.plan is not None and s.plan.diffbins.size:
        result = np.stack([s.plan.apply(result[i])
                           for i in range(result.shape[0])])
    return pad_to_good_N(result, args.numout)


def _elastic_run(args):
    """The worker-loss-tolerant DM fan-out: every DM shard is a leased
    row in the workdir's shard ledger, any host computes any shard on
    its LOCAL devices, and commits ride the ledger's epoch fence — so
    a dead cluster member costs a lease TTL, not the run."""
    from presto_tpu.io.infodata import write_inf
    from presto_tpu.parallel import elastic
    from presto_tpu.pipeline.shardledger import make_dm_shards

    if args.sub:
        raise SystemExit("prepsubband: -elastic does not support -sub")
    if args.downsamp < 1:
        raise SystemExit("prepsubband: -downsamp must be >= 1")
    outbase, names = _expected_outputs(args)
    workdir = os.path.dirname(os.path.abspath(outbase)) or "."
    host = elastic.default_host_id(args.procid)
    ecfg = elastic.ElasticConfig(
        barrier_timeout=args.barrier_timeout,
        lease_ttl=args.lease_ttl,
        heartbeat_interval=args.heartbeat_interval,
        shard_rows=args.shard_rows)
    cluster = elastic.ElasticCluster(workdir, host, ecfg)
    # join BEFORE the backend spins up: jax.distributed.initialize
    # must precede first device use, exactly like the -coordinator
    # path
    cluster.join(args.coordinator, args.nproc, args.procid)
    s = _Setup(args)
    nproc = max(int(args.nproc or 1), 1)
    # auto shard size: ~2 shards per host so one loss re-admits at
    # most half a host's work
    rows = args.shard_rows or max(1, -(-args.numdms // (2 * nproc)))
    specs = make_dm_shards(args.numdms, rows)
    local_dev = jax.local_devices()[0]

    def compute(lease):
        lo, hi = lease.rows
        with jax.default_device(local_dev):
            result, valid, numout = _dedisperse_rows(s, args, (lo, hi))
        staged = {}
        for k, i in enumerate(range(lo, hi)):
            name = names[i]
            info = fil_to_inf(s.fb, name, result.shape[1],
                              dm=float(s.dms[i]))
            if s.plan is not None:
                set_bary_epoch(info, s.plan)
            elif s.skip:
                info.mjd_f += s.skip * s.dt / 86400.0
                info.mjd_i += int(info.mjd_f)
                info.mjd_f %= 1.0
            info.dt = s.dt * args.downsamp
            set_onoff(info, valid, numout)
            info.name = name
            info.N = result.shape[1]
            dat_tmp = elastic.stage_path(name + ".dat", host,
                                         lease.epoch)
            inf_tmp = elastic.stage_path(name + ".inf", host,
                                         lease.epoch)
            write_dat(dat_tmp, result[k])
            write_inf(info, inf_tmp)
            staged[name + ".dat"] = dat_tmp
            staged[name + ".inf"] = inf_tmp
        return staged

    try:
        n = cluster.run(specs, compute,
                        meta={"outbase": os.path.basename(outbase),
                              "numdms": int(args.numdms),
                              "shard_rows": int(rows)})
    finally:
        cluster.close()
        s.fb.close()
    print("prepsubband: elastic run complete — %d/%d shards by this "
          "host (epoch %d)" % (n, len(specs), cluster.epoch))
    return outbase, s.dms


def _write_subbands(args, fb, plan, subouts, dms, dt, maxd, Neff,
                    skip=0):
    """-sub output: one int16 stream per subband, outbase.sub0000...
    (the short-int subband files read_PRESTO_subbands consumes,
    prepsubband.c:825-846), each with a .sub.inf sidecar carrying the
    subband layout (num_chan = nsub)."""
    import jax.numpy as jnp
    from presto_tpu.apps.common import fil_to_inf
    from presto_tpu.io.infodata import write_inf

    subs = np.asarray(jnp.concatenate(subouts, axis=1))  # [nsub, T]
    valid = max(Neff - maxd, 0)
    subs = subs[:, :valid]
    if plan is not None and plan.diffbins.size:
        # same bary bin add/remove schedule as the .dat path, applied
        # to every subband stream so the bary epoch in the sidecar
        # matches the sample schedule
        subs = np.stack([plan.apply(subs[s])
                         for s in range(subs.shape[0])])
        valid = subs.shape[1]     # diffbins changed the sample count
    outbase = args.outfile or "prepsubband_out"
    subdm = (args.subdm if args.subdm is not None
             else float(np.mean(dms)))
    name = "%s_DM%.*f" % (outbase, args.dmprec, subdm)
    for s in range(subs.shape[0]):
        q = np.clip(np.trunc(subs[s]), -32768, 32767).astype("<i2")
        q.tofile("%s.sub%04d" % (name, s))
    info = fil_to_inf(fb, name, valid, dm=subdm)
    if plan is not None:
        set_bary_epoch(info, plan)
    elif skip:
        info.mjd_f += skip * dt / 86400.0
        info.mjd_i += int(info.mjd_f)
        info.mjd_f %= 1.0
    info.dt = dt
    info.num_chan = subs.shape[0]
    info.chan_wid = abs(fb.header.foff) * (fb.header.nchans
                                           // subs.shape[0])
    write_inf(info, name + ".sub.inf")
    fb.close()
    print("Wrote %d subbands x %d samples at subdm=%g to %s.sub****"
          % (subs.shape[0], valid, subdm, name))
    return name, dms


def main(argv=None):
    from presto_tpu.utils.timing import app_timer
    args = build_parser().parse_args(argv)
    with app_timer("prepsubband"):
        run(args)


if __name__ == "__main__":
    main()
