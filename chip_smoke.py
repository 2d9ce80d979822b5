#!/usr/bin/env python3
"""One-command survey on the chip: the repo's quickest proof that the
main path still starts and finds a pulsar there.

    python chip_smoke.py              # one TPU: the survey, full size
    python chip_smoke.py --chips 4    # four TPUs: DM-sharded survey ==
                                      # the single-device survey
    python chip_smoke.py --rehearse   # CPU, tiny size (tests/sandbox)

One process does everything (a chip belongs to one process): it writes
a seeded 8-bit SIGPROC beam in constant memory (models/synth.write_beam)
with one pulsar injected through models/inject.py, runs the survey
through its CLI entry point (apps/pipeline.main, ``--recipe palfa``),
and fails unless the chip ran it with the Pallas accel engines and the
pulsar comes back in sift and fold.  Every cut of the target size is
printed.  The last stdout line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# the target observation (ROADMAP: 2^23 samples x 256 channels, one
# chip's 512-DM share of 4096 DM trials) at PALFA sampling
# (pipeline/recipes.py PALFA: rfi_time = 2^15 * 64 us), 1.4 GHz band;
# the DM trials sit around the pulsar, where DDplan steps 0.1 with no
# downsampling
NSAMP, NCHAN, DT = 1 << 23, 256, 64e-6
LOFREQ, CHANWIDTH = 1250.0, 300.0 / 256     # 1250-1549 MHz
TARGET_DMS = 512
# the cut: DM trials first, never channels or samples, and memory is
# what forces it (a warm run took 279 s of the 1200 s limit, PR 21).
# The fused seam holds one DDplan method's whole DM fan-out on the
# device, and prepsubband's assembly holds four copies of it at once
# (the per-block outputs, their concatenation, the trimmed and the
# padded series).  Peak device bytes measured on one v5e (PR 21):
# 9,887,202,816 at 72 trials, 13,114,281,984 at 96 -- PEAK_BASE +
# PEAK_PER_DM * trials (~4 x 2^23 x 4 B per trial).  The smoke takes
# the most whole FFT_CHUNK-trial seam chunks (one compiled shape)
# whose peak fits the chip: 96 on a 16 GB v5e, where 128 would need
# 1.74e10 of its 1.69e10 bytes.
PEAK_BASE, PEAK_PER_DM = 205_965_312, 134_461_632
FFT_CHUNK = 32                    # 2^30 B // (2^23 x 4 B) per chunk
# --chips 4 compares two whole surveys (sharded, single-device) at
# four times the chip cost: 8 trials per chip, and no fold (the
# comparison is the sifted list)
FOUR_CHIP_DMS = 32
# the pulsar: a bright millisecond pulsar off the trial grid in f and
# DM (folded S/N over the whole beam), narrow enough (0.03 turns of
# 3.2 ms, near dt) that one DM step (0.1) costs visible S/N, so sift
# and fold can be held to one DM step
PSR_F, PSR_DM, PSR_SNR, PSR_WIDTH = 312.3713, 12.03, 50.0, 0.03
NOISE_SIGMA = 2.0                 # data units; write_beam quantizes x4
SEED = 20261015
FOLDTOP = 3
REHEARSE = dict(nsamp=1 << 16, nchan=64, dms=96)


def smoke_dms(bytes_limit: int) -> int:
    """The DM trials one chip holds: whole seam chunks under its
    bytes_limit, at most the target share."""
    fit = (bytes_limit - PEAK_BASE) // PEAK_PER_DM
    return min(TARGET_DMS, fit // FFT_CHUNK * FFT_CHUNK)


def log(*a):
    print("smoke:", *a, flush=True)


def fail(msg: str) -> None:
    log("FAIL", msg)
    sys.exit(1)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: the DM-sharded survey against the "
                        "single-device one, and no other phase")
    p.add_argument("--rehearse", action="store_true",
                   help="run on the CPU at a tiny size (never a chip "
                        "result)")
    return p.parse_args(argv)


class CompileMeter:
    """Compile seconds and persistent-cache hits from JAX's own
    monitoring events (backend_compile_duration wraps compile OR
    cache load)."""

    def __init__(self):
        import jax.monitoring as mon
        self.secs = 0.0
        self.n = 0
        self.hits = 0
        self.misses = 0
        self.by_fun = {}
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._event)

    def _dur(self, event, secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1
            n, t = self.by_fun.get(fun_name, (0, 0.0))
            self.by_fun[fun_name] = (n + 1, t + secs)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.secs, self.n, self.hits, self.misses)

    def top(self, k=12) -> str:
        rows = sorted(self.by_fun.items(), key=lambda kv: -kv[1][1])[:k]
        return "; ".join("%s x%d %.3f s" % (f, n, t)
                         for f, (n, t) in rows)

    def since(self, snap) -> str:
        s, n, h, m = (a - b for a, b in zip(self.snapshot(), snap))
        return ("%d compiles, %.3f s compile+load, cache hits %d, "
                "misses %d" % (n, s, h, m))


def versions() -> str:
    from importlib import metadata
    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    return "jax %s, jaxlib %s, libtpu %s" % (jax.__version__,
                                             jaxlib.__version__, libtpu)


def complex_transfer() -> str:
    """Do complex64 arrays cross host<->device both ways?"""
    import numpy as np
    import jax
    import jax.numpy as jnp
    x = (np.arange(8) + 1j * np.arange(8)[::-1]).astype(np.complex64)
    try:
        dev = jax.device_put(x)
        back = np.asarray(dev * (1 + 1j))
        ok = np.allclose(back, x * (1 + 1j))
        made = np.asarray(jnp.fft.fft(jnp.asarray(x.real)))
        ok = ok and made.dtype == np.complex64
        return "yes (H2D, D2H, device-made D2H)" if ok else "WRONG VALUES"
    except Exception as e:      # the finding itself, not a failure
        return "no: %s: %s" % (type(e).__name__, str(e)[:200])


def device_bytes():
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append((str(d.id), st.get("peak_bytes_in_use"),
                    st.get("bytes_limit")))
    return out


def dm_plan(nchan, dms, multiple=1):
    """DDplan for at most ``dms`` trials, a multiple of ``multiple``,
    centred on the pulsar: (plan, lodm, hidm, step).  DDplan rounds up
    to whole prepsubband calls, so the request shrinks until the plan
    fits."""
    from presto_tpu.pipeline.ddplan import Observation, plan_dedispersion
    width = CHANWIDTH * (NCHAN / nchan)
    obs = Observation(dt=DT, f_ctr=LOFREQ + 0.5 * (nchan - 1) * width,
                      bw=nchan * width, numchan=nchan)
    step = plan_dedispersion(obs, PSR_DM, PSR_DM + 1.0,
                             numsub=32).methods[0].ddm
    for want in range(dms, 0, -1):
        lodm = max(0.0, round(PSR_DM - 0.5 * want * step, 1))
        hidm = lodm + want * step
        plan = plan_dedispersion(obs, lodm, hidm, numsub=32)
        if plan.total_numdms <= dms and \
                plan.total_numdms % multiple == 0:
            return plan, lodm, hidm, step
    raise ValueError("no DDplan of at most %d trials" % dms)


def write_beam(work, nsamp, nchan):
    from presto_tpu.models import inject, synth
    width = CHANWIDTH * (NCHAN / nchan)
    path = os.path.join(work, "beam.fil")
    params = inject.InjectParams(f=PSR_F, dm=PSR_DM, shape="gauss",
                                 width=PSR_WIDTH)
    params.amp = inject.amp_for_snr(PSR_SNR, params, nsamp, NOISE_SIGMA,
                                    nchan)
    t0 = time.time()
    synth.write_beam(path, nsamp, DT, nchan, LOFREQ, width,
                     noise_sigma=NOISE_SIGMA, seed=SEED, inject=params)
    truth = inject.write_truth_sidecar(
        path, [inject.truth_record(params, snr=PSR_SNR)])
    log("beam: %s, %d bytes in %.3f s; truth %s"
        % (path, os.path.getsize(path), time.time() - t0,
           json.load(open(truth))["injected"][0]))
    return path, nsamp * DT


def read_sifted(path):
    """(name, DM, sigma, numharm, P ms, r) rows of cands_sifted.txt."""
    rows = []
    for line in open(path):
        m = re.match(r"(\S+_ACCEL_\d+:\d+)\s+(\S+)\s+(\S+)\s+(\S+)\s+(\d+)"
                     r"\s+\S+\s+\S+\s+(\S+)\s+(\S+)", line)
        if m:
            rows.append((m.group(1), float(m.group(2)),
                         float(m.group(4)), int(m.group(5)),
                         float(m.group(6)), float(m.group(7))))
    return rows


def harmonic_match(f, f0, tol):
    """f within tol (Hz, one Fourier bin) of a harmonic or
    subharmonic (1..16) of f0."""
    for h in range(1, 17):
        if abs(f - h * f0) <= tol or abs(f * h - f0) <= tol:
            return h if abs(f - h * f0) <= tol else 1.0 / h
    return None


def run_survey(work, beam, lodm, hidm, extra=(), foldtop=FOLDTOP):
    from presto_tpu.apps import pipeline
    argv = ["--recipe", "palfa", "-lodm", "%.2f" % lodm,
            "-hidm", "%.2f" % hidm, "-foldtop", str(foldtop),
            "-workdir", work] + list(extra) + [beam]
    log("pipeline.main(%s)" % " ".join(argv))
    rc = pipeline.main(argv)
    if rc != 0:
        fail("pipeline.main returned %r" % rc)


def stage_seconds(obs, since):
    """Wall seconds per survey stage from the obs stage spans."""
    out = {}
    for s in obs.tracer.finished()[since:]:
        if s.name.startswith("stage:"):
            key = s.name[len("stage:"):]
            out[key] = out.get(key, 0.0) + (s.end - s.start)
    return out


def bytes_under(d, skip=()):
    n = 0
    for root, _dirs, files in os.walk(d):
        for f in files:
            p = os.path.join(root, f)
            if p not in skip:
                n += os.path.getsize(p)
    return n


def check_recovery(work, T, dmstep, fold_sigma, fold=True):
    from presto_tpu.io.pfd import read_pfd
    rows = read_sifted(os.path.join(work, "cands_sifted.txt"))
    log("sifted candidates: %d" % len(rows))
    tol = 1.0 / T
    hits = [r for r in rows
            if abs(r[1] - PSR_DM) <= dmstep + 1e-9
            and harmonic_match(1000.0 / r[4], PSR_F, tol) is not None]
    if not hits:
        for r in rows[:10]:
            log("  top sifted:", r)
        fail("injected pulsar (f=%g Hz, DM=%g) not among the sifted "
             "candidates within one bin and one DM step" % (PSR_F, PSR_DM))
    best = max(hits, key=lambda r: r[2])
    log("recovered in sift: %s DM=%.2f sigma=%.2f numharm=%d "
        "f=%.6f Hz (harmonic %s of %.6f); truth DM=%.2f"
        % (best[0], best[1], best[2], best[3], 1000.0 / best[4],
           harmonic_match(1000.0 / best[4], PSR_F, tol), PSR_F, PSR_DM))
    if best[2] <= fold_sigma:
        fail("recovered sigma %.2f is not above the fold threshold %.1f"
             % (best[2], fold_sigma))
    if not fold:
        return
    folds = []
    for name in sorted(os.listdir(work)):
        if name.startswith("fold_cand") and name.endswith(".pfd"):
            p = read_pfd(os.path.join(work, name))
            folds.append((name, p.fold_p1, p.bestdm))
    good = [f for f in folds
            if harmonic_match(f[1], PSR_F, tol) is not None
            and abs(f[2] - PSR_DM) <= dmstep + 1e-9]
    for f in folds:
        log("fold %s: f=%.6f Hz, DM=%.2f" % f)
    if not good:
        fail("no fold of the injected pulsar within one DM step of "
             "DM %g" % PSR_DM)
    log("recovered in fold: %s f=%.6f Hz at DM %.2f (truth %.2f, step "
        "%.2f)" % (good[0] + (PSR_DM, dmstep)))


def check_engines(rehearse, passes):
    """Every accel pass of the recipe (and no other) built and scanned
    on the Pallas engine alone, as accel.ENGINES recorded it."""
    from presto_tpu.search import accel
    by_z = {}
    for (zmax, stage, engine), n in sorted(accel.ENGINES.items()):
        by_z.setdefault(zmax, []).append("%s=%s x%d" % (stage, engine, n))
    for zmax, what in sorted(by_z.items()):
        log("accel zmax=%d engines: %s" % (zmax, ", ".join(what)))
    if rehearse:
        return
    want = {z for (z, _nh, _sg, _flo) in passes}
    if set(by_z) != want:
        fail("accel engines recorded for zmax %s, the recipe's passes "
             "are %s" % (sorted(by_z), sorted(want)))
    for zmax in sorted(want):
        for stage in ("build", "scan"):
            used = {e for (z, s, e) in accel.ENGINES
                    if z == zmax and s == stage}
            if used != {"pallas"}:
                fail("accel zmax=%d %s did not run on the Pallas engine "
                     "alone: %s" % (zmax, stage, sorted(used)))


def seam_placement():
    """Record where every seam block's device series lives."""
    from presto_tpu.pipeline import fusion
    seen = []
    orig = fusion.StageSeam.add_block

    def add_block(self, block):
        per = {}
        for sh in block.series_dev.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
        seen.append(per)
        return orig(self, block)

    fusion.StageSeam.add_block = add_block
    return seen


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, HERE)
    t_start = time.time()
    import jax
    import presto_tpu                       # noqa: F401  (cache setup)
    from presto_tpu import obs as obsmod
    from presto_tpu.io import native

    devs = jax.devices()
    d0 = devs[0]
    log("device: %s %r x%d; %s" % (d0.platform, d0.device_kind, len(devs),
                                   versions()))
    if not args.rehearse and d0.platform != "tpu":
        fail("no TPU (platform %r); --rehearse is the only CPU run"
             % d0.platform)
    if len(devs) < args.chips:
        fail("--chips %d but %d devices" % (args.chips, len(devs)))
    cache = jax.config.jax_compilation_cache_dir
    log("compile cache: %s (JAX_COMPILATION_CACHE_DIR %s)"
        % (cache, "set" if "JAX_COMPILATION_CACHE_DIR" in os.environ
           else "unset"))
    if not native.available():
        fail("native IO library: %s" % native.build_error)
    log("native IO: %s" % native.library_path())
    meter = CompileMeter()
    log("complex64 across host<->device: %s" % complex_transfer())

    if args.rehearse:
        nsamp, nchan, dms = REHEARSE["nsamp"], REHEARSE["nchan"], \
            REHEARSE["dms"]
    else:
        nsamp, nchan = NSAMP, NCHAN
        limit = int(d0.memory_stats()["bytes_limit"])
        dms = smoke_dms(limit)
        if args.chips == 4:
            dms = min(dms, FOUR_CHIP_DMS)
    plan, lodm, hidm, dmstep = dm_plan(nchan, dms, args.chips)
    if args.rehearse:
        log("cut (rehearsal): %d samples x %d channels, %d DM trials, "
            "CPU, Pallas engines not run" % (nsamp, nchan,
                                             plan.total_numdms))
    elif args.chips == 4:
        log("cut (--chips 4): DM trials %d -> %d, %d per chip, no fold "
            "(channels and samples uncut): two whole surveys on four "
            "chips (see FOUR_CHIP_DMS)"
            % (TARGET_DMS, plan.total_numdms, plan.total_numdms // 4))
    elif plan.total_numdms < TARGET_DMS:
        nxt = dms + FFT_CHUNK
        log("cut: DM trials %d -> %d (channels and samples uncut), by "
            "memory: %d trials would peak at %d of this chip's %d bytes "
            "(see PEAK_PER_DM)" % (TARGET_DMS, plan.total_numdms, nxt,
                                   PEAK_BASE + PEAK_PER_DM * nxt, limit))
    log("geometry: %d channels x %d samples (dt %g s, %.1f-%.1f MHz), "
        "DM %.2f-%.2f: %d trials in %s"
        % (nchan, nsamp, DT, LOFREQ,
           LOFREQ + nchan * CHANWIDTH * (NCHAN / nchan), lodm, hidm,
           plan.total_numdms,
           [(m.lodm, m.ddm, m.numdms, m.downsamp) for m in plan.methods]))

    work = os.path.join(HERE, ".smoke")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    obs = obsmod.configure(obsmod.ObsConfig(enabled=True))
    # the non-durable fused tier (pipeline/fusion.py): stages hand
    # device arrays across the seam and write .dat/.fft only on demand;
    # no roofline cost probes (they compile programs for accounting)
    os.environ["PRESTO_TPU_DURABLE"] = "0"
    os.environ["PRESTO_TPU_COST"] = "0"
    from presto_tpu.pipeline.recipes import PALFA
    try:
        beam, T = write_beam(work, nsamp, nchan)
        if args.chips == 4:
            placement = seam_placement()
            results = []
            for label, env in (("sharded", None), ("single-device", "1")):
                wd = os.path.join(work, label)
                if env:
                    os.environ["PRESTO_TPU_DISABLE_MESH"] = env
                else:
                    os.environ.pop("PRESTO_TPU_DISABLE_MESH", None)
                t0, snap, n0 = time.time(), meter.snapshot(), len(placement)
                nspan = len(obs.tracer.finished())
                # the comparison needs sift, not rfifind, single-pulse
                # or fold
                run_survey(wd, beam, lodm, hidm, ("-norfi", "-nosp"),
                           foldtop=0)
                log("%s survey: %.3f s wall; %s" % (label, time.time() - t0,
                                                     meter.since(snap)))
                log("slowest compiles+loads so far: %s" % meter.top(8))
                for k, v in stage_seconds(obs, nspan).items():
                    log("%s stage %-30s %.3f s" % (label, k, v))
                for per in placement[n0:]:
                    log("%s seam block bytes per device: %s"
                        % (label, per))
                log("%s peak device bytes: %s" % (label, device_bytes()))
                results.append(read_sifted(os.path.join(
                    wd, "cands_sifted.txt")))
            sharded_devs = {d for per in placement for d in per
                            if len(per) > 1}
            if len(sharded_devs) != len(devs):
                fail("sharded seam blocks held on devices %s, not all %d"
                     % (sorted(sharded_devs), len(devs)))
            if results[0] != results[1]:
                for i in range(max(map(len, results))):
                    a, b = (r[i] if i < len(r) else None for r in results)
                    if a != b:
                        log("first difference, row %d: sharded %s, "
                            "single-device %s" % (i, a, b))
                        break
                fail("sifted candidates differ: sharded %d vs "
                     "single-device %d" % (len(results[0]),
                                           len(results[1])))
            log("sifted candidates equal: %d sharded == %d single-device"
                % (len(results[0]), len(results[1])))
            check_engines(args.rehearse, PALFA.accel_passes)
            check_recovery(os.path.join(work, "sharded"), T, dmstep,
                           PALFA.fold_sigma, fold=False)
        else:
            t0, snap = time.time(), meter.snapshot()
            run_survey(work, beam, lodm, hidm)
            log("survey: %.3f s wall; %s" % (time.time() - t0,
                                             meter.since(snap)))
            for k, v in stage_seconds(obs, 0).items():
                log("stage %-30s %.3f s" % (k, v))
            log("peak device bytes (id, peak, limit): %s"
                % (device_bytes(),))
            log("bytes on disk: beam %d, survey outputs %d"
                % (os.path.getsize(beam), bytes_under(work, {beam})))
            check_engines(args.rehearse, PALFA.accel_passes)
            check_recovery(work, T, dmstep, PALFA.fold_sigma)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    log("total %.3f s; %s" % (time.time() - t_start, meter.since((0,) * 4)))
    log("slowest compiles+loads: %s" % meter.top())
    result = {"ok": True, "device": {"platform": d0.platform,
                                     "kind": d0.device_kind,
                                     "count": len(devs)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
