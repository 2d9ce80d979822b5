"""One-command search pipeline (the survey-script layer, SURVEY §L7).

The reference orchestrates its searches with per-survey Python drivers
(bin/PALFA_presto_search.py, GBT350_drift_search.py, GBNCC_search.py)
that all run the same canonical flow — the tutorial command history
(docs/GBT_Lband_PSR_cmd_history.txt):

  rfifind -> DDplan -> prepsubband -> realfft -> [zapbirds] ->
  accelsearch -> ACCEL_sift -> prepfold (top cands) ->
  single_pulse_search

This module is that flow as one restartable driver.  Every stage
writes the standard durable artifacts (.mask/.dat/.inf/.fft/
ACCEL_*/cands_sifted.txt/.pfd/.singlepulse), and a stage is skipped
when its outputs are VERIFIED complete (the artifact-per-stage
contract IS the checkpoint system, SURVEY §5.4) — verified, not
merely present: every artifact is written atomically (io/atomic.py)
and journaled with size + CRC-32 in the workdir's manifest.json
(pipeline/manifest.py), so a resume after a kill redoes any stage
whose outputs are missing, truncated, checksum-stale, or were never
journaled, instead of silently trusting whatever bytes survived.

Chaos hooks: SurveyConfig.fault_injector (testing/chaos.py
FaultInjector) is called at every stage and chunk boundary; the chaos
test matrix kills the survey at each point and asserts a resumed run
produces byte-identical final artifacts.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import List, Optional, Sequence


@dataclass
class SurveyConfig:
    # DM plan
    lodm: float = 0.0
    hidm: float = 100.0
    nsub: int = 32
    # rfifind
    rfi_time: float = 2.0
    # accelsearch
    zmax: int = 0
    numharm: int = 8
    sigma: float = 4.0
    flo: float = 1.0                       # min freq searched (Hz)
    zaplist: Optional[str] = None
    # extra accelsearch passes beyond (zmax, numharm, sigma[, flo]),
    # e.g. the PALFA lo/hi pair — each entry is (zmax, numharm,
    # sigma) or (zmax, numharm, sigma, flo); a 3-tuple inherits flo.
    # A jerk pass adds accelsearch's -wmax and -fhi: (zmax, numharm,
    # sigma, flo, wmax[, fhi]); fhi 0 searches up to Nyquist
    accel_passes: Optional[tuple] = None
    # sifting / folding
    min_dm_hits: int = 2
    low_dm_cutoff: float = 2.0
    fold_top: int = 3
    sift_policy: Optional[object] = None   # sifting.SiftPolicy
    fold_sigma: Optional[float] = None     # fold all cands above this
    max_folds: int = 150                   # ... capped here
    # per-pass fold caps aligned with all_passes, e.g. the GBNCC/
    # GBT350 20-lo + 10-hi split (GBNCC_search.py:21-22,
    # GBT350_drift_search.py:21-22); None -> one combined max_folds
    max_folds_per_pass: Optional[tuple] = None
    # single pulse
    sp_threshold: float = 5.0
    sp_maxwidth: float = 0.0
    singlepulse: bool = True
    skip_rfifind: bool = False
    # barycentre the dedispersed series (drops prepsubband's -nobary).
    # Bary runs flow through the same in-memory stage seam: the
    # resampling consumes the seam series on host and re-deposits, so
    # the .dat spill is byte-equal to a staged bary run's.
    bary: bool = False
    # serving hook: an object with .searcher(acfg, T, numbins) (serve/
    # plancache.SearcherProvider).  None -> build searchers inline, the
    # batch-driver behavior.  A resident service shares one provider
    # across jobs so same-shaped trial groups reuse compiled plans.
    plan_provider: Optional[object] = None
    # fault-tolerance hooks: fault_injector is an object with
    # .point(name) (testing/chaos.FaultInjector) called at stage/chunk
    # boundaries; verify_resume=False reverts to the legacy trust-
    # existence checkpoint contract (no manifest journal).
    fault_injector: Optional[object] = None
    verify_resume: bool = True
    # elastic worker-loss recovery for the DM-sharded prepsubband
    # stage: an ElasticConfig (parallel/elastic.py) or True for
    # defaults.  The stage's DM fan-out then runs as leased shards
    # from the workdir's shard ledger (pipeline/shardledger.py) —
    # a cluster member dying mid-method costs a lease TTL instead of
    # stalling the collective, and a single-host run gains shard-level
    # crash-safe resume.
    elastic: Optional[object] = None
    # observability: an obs.ObsConfig or obs.Observability.  None ->
    # the process default (enabled only when PRESTO_TPU_OBS=1), so an
    # unconfigured run pays one branch per telemetry point and writes
    # no telemetry files — byte-identical to an uninstrumented run.
    obs: Optional[object] = None
    # device-aware autotuning (presto_tpu/tune): True/False forces
    # tuning-DB lookups on/off for this survey; None defers to
    # PRESTO_TPU_TUNE=1.  Tuned knobs pick execution geometry (kernel
    # tile, DM-batch bound, bucket edges) and never change output
    # bytes; a tuned run writes <workdir>/tuned.json provenance
    # (rendered by presto-report).
    tune: Optional[bool] = None
    # stage durability tier (pipeline/fusion.py): stages hand their
    # successors device-resident arrays across an in-memory seam
    # whenever the execution path allows it; durable_stages decides
    # whether the would-be intermediate artifacts (.dat/.fft) are
    # ALSO written+journaled at each boundary.  True (the resolved
    # default) keeps the staged checkpoint contract byte-for-byte
    # (write-through, no read-back); False — the presto-serve/bench
    # tier — skips them, spilling only on demand (prepfold) so a
    # killed run simply redoes the fused stages from the last durable
    # artifact.  None resolves to True unless PRESTO_TPU_DURABLE=0.
    durable_stages: Optional[bool] = None
    # cross-stage in-flight window depth (FFT of DM-group i overlaps
    # search of group i-1); None resolves via the tuning DB's
    # pipeline_inflight_depth family, else the built-in default of 2.
    # Depth only changes dispatch overlap, never output bytes.
    inflight_depth: Optional[int] = None
    # learned candidate triage (presto_tpu/triage): None/False keeps
    # the byte-stable heuristic fold selection; True or a dict
    # {"budget"|"budget_frac", "weights", "borderline_frac"} (or a
    # ready triage.TriagePolicy) reorders/truncates the heuristic
    # selection under a learned score before folding.  Policy, never
    # data path: a missing/corrupt weights file degrades to the
    # heuristic selection unchanged.
    triage: Optional[object] = None

    @property
    def all_passes(self):
        """The passes as tuples: (zmax, numharm, sigma, flo), or with
        a jerk pass's (..., wmax[, fhi]) kept."""
        raw = ((self.zmax, self.numharm, self.sigma, self.flo),) + \
            tuple(self.accel_passes or ())
        return tuple(tuple(p) if len(p) >= 4 else tuple(p) + (self.flo,)
                     for p in raw)


def pass_fields(p) -> tuple:
    """(zmax, numharm, sigma, flo, wmax, fhi) of one normalized pass;
    a 4-tuple is wmax 0 and fhi 0 (up to Nyquist)."""
    zmax, nh, sg, flo = p[:4]
    wmax = int(p[4]) if len(p) > 4 else 0
    fhi = float(p[5]) if len(p) > 5 else 0.0
    return int(zmax), int(nh), float(sg), float(flo), wmax, fhi


def pass_tag(p) -> str:
    """The ACCEL artifact suffix of one pass: ``_ACCEL_<zmax>``, and
    ``_JERK_<wmax>`` after it for a jerk pass (apps/accelsearch)."""
    zmax, _nh, _sg, _flo, wmax, _fhi = pass_fields(p)
    return "_ACCEL_%d" % zmax + ("_JERK_%d" % wmax if wmax else "")


@dataclass
class SurveyResult:
    workdir: str
    maskfile: Optional[str] = None
    datfiles: List[str] = field(default_factory=list)
    candfile: str = ""
    folded: List[str] = field(default_factory=list)
    sp_events: int = 0
    sifted: Optional[object] = None      # sifting.Candlist
    quality: Optional[object] = None     # io/quality.DataQualityReport


def _stage(done_glob: str, workdir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(workdir, done_glob)))


def _chaos(cfg: SurveyConfig, point: str, obs=None) -> None:
    """Fire the configured fault injector at a named kill point.  The
    point is flight-recorded FIRST, so a kill here leaves its own name
    as the dump's final record — the post-mortem starts at the truth."""
    if obs is not None and obs.enabled:
        obs.event("chaos-point", point=point)
    fi = getattr(cfg, "fault_injector", None)
    if fi is not None:
        fi.point(point)


def _valid(manifest, path: str) -> bool:
    """Is this artifact trustworthy for resume?  With a manifest:
    exists AND matches its journaled size+checksum.  Without
    (verify_resume=False): the legacy existence check."""
    if manifest is None:
        return os.path.exists(path)
    return manifest.valid(path)


def _record(manifest, paths, stage: str) -> None:
    if manifest is not None:
        manifest.record_many([p for p in paths if os.path.exists(p)],
                             stage)


def _elastic_argv(elastic_cfg) -> List[str]:
    """Map a SurveyConfig.elastic value (True or an ElasticConfig)
    onto prepsubband -elastic CLI flags."""
    argv = ["-elastic"]
    if elastic_cfg is True:
        return argv
    for flag, attr in (("-shard-rows", "shard_rows"),
                       ("-lease-ttl", "lease_ttl"),
                       ("-barrier-timeout", "barrier_timeout"),
                       ("-heartbeat-interval", "heartbeat_interval")):
        val = getattr(elastic_cfg, attr, None)
        if val:
            argv += [flag, str(val)]
    return argv


def _drop_stale(manifest, paths) -> List[str]:
    """Delete + forget artifacts that fail verification; returns the
    surviving (valid) subset."""
    if manifest is None:
        return [p for p in paths if os.path.exists(p)]
    stale = set(manifest.invalidate_stale(paths))
    return [p for p in paths if p not in stale]


def run_survey(rawfiles: Sequence[str], cfg: SurveyConfig,
               workdir: str = ".", timer=None) -> SurveyResult:
    from presto_tpu.obs import resolve_obs
    obs = resolve_obs(getattr(cfg, "obs", None))
    os.makedirs(workdir, exist_ok=True)
    rawfiles = [os.path.abspath(f) for f in rawfiles]
    base = os.path.join(
        workdir, os.path.splitext(os.path.basename(rawfiles[0]))[0])
    res = SurveyResult(workdir=workdir)
    # crash-safe resume setup: sweep a killed run's in-flight temp
    # files, then load the artifact journal this run will verify
    # against and append to
    from presto_tpu.io.atomic import cleanup_stale_tmp
    cleanup_stale_tmp(workdir)
    manifest = None
    if cfg.verify_resume:
        from presto_tpu.pipeline.manifest import SurveyManifest
        manifest = SurveyManifest.load(workdir)
    if timer is None:
        from presto_tpu.utils.timing import StageTimer
        timer = StageTimer(obs=obs)
    root = obs.span("survey", workdir=workdir,
                    raw=os.path.basename(rawfiles[0]))
    from presto_tpu import tune as _tune
    try:
        with _tune.scoped(cfg.tune):
            result = _run_survey_stages(rawfiles, cfg, workdir, base,
                                        res, timer, manifest, obs)
        root.finish()
        return result
    except BaseException as e:
        # post-mortem on ANY death: unhandled exceptions, typed
        # PrestoIOError, and injected SimulatedCrash (a BaseException)
        # all leave the last N seconds of telemetry next to the
        # artifacts they orphaned
        root.finish("error: %s" % type(e).__name__)
        obs.dump_flight(workdir, reason=type(e).__name__)
        raise
    finally:
        timer.mark(None)
        timer.report()
        # tuned-config provenance beside the artifacts it shaped
        # (written even on death — a post-mortem wants to know which
        # configs were live); no-op when tuning is disabled
        with _tune.scoped(cfg.tune):
            _tune.write_provenance(workdir)
        obs.flush(default_dir=workdir)


def _run_survey_stages(rawfiles, cfg, workdir, base, res, timer,
                       manifest=None, obs=None):
    seam, disk_only = _survey_head(rawfiles, cfg, workdir, base, res,
                                   timer, manifest, obs)
    _device_search_stages(seam, disk_only, res.datfiles, cfg,
                          cfg.all_passes, timer, manifest, obs)
    timer.mark("sift")
    _chaos(cfg, "pre-sift", obs)
    return _finish_survey_stages(rawfiles, cfg, workdir, base, res,
                                 timer, manifest, obs, seam=seam)


def _survey_head(rawfiles, cfg, workdir, base, res, timer,
                 manifest=None, obs=None):
    """Stages 1-3 (rfifind -> DDplan -> prepsubband), depositing the
    DM fan-out at the in-memory stage seam.  Returns (seam,
    disk_only): the seam plus the trials that must flow through the
    original disk consumers.  Split out of _run_survey_stages so the
    stacked cross-job executor (run_survey_stacked) can run N heads
    and then ONE merged device-search stage."""

    timer.mark("rfifind")
    _chaos(cfg, "pre-rfifind", obs)
    # ---- 1. rfifind ---------------------------------------------------
    mask = base + "_rfifind.mask"
    if not cfg.skip_rfifind:
        if not _valid(manifest, mask):
            _drop_stale(manifest,
                        glob.glob(base + "_rfifind.*")
                        + [base + "_rfifind_quality.json"])
            from presto_tpu.apps.rfifind import main as rfifind_main
            rfifind_main(["-time", str(cfg.rfi_time), "-o", base]
                         + rawfiles)
            _record(manifest,
                    glob.glob(base + "_rfifind.*")
                    + [base + "_rfifind_quality.json"], "rfifind")
        res.maskfile = mask
        qpath = base + "_rfifind_quality.json"
        if os.path.exists(qpath):
            from presto_tpu.io.quality import DataQualityReport
            try:
                res.quality = DataQualityReport.read(qpath)
            except (OSError, ValueError):
                pass
        if res.quality is not None and obs is not None:
            # ingest health onto the shared registry: quarantine
            # tallies become /metrics counters, not just per-run JSON
            res.quality.publish(obs.metrics)
    _chaos(cfg, "post-rfifind", obs)

    timer.mark("ddplan")
    # ---- 2. DDplan ----------------------------------------------------
    from presto_tpu.apps.common import open_raw
    from presto_tpu.pipeline.ddplan import Observation, plan_dedispersion
    fb = open_raw(rawfiles)
    hdr = fb.header
    fb.close()
    observation = Observation(dt=hdr.tsamp, f_ctr=hdr.lofreq
                              + 0.5 * (hdr.nchans - 1) * abs(hdr.foff),
                              bw=hdr.nchans * abs(hdr.foff),
                              numchan=hdr.nchans)
    plan = plan_dedispersion(observation, cfg.lodm, cfg.hidm,
                             numsub=cfg.nsub)
    print("survey: DDplan -> %d methods, %d total DMs"
          % (len(plan.methods), plan.total_numdms))

    timer.mark("prepsubband")
    _chaos(cfg, "pre-prepsubband", obs)
    # ---- 3. prepsubband per method ------------------------------------
    # The DM fan-out crosses an IN-MEMORY stage seam
    # (pipeline/fusion.py): prepsubband deposits the device-resident
    # series for the FFT/search/single-pulse stages, and
    # cfg.durable_stages decides whether the .dat artifacts are also
    # written at the boundary (write-through) or only spilled on
    # demand.  The DM-sharded mesh path deposits a ShardedSeamBlock
    # (one DM sub-range per device, consumed in place by the sharded
    # FFT/search below) and barycentred runs re-deposit after the
    # host resampling; only elastic and multi-process runs are
    # seam-incompatible and keep the staged/ledger disk contract —
    # there the seam just stays empty and every consumer below falls
    # back to disk.
    from presto_tpu.apps.prepsubband import main as prepsubband_main
    from presto_tpu.pipeline import fusion
    seam = fusion.StageSeam(workdir, durable=_durable(cfg),
                            manifest=manifest, obs=obs,
                            inflight_depth=cfg.inflight_depth)
    dat_glob = os.path.basename(base) + "_DM*.dat"
    # verify survivors of a previous run ONCE, before the loop — this
    # run's own per-method outputs are journaled as each method lands,
    # so they must not be re-judged (and deleted) mid-flight
    _drop_stale(manifest, _stage(dat_glob, workdir))
    for m in plan.methods:
        have = _stage(dat_glob, workdir)
        missing = [dm for dm in m.dms
                   if not any("_DM%.2f.dat" % dm in f for f in have)]
        if not missing:
            continue
        argv = ["-lodm", str(m.lodm), "-dmstep", str(m.ddm),
                "-numdms", str(m.numdms), "-nsub", str(cfg.nsub),
                "-downsamp", str(m.downsamp), "-o", base]
        if not getattr(cfg, "bary", False):
            argv += ["-nobary"]
        if res.maskfile and os.path.exists(res.maskfile):
            argv += ["-mask", res.maskfile]
        if getattr(cfg, "elastic", None):
            # worker-loss-tolerant DM fan-out: run the method through
            # the leased-shard ledger (apps/prepsubband -elastic);
            # the survey's chaos injector threads through the elastic
            # layer's process seam (argv can't carry objects)
            from presto_tpu.parallel import elastic as _elastic
            argv += _elastic_argv(cfg.elastic)
            _elastic.set_process_injector(cfg.fault_injector)
            _elastic.set_process_obs(obs)
            try:
                prepsubband_main(argv + rawfiles)
            finally:
                _elastic.set_process_injector(None)
                _elastic.set_process_obs(None)
            _chaos(cfg, "elastic-method", obs)
        elif os.environ.get("PRESTO_TPU_FUSION", "1") == "0":
            # operational kill switch: keep the pre-fusion staged
            # contract exactly (every stage boundary on disk)
            prepsubband_main(argv + rawfiles)
        else:
            fusion.set_process_seam(seam)
            try:
                prepsubband_main(argv + rawfiles)
            finally:
                fusion.set_process_seam(None)
        done = _stage(dat_glob, workdir)
        _record(manifest, done + [f[:-4] + ".inf" for f in done],
                "prepsubband")
        _chaos(cfg, "prepsubband-method", obs)
    disk_dats = _stage(dat_glob, workdir)
    seam_set = {os.path.abspath(p) for p in seam.dat_paths()}
    res.datfiles = sorted(set(disk_dats)
                          | {os.path.join(workdir, os.path.basename(p))
                             for p in seam.dat_paths()})
    # trials the seam does NOT hold (a previous staged run's verified
    # survivors, or a seam-incompatible execution path): these flow
    # through the original disk consumers below
    disk_only = [f for f in res.datfiles
                 if os.path.abspath(f) not in seam_set]
    n_sharded = sum(len(b.names) for b in seam.blocks
                    if fusion.is_sharded(b))
    print("survey: %d dedispersed time series (%d seam-resident, "
          "%d sharded)" % (len(res.datfiles), len(seam), n_sharded))
    _chaos(cfg, "seam-handoff", obs)
    if n_sharded:
        _chaos(cfg, "shard-seam-handoff", obs)
    _chaos(cfg, "post-prepsubband", obs)
    return seam, disk_only


def _device_search_stages(seam, disk_only, datfiles, cfg, passes,
                          timer, manifest=None, obs=None):
    """Stages 9a + 4/5/6: single-pulse, rFFT, (zapbirds), accelsearch
    over the seam-resident series plus the disk-trial fallbacks.  This
    is the survey's device-bound middle — exactly what the stacked
    serve executor runs ONCE over a merged cross-job seam
    (run_survey_stacked) instead of once per job."""

    # ---- 9a. single-pulse search over the seam-resident series ------
    # runs BEFORE the FFT consumes (and may donate) the series block;
    # artifacts and candidate sets are byte-identical to the staged
    # stage-ordered run — only the wall-clock attribution moves.
    if cfg.singlepulse and len(seam):
        timer.mark("single_pulse")
        _seam_singlepulse(seam, cfg, manifest, obs)

    from dataclasses import replace as _replace
    if cfg.zaplist:
        timer.mark("realfft")
        if len(seam):
            # seam trials: FFT + in-memory zap + every accel pass
            # without touching disk (spectra spilled only on the
            # durable tier, journaled at the post-zap "zapbirds" state)
            timer.mark("realfft+accelsearch (fused)")
            _seam_fft_search(seam, cfg, passes, manifest, obs,
                             zap=True)
            timer.mark("realfft")
        _staged_fft_search_head(disk_only, cfg, manifest, obs)
        # the staged sweep covers disk trials AND any seam trial whose
        # zapped spectrum already sits journaled on disk (re-zapping
        # is excluded by contract, so those search from the artifact)
        fftfiles = sorted({f[:-4] + ".fft" for f in disk_only}
                          | {f[:-4] + ".fft" for f in datfiles
                             if os.path.exists(f[:-4] + ".fft")})
        timer.mark("zapbirds")
        # ---- 5. zapbirds ---------------------------------------------
        # zapping mutates the .fft in place and is NOT idempotent, so
        # the journal's stage tag is the checkpoint: a spectrum whose
        # entry already says "zapbirds" (and still verifies) is done.
        from presto_tpu.apps.zapbirds import main as zap_main
        for f in fftfiles:
            if (manifest is not None and manifest.valid(f)
                    and manifest.stage_of(f) == "zapbirds"):
                continue
            zap_main(["-zap", "-zapfile", cfg.zaplist, f])
            _record(manifest, [f], "zapbirds")
            _chaos(cfg, "zapbirds-file", obs)
        timer.mark("accelsearch")
        # ---- 6. accelsearch: BATCHED over the DM fan-out, once per
        # recipe pass (e.g. PALFA's zmax=0/nh=16 + zmax=50/nh=8) -----
        for p in passes:
            zmax, nh, sg, flo, wmax, fhi = pass_fields(p)
            _batched_accelsearch(
                fftfiles, _replace(cfg, zmax=zmax, numharm=nh,
                                   sigma=sg, flo=flo), manifest, obs,
                wmax=wmax, fhi=fhi)
    else:
        # ---- 4+6 fused fast path: realfft -> accelsearch with the
        # spectra RESIDENT on device (no zapbirds in between).  Seam
        # trials never touch disk at all (the dedisp output block is
        # the FFT input block, donated where the backend supports it);
        # disk trials keep the read-once upload path.  ACCEL artifacts
        # are always written, preserving the checkpoint contract.
        timer.mark("realfft+accelsearch (fused)")
        if len(seam):
            _seam_fft_search(seam, cfg, passes, manifest, obs)
        _fused_fft_search(disk_only, cfg, manifest, obs)
        for p in passes:
            # resume case for the first pass; full searches for the
            # recipe's additional passes
            zmax, nh, sg, flo, wmax, fhi = pass_fields(p)
            _batched_accelsearch(
                [f[:-4] + ".fft" for f in disk_only],
                _replace(cfg, zmax=zmax, numharm=nh, sigma=sg,
                         flo=flo), manifest, obs, wmax=wmax, fhi=fhi)


def _length_groups(files, item_bytes):
    """Group files by payload length (dict length -> file list);
    item_bytes converts a file size to its logical length."""
    by_len = {}
    for f in files:
        by_len.setdefault(item_bytes(os.path.getsize(f)), []).append(f)
    return by_len


def _durable(cfg) -> bool:
    """Resolve the stage-durability tier: an explicit
    cfg.durable_stages wins; None defaults to durable (the
    resume-critical contract) unless PRESTO_TPU_DURABLE=0."""
    d = getattr(cfg, "durable_stages", None)
    if d is not None:
        return bool(d)
    return os.environ.get("PRESTO_TPU_DURABLE", "1") != "0"


def _searcher_for(cfg, T, nbins, wmax=0, fhi=0.0):
    """One accel searcher for a (pass config, duration, length) —
    through the plan provider when a resident service shares one
    (serve/plancache), so same-shaped trial groups reuse compiled
    plans across the staged AND seam paths.  ``wmax`` and ``fhi`` are
    a jerk pass's -wmax and -fhi (its band's top, Hz)."""
    from presto_tpu.search.accel import AccelConfig, AccelSearch
    acfg = AccelConfig(zmax=cfg.zmax, numharm=cfg.numharm,
                       sigma=cfg.sigma, flo=cfg.flo, wmax=int(wmax),
                       rhi=float(fhi) * T if fhi else 0.0)
    if cfg.plan_provider is not None:
        return cfg.plan_provider.searcher(acfg, T, nbins)
    return AccelSearch(acfg, T=T, numbins=nbins)


def _survey_searcher(first_file, nbins, cfg, wmax=0, fhi=0.0):
    """(searcher, T) for one same-length trial group."""
    from presto_tpu.io.infodata import read_inf
    info = read_inf(first_file[:-4] + ".inf")
    T = info.N * info.dt
    return _searcher_for(cfg, T, nbins, wmax, fhi), T


def _seam_fft_search(seam, cfg, passes, manifest=None, obs=None,
                     zap=False) -> None:
    """Every accel pass over the seam-resident series: batched rfft
    straight off the dedisp output block (donated to the FFT where
    the backend supports aliasing), search_many on the device
    spectra, ONE download per chunk for candidate refinement (and the
    durable tier's .fft spill).  Dispatch of chunk i+1's FFT is
    admitted to the in-flight window before chunk i's results are
    collected, so the host-side refine/write of one chunk overlaps
    the device work of the next.

    With ``zap`` the downloaded spectrum is zapped in memory
    (apps/zapbirds.zap_pairs_batch) and the ZAPPED pairs are what the
    search consumes — the staged rfft->zapbirds->accelsearch flow
    without the two disk round-trips.  Durable spills journal the
    .fft at its post-zap state (stage "zapbirds"), matching the
    staged journal's non-idempotency contract; a trial whose .fft is
    already journaled zapped is left to the disk consumers
    (re-zapping is not byte-stable).

    Sharded seam blocks stay sharded through the whole chain: the
    batched rFFT keeps each device's spectra resident
    (fused_rfft_batch with the mesh's out_shardings), the search runs
    shard_map'd in place (search_many(mesh=...)), and the single bulk
    download is the per-shard gather that feeds zap/refine/spill."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dataclasses import replace as _replace
    from presto_tpu.apps.accelsearch import refine_and_write
    from presto_tpu.io import datfft
    from presto_tpu.obs import jaxtel, maybe_span
    from presto_tpu.ops import fftpack
    from presto_tpu.pipeline import fusion

    can_donate = jax.devices()[0].platform != "cpu"
    # one searcher per (pass, duration, length): the seam chunks of a
    # pass share its compiled build and scan programs
    searchers = {}

    def searcher_for(pcfg, wmax, fhi, T, nbins):
        key = (pcfg.zmax, pcfg.numharm, pcfg.sigma, pcfg.flo, wmax, fhi,
               T, nbins)
        if key not in searchers:
            searchers[key] = _searcher_for(pcfg, T, nbins, wmax, fhi)
        return searchers[key]

    def collect(ent):
        """Search + refine + write one FFT'd chunk (the sync point)."""
        (block, rows, pairs_dev, todo_passes, n, mesh) = ent
        with maybe_span(obs, "fused-collect", files=len(rows), nbins=n):
            _collect(block, rows, pairs_dev, todo_passes, n, mesh)

    def _collect(block, rows, pairs_dev, todo_passes, n, mesh):
        nbins = n // 2
        T = block.numout * fusion.inf_float(block.dt)
        # the download waits for the chunk's rFFT
        with maybe_span(obs, "seam:download") as sp:
            if mesh is not None:
                # per-shard D2H (candidate collection + durable spill)
                pairs_host = fusion.gather_shards(pairs_dev, obs=obs)
            else:
                pairs_host = np.array(pairs_dev)      # one download
                jaxtel.note_get(obs, pairs_host.nbytes)
            sp.set_attr("bytes", pairs_host.nbytes)
        search_dev = pairs_dev
        if zap and cfg.zaplist:
            from presto_tpu.apps.zapbirds import zap_pairs_batch
            with maybe_span(obs, "seam:zap"):
                pairs_host = zap_pairs_batch(pairs_host, cfg.zaplist, T,
                                             block.numout)
            with maybe_span(obs, "seam:upload", bytes=pairs_host.nbytes):
                if mesh is not None:      # re-upload zapped, per shard
                    from presto_tpu.parallel.mesh import dm_sharding
                    search_dev = jax.device_put(pairs_host,
                                                dm_sharding(mesh, 3))
                else:
                    search_dev = jnp.asarray(pairs_host)
            jaxtel.note_put(obs, pairs_host.nbytes)
            _chaos(cfg, "zapbirds-file", obs)
        for pcfg, wmax, fhi, tag in todo_passes:
            searcher = searcher_for(pcfg, wmax, fhi, T, nbins)
            jaxtel.note_dispatch(obs, "accel_search")
            with maybe_span(obs, "accel:search", zmax=pcfg.zmax,
                            **({"wmax": wmax} if wmax else {})):
                results = searcher.search_many(search_dev, mesh=mesh,
                                               obs=obs)
            arts = []
            for row, pr, raw in zip(rows, pairs_host, results):
                name = block.names[row]
                with maybe_span(obs, "accel:refine", cands=len(raw)):
                    amps = fftpack.np_pairs_to_complex64(pr)
                    refine_and_write(raw, amps, T, searcher, name,
                                     pcfg.zmax, wmax, quiet=True, obs=obs)
                acc = name + tag
                arts += [acc, acc + ".cand"]
            _record(manifest, arts, "accel" if zap else "fft+accel")
        if seam.durable:
            ffts = []
            for row, pr in zip(rows, pairs_host):
                f = block.names[row] + ".fft"
                datfft.write_fft(f, fftpack.np_pairs_to_complex64(pr))
                ffts.append(f)
            _record(manifest, ffts, "zapbirds" if zap else "fft+accel")
        jaxtel.sample_live_buffers(obs)
        _chaos(cfg, "fused-chunk", obs)
        if mesh is not None:
            _chaos(cfg, "sharded-fused-chunk", obs)

    ndone = 0
    pending = []          # the cross-stage in-flight window: chunk
    depth = seam.depths["window"]   # i+1's FFT is queued on the
    shard_depth = seam.depths["shard_window"]   # device before chunk
    for numout, blocks in sorted(seam.groups().items()):  # i's host
        n = numout & ~1   # collection starts
        for block in blocks:
            sharded = fusion.is_sharded(block)
            mesh = block.mesh if sharded else None
            ndev = (len(list(mesh.devices.flat)) if sharded else 1)
            # the staged consumers' verify-or-redo contract, per trial
            tags = [pass_tag(p) for p in passes]
            arts = []
            for name in block.names:
                for tag in tags:
                    acc = name + tag
                    arts += [acc, acc + ".cand"]
            _drop_stale(manifest, arts)
            rows = []
            for row, name in enumerate(block.names):
                if zap and manifest is not None and \
                        _valid(manifest, name + ".fft") and \
                        manifest.stage_of(name + ".fft") == "zapbirds":
                    continue     # journaled zapped spectrum: disk path
                need = any(
                    not (_valid(manifest, name + tag)
                         and _valid(manifest, name + tag + ".cand"))
                    for tag in tags)
                if need or (seam.durable
                            and not _valid(manifest, name + ".fft")):
                    rows.append(row)
            if not rows:
                continue
            todo_passes = []
            for p in passes:
                z, nh, sg, flo, wmax, fhi = pass_fields(p)
                todo_passes.append((_replace(cfg, zmax=z, numharm=nh,
                                             sigma=sg, flo=flo),
                                    wmax, fhi, pass_tag(p)))
            # memory budget is per DEVICE: a sharded whole-block holds
            # numdms/ndev rows on each chip
            per = max(1, int(2 ** 30 // max(n * 4, 1))) * ndev
            whole = rows == list(range(len(block.names))) \
                and len(rows) <= per
            # a partial sharded block (mixed resume) gathers its rows
            # off the mesh and takes the single-device path below
            chunk_mesh = mesh if (sharded and whole) else None
            for g0 in range(0, len(rows), per):
                chunk_rows = rows[g0:g0 + per]
                span = (obs.span("sharded-fused-chunk" if chunk_mesh
                                 is not None else "fused-chunk",
                                 files=len(chunk_rows), nbins=n)
                        if obs is not None else None)
                if whole and can_donate:
                    # the dedisp output block IS the FFT input block:
                    # donate it (input [nd, n] f32 and output
                    # [nd, n/2, 2] f32 are the same size, so the seam
                    # crossing is allocation-neutral); the host copy
                    # stays for spills.  CPU's XLA cannot alias these
                    # and would only warn.
                    chunk_dev = block.series_dev[:, :n]
                    seam.release(block)
                    pairs_dev = fusion.fused_rfft_batch(
                        chunk_dev, donate=True, obs=obs,
                        mesh=chunk_mesh)
                elif whole:
                    pairs_dev = fusion.fused_rfft_batch(
                        block.series_dev[:, :n], obs=obs,
                        mesh=chunk_mesh)
                else:
                    pairs_dev = fusion.fused_rfft_batch(
                        block.series_dev[np.asarray(chunk_rows), :n],
                        obs=obs)
                if span is not None:      # this chunk's dispatch only
                    span.finish()
                pending.append((block, chunk_rows, pairs_dev,
                                todo_passes, n, chunk_mesh))
                window = (shard_depth if chunk_mesh is not None
                          else depth)
                while len(pending) >= max(window, 1):
                    collect(pending.pop(0))
                    ndone += 1
    while pending:
        collect(pending.pop(0))
        ndone += 1
    if ndone:
        print("survey: fused realfft+accelsearch over %d seam chunks "
              "(device-resident, %d passes%s)"
              % (ndone, len(passes), ", zap" if zap else ""))


def _seam_singlepulse(seam, cfg, manifest=None, obs=None) -> None:
    """Single-pulse search over the seam-resident series: the exact
    app pipeline (apps/single_pulse_search) fed from HBM instead of a
    third .dat disk read + re-upload.  Inputs are bit-equal to the
    staged path's (same padded series, same .inf-roundtripped dt/dm,
    same onoff-derived offregions), so the .singlepulse artifacts are
    byte-identical.

    Sharded blocks search PER SHARD: each mesh device's DM sub-range
    runs search_many_resident on the device that dedispersed it (the
    per-file results are independent of batch composition, so shard
    batches equal the whole-batch candidate sets) — no gather, no
    re-upload.  A partially-resumed sharded block falls back to the
    row-stacking path below."""
    import jax.numpy as jnp
    from presto_tpu.apps.single_pulse_search import (sp_block_plan,
                                                     sp_input_plan)
    from presto_tpu.obs import jaxtel
    from presto_tpu.pipeline import fusion
    from presto_tpu.search.singlepulse import (SinglePulseSearch,
                                               write_singlepulse)

    sp = SinglePulseSearch(threshold=cfg.sp_threshold,
                           maxwidth=cfg.sp_maxwidth)
    planned = []          # (block, row, nuse, offregions)
    sharded_todo = []     # (block, nuse, offregions): whole blocks
    spfiles = [name + ".singlepulse" for b in seam.blocks
               for name in b.names]
    _drop_stale(manifest, spfiles)
    nsh = 0
    for block in seam.blocks:
        rows_todo = [row for row, name in enumerate(block.names)
                     if not _valid(manifest, name + ".singlepulse")]
        if not rows_todo:
            continue
        if fusion.is_sharded(block) and \
                rows_todo == list(range(len(block.names))):
            bplan = sp_block_plan(block.infos, block.numout)
            if bplan is not None:
                sharded_todo.append((block,) + tuple(bplan))
                nsh += len(rows_todo)
                continue
        for row in rows_todo:
            nuse, offregions = sp_input_plan(block.infos[row],
                                             block.numout)
            planned.append((block, row, nuse, offregions))

    nev = 0
    for block, nuse, offregions in sharded_todo:
        bdt = fusion.inf_float(block.dt)
        for sh in block.series_dev.addressable_shards:
            lo = sh.index[0].start or 0
            batch = sh.data[:, :nuse]       # stays on sh's device
            rows = list(range(lo, lo + int(batch.shape[0])))
            span = (obs.span("sp-seam-chunk", files=len(rows),
                             nuse=nuse, sharded=True)
                    if obs is not None else None)
            jaxtel.note_dispatch(obs, "sp_search")
            results = sp.search_many_resident(
                batch, bdt,
                dms=[fusion.inf_float(block.infos[r].dm, 12)
                     for r in rows],
                offregions_list=[offregions] * len(rows), obs=obs)
            written = []
            for r, (cands, _stds, _bad) in zip(rows, results):
                f = block.names[r] + ".singlepulse"
                write_singlepulse(f, cands)
                written.append(f)
                nev += len(cands)
            _record(manifest, written, "singlepulse")
            if span is not None:
                span.finish()
            _chaos(cfg, "sp-seam-chunk", obs)
    if not planned:
        if nsh:
            print("survey: single-pulse search over %d seam-resident "
                  "series (%d events, sharded)" % (nsh, nev))
        return
    groups = {}
    for item in planned:
        key = (item[2], fusion.inf_float(item[0].dt))
        groups.setdefault(key, []).append(item)
    for (nuse, dt), items in sorted(groups.items()):
        per = max(1, int(2 ** 30 // max(nuse * 4, 1)))
        for g0 in range(0, len(items), per):
            chunk = items[g0:g0 + per]
            span = (obs.span("sp-seam-chunk", files=len(chunk),
                             nuse=nuse)
                    if obs is not None else None)
            batch = jnp.stack([b.series_dev[row, :nuse]
                               for (b, row, _n, _o) in chunk])
            jaxtel.note_dispatch(obs, "sp_search")
            results = sp.search_many_resident(
                batch, dt,
                dms=[fusion.inf_float(b.infos[row].dm, 12)
                     for (b, row, _n, _o) in chunk],
                offregions_list=[o for (_b, _r, _n, o) in chunk],
                obs=obs)
            written = []
            for (b, row, _n, _o), (cands, _stds, bad) in zip(chunk,
                                                             results):
                f = b.names[row] + ".singlepulse"
                write_singlepulse(f, cands)
                written.append(f)
                nev += len(cands)
            _record(manifest, written, "singlepulse")
            if span is not None:
                span.finish()
            _chaos(cfg, "sp-seam-chunk", obs)
    print("survey: single-pulse search over %d seam-resident series "
          "(%d events%s)" % (len(planned) + nsh, nev,
                             ", %d sharded" % nsh if nsh else ""))


def _fused_fft_search(datfiles, cfg, manifest=None, obs=None) -> None:
    """Stage 4+6 fused (disk trials): batched rfft, search_many on the
    DEVICE spectra, one download for the .fft artifacts.  Only
    processes trials with NO verified .fft yet — existing valid
    spectra (an interrupted run's checkpoints) are left to
    _batched_accelsearch so their upload isn't paid twice."""
    _drop_stale(manifest, [f[:-4] + ".fft" for f in datfiles])
    todo = [f for f in datfiles
            if not _valid(manifest, f[:-4] + ".fft")]
    if not todo:
        return
    import jax
    import jax.numpy as jnp
    import numpy as np
    from presto_tpu.io import datfft
    from presto_tpu.obs import costmodel, jaxtel
    from presto_tpu.ops import fftpack
    from presto_tpu.apps.accelsearch import refine_and_write

    batched = jax.jit(jax.vmap(fftpack.realfft_packed_pairs))
    for n, files in _length_groups(
            todo, lambda sz: (sz // 4) & ~1).items():
        searcher, T = _survey_searcher(files[0], n // 2, cfg)
        per = max(1, int(2 ** 30 // max(n * 4, 1)))
        for g0 in range(0, len(files), per):
            chunk = files[g0:g0 + per]
            sp = (obs.span("fused-chunk", files=len(chunk), nbins=n)
                  if obs is not None else None)
            arr = np.stack([datfft.read_dat(f)[:n] for f in chunk])
            jaxtel.note_put(obs, arr.nbytes)
            costmodel.probe(obs, "rfft_batch", batched, arr)
            jaxtel.note_dispatch(obs, "rfft_batch")
            pairs_dev = batched(jnp.asarray(arr))    # stays in HBM
            jaxtel.note_dispatch(obs, "accel_search")
            results = searcher.search_many(pairs_dev, obs=obs)
            pairs_host = np.asarray(pairs_dev)       # one download
            jaxtel.note_get(obs, pairs_host.nbytes)
            arts = []
            for f, pr, raw in zip(chunk, pairs_host, results):
                amps = fftpack.np_pairs_to_complex64(pr)
                datfft.write_fft(f[:-4] + ".fft", amps)
                refine_and_write(raw, amps, T, searcher, f[:-4],
                                 cfg.zmax, quiet=True)
                acc = f[:-4] + "_ACCEL_%d" % cfg.zmax
                arts += [f[:-4] + ".fft", acc, acc + ".cand"]
            _record(manifest, arts, "fft+accel")
            jaxtel.sample_live_buffers(obs)
            if sp is not None:
                sp.finish()
            _chaos(cfg, "fused-chunk", obs)
    print("survey: fused realfft+accelsearch over %d trials "
          "(device-resident spectra)" % len(todo))


def _staged_fft_search_head(datfiles, cfg, manifest=None, obs=None):
    """Stage 4 alone (the staged path used when zapbirds intervenes).

    Resume caveat: an .fft the journal marks "zapbirds" is a ZAPPED
    spectrum — still valid, must not be regenerated (that would undo
    the zap and desync the stage tag)."""
    _drop_stale(manifest, [f[:-4] + ".fft" for f in datfiles])
    todo = [f for f in datfiles
            if not _valid(manifest, f[:-4] + ".fft")]
    if todo:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from presto_tpu.io import datfft
        from presto_tpu.obs import costmodel, jaxtel
        from presto_tpu.ops import fftpack
        batched = jax.jit(jax.vmap(fftpack.realfft_packed_pairs))
        for n, files in _length_groups(
                todo, lambda sz: (sz // 4) & ~1).items():
            # memory budget: read/stack/upload at most ~1 GB per group
            per = max(1, int(2 ** 30 // max(n * 4, 1)))
            for g0 in range(0, len(files), per):
                chunk = files[g0:g0 + per]
                sp = (obs.span("fft-chunk", files=len(chunk), nbins=n)
                      if obs is not None else None)
                # no mean subtraction: byte parity with the realfft
                # app (bin 0 is outside the searched range anyway)
                arr = np.stack([datfft.read_dat(f)[:n] for f in chunk])
                jaxtel.note_put(obs, arr.nbytes)
                costmodel.probe(obs, "rfft_batch", batched, arr)
                jaxtel.note_dispatch(obs, "rfft_batch")
                pairs = np.asarray(batched(jnp.asarray(arr)))
                jaxtel.note_get(obs, pairs.nbytes)
                for f, pr in zip(chunk, pairs):
                    datfft.write_fft(f[:-4] + ".fft",
                                     fftpack.np_pairs_to_complex64(pr))
                _record(manifest, [f[:-4] + ".fft" for f in chunk],
                        "realfft")
                if sp is not None:
                    sp.finish()
                _chaos(cfg, "fft-chunk", obs)
        print("survey: realfft over %d series (batched)" % len(todo))


def _batched_accelsearch(fftfiles, cfg, manifest=None, obs=None,
                         wmax=0, fhi=0.0):
    """Stage 6 alone (staged path): grouped search_many over .fft
    files already on disk (``wmax``/``fhi``: a jerk pass's)."""
    tag = pass_tag((cfg.zmax, cfg.numharm, cfg.sigma, cfg.flo, wmax))
    accs = [f[:-4] + tag for f in fftfiles]
    # the ACCEL table and its binary .cand companion are one logical
    # artifact: either going stale redoes both
    _drop_stale(manifest, accs + [a + ".cand" for a in accs])
    todo = [f for f, a in zip(fftfiles, accs)
            if not (_valid(manifest, a)
                    and _valid(manifest, a + ".cand"))]
    if todo:
        import numpy as np
        from presto_tpu.io import datfft
        from presto_tpu.obs import jaxtel
        from presto_tpu.ops import fftpack
        from presto_tpu.apps.accelsearch import refine_and_write
        for nbins, files in _length_groups(
                todo, lambda sz: sz // 8).items():
            searcher, T = _survey_searcher(files[0], nbins, cfg, wmax,
                                           fhi)
            # memory budget ~1 GB of host spectra per batched call
            per = max(1, int(2 ** 30 // max(nbins * 8, 1)))
            for g0 in range(0, len(files), per):
                chunk = files[g0:g0 + per]
                sp = (obs.span("accel-chunk", files=len(chunk),
                               nbins=nbins, zmax=cfg.zmax)
                      if obs is not None else None)
                amps_list = [datfft.read_fft(f) for f in chunk]
                batch = np.stack([fftpack.np_complex64_to_pairs(a)
                                  for a in amps_list])
                jaxtel.note_put(obs, batch.nbytes)
                jaxtel.note_dispatch(obs, "accel_search")
                results = searcher.search_many(batch, obs=obs)
                arts = []
                for f, amps, raw in zip(chunk, amps_list, results):
                    refine_and_write(raw, amps, T, searcher, f[:-4],
                                     cfg.zmax, wmax, quiet=True)
                    acc = f[:-4] + tag
                    arts += [acc, acc + ".cand"]
                _record(manifest, arts, "accel")
                jaxtel.sample_live_buffers(obs)
                if sp is not None:
                    sp.finish()
                _chaos(cfg, "accel-chunk", obs)
        print("survey: accelsearch over %d trials (batched)"
              % len(todo))


def resolve_triage_policy(spec, datdir):
    """cfg.triage -> a sifting policy callable (or None).

    Accepts None/False (off), True (defaults), a dict with any of
    {"budget", "budget_frac", "weights", "borderline_frac"}, or an
    already-built triage.TriagePolicy (returned as-is, datdir filled
    if unset)."""
    if not spec:
        return None
    from presto_tpu.triage import TriagePolicy
    if isinstance(spec, TriagePolicy):
        if spec.datdir is None:
            spec.datdir = datdir
        return spec
    kw = spec if isinstance(spec, dict) else {}
    return TriagePolicy(weights_path=kw.get("weights"),
                        budget=kw.get("budget"),
                        budget_frac=kw.get("budget_frac"),
                        borderline_frac=kw.get("borderline_frac", 0.25),
                        datdir=datdir)


def _finish_survey_stages(rawfiles, cfg, workdir, base, res, timer,
                          manifest=None, obs=None, seam=None):
    # ---- 7. sift ------------------------------------------------------
    from presto_tpu.pipeline.sifting import sift_candidates
    accfiles = []
    for p in cfg.all_passes:
        accfiles += _stage(os.path.basename(base) + "_DM*" + pass_tag(p),
                           workdir)
    accfiles = sorted(set(accfiles))
    res.candfile = os.path.join(workdir, "cands_sifted.txt")
    cl = sift_candidates(accfiles, numdms_min=cfg.min_dm_hits,
                         low_DM_cutoff=cfg.low_dm_cutoff,
                         policy=cfg.sift_policy)
    cl.to_file(res.candfile)
    _record(manifest, [res.candfile], "sift")
    res.sifted = cl
    print("survey: %d sifted candidates -> %s"
          % (len(cl), res.candfile))
    _chaos(cfg, "post-sift", obs)

    timer.mark("prepfold")
    # ---- 8. fold the top candidates -----------------------------------
    # recipe policy: fold everything above to_prepfold_sigma, never
    # more than max_folds (PALFA_presto_search.py:32-33); per-pass
    # caps split the budget by search pass, e.g. 20 lo-accel + 10
    # hi-accel (GBNCC_search.py:479-486).  The selection itself is
    # shared with the discovery-DAG sift node (sifting.py), so a DAG
    # fans out exactly the folds this driver would run.
    from presto_tpu.apps.prepfold import main as prepfold_main
    from presto_tpu.pipeline.sifting import select_fold_candidates
    accounting = {}
    top = select_fold_candidates(
        cl, fold_top=cfg.fold_top, fold_sigma=cfg.fold_sigma,
        max_folds=cfg.max_folds,
        max_folds_per_pass=cfg.max_folds_per_pass,
        pass_zmaxes=[pass_tag(p) for p in cfg.all_passes],
        policy=resolve_triage_policy(cfg.triage, workdir),
        accounting=accounting)
    tacct = accounting.get("triage")
    if tacct:
        print("survey: triage %s: scored %d, folding %d (%d avoided)"
              % (tacct.get("mode"), tacct.get("scored", 0),
                 tacct.get("selected", len(top)),
                 tacct.get("folds_avoided", 0)))
    for i, c in enumerate(top):
        accpath = os.path.join(workdir, c.filename) \
            if not os.path.dirname(c.filename) else c.filename
        if c.path:
            accpath = os.path.join(c.path, c.filename)
        candfile = accpath + ".cand"
        datfile = accpath.split("_ACCEL_")[0] + ".dat"
        if seam is not None:
            # prepfold reads its series from disk: spill this one
            # trial from the seam on demand (a no-op when the durable
            # tier already wrote it)
            seam.ensure_dat(datfile)
        outbase = os.path.join(workdir, "fold_cand%d" % (i + 1))
        if _valid(manifest, outbase + ".pfd"):
            res.folded.append(outbase + ".pfd")
            continue
        try:
            prepfold_main(["-accelfile", candfile,
                           "-accelcand", str(c.candnum),
                           "-dm", "%.2f" % c.DM, "-nosearch",
                           "-o", outbase, datfile])
            res.folded.append(outbase + ".pfd")
            _record(manifest, [outbase + ".pfd"], "prepfold")
        except SystemExit as e:
            print("survey: fold of cand %d failed: %s" % (i + 1, e))
        _chaos(cfg, "fold-cand", obs)
    print("survey: folded %d candidates" % len(res.folded))

    timer.mark("single_pulse")
    _chaos(cfg, "pre-singlepulse", obs)
    # ---- 9. single-pulse search --------------------------------------
    if cfg.singlepulse and res.datfiles:
        from presto_tpu.apps.single_pulse_search import main as sp_main
        # seam trials were searched device-resident (stage 9a) and
        # their .singlepulse artifacts verify here; anything else goes
        # through the app — spilled from the seam first if its .dat
        # never hit disk.
        _drop_stale(manifest,
                    [f[:-4] + ".singlepulse" for f in res.datfiles])
        sp_todo = [f for f in res.datfiles
                   if not _valid(manifest, f[:-4] + ".singlepulse")]
        if seam is not None:
            for f in sp_todo:
                seam.ensure_dat(f)
            sp_todo = [f for f in sp_todo if os.path.exists(f)]
        if sp_todo:
            argv = ["-t", str(cfg.sp_threshold)]
            if cfg.sp_maxwidth:
                argv += ["-m", str(cfg.sp_maxwidth)]
            sp_main(argv + sp_todo)
            _record(manifest,
                    [f[:-4] + ".singlepulse" for f in sp_todo],
                    "singlepulse")
        from presto_tpu.search.singlepulse import read_singlepulse
        for f in res.datfiles:
            spf = f[:-4] + ".singlepulse"
            if os.path.exists(spf):
                res.sp_events += len(read_singlepulse(spf))
        print("survey: %d single-pulse events" % res.sp_events)
    _chaos(cfg, "post-survey", obs)

    return res


# ----------------------------------------------------------------------
# Stacked cross-job execution (the serve layer's batch executor)
# ----------------------------------------------------------------------

class StackedSeamError(RuntimeError):
    """This job set cannot share one stacked device chain (e.g. the
    seams hold mesh-sharded blocks, whose concatenation would cross
    device placements).  The serve scheduler treats it like any batch
    failure: degrade to the per-job path."""


class _FanTimer:
    """StageTimer fan-out: the merged device stage advances every
    stacked job's stage clock together (a shared device call IS each
    job's stage work; attributing it N ways would hide it from N-1
    of them)."""

    def __init__(self, timers):
        self.timers = [t for t in timers if t is not None]

    def mark(self, name):
        for t in self.timers:
            t.mark(name)


class _FanInjector:
    """Chaos fan-out for the merged chain: a fault injected into ANY
    stacked job must abort the shared device call (the scheduler then
    degrades the whole batch to per-job execution)."""

    def __init__(self, injectors):
        self.injectors = list(injectors)

    def point(self, name):
        for fi in self.injectors:
            fi.point(name)


class _StackManifest:
    """Artifact-journal fan-out for a merged seam: every record /
    verify routes to the manifest of the job whose workdir holds the
    path, so N stacked jobs' journals end up exactly what N per-job
    runs would have written."""

    def __init__(self, routes):
        #: [(abs workdir, manifest-or-None)], deepest path first so a
        #: nested workdir routes to its own journal
        self.routes = sorted(((os.path.abspath(w), m)
                              for w, m in routes),
                             key=lambda e: -len(e[0]))

    def _for(self, path):
        p = os.path.abspath(path)
        for wd, m in self.routes:
            if p == wd or p.startswith(wd + os.sep):
                return m
        return None

    def _grouped(self, paths):
        groups = {}
        for p in paths:
            m = self._for(p)
            groups.setdefault(id(m), (m, []))[1].append(p)
        return list(groups.values())

    def valid(self, path):
        m = self._for(path)
        return os.path.exists(path) if m is None else m.valid(path)

    def stage_of(self, path):
        m = self._for(path)
        return "" if m is None else m.stage_of(path)

    def record_many(self, paths, stage="", save=True):
        for m, ps in self._grouped(paths):
            if m is not None:
                m.record_many(ps, stage, save=save)

    def invalidate_stale(self, paths, remove=True):
        stale = []
        for m, ps in self._grouped(paths):
            if m is not None:
                stale += list(m.invalidate_stale(ps, remove=remove))
            else:
                # journal-less jobs keep the legacy contract: missing
                # files are simply not survivors
                stale += [p for p in ps if not os.path.exists(p)]
        return stale


def _merged_seam(ctxs, obs, manifest):
    """ONE StageSeam over every stacked job's deposited blocks:
    same-geometry blocks (equal padded length, valid span, and sample
    time) are concatenated on the batch axis — jobs stacked into one
    [sum(numdms), numout] device array — so the downstream FFT /
    accelsearch / single-pulse stages run one batched dispatch where
    N per-job runs paid N.  Per-trial math is independent of batch
    composition (the DM-sharded seam's pinned invariant), so every
    artifact byte matches the per-job run.  Source blocks hand their
    DEVICE reference to the merged copy (host copies stay with each
    job's own seam for spills and prepfold)."""
    import jax.numpy as jnp
    import numpy as np
    from presto_tpu.pipeline import fusion

    cfg0 = ctxs[0]["cfg"]
    seam = fusion.StageSeam(ctxs[0]["workdir"], durable=_durable(cfg0),
                            manifest=manifest, obs=obs,
                            inflight_depth=cfg0.inflight_depth)
    groups = {}
    order = []
    for c in ctxs:
        for b in c["seam"].blocks:
            if fusion.is_sharded(b):
                raise StackedSeamError(
                    "mesh-sharded seam blocks cannot be stacked "
                    "across jobs")
            key = (int(b.numout), int(b.valid), float(b.dt))
            if key not in groups:
                order.append(key)
            groups.setdefault(key, []).append(b)
    for key in order:
        blocks = groups[key]
        if len(blocks) == 1:
            mb = blocks[0]
        else:
            mb = fusion.SeamBlock(
                names=[n for b in blocks for n in b.names],
                infos=[i for b in blocks for i in b.infos],
                dms=[d for b in blocks for d in b.dms],
                series_dev=jnp.concatenate(
                    [b.series_dev for b in blocks], axis=0),
                series_host=np.concatenate(
                    [b.series_host for b in blocks], axis=0),
                valid=key[1], numout=key[0], dt=key[2])
            for b in blocks:
                # the merged copy owns the HBM now; each job's seam
                # keeps the bit-identical host copy for spills/folds
                b.series_dev = None
        seam.blocks.append(mb)
        for row, name in enumerate(mb.names):
            seam._by_dat[os.path.abspath(name + ".dat")] = (mb, row)
    return seam


def _stacked_device_stages(ctxs):
    """The merged middle for one sub-stack: every job's seam blocks
    concatenated, ONE _device_search_stages pass over the union."""
    from dataclasses import replace as _replace
    cfg0 = ctxs[0]["cfg"]
    obs0 = ctxs[0]["obs"]
    manifest = _StackManifest([(c["workdir"], c["manifest"])
                               for c in ctxs])
    injectors = [c["cfg"].fault_injector for c in ctxs
                 if c["cfg"].fault_injector is not None]
    cfg_m = cfg0
    if injectors and (len(injectors) > 1
                      or injectors[0] is not cfg0.fault_injector):
        cfg_m = _replace(cfg0, fault_injector=_FanInjector(injectors))
    seam = _merged_seam(ctxs, obs0, manifest)
    disk_only = [f for c in ctxs for f in c["disk_only"]]
    datfiles = [f for c in ctxs for f in c["res"].datfiles]
    timer = _FanTimer([c["timer"] for c in ctxs])
    _device_search_stages(seam, disk_only, datfiles, cfg_m,
                          cfg_m.all_passes, timer, manifest, obs0)


def run_survey_stacked(jobs, stack_planner=None):
    """Run N same-geometry surveys with the device-bound middle
    STACKED: per-job heads (rfifind -> DDplan -> prepsubband) deposit
    N seams, the merged DM fan-outs cross the rFFT -> (zap) ->
    accelsearch -> single-pulse chain in shared batched dispatches
    (one H2D already paid at dedisp time, one candidate-collection
    download per stacked chunk), and per-job tails (sift / fold /
    residual single-pulse) finish each survey.

    jobs: sequence of (rawfiles, cfg, workdir, timer) tuples whose
    configs are stack-compatible (serve/batchexec checks the full
    signature; the chain itself requires equal pass geometry).
    stack_planner: optional callable(per_job_chain_bytes: list[int])
    -> sub-stack sizes summing to N (serve/batchexec supplies the
    tuned max-stack x pad-bucket plan with the HBM-budget clamp);
    None = one stack spanning every job.

    Byte-identity invariant: stacking only widens the batch axis of
    dispatches whose per-trial math is independent (the invariant the
    DM-sharded seam already pins), so every artifact is byte-identical
    to N independent run_survey calls.  Any failure propagates to the
    caller — the serve scheduler's existing degradation path then
    redoes the batch per-job (the verify-not-trust resume contract
    makes the partial head work safe to redo).
    """
    from presto_tpu import tune as _tune
    from presto_tpu.io.atomic import cleanup_stale_tmp
    from presto_tpu.obs import resolve_obs
    from presto_tpu.utils.timing import StageTimer

    ctxs = []
    for (rawfiles, cfg, workdir, timer) in jobs:
        obs = resolve_obs(getattr(cfg, "obs", None))
        os.makedirs(workdir, exist_ok=True)
        rawfiles = [os.path.abspath(f) for f in rawfiles]
        base = os.path.join(
            workdir,
            os.path.splitext(os.path.basename(rawfiles[0]))[0])
        cleanup_stale_tmp(workdir)
        manifest = None
        if cfg.verify_resume:
            from presto_tpu.pipeline.manifest import SurveyManifest
            manifest = SurveyManifest.load(workdir)
        if timer is None:
            timer = StageTimer(obs=obs)
        ctxs.append({
            "rawfiles": rawfiles, "cfg": cfg, "workdir": workdir,
            "base": base, "res": SurveyResult(workdir=workdir),
            "timer": timer, "manifest": manifest, "obs": obs,
            "span": None, "result": None,
        })
    cfg0 = ctxs[0]["cfg"]
    try:
        with _tune.scoped(cfg0.tune):
            for c in ctxs:
                c["span"] = c["obs"].span(
                    "survey", workdir=c["workdir"],
                    raw=os.path.basename(c["rawfiles"][0]),
                    stacked=len(ctxs))
                c["seam"], c["disk_only"] = _survey_head(
                    c["rawfiles"], c["cfg"], c["workdir"], c["base"],
                    c["res"], c["timer"], c["manifest"], c["obs"])
            sizes = [len(ctxs)]
            if stack_planner is not None:
                per_job = [sum(len(b.names) * b.numout * 4 * 3
                               for b in c["seam"].blocks)
                           for c in ctxs]
                sizes = list(stack_planner(per_job)) or sizes
            if sum(sizes) != len(ctxs):
                raise StackedSeamError(
                    "stack plan %r does not cover %d jobs"
                    % (sizes, len(ctxs)))
            i = 0
            for size in sizes:
                _stacked_device_stages(ctxs[i:i + size])
                i += size
            for c in ctxs:
                c["timer"].mark("sift")
                _chaos(c["cfg"], "pre-sift", c["obs"])
                c["result"] = _finish_survey_stages(
                    c["rawfiles"], c["cfg"], c["workdir"], c["base"],
                    c["res"], c["timer"], c["manifest"], c["obs"],
                    seam=c["seam"])
                c["span"].finish()
                c["span"] = None
    except BaseException as e:
        for c in ctxs:
            if c["span"] is not None:
                c["span"].finish("error: %s" % type(e).__name__)
                c["span"] = None
            c["obs"].dump_flight(c["workdir"],
                                 reason=type(e).__name__)
        raise
    finally:
        for c in ctxs:
            c["timer"].mark(None)
            c["timer"].report()
            with _tune.scoped(c["cfg"].tune):
                _tune.write_provenance(c["workdir"])
            c["obs"].flush(default_dir=c["workdir"])
    return [c["result"] for c in ctxs]
