"""The survey's per-trial device path, driven through its seam consumers.

Each chunk of full-length DM trials is synthesized on the device from
the seed, deposited at a ``fusion.StageSeam`` as one ``SeamBlock`` (as
prepsubband's hand-off does), and consumed by the survey's own stages:
``survey._seam_singlepulse`` (single-pulse search) and
``survey._seam_fft_search(..., zap=True)`` (batched rFFT, download,
zap, re-upload, every accel pass, candidate refinement and ACCEL
writes).  Two private functions: the program has no public seam entry.

Correctness taps wrap three program functions without changing what
they do, and keep what the timed path produced for the sampled trials:
the zapped spectrum, the raw candidates (the F-Fdot build and harmonic
sum's output) and the block geometry of each pass's searcher
(``refine_and_write``), the polished candidates and their seeds
(``optimize_accelcands``), and the single-pulse events
(``SinglePulseSearch.search_many_resident``).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from dataclasses import replace

import numpy as np

from perfbench import counts, synth
from perfbench.harness import ROOT
from perfbench.reference import search_ref


def inf_float(x, digits: int = 12) -> float:
    return float(("%%.%dg" % digits) % float(x))


class Taps:
    """Pass-through wrappers that record the sampled trials' outputs."""

    def __init__(self):
        self.want = {}           # trial name -> record dict
        self.dm_names = {}       # dm (as .inf text) -> trial name
        self._cur = None
        self._saved = []
        self.polish_args = None  # the last polish call's arguments

    def install(self):
        import presto_tpu.apps.accelsearch as acc
        import presto_tpu.search.polish as pol
        import presto_tpu.search.singlepulse as spm
        taps = self
        rw0, oa0 = acc.refine_and_write, pol.optimize_accelcands
        sp0 = spm.SinglePulseSearch.search_many_resident

        def refine_and_write(raw_cands, amps, T, searcher, base, zmax,
                             *a, **kw):
            rec = taps.want.get(base)
            if rec is None:
                return rw0(raw_cands, amps, T, searcher, base, zmax, *a, **kw)
            g = searcher._plane_geom()
            # an empty candidate list is never polished: recorded empty
            rec[zmax] = {"amps": amps, "T": T, "polish": ([], []),
                         "geom": (searcher.cfg.uselen, g.hw_use,
                                  g.numdata),
                         "raw": [(c.r, c.z, c.numharm, c.power)
                                 for c in raw_cands]}
            taps._cur = rec[zmax]
            try:
                return rw0(raw_cands, amps, T, searcher, base, zmax, *a,
                           **kw)
            finally:
                taps._cur = None

        def optimize_accelcands(amps, cands, T, numindep, *a, **kw):
            taps.polish_args = (amps, T, numindep, a, kw)
            seeds = [(c.r, c.z, c.numharm) for c in cands]
            out = oa0(amps, cands, T, numindep, *a, **kw)
            if taps._cur is not None:
                taps._cur["polish"] = (seeds, [
                    None if o is None else (o.r, o.z, o.power, o.numharm)
                    for o in out])
            return out

        def search_many_resident(self_, series, dt, dms, *a, **kw):
            res = sp0(self_, series, dt, dms, *a, **kw)
            for dm, r in zip(dms, res):
                rec = taps.want.get(taps.dm_names.get(inf_float(dm)))
                if rec is not None:
                    rec["sp"] = [(c.bin, c.downfact, c.sigma) for c in r[0]]
            return res

        self._saved = [(acc, "refine_and_write", rw0),
                       (pol, "optimize_accelcands", oa0),
                       (spm.SinglePulseSearch, "search_many_resident", sp0)]
        acc.refine_and_write = refine_and_write
        pol.optimize_accelcands = optimize_accelcands
        spm.SinglePulseSearch.search_many_resident = search_many_resident

    def remove(self):
        for obj, name, fn in self._saved:
            setattr(obj, name, fn)
        self._saved = []


class Cell:
    def __init__(self, config, traffic, spans, rehearse=False):
        t = dict(traffic)
        if rehearse:
            t.update({k: v for k, v in traffic["rehearse"].items()
                      if k != "check"})
            t["check"] = dict(traffic["check"],
                              **traffic["rehearse"].get("check", {}))
        self.config, self.traffic, self.spans = config, t, spans
        self.rehearse = rehearse
        self.dt = float(config["dt_s"])
        self.ntr = int(t["trials_per_chunk"])
        nsamp = int(t.get("nsamp", config["nsamp"]))
        lo = config["fctr_mhz"] - config["bw_mhz"] / 2
        hi = config["fctr_mhz"] + config["bw_mhz"] / 2
        maxd = synth.max_delay_samples(t["lodm"] + self.ntr * t["dmstep"],
                                       lo, hi, self.dt)
        self.valid = nsamp - maxd
        self.obs = None
        self.taps = Taps()
        self.chunks = []          # window chunk ids
        self.names = {}           # chunk id -> trial names

    # -- set-up ----------------------------------------------------------

    def setup(self, seed: int) -> None:
        from presto_tpu.pipeline.recipes import get_recipe
        from presto_tpu.utils.psr import choose_N, good_fft_size

        self.seed = seed
        c = self.config
        self.numout = (choose_N(self.valid)
                       or good_fft_size(self.valid, multiple_of=2))
        recipe = get_recipe(c["name"])
        cfg = recipe.to_config(c["lodm"], c["hidm"],
                               zaplist=os.path.join(ROOT, c["zaplist"]))
        # the resident service's plan cache (serve/plancache): each
        # accel geometry compiles once, not once per seam call
        from presto_tpu.serve.plancache import PlanCache, SearcherProvider
        self.cfg = replace(cfg, durable_stages=False,
                           plan_provider=SearcherProvider(PlanCache(8)))
        self.passes = self.cfg.all_passes
        want = [tuple(p) for p in c["accel_passes"]]
        if [tuple(p) for p in self.passes] != want:
            raise SystemExit("recipe passes %s differ from the config's %s"
                             % (self.passes, want))
        self.workdir = tempfile.mkdtemp(prefix="perfbench_search_")
        self.taps.install()
        # compiles every program of the path; the polish's shape buckets
        # follow each trial's candidate count, so two chunks
        for cid in range(int(self.traffic["warmup_chunks"])):
            with self.spans("warmup"):
                self.chunk(cid)
        with self.spans("warmup"):
            self._warm_polish()
        self.first = int(self.traffic["warmup_chunks"])
        self.chunks = []

    def _warm_polish(self) -> None:
        """Load the polish's programs for every candidate-list bucket
        the window can meet.  The polish pads a trial's list to power-
        of-two counts of (candidate, harmonic) pairs, so which programs
        a chunk needs follows its candidate count: warm-up chunks meet
        the common bucket, and a window trial with more candidates
        would load (on a fresh checkout, compile) the next one inside
        the window.  Each ``warm_polish`` entry of the traffic,
        [candidates, numharm, z], is one synthetic list polished with
        the warm-up's last spectrum and arguments."""
        import presto_tpu.search.polish as pol
        from presto_tpu.search.accel import AccelCand

        amps, T, numindep, a, kw = self.taps.polish_args
        for n, nh, z in self.traffic.get("warm_polish", []):
            cands = [AccelCand(power=1.0, sigma=1.0, numharm=int(nh),
                               r=1000.0 + 8.0 * i, z=float(z))
                     for i in range(int(n))]
            pol.optimize_accelcands(amps, cands, T, numindep, *a, **kw)
        self.taps.polish_args = None

    def chunk(self, cid: int) -> None:
        """One chunk through the seam consumers."""
        from presto_tpu.io.infodata import InfoData
        from presto_tpu.apps.common import set_onoff
        from presto_tpu.pipeline import fusion, survey

        t = self.traffic
        with self.spans("synth"):
            p = synth.search_params(t, self.seed, cid, self.ntr,
                                    self.valid, self.numout, self.dt)
            series = synth.search_series(t, self.seed, cid, p, self.valid,
                                         self.numout)
        base = cid * self.ntr
        dms = [inf_float(t["lodm"] + ((base + i) % t["dms_per_method"])
                         * t["dmstep"]) for i in range(self.ntr)]
        names, infos = [], []
        for i, dm in enumerate(dms):
            name = os.path.join(self.workdir, "c%05d_DM%.2f" % (cid, dm))
            info = InfoData(name=name, N=self.numout, dt=self.dt, dm=dm,
                            telescope="Arecibo",
                            num_chan=int(self.config["nchan"]),
                            mjd_i=60000)
            set_onoff(info, self.valid, self.numout)
            names.append(name)
            infos.append(info)
        self.names[cid] = names
        self.taps.dm_names = {inf_float(dm): n for dm, n in zip(dms, names)}
        self._arm(cid, names)
        block = fusion.SeamBlock(names=names, infos=infos, dms=dms,
                                 series_dev=series, series_host=None,
                                 valid=self.valid, numout=self.numout,
                                 dt=self.dt)
        seam = fusion.StageSeam(self.workdir, durable=False, obs=self.obs)
        seam.add_block(block)
        with self.spans("single_pulse"):
            survey._seam_singlepulse(seam, self.cfg, None, self.obs)
        with self.spans("fft_search"):
            survey._seam_fft_search(seam, self.cfg, self.passes, None,
                                    self.obs, zap=True)

    def _arm(self, cid: int, names) -> None:
        """Mark this chunk's sampled trials for the taps."""
        if cid < int(self.traffic["warmup_chunks"]):
            return
        k = int(self.traffic["check"]["trials_per_chunk"])
        g = synth.rng(self.seed, 5, cid)
        for i in sorted(g.choice(self.ntr, size=min(k, self.ntr),
                                 replace=False).tolist()):
            self.taps.want[names[i]] = {"chunk": cid, "row": i}

    # -- the measured window ----------------------------------------------

    def window(self, seconds: float) -> dict:
        nmax = int(self.traffic.get("chunks", 0)) if self.rehearse else 0
        t0 = time.perf_counter()
        cid = self.first
        while True:
            with self.spans("chunk"):
                self.chunk(cid)
            self.chunks.append(cid)
            cid += 1
            if nmax:
                if len(self.chunks) >= nmax:
                    break
            elif time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        trials = len(self.chunks) * self.ntr
        return {"elapsed_s": elapsed, "trials": trials,
                "search_rate": trials / elapsed, "attempted": trials}

    def close(self) -> None:
        self.taps.remove()
        if getattr(self, "workdir", None):
            shutil.rmtree(self.workdir, ignore_errors=True)
            self.workdir = None

    # -- per-layer counts ---------------------------------------------------

    def required(self) -> dict:
        """Required work per trial of each accel layer, both passes."""
        n = self.numout & ~1
        T = self.numout * inf_float(self.dt, 15)
        build = {"flops": 0.0, "bytes": 0.0}
        scan = {"flops": 0.0, "bytes": 0.0}
        for zmax, nh, _sg, flo in self.passes:
            b = counts.accel_build(n // 2, T, zmax, flo, 1)
            s = counts.accel_scan(n // 2, T, zmax, nh, flo, 1)
            for k in build:
                build[k] += b[k]
                scan[k] += s[k]
        return {"accel_build": build, "accel_scan": scan}

    # -- correctness ----------------------------------------------------------

    def release(self) -> None:
        """Nothing of the program's stays on the device between chunks."""

    def missing(self) -> int:
        """Window trials whose artifacts never came."""
        miss = 0
        zs = [p[0] for p in self.passes]
        for cid in self.chunks:
            for name in self.names[cid]:
                files = ([name + "_ACCEL_%d" % z for z in zs]
                         + [name + ".singlepulse"])
                miss += not all(os.path.exists(f) for f in files)
        return miss

    def check(self, control: bool = False):
        """(checks [(name, value, limit)], failed, control readings)."""
        chk = self.traffic["check"]
        lim = chk["limits"]
        rec_tol = chk["recall"]
        birds = search_ref.read_birds(os.path.join(ROOT,
                                                   self.config["zaplist"]))
        failed = self.missing()
        n = self.numout & ~1
        T = self.numout * inf_float(self.dt, 15)
        zs = [p[0] for p in self.passes]
        names = ("spec_gap", "scan_gap", "cand_gap", "sp_gap")
        gaps = {k: [0.0] for k in names}
        ctl = {k: [0.0] for k in names}
        series_of = {}
        armed = sorted(nm for nm, r in self.taps.want.items()
                       if r["chunk"] in self.chunks)
        g = synth.rng(self.seed, 8)
        keep = sorted(g.choice(len(armed), size=min(len(armed),
                                                    chk["max_trials"]),
                               replace=False).tolist())
        for name in [armed[i] for i in keep]:
            rec = self.taps.want[name]
            cid, row = rec["chunk"], rec["row"]
            if not all(z in rec for z in zs) or "sp" not in rec:
                from perfbench.harness import eprint
                eprint("no outputs recorded for %s: passes %s, sp %s"
                       % (name, [z for z in zs if z in rec], "sp" in rec))
                failed += 1
                gaps["spec_gap"].append(float("inf"))
                continue
            if cid not in series_of:
                p = synth.search_params(self.traffic, self.seed, cid,
                                        self.ntr, self.valid, self.numout,
                                        self.dt)
                s = synth.search_series(self.traffic, self.seed, cid, p,
                                        self.valid, self.numout)
                series_of = {cid: (p, np.asarray(s))}
            p, sarr = series_of[cid]
            x = sarr[row].astype(np.float64)
            X = search_ref.packed_rfft(x[:n])
            Z, mask = search_ref.zap(X, birds, T, self.numout)
            gaps["spec_gap"].append(search_ref.spectrum_gap(
                rec[zs[0]]["amps"], Z, mask))
            if control:
                Xl = search_ref.packed_rfft(x[:n], lowp=True)
                Zl, _m = search_ref.zap(Xl, birds, T, self.numout)
                ctl["spec_gap"].append(search_ref.spectrum_gap(Zl, Z, mask))
            g = synth.rng(self.seed, 7, cid)
            for z in zs:
                self._scan_gaps(rec[z], Z, g, chk["max_cands"], gaps, ctl,
                                control)
                seeds, outs = rec[z]["polish"]
                ok = [i for i, o in enumerate(outs) if o is not None]
                top = sorted(ok, key=lambda i: -outs[i][2])[:8]
                rest = [i for i in ok if i not in top]
                more = g.choice(len(rest), size=min(len(rest),
                                                    chk["max_cands"] - len(top)),
                                replace=False) if rest else []
                pick = top + [rest[j] for j in more]
                if not pick:
                    continue
                ref = search_ref.candidate_powers(Z, seeds, outs, pick)
                prog = np.array([outs[i][2] for i in pick])
                gaps["cand_gap"].append(float(np.max(np.abs(prog - ref)
                                                     / ref)))
                if control:
                    low = search_ref.candidate_powers(Z, seeds, outs, pick,
                                                      lowp=True)
                    ctl["cand_gap"].append(float(np.max(np.abs(low - ref)
                                                        / ref)))
                failed += self._missed_pulsars(rec[z]["raw"], p, row, z,
                                               rec_tol, name, mask)
            normed, bad = search_ref.sp_normalized(x, self.valid)
            ev = rec["sp"]
            if ev:
                ref = search_ref.sp_sigmas(normed, [(b, d) for b, d, _s in ev])
                prog = np.array([s for _b, _d, s in ev])
                gaps["sp_gap"].append(float(np.max(np.abs(prog - ref))))
                if control:
                    low = search_ref.sp_sigmas(
                        search_ref.sp_normalized(x, self.valid, lowp=True)[0],
                        [(b, d) for b, d, _s in ev], lowp=True)
                    ctl["sp_gap"].append(float(np.max(np.abs(low - ref))))
            failed += self._missed_pulses(ev, p, row, rec_tol, name,
                                          normed)
        checks = [(k, max(v), lim[k]) for k, v in gaps.items()]
        failed += sum(1 for _k, v, l in checks if not v <= l)
        return checks, failed, ({k: max(v) for k, v in ctl.items()}
                                if control else None)

    @staticmethod
    def _scan_gaps(rec, Z, g, kmax, gaps, ctl, control) -> None:
        """Widest relative gap of a raw candidate's summed power (the
        F-Fdot build and harmonic sum) from the reference's at its (r,
        z, numharm): the strongest 8 and a seeded draw of the rest."""
        raw = rec["raw"]
        if not raw:
            return
        order = sorted(range(len(raw)), key=lambda i: -raw[i][3])
        rest = order[8:]
        more = g.choice(len(rest), size=min(len(rest), kmax - 8),
                        replace=False).tolist() if rest else []
        pick = order[:8] + [rest[j] for j in more]
        plane = search_ref.Plane(Z, rec["geom"])
        ref = np.array([plane.summed(*raw[i][:3]) for i in pick])
        prog = np.array([raw[i][3] for i in pick])
        gaps["scan_gap"].append(float(np.max(np.abs(prog - ref) / ref)))
        if control:
            low = search_ref.Plane(Z, rec["geom"], lowp=True)
            lp = np.array([low.summed(*raw[i][:3]) for i in pick])
            ctl["scan_gap"].append(float(np.max(np.abs(lp - ref) / ref)))

    @staticmethod
    def _missed_pulsars(raw, p, row, zmax, tol, name, zapped) -> int:
        """Injected pulsars not among the raw candidates, except those
        whose fundamental's sweep the reference's zap covers (a pulsar
        on a mains harmonic is removed by design)."""
        miss = 0
        for j in range(p["r_mid"].shape[1]):
            r0, z0 = p["r_mid"][row, j], float(p["z"][row, j])
            lo = int(np.floor(r0 - abs(z0) / 2)) - 1
            if abs(z0) > zmax or zapped[lo:lo + int(abs(z0)) + 4].any():
                continue
            hit = any(abs(r - r0) <= tol["r_tol"]
                      and abs(z - z0) <= tol["z_tol"]
                      for r, z, _h, _pw in raw)
            if not hit:
                from perfbench.harness import eprint
                eprint("missed pulsar %d of %s in the zmax=%d pass "
                       "(r %.2f z %.2f)" % (j, name, zmax, r0, z0))
                miss += 1
        return miss

    @staticmethod
    def _missed_pulses(ev, p, row, tol, name, normed) -> int:
        """Injected pulses not reported, except those where the
        reference searches nothing: a block its bad-block cut zeroes,
        or the tail past the last whole chunk (single_pulse_search
        semantics)."""
        miss = 0
        for k in range(p["sp_start"].shape[1]):
            w = int(p["sp_width"][row, k])
            s0 = int(p["sp_start"][row, k])
            if not np.any(normed[s0:s0 + w]):
                continue
            c = s0 + w / 2.0
            if not any(abs(b - c) <= w and s >= tol["sp_min_sigma"]
                       for b, _d, s in ev):
                from perfbench.harness import eprint
                eprint("missed single pulse %d of %s (bin %.0f width %d)"
                       % (k, name, c, w))
                miss += 1
        return miss

