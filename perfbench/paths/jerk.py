"""A band of the survey's (r, z, w) jerk pass, driven through its seam
consumer.

Each chunk of full-length DM trials is synthesized on the device from
the seed (noise as in the search traffic, binary pulsars whose
harmonics carry constant fdot and fdotdot), deposited at a
``fusion.StageSeam`` as one ``SeamBlock`` and consumed by
``survey._seam_fft_search(..., zap=True)`` with the configuration's
jerk pass banded to this chip's share of the volume: batched rFFT,
download, zap, re-upload, the banded jerk volume (search/jerk.py),
candidate refinement with the (r, z, w) polish, and the ACCEL writes.
No single-pulse search runs.

Correctness taps wrap two program functions without changing what
they do: ``refine_and_write`` (the zapped spectrum, the raw
candidates and the searcher's block geometry of the sampled trials)
and ``optimize_jerk_cands`` (the polished candidates and their seeds;
every call of it is timed as a ``jerk_polish`` span).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from dataclasses import replace

import numpy as np

from perfbench import counts_jerk, synth
from perfbench.harness import ROOT, eprint
from perfbench.paths.search import inf_float
from perfbench.reference import jerk_ref, search_ref


class Taps:
    """Pass-through wrappers that record the sampled trials' outputs."""

    def __init__(self, spans):
        self.spans = spans
        self.want = {}           # trial name -> record dict
        self._cur = None
        self._saved = []
        self.polish_args = None  # the last jerk polish call's arguments

    def install(self):
        import presto_tpu.apps.accelsearch as acc
        import presto_tpu.search.polish as pol
        taps = self
        rw0, oj0 = acc.refine_and_write, pol.optimize_jerk_cands

        def refine_and_write(raw_cands, amps, T, searcher, base, zmax,
                             *a, **kw):
            rec = taps.want.get(base)
            if rec is not None:
                from presto_tpu.search import jerk
                v = jerk.volume(searcher)
                rec.update(amps=amps, polish=([], []),
                           geom=(searcher.cfg.uselen, v.hw, v.numdata,
                                 searcher.kern.kmax),
                           raw=[(c.r, c.z, c.w, c.numharm, c.power)
                                for c in raw_cands])
            taps._cur = rec
            try:
                return rw0(raw_cands, amps, T, searcher, base, zmax, *a,
                           **kw)
            finally:
                taps._cur = None

        def optimize_jerk_cands(amps, cands, T, numindep, *a, **kw):
            taps.polish_args = (amps, T, numindep, a, kw)
            with taps.spans("jerk_polish"):
                out = oj0(amps, cands, T, numindep, *a, **kw)
            if taps._cur is not None:
                taps._cur["polish"] = (
                    [(c.r, c.z, c.w, c.numharm) for c in cands],
                    [(o.r, o.z, o.w, o.power) for o in out])
            return out

        self._saved = [(acc, "refine_and_write", rw0),
                       (pol, "optimize_jerk_cands", oj0)]
        acc.refine_and_write = refine_and_write
        pol.optimize_jerk_cands = optimize_jerk_cands

    def remove(self):
        for obj, name, fn in self._saved:
            setattr(obj, name, fn)
        self._saved = []


# ----------------------------------------------------------------------
# inputs
# ----------------------------------------------------------------------

def jerk_params(traffic, seed, chunk, ntr, valid, numout, dt, band):
    """Host-drawn pulsars of one chunk: the top harmonic's plane
    frequency inside the band, its plane |z| and |w| in the traffic's
    ranges; returned in fundamental units (phase step of the mean-free
    linear term, t = 0 fdot z0 and w, mean bin and mean z)."""
    g = synth.rng(seed, 11, chunk)
    psrs = traffic["pulsars"]
    npsr = len(psrs)
    nharm = max(p["nharm"] for p in psrs)
    T = numout * dt
    m = np.zeros((ntr, npsr), np.uint32)
    z0 = np.zeros((ntr, npsr), np.float32)
    w = np.zeros((ntr, npsr), np.float32)
    amp = np.zeros((ntr, npsr, nharm), np.float32)
    r_mid = np.zeros((ntr, npsr))
    z_mid = np.zeros((ntr, npsr))
    for j, p in enumerate(psrs):
        h = p["nharm"]
        fz = g.uniform(*p["z"], size=ntr) * g.choice([-1.0, 1.0], ntr) / h
        fw = g.uniform(*p["w"], size=ntr) * g.choice([-1.0, 1.0], ntr) / h
        # the top harmonic's mean plane bin, a whole guard of the
        # sweep inside the band
        guard = (np.abs(fz) + np.abs(fw)) * h + 8.0
        top = g.uniform(band[0] + guard, band[1] - guard)
        rm = top / h
        z0[:, j] = fz - fw / 2.0
        w[:, j] = fw
        r0 = rm - z0[:, j] / 2.0 - fw / 6.0
        for i in range(ntr):
            m[i, j] = synth.phase_step(r0[i] / T, dt)
        r_mid[:, j] = (m[:, j].astype(np.float64) * numout / synth.TWO32
                       + z0[:, j] / 2.0 + w[:, j] / 6.0)
        z_mid[:, j] = z0[:, j] + w[:, j] / 2.0
        a = np.sqrt(4.0 * p["power"] / valid)
        amp[:, j, :h] = (a * p["decay"] ** np.arange(h))[None]
    return dict(m=m, z0=z0, w=w, amp=amp, r_mid=r_mid, z_mid=z_mid,
                nharm=np.array([p["nharm"] for p in psrs]))


_series_fns: dict = {}


def jerk_series(traffic, seed, chunk, params, valid, numout):
    """[ntr, numout] float32 device series: noise and pulsars over the
    valid span, padded with each series' mean."""
    import jax
    import jax.numpy as jnp

    key = (valid, numout)
    fn = _series_fns.get(key)
    if fn is None:
        def one(k, m, z0, w, amp, off, sig):
            n = jnp.arange(numout, dtype=jnp.uint32)
            x = jax.random.normal(k, (numout,), jnp.float32)
            u = n.astype(jnp.float32) / numout
            for j in range(m.shape[0]):
                lin = (n * m[j]).astype(jnp.float32) / synth.TWO32
                quad = jnp.mod(0.5 * z0[j] * u * u, 1.0)
                cub = jnp.mod((w[j] / 6.0) * u * u * u, 1.0)
                ph = jnp.mod(lin + quad + cub, 1.0)
                for h in range(amp.shape[1]):
                    x = x + amp[j, h] * jnp.cos(
                        2 * jnp.pi * jnp.mod((h + 1) * ph, 1.0))
            x = off + sig * x
            ok = n.astype(jnp.int32) < valid
            mean = jnp.sum(jnp.where(ok, x, 0.0)) / valid
            return jnp.where(ok, x, mean)

        def batch(k0, m, z0, w, amp, off, sig):
            keys = jax.vmap(lambda i: jax.random.fold_in(k0, i))(
                jnp.arange(m.shape[0]))
            return jax.vmap(one, in_axes=(0, 0, 0, 0, 0, None, None))(
                keys, m, z0, w, amp, off, sig)

        fn = jax.jit(batch)
        _series_fns[key] = fn
    return fn(synth.jax_key(seed, 12, chunk), params["m"], params["z0"],
              params["w"], params["amp"], np.float32(traffic["offset"]),
              np.float32(traffic["sigma"]))


# ----------------------------------------------------------------------
# the cell
# ----------------------------------------------------------------------

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
        self.numout = int(t.get("nsamp", config["nsamp"]))
        lo = config["fctr_mhz"] - config["bw_mhz"] / 2
        hi = config["fctr_mhz"] + config["bw_mhz"] / 2
        maxd = synth.max_delay_samples(
            t["lodm"] + t["dms_per_method"] * t["dmstep"], lo, hi, self.dt)
        self.valid = self.numout - maxd
        # this chip's band: share k of n equal bands of [0, Nyquist)
        k, nb = config["band"]
        nbins = self.numout // 2
        self.band = ((k - 1) * nbins // nb, k * nbins // nb)
        self.T = self.numout * inf_float(self.dt, 15)
        self.obs = None
        self.taps = Taps(spans)
        self.chunks = []
        self.names = {}
        self.params = {}

    def jerk_pass(self):
        """The configuration's jerk pass with its band as -flo/-fhi:
        each edge a quarter bin above the band's edge bin, so the
        searcher's rlo/rhi land on the edge bins."""
        z, nh, sg, _flo, wmax = self.config["jerk_pass"]
        flo = (self.band[0] + 0.25) / self.T
        fhi = (self.band[1] + 0.25) / self.T
        return (int(z), int(nh), float(sg), flo, int(wmax), fhi)

    # -- set-up ----------------------------------------------------------

    def setup(self, seed: int) -> None:
        from presto_tpu.pipeline.recipes import get_recipe

        self.seed = seed
        c = self.config
        recipe = get_recipe(c["name"])
        if tuple(c["jerk_pass"]) not in recipe.accel_passes:
            raise SystemExit("the config's jerk pass %s is not a pass of "
                             "recipe %s" % (c["jerk_pass"], recipe.name))
        cfg = recipe.to_config(c["lodm"], c["hidm"],
                               zaplist=os.path.join(ROOT, c["zaplist"]))
        from presto_tpu.serve.plancache import PlanCache, SearcherProvider
        self.cfg = replace(cfg, durable_stages=False, singlepulse=False,
                           plan_provider=SearcherProvider(PlanCache(8)))
        self.passes = [self.jerk_pass()]
        self.workdir = tempfile.mkdtemp(prefix="perfbench_jerk_")
        self.taps.install()
        for cid in range(int(self.traffic["warmup_chunks"])):
            with self.spans("warmup"):
                self.chunk(cid)
        with self.spans("warmup"):
            self._warm_polish()
        self.first = int(self.traffic["warmup_chunks"])
        self.chunks = []

    def _warm_polish(self) -> None:
        """Load the polish programs of every candidate-list bucket and
        window geometry the window can meet.  Both polishes pad a
        trial's list to power-of-two pair counts and size their
        window from the batch's largest |z h| and |w h|, so each
        ``warm_polish`` entry of the traffic, [candidates, numharm, z,
        w] (fundamental units), is one synthetic list polished, at w =
        0 and then in (r, z, w), with the warm-up's last spectrum."""
        import presto_tpu.search.polish as pol
        from presto_tpu.search.accel import AccelCand

        amps, T, numindep, a, kw = self.taps.polish_args
        for n, nh, z, w in self.traffic.get("warm_polish", []):
            cands = [AccelCand(power=1.0, sigma=1.0, numharm=int(nh),
                               r=self.band[0] / nh + 8.0 * i, z=float(z),
                               w=float(w)) for i in range(int(n))]
            pol.optimize_accelcands(amps, cands, T, numindep,
                                    harmpolish=True, with_props=False)
            pol.optimize_jerk_cands(amps, cands, T, numindep, *a, **kw)
        self.taps.polish_args = None

    def chunk(self, cid: int) -> None:
        """One chunk through the seam consumer."""
        from presto_tpu.apps.common import set_onoff
        from presto_tpu.io.infodata import InfoData
        from presto_tpu.pipeline import fusion, survey

        t = self.traffic
        with self.spans("synth"):
            p = jerk_params(t, self.seed, cid, self.ntr, self.valid,
                            self.numout, self.dt, self.band)
            series = jerk_series(t, self.seed, cid, p, self.valid,
                                 self.numout)
        base = cid * self.ntr
        dms = [inf_float(t["lodm"] + ((base + i) % t["dms_per_method"])
                         * t["dmstep"]) for i in range(self.ntr)]
        names, infos = [], []
        for i, dm in enumerate(dms):
            name = os.path.join(self.workdir, "c%05d_DM%.2f" % (cid, dm))
            info = InfoData(name=name, N=self.numout, dt=self.dt, dm=dm,
                            telescope="GBT",
                            num_chan=int(self.config["nchan"]),
                            mjd_i=60000)
            set_onoff(info, self.valid, self.numout)
            names.append(name)
            infos.append(info)
        self.names[cid] = names
        self._arm(cid, names, p)
        block = fusion.SeamBlock(names=names, infos=infos, dms=dms,
                                 series_dev=series, series_host=None,
                                 valid=self.valid, numout=self.numout,
                                 dt=self.dt)
        seam = fusion.StageSeam(self.workdir, durable=False, obs=self.obs)
        seam.add_block(block)
        with self.spans("fft_search"):
            survey._seam_fft_search(seam, self.cfg, self.passes, None,
                                    self.obs, zap=True)

    def _arm(self, cid: int, names, p) -> None:
        """Mark this chunk's sampled trials for the taps."""
        if cid < int(self.traffic["warmup_chunks"]):
            return
        k = int(self.traffic["check"]["trials_per_chunk"])
        g = synth.rng(self.seed, 13, cid)
        for i in sorted(g.choice(self.ntr, size=min(k, self.ntr),
                                 replace=False).tolist()):
            self.taps.want[names[i]] = {"chunk": cid, "row": i}
        self.params[cid] = p

    # -- the measured window ----------------------------------------------

    def _cells_built(self) -> float:
        if self.obs is None:
            return 0.0
        fam = self.obs.metrics.snapshot().get(
            "accel_jerk_cells_built_total") or {}
        return float(sum(s["value"] for s in fam.get("series", [])))

    def window(self, seconds: float) -> dict:
        nmax = int(self.traffic.get("chunks", 0)) if self.rehearse else 0
        built0 = self._cells_built()
        t0 = time.perf_counter()
        t0_ns = time.perf_counter_ns()
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
        out = {"elapsed_s": elapsed, "trials": trials,
               "search_rate": trials / elapsed, "attempted": trials,
               "jerk_polish_s": sum(
                   (t1 - s0) / 1e9 for n, s0, t1 in self.spans.records
                   if n == "jerk_polish" and s0 >= t0_ns)}
        built = self._cells_built() - built0
        if built > 0:
            out["jerk_cells_built"] = built
        return out

    def close(self) -> None:
        self.taps.remove()
        if getattr(self, "workdir", None):
            shutil.rmtree(self.workdir, ignore_errors=True)
            self.workdir = None

    # -- per-layer counts ---------------------------------------------------

    def required(self) -> dict:
        """Required work of one trial's band volume, and the band's
        fundamental plane cells."""
        z, nh, _sg, _flo, wmax = self.config["jerk_pass"]
        nbins = self.band[1] - self.band[0]
        return {"jerk_volume": counts_jerk.jerk_volume(nbins, z, wmax, nh,
                                                       1),
                "band_cells": counts_jerk.band_cells(nbins, z, wmax)}

    # -- correctness ----------------------------------------------------------

    def release(self) -> None:
        """Nothing of the program's stays on the device between chunks."""

    def missing(self) -> int:
        """Window trials whose ACCEL files never came."""
        z, _nh, _sg, _flo, wmax = self.config["jerk_pass"]
        tag = "_ACCEL_%d_JERK_%d" % (z, wmax)
        return sum(1 for cid in self.chunks for name in self.names[cid]
                   if not (os.path.exists(name + tag)
                           and os.path.exists(name + tag + ".cand")))

    def check(self, control: bool = False):
        """(checks [(name, value, limit)], failed, control readings)."""
        chk = self.traffic["check"]
        lim = chk["limits"]
        birds = search_ref.read_birds(os.path.join(ROOT,
                                                   self.config["zaplist"]))
        failed = self.missing()
        n = self.numout & ~1
        names = ("spec_gap", "jscan_gap", "jcand_gap")
        gaps = {k: [0.0] for k in names}
        ctl = {k: [0.0] for k in names}
        armed = sorted(nm for nm, r in self.taps.want.items()
                       if r["chunk"] in self.chunks)
        g = synth.rng(self.seed, 14)
        keep = sorted(g.choice(len(armed), size=min(len(armed),
                                                    chk["max_trials"]),
                               replace=False).tolist())
        series_of = {}
        for name in [armed[i] for i in keep]:
            rec = self.taps.want[name]
            cid, row = rec["chunk"], rec["row"]
            if "raw" not in rec:
                eprint("no outputs recorded for %s" % name)
                failed += 1
                gaps["spec_gap"].append(float("inf"))
                continue
            if cid not in series_of:
                s = jerk_series(self.traffic, self.seed, cid,
                                self.params[cid], self.valid, self.numout)
                series_of = {cid: np.asarray(s)}
            x = series_of[cid][row].astype(np.float64)
            X = search_ref.packed_rfft(x[:n])
            Z, mask = search_ref.zap(X, birds, self.T, self.numout)
            gaps["spec_gap"].append(search_ref.spectrum_gap(
                rec["amps"], Z, mask))
            if control:
                Xl = search_ref.packed_rfft(x[:n], lowp=True)
                Zl, _m = search_ref.zap(Xl, birds, self.T, self.numout)
                ctl["spec_gap"].append(search_ref.spectrum_gap(Zl, Z,
                                                               mask))
            g = synth.rng(self.seed, 15, cid, row)
            vol = jerk_ref.Volume(Z, rec["geom"])
            self._scan_gaps(rec, vol, g, chk["max_cands"], gaps, ctl,
                            control)
            seeds, outs = rec["polish"]
            order = sorted(range(len(outs)), key=lambda i: -outs[i][3])
            top = order[:8]
            rest = order[8:]
            more = g.choice(len(rest), size=min(len(rest),
                                                chk["max_cands"] - 8),
                            replace=False).tolist() if rest else []
            pick = top + [rest[j] for j in more]
            if pick:
                ref = jerk_ref.polished_powers(Z, seeds, outs, pick)
                prog = np.array([outs[i][3] for i in pick])
                gaps["jcand_gap"].append(float(np.max(np.abs(prog - ref)
                                                      / ref)))
                if control:
                    low = jerk_ref.polished_powers(Z, seeds, outs, pick,
                                                   lowp=True)
                    ctl["jcand_gap"].append(float(np.max(
                        np.abs(low - ref) / ref)))
            failed += self._missed_pulsars(rec["raw"], vol,
                                           self.params[cid], row,
                                           chk["recall"], name)
        checks = [(k, max(v), lim[k]) for k, v in gaps.items()]
        failed += sum(1 for _k, v, l in checks if not v <= l)
        return checks, failed, ({k: max(v) for k, v in ctl.items()}
                                if control else None)

    @staticmethod
    def _scan_gaps(rec, vol, g, kmax, gaps, ctl, control) -> None:
        """Widest relative gap of a raw candidate's summed power (the
        banded volume's build and harmonic sum) from the reference's at
        its (r, z, w, numharm): the strongest 8 and a seeded draw."""
        raw = rec["raw"]
        if not raw:
            return
        order = sorted(range(len(raw)), key=lambda i: -raw[i][4])
        rest = order[8:]
        more = g.choice(len(rest), size=min(len(rest), kmax - 8),
                        replace=False).tolist() if rest else []
        pick = order[:8] + [rest[j] for j in more]
        ref = np.array([vol.summed(*raw[i][:4]) for i in pick])
        prog = np.array([raw[i][4] for i in pick])
        gaps["jscan_gap"].append(float(np.max(np.abs(prog - ref) / ref)))
        if control:
            low = jerk_ref.Volume(vol.X, rec["geom"], lowp=True)
            lp = np.array([low.summed(*raw[i][:4]) for i in pick])
            ctl["jscan_gap"].append(float(np.max(np.abs(lp - ref) / ref)))

    def _missed_pulsars(self, raw, vol, p, row, tol, name) -> int:
        """Injected pulsars not found: none of the raw candidates with
        all their harmonics summed lies within the tolerances of the
        pulsar's fundamental (r, z, w), or the strongest such candidate
        is not the volume's local peak — a reference cell within
        +-peak_steps w planes, one z step and one column of it (inside
        the grid and the band) sums more than (1 + peak_tol) times its
        power, as when the program misses a w plane."""
        zmax, _nh, _sg, _flo, wmax = self.config["jerk_pass"]
        miss = 0
        for j in range(p["r_mid"].shape[1]):
            r0, z0 = p["r_mid"][row, j], p["z_mid"][row, j]
            w0, nh = float(p["w"][row, j]), int(p["nharm"][j])
            near = [c for c in raw if c[3] == nh
                    and abs(c[0] - r0) <= tol["r_tol"]
                    and abs(c[1] - z0) <= tol["z_tol"]
                    and abs(c[2] - w0) <= tol["w_tol"]]
            why = None
            if not near:
                why = "no candidate"
            else:
                r, z, w, _h, pw = max(near, key=lambda c: c[4])
                k = int(tol["peak_steps"])
                peak = 0.0
                for dc in (-1, 0, 1):
                    col = round(2 * r * nh) + dc
                    if not 2 * self.band[0] <= col < 2 * self.band[1]:
                        continue
                    for dz in (-2, 0, 2):
                        for dw in range(-k, k + 1):
                            zz = round(z * nh) + dz
                            ww = round(w * nh) + 20 * dw
                            if abs(zz) <= zmax and abs(ww) <= wmax:
                                peak = max(peak, vol.summed(
                                    col / (2.0 * nh), zz / nh, ww / nh,
                                    nh))
                if peak > pw * (1.0 + tol["peak_tol"]):
                    why = ("power %.4f under the local peak %.4f"
                           % (pw, peak))
            if why:
                eprint("missed pulsar %d of %s (r %.2f z %.2f w %.2f): %s"
                       % (j, name, r0, z0, w0, why))
                miss += 1
        return miss
