#!/usr/bin/env python
"""chaos_survey: randomized kill/corruption schedules over a tiny
synthetic survey, asserting resume equivalence (ISSUE 2 CI tool).

Each trial draws a random kill schedule (seeded, reproducible): the
survey is killed at a random instrumented point 1-3 times, optionally
with a random artifact corruption (truncate/bitflip/delete) between
crashes, then resumed to completion.  The final artifacts must be
byte-identical to a reference run that was never interrupted.

Usage:
    python tools/chaos_survey.py [--trials 5] [--seed 0]
        [--workdir DIR] [--keep] [--nspec 8192] [--nchan 16]

Exit status 0 iff every trial converged to the reference artifacts —
usable in CI as a slow job:
    python tools/chaos_survey.py --trials 10 --seed $BUILD_NUMBER
"""

from __future__ import annotations

import argparse
import glob
import os
import random
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

KILL_POINTS = ["pre-rfifind", "post-rfifind", "prepsubband-method",
               "post-prepsubband", "fused-chunk", "pre-sift",
               "post-sift", "fold-cand", "pre-singlepulse"]

COMPARABLE = (".dat", ".fft", ".cand", ".singlepulse", ".mask",
              ".stats", ".txt")


def _artifacts(workdir):
    out = {}
    for p in sorted(glob.glob(os.path.join(workdir, "*"))):
        name = os.path.basename(p)
        comparable = ((name.endswith(COMPARABLE)
                       or "_ACCEL_" in name)
                      and not name.endswith(".inf"))
        if os.path.isfile(p) and comparable:
            with open(p, "rb") as f:
                out[name] = f.read()
    return out


def _make_obs(root, nspec, nchan):
    from presto_tpu.models.synth import FakeSignal, \
        fake_filterbank_file
    raw = os.path.join(root, "psr.fil")
    sig = FakeSignal(f=17.0, dm=10.0, shape="gauss", width=0.08,
                     amp=0.8)
    fake_filterbank_file(raw, nspec, 2e-4, nchan, 400.0, 1.0, sig,
                         noise_sigma=2.0, nbits=8)
    return raw


def _cfg(provider, fault_injector=None):
    from presto_tpu.pipeline.survey import SurveyConfig
    return SurveyConfig(lodm=5.0, hidm=12.0, nsub=16, zmax=0,
                        numharm=2, sigma=3.0, fold_top=0,
                        rfi_time=0.4, singlepulse=True,
                        plan_provider=provider,
                        fault_injector=fault_injector)


def _corrupt_random_artifact(workdir, rng):
    """Truncate, bitflip, or delete one completed artifact."""
    from presto_tpu.testing import chaos
    victims = [p for n, p in
               ((os.path.basename(p), p) for p in
                glob.glob(os.path.join(workdir, "*")))
               if n.endswith((".dat", ".fft")) or "_ACCEL_" in n]
    if not victims:
        return None
    victim = rng.choice(sorted(victims))
    op = rng.choice(["truncate", "bitflip", "delete"])
    if op == "truncate":
        chaos.truncate_file(victim, keep_frac=rng.uniform(0.1, 0.9))
    elif op == "bitflip":
        chaos.bitflip_file(victim, nflips=rng.randrange(1, 5),
                           seed=rng.randrange(1 << 30))
    else:
        os.remove(victim)
    return "%s %s" % (op, os.path.basename(victim))


def run_trial(trial, rng, raw, provider, ref_arts, root):
    from presto_tpu.pipeline.survey import run_survey
    from presto_tpu.testing import chaos
    work = os.path.join(root, "trial%02d" % trial)
    os.makedirs(work, exist_ok=True)
    nkills = rng.randrange(1, 4)
    schedule = []
    for k in range(nkills):
        kill_at = rng.choice(KILL_POINTS)
        kill_after = rng.randrange(1, 3)
        schedule.append("%s#%d" % (kill_at, kill_after))
        fi = chaos.FaultInjector(kill_at=kill_at,
                                 kill_after=kill_after)
        try:
            run_survey([raw], _cfg(provider, fi), workdir=work)
        except chaos.SimulatedCrash as e:
            if rng.random() < 0.5:
                note = _corrupt_random_artifact(work, rng)
                if note:
                    schedule.append("corrupt:" + note)
        # injector that never matched its point: run completed; later
        # kills in the schedule then exercise the no-op resume path
    run_survey([raw], _cfg(provider), workdir=work)
    got = _artifacts(work)
    ok = got == ref_arts
    detail = ""
    if not ok:
        only_got = sorted(set(got) - set(ref_arts))
        only_ref = sorted(set(ref_arts) - set(got))
        differ = [n for n in ref_arts
                  if n in got and got[n] != ref_arts[n]]
        detail = " only-in-trial=%s only-in-ref=%s differ=%s" % (
            only_got[:5], only_ref[:5], differ[:5])
    print("trial %02d [%s]: %s%s"
          % (trial, " -> ".join(schedule),
             "PASS" if ok else "FAIL", detail))
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="chaos_survey",
        description="randomized kill/corruption schedules over a "
                    "tiny survey; asserts resume equivalence")
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--nspec", type=int, default=1 << 13)
    p.add_argument("--nchan", type=int, default=16)
    p.add_argument("--workdir", type=str, default=None,
                   help="Scratch root (default: a fresh temp dir)")
    p.add_argument("--keep", action="store_true",
                   help="Keep the scratch tree for inspection")
    args = p.parse_args(argv)

    root = args.workdir or tempfile.mkdtemp(prefix="chaos_survey_")
    os.makedirs(root, exist_ok=True)
    rng = random.Random(args.seed)
    print("chaos_survey: scratch=%s seed=%d trials=%d"
          % (root, args.seed, args.trials))

    from presto_tpu.pipeline.survey import run_survey
    from presto_tpu.serve.plancache import PlanCache, SearcherProvider
    provider = SearcherProvider(PlanCache(capacity=8))

    raw = _make_obs(root, args.nspec, args.nchan)
    refdir = os.path.join(root, "reference")
    run_survey([raw], _cfg(provider), workdir=refdir)
    ref_arts = _artifacts(refdir)
    print("reference run: %d comparable artifacts" % len(ref_arts))

    failures = 0
    for trial in range(args.trials):
        if not run_trial(trial, rng, raw, provider, ref_arts, root):
            failures += 1
    if not args.keep and args.workdir is None:
        shutil.rmtree(root, ignore_errors=True)
    print("chaos_survey: %d/%d trials passed"
          % (args.trials - failures, args.trials))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
