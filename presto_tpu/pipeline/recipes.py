"""Survey recipes: complete, named end-to-end search policies.

The reference ships three battle-tested survey orchestrations
(bin/PALFA_presto_search.py, GBNCC_search.py, GBT350_drift_search.py)
whose value is the POLICY they encode — interval lengths, the lo/hi
acceleration-pass pair, sifting thresholds, fold selection, the
single-pulse settings, zaplist handling.  A recipe captures that
policy as data and expands to a ready SurveyConfig, so

    presto-pipeline --recipe palfa obs.fits

reproduces the PALFA flow end to end (and the policies are testable
on synthetic data, tests/test_survey_recipe.py).

Recipe values are taken from the reference drivers:
PALFA_presto_search.py:28-52, GBNCC_search.py:16-35,
GBT350_drift_search.py:16-35.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

from presto_tpu.pipeline.sifting import SiftPolicy
from presto_tpu.pipeline.survey import SurveyConfig


@dataclass(frozen=True)
class SurveyRecipe:
    name: str
    rfi_time: float                       # rfifind interval (s)
    # ((zmax, numharm, sigma, flo), ...): first is the primary pass;
    # flo is the per-pass low-frequency search limit in Hz
    # (lo_accel_flo=2.0 / hi_accel_flo=1.0, PALFA_presto_search.py:39-43).
    # A jerk pass appends accelsearch's -wmax (and optionally -fhi):
    # (zmax, numharm, sigma, flo, wmax[, fhi])
    accel_passes: Tuple[tuple, ...]
    sift: SiftPolicy
    fold_sigma: float                     # to_prepfold_sigma
    max_folds: int                        # max_cands_to_fold (combined)
    sp_threshold: float
    sp_maxwidth: float
    use_default_zaplist: bool = True
    nsub: int = 32
    # per-pass fold caps aligned with accel_passes, e.g. GBNCC's
    # 20-lo + 10-hi split (GBNCC_search.py:21-22); None -> one
    # combined max_folds cap (PALFA_presto_search.py:33)
    fold_caps_per_pass: Optional[Tuple[int, ...]] = None

    def to_config(self, lodm: float, hidm: float,
                  nsub: Optional[int] = None,
                  zaplist: Optional[str] = None) -> SurveyConfig:
        """Expand to a SurveyConfig for one DM range."""
        if zaplist is None and self.use_default_zaplist:
            from presto_tpu.utils.catalog import default_birds_path
            zaplist = default_birds_path()
        (zmax0, nh0, sg0, flo0), *rest = self.accel_passes
        return SurveyConfig(
            lodm=lodm, hidm=hidm, nsub=nsub or self.nsub,
            rfi_time=self.rfi_time,
            zmax=zmax0, numharm=nh0, sigma=sg0, flo=flo0,
            accel_passes=tuple(rest) or None,
            zaplist=zaplist,
            sift_policy=self.sift,
            fold_sigma=self.fold_sigma, max_folds=self.max_folds,
            max_folds_per_pass=self.fold_caps_per_pass,
            sp_threshold=self.sp_threshold,
            sp_maxwidth=self.sp_maxwidth)


# -- the shipped recipes ------------------------------------------------

# PALFA (Arecibo L-band Feed Array; PALFA_presto_search.py:28-52):
# ~2.1 s RFI intervals, a zmax=0/numharm=16 low pass + a zmax=50/
# numharm=8 high pass, sift at to_prepfold_sigma-1, fold everything
# above 6 sigma capped at 150, single-pulse to 0.1 s widths.
PALFA = SurveyRecipe(
    name="palfa",
    rfi_time=2 ** 15 * 0.000064,          # 2.097 s
    accel_passes=((0, 16, 2.0, 2.0), (50, 8, 3.0, 1.0)),
    sift=SiftPolicy(sigma_threshold=5.0, c_pow_threshold=100.0,
                    short_period=0.0005, long_period=15.0,
                    harm_pow_cutoff=8.0, r_err=1.1),
    fold_sigma=6.0, max_folds=150,
    sp_threshold=5.0, sp_maxwidth=0.1,
    nsub=32)

# GBNCC (GBT 350 MHz Northern Celestial Cap; GBNCC_search.py:16-35):
# same lo/hi accel pair and thresholds at GBT 350 MHz sampling, with
# the per-pass fold budget (20 lo-accel + 10 hi-accel,
# GBNCC_search.py:21-22,479-486).
GBNCC = SurveyRecipe(
    name="gbncc",
    rfi_time=25600 * 0.00008192,          # 2.097 s
    accel_passes=((0, 16, 2.0, 2.0), (50, 8, 3.0, 1.0)),
    sift=SiftPolicy(sigma_threshold=5.0, c_pow_threshold=100.0,
                    short_period=0.0005, long_period=15.0,
                    harm_pow_cutoff=8.0, r_err=1.1),
    fold_sigma=6.0, max_folds=30, fold_caps_per_pass=(20, 10),
    sp_threshold=5.0, sp_maxwidth=0.1,
    nsub=32)

# GBT350 drift survey (GBT350_drift_search.py:16-35): GBNCC's policy
# (same lo/hi passes, same 20+10 per-pass fold caps,
# GBT350_drift_search.py:21-22) applied per drift-scan pointing.
# Split a raw drift scan into overlapping pointings first with
# `python -m presto_tpu.apps.drift_prep` (the GBT350_drift_prep.py
# analog) or pass --driftprep to the pipeline app.
GBT350_DRIFT = replace(GBNCC, name="gbt350drift")

# Terzan 5 binary-pulsar search (Andersen & Ransom 2018, ApJL 863, L13:
# PRESTO's Fourier-domain jerk search on GBT S-band observations of the
# cluster): GBNCC's lo pass and sifting, then one jerk pass at zmax 200
# / wmax 300 / 8 harmonics.  For a 30 m/s^2 orbit of Pb = 2 h the 8th
# harmonic of a 300 Hz MSP reaches z ~ 113 and w ~ 68 over a 687 s
# segment (5-15% of the orbit, where the paper finds the jerk search
# pays), so the pass covers the cluster's spider binaries with margin.
TER5 = SurveyRecipe(
    name="ter5",
    rfi_time=2.0,
    accel_passes=((0, 16, 2.0, 2.0), (200, 8, 3.0, 1.0, 300)),
    sift=GBNCC.sift,
    fold_sigma=6.0, max_folds=40,
    sp_threshold=5.0, sp_maxwidth=0.1,
    nsub=32)

RECIPES = {r.name: r for r in (PALFA, GBNCC, GBT350_DRIFT, TER5)}


def get_recipe(name: str) -> SurveyRecipe:
    try:
        return RECIPES[name.lower()]
    except KeyError:
        raise ValueError("unknown survey recipe %r (have: %s)"
                         % (name, ", ".join(sorted(RECIPES))))
