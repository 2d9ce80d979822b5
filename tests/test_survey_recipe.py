"""Survey recipe acceptance (VERDICT r2 item 7): one command runs the
PALFA-style policy end-to-end on a scaled synthetic observation —
both accel passes searched, sifting at the recipe thresholds, folds
selected by fold_sigma, single-pulse stage run, zaplist applied."""

import glob
import os

import pytest

from presto_tpu.models.synth import FakeSignal, fake_filterbank_file


@pytest.mark.slow
def test_palfa_recipe_one_command(tmp_path):
    d = str(tmp_path)
    path = os.path.join(d, "obs.fil")
    sig = FakeSignal(f=9.2, dm=45.0, shape="gauss", width=0.05,
                     amp=1.2)
    fake_filterbank_file(path, N=1 << 15, dt=5e-4, nchan=32,
                         lofreq=1350.0, chanwidth=3.0, signal=sig,
                         noise_sigma=3.0, nbits=8)
    from presto_tpu.apps.pipeline import main as pipeline_main
    rc = pipeline_main(["--recipe", "palfa", "-lodm", "30",
                        "-hidm", "60", "-nsub", "16",
                        "-workdir", d, path])
    assert rc == 0
    # both recipe passes produced ACCEL files for every DM trial
    a0 = glob.glob(os.path.join(d, "obs_DM*_ACCEL_0"))
    a50 = glob.glob(os.path.join(d, "obs_DM*_ACCEL_50"))
    assert a0 and a50 and len(a0) == len(a50)
    # sifted candidate list exists and recovers the injection
    from presto_tpu.pipeline.sifting import read_candidates
    assert os.path.exists(os.path.join(d, "cands_sifted.txt"))
    folded = glob.glob(os.path.join(d, "fold_cand*.pfd"))
    assert folded, "recipe folded no candidates"
    from presto_tpu.io.pfd import read_pfd
    ps = [read_pfd(f).fold_p1 for f in folded]
    assert any(abs(f / 9.2 - round(f / 9.2)) < 1e-2 for f in ps), ps
    # single-pulse stage ran over the DM fan-out
    assert glob.glob(os.path.join(d, "obs_DM*.singlepulse"))


def test_recipe_expansion():
    """Recipe -> SurveyConfig policy mapping (fast check)."""
    from presto_tpu.pipeline.recipes import get_recipe, RECIPES
    assert set(RECIPES) == {"palfa", "gbncc", "gbt350drift", "ter5"}
    ter5 = get_recipe("ter5").to_config(230.0, 246.0)
    assert ter5.all_passes == ((0, 16, 2.0, 2.0), (200, 8, 3.0, 1.0, 300))
    drift = get_recipe("gbt350drift").to_config(0.0, 90.0)
    # per-pass flo: lo_accel_flo=2.0 / hi_accel_flo=1.0
    # (GBT350_drift_search.py:30-33)
    assert drift.all_passes == ((0, 16, 2.0, 2.0), (50, 8, 3.0, 1.0))
    assert drift.rfi_time == pytest.approx(25600 * 0.00008192)
    # per-pass fold budget: 20 lo + 10 hi (GBT350_drift_search.py:21-22,
    # GBNCC_search.py:21-22)
    assert drift.max_folds_per_pass == (20, 10)
    assert drift.max_folds == 30
    gbncc = get_recipe("gbncc").to_config(0.0, 90.0)
    assert gbncc.max_folds_per_pass == (20, 10)
    cfg = get_recipe("palfa").to_config(10.0, 50.0)
    assert (cfg.zmax, cfg.numharm, cfg.sigma, cfg.flo) == \
        (0, 16, 2.0, 2.0)
    assert cfg.accel_passes == ((50, 8, 3.0, 1.0),)
    assert cfg.all_passes == ((0, 16, 2.0, 2.0), (50, 8, 3.0, 1.0))
    assert cfg.sift_policy.sigma_threshold == 5.0
    # PALFA keeps the single combined cap (PALFA_presto_search.py:33)
    assert cfg.fold_sigma == 6.0 and cfg.max_folds == 150
    assert cfg.max_folds_per_pass is None
    assert cfg.sp_maxwidth == 0.1
    assert cfg.zaplist and os.path.exists(cfg.zaplist)
    with pytest.raises(ValueError):
        get_recipe("nope")
