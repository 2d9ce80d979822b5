"""The jerk search on the survey's seam path, banded, against the plain
float64 reference (presto_tpu/testing/jerk_ref.py).

Seeded noise spectra at 2^16 samples with one injected binary pulsar
(narrow pulse, so its harmonics carry power) of fundamental mean fdot
z1 and jerk w1 (harmonic k at (k r1, k z1, k w1)); zmax 20, wmax 40.
"""

import glob
import os

import numpy as np
import pytest

N, DT = 1 << 16, 5e-4
T = N * DT
F0, Z1, W1 = 37.3, 1.0, 2.5          # fundamental: Hz, mean z, w
SUMMED_TOL = 1e-4                     # raw harmonic sums (read 3e-7)
POLISHED_TOL = 1e-3                   # polished (r, z, w) powers


def _series(seed: int, amp: float = 0.25) -> np.ndarray:
    rng = np.random.default_rng(seed)
    t = np.arange(N) * DT
    fd = (Z1 - W1 / 2) / T ** 2       # t = 0 fdot whose mean is Z1
    fdd = W1 / T ** 3
    phi = F0 * t + fd * t * t / 2 + fdd * t ** 3 / 6
    prof = np.exp(-0.5 * (((phi + 0.5) % 1.0) - 0.5) ** 2 / 0.04 ** 2)
    return (amp * prof + rng.normal(0, 1, N)).astype(np.float32)


def _r1() -> float:
    """The pulsar's fundamental mean frequency x T (Fourier bins)."""
    fd = (Z1 - W1 / 2) / T ** 2
    return (F0 + fd * T / 2 + (W1 / T ** 3) * T * T / 6) * T


def _seam_run(tmp_path, passes, seeds, monkeypatch=None, record=None):
    """The survey's seam consumer over one block of trials; returns the
    trial base names."""
    import jax.numpy as jnp
    from presto_tpu.apps.common import set_onoff
    from presto_tpu.io.infodata import InfoData
    from presto_tpu.pipeline import fusion, survey

    xs = np.stack([_series(s) for s in seeds])
    names, infos, dms = [], [], []
    for i in range(len(seeds)):
        name = str(tmp_path / ("t_DM%.2f" % (10.0 + i)))
        info = InfoData(name=name, N=N, dt=DT, dm=10.0 + i,
                        telescope="GBT", num_chan=64, mjd_i=60000)
        set_onoff(info, N, N)
        names.append(name)
        infos.append(info)
        dms.append(10.0 + i)
    if record is not None:
        _record(monkeypatch, record)
    block = fusion.SeamBlock(names=names, infos=infos, dms=dms,
                             series_dev=jnp.asarray(xs), series_host=None,
                             valid=N, numout=N, dt=DT)
    seam = fusion.StageSeam(str(tmp_path), durable=False)
    seam.add_block(block)
    cfg = survey.SurveyConfig(durable_stages=False, singlepulse=False)
    survey._seam_fft_search(seam, cfg, passes, None, None)
    return names


def _record(mp, rec):
    """Keep each trial's raw candidates, searcher and jerk polish."""
    import presto_tpu.apps.accelsearch as acc
    import presto_tpu.search.polish as pol
    rw0, oj0 = acc.refine_and_write, pol.optimize_jerk_cands

    def refine_and_write(raw, amps, T_, searcher, base, zmax, *a, **kw):
        rec[base] = {"raw": [(c.r, c.z, c.w, c.numharm, c.power)
                             for c in raw],
                     "amps": amps, "searcher": searcher}
        rec["_cur"] = base
        return rw0(raw, amps, T_, searcher, base, zmax, *a, **kw)

    def optimize_jerk_cands(amps, cands, *a, **kw):
        out = oj0(amps, cands, *a, **kw)
        rec[rec["_cur"]]["polish"] = (
            [(c.r, c.z, c.w, c.numharm) for c in cands],
            [(o.r, o.z, o.w, o.power) for o in out])
        return out
    mp.setattr(acc, "refine_and_write", refine_and_write)
    mp.setattr(pol, "optimize_jerk_cands", optimize_jerk_cands)


@pytest.mark.parametrize("numharm", [4, 8])
def test_banded_jerk_pass_matches_reference(tmp_path, monkeypatch,
                                            numharm):
    from presto_tpu.search import jerk
    from presto_tpu.testing import jerk_ref as jr

    rec = {}
    fhi = 400.0
    names = _seam_run(tmp_path, [(20, numharm, 3.0, 100.0, 40, fhi)],
                      [11, 12], monkeypatch, rec)
    for name in names:
        assert os.path.exists(name + "_ACCEL_20_JERK_40")
        assert os.path.exists(name + "_ACCEL_20_JERK_40.cand")
        r = rec[name]
        s = r["searcher"]
        assert s.cfg.wmax == 40 and s.rhi == pytest.approx(fhi * T)
        v = jerk.volume(s)
        X = r["amps"].astype(np.complex128)
        ref = jr.Volume(X, (s.cfg.uselen, v.hw, v.numdata, s.kern.kmax))
        raw = sorted(r["raw"], key=lambda c: -c[4])
        assert raw
        # the band: every plane column inside [100 Hz, 400 Hz) x T
        for rr, _z, _w, nh, _p in raw:
            assert 100.0 * T <= rr * nh < fhi * T
        for rr, z, w, nh, p in raw[:10]:
            want = ref.summed(rr, z, w, nh)
            assert abs(p - want) <= SUMMED_TOL * want, (rr, z, w, nh)
        # the pulsar, at its (r, z, w) in fundamental units
        hit = [c for c in raw if c[3] == numharm
               and abs(c[0] - _r1()) <= 1.0 and abs(c[1] - Z1) <= 1.0
               and abs(c[2] - W1) <= 5.0]
        assert hit, "injected pulsar not among the raw candidates"
        seeds, outs = r["polish"]
        pick = sorted(range(len(outs)), key=lambda i: -outs[i][3])[:6]
        want = jr.polished_powers(X, seeds, outs, pick)
        got = np.array([outs[i][3] for i in pick])
        np.testing.assert_allclose(got, want, rtol=POLISHED_TOL)


def test_band_union_equals_full_band(monkeypatch):
    """Four bands' candidates, above the full search's power cuts, are
    the full-band search's candidates, power for power; the full band
    runs in pieces narrower than a band, so a piece edge and a band
    edge fall in different places."""
    import jax.numpy as jnp
    from presto_tpu.ops import fftpack
    from presto_tpu.search import jerk
    from presto_tpu.search.accel import AccelConfig, AccelSearch

    monkeypatch.setattr(jerk, "PIECE_COLS", 3 * jerk.ALIGN)
    x = _series(21, amp=0.3)
    pairs = np.asarray(fftpack.realfft_packed_pairs(
        jnp.asarray(x - x.mean())))
    nb = pairs.shape[0]

    def run(rlo, rhi):
        s = AccelSearch(AccelConfig(zmax=20, wmax=40, numharm=4,
                                    sigma=2.5, rlo=rlo, rhi=rhi),
                        T=T, numbins=nb)
        return s, s.search(pairs)

    full, cands = run(2000.0, nb - 1)
    edges = [2000.0, 5000.0, 9000.0, 14000.0, nb - 1]
    union = []
    for lo, hi in zip(edges[:-1], edges[1:]):
        _s, cs = run(lo, hi)
        union += [c for c in cs
                  if c.power > full.powcut[int(np.log2(c.numharm))]]

    def key(cs):
        return sorted((c.numharm, c.r, c.z, c.w, c.power) for c in cs)
    assert len(cands) > 20
    assert key(union) == key(cands)


@pytest.mark.parametrize("recipe", ["palfa", "gbncc"])
def test_plain_recipe_passes_unchanged_by_five_field_form(tmp_path,
                                                          recipe):
    """A recipe's 4-tuple passes and the same passes written with the
    jerk pass's fifth field (wmax 0) write the same ACCEL files, byte
    for byte, under the same names."""
    from presto_tpu.pipeline.recipes import get_recipe
    passes = get_recipe(recipe).accel_passes
    assert all(len(p) == 4 for p in passes)
    out = {}
    for form, ps in (("four", passes),
                     ("five", tuple(tuple(p) + (0,) for p in passes))):
        d = tmp_path / form
        d.mkdir()
        _seam_run(d, ps, [31, 32])
        out[form] = {os.path.basename(f): open(f, "rb").read()
                     for f in glob.glob(str(d / "*_ACCEL_*"))}
    assert sorted(out["four"]) == sorted(
        "t_DM%.2f_ACCEL_%d%s" % (10.0 + i, p[0], ext)
        for i in range(2) for p in passes for ext in ("", ".cand"))
    assert out["four"] == out["five"]
