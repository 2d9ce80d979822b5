"""The jerk cell (ter5.jerk) and the GBNCC search cell (gbncc.search):
CPU rehearsals, the required-work counts of a band's volume, and the
faults that must make ``correct`` false."""

import dataclasses
import math

import pytest

from perfbench import counts_jerk
from perfbench.tests.helpers import rehearse


@pytest.mark.parametrize("workload", ["ter5.jerk", "gbncc.search"])
def test_rehearsal_prints_a_correct_line_without_metrics(workload):
    rc, lines = rehearse(workload)
    assert rc == 0
    res = lines[-1]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {}          # a CPU run prints no metric
    assert res["device"]["platform"] == "cpu"
    want = ({"spec_gap", "jscan_gap", "jcand_gap"} if workload == "ter5.jerk"
            else {"spec_gap", "scan_gap", "cand_gap", "sp_gap"})
    assert set(res["checks"]) == want


def test_counts_of_a_toy_band():
    # zmax 4, wmax 20: numz 5, numw 3; fftlen for the widest kernel
    hw = counts_jerk._w_halfwidth(4, 20)
    n = counts_jerk.fftlen(4, 20)
    assert hw == math.ceil(2 + 20 / 12) + 16
    assert n == counts_jerk.next2_to_n(7470 + 2 + 4 * hw) == 8192
    assert counts_jerk.band_cells(1000, 4, 20) == 5 * 3 * 2 * 1000
    req = counts_jerk.jerk_volume(1000, 4, 20, 2, 3)
    nblocks = 1                                   # 2000 half bins < 7470
    corr = 5 * 3 * nblocks * (6 * n + 5 * n * 13 + 3 * 7470)
    harm = 5 * 3 * 2000 * (1 + 2)
    assert req["flops"] == pytest.approx(3 * (corr + harm))
    assert req["bytes"] == 3 * 8 * 1000
    # the cell: band 5 of 16 of a 2^23-sample series, 201 z x 31 w
    assert counts_jerk.band_cells(1 << 18, 200, 300) == 201 * 31 * (1 << 19)


def _altered_polish(mp):
    """The strongest jerk-polished candidate's power raised by 1%."""
    import presto_tpu.search.polish as pol
    real = pol.optimize_jerk_cands

    def altered(*a, **kw):
        out = real(*a, **kw)
        if out:
            i = max(range(len(out)), key=lambda k: out[k].power)
            out[i] = dataclasses.replace(out[i], power=out[i].power * 1.01)
        return out
    mp.setattr(pol, "optimize_jerk_cands", altered)


def _altered_scan(mp):
    """Every raw candidate's harmonic sum raised by 1%."""
    from presto_tpu.search import jerk
    real = jerk.JerkVolume.search_many

    def altered(self, *a, **kw):
        out = real(self, *a, **kw)
        for cands in out:
            for c in cands:
                c.power *= 1.01
        return out
    mp.setattr(jerk.JerkVolume, "search_many", altered)


def _dropped_w_plane(mp):
    """One w plane of the volume lost: for each trial, the plane of its
    strongest fully summed candidate never reaches the collection."""
    from presto_tpu.search import jerk
    real_many, real_collect = (jerk.JerkVolume.search_many,
                               jerk.JerkVolume._collect)

    def search_many(self, batch, obs=None):
        out = []
        for i in range(int(batch.shape[0])):
            cands = real_many(self, batch[i:i + 1], obs)[0]
            top = max((c for c in cands
                       if c.numharm == self.cfg.numharm),
                      key=lambda c: c.power, default=None)
            if top is None:
                out.append(cands)
                continue
            lost = top.w * top.numharm

            def collect(self_, p0, w, comp, args):
                return [] if w == lost else real_collect(self_, p0, w,
                                                         comp, args)
            mp.setattr(jerk.JerkVolume, "_collect", collect)
            try:
                out += real_many(self, batch[i:i + 1], obs)
            finally:
                mp.setattr(jerk.JerkVolume, "_collect", real_collect)
        return out
    mp.setattr(jerk.JerkVolume, "search_many", search_many)


FAULTS = {
    "altered_polish": _altered_polish,
    "altered_scan": _altered_scan,
    "dropped_w_plane": _dropped_w_plane,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_jerk_run_incorrect(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    rc, lines = rehearse("ter5.jerk", 3000000023)
    assert rc == 0
    assert lines[-1]["correct"] is False
