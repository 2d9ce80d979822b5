"""Drive a whole run with the timed path broken underneath (no look
for a chip, rehearsal size) and see ``correct`` come out false: once
for each fault a one-chip cell can have.  The exchange between chips
does not exist in either cell (one chip each)."""

import dataclasses
import itertools

import pytest

from perfbench.tests.helpers import rehearse


def _stale_rfft(mp):
    """A step that returns its state unchanged: the rFFT hands every
    chunk the spectra of the first one."""
    from presto_tpu.pipeline import fusion
    real = fusion.fused_rfft_batch
    held = []

    def stale(series, *a, **kw):
        if not held:
            held.append(real(series, *a, **kw))
        return held[0]
    mp.setattr(fusion, "fused_rfft_batch", stale)


def _half_searched(mp):
    """Half of the batch left out: every other trial's candidates are
    never refined or written."""
    import presto_tpu.apps.accelsearch as acc
    real = acc.refine_and_write
    n = itertools.count()

    def half(raw, amps, T, searcher, base, zmax, *a, **kw):
        if next(n) % 4 >= 2:          # both passes of every other trial
            return [], base
        return real(raw, amps, T, searcher, base, zmax, *a, **kw)
    mp.setattr(acc, "refine_and_write", half)


def _altered_power(mp):
    """An answer altered where it is produced: the strongest polished
    candidate's power is raised by 1%."""
    import presto_tpu.search.polish as pol
    real = pol.optimize_accelcands

    def altered(*a, **kw):
        out = real(*a, **kw)
        i = max(range(len(out)), key=lambda k: out[k].power)
        out[i] = dataclasses.replace(out[i], power=out[i].power * 1.01)
        return out
    mp.setattr(pol, "optimize_accelcands", altered)


def _altered_scan(mp):
    """An answer altered where it is produced: the harmonic sum of the
    strongest raw candidate of each trial is raised by 1%."""
    from presto_tpu.search.accel import AccelSearch
    real = AccelSearch.search_many

    def altered(self, *a, **kw):
        out = real(self, *a, **kw)
        for cands in out:
            if cands:
                top = max(cands, key=lambda c: c.power)
                top.power *= 1.01
        return out
    mp.setattr(AccelSearch, "search_many", altered)


def _block_step(mp, fault):
    from presto_tpu.ops import dedispersion as dd
    real = dd.make_block_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(prev_raw, cur, prev_sub):
            sub, series = step(prev_raw, cur, prev_sub)
            if fault == "stale":
                return prev_sub, series      # state returned unchanged
            if fault == "half":
                half = series.shape[0] // 2
                return sub, series.at[half:].set(0.0)
            return sub, series.at[:, 17].add(50.0)
        return broken
    mp.setattr(dd, "make_block_step", make)


FAULTS = {
    ("palfa.search", "stale_state"): _stale_rfft,
    ("palfa.search", "half_batch"): _half_searched,
    ("palfa.search", "altered_answer"): _altered_power,
    ("palfa.search", "altered_scan"): _altered_scan,
    ("gbncc.dedisp", "stale_state"): lambda mp: _block_step(mp, "stale"),
    ("gbncc.dedisp", "half_batch"): lambda mp: _block_step(mp, "half"),
    ("gbncc.dedisp", "altered_answer"): lambda mp: _block_step(mp,
                                                               "altered"),
}


@pytest.mark.parametrize("workload,fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(monkeypatch, workload, fault):
    FAULTS[(workload, fault)](monkeypatch)
    rc, lines = rehearse(workload, 3000000023)
    assert rc == 0
    assert lines[-1]["correct"] is False
