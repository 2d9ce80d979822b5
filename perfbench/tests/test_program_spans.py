"""The program-span reduction, by hand on a made-up trace and on a small
trace recorded on a v5e (testdata/spans.xplane.pb, record_spans.py),
and the span run of one cell rehearsed on the CPU."""

import contextlib
import io
import json
import os
from types import SimpleNamespace as NS

import pytest

from perfbench import program_spans as ps
from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "testdata",
                        "spans.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def fake():
    # device busy 100-170 and 300-400 in a chunk 50-450
    ops = [ev("fusion.1", 100, 70), ev("copy", 300, 100)]
    main = [ev("bench:chunk", 50, 400),
            ev("presto:fused-collect", 90, 350),        # 90-440
            ev("presto:seam:download", 95, 80),         # 95-175
            ev("presto:accel:search", 180, 200),        # 180-380
            ev("presto:accel:collect", 200, 90)]        # 200-290
    # a worker's short spans over every gap (by length they would win)
    worker = [ev("presto:ingest:decode", t, 10) for t in range(0, 460, 10)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=main),
                                    NS(name="python", events=worker)])])


def test_self_time_and_dispatch_thread_gaps_on_a_made_up_trace():
    red = ps.reduce(fake())
    assert red["window_s"] == pytest.approx(400e-9)
    assert red["busy_s"] == pytest.approx(170e-9)
    spans = red["program_spans"]
    assert spans["fused-collect"]["total_s"] == pytest.approx(350e-9)
    # less download (80) and accel:search (200)
    assert spans["fused-collect"]["self_s"] == pytest.approx(70e-9)
    assert spans["accel:search"]["self_s"] == pytest.approx(110e-9)
    assert spans["accel:collect"]["self_s"] == pytest.approx(90e-9)
    # the worker's spans are clipped to the window 50-450
    assert spans["ingest:decode"]["count"] == 40
    assert spans["ingest:decode"]["total_s"] == pytest.approx(400e-9)
    gaps = dict(red["idle_gaps"])
    # 50-100 (middle 75): fused-collect is not yet open -> chunk
    # 170-300 (middle 235): accel:collect; 400-450 (425): fused-collect
    assert gaps == pytest.approx({"chunk": 50e-9, "accel:collect": 130e-9,
                                  "fused-collect": 50e-9})
    assert "ingest:decode" not in gaps


def test_a_trace_without_window_spans_is_an_error():
    pd = fake()
    pd.planes[1].lines[0].events = pd.planes[1].lines[0].events[1:]
    with pytest.raises(ValueError):
        ps.reduce(pd)


def test_quantities_per_trial_and_block():
    red = {"program_spans": {
        "seam:download": {"count": 2, "total_s": 0.2, "self_s": 0.2},
        "seam:zap": {"count": 2, "total_s": 0.1, "self_s": 0.1},
        "accel:collect": {"count": 4, "total_s": 0.8, "self_s": 0.8},
        "accel:refine": {"count": 8, "total_s": 0.4, "self_s": 0.3},
        "ingest:wait": {"count": 5, "total_s": 2.0, "self_s": 2.0},
        "ingest:prep": {"count": 4, "total_s": 1.0, "self_s": 1.0}}}
    q = ps.quantities("search", red, {"trials": 4})
    assert q == pytest.approx({"seam_trip_ms_per_trial": 75.0,
                               "accel_sync_ms_per_trial": 200.0,
                               "refine_ms_per_trial": 100.0})
    q = ps.quantities("dedisp", red, {"steps": 4})
    assert q == pytest.approx({"ingest_wait_ms_per_block": 500.0,
                               "prep_ms_per_block": 250.0})


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace")
def test_recorded_v5e_trace():
    red = ps.reduce(tr.load(RECORDED))
    spans = red["program_spans"]
    assert spans["collect"]["count"] == spans["host"]["count"] == 3
    assert spans["worker"]["count"] > 20
    # the host sleep is collect's only child
    assert spans["collect"]["self_s"] == pytest.approx(
        spans["collect"]["total_s"] - spans["host"]["total_s"], abs=1e-9)
    assert spans["host"]["total_s"] > 3 * 0.018
    gaps = dict(red["idle_gaps"])
    # gaps go to the main thread's spans, never to the worker's
    assert "worker" not in gaps
    assert gaps["host"] > 3 * 0.018
    assert gaps["chunk"] > 3 * 0.004 * 0.5
    # the window, busy time and idle total agree with trace_reduce
    base = tr.reduce(tr.load(RECORDED))
    assert red["window_s"] == pytest.approx(base["window_s"])
    assert red["busy_s"] == pytest.approx(base["busy_s"])
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _k, v in base["idle_gaps"]))


def test_span_run_rehearsed_on_the_cpu():
    import presto_tpu.obs as obsmod
    saved = obsmod._default
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = ps.main(["--workload", "gbncc.dedisp", "--seed",
                          "3000000019", "--seconds", "1", "--rehearse"])
    finally:
        obsmod._default = saved
    assert rc == 0
    res = json.loads([l for l in out.getvalue().splitlines()
                      if l.startswith("{")][-1])
    assert res["window"]["steps"] > 0
    spans = res["trace"]["program_spans"]
    assert {"ingest:decode", "ingest:prep", "ingest:wait"} <= set(spans)
    assert spans["ingest:wait"]["count"] >= res["window"]["steps"]
    assert set(res["quantities"]) == {"ingest_wait_ms_per_block",
                                      "prep_ms_per_block"}
