"""The trace reduction, by hand on a made-up trace and on a small trace
recorded on a v5e (testdata/small.xplane.pb, record_trace.py)."""

import os
from types import SimpleNamespace as NS

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "testdata",
                        "small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def fake():
    ops = [ev("fusion.1", 100, 50), ev("fusion.2", 140, 30),  # 100-170
           ev("copy", 300, 100)]                            # 300-400
    mods = [ev("jit_build_body(7)", 100, 70), ev("jit_step(3)", 300, 100)]
    host = [ev("bench:chunk", 50, 400), ev("bench:host_wait", 180, 100),
            ev("other", 0, 10)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                        NS(name="XLA Modules",
                                           events=mods)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


def test_hand_reduction_of_a_made_up_trace():
    red = tr.reduce(fake())
    # window 50..450 (the bench:chunk span); busy = 70 + 100 ns
    assert red["window_s"] == pytest.approx(400e-9)
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["programs"] == pytest.approx({"build_body": 70e-9,
                                             "step": 100e-9})
    # idle: 50-100 and 400-450 under chunk, 170-300 (middle 235) under
    # host_wait
    gaps = dict(red["idle_gaps"])
    assert gaps["host_wait"] == pytest.approx(130e-9)
    assert gaps["chunk"] == pytest.approx(100e-9)
    assert red["device_ops"][0] == ["step/copy", pytest.approx(100e-9)]
    assert dict(red["device_ops"])["build_body/fusion.1"] == \
        pytest.approx(50e-9)


def test_union_and_names():
    assert tr.union([(5, 9), (1, 3), (2, 4), (8, 12)]) == [[1, 4], [5, 12]]
    assert tr.module_name("jit_scan_many_compact(123)") == \
        "scan_many_compact"
    assert tr.program_seconds({"programs": {"build_body": 2.0,
                                            "step": 1.0}},
                              [r"^step$"]) == 1.0


def test_no_device_operation_is_an_error():
    pd = fake()
    pd.planes = pd.planes[1:]
    with pytest.raises(ValueError):
        tr.reduce(pd)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace")
def test_recorded_v5e_trace():
    red = tr.reduce(tr.load(RECORDED))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert set(red["programs"]) >= {"mult", "add_rows"}
    # three chunks each slept 20 ms on the host with the chip idle
    gaps = dict(red["idle_gaps"])
    assert gaps["host_wait"] > 3 * 0.018
    # the two programs' module spans cover the ops' busy time (the
    # module spans also hold the few-ns joints between ops)
    assert sum(red["programs"].values()) == pytest.approx(red["busy_s"],
                                                          rel=0.01)
    # busy by hand: the ops of the device plane, clipped to the first
    # and last bench:chunk span (the host and device clocks agree to a
    # few ms; the first op here starts 1.5 ms before its span)
    pd = tr.load(RECORDED)
    dev = [p for p in pd.planes if p.name.startswith("/device:")][0]
    spans = [(e.start_ns, e.start_ns + e.duration_ns)
             for p in pd.planes if p.name.startswith("/host:")
             for l in p.lines for e in l.events if e.name == "bench:chunk"]
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
           for l in dev.lines if l.name == "XLA Ops" for e in l.events
           if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
    total, end = 0, None
    for s0, e0 in sorted(ops):
        if end is None or s0 > end:
            total += e0 - s0
            end = e0
        elif e0 > end:
            total += e0 - end
            end = e0
    assert red["busy_s"] == pytest.approx(total / 1e9, rel=1e-6)


def test_build_reader_counts_a_lambda_only_by_its_build_kernel():
    from perfbench import harness
    read = harness.reader("accel_build_roofline")
    red = {"programs": {"_lambda": 2.0, "build_body": 1.0, "other": 5.0},
           "device_ops": [["_lambda/%build.3", 1.5],
                          ["other/%fusion.1", 5.0]]}
    ctx = {"trace": red, "window": {"trials": 1},
           "required": {"accel_build": {"flops": 0.0, "bytes": 819e9}},
           "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    assert read(ctx) == pytest.approx(100.0 / 3.0)
    red["device_ops"] = [["_lambda/%fusion.2", 1.5]]
    assert read(ctx) == pytest.approx(100.0)
