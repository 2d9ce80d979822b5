"""The program's obs spans inside the JAX profiler's trace (the
``presto:`` annotations), the candidate-collection span tree of the
fused FFT search, and the ingest double buffer's wait spans."""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import presto_tpu.obs as obsmod
from presto_tpu.obs import ObsConfig, Observability
from presto_tpu.obs.trace import Tracer


def _host_events(tdir):
    """[(line index, name, start_ns, end_ns)] of the host planes'
    ``presto:`` and ``outer`` events of the trace under tdir."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("presto:") or e.name == "outer":
                    out.append((i, e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def test_enabled_span_is_a_presto_annotation_in_the_profiler_trace(
        tmp_path):
    import jax
    import jax.numpy as jnp
    on, off = Tracer(enabled=True), Tracer(enabled=False)
    x = jnp.ones(64)
    (x + 1).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"):
            with on.span("collect", files=3):
                with on.span("child"):
                    (x * 2).block_until_ready()
            with off.span("hidden"):
                (x * 3).block_until_ready()
        # finished on another thread: kept by the tracer only
        cross = on.span("handoff")
        t = threading.Thread(target=cross.finish)
        t.start()
        t.join()
    ev = {name: (line, s, e) for line, name, s, e in _host_events(
        str(tmp_path))}
    assert set(ev) == {"outer", "presto:collect", "presto:child"}
    line, s0, e0 = ev["outer"]
    _l, s1, e1 = ev["presto:collect"]
    _l2, s2, e2 = ev["presto:child"]
    assert ev["presto:collect"][0] == ev["presto:child"][0] == line
    assert s0 <= s1 <= s2 and e2 <= e1 <= e0
    assert [s.name for s in on.finished()] == ["child", "collect",
                                               "handoff"]
    assert off.finished() == []


def test_spans_finished_on_other_threads_stay_out_of_the_trace(tmp_path):
    """Many threads finishing spans the main thread opened, with a
    short switch interval: every span reaches the buffer, none reaches
    the device trace, and the parked annotations are dropped at the
    first span after the session."""
    import jax
    on = Tracer(enabled=True)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            spans = [on.span("handoff", current=False) for _ in range(400)]
            workers = [threading.Thread(target=lambda part: [
                s.finish() for s in part], args=(spans[i::8],))
                for i in range(8)]
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=30)
            assert not any(w.is_alive() for w in workers)
            assert len(on._parked) == 400
    finally:
        sys.setswitchinterval(old)
    with on.span("after"):
        pass
    assert on._parked == []
    assert len(on.finished()) == 401
    assert not [e for e in _host_events(str(tmp_path))
                if e[1] == "presto:handoff"]


def test_spans_outside_a_profiler_session_record_no_annotation():
    on = Tracer(enabled=True)
    with on.span("quiet") as sp:
        assert sp._annotation is None
    assert [s.name for s in on.finished()] == ["quiet"]


def test_disabled_tracer_imports_nothing_from_jax():
    code = ("import sys\n"
            "from presto_tpu.obs import Observability, ObsConfig\n"
            "from presto_tpu.obs.trace import NOOP_SPAN, Tracer\n"
            "assert Tracer(enabled=False).span('x') is NOOP_SPAN\n"
            "assert Observability(ObsConfig()).span('x') is NOOP_SPAN\n"
            "print(sorted(m for m in sys.modules\n"
            "             if m == 'jax' or m.startswith('jax.')))\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.stdout.strip() == "[]"


# ----------------------------------------------------------------------
# the fused FFT search's candidate-collection spans
# ----------------------------------------------------------------------

def _block(workdir, prefix, ntrials=3, n=4096, dt=2e-4):
    import jax.numpy as jnp
    from presto_tpu.io.infodata import InfoData
    from presto_tpu.pipeline.fusion import SeamBlock
    rng = np.random.default_rng(5)
    host = rng.normal(size=(ntrials, n)).astype(np.float32)
    names = [os.path.join(workdir, "%s_DM%.2f" % (prefix, float(i)))
             for i in range(ntrials)]
    infos = [InfoData(name=names[i], N=n, dt=dt, dm=float(i))
             for i in range(ntrials)]
    return SeamBlock(names=names, infos=infos,
                     dms=[float(i) for i in range(ntrials)],
                     series_dev=jnp.asarray(host), series_host=host,
                     valid=n, numout=n, dt=dt)


def test_seam_fft_search_span_tree(tmp_path):
    from presto_tpu.pipeline import survey
    from presto_tpu.pipeline.fusion import StageSeam
    zaplist = tmp_path / "birds.txt"
    zaplist.write_text("  50.0   0.5\n  100.0  0.5\n")
    obs = Observability(ObsConfig(enabled=True))
    seam = StageSeam(str(tmp_path), durable=False)
    ntr, n = 3, 4096
    for prefix in ("a", "b"):
        seam.add_block(_block(str(tmp_path), prefix, ntr, n))
    passes = [(0, 2, 2.0, 1.0), (4, 2, 2.0, 1.0)]
    cfg = survey.SurveyConfig(zaplist=str(zaplist))
    survey._seam_fft_search(seam, cfg, passes, None, obs, zap=True)

    spans = obs.tracer.finished()
    by_id = {s.span_id: s for s in spans}

    def named(name):
        return [s for s in spans if s.name == name]

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id in by_id else None

    chunks, collects = named("fused-chunk"), named("fused-collect")
    assert len(chunks) == len(collects) == 2
    # a chunk span covers its rFFT dispatch only (and the dispatch's
    # cost probe): no collection inside
    assert {s.name for s in spans if parent(s) == "fused-chunk"} <= \
        {"obs:roofline-probe"}
    for c in chunks:
        assert not [k for k in collects
                    if c.start <= k.start and k.end <= c.end]
    for name in ("seam:download", "seam:zap", "seam:upload"):
        assert [parent(s) for s in named(name)] == ["fused-collect"] * 2
    nbytes = ntr * (n // 2) * 2 * 4
    assert [s.attrs["bytes"] for s in named("seam:download")] == \
        [nbytes, nbytes]
    assert [s.attrs["bytes"] for s in named("seam:upload")] == \
        [nbytes, nbytes]
    assert [parent(s) for s in named("accel:search")] == \
        ["fused-collect"] * 4
    assert named("accel:collect")
    assert {parent(s) for s in named("accel:collect")} == {"accel:search"}
    refine = named("accel:refine")
    assert len(refine) == 2 * ntr * len(passes)     # per trial and pass
    assert {parent(s) for s in refine} == {"fused-collect"}
    assert all(isinstance(s.attrs["cands"], int) for s in refine)
    assert [parent(s) for s in named("accel:write")] == \
        ["accel:refine"] * len(refine)
    assert all(s.status == "ok" for s in spans)


def test_seam_fft_search_without_a_handle_records_nothing(tmp_path):
    from presto_tpu.pipeline import survey
    from presto_tpu.pipeline.fusion import StageSeam
    seam = StageSeam(str(tmp_path), durable=False)
    seam.add_block(_block(str(tmp_path), "a", 2, 4096))
    default = obsmod.get_obs()
    before = len(default.tracer.finished())
    survey._seam_fft_search(seam, survey.SurveyConfig(),
                            [(0, 2, 2.0, 1.0)], None, None)
    assert len(default.tracer.finished()) == before
    assert os.path.exists(str(tmp_path / "a_DM1.00_ACCEL_0"))


# ----------------------------------------------------------------------
# block ingest
# ----------------------------------------------------------------------

@pytest.fixture
def default_obs():
    """An enabled process-default handle, restored afterwards."""
    saved = obsmod._default
    obs = obsmod.configure(ObsConfig(enabled=True))
    yield obs
    obsmod._default = saved


@pytest.mark.parametrize("slow", ["producer", "consumer"])
def test_ingest_wait_spans(default_obs, slow):
    from presto_tpu.pipeline.fusion import DoubleBufferedIngest

    def produce():
        for i in range(4):
            if slow == "producer":
                time.sleep(0.05)
            yield i

    got = []
    with DoubleBufferedIngest(produce(), depth=1) as ing:
        for item in ing:
            if slow == "consumer":
                time.sleep(0.05)
            got.append(item)
    assert got == [0, 1, 2, 3]
    spans = default_obs.tracer.finished()
    me = threading.current_thread().name
    waits = [s for s in spans if s.name == "ingest:wait"]
    full = [s for s in spans if s.name == "ingest:full"]
    assert waits and {s.thread for s in waits} == {me}
    if slow == "producer":
        # the consumer waits out most of each 50 ms block
        assert sum(s.duration for s in waits) > 3 * 0.03
        assert not full
    else:
        # a fast producer finds the one-slot queue full
        assert full and {s.thread for s in full} == {"presto-ingest"}
        assert sum(s.duration for s in full) > 2 * 0.03


def test_decode_and_prep_spans(default_obs):
    from types import SimpleNamespace
    from presto_tpu.apps.common import BlockPrep
    from presto_tpu.io.sigproc import (FilterbankHeader,
                                       decode_spectra_block)
    hdr = FilterbankHeader(fch1=400.0, foff=-1.0, nchans=16, nbits=8,
                           tsamp=1e-4, nifs=1, N=64)
    raw = np.random.default_rng(1).integers(0, 255, 64 * 16,
                                            dtype=np.uint8)
    block = decode_spectra_block(hdr, raw, 64)
    prep = BlockPrep(16, 1e-4, SimpleNamespace(clip=6.0, noclip=False))
    prep(block, 0)
    names = [s.name for s in default_obs.tracer.finished()]
    assert names == ["ingest:decode", "ingest:prep"]
