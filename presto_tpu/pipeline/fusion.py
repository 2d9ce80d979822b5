"""Device-resident stage fusion for the survey pipeline.

BENCH_r05 put the accel kernel at 2.93e9 cells/s device-resident but
only 1.10e9 cells/s inclusive: the gap is host transfers, per-stage
``.dat``/``.fft`` disk round-trips, and warmup — not compute.  The
staged survey (pipeline/survey.py) materializes every stage boundary
to disk: prepsubband downloads the DM fan-out and writes ``.dat``
files, the FFT stage reads them back and re-uploads, and the
single-pulse stage reads them from disk a third time.  This module
gives stages an IN-MEMORY seam instead: dedispersed series flow
HBM -> (zap) -> FFT -> accel/single-pulse search without touching
disk, and the artifact journal becomes an optional *durability tier*
rather than the data path (AstroAccelerate's FDAS gets its real-time
claim from exactly this shape: a device-resident dedisp->FFT->search
chain with ingest overlapped against compute).

Three pieces, each usable on its own:

``StageSeam``
    The hand-off object: a producer stage (prepsubband) deposits
    device arrays + per-trial metadata; consumer stages (realfft,
    accelsearch, single_pulse_search) read them without a disk
    round-trip.  ``spill()``/``ensure_dat()`` write the would-be
    artifacts (atomic + journaled) when durability — or a downstream
    consumer like prepfold — demands them; spilled bytes are counted
    on ``survey_fused_bytes_spilled_total`` and every hand-off/spill
    opens a ``pipeline:seam`` span.

``InflightWindow``
    Bounded cross-stage async dispatch: jax dispatches are async, so
    queueing stage N+1's work before collecting stage N's overlaps
    them — but an unbounded queue pins every intermediate buffer in
    HBM.  The window admits new in-flight values and forces the oldest
    once ``depth`` are pending (the jerk ladder's 2-deep pattern from
    search/accel.py, generalized).

``DoubleBufferedIngest``
    Host-side ingest overlap: a worker thread decodes/preprocesses
    block k+1 while the caller feeds block k to the device,
    generalizing the csrc/native_io.cpp feeder's raw-read prefetch to
    the whole decode->mask->clip->transpose stage.

The seam crosses the survey's app-CLI boundary (argv cannot carry
objects) the same way the elastic layer's injector does: the survey
installs a process-level seam with :func:`set_process_seam`, and
apps/prepsubband.py picks it up when its execution path is
seam-compatible (single-process, non--sub; sharded mesh and
barycentred runs included).  On the DM-sharded mesh path the deposit
is a :class:`ShardedSeamBlock`: one global jax.Array whose DM axis is
sharded over the mesh, each device holding the sub-range it
dedispersed (parallel/sharded.ShardedDedispPlan) — the downstream
sharded rFFT, in-memory zap, accel and single-pulse searches consume
the shards in place, and host download happens only at candidate
collection and durable spill (``gather_shards``).

Byte-identity invariant: fusion only changes WHERE bytes live between
stages, never their values.  The seam's device series are bit-equal
to the staged path's ``.dat`` bytes (the pad tail is computed on host
with the exact NumPy semantics of pad_to_good_N and uploaded), so any
artifact the fused path spills — and every always-written final
artifact (ACCEL/.cand/cands_sifted/.singlepulse) — is byte-identical
to a staged run's.  tests/test_fusion.py and the chaos matrix pin
this.
"""

from __future__ import annotations

import os
import queue
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

import numpy as np

from presto_tpu.obs import resolve_obs

#: defaults for the fused pipeline's two depth knobs; the
#: ``pipeline_inflight_depth`` tune family (tune/space.py) overrides
#: them per device fingerprint.  Depths only change dispatch/ingest
#: overlap, never output bytes.
DEFAULT_WINDOW_DEPTH = 2     # cross-stage in-flight dispatches
DEFAULT_INGEST_DEPTH = 2     # host blocks decoded ahead of the device


def resolve_depths(inflight_depth: Optional[int] = None,
                   obs=None) -> Dict[str, int]:
    """The fused pipeline's depth knobs: an explicit caller value wins
    for the windows; otherwise the tuning DB's
    ``pipeline_inflight_depth`` (and, for the DM-sharded seam path,
    ``sharded_inflight_depth``) entries when tuning is active
    (presto_tpu/tune), else the defaults.  ``shard_window`` paces the
    sharded fused chain — its sweet spot differs from the
    single-device window because each in-flight chunk pins HBM on
    EVERY mesh device — and falls back to ``window`` when the sharded
    family has no measurement.  Clamped to [1, 8] — a depth only
    changes overlap, so any clamp is safe."""
    window, ingest = DEFAULT_WINDOW_DEPTH, DEFAULT_INGEST_DEPTH
    shard_window = None
    from presto_tpu import tune
    if tune.enabled():
        cfg = tune.best("pipeline_inflight_depth", tune.GLOBAL_KEY,
                        obs=obs)
        if cfg:
            try:
                window = int(cfg.get("window", window))
                ingest = int(cfg.get("ingest_depth", ingest))
            except (TypeError, ValueError):
                pass
        scfg = tune.best("sharded_inflight_depth", tune.GLOBAL_KEY,
                         obs=obs)
        if scfg:
            try:
                shard_window = int(scfg.get("window"))
            except (TypeError, ValueError):
                pass
    if inflight_depth is not None:
        window = int(inflight_depth)
        shard_window = int(inflight_depth)
    if shard_window is None:
        shard_window = window
    return {"window": max(1, min(int(window), 8)),
            "ingest_depth": max(1, min(int(ingest), 8)),
            "shard_window": max(1, min(int(shard_window), 8))}


def inf_float(x, digits: int = 15) -> float:
    """The value a staged consumer reads back from a ``.inf`` sidecar:
    the ``{:.Ng}`` text roundtrip (io/infodata.py writes dt with 15
    significant digits, dm with 12).  Seam consumers must use THIS —
    not the full-precision float — wherever the staged path derives a
    number from the sidecar, or fused and staged artifacts could
    differ in the last ulp."""
    return float(("%%.%dg" % int(digits)) % float(x))


# ----------------------------------------------------------------------
# InflightWindow
# ----------------------------------------------------------------------

class InflightWindow:
    """Keep at most ``depth`` async device computations in flight.

    ``admit(x)`` registers a freshly-dispatched value (any pytree of
    jax arrays); when more than ``depth`` are pending the OLDEST is
    forced (block_until_ready) and released — so stage N+1's dispatch
    overlaps stage N's execution while HBM holds a bounded number of
    intermediates.  ``drain()`` forces everything left."""

    def __init__(self, depth: int = DEFAULT_WINDOW_DEPTH):
        self.depth = max(1, int(depth))
        self._pending: List[object] = []

    def admit(self, x) -> None:
        self._pending.append(x)
        while len(self._pending) > self.depth:
            self._force(self._pending.pop(0))

    def drain(self) -> None:
        while self._pending:
            self._force(self._pending.pop(0))

    @staticmethod
    def _force(x) -> None:
        try:
            import jax
            jax.block_until_ready(x)
        except Exception:
            pass     # host values (or no backend): nothing to await


# ----------------------------------------------------------------------
# DoubleBufferedIngest
# ----------------------------------------------------------------------

class _IngestStop(Exception):
    pass


class DoubleBufferedIngest:
    """Iterate ``source`` on a worker thread, ``depth`` items ahead.

    The producer runs the expensive host-side block work (read,
    decode, mask/clip, transpose) while the consumer keeps the device
    busy with the previous block — the (data, lastdata) double-buffer
    of the reference's streaming loop lifted to the whole ingest
    stage.  Items are delivered strictly in order; a producer
    exception is re-raised at the consumer's next pull, and close()
    always joins the thread.

    The process default observability handle times the consumer's
    wait for an item (``ingest:wait``) and the producer's wait for
    room in a full queue (``ingest:full``)."""

    def __init__(self, source: Iterator, depth: int = DEFAULT_INGEST_DEPTH):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._done = object()
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, args=(source,), daemon=True,
            name="presto-ingest")
        self._thread.start()

    def _run(self, source) -> None:
        try:
            for item in source:
                if self._stop.is_set():
                    return
                try:
                    self._q.put_nowait(item)
                    continue
                except queue.Full:
                    pass
                with resolve_obs(None).span("ingest:full"):
                    while not self._stop.is_set():
                        try:
                            self._q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                if self._stop.is_set():
                    return
        except BaseException as e:           # relay to the consumer
            self._exc = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(self._done, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        with resolve_obs(None).span("ingest:wait"):
            item = self._q.get()
        if item is self._done:
            if self._exc is not None:
                exc, self._exc = self._exc, None
                raise exc
            raise StopIteration
        return item

    def close(self) -> None:
        self._stop.set()
        try:                                 # unblock a full queue
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ----------------------------------------------------------------------
# StageSeam
# ----------------------------------------------------------------------

@dataclass
class SeamBlock:
    """One prepsubband method's DM fan-out held at the seam: the
    device-resident padded series (the FFT stage's input block), the
    bit-identical host copy (artifact/spill/fold source), and the
    per-trial metadata a consumer stage would otherwise re-read from
    ``.inf`` sidecars."""
    names: List[str]            # per-trial base paths (no extension)
    infos: List[object]         # per-trial InfoData
    dms: List[float]
    series_dev: object          # [ntrials, numout] float32 jax array
    series_host: np.ndarray     # same bytes, host side
    valid: int                  # data samples before the pad
    numout: int                 # padded length
    dt: float                   # post-downsample sample time
    T: float = 0.0              # numout * dt (searcher geometry)

    def __post_init__(self):
        if not self.T:
            self.T = self.numout * self.dt


@dataclass
class ShardedSeamBlock(SeamBlock):
    """A SeamBlock whose ``series_dev`` is ONE global jax.Array with
    the DM axis sharded over ``mesh`` (parallel/mesh dm_sharding):
    each device holds exactly the DM sub-range it dedispersed
    (parallel/sharded.ShardedDedispPlan), and downstream consumers —
    the DM-sharded batched rFFT, in-memory zapbirds, search_many and
    single-pulse — operate on the shards IN PLACE.  The host copy is
    assembled per shard (``gather_shards``: parallel per-device D2H,
    no cross-device gather) and exists for the same reason the
    unsharded block's does: the pad tail must be computed with
    pad_to_good_N's exact NumPy semantics, and spills/folds/candidate
    refinement read host bytes.  Placement-aware spill = the durable
    tier writes each DM trial's ``.dat`` from that assembled copy
    without ever staging the fan-out through a single device."""
    mesh: object = None


class StageSeam:
    """In-memory seam between survey stages (see module docstring).

    ``durable`` selects the durability tier: True spills every
    deposited block's artifacts immediately (the staged contract with
    the disk round-trip removed from the CONSUMER side only); False —
    the presto-serve/bench tier — writes nothing until a consumer
    calls ``ensure_dat`` (prepfold) or ``spill`` explicitly."""

    def __init__(self, workdir: str, durable: bool = False,
                 manifest=None, obs=None,
                 inflight_depth: Optional[int] = None):
        self.workdir = os.path.abspath(workdir)
        self.durable = bool(durable)
        self.manifest = manifest
        self.obs = obs
        self.blocks: List[SeamBlock] = []
        self.depths = resolve_depths(inflight_depth, obs=obs)
        self._by_dat: Dict[str, tuple] = {}   # .dat path -> (block, row)
        self._spilled: set = set()

    # -- producer side -------------------------------------------------

    def add_block(self, block: SeamBlock) -> None:
        """Deposit one method's fan-out at the seam (producer side).
        The ``.inf`` sidecars are written on EVERY tier — they are
        per-trial metadata the final-artifact consumers (sifting,
        prepfold) read from disk, not the bulk data path."""
        from presto_tpu.io.infodata import write_inf
        sp = self._span("handoff", trials=len(block.names),
                        numout=block.numout,
                        sharded=is_sharded(block))
        self.blocks.append(block)
        infs = []
        for row, name in enumerate(block.names):
            self._by_dat[os.path.abspath(name + ".dat")] = (block, row)
            write_inf(block.infos[row], name + ".inf")
            infs.append(name + ".inf")
        if self.manifest is not None:
            self.manifest.record_many(
                [p for p in infs if os.path.exists(p)], "prepsubband")
        if self.obs is not None and self.obs.enabled:
            self.obs.metrics.counter(
                "survey_fused_trials_total",
                "DM trials handed across the in-memory stage seam"
            ).inc(len(block.names))
            if is_sharded(block):
                self.obs.metrics.counter(
                    "survey_fused_shard_trials_total",
                    "DM trials handed across the seam as device "
                    "shards (one DM sub-range per mesh device)"
                ).inc(len(block.names))
        if self.durable:
            self.spill(block)
        if sp is not None:
            sp.finish()

    # -- consumer side -------------------------------------------------

    def __len__(self) -> int:
        return sum(len(b.names) for b in self.blocks)

    def dat_paths(self) -> List[str]:
        return sorted(self._by_dat)

    def groups(self) -> Dict[int, List[SeamBlock]]:
        """Blocks grouped by padded length (the FFT/search batching
        axis, mirroring the staged path's _length_groups)."""
        by_len: Dict[int, List[SeamBlock]] = {}
        for b in self.blocks:
            by_len.setdefault(b.numout, []).append(b)
        return by_len

    # -- durability tier -----------------------------------------------

    def spill(self, block: Optional[SeamBlock] = None,
              record_stage: str = "prepsubband") -> int:
        """Write the ``.dat``+``.inf`` artifacts for one block (or
        all), atomic + journaled — the staged path's durable outputs,
        produced from the seam's host copy.  Returns bytes written."""
        from presto_tpu.io.datfft import write_dat
        blocks = [block] if block is not None else list(self.blocks)
        total = 0
        for b in blocks:
            sp = self._span("spill", trials=len(b.names),
                            numout=b.numout, sharded=is_sharded(b))
            written = []
            for row, name in enumerate(b.names):
                dat = name + ".dat"
                if os.path.abspath(dat) in self._spilled:
                    continue
                write_dat(dat, b.series_host[row], b.infos[row])
                self._spilled.add(os.path.abspath(dat))
                written += [dat, name + ".inf"]
                total += b.series_host[row].nbytes
            if written and self.manifest is not None:
                self.manifest.record_many(
                    [p for p in written if os.path.exists(p)],
                    record_stage)
            if sp is not None:
                sp.finish()
        self._count_spill(total)
        return total

    def ensure_dat(self, datpath: str) -> bool:
        """Spill ONE trial's ``.dat``+``.inf`` on demand (prepfold
        reads its candidate's series from disk).  Returns True when
        the path is now on disk (or was never seam-held)."""
        key = os.path.abspath(datpath)
        ent = self._by_dat.get(key)
        if ent is None:
            return os.path.exists(datpath)
        if key in self._spilled or os.path.exists(datpath):
            return True
        from presto_tpu.io.datfft import write_dat
        block, row = ent
        sp = self._span("spill", trials=1, numout=block.numout,
                        on_demand=True, sharded=is_sharded(block))
        write_dat(datpath, block.series_host[row], block.infos[row])
        self._spilled.add(key)
        if self.manifest is not None:
            self.manifest.record_many(
                [p for p in (datpath, block.names[row] + ".inf")
                 if os.path.exists(p)], "prepsubband")
        self._count_spill(block.series_host[row].nbytes)
        if sp is not None:
            sp.finish()
        return True

    def release(self, block: SeamBlock) -> None:
        """Drop the seam's reference to a block's DEVICE array (the
        host copy stays for spills) — lets a consumer donate the
        buffer to its own computation."""
        block.series_dev = None

    # -- internals -----------------------------------------------------

    def _span(self, op: str, sharded: bool = False, **attrs):
        if self.obs is None or not self.obs.enabled:
            return None
        if sharded:
            return self.obs.span("pipeline:shard-seam", op=op, **attrs)
        return self.obs.span("pipeline:seam", op=op, **attrs)

    def _count_spill(self, nbytes: int) -> None:
        if nbytes and self.obs is not None and self.obs.enabled:
            self.obs.metrics.counter(
                "survey_fused_bytes_spilled_total",
                "Seam-held artifact bytes spilled to the durable tier"
            ).inc(int(nbytes))


# ----------------------------------------------------------------------
# fused device helpers
# ----------------------------------------------------------------------

def is_sharded(block) -> bool:
    """Is this seam block's device series mesh-sharded on the DM axis?"""
    return getattr(block, "mesh", None) is not None


def gather_shards(arr, obs=None) -> np.ndarray:
    """Placement-aware D2H of a DM-sharded device array: each device's
    shard downloads independently into its row range of the host
    buffer (parallel per-device transfers, never a cross-device gather
    through one chip).  This is the sharded seam's ONLY bulk download
    — it feeds the pad computation, the durable spill, and candidate
    refinement; counted on survey_fused_shard_gather_bytes_total."""
    out = np.empty(arr.shape, dtype=arr.dtype)
    total = 0
    for sh in arr.addressable_shards:
        data = np.asarray(sh.data)
        out[sh.index] = data
        total += data.nbytes
    if obs is not None and getattr(obs, "enabled", False):
        obs.metrics.counter(
            "survey_fused_shard_gather_bytes_total",
            "Bytes downloaded per-shard from the DM-sharded seam "
            "(pad/spill/candidate collection)").inc(int(total))
        from presto_tpu.obs import jaxtel
        jaxtel.note_get(obs, total)
    return out


_fft_fns: dict = {}


def fused_rfft_batch(series_dev, donate: bool = False, obs=None,
                     mesh=None):
    """Batched packed real FFT of the seam's series block, optionally
    DONATING the input buffer to XLA (the dedisp output block becomes
    the FFT's workspace — input [n, N] float32 and output [n, N/2, 2]
    float32 are the same size, so donation makes the seam crossing
    allocation-neutral).  Identical floats either way; donation only
    changes buffer lifetime.

    With ``mesh`` the batch axis is the DM-sharded axis and the FFT
    runs shard_map'd: each device transforms ONLY its own rows and
    the spectra stay on the device that dedispersed the series.  The
    shard_map is load-bearing, not style — a plain jit (even with
    out_shardings pinned) lets GSPMD compute the batched FFT
    replicated and slice afterwards, which both re-gathers the
    fan-out and multiplies the FLOPs by the device count (measured 7x
    slower on the 8-device CPU mesh).  Per-row FFTs are independent,
    so the per-shard program computes identical floats."""
    import jax
    from presto_tpu.ops import fftpack
    key = (bool(donate), mesh)
    fn = _fft_fns.get(key)
    if fn is None:
        kw = {"donate_argnums": 0} if donate else {}
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            axis = mesh.axis_names[0]
            fn = jax.jit(jax.shard_map(
                jax.vmap(fftpack.realfft_packed_pairs), mesh=mesh,
                in_specs=P(axis, None),
                out_specs=P(axis, None, None)), **kw)
        else:
            fn = jax.jit(jax.vmap(fftpack.realfft_packed_pairs), **kw)
        _fft_fns[key] = fn
    from presto_tpu.obs import costmodel, jaxtel
    costmodel.probe(obs, "rfft_batch", fn, series_dev)
    jaxtel.note_dispatch(obs, "rfft_batch")
    if donate:
        jaxtel.note_donation(obs, int(np.prod(series_dev.shape)) * 4)
    return fn(series_dev)


# ----------------------------------------------------------------------
# process-level seam hand-off (the argv boundary, like
# parallel/elastic.set_process_injector)
# ----------------------------------------------------------------------

_process_seam: Optional[StageSeam] = None


def set_process_seam(seam: Optional[StageSeam]) -> None:
    """Install (or clear) the seam the next seam-aware app run in this
    process should deposit into.  The survey driver brackets its
    prepsubband calls with this; app CLIs launched any other way see
    None and keep the staged contract."""
    global _process_seam
    _process_seam = seam


def current_process_seam() -> Optional[StageSeam]:
    return _process_seam
