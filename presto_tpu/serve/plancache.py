"""Compiled-plan cache keyed on trial geometry (serve layer).

XLA compiles one executable per program shape; in the batch driver a
new process pays that cost for every run.  A resident service only
pays it once per *bucket*: plans are keyed on
(nchan, nsamp, dtype, DM-block shape, zmax, numharm) with the sample
count quantized pad-to-bucket (next power of two), so beams whose raw
lengths differ by a few percent land in the same bucket and reuse the
same jitted dedispersion/accelsearch executables — the plan-cache
shape modern inference servers use for sequence lengths.

Two cooperating layers:

  * `bucket_key(rawfile, cfg)` — the *scheduling* key: what the
    micro-batching loop coalesces on (same bucket -> same batch).
  * `PlanCache` + `SearcherProvider` — the *execution* cache: the
    survey's searcher construction (`_survey_searcher`) routes through
    `SurveyConfig.plan_provider`, so same-shaped trial groups across
    jobs share one AccelSearch instance (one kernel bank + one jit
    cache) instead of recompiling per job.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
import warnings
from collections import OrderedDict
from dataclasses import asdict, dataclass
from typing import Any, Callable, Dict, Optional, Tuple


@dataclass(frozen=True)
class PlanKey:
    """Hashable plan identity.  `kind` separates plan families
    ("job" scheduling buckets vs "accel" searcher plans); `extra`
    carries family-specific fields (e.g. sigma/flo/T for accel)."""
    kind: str
    nchan: int
    nsamp: int
    dtype: str
    dm_block: Tuple
    zmax: int
    numharm: int
    extra: Tuple = ()


#: bucket-edge schemes: sub-pow2 mantissa steps each scheme admits.
#: "pow2" is the classic next-power-of-two; the finer schemes add
#: half/quarter points between octaves (fewer padded samples per job,
#: more distinct buckets = more compiles — the trade the tuning DB's
#: `plancache_bucket` family scores offline).
_BUCKET_SCHEMES = {
    "pow2": (1.0,),
    "pow2_half": (1.0, 1.5),
    "pow2_quarter": (1.0, 1.25, 1.5, 1.75),
}


def bucket_quantize(n: int, scheme: str = "pow2") -> int:
    """Smallest bucket edge >= n under `scheme`.  Unknown schemes
    fall back to pow2 (a tuned DB entry can degrade granularity,
    never produce an undersized bucket)."""
    n = max(int(n), 1)
    steps = _BUCKET_SCHEMES.get(scheme) or _BUCKET_SCHEMES["pow2"]
    p2 = 1 << (n - 1).bit_length()          # next pow2 >= n
    best = p2
    for m in steps:
        edge = int(m * (p2 >> 1))           # edges in (p2/2, p2]
        if edge >= n and edge < best:
            best = edge
    return best


def quantize_nsamp(n: int) -> int:
    """Pad-to-bucket sample-count quantization.

    Coarse on purpose — the goal is few buckets and many hits, not a
    tight fit; the survey's own choose_N padding happens downstream of
    this at the actual trial length.  Default is next power of two;
    when tuning is active (PRESTO_TPU_TUNE=1 / presto-tune) the
    bucket-edge scheme comes from the tuning DB's `plancache_bucket`
    entry, with pow2 as the fallback.  The bucket is a *scheduling*
    key (what the micro-batching loop coalesces on) — it never changes
    job outputs."""
    from presto_tpu import tune
    if tune.enabled():
        cfg = tune.best("plancache_bucket", tune.GLOBAL_KEY)
        if cfg:
            return bucket_quantize(n, str(cfg.get("scheme", "pow2")))
    from presto_tpu.utils.psr import next2_to_n
    return int(next2_to_n(max(int(n), 1)))


def dm_block_shape(cfg) -> Tuple:
    """The DM fan-out geometry of a SurveyConfig, as a hashable
    shape: (lodm, hidm, nsub) fully determine the DDplan methods for
    a given observation."""
    return (round(float(cfg.lodm), 3), round(float(cfg.hidm), 3),
            int(cfg.nsub))


def bucket_key(rawfiles, cfg) -> PlanKey:
    """Scheduling bucket for a job: observation geometry (from the raw
    header) + search geometry (from the config).  Jobs with equal
    buckets produce identically-shaped device programs, so the
    scheduler may coalesce them."""
    from presto_tpu.apps.common import open_raw
    paths = [rawfiles] if isinstance(rawfiles, str) else list(rawfiles)
    fb = open_raw(paths)
    hdr = fb.header
    nchan, nsamp, nbits = int(hdr.nchans), int(hdr.N), int(hdr.nbits)
    fb.close()
    return PlanKey(kind="job", nchan=nchan,
                   nsamp=quantize_nsamp(nsamp),
                   dtype="uint%d" % nbits if nbits < 32 else "float32",
                   dm_block=dm_block_shape(cfg),
                   zmax=int(cfg.zmax), numharm=int(cfg.numharm))


@dataclass
class CompiledPlan:
    """A cached executable bundle + bookkeeping.  `device` records the
    executable->device binding at build time (obs/jaxtel
    current_device_id), so a TPU reset can evict exactly the plans
    bound to the dead device instead of flushing the whole cache."""
    key: PlanKey
    obj: Any
    build_seconds: float
    built_at: float
    uses: int = 0
    device: Optional[str] = None

    def place(self, batch, mesh=None):
        """Mesh-aware placement of a stacked same-bucket batch: shard
        the leading (job/trial) axis across the mesh so one batched
        device call spans the chips (no-op passthrough without a
        mesh)."""
        if mesh is None:
            return batch
        import jax
        import jax.numpy as jnp
        from presto_tpu.parallel.mesh import batch_sharding
        arr = jnp.asarray(batch)
        return jax.device_put(
            arr, batch_sharding(mesh, ndim=arr.ndim))


class PlanCache:
    """Thread-safe LRU cache of compiled plans with hit/miss/eviction
    accounting on the shared metrics registry (the /metrics `plans`
    block and the `plancache_*` Prometheus series are the same
    counters)."""

    def __init__(self, capacity: int = 32, events=None, obs=None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if obs is None:
            from presto_tpu.obs import Observability, ObsConfig
            obs = Observability(ObsConfig(enabled=True))
        self.capacity = capacity
        self.obs = obs
        self._events = events
        self._lock = threading.Lock()  # presto-lint: guards(_plans, _compile_s)
        self._plans: "OrderedDict[PlanKey, CompiledPlan]" = \
            OrderedDict()
        self._compile_s = 0.0
        reg = obs.metrics
        self._c_hits = reg.counter("plancache_hits_total",
                                   "Plan-cache hits")
        self._c_misses = reg.counter("plancache_misses_total",
                                     "Plan-cache misses (compiles)")
        self._c_evict = reg.counter(
            "plancache_evictions_total", "Plan-cache evictions",
            ("reason",))
        self._g_size = reg.gauge("plancache_size",
                                 "Compiled plans resident")

    def get(self, key: PlanKey, builder: Callable[[], Any]) -> Any:
        """Return the cached plan for `key`, building (and counting a
        compile) on first use.  The builder runs outside the lock so a
        long XLA compile never blocks cache hits on other keys; two
        racing builders for one key keep the first-inserted plan."""
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
                self._c_hits.inc()
                plan.uses += 1
                return plan.obj
            self._c_misses.inc()
        from presto_tpu.obs import jaxtel
        t0 = time.time()
        obj = builder()
        dt = time.time() - t0
        device = jaxtel.current_device_id()
        with self._lock:
            existing = self._plans.get(key)
            if existing is not None:        # lost the build race
                existing.uses += 1
                return existing.obj
            self._compile_s += dt
            self._plans[key] = CompiledPlan(
                key=key, obj=obj, build_seconds=dt, built_at=t0,
                uses=1, device=device)
            self._plans.move_to_end(key)
            while len(self._plans) > self.capacity:
                old_key, _ = self._plans.popitem(last=False)
                self._c_evict.labels(reason="capacity").inc()
                if self._events is not None:
                    self._events.emit("evict", plan=repr(old_key))
            self._g_size.set(len(self._plans))
        # the built plan rides along so obs/costmodel can harvest its
        # unit cost when it IS a compiled executable (AOT bundles);
        # AccelSearch-style plan objects are skipped silently
        jaxtel.note_compile(self.obs, kind=key.kind, seconds=dt,
                            key=key, device=device, compiled=obj)
        if self._events is not None:
            self._events.emit("compile", plan=repr(key), seconds=dt)
        return obj

    def evict_bucket(self, device: Optional[str] = None,
                     reason: str = "device_error") -> int:
        """Flush plans bound to `device` (None = every plan): the
        scheduler's retry path calls this on a device/executable
        RuntimeError so a retry re-warms a fresh executable instead of
        re-entering the poisoned one (ROADMAP: plan-cache invalidation
        on device error).  Returns the number evicted; each eviction
        counts under `plancache_evictions_total{reason=...}`."""
        with self._lock:
            doomed = [k for k, p in self._plans.items()
                      if device is None or p.device == device
                      or p.device is None]
            for k in doomed:
                del self._plans[k]
                self._c_evict.labels(reason=reason).inc()
            self._g_size.set(len(self._plans))
        for k in doomed:
            if self._events is not None:
                self._events.emit("plan-evict", plan=repr(k),
                                  reason=reason, device=device or "*")
        self.obs.event("plan-evict", n=len(doomed), reason=reason,
                       device=device or "*")
        return len(doomed)

    def contains(self, key: PlanKey) -> bool:
        with self._lock:
            return key in self._plans

    def stats(self) -> dict:
        hits = int(self._c_hits.value)
        misses = int(self._c_misses.value)
        total = hits + misses
        with self._lock:
            size = len(self._plans)
            compile_s = self._compile_s
        return {
            "size": size,
            "capacity": self.capacity,
            "hits": hits,
            "misses": misses,
            "evictions": int(self._c_evict.total()),
            "compile_s": round(compile_s, 3),
            "hit_rate": (hits / total) if total else 0.0,
        }


def accel_plan_key(acfg, T: float, numbins: int) -> PlanKey:
    """The execution-plan identity of one accel searcher.  T enters
    the key (it scales the z grid and candidate frequencies), so only
    genuinely identical trial geometries share a plan — required for
    byte-equality with the batch driver."""
    return PlanKey(kind="accel", nchan=0, nsamp=int(numbins),
                   dtype="float32", dm_block=(),
                   zmax=int(acfg.zmax), numharm=int(acfg.numharm),
                   extra=(float(acfg.sigma), float(acfg.flo),
                          round(float(T), 9))
                   # a jerk pass (-wmax) or a band (-rlo/-rhi) is
                   # another plan; a plain pass keeps its old key
                   + ((int(acfg.wmax), float(acfg.rlo), float(acfg.rhi))
                      if acfg.wmax or acfg.rlo or acfg.rhi else ()))


class SearcherProvider:
    """The `SurveyConfig.plan_provider` adapter: routes the survey's
    per-trial-group searcher construction through a PlanCache, so a
    resident service compiles each accel-plan geometry once.  With a
    PlanStore attached, every plan built is also *recorded* — its
    rebuild recipe lands in the persistent tier, so a cold replica
    can re-derive the whole working set before its first job."""

    def __init__(self, cache: PlanCache, mesh=None,
                 store: Optional["PlanStore"] = None):
        self.cache = cache
        self.mesh = mesh
        self.store = store

    def searcher(self, acfg, T: float, numbins: int):
        """Cached AccelSearch for (acfg, T, numbins)."""
        key = accel_plan_key(acfg, T, numbins)

        def _build():
            from presto_tpu.search.accel import AccelSearch
            s = AccelSearch(acfg, T=T, numbins=numbins)
            if self.store is not None:
                self.store.record(key, {
                    "kind": "accel", "acfg": asdict(acfg),
                    "T": float(T), "numbins": int(numbins)})
            return s

        return self.cache.get(key, _build)

    def prewarm(self, limit: Optional[int] = None) -> int:
        """Rebuild every plan the persistent tier knows for this
        device fingerprint into the in-memory cache (a no-op without
        a store).  With JAX's compilation cache enabled underneath,
        the XLA executables come off disk instead of recompiling —
        a freshly joined replica warms in seconds, not per-bucket
        compile time.  Returns the number of plans warmed."""
        if self.store is None:
            return 0
        from presto_tpu.search.accel import AccelConfig
        n = 0
        for recipe in self.store.known().values():
            if recipe.get("kind") != "accel":
                continue
            if limit is not None and n >= limit:
                break
            try:
                acfg = AccelConfig(**recipe["acfg"])
                self.searcher(acfg, float(recipe["T"]),
                              int(recipe["numbins"]))
                n += 1
            except Exception as e:     # a stale recipe must not
                warnings.warn(          # block replica start
                    "plan prewarm skipped a recorded plan: %s" % e,
                    RuntimeWarning, stacklevel=2)
        if self.store is not None:
            self.store.note_warm(self.cache)
        return n


# ----------------------------------------------------------------------
# persistent compiled-plan tier
# ----------------------------------------------------------------------

#: sidecar schema version (bumping it orphans old recipes, never
#: crashes a replica — loads are defensive like tune/db.py)
STORE_SCHEMA = 1


class PlanStore:
    """Persistent compiled-plan tier keyed by device fingerprint.

    Two cooperating layers close the cold-replica problem:

      * **JAX's compilation cache** (`enable()`): XLA executables are
        serialized under `<root>/<fingerprint>/xla/` (or the
        environment's JAX_COMPILATION_CACHE_DIR where it is set), so
        rebuilding a known plan on a fresh replica deserializes
        instead of recompiling.
      * **A plan-recipe sidecar** (`plankeys.json`): every plan the
        fleet ever built is recorded with enough to rebuild it
        (`SearcherProvider.prewarm`), merged atomically under a lock
        directory so concurrent replicas compose.

    The fingerprint is `tune/db.py`'s device fingerprint — the same
    cache-correctness boundary the tuning DB uses: an executable
    serialized on one chip generation / jaxlib never warms another.
    """

    def __init__(self, root: str, fingerprint: Optional[str] = None,
                 obs=None):
        from presto_tpu.tune.db import (device_fingerprint,
                                        fingerprint_key)
        if obs is None:
            from presto_tpu.obs import Observability, ObsConfig
            obs = Observability(ObsConfig(enabled=True))
        self.obs = obs
        self.fingerprint = fingerprint or fingerprint_key(
            device_fingerprint())
        fp_id = hashlib.sha1(
            self.fingerprint.encode()).hexdigest()[:16]
        self.root = os.path.abspath(root)
        self.dir = os.path.join(self.root, fp_id)
        # the environment's cache directory, when it places one, is
        # the only one (the store then shares it rather than its own)
        self.xla_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                        or os.path.join(self.dir, "xla"))
        self.sidecar = os.path.join(self.dir, "plankeys.json")
        from presto_tpu.pipeline.leaseledger import _LockDir
        self._lock = _LockDir(self.sidecar + ".lock")
        self.supported: Optional[bool] = None
        reg = obs.metrics
        self._g_warm = reg.gauge(
            "plancache_warm_fraction",
            "Fraction of persistently-known plans resident in the "
            "in-memory cache")
        self._c_prewarmed = reg.counter(
            "plancache_prewarmed_total",
            "Plans rebuilt from the persistent tier at replica start")
        self._g_known = reg.gauge(
            "plancache_store_plans",
            "Plans recorded in the persistent tier sidecar")

    # -- XLA compilation cache ----------------------------------------
    def enable(self) -> bool:
        """Point JAX's persistent compilation cache at this store's
        fingerprint directory (min-size/min-time thresholds dropped so
        every bucket executable persists).  Where the environment
        places the cache (JAX_COMPILATION_CACHE_DIR), that directory
        stays the only one and this store's xla_dir goes unused."""
        import jax
        os.makedirs(self.xla_dir, exist_ok=True)
        if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
            jax.config.update("jax_compilation_cache_dir", self.xla_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          -1)
        self.supported = True
        return True

    def xla_entries(self) -> int:
        """Serialized executables currently on disk (0 when the
        backend never persisted any)."""
        try:
            return sum(1 for n in os.listdir(self.xla_dir)
                       if not n.startswith("."))
        except OSError:
            return 0

    # -- recipe sidecar ------------------------------------------------
    def _load_sidecar(self) -> dict:
        try:
            with open(self.sidecar) as f:
                raw = json.load(f)
            if (isinstance(raw, dict)
                    and raw.get("schema") == STORE_SCHEMA
                    and isinstance(raw.get("plans"), dict)):
                return raw["plans"]
        except (OSError, ValueError):
            pass
        return {}

    def known(self) -> Dict[str, dict]:
        """{plan-key repr: rebuild recipe} recorded for this
        fingerprint."""
        plans = self._load_sidecar()
        self._g_known.set(len(plans))
        return plans

    def record(self, key: PlanKey, recipe: dict) -> None:
        """Merge one rebuild recipe into the sidecar (atomic
        read-modify-replace under the lock, so concurrent replicas
        compose instead of clobbering)."""
        from presto_tpu.io.atomic import atomic_write_text
        os.makedirs(self.dir, exist_ok=True)
        with self._lock():
            plans = self._load_sidecar()
            plans[repr(key)] = dict(recipe, recorded_at=time.time())
            atomic_write_text(self.sidecar, json.dumps(
                {"schema": STORE_SCHEMA, "plans": plans},
                indent=1, sort_keys=True))
        self._g_known.set(len(plans))

    # -- warm accounting ----------------------------------------------
    @staticmethod
    def _recipe_key(recipe: dict) -> Optional[PlanKey]:
        if recipe.get("kind") != "accel":
            return None
        try:
            from presto_tpu.search.accel import AccelConfig
            return accel_plan_key(AccelConfig(**recipe["acfg"]),
                                  float(recipe["T"]),
                                  int(recipe["numbins"]))
        except Exception:
            return None

    def warm_fraction(self, cache: PlanCache) -> float:
        """How much of the persistently-known working set is resident
        in `cache` — the readiness signal a router uses to keep
        traffic off a cold replica.  An empty store is vacuously warm
        (a brand-new fleet has nothing to wait for)."""
        keys = [k for k in (self._recipe_key(r)
                            for r in self.known().values())
                if k is not None]
        if not keys:
            frac = 1.0
        else:
            frac = (sum(1 for k in keys if cache.contains(k))
                    / float(len(keys)))
        self._g_warm.set(frac)
        return frac

    def note_warm(self, cache: PlanCache) -> None:
        self._c_prewarmed.inc(cache.stats()["size"])
        self.warm_fraction(cache)
