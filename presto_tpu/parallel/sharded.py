"""DM-sharded dedispersion + search pipeline steps (pjit over a Mesh).

The mpiprepsubband invariant (SURVEY.md §4.8): sharded output must
equal unsharded output for the same DMs.  Tests enforce this on an
8-device virtual CPU mesh; the driver's dryrun validates compile+run.

Sharding layout (mirrors mpiprepsubband.c:288-297's DM partition):
  raw blocks      [C, T]            replicated  (the MPI_Bcast analog)
  chan delays     [C]               replicated
  per-DM delays   [numdms, nsub]    sharded on 'dm'
  output series   [numdms, T]       sharded on 'dm'
No inter-device communication is needed after the input replication —
XLA sees the gather/sum is elementwise in the sharded axis.
"""

from __future__ import annotations

from functools import partial
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from presto_tpu.ops.dedispersion import (dedisp_subbands_block,
                                         float_dedisp_many_block,
                                         downsample_block)
from presto_tpu.parallel.mesh import (dm_sharding, replicated,
                                      shard_row_ranges)

def shard_dm_array(arr, mesh: Mesh):
    """Place [numdms, ...] array with the DM axis across mesh 'dm'."""
    return jax.device_put(arr, dm_sharding(mesh, np.ndim(arr)))


def make_sharded_dedisperse_step(mesh: Mesh, numsubbands: int,
                                 downsamp: int = 1):
    """jit-compiled (prev_raw, raw, prev_sub, chan_delays, dm_delays) ->
    (sub, series[numdms, T//downsamp]) with DM-sharded output.

    One streaming step of the prepsubband pipeline: channels->subbands
    on replicated data, then the DM fan-out sharded over devices.
    """
    out_shardings = (replicated(mesh), dm_sharding(mesh, 2))

    @partial(jax.jit, out_shardings=out_shardings)
    def step(prev_raw, raw, prev_sub, chan_delays, dm_delays):
        sub = dedisp_subbands_block(prev_raw, raw, chan_delays, numsubbands)
        series = float_dedisp_many_block(prev_sub, sub, dm_delays)
        return sub, downsample_block(series, downsamp)

    return step


def sharded_dedisperse_stream(blocks, chan_delays, dm_delays, mesh: Mesh,
                              numsubbands: int, downsamp: int = 1):
    """Dedisperse a [nblocks, C, T] stream at [numdms, nsub] delays with
    the DM axis sharded over `mesh`.  Returns [numdms, (nblocks-2)*T].

    Host-driven block loop (the real pipeline streams from disk); the
    carry logic matches ops.dedispersion.dedisperse_scan.
    """
    step = make_sharded_dedisperse_step(mesh, numsubbands, downsamp)
    chan_delays = jnp.asarray(chan_delays, dtype=jnp.int32)
    dm_delays = shard_dm_array(jnp.asarray(dm_delays, dtype=jnp.int32), mesh)
    prev_raw = jnp.asarray(blocks[0])
    raw = jnp.asarray(blocks[1])
    prev_sub = dedisp_subbands_block(prev_raw, raw, chan_delays,
                                     numsubbands)
    outs = []
    for i in range(2, len(blocks)):
        cur = jnp.asarray(blocks[i])
        sub, series = step(raw, cur, prev_sub, chan_delays, dm_delays)
        outs.append(series)
        prev_sub, raw = sub, cur
    return jnp.concatenate(outs, axis=1)


# ----------------------------------------------------------------------
# Static-delay DM-sharded dedispersion (per-device compiled plans)
# ----------------------------------------------------------------------

def _device_block_step(chan_delays: np.ndarray, dm_chunk: np.ndarray,
                       numsubbands: int, downsamp: int):
    """One device's composed streaming step with its DM sub-range's
    delays embedded as STATIC constants: the per-device twin of
    ops.dedispersion.make_block_step.  Both delay operands stay host
    NumPy so float_dedisp_many_block takes the static-slice fast path
    (and its `dedisp_dm_batch` tuning-DB bound) and the channel plan
    folds into the trace — nothing here pins the program to a device;
    it runs wherever its inputs are committed."""
    chan_np = np.ascontiguousarray(chan_delays, dtype=np.int32)
    dm_np = np.ascontiguousarray(dm_chunk, dtype=np.int32)

    @jax.jit
    def step(prev_raw, cur, prev_sub):
        sub = dedisp_subbands_block(prev_raw, cur, chan_np,
                                    numsubbands)
        series = float_dedisp_many_block(prev_sub, sub, dm_np)
        return sub, downsample_block(series, downsamp)

    return step


class ShardedDedispPlan:
    """DM-sharded streaming dedispersion with STATIC per-device delay
    plans — the mpiprepsubband partition as per-device (MPMD)
    dispatches instead of one traced-delay SPMD program.

    make_sharded_dedisperse_step passes the [numdms, nsub] delay table
    as a traced, device-sharded operand, which forces the vmap-of-
    dynamic-slice dedispersion path (the PR 5 caveat: the
    `dedisp_dm_batch` tune family never drove the multi-device path).
    Here each device gets its own compiled program with its DM
    sub-range's delays embedded as constants — the same static-slice
    fast path (and tuned unroll bound) the single-device loop uses,
    bit-identical output by the float_dedisp_many_block contract.
    Dispatches are issued per device back-to-back (async), so devices
    compute concurrently; the per-device outputs assemble into ONE
    global dm-sharded jax.Array with `concat()` — no host round-trip,
    which is exactly the hand-off the fused pipeline's sharded seam
    (pipeline/fusion.ShardedSeamBlock) consumes in place.

    Single-process only: the per-device dispatch model has no
    cross-process collective, so multi-host (-coordinator) runs keep
    the traced shard_map step.
    """

    def __init__(self, mesh: Mesh, numsubbands: int, downsamp: int,
                 chan_delays: np.ndarray, dm_delays: np.ndarray):
        self.mesh = mesh
        self.devices = list(mesh.devices.flat)
        self.numdms = int(dm_delays.shape[0])
        self.row_ranges = shard_row_ranges(mesh, self.numdms)
        self.numsubbands = int(numsubbands)
        self._chan_np = np.ascontiguousarray(chan_delays,
                                             dtype=np.int32)
        dm_np = np.asarray(dm_delays, dtype=np.int32)
        self.steps = [
            _device_block_step(self._chan_np, dm_np[lo:hi],
                               numsubbands, downsamp)
            for (lo, hi) in self.row_ranges]

    def put_block(self, blockT: np.ndarray):
        """Replicate one host channel-major block onto every mesh
        device (the MPI_Bcast analog) as per-device committed arrays."""
        return [jax.device_put(blockT, d) for d in self.devices]

    def prime(self, prev_raw, cur):
        """First-window subband carry, per device (the two-buffer SWAP
        priming of the reference's streaming loop)."""
        return [dedisp_subbands_block(pr, cu, self._chan_np,
                                      self.numsubbands)
                for pr, cu in zip(prev_raw, cur)]

    def step(self, prev_raw, cur, prev_sub):
        """One streaming step on every device: returns (subs, series)
        as per-device lists; all dispatches are queued before any
        result is awaited, so the mesh computes concurrently."""
        subs, series = [], []
        for st, pr, cu, ps in zip(self.steps, prev_raw, cur, prev_sub):
            sub, ser = st(pr, cu, ps)
            subs.append(sub)
            series.append(ser)
        return subs, series

    def concat(self, outs):
        """[per-block list of per-device series] -> ONE global
        [numdms, T] jax.Array sharded on the mesh 'dm' axis, each
        shard living on the device that computed it."""
        parts = [jnp.concatenate([blk[k] for blk in outs], axis=1)
                 for k in range(len(self.devices))]
        shape = (self.numdms, int(parts[0].shape[1]))
        return jax.make_array_from_single_device_arrays(
            shape, dm_sharding(self.mesh, 2), parts)


# ----------------------------------------------------------------------
# Sequence-sharded six-step FFT (the out-of-core / huge-FFT analog)
# ----------------------------------------------------------------------

def sixstep_fft(x, rows: int):
    """Complex DFT of x (length N = rows*cols) via the six-step
    decomposition (reference fastffts.c:38-195 / twopass_real_fwd.c:10):
      view x as [rows, cols] row-major -> FFT columns (length rows)
      -> twiddle W_N^(j2*k1) -> FFT rows (length cols) -> transpose.
    Shards naturally: with the row axis sharded over 'seq', the column
    FFT is local, the twiddle is elementwise, and the final transpose
    is XLA's all-to-all — the disk-transpose of the reference's
    out-of-core FFT becomes ICI traffic.

    Returns X[k] == jnp.fft.fft(x) (validated in tests).
    """
    N = x.shape[-1]
    cols = N // rows
    # x[j1*cols + j2] -> A[j1, j2]
    A = x.reshape(rows, cols)
    # sum over j1: FFT along axis 0 (length rows) for each j2 -> B[k1, j2]
    B = jnp.fft.fft(A, axis=0)
    # twiddle W_N^(j2*k1)
    k1 = jnp.arange(rows)[:, None]
    j2 = jnp.arange(cols)[None, :]
    tw = jnp.exp(-2j * jnp.pi * (k1 * j2) / N).astype(B.dtype)
    C = B * tw
    # sum over j2: FFT along axis 1 (length cols) -> D[k1, k2]
    D = jnp.fft.fft(C, axis=1)
    # X[k1 + rows*k2] = D[k1, k2] -> transpose then ravel
    return D.T.reshape(-1)


def make_sharded_sixstep_fft(mesh: Mesh, rows: int):
    """jit'd sequence-sharded FFT: input pairs [N,2] float32 sharded on
    'seq' (if present, else 'dm'), output pairs sharded the same way.

    The intermediate [rows, cols] matrix is sharded on the row axis;
    jnp.fft.fft along the sharded axis forces XLA to insert the
    all-to-all — exactly the six-step communication pattern.
    """
    axis = "seq" if "seq" in mesh.axis_names else mesh.axis_names[0]
    io_sharding = NamedSharding(mesh, P(axis, None))

    @partial(jax.jit, out_shardings=io_sharding)
    def fft_pairs(xp):
        x = xp[..., 0] + 1j * xp[..., 1]
        X = sixstep_fft(x, rows)
        return jnp.stack([X.real, X.imag], axis=-1).astype(jnp.float32)

    return fft_pairs


# ----------------------------------------------------------------------
# DM-batch-sharded accelsearch (the search-stage mpiprepsubband analog)
# ----------------------------------------------------------------------


def compact_search_fn(searcher, mesh: Mesh, g, scanner, shape,
                      compact_m: int):
    """The jitted per-shard program of sharded_accel_search_many: the
    fused build+scan of each local trial, compacted on-shard, over a
    [numdms, numbins, 2] batch of ``shape``.  Cached on the searcher
    (jax.jit caches on function identity; a fresh closure per call
    would re-trace the fused build+scan every survey group)."""
    from presto_tpu.search.accel import compact_scan_packed
    axis = mesh.axis_names[0]
    fkey = ("sharded_search_c", mesh, g.key, scanner, tuple(shape),
            compact_m)
    fn = searcher._fn_cache.get(fkey)
    if fn is None:
        build_body, scan_body = g.build_body, scanner.body

        def per_shard(local, kern, sc):
            def per_dm(_, x):
                packed = scan_body(build_body(x, kern), sc)
                return None, compact_scan_packed(packed, compact_m)
            _, comp = jax.lax.scan(per_dm, None, local)
            return comp                      # [nd_loc, 3, m]

        # check_vma off: the Pallas builder and reducer declare their
        # outputs without mesh-axis variance, which shard_map's check
        # refuses on the chip
        fn = jax.jit(jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(axis), P(), P()),
            out_specs=P(axis), check_vma=False))
        searcher._fn_cache[fkey] = fn
    return fn


def sharded_accel_search_many(searcher, pairs_batch, mesh: Mesh,
                              slab: int = 1 << 20,
                              compact_m: int = None, obs=None):
    """Accelsearch over a DM fan-out with the trial axis sharded over
    `mesh` — the search-stage application of the mpiprepsubband
    invariant (SURVEY §4.8; mpiprepsubband.c:288-297's DM partition):
    each device owns numdms/n trials and runs the IDENTICAL fused
    build+scan program on its shard sequentially (one plane resident
    per device at a time), with no cross-device communication at all.
    Each trial's candidates COMPACT on-shard before the gather
    (accel.compact_scan_packed: the dense per-stage top-k tensors are
    the dominant cross-device traffic of a sharded survey — ~100 MB
    per 512 trials over ICI/DCN vs ~12 MB compacted); host collection
    decodes to lists byte-identical to the single-device path — tests
    pin sharded lists == single-device lists — with a lossless dense
    re-gather fallback for trials that overflow the budget.

    searcher: an AccelSearch whose geometry matches pairs_batch's
    numbins.  pairs_batch: [numdms, numbins, 2] float32 (host).
    Returns per-DM candidate lists (search_many semantics).
    """
    from presto_tpu.search.accel import COMPACT_CANDS
    if compact_m is None:
        compact_m = COMPACT_CANDS
    cfg = searcher.cfg
    if cfg.wmax:
        # jerk searches keep the per-w plane-cache loop (no sharded
        # variant yet) — same results, device-serial
        return searcher.search_many(pairs_batch, slab=slab)
    if isinstance(pairs_batch, jax.Array):
        batch = pairs_batch          # device-resident: never round-
        if batch.dtype != jnp.float32:    # trip through the host
            batch = batch.astype(jnp.float32)
    else:
        batch = np.ascontiguousarray(np.asarray(pairs_batch,
                                                np.float32))
    nd = int(batch.shape[0])
    if nd == 0:
        return []
    n = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    axis = mesh.axis_names[0]
    g = searcher._build_plan_ns()
    if g is None:
        return [[] for _ in range(nd)]
    splan = searcher._slab_plan(g.plane_numr, slab)
    if splan is None:
        return [[] for _ in range(nd)]
    slab_, k, scanner, start_cols = splan
    kern_dev = searcher._kern_bank_dev()
    build_body, scan_body = g.build_body, scanner.body
    # pad the DM axis to a mesh multiple (padded trials re-search the
    # last spectrum; their results are dropped)
    pad = (-nd) % n
    if pad:
        xp = jnp if isinstance(batch, jax.Array) else np
        batch = xp.concatenate([batch] + [batch[-1:]] * pad)
    scols = jnp.asarray(np.asarray(start_cols, np.int32))

    fn = compact_search_fn(searcher, mesh, g, scanner, batch.shape,
                           compact_m)
    if obs is not None:
        from presto_tpu.obs import costmodel
        costmodel.probe(obs, "accel_search", fn, jnp.asarray(batch),
                        kern_dev, scols)
    comp = np.asarray(fn(jnp.asarray(batch), kern_dev, scols))
    dense = None
    out = []
    for d in range(nd):
        try:
            out.append(searcher.collect_compacted(
                comp[d], start_cols, requested_m=compact_m))
        except ValueError:
            # budget overflow (pathological trial): lossless dense
            # re-gather, compiled only when needed
            if dense is None:
                dkey = ("sharded_search", mesh, g.key, slab_, k,
                        batch.shape)
                dfn = searcher._fn_cache.get(dkey)
                if dfn is None:
                    def per_shard_dense(local, kern, sc):
                        def per_dm(_, x):
                            return None, scan_body(
                                build_body(x, kern), sc)
                        _, packed = jax.lax.scan(per_dm, None, local)
                        return jnp.moveaxis(packed, 1, 0)
                    dfn = jax.jit(jax.shard_map(
                        per_shard_dense, mesh=mesh,
                        in_specs=(P(axis), P(), P()),
                        out_specs=P(None, axis), check_vma=False))
                    searcher._fn_cache[dkey] = dfn
                from presto_tpu.search.accel import _unpack_scan
                dense = _unpack_scan(np.asarray(
                    dfn(jnp.asarray(batch), kern_dev, scols)))
            vals, cidx, zrow = dense
            out.append(searcher._dedup_sort(searcher._collect_group(
                vals[d], cidx[d], zrow[d], start_cols)))
    return out
