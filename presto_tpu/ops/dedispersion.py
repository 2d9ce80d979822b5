"""Dedispersion: delay planning (host, float64) + shift-and-sum (device).

Parity targets: reference src/dispersion.c.
  delay_from_dm            dispersion.c:30-39   Δt = DM / (0.000241 f²)
  dedisp_delays            dispersion.c:54-73
  subband_delays           dispersion.c:103-121
  subband_search_delays    dispersion.c:124-162
  dedisp_subbands          dispersion.c:165-203 (hot loop 1a)
  float_dedisp             dispersion.c:206-229 (hot loop 1b)
  combine_subbands         dispersion.c:232-287 (profile-domain, see ops/fold.py)

Streaming convention.  The reference processes blocks with a two-buffer
(lastdata, data) window: output sample t of a block whose window starts
at stream position S is  out[t] = Σ_ch  x_ch[S + t + delay_ch]  (delays
in bins, 0 <= delay < block_len).  Here that becomes: concatenate the
previous and current block along time and gather each channel at offset
delay_ch.  The carry (previous block) is explicit state — no statics —
so the whole stream is a `lax.scan`.

Dtype policy.  Delays are planned in float64 numpy on the host and
rounded to int32 bins exactly as the reference does; per-sample compute
is float32 on device.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from functools import partial

from presto_tpu.utils.psr import doppler


# ----------------------------------------------------------------------
# Host-side delay planning (float64)
# ----------------------------------------------------------------------

def delay_from_dm(dm, freq_emitted):
    """Dispersion delay in seconds. Parity: dispersion.c:30-39."""
    freq = np.asarray(freq_emitted, dtype=np.float64)
    with np.errstate(divide="ignore"):
        d = dm / (0.000241 * freq * freq)
    return np.where(freq == 0.0, 0.0, d)


def dm_from_delay(delay, freq_emitted):
    """Inverse of delay_from_dm. Parity: dispersion.c:42-51."""
    freq = np.asarray(freq_emitted, dtype=np.float64)
    return np.where(freq == 0.0, 0.0, delay * 0.000241 * freq * freq)


def dedisp_delays(numchan, dm, lofreq, chanwidth, voverc=0.0):
    """Per-channel delays (s) at `dm`; lofreq = center freq of lowest channel.

    Parity: dispersion.c:54-73 (including Doppler correction of each
    channel frequency by the observatory radial velocity).
    """
    freqs = doppler(lofreq + np.arange(numchan, dtype=np.float64) * chanwidth,
                    voverc)
    return delay_from_dm(dm, freqs)


def subband_delays(numchan, numsubbands, dm, lofreq, chanwidth, voverc=0.0):
    """Delays (s) for the highest-frequency channel of each subband.

    Parity: dispersion.c:103-121.
    """
    chan_per_subband = numchan // numsubbands
    subbandwidth = chanwidth * chan_per_subband
    losub_hifreq = lofreq + subbandwidth - chanwidth
    return dedisp_delays(numsubbands, dm, losub_hifreq, subbandwidth, voverc)


def subband_search_delays(numchan, numsubbands, dm, lofreq, chanwidth,
                          voverc=0.0):
    """Per-channel delays for subband dedispersion at a nominal `dm`.

    Each channel's full delay minus the delay of the *highest* channel in
    its subband, so subbands stay internally dedispersed but offset as
    wholes — ready for a later float_dedisp over subbands.
    Parity: dispersion.c:124-162.
    """
    chan_per_subband = numchan // numsubbands
    sdelays = subband_delays(numchan, numsubbands, dm, lofreq, chanwidth,
                             voverc)
    delays = dedisp_delays(numchan, dm, lofreq, chanwidth, voverc)
    return delays - np.repeat(sdelays, chan_per_subband)


def delays_to_bins(delays_sec, dt):
    """Seconds -> integer sample bins, rounded half-up like the reference
    (prepsubband.c uses (int)(delay/dt + 0.5))."""
    return np.floor(np.asarray(delays_sec, dtype=np.float64) / dt
                    + 0.5).astype(np.int32)


# ----------------------------------------------------------------------
# Device ops (jit-compiled, float32)
# ----------------------------------------------------------------------

def _shifted_row(x2_row, delay, numpts):
    """x2_row[delay : delay + numpts] with a traced integer delay.

    lax.dynamic_slice, NOT a gather: minor-axis gathers are the
    dominant TPU scan-time cost for this access pattern (measured 35x
    slower for the 128-DM x 2^17 float_dedisp block on v5e), while a
    dynamic slice is a straight windowed copy.
    """
    return jax.lax.dynamic_slice(x2_row, (delay,), (numpts,))


_UNROLL_LIMIT = 256     # rows unrolled in the jit graph before
                        # switching to a scan (program size vs the
                        # small per-step scan overhead)


def _accum_shifted_rows(x2, delays, numpts):
    """Σ_r x2[r, d_r : d_r + numpts], row-ascending accumulation.

    Unrolled for few rows (fastest); lax.scan beyond _UNROLL_LIMIT so
    HLO size stays O(1) in the channel count (a 4096-channel
    filterbank would otherwise put ~8k slice/add ops in every scan
    body).  Both paths keep the dynamic-slice access pattern and the
    same row order, so results are bit-identical.
    """
    R = x2.shape[0]
    if R <= _UNROLL_LIMIT:
        acc = _shifted_row(x2[0], delays[0], numpts)
        for r in range(1, R):
            acc = acc + _shifted_row(x2[r], delays[r], numpts)
        return acc

    def body(acc, xs):
        row, d = xs
        return acc + _shifted_row(row, d, numpts), None

    acc0 = jnp.zeros((numpts,), x2.dtype)
    acc, _ = jax.lax.scan(body, acc0, (x2, jnp.asarray(delays)))
    return acc


@partial(jax.jit, static_argnames=("numsubbands",))
def dedisp_subbands_block(lastdata, data, delays, numsubbands):
    """Channels -> subbands shift-and-add for one streaming block.

    lastdata, data: [numchan, numpts] float32, channel-major (all of a
    channel's samples contiguous), ascending frequency — the same layout
    the reference's prep_subbands produces after its r2r transpose.
    delays: [numchan] int32 bins, each < numpts.

    Returns [numsubbands, numpts]: out[s, t] = Σ_{c in s} window_c[t+d_c]
    with the window starting at the lastdata block.
    Parity: dispersion.c:165-203.  Accumulation is channel-ascending
    within each subband, matching the reference's inner loop order.
    """
    numchan, numpts = lastdata.shape
    x2 = jnp.concatenate([lastdata, data], axis=1)
    per = numchan // numsubbands
    x3 = x2.reshape(numsubbands, per, 2 * numpts)
    d2 = jnp.asarray(delays).reshape(numsubbands, per)
    if numchan <= _UNROLL_LIMIT:      # bound TOTAL unrolled rows
        return jnp.stack([_accum_shifted_rows(x3[s], d2[s], numpts)
                          for s in range(numsubbands)])
    return jax.lax.map(
        lambda xs: _accum_shifted_rows(xs[0], xs[1], numpts), (x3, d2))


@jax.jit
def float_dedisp_block(lastdata, data, delays, approx_mean=0.0):
    """Subbands (or channels) -> one dedispersed series for one block.

    lastdata, data: [numchan, numpts] float32 channel-major.
    delays: [numchan] int32.  Returns [numpts].
    Parity: dispersion.c:206-229 (which takes time-major input; layout
    here is channel-major for TPU-friendly contiguity — semantics equal).
    """
    numchan, numpts = lastdata.shape
    x2 = jnp.concatenate([lastdata, data], axis=1)
    return _accum_shifted_rows(x2, delays, numpts) - approx_mean


def float_dedisp_many_block(lastdata, data, delays_dm, approx_mean=0.0,
                            batch_limit=None):
    """float_dedisp over many DM trials at once.

    lastdata, data: [nsub, numpts]; delays_dm: [numdms, nsub] int32.
    Returns [numdms, numpts].  This is hot loop 1b batched over the DM
    axis — the axis the sharded plan splits over devices.

    When delays_dm is a HOST array (np.ndarray — the normal case: DM
    plans are host-computed constants), every slice is static and each
    DM row's nsub-term sum fuses into ONE XLA pass with the
    accumulator in registers — ~2.4x faster on v5e than the
    traced-delay vmap (whose batched dynamic slices lower to
    gathers).  Traced delays (the DM-sharded mesh step, which splits
    delays_dm across devices) keep the vmap-of-dynamic-slice path.
    Both accumulate subband-ascending, matching the reference's inner
    loop (dispersion.c:165-229) bit-for-bit.

    NOT jitted itself: the dispatch must see the host array.  Callers
    may close over it inside their own jit — with np delays the
    static path's constants embed in the enclosing trace.  Plans past
    the batch bound total slices run the SAME static path in DM
    batches (one compiled program per batch, outputs concatenated) so
    the unrolled HLO stays bounded while throughput keeps the fused
    full-width passes; only traced (device-array) delays use the vmap
    path.

    `batch_limit` overrides the unroll bound (numdms*nsub slices per
    compiled batch).  None resolves it: the tuning DB's
    `dedisp_dm_batch` entry for this subband count when tuning is
    active (presto_tpu/tune), else _STATIC_SLICE_LIMIT.  The bound
    only partitions the DM axis — each row's subband-ascending sum is
    identical in any partition, so tuned and untuned outputs are
    byte-equal.
    """
    if isinstance(delays_dm, np.ndarray):
        limit = (_resolve_batch_limit(delays_dm.shape[1])
                 if batch_limit is None else max(int(batch_limit), 1))
        if delays_dm.size <= limit:
            return _static_fn_for(delays_dm)(lastdata, data,
                                             float(approx_mean))
        # bigger plans (the 512-DM x 64-sub per-device target-scale
        # share) stay on the fast path in DM batches: each batch is
        # its own compiled program, outputs concatenate
        per = max(1, limit // delays_dm.shape[1])
        outs = [_static_fn_for(delays_dm[i:i + per])(
                    lastdata, data, float(approx_mean))
                for i in range(0, delays_dm.shape[0], per)]
        return jnp.concatenate(outs, axis=0)
    return _float_dedisp_vmap(lastdata, data, jnp.asarray(delays_dm),
                              approx_mean)


_STATIC_SLICE_LIMIT = 16384   # numdms*nsub unroll bound
_static_fns: dict = {}        # delay-plan bytes -> compiled closure


def _resolve_batch_limit(nsub: int) -> int:
    """The DM-batch unroll bound for an nsub-subband plan: a measured
    tuning-DB value when tuning is active (clamped to >= nsub so a
    batch always holds at least one DM row), else the built-in
    default.  One branch when tuning is disabled."""
    from presto_tpu import tune
    if not tune.enabled():
        return _STATIC_SLICE_LIMIT
    cfg = tune.best("dedisp_dm_batch", tune.key_dedisp_batch(nsub))
    if cfg:
        try:
            return max(int(cfg.get("limit", 0)), int(nsub), 1)
        except (TypeError, ValueError):
            pass
    return _STATIC_SLICE_LIMIT


def _static_fn_for(delays_dm: np.ndarray):
    """Compiled static-slice closure for one delay plan, memoized on
    the plan's bytes — prepsubband calls this once per streamed block
    with the same plan, and rebuilding + jit-cache-hashing a
    numdms*nsub static tuple every call is measurable host overhead."""
    key = (delays_dm.shape, delays_dm.dtype.str, delays_dm.tobytes())
    fn = _static_fns.get(key)
    if fn is None:
        while len(_static_fns) > 32:   # bound retained programs:
            # evict the OLDEST only — clearing everything would make
            # plans whose batch count exceeds the bound re-jit every
            # streamed block (dict preserves insertion order)
            _static_fns.pop(next(iter(_static_fns)))
        dkey = tuple(map(tuple, delays_dm.astype(np.int64).tolist()))

        @jax.jit
        def fn(lastdata, data, approx_mean):
            return _float_dedisp_static_body(lastdata, data, dkey,
                                             approx_mean)
        _static_fns[key] = fn
    return fn


@jax.jit
def _float_dedisp_vmap(lastdata, data, delays_dm, approx_mean=0.0):
    nsub, numpts = lastdata.shape
    x2 = jnp.concatenate([lastdata, data], axis=1)       # [nsub, 2T]

    def per_dm(dly):                                     # dly: [nsub]
        return _accum_shifted_rows(x2, dly, numpts)

    return jax.vmap(per_dm)(delays_dm) - approx_mean


def _float_dedisp_static_body(lastdata, data, dkey, approx_mean):
    """Static-delay float_dedisp: per-DM sums of statically-sliced
    subband windows (see float_dedisp_many_block).  Slices are 1-D
    views of the flattened subband buffer — [1, T] 2-D rows leave 7 of
    8 sublanes idle on TPU and XLA materializes them; flat slices keep
    each row's sum a single fused full-width pass."""
    nsub, numpts = lastdata.shape
    x2 = jnp.concatenate([lastdata, data], axis=1)       # [nsub, 2T]
    flat = x2.reshape(-1)
    w = 2 * numpts
    rows = []
    for dly in dkey:
        acc = jax.lax.slice(flat, (int(dly[0]),),
                            (int(dly[0]) + numpts,))
        for s in range(1, nsub):
            o = s * w + int(dly[s])
            acc = acc + jax.lax.slice(flat, (o,), (o + numpts,))
        rows.append(acc)
    return jnp.stack(rows, axis=0) - approx_mean


def make_block_step(chan_delays, dm_delays, numsubbands, downsamp=1):
    """ONE-dispatch streaming step for the prep family's block loop:
    channels->subbands shift-add + per-DM dedispersion + downsample
    composed into a single jitted program.

    The separate-op loop paid the per-dispatch overhead three times
    per streamed block; the survey's fused pipeline (pipeline/
    fusion.py) issues blocks back-to-back, so the composed step cuts
    the per-block dispatch count to one.  Results are bit-identical
    to calling the three ops separately — XLA preserves the add order
    of the composed graph, and the DM-sharded mesh step
    (parallel/sharded.make_sharded_dedisperse_step) has always relied
    on exactly this composition equivalence, pinned by the multi-host
    byte-equality tests.

    chan_delays: [numchan] int32 bins; dm_delays: [numdms, nsub] —
    keep it a HOST np.ndarray so the static-slice fast path embeds
    the plan as constants (see float_dedisp_many_block).

    Returns step(prev_raw, cur, prev_sub) -> (sub, series).
    """
    chan_dev = jnp.asarray(chan_delays, dtype=jnp.int32)

    @jax.jit
    def step(prev_raw, cur, prev_sub):
        sub = dedisp_subbands_block(prev_raw, cur, chan_dev,
                                    numsubbands)
        series = float_dedisp_many_block(prev_sub, sub, dm_delays)
        series = downsample_block(series, downsamp)
        return sub, series

    return step


def dedisperse_series(data, delays):
    """Whole-series dedispersion of an in-memory [numchan, N] array.

    out[t] = Σ_c data[c, t + d_c], zero beyond the end; valid for
    t < N - max(d).  Equivalent to streaming the block ops over the
    series with a zero final block.
    """
    numchan, N = data.shape
    maxd = int(jnp.max(delays)) if not isinstance(delays, np.ndarray) \
        else int(np.max(delays))
    return _dedisperse_series_jit(data, jnp.asarray(delays, jnp.int32),
                                  maxd)


@partial(jax.jit, static_argnames=("maxd",))
def _dedisperse_series_jit(data, delays, maxd):
    # one dispatch for the whole series: the unrolled slice/add loop
    # would otherwise issue ~2*numchan eager ops, each paying a
    # dispatch
    numchan, N = data.shape
    pad = jnp.zeros((numchan, maxd), dtype=data.dtype)
    x = jnp.concatenate([data, pad], axis=1)
    return _accum_shifted_rows(x, delays, N)


@partial(jax.jit, static_argnames=("factor",))
def downsample_block(x, factor):
    """Time-average consecutive groups of `factor` samples.

    x: [..., T] with T divisible by factor.  The reference *sums* then
    divides by the downsample factor in prepsubband.c:967-984 — i.e. a
    mean, preserved here.
    """
    if factor == 1:
        return x
    shape = x.shape[:-1] + (x.shape[-1] // factor, factor)
    return x.reshape(shape).mean(axis=-1)


def dedisperse_scan(blocks, delays_dm, numsubbands, approx_mean=0.0,
                    downsamp=1):
    """Full streaming pipeline over in-memory blocks via lax.scan.

    blocks: [nblocks, numchan, numpts] channel-major float32 (nblocks>=2).
    delays_dm: dict with
        'chan': [numchan] int32 subband_search_delays bins (chan->subband)
        'dm':   [numdms, nsub] int32 per-DM subband delay bins
    Returns [numdms, (nblocks-2) * numpts // downsamp], the dedispersed
    series starting at stream sample 0.

    Stream algebra: subband block j (from raw blocks j-1, j) covers
    subband-stream window [(j-1)T, jT); output block k (from subband
    blocks k, k+1) covers [(k-1)T, kT).  So the first output needs raw
    blocks 0..2 — the first two reads only prime the carry, mirroring
    the reference's two-buffer SWAP priming (prepsubband.c:985-991).
    """
    chan_delays = jnp.asarray(delays_dm["chan"], dtype=jnp.int32)
    # host np DM delays stay host-side: float_dedisp_many_block's
    # static-slice fast path needs them as Python constants
    dm_delays = delays_dm["dm"]
    if not isinstance(dm_delays, np.ndarray):
        dm_delays = jnp.asarray(dm_delays, dtype=jnp.int32)

    def step(carry, block):
        last_raw, last_sub = carry
        sub = dedisp_subbands_block(last_raw, block, chan_delays, numsubbands)
        out = float_dedisp_many_block(last_sub, sub, dm_delays, approx_mean)
        out = downsample_block(out, downsamp)
        return (block, sub), out

    sub1 = dedisp_subbands_block(blocks[0], blocks[1], chan_delays,
                                 numsubbands)
    (_, _), outs = jax.lax.scan(step, (blocks[1], sub1), blocks[2:])
    # outs: [nblocks-2, numdms, numpts//downsamp] -> [numdms, T]
    return jnp.moveaxis(outs, 0, 1).reshape(dm_delays.shape[0], -1)
