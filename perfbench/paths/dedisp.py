"""prepsubband's streaming path, driven from a host pool of raw blocks.

The window runs what ``apps/prepsubband.run`` runs per block, with no
file I/O: the reader's 8-bit decode (``sigproc.decode_spectra_block``),
``BlockPrep`` (clipping with its carry state), the transpose, the
``DoubleBufferedIngest`` worker thread, the upload and the composed
``dedispersion.make_block_step``.  The geometry comes from the
program's own ``plan_delays`` and ``stream_blocklen``.  The loop body
is a copy of prepsubband's (no public per-block entry exists), so the
cell does not measure ``prepsubband.run``'s own loop: a change there
moves it only where it changes what the copy calls.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from perfbench import counts, synth
from perfbench.reference import dedisp_ref


def band(config: dict, nchan: int):
    """(fch1, foff, lofreq, chanwidth) of the recorded band; lofreq is
    computed as the SIGPROC header computes it."""
    chanbw = config["bw_mhz"] / nchan
    lo = config["fctr_mhz"] - config["bw_mhz"] / 2 + chanbw / 2
    if config.get("band_descending"):
        fch1, foff = lo + (nchan - 1) * chanbw, -chanbw
        return fch1, foff, fch1 + (nchan - 1) * foff, chanbw
    return lo, chanbw, lo, chanbw


WARMUP_BLOCKS = 4


class Cell:
    def __init__(self, config, traffic, spans, rehearse=False):
        t = dict(traffic)
        if rehearse:
            t.update({k: v for k, v in traffic["rehearse"].items()
                      if k != "check"})
            t["check"] = dict(traffic["check"],
                              **traffic["rehearse"].get("check", {}))
        self.config, self.traffic, self.spans = config, t, spans
        self.rehearse = rehearse
        self.nchan = int(t.get("nchan", config["nchan"]))
        self.ndms = int(t["ndms"])
        self.nsub = int(config["nsub"])
        self.dt = float(config["dt_s"])
        self.descending = bool(config.get("band_descending"))
        self.keep = []          # window outputs (device), step order
        self.window_steps = []  # stream block index of each output

    # -- set-up ----------------------------------------------------------

    def setup(self, seed: int) -> None:
        import jax
        import jax.numpy as jnp
        from presto_tpu.apps.common import BlockPrep, stream_blocklen
        from presto_tpu.apps.prepsubband import plan_delays
        from presto_tpu.io.sigproc import (FilterbankHeader,
                                           decode_spectra_block)
        from presto_tpu.ops import dedispersion as dd
        from presto_tpu.pipeline import fusion

        self.seed = seed
        t, c = self.traffic, self.config
        fch1, foff, _lo, _cw = band(c, self.nchan)
        hdr = FilterbankHeader(
            fch1=fch1, foff=foff, nchans=self.nchan, nbits=c["nbits"],
            tsamp=self.dt, nifs=1, N=int(c["nsamp"]))
        args = SimpleNamespace(
            lodm=t["lodm"], numdms=self.ndms, dmstep=t["dmstep"],
            nsub=self.nsub, subdm=None, clip=c["clip_sigma"],
            noclip=False, invert=False, zerodm=False, runavg=False)
        _dms, chan_bins, dm_bins = plan_delays(hdr, args)
        self.blocklen = L = int(t.get("blocklen") or stream_blocklen(
            self.nchan, max(int(chan_bins.max()), int(dm_bins.max()))))
        self.pool = synth.raw_pool(t, seed, int(t["pool_blocks"]), L,
                                   self.nchan)
        prep = BlockPrep(self.nchan, self.dt, args)
        self.step = dd.make_block_step(chan_bins, np.asarray(dm_bins),
                                       self.nsub, 1)
        chan_d = jnp.asarray(chan_bins)
        spans, pool = self.spans, self.pool

        def produce(j, stop, span):
            """prepsubband's _produce_blocks over the in-memory pool,
            blocks j..stop-1 (without end if stop is None)."""
            while stop is None or j < stop:
                with spans(span):
                    block = decode_spectra_block(hdr, pool[j % len(pool)], L)
                    block = prep(block, j * L)
                    blockT = np.ascontiguousarray(block.T)
                yield j, blockT
                j += 1

        # prepsubband.run's ingest without a seam (the seam's depth is
        # the survey's; this streams one file's blocks)
        def ingest_for(j, stop, span):
            return fusion.DoubleBufferedIngest(
                produce(j, stop, span), depth=fusion.DEFAULT_INGEST_DEPTH)

        self.ingest_for = ingest_for
        self.prev_raw = self.prev_sub = None
        self.subbands = lambda a, b: dd.dedisp_subbands_block(
            a, b, chan_d, self.nsub)
        # warm-up: the priming subband pass and two composed steps, from
        # an ingest that decodes these 4 blocks and no more (the clip
        # state carries on in ``prep``)
        self.ingest = self.ingest_for(0, WARMUP_BLOCKS, "warmup_decode")
        for _ in range(WARMUP_BLOCKS):
            out = self._next()
        jax.block_until_ready(out)
        self.ingest.close()
        self.ingest = None

    def _next(self):
        """One streamed block through the device (prepsubband's loop
        body); returns the dedispersed series when this block completes
        a step."""
        import jax.numpy as jnp
        j, blockT = next(self.ingest)
        with self.spans("upload"):
            cur = jnp.asarray(blockT)
        series = None
        if self.prev_raw is not None:
            if self.prev_sub is None:
                sub = self.subbands(self.prev_raw, cur)
            else:
                sub, series = self.step(self.prev_raw, cur, self.prev_sub)
            self.prev_sub = sub
        self.prev_raw = cur
        self.j = j
        return series

    # -- the measured window ----------------------------------------------

    def window(self, seconds: float) -> dict:
        import jax
        blocks = int(self.traffic.get("blocks", 0)) if self.rehearse else 0
        t0 = time.perf_counter()
        # a fresh ingest: every block the window counts is decoded in it
        self.ingest = self.ingest_for(self.j + 1, None, "decode")
        last = None
        while True:
            with self.spans("block"):
                series = self._next()
            if series is not None:
                self.keep.append(series)
                self.window_steps.append(self.j)
                last = series
            done = len(self.keep)
            if blocks:
                if done >= blocks:
                    break
            elif time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(last)
        elapsed = time.perf_counter() - t0
        obs_s = done * self.blocklen * self.dt
        return {"elapsed_s": elapsed, "steps": done,
                "dedisp_rate": self.ndms * obs_s / elapsed,
                "attempted": done}

    def close(self) -> None:
        ingest = getattr(self, "ingest", None)
        if ingest is not None:
            ingest.close()
            self.ingest = None

    # -- per-layer counts ---------------------------------------------------

    def required(self) -> dict:
        """Required work of one block step at this cell's shapes."""
        return {"dedisp": counts.dedisp_step(self.nchan, self.nsub,
                                             self.ndms, self.blocklen)}

    # -- correctness ----------------------------------------------------------

    def sample(self):
        """(window output indices, DM rows) drawn from the seed."""
        chk = self.traffic["check"]
        g = synth.rng(self.seed, 6)
        n = len(self.keep)
        picks = sorted(g.choice(n, size=min(chk["steps"], n),
                                replace=False).tolist())
        rows = sorted(g.choice(self.ndms, size=min(chk["dms"], self.ndms),
                               replace=False).tolist())
        return picks, rows

    def program_outputs(self, picks, rows):
        import jax.numpy as jnp
        ridx = jnp.asarray(np.asarray(rows, np.int32))
        return {self.window_steps[k]: np.asarray(self.keep[k][ridx])
                for k in picks}

    def release(self) -> None:
        """Download the sampled outputs, then drop every device array."""
        self.picks, self.rows = self.sample()
        self.prog = self.program_outputs(self.picks, self.rows)
        self.keep = []
        self.prev_raw = self.prev_sub = None

    def reference(self, steps, rows, lowp=False):
        c, t = self.config, self.traffic
        _f1, _fo, lofreq, chanw = band(c, self.nchan)
        chan_bins, dm_bins = dedisp_ref.plan(
            self.nchan, self.nsub, lofreq, chanw, t["lodm"], self.ndms,
            t["dmstep"], self.dt)
        return dedisp_ref.outputs(
            lambda j: self.pool[j % len(self.pool)], max(steps) + 1, steps,
            rows, self.blocklen, self.nchan, self.nsub, chan_bins, dm_bins,
            c["clip_sigma"], self.descending, lowp=lowp)

    def check(self, control: bool = False):
        """(checks [(name, value, limit)], failed, control readings)."""
        prog, rows = self.prog, self.rows
        steps = sorted(prog)
        ref = self.reference(steps, rows)
        limit = self.traffic["check"]["limits"]["dedisp_gap"]
        gaps = {j: dedisp_ref.gap(prog[j], ref[j]) for j in steps}
        failed = sum(1 for g in gaps.values() if not g <= limit)
        checks = [("dedisp_gap", max(gaps.values()), limit)]
        ctl = None
        if control:
            low = self.reference(steps, rows, lowp=True)
            ctl = {"dedisp_gap": max(dedisp_ref.gap(low[j], ref[j])
                                     for j in steps)}
        return checks, failed, ctl
