"""Inputs made from the seed, on the device: the benchmark's traffic.

Both the timed path and the reference read these; neither makes them.
Per-trial parameters are drawn on the host from the seed (numpy), the
bulk samples on the device in one jitted call per chunk or block, so
the same seed gives the same bytes on the same device.
"""

from __future__ import annotations

import numpy as np

K_DM = 4.148808e3           # s MHz^2 / (pc cm^-3), dispersion constant
TWO32 = 2.0 ** 32


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one stream of the run (seeds may exceed
    32 bits)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32] + [int(s) for s in stream])


def jax_key(seed: int, *stream: int):
    import jax
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0x7FFFFFFF)
    for s in stream:
        key = jax.random.fold_in(key, int(s))
    return key


def phase_step(freq_hz: float, dt: float) -> int:
    """Per-sample phase increment as a 32-bit fixed-point fraction, so
    that the linear phase is exact over millions of samples."""
    return int(round(freq_hz * dt * TWO32)) % (1 << 32)


# ----------------------------------------------------------------------
# search traffic: full-length dedispersed series
# ----------------------------------------------------------------------

def search_params(traffic: dict, seed: int, chunk: int, ntr: int,
                  valid: int, numout: int, dt: float) -> dict:
    """Host-drawn injections of one chunk (same shapes for every seed:
    the same number of pulsars, harmonics and pulses per trial)."""
    g = rng(seed, 1, chunk)
    psrs = traffic["pulsars"]
    npsr = len(psrs)
    nharm = max(p["nharm"] for p in psrs)
    m = np.zeros((ntr, npsr), np.uint32)
    z = np.zeros((ntr, npsr), np.float32)
    amp = np.zeros((ntr, npsr, nharm), np.float32)
    r_mid = np.zeros((ntr, npsr))
    for j, p in enumerate(psrs):
        f = g.uniform(*p["freq_hz"], size=ntr)
        zz = g.uniform(*p["z"], size=ntr) * g.choice([-1.0, 1.0], size=ntr)
        for i in range(ntr):
            m[i, j] = phase_step(f[i], dt)
        z[:, j] = zz
        # fundamental amplitude for the stated normalised Fourier power
        # (|X|^2 / (N sigma^2) = a^2 N / 4) over the valid span
        a = np.sqrt(4.0 * p["power"] / valid)
        amp[:, j, :p["nharm"]] = (a * p["decay"] **
                                  np.arange(p["nharm"]))[None]
        # Fourier bin of the mean frequency over the padded length:
        # phase = r0 u + (z/2) u^2 = (r0 + z/2) u + z (u^2 - u)/2
        r_mid[:, j] = m[:, j].astype(np.float64) * numout / TWO32 + z[:, j] / 2
    sp = traffic["single_pulses"]
    nsp = sp["count"]
    width = g.integers(sp["width"][0], sp["width"][1] + 1, size=(ntr, nsp))
    # one pulse per equal slice of the valid span, clear of the edges
    # and of the tail that single_pulse_search leaves out (the part of
    # the last 8000-sample chunk past the last whole one)
    edges = np.linspace(0, valid - 9000, nsp + 1).astype(np.int64)
    start = np.stack([g.integers(edges[k] + 2000, edges[k + 1] - 2000 -
                                 sp["width"][1], size=ntr)
                      for k in range(nsp)], axis=1)
    return dict(m=m, z=z, amp=amp, r_mid=r_mid,
                sp_start=start.astype(np.int32),
                sp_width=width.astype(np.int32),
                sp_amp=(sp["snr"] / np.sqrt(width)).astype(np.float32),
                mains=np.uint32(phase_step(traffic["mains"]["freq_hz"], dt)))


_series_fns: dict = {}


def search_series(traffic: dict, seed: int, chunk: int, params: dict,
                  valid: int, numout: int):
    """[ntr, numout] float32 device series: noise + pulsars + mains +
    single pulses over the valid span, padded with each series' mean
    (pad_to_good_N semantics)."""
    import jax
    import jax.numpy as jnp

    key = (valid, numout)
    fn = _series_fns.get(key)
    if fn is None:
        def one(k, m, z, amp, sp_start, sp_width, sp_amp, mains, off, sig,
                mains_amp):
            n = jnp.arange(numout, dtype=jnp.uint32)
            x = jax.random.normal(k, (numout,), jnp.float32)
            u = n.astype(jnp.float32) / numout
            for j in range(m.shape[0]):
                lin = (n * m[j]).astype(jnp.float32) / TWO32
                quad = 0.5 * z[j] * u * u
                ph = jnp.mod(lin + jnp.mod(quad, 1.0), 1.0)
                for h in range(amp.shape[1]):
                    x = x + amp[j, h] * jnp.cos(
                        2 * jnp.pi * jnp.mod((h + 1) * ph, 1.0))
            mph = (n * mains).astype(jnp.float32) / TWO32
            x = x + mains_amp * jnp.cos(2 * jnp.pi * mph)
            ni = n.astype(jnp.int32)
            for p in range(sp_start.shape[0]):
                on = (ni >= sp_start[p]) & (ni < sp_start[p] + sp_width[p])
                x = x + jnp.where(on, sp_amp[p], 0.0)
            x = off + sig * x
            ok = ni < valid
            mean = jnp.sum(jnp.where(ok, x, 0.0)) / valid
            return jnp.where(ok, x, mean)

        def batch(k0, m, z, amp, sp_start, sp_width, sp_amp, mains, off,
                  sig, mains_amp):
            keys = jax.vmap(lambda i: jax.random.fold_in(k0, i))(
                jnp.arange(m.shape[0]))
            return jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, 0, None, None,
                                          None, None))(
                keys, m, z, amp, sp_start, sp_width, sp_amp, mains, off,
                sig, mains_amp)

        fn = jax.jit(batch)
        _series_fns[key] = fn
    return fn(jax_key(seed, 2, chunk), params["m"], params["z"],
              params["amp"], params["sp_start"], params["sp_width"],
              params["sp_amp"], params["mains"],
              np.float32(traffic["offset"]), np.float32(traffic["sigma"]),
              np.float32(traffic["mains"]["amp"]))


def max_delay_samples(dm: float, flo: float, fhi: float, dt: float) -> int:
    """Dispersion sweep across the band in samples (pads the series the
    way prepsubband trims its valid length)."""
    return int(np.ceil(K_DM * dm * (flo ** -2 - fhi ** -2) / dt))


# ----------------------------------------------------------------------
# dedispersion traffic: raw 8-bit filterbank blocks
# ----------------------------------------------------------------------

_raw_fns: dict = {}


def raw_block(traffic: dict, seed: int, block: int, blocklen: int,
              nchan: int):
    """One [blocklen, nchan] uint8 block of raw spectra as the file
    stores them (channel order as recorded): noise around a bandpass,
    plus broadband RFI bursts drawn from the seed."""
    import jax
    import jax.numpy as jnp

    g = rng(seed, 3, block)
    rfi = traffic["rfi"]
    nb = rfi["bursts_per_block"]
    lens = g.integers(rfi["length"][0], rfi["length"][1] + 1, size=nb)
    starts = g.integers(0, blocklen - rfi["length"][1], size=nb)
    key = (blocklen, nchan)
    fn = _raw_fns.get(key)
    if fn is None:
        def make(k, starts, lens, mean, sig, depth, amp):
            c = jnp.arange(nchan, dtype=jnp.float32) / nchan
            bandpass = mean * (1.0 - depth * (2 * c - 1.0) ** 2)
            x = bandpass[None, :] + sig * jax.random.normal(
                k, (blocklen, nchan), jnp.float32)
            t = jnp.arange(blocklen, dtype=jnp.int32)[:, None]
            on = jnp.zeros((blocklen, 1), bool)
            for i in range(starts.shape[0]):
                on = on | ((t >= starts[i]) & (t < starts[i] + lens[i]))
            x = x + jnp.where(on, amp, 0.0)
            return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)
        fn = jax.jit(make)
        _raw_fns[key] = fn
    return fn(jax_key(seed, 4, block), starts.astype(np.int32),
              lens.astype(np.int32), np.float32(traffic["chan_mean"]),
              np.float32(traffic["chan_sigma"]),
              np.float32(traffic["bandpass_depth"]),
              np.float32(rfi["amp"]))


def raw_pool(traffic: dict, seed: int, nblocks: int, blocklen: int,
             nchan: int) -> np.ndarray:
    """[nblocks, blocklen * nchan] uint8 host pool of raw blocks."""
    pool = np.empty((nblocks, blocklen * nchan), np.uint8)
    for b in range(nblocks):
        pool[b] = np.asarray(raw_block(traffic, seed, b, blocklen,
                                       nchan)).reshape(-1)
    return pool
