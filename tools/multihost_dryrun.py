"""Multi-host (DCN) dryrun: 2 processes x 4 virtual CPU devices.

VERDICT r1 flagged the comm backend as partial because jax.distributed
multi-host was never exercised, even in dryrun form.  This tool runs
the mpiprepsubband-equivalent dedispersion over a REAL multi-process
jax.distributed cluster: two OS processes connect through the gRPC
coordinator (the DCN transport), form one global 8-device mesh, run
the DM-sharded dedispersion step with replicated raw input (the
reference's MPI_Bcast pattern, mpiprepsubband.c:988-991), reduce with
a cross-process collective, and the parent verifies the checksum
against a single-process NumPy reference.

Round 5 (VERDICT r4 weak #6) extends the proof through the SEARCH
stage on the current pipeline: the fused build+scan accelsearch
program runs shard_map'd over the global 2-process mesh (1 DM trial
per device), the packed top-k tensors allgather across the DCN
transport, and the candidate lists must equal a single-process
search_many of the same spectra exactly.

Writes MULTIHOST_r05.json.  Run:  python tools/multihost_dryrun.py
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUMCHAN, NSUB, NUMDMS, NUMPTS = 64, 16, 64, 4096
COORD = "localhost:12765"
NPROC = 2

CHILD = r"""
import os, sys
sys.path.insert(0, %(repo)r)
pid = int(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(%(coord)r, num_processes=%(nproc)d,
                           process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from presto_tpu.ops.dedispersion import (dedisp_subbands_block,
                                         float_dedisp_many_block)

assert len(jax.devices()) == 4 * %(nproc)d, len(jax.devices())
mesh = Mesh(np.array(jax.devices()), ("dm",))
repl = NamedSharding(mesh, P())
dmsh = NamedSharding(mesh, P("dm"))

# identical inputs on every process (the Bcast-replicated raw block)
rng = np.random.default_rng(99)
last = rng.normal(size=(%(numchan)d, %(numpts)d)).astype(np.float32)
cur = rng.normal(size=(%(numchan)d, %(numpts)d)).astype(np.float32)
chan_d = (np.arange(%(numchan)d) %% 97).astype(np.int32)
dm_d = (np.arange(%(numdms)d)[:, None]
        * np.linspace(0, 5, %(nsub)d)[None, :]).astype(np.int32)

def mk(arr, shd):
    return jax.make_array_from_callback(
        arr.shape, shd, lambda idx: arr[idx])

@jax.jit
def step(last, cur, dly):
    sub_last = dedisp_subbands_block(last, cur, chan_dev, %(nsub)d)
    sub_cur = dedisp_subbands_block(cur, last, chan_dev, %(nsub)d)
    out = float_dedisp_many_block(sub_last, sub_cur, dly)
    # cross-process reduction: per-DM power then a global sum — the
    # collective rides the gRPC/DCN transport between the 2 processes
    return (out * out).sum(axis=1), out.sum()

chan_dev = mk(chan_d, repl)
outp, chk = jax.jit(step, in_shardings=(repl, repl, dmsh),
                    out_shardings=(dmsh, repl))(
    mk(last, repl), mk(cur, repl), mk(dm_d, dmsh))
from jax.experimental import multihost_utils
per_dm = np.asarray(multihost_utils.process_allgather(outp,
                                                      tiled=True))
if pid == 0:
    print("CHK %%0.6f %%0.6f %%d" %% (float(chk), float(per_dm.sum()),
                                      per_dm.size), flush=True)
jax.distributed.shutdown()
"""


def reference():
    import numpy as np
    rng = np.random.default_rng(99)
    last = rng.normal(size=(NUMCHAN, NUMPTS)).astype(np.float32)
    cur = rng.normal(size=(NUMCHAN, NUMPTS)).astype(np.float32)
    chan_d = (np.arange(NUMCHAN) % 97).astype(np.int64)
    dm_d = (np.arange(NUMDMS)[:, None]
            * np.linspace(0, 5, NSUB)[None, :]).astype(np.int64)
    per = NUMCHAN // NSUB

    def subs(a, b):
        x2 = np.concatenate([a, b], axis=1)
        out = np.zeros((NSUB, NUMPTS), np.float32)
        for c in range(NUMCHAN):
            out[c // per] += x2[c, chan_d[c]:chan_d[c] + NUMPTS]
        return out

    s1, s2 = subs(last, cur), subs(cur, last)
    x2 = np.concatenate([s1, s2], axis=1)
    out = np.zeros((NUMDMS, NUMPTS), np.float32)
    for d in range(NUMDMS):
        for s in range(NSUB):
            out[d] += x2[s, dm_d[d, s]:dm_d[d, s] + NUMPTS]
    return float(out.sum()), float((out.astype(np.float64) ** 2)
                                   .sum(axis=1).sum())


SEARCH_NUMBINS, SEARCH_NUMDMS = 1 << 14, 8
SEARCH_T = 120.0

SEARCH_SETUP = r"""
import numpy as np


def make_batch():
    rng = np.random.default_rng(1234)
    b = rng.normal(size=(%(numdms)d, %(numbins)d, 2)).astype(np.float32)
    for d in range(%(numdms)d):          # one tone per trial
        b[d, 3000 + 700 * d] = (60.0, 0.0)
    return b


def cand_keys(cands):
    return [(c.numharm, round(c.r, 3), round(c.z, 3),
             round(c.power, 2)) for c in cands]
"""

SEARCH_CHILD = r"""
import json, os, sys
sys.path.insert(0, %(repo)r)
pid = int(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(%(coord)r, num_processes=%(nproc)d,
                           process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils
from presto_tpu.search.accel import AccelConfig, AccelSearch

%(setup)s

assert len(jax.devices()) == 4 * %(nproc)d
mesh = Mesh(np.array(jax.devices()), ("dm",))
batch = make_batch()
searcher = AccelSearch(AccelConfig(zmax=20, numharm=4, sigma=3.0),
                       T=%(T)r, numbins=%(numbins)d)
g = searcher._build_plan_ns()
splan = searcher._slab_plan(g.plane_numr, 1 << 20)
slab_, k, scanner, start_cols = splan
build_body, scan_body = g.build_body, scanner.body
# the complex kernel bank as a HOST array: every process re-makes the
# identical value, jit replicates it (a single-process device array
# would be non-addressable on the peer)
kern_host = np.asarray(searcher._kern_bank_dev())
scols = np.asarray(start_cols, np.int32)


def per_shard(local, kern, sc):
    def per_dm(_, x):
        return None, scan_body(build_body(x, kern), sc)
    _, packed = jax.lax.scan(per_dm, None, local)
    return jnp.moveaxis(packed, 1, 0)     # [3, nd_loc, nsl, st, k]



# check_vma off, as parallel/sharded.compact_search_fn: on a TPU the
# Pallas kernels' outputs carry no mesh-axis variance
fn = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                        in_specs=(P("dm"), P(), P()),
                        out_specs=P(None, "dm"), check_vma=False))
dmsh = NamedSharding(mesh, P("dm"))
gbatch = jax.make_array_from_callback(
    batch.shape, dmsh, lambda idx: batch[idx])
packed = fn(gbatch, kern_host, scols)
# the packed top-k tensors cross the DCN transport here
full = np.asarray(multihost_utils.process_allgather(packed,
                                                    tiled=True))
if pid == 0:
    from presto_tpu.search.accel import _unpack_scan
    vals, cidx, zrow = _unpack_scan(full)
    out = [cand_keys(searcher._dedup_sort(searcher._collect_group(
        vals[d], cidx[d], zrow[d], start_cols)))
           for d in range(%(numdms)d)]
    print("CANDS " + json.dumps(out), flush=True)
jax.distributed.shutdown()
"""

SEARCH_REF = r"""
import json, os, sys
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
from presto_tpu.search.accel import AccelConfig, AccelSearch

%(setup)s

searcher = AccelSearch(AccelConfig(zmax=20, numharm=4, sigma=3.0),
                       T=%(T)r, numbins=%(numbins)d)
res = searcher.search_many(make_batch())
print("CANDS " + json.dumps([cand_keys(c) for c in res]), flush=True)
"""


def _sharded_search_check():
    """Search-stage DCN proof (VERDICT r4 weak #6): the fused
    build+scan over the global 2-process mesh must produce candidate
    lists EQUAL to a single-process search_many — the same invariant
    MULTICHIP asserts over ICI, here over the gRPC/DCN transport."""
    out = {"ok": False}
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    setup = SEARCH_SETUP % dict(numdms=SEARCH_NUMDMS,
                                numbins=SEARCH_NUMBINS)
    coord = "localhost:12771"
    code = SEARCH_CHILD % dict(repo=REPO, coord=coord, nproc=NPROC,
                               setup=setup, T=SEARCH_T,
                               numbins=SEARCH_NUMBINS,
                               numdms=SEARCH_NUMDMS)
    procs = [subprocess.Popen([sys.executable, "-c", code, str(pid)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=env, cwd=REPO)
             for pid in range(NPROC)]
    try:
        outs = [p.communicate(timeout=900) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        out["stage"] = "cluster-timeout"
        return out
    if any(p.returncode for p in procs):
        out["stage"] = "cluster"
        out["stderr"] = [o[1][-1200:] for o in outs]
        return out
    line = next((ln for ln in outs[0][0].splitlines()
                 if ln.startswith("CANDS ")), None)
    if line is None:
        out["stage"] = "no-cands-line"
        return out
    sharded = json.loads(line[6:])
    ref_code = SEARCH_REF % dict(repo=REPO, setup=setup, T=SEARCH_T,
                                 numbins=SEARCH_NUMBINS)
    r = subprocess.run([sys.executable, "-c", ref_code], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=REPO)
    if r.returncode != 0:
        out["stage"] = "reference"
        out["stderr"] = r.stderr[-1200:]
        return out
    rline = next((ln for ln in r.stdout.splitlines()
                  if ln.startswith("CANDS ")), None)
    single = json.loads(rline[6:]) if rline else None
    out["numdms"] = SEARCH_NUMDMS
    out["cands_per_dm"] = [len(c) for c in sharded]
    out["lists_equal"] = bool(sharded == single)
    out["ok"] = bool(out["lists_equal"]
                     and sum(out["cands_per_dm"]) > 0)
    return out


def main():
    code = CHILD % dict(repo=REPO, coord=COORD, nproc=NPROC,
                        numchan=NUMCHAN, nsub=NSUB, numdms=NUMDMS,
                        numpts=NUMPTS)
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    procs = [subprocess.Popen([sys.executable, "-c", code, str(pid)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=env, cwd=REPO)
             for pid in range(NPROC)]
    outs = [p.communicate(timeout=600) for p in procs]
    rcs = [p.returncode for p in procs]
    chk_line = next((ln for ln in outs[0][0].splitlines()
                     if ln.startswith("CHK ")), None)
    art = {"nproc": NPROC, "devices_per_proc": 4,
           "coordinator": COORD, "returncodes": rcs}
    ok = all(rc == 0 for rc in rcs) and chk_line is not None
    if ok:
        chk, sq, nd = chk_line.split()[1:]
        ref_sum, ref_sq = reference()
        art["checksum_distributed"] = float(chk)
        art["checksum_reference"] = ref_sum
        art["sq_distributed"] = float(sq)
        art["sq_reference"] = ref_sq
        art["per_dm_rows_gathered"] = int(nd)
        ok = (abs(float(chk) - ref_sum) < 1e-3 * max(abs(ref_sum), 1)
              and abs(float(sq) - ref_sq) < 1e-3 * max(abs(ref_sq), 1)
              and int(nd) == NUMDMS)
    else:
        art["stderr_tail"] = [o[1][-1500:] for o in outs]
    art["prepsubband_cli"] = _prepsubband_cli_check()
    art["sharded_search"] = _sharded_search_check()
    art["ok"] = bool(ok and art["prepsubband_cli"].get("ok")
                     and art["sharded_search"].get("ok"))
    with open(os.path.join(REPO, "MULTIHOST_r05.json"), "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art, indent=1))
    return 0 if art["ok"] else 1


PSB_CHILD = r"""
import os, sys
sys.path.insert(0, %(repo)r)
pid = int(sys.argv[1])
work = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
from presto_tpu.apps import prepsubband as app
app.run(app.build_parser().parse_args(
    ["-coordinator", %(coord)r, "-nproc", "%(nproc)d",
     "-procid", str(pid), "-o", os.path.join(work, "mh"),
     "-lodm", "10", "-dmstep", "2", "-numdms", "16", "-nsub", "16",
     "-nobary", os.path.join(work, "m.fil")]))
"""


def _prepsubband_cli_check():
    """The mpiprepsubband CLI analog end-to-end: prepsubband with
    -coordinator across 2 processes, each writing its own DM shard's
    .dat files (mpiprepsubband.c:1057-1060), byte-identical to a
    single-process run."""
    import glob
    import tempfile

    out = {"ok": False}
    work = tempfile.mkdtemp(prefix="mhpsb_")
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    # synthesize + single-process reference (its own process so the
    # parent never initializes jax)
    ref_code = (
        "import sys, os\nsys.path.insert(0, %r)\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "import jax\njax.config.update('jax_platforms', 'cpu')\n"
        "from presto_tpu.models.synth import FakeSignal, "
        "fake_filterbank_file\n"
        "sig = FakeSignal(f=5.0, dm=30.0, shape='gauss', width=0.1, "
        "amp=1.0)\n"
        "fake_filterbank_file(%r + '/m.fil', 1 << 14, 5e-4, 32, 400.0, "
        "1.5, sig, noise_sigma=2.0, nbits=8)\n"
        "from presto_tpu.apps import prepsubband as app\n"
        "app.run(app.build_parser().parse_args(['-o', %r + '/ref', "
        "'-lodm', '10', '-dmstep', '2', '-numdms', '16', '-nsub', "
        "'16', '-nobary', %r + '/m.fil']))\n" % (REPO, work, work,
                                                 work))
    r = subprocess.run([sys.executable, "-c", ref_code], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=REPO)
    if r.returncode != 0:
        out["stage"] = "reference"
        out["stderr"] = r.stderr[-800:]
        return out
    coord = "localhost:12799"
    code = PSB_CHILD % dict(repo=REPO, coord=coord, nproc=NPROC)
    procs = [subprocess.Popen([sys.executable, "-c", code, str(pid),
                               work],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=env, cwd=REPO)
             for pid in range(NPROC)]
    try:
        outs = [p.communicate(timeout=600) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:       # a hung child (dead peer, bound port):
            p.kill()          # record the failure, don't abort main()
        out["stage"] = "cluster-timeout"
        return out
    if any(p.returncode for p in procs):
        out["stage"] = "cluster"
        out["stderr"] = [o[1][-800:] for o in outs]
        return out
    refs = sorted(glob.glob(os.path.join(work, "ref_DM*.dat")))
    mhs = sorted(glob.glob(os.path.join(work, "mh_DM*.dat")))
    out["ref_files"] = len(refs)
    out["mh_files"] = len(mhs)
    # fused-vs-staged ROUTING visibility (PR 8): prepsubband prints
    # which contract its sharded path took; a multi-process cluster
    # must stay on the staged contract (the seam is single-process),
    # so anything else here is a routing regression.  The fused-seam
    # counterpart is asserted by __graft_entry__.dryrun_multichip's
    # routing probe and lands in MULTICHIP_*.json.
    routing = sorted({ln.split("= ", 1)[1].strip()
                      for o in outs for ln in o[0].splitlines()
                      if ln.startswith("prepsubband: sharded routing")})
    out["sharded_routing"] = routing
    out["routing_ok"] = routing == ["staged"]
    same = (len(refs) == len(mhs) == 16 and all(
        open(a, "rb").read() == open(b, "rb").read()
        for a, b in zip(refs, mhs)))
    out["byte_identical"] = bool(same)
    out["ok"] = bool(same and out["routing_ok"])
    return out


if __name__ == "__main__":
    sys.exit(main())
