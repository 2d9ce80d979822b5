"""Record the small device trace that test_trace_reduce.py reads.

    python3 perfbench/tests/record_trace.py <out.xplane.pb>

Run on the chip: two jitted programs inside ``bench:`` spans with an
idle host sleep between them, as the harness records a window.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


@jax.jit
def mult(a, b):
    return a @ b


@jax.jit
def add_rows(a):
    return jnp.cumsum(a, axis=0) + 1.0


def main(out: str) -> None:
    a = jnp.ones((2048, 2048), jnp.float32)
    mult(a, a).block_until_ready()
    add_rows(a).block_until_ready()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:chunk"):
            mult(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:host_wait"):
                time.sleep(0.02)
            add_rows(a).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(tdir)


if __name__ == "__main__":
    main(sys.argv[1])
