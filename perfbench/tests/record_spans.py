"""Record the small device trace with program spans that
test_program_spans.py reads.

    python3 perfbench/tests/record_spans.py <out.xplane.pb>

Run on the chip: three ``bench:chunk`` spans on the main thread, each
holding an enabled obs span ``collect`` around two jitted programs and a
child span ``host`` around a 20 ms host sleep with the chip idle, then
5 ms idle in the chunk outside ``collect``.  Meanwhile a worker thread
opens 2 ms spans ``worker`` back to back, so a worker span covers the
middle of every idle gap and is shorter than any span of the main
thread.
"""

import glob
import os
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from presto_tpu.obs.trace import Tracer  # noqa: E402


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
    tracer = Tracer(enabled=True)
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            with tracer.span("worker"):
                time.sleep(0.002)

    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    t = threading.Thread(target=worker, name="worker")
    t.start()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:chunk"):
            with tracer.span("collect"):
                mult(a, a).block_until_ready()
                with tracer.span("host"):
                    time.sleep(0.02)
                add_rows(a).block_until_ready()
            time.sleep(0.005)
    stop.set()
    t.join()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(tdir)


if __name__ == "__main__":
    main(sys.argv[1])
