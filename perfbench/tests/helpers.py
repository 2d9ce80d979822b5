"""Drive run.main in-process at the rehearsal size (no look for a chip)
and read the result line."""

import contextlib
import io
import json

from perfbench import run


def rehearse(workload: str, seed: int = 3000000019, *extra):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1", "--rehearse", *extra])
    lines = [l for l in out.getvalue().splitlines() if l.startswith("{")]
    return rc, [json.loads(l) for l in lines]
