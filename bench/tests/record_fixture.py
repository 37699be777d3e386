"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_fixture.py <out.xplane.pb>

On the chip: inside a ``bench.window`` span, three calls of a jitted
program ``jit_fixture_step`` (each in a ``bench.step`` span, waited for)
with a 30 ms ``bench.wait`` sleep between the second and the third, so
the trace holds known programs and one known idle gap.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        print("record_fixture: no accelerator", file=sys.stderr)
        return 2

    def fixture_step(x):
        return jnp.tanh(x @ x) * 0.5

    step = jax.jit(fixture_step)
    x = jnp.ones((512, 512), jnp.float32) / 512
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="fixture-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            if i == 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.03)
            with jax.profiler.TraceAnnotation("bench.step"):
                x = step(x)
                x.block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_fixture: {os.path.getsize(out)} bytes -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
