#!/usr/bin/env python3
"""Record the small trace that ``test_bench_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Run once on a TPU: inside a host span ``window`` it runs a phase-1
kernel (``neighbor_count`` on 4,096 points) in a span ``refresh``, leaves
the chip idle for 50 ms in a span ``query``, then runs a small jitted
reduction, and copies the profiler's ``.xplane.pb`` to ``out``."""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (4096, 2)), jnp.float32)
    mask = jnp.ones((4096,), bool)
    count = jax.jit(lambda a, m: ops.neighbor_count(a, m, 0.02))
    total = jax.jit(lambda a: jnp.sum(a * a))
    jax.block_until_ready((count(x, mask), total(x)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("refresh"):
            jax.block_until_ready(count(x, mask))
        with jax.profiler.TraceAnnotation("query"):
            time.sleep(0.05)
            jax.block_until_ready(total(x))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(found[0], out)
    shutil.rmtree(tmp)
    print(out, os.path.getsize(out), jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
