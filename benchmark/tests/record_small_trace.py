"""Records the small trace ``test_trace_reduce.py`` checks the
reduction against: three executions of one tiny jitted function on the
chip, a pause between them. Run on the chip, once, by hand:

    python3 benchmark/tests/record_small_trace.py <out_dir>

and copy ``<out_dir>/small.xplane.pb`` to ``benchmark/tests/data/``.
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    def small_step(x):
        return jnp.tanh(x @ x).sum()

    f = jax.jit(small_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    print(os.path.getsize(src), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
