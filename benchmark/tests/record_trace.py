"""Record the small trace that ``test_trace_reduce.py`` reads (run on the
chip, by hand, when the profiler's format changes):

    python benchmark/tests/record_trace.py <out_dir>

Six executions of one jitted matmul program under ``bench.step`` spans, a
20 ms ``bench.wait`` sleep after each, all inside one ``bench.window``.
"""

import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out_dir):
    @jax.jit
    def small_step(x, w):
        return jnp.tanh(x @ w) @ w.T

    x = jnp.ones((512, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 2048), jnp.bfloat16)
    small_step(x, w).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with TraceAnnotation("bench.window"):
        for _ in range(6):
            with TraceAnnotation("bench.step"):
                small_step(x, w).block_until_ready()
            with TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
