#!/usr/bin/env python3
"""Record the small trace the reduction is tested on.  Run on the chip:

    python benchmarks/tests/fixtures/record_trace.py <out dir>

Four executions of one named program (two matrix products and a
reduction at 512x512 bf16) with a host sleep of 20 ms between them, so
the trace holds a known number of program runs and idle gaps."""

import sys
import time

import jax
import jax.numpy as jnp


def fixture_step(x):
    return jnp.tanh(x @ x) @ x


def main(out: str) -> None:
    step = jax.jit(fixture_step)
    x = jnp.ones((512, 512), jnp.bfloat16)
    step(x).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(4):
        step(x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    print(jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
