"""Records the small trace the tests reduce (tests/data/small.xplane.pb):
two programs, four rounds, a host sleep in each round.  Run on the chip:

    python3 chipbench/tests/record_trace.py chiprun_out/recorded

It prints what the test then expects: each program's launches and the sum
of the module events' durations, read straight from the events."""
import glob
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData


def step(w, x):
    for _ in range(3):
        x = jnp.tanh(x @ w)
    return x


def prefill(w, x):
    return jnp.sum(jnp.exp(x @ w), axis=-1)


def main(out):
    js, jp = jax.jit(step), jax.jit(prefill)
    w, x = jnp.ones((1024, 1024)), jnp.ones((512, 1024))
    jax.block_until_ready((js(w, x), jp(w, x)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(4):
        jax.block_until_ready((js(w, x), jp(w, x)))
        time.sleep(0.003)
    jax.profiler.stop_trace()
    path = glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                for e in line.events:
                    print(e.name, e.start_ns, e.duration_ns)
    print(path)


if __name__ == "__main__":
    main(sys.argv[1])
