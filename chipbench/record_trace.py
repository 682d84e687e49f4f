"""Record the small device trace that tests/chipbench reads: a few steps of a
tiny program under the profiler, with chipbench's own spans.

    python -m chipbench.record_trace tests/chipbench/data/tiny_v5e.xplane.pb

Run on the chip; the file is kept only if it is under 2 MB."""

import os
import shutil
import sys

import jax
import jax.numpy as jnp

from chipbench import runner, spans as spans_mod

STEPS = 8


def main(out):
    if jax.devices()[0].platform != "tpu":
        print("chipbench.record_trace: JAX found no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def tiny_step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x, jnp.sum(x.astype(jnp.float32))

    class Job:
        def __init__(self):
            self.x = jnp.ones((512, 512), jnp.bfloat16)
            self.w = jnp.full((512, 512), 0.001, jnp.bfloat16)

        def step(self, k):
            self.x, loss = tiny_step(self.x, self.w)
            return loss, loss.reshape(1)

    job, spans = Job(), spans_mod.Spans()
    runner.drive(job, spans, 0, steps=3)
    root = os.path.abspath(runner.TRACE_DIR)
    shutil.rmtree(root, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(root, profiler_options=options)
    spans.annotate = True
    runner.drive(job, spans, 0, steps=STEPS)
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(runner.trace_path(root), out)
    shutil.rmtree(root, ignore_errors=True)
    print(f"wrote {out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
