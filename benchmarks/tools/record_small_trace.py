"""Record the small trace the reduction is checked on
(``lib/testdata/small.xplane.pb``): a few named steps of a small jitted
program on whatever chips are attached, with the benchmark's own spans.
Run once on the chip by the builder; the file is committed."""

import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


STEPS = 30


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from benchmarks.lib import trace as tr
    from benchmarks.lib.harness import Spans
    out = sys.argv[1]
    devs = jax.devices()
    mesh = Mesh(devs, ("x",))
    x = jax.device_put(jnp.ones((len(devs) * 2048, 4096), jnp.bfloat16),
                       NamedSharding(mesh, P("x", None)))
    w = jax.device_put(jnp.ones((4096, 4096), jnp.bfloat16),
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return y, y.astype(jnp.float32).sum()   # the sum is a collective

    jax.block_until_ready(step(x, w))
    spans = Spans()
    tmp = os.path.join(os.path.dirname(out), "small_trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    spans.tracing = True
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(STEPS):
            with spans.span("engine.step"):
                jax.block_until_ready(step(x, w))
            with spans.span("collect"):
                time.sleep(0.001)
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp)
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    red = tr.reduce_trace(tr.load_xplane(out))
    print("small trace:", os.path.getsize(out), "bytes; window",
          red.window_s, "busy", red.busy_s, "ops", len(red.op_seconds),
          "steps with device work", sum(
              1 for _, b in tr.busy_inside(red, "engine.step") if b > 0),
          "collective", red.collective_s, "exposed",
          red.collective_exposed_s, "idle", red.idle_by_span)
    return 0


if __name__ == "__main__":
    sys.exit(main())
