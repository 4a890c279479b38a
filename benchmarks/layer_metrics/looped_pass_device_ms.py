"""Device ms of ONE pass of the looped decoder, mean over the traced
steps: the device time a step of everything the program runs under its
layers' names and ``loop_norm`` (``lib/scoped_ops``; the embedding and
the head run once a step and are left out) over ``ut_steps`` of the
step records."""

from benchmarks.lib import ouro_spans, scoped_ops
from benchmarks.lib.harness import say
from benchmarks.lib.laguna_spans import counts
from benchmarks.lib.trace import busy_inside

ONCE_A_STEP = ("embed", "head", scoped_ops.UNSCOPED, scoped_ops.UNKNOWN)


def read(h):
    steps = ouro_spans.traced_steps(h)
    j = scoped_ops.joined(h) if steps else None
    passes = counts(h, "ut_steps") if j else None
    pairs = busy_inside(h.reduced, "engine.step") if passes else []
    if not pairs or j.total_s <= 0:
        return None
    U = max(p[0] for p in passes)
    step_ms = 1e3 * sum(busy for _, busy in pairs) / len(pairs)
    inside = sum(v for k, v in j.by_scope.items() if k not in ONCE_A_STEP)
    say(f"looped decoder: {U} passes a step, {100.0 * inside / j.total_s:.2f}"
        f" % of the traced device time under the passes' names, of "
        f"{step_ms:.3f} ms a step")
    return step_ms * inside / j.total_s / U
