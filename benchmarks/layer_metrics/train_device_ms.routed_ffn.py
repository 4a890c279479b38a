"""Device time a training step of the operations the program runs under
``routed_ffn`` and the names that answer for it (``moe_route``,
``moe_dispatch``, ``moe_combine``): the routed FFN whole — forward,
recomputed forward and backward — which ``train_device_ms.mlp`` (the
dense ``ffn`` scope) does not see."""

from benchmarks.lib import mellum_spans as ms


def read(h):
    steps = ms.traced_steps(h)
    took = ms.seconds(h, ("routed_ffn",) + ms.PERMUTE)
    return 1e3 * took / steps if steps and took > 0 else None
