"""What `remat` "full" would keep on a chip, priced WITHOUT a chip.

Builds a training configuration's step exactly as the four-chip cell
does (`benchmarks/systems/llama_pretrain.py::compile_for`: shapes only,
for a DESCRIBED v5e:2x2 — nothing runs), with the one thing a described
device cannot say handed in: its `bytes_limit` (`--limit-gb`, 16.91 by
default: what a v5e reports, 15.75 GiB).  The builder then does what it
does on the chip — compiles the floor program (every layer's checkpoint
keeps nothing), takes its need from the limit less the margin, walks
`trainer/pretrain.py::choose_remat_plan`, compiles the chosen program
and holds its need to the same limit — and this prints the plan beside
the layout it was priced under (`seq_sharded`: whether the activations
between a row-parallel and the next column-parallel product are held
S/mp rows a chip, and each name's bytes a layer a chip), the floor's
and the chosen program's need by the compiler's own account, and the
chosen program's flash custom calls, all-reduces and reduce-scatters
(the TPU compiler's `%all-reduce-scatter` fusions, each of which holds
one of the all-reduces).  About
90 s a compile (two where the first choice fits, one more a try where
it does not); the model's float32
parameters are built on the host for real (7.6 GB at the cell's size).

    JAX_PLATFORMS=cpu python tools/remat_plan_aot.py
    JAX_PLATFORMS=cpu python tools/remat_plan_aot.py --margin-gb 0.5

A compile is not a chip run: a time or a rate comes only from the cell.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mistral-7b-v0.3-train-zero2-mp2")
    ap.add_argument("--limit-gb", type=float, default=16.91)
    ap.add_argument("--margin-gb", type=float, default=None,
                    help="instead of pretrain.REMAT_MARGIN_BYTES")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from benchmarks.systems import llama_pretrain
    from paddle_tpu.ops import flash_attention, pallas_flash
    from paddle_tpu.trainer import pretrain

    with open(os.path.join(REPO, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    # code that asks jax for its backend sees the CPU here: steered to
    # the chip's branch, as benchmarks/tests/test_aot_compile.py does
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.config.update("jax_default_matmul_precision", None)
    patches = [
        mock.patch.object(flash_attention, "_tpu_flash_available",
                          lambda: True),
        mock.patch.object(pallas_flash, "_interpret", lambda: False),
        mock.patch.object(pretrain, "_bytes_limit",
                          lambda mesh: int(args.limit_gb * 1e9))]
    if args.margin_gb is not None:
        patches.append(mock.patch.object(pretrain, "REMAT_MARGIN_BYTES",
                                         int(args.margin_gb * 1e9)))
    seen = {}
    build = pretrain.build_llama_pretrain_step

    def keep_meta(*a, **kw):
        out = build(*a, **kw)
        seen.update(out[2]["remat_plan"])
        return out

    patches.append(mock.patch.object(pretrain, "build_llama_pretrain_step",
                                     keep_meta))
    for p in patches:
        p.start()
    try:
        compiled = llama_pretrain.compile_for(config, topo.devices)
    finally:
        for p in patches:
            p.stop()
    gb = lambda n: None if n is None else round(n / 1e9, 3)  # noqa: E731
    txt = compiled.as_text()
    print(json.dumps({
        "limit_GB": gb(seen["limit"]), "margin_GB": gb(seen["margin"]),
        "floor_need_GB": gb(seen["floor_need"]),
        "headroom_GB": gb(seen["headroom"]),
        "chosen_need_GB": gb(pretrain._program_need(compiled)),
        "saved_GB_by_shapes": gb(seen["saved_bytes"]),
        "seq_sharded": seen["seq_sharded"],
        "MB_a_layer_a_chip": {k: round(v / 1e6, 1)
                              for k, v in seen["nbytes"].items()},
        "layers": [list(k) for k in seen["layers"]],
        "flash_custom_calls":
            txt.count("custom_call_target=\"tpu_custom_call\""),
        "all_reduces": sum(ln.count(" all-reduce(")
                           + ln.count(" all-reduce-start(")
                           for ln in txt.splitlines()),
        "reduce_scatters": sum(ln.startswith("%all-reduce-scatter")
                               for ln in txt.splitlines())}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
