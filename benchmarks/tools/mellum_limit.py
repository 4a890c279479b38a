"""The readings that the limits of ``systems/mellum_pretrain.py``'s
check lie between, on the chip, at the published widths and the timed
sizes (PERF.md, Findings of PR 66):

    chiprun --timeout 3000 -- python3 benchmarks/tools/mellum_limit.py --seeds 11 12 13

For each seed: the plain reference at the step-0 weights in float32 and
bfloat16 (the yardstick), then — with ``--faults`` — the float32
reference with ONE fault planted (``full_attention``: no window on the
sliding layers; ``first_choice``: F_e over the first choice alone;
``share``: experts [16, 32) for [0, 16); ``held_alone``: the weights
normalised over the held choices alone) and the reference with its
matrices and inputs rounded through float8, the nearest precision below
the configuration's bfloat16 (``float8``); then the TIMED path's first
step, judged against each, and judged against the clean reference with
the optimiser's step undone on the stacks (``update_skipped``) and with
the bfloat16 copy left as it was (``stale_copy``).  ``clean`` has to come
out ok; every other line not ok, and ``failed`` names the limits that
say so.
"""

import argparse
import json
import os
import sys
import time
from unittest import mock

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
NAME = "mellum2-12b-a2.5b-train-ep4-d4"


def readings(config, seed, rehearse, faults):
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_mellum as ref
    from benchmarks.systems.mellum_pretrain import System
    system = System(config, rehearse, seed, jax.devices()[:1])
    ids, labels = next(system.batches())
    route, balance = ref.route, ref.load_balance
    first, count = system.ref_kw["held"]

    def held_alone(h2, wr, top_k):
        g, topi, w = route(h2, wr, top_k)
        w = jnp.where((topi >= first) & (topi < first + count), w, 0.0)
        return g, topi, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    wants = {"clean": system.reference(ids, labels)}
    if faults:
        plant = {
            "full_attention": mock.patch.dict(system.ref_kw,
                                              sliding_window=None),
            "first_choice": mock.patch.object(
                ref, "load_balance", lambda g, topi: balance(g, topi[:, :1])),
            "share": mock.patch.dict(system.ref_kw, held=(count, count)),
            "held_alone": mock.patch.object(ref, "route", held_alone)}
        for name, patch in plant.items():
            with patch:
                wants[name] = system.reference(ids, labels, ("f32",))
        # the nearest precision below: every matrix and the embedded
        # inputs through float8, the arithmetic in bfloat16
        f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype) \
            if a.ndim >= 2 else a                       # noqa: E731
        state = system.state
        system.state = state._replace(master=jax.tree.map(f8, state.master))
        low = system.reference(ids, labels, ("bf16",))
        system.state = state
    got = system.first_step(ids, labels, wants["clean"]["rows"])
    out = {}
    for name, want in wants.items():
        # a faulty reference is held to the CLEAN run's yardstick
        want = {**wants["clean"], **want, "noise": wants["clean"]["noise"]}
        out[name] = system.judge(want, got, labels.size)
    if faults:
        # the float8 run in the trainer's place, held to the clean limits
        out["float8"] = system.judge(wants["clean"], {
            "loss": low["loss_bf16"], "aux": low["aux_bf16"],
            "moments": low["bf16"]}, labels.size)
        for name, fault in state_faults(got).items():
            out[name] = system.judge(wants["clean"], fault, labels.size)
    return out


def state_faults(got):
    """The timed path's first step with a fault planted in the STATE it
    left: the optimiser's step undone on the router and expert stacks
    (master and copy as they were), and the copy the next forward reads
    left as it was on every compared tensor beside a master that moved."""
    import jax.numpy as jnp
    import numpy as np
    stacks = ("router", "expert_down")
    copy = np.asarray(got["copy"]["embed"]).dtype
    old = {k: np.asarray(jnp.asarray(v, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32), copy)
        for k, v in got["before"].items()}
    return {"update_skipped": {
                **got,
                "after": {**got["after"],
                          **{k: got["before"][k] for k in stacks}},
                "copy": {**got["copy"], **{k: old[k] for k in stacks}}},
            "stale_copy": {**got, "copy": old}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        config = json.load(f)
    from paddle_tpu._bootstrap import configure_compile_cache
    configure_compile_cache()
    for seed in args.seeds:
        t0 = time.time()
        for name, v in readings(config, seed, args.rehearse,
                                args.faults).items():
            print(json.dumps({"seed": seed, "reference": name, **{
                k: (float(f"{x:.4g}") if isinstance(x, float) else x)
                for k, x in v.items()}}), flush=True)
        print(f"[limit] seed {seed}: {time.time() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
