#!/usr/bin/env python3
"""Read what ``selection_mismatch``'s limit is set from, in one process on the
chip: for each seed the share of the pairs the PROGRAM's layer-0 indexer
selects for the first batch (the model zoo's own blocks on the seeded weights
in the stored type: the kernel on the chip) that the reference's float32
selection does not hold, and beside it the same share for the control, the
reference's own selection with its index products' operands in fp8.

    python3 benchmark/tools/calibrate_selection.py --workload <cell> --seeds 101,102,...

Prints one JSON line a seed (pairs selected, pairs astray, the shares, the
seconds each side took) and a summary: the sound runs' largest and the
control's smallest. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from harness import device, loader, program  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = loader.resolve_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    config = loader.load_json("configs", cell["config"])
    traffic = loader.load_json("traffic", cell["traffic"])
    device.find_devices(cell["chips"], args.rehearse)
    program.setup(args.rehearse)
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo import keye as zoo

    ref = loader.load_module("references", config["family"])
    model = loader.load_module("models", config["family"])
    one_layer = dict(config, num_hidden_layers=1)  # layer 0 is all that is read
    net = zoo.KeyeVL2Model(dict(one_layer, num_experts=config["published"]["num_experts"]),
                           experts_held=tuple(config["experts_held"]))
    net.initialize()
    net.cast(config["dtype"])
    names = model.leaf_names(one_layer, net.prefix)
    select = jax.jit(lambda p, x, quant: ref.first_selection(one_layer, p, x, quant),
                     static_argnames="quant")

    def astray(got, want):
        return int(jnp.sum(jnp.logical_and(got, jnp.logical_not(want)), dtype=jnp.int32))

    sound, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        params = ref.init(one_layer, seed)
        ids = ref.batches(one_layer, traffic, seed)[0][0]
        model.common.set_parameters(net.collect_params(), names, params)
        t0 = time.perf_counter()
        from mxnet_tpu import nd

        blk = net.blocks[0]
        got = blk.indexer(blk.attn_norm(net.embed(nd.NDArray(ids)))).data != 0
        got.block_until_ready()
        t1 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            want = select(params, ids, None)
            low = select(params, ids, "fp8")
        low.block_until_ready()
        t2 = time.perf_counter()
        n = int(jnp.sum(got, dtype=jnp.int32))
        row = {"seed": seed, "selected": n, "program_astray": astray(got, want),
               "fp8_astray": astray(low, want), "program_s": t1 - t0,
               "reference_s": t2 - t1}
        row["selection_mismatch"] = row["program_astray"] / n
        row["fp8_selection_mismatch"] = row["fp8_astray"] / int(jnp.sum(low, dtype=jnp.int32))
        sound.append(row["selection_mismatch"])
        control.append(row["fp8_selection_mismatch"])
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(sound),
                      "sound_largest": max(sound), "control_smallest": min(control)}),
          flush=True)


if __name__ == "__main__":
    main()
