#!/usr/bin/env python3
"""The fp8 control and the faults a reference plants in itself, for a training
cell whose reference leaves no room on the chip for
``calibrate_training.py --planted`` (it keeps the sound reference's float32
first gradient on the device while each fault's step loads: at 798M parameters
and 8192 tokens that is 3.2 GB beside 11.3). ``calibrate_control.py``'s way,
for several faults a seed: the sound reference runs FIRST and its first
gradient waits on the host; then each fault runs and the sound gradient visits
the device a leaf at a time, beside the fault's.

    python3 benchmark/tools/calibrate_planted.py --workload <cell> --seeds 101,... \
        --faults fp8,carry_dropped,gate_after_norm

Prints one JSON line a seed and fault: every number the comparison reads, of
the fault held against the sound reference, then what ``compare.judge`` makes
of them under the cell's own limits: ``correct`` (false: the fault is refused)
and the numbers that ``failed``. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from harness import compare, device, loader, program, train_reference  # noqa: E402


def distance(fault, sound):
    """``train_reference.gradient_distance`` of ``fault`` (on the device) from
    ``sound`` (on the host: put beside the fault's one leaf at a time)."""
    import jax
    import numpy as np

    num = den = np.float32(0.0)
    leaf = {}
    for k in fault:
        want = jax.device_put(sound[k], fault[k].sharding)
        n, d = jax.device_get(train_reference._leaf_sums(fault[k], want))
        num, den, leaf[k] = num + n, den + d, float(np.sqrt(n))
    return float(np.sqrt(num / den)), leaf


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="fp8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = loader.resolve_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    config = loader.load_json("configs", cell["config"])
    traffic = loader.load_json("traffic", cell["traffic"])
    devs = device.find_devices(cell["chips"], args.rehearse)
    program.setup(args.rehearse)
    import jax

    ref = loader.load_module("references", config["family"])
    opt = train_reference.effective_optimizer(config, traffic)
    steps = traffic["first_steps"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params, pool = ref.init(config, seed), ref.batches(config, traffic, seed)
        sound = train_reference.first_steps(ref, config, opt, params, pool, steps=steps,
                                            keep_gradient=True, devices=devs)
        sound_gradient = jax.device_get(sound.pop("first_gradient"))
        gc.collect()
        reference_s = time.perf_counter() - t0
        for fault in args.faults.split(","):
            t1 = time.perf_counter()
            low = train_reference.first_steps(ref, config, opt, params, pool, steps=steps,
                                              quant=fault, keep_gradient=True, devices=devs)
            rel, norms = distance(low.pop("first_gradient"), sound_gradient)
            row = {"seed": seed, "fault": fault, "reference_s": reference_s,
                   "fault_s": time.perf_counter() - t1, "numbers": {}, "worst_leaf": {}}
            numbers = compare.training_numbers(
                low, dict(sound, grad_rel_diff=rel, grad_diff_norms=norms))
            for name, value, detail in numbers:
                row["numbers"][name] = value
                row["worst_leaf"][name] = detail
            judged = compare.judge(numbers, cell["limits"])
            row["limits"] = cell["limits"]
            row["failed"] = [r["compared"] for r in judged if not r["ok"]]
            row["correct"] = not row["failed"]
            row["peak_bytes"] = device.memory_peak_bytes(devs)
            print(json.dumps(row), flush=True)
            gc.collect()
        del params, pool, sound_gradient, sound
        gc.collect()


if __name__ == "__main__":
    main()
