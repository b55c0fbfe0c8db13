#!/usr/bin/env python3
"""The fp8 control of a training cell whose reference leaves no room on the
chip for ``calibrate_training.py``'s way (it keeps the sound reference's
float32 first gradient on the device while the control's step loads: at 659M
parameters and 8192 tokens that is 2.6 GB too many). Here the control runs
FIRST and its first gradient waits on the host; then the sound reference runs
and the control's gradient visits the device a leaf at a time
(``train_reference.gradient_distance``). The sound readings of the PROGRAM
against the reference are what every run of ``run.py`` prints as ``compared``.

    python3 benchmark/tools/calibrate_control.py --workload <cell> --seeds 101,...

Prints one JSON line a seed: every number the comparison reads, of the
control held against the sound reference, then what ``compare.judge`` makes
of them under the cell's own limits: ``correct`` (false: the control is
refused) and the numbers that ``failed``. The benchmark's own runs never call
this.
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
    devs = device.find_devices(cell["chips"], args.rehearse)
    program.setup(args.rehearse)
    import jax

    ref = loader.load_module("references", config["family"])
    opt = train_reference.effective_optimizer(config, traffic)
    steps = traffic["first_steps"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params, pool = ref.init(config, seed), ref.batches(config, traffic, seed)
        low = train_reference.first_steps(ref, config, opt, params, pool, steps=steps,
                                          quant="fp8", keep_gradient=True, devices=devs)
        low_gradient = jax.device_get(low.pop("first_gradient"))
        gc.collect()
        t1 = time.perf_counter()
        sound = train_reference.first_steps(ref, config, opt, params, pool, steps=steps,
                                            keep_gradient=True, devices=devs)
        rel, norms = train_reference.gradient_distance(
            low_gradient, sound.pop("first_gradient"))
        row = {"seed": seed, "control_s": t1 - t0, "reference_s": time.perf_counter() - t1,
               "control": {}, "worst_leaf": {}}
        numbers = compare.training_numbers(
            low, dict(sound, grad_rel_diff=rel, grad_diff_norms=norms))
        for name, value, detail in numbers:
            row["control"][name] = value
            row["worst_leaf"][name] = detail
        judged = compare.judge(numbers, cell["limits"])
        row["limits"] = cell["limits"]
        row["failed"] = [r["compared"] for r in judged if not r["ok"]]
        row["correct"] = not row["failed"]
        print(json.dumps(row), flush=True)
        del params, pool, low_gradient, sound
        gc.collect()


if __name__ == "__main__":
    main()
