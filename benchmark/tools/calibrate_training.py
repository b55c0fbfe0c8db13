#!/usr/bin/env python3
"""Read what the limits of a training cell are set from, in one process on
the chip: for each seed the numbers that the comparison reads from the
program's first steps against the plain reference, and for the first
``--control`` seeds the same numbers of the control, which is the reference
itself computed in fp8 and put in the program's place. ``--planted`` names
further faults that the family's reference can plant in itself (ResNet:
``fp8_branch_backward``), read on the control's seeds too.

    python3 benchmark/tools/calibrate_training.py --workload <cell> --seeds 101,102,... --control 3

Prints one JSON line a seed and a summary: the sound runs' largest and the
control's smallest of every number, and beside each reading the allocator's
peak bytes once the follower has run, to size the next cell by. ``--out``
keeps every leaf's norms and distances, so that a statistic over the leaves
can be chosen afterwards.
``--budget-seconds`` starts no further seed once that much time has gone.
The benchmark's own runs never call this.
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


def allocator(devs, key):
    """The allocator's reading on the fullest of the chips used."""
    return max(int(st.get(key, 0)) for st in device.memory_stats(devs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--planted", default="")
    ap.add_argument("--budget-seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = loader.resolve_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % cell["chips"]
    config = loader.load_json("configs", cell["config"])
    traffic = loader.load_json("traffic", cell["traffic"])
    devs = device.find_devices(cell["chips"], args.rehearse)
    program.setup(args.rehearse)
    ref = loader.load_module("references", config["family"])
    model = loader.load_module("models", config["family"])
    runner = loader.load_module("runners", cell["runner"])
    opt = train_reference.effective_optimizer(config, traffic)
    sound, control = {}, {}
    rows = []
    t_start = time.perf_counter()
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if args.budget_seconds is not None and t0 - t_start > args.budget_seconds:
            print(json.dumps({"stopped_before_seed": seed, "seconds": t0 - t_start}), flush=True)
            break
        params = ref.init(config, seed)
        pool = ref.batches(config, traffic, seed)
        prog = model.build(config, traffic, params, devs, opt)
        batches = [prog.batch(x, y) for x, y in pool]
        first, later = runner.first_steps(prog, batches, params, traffic)
        if n == 0:  # what run.py holds the lowering for memory_analysis() to
            c0 = program.counters()
            share = device.program_share(prog.compiled_step())
            print(json.dumps({"lowered_again": program.delta(program.counters(), c0),
                              "program": share}), flush=True)
        del prog, batches
        gc.collect()
        in_use = allocator(devs, "bytes_in_use")
        t1 = time.perf_counter()
        ref_first = train_reference.first_steps(
            ref, config, opt, params, pool, steps=traffic["first_steps"],
            program_gradient=first.pop("first_gradient"), keep_gradient=True, devices=devs)
        ref_gradient = ref_first.pop("first_gradient")
        t2 = time.perf_counter()
        # the allocator's peak never falls: on a cell's first seed it is the
        # follower's where that is over the program's own (it counts no
        # program's temporaries: harness/device.py)
        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "bytes_in_use_before_reference": in_use,
               "peak_bytes_after_reference": allocator(devs, "peak_bytes_in_use"),
               "later_compiles": later, "losses": first["losses"],
               "ref_losses": ref_first["losses"], "sound": {}, "sound_leaf": {}}
        for name, value, detail in compare.training_numbers(first, ref_first):
            key = name.split(".")[0]
            row["sound"][name] = value
            row["sound_leaf"][name] = detail
            sound[key] = max(sound.get(key, 0.0), value)
        leaves = {"program": {k: first[k] for k in ("grad_norms", "delta_norms")},
                  "reference": {k: ref_first[k] for k in
                                ("grad_norms", "delta_norms", "grad_diff_norms")}}
        faults = ["fp8"] + [q for q in args.planted.split(",") if q]
        for quant in faults if n < args.control else []:
            t3 = time.perf_counter()
            low = train_reference.first_steps(ref, config, opt, params, pool,
                                              steps=traffic["first_steps"], quant=quant,
                                              keep_gradient=True, devices=devs)
            gradient = low.pop("first_gradient")
            rel_diff, diff_norms = train_reference.gradient_distance(gradient, ref_gradient)
            low_ref = dict(ref_first, grad_rel_diff=rel_diff, grad_diff_norms=diff_norms)
            del gradient
            tag = "control" if quant == "fp8" else quant
            row[tag] = {}
            leaves[tag] = dict(low, grad_diff_norms=low_ref["grad_diff_norms"])
            for name, value, detail in compare.training_numbers(low, low_ref):
                row[tag][name] = value
                control.setdefault(tag, {}).setdefault(name.split(".")[0], []).append(value)
            row[tag + "_s"] = time.perf_counter() - t3
            row[tag + "_peak_bytes"] = allocator(devs, "peak_bytes_in_use")
        print(json.dumps(row), flush=True)
        row["leaves"] = leaves
        rows.append(row)
        del params, pool, ref_gradient
        gc.collect()
    summary = {"workload": args.workload, "seeds": len(rows),
               "sound_largest": sound,
               "smallest_under_each_fault": {tag: {k: min(v) for k, v in by.items()}
                                             for tag, by in control.items()},
               "memory_peak_bytes": device.memory_peak_bytes(devs)}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f)


if __name__ == "__main__":
    main()
