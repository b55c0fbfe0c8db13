#!/usr/bin/env python3
"""One traced run of a benchmark cell with the device time by scope.

    python3 tools/trace_cell.py --workload resnet50_train_b256 --seed 7 --seconds 30

Runs ``benchmark/run.py --trace 1`` in this process, and before the run's
trace is deleted reads it back with ``mx.profiler.aggregate``: it prints the
run's own lines and result, then ``dumps()``'s device table, then one JSON
line ``{"scoped": ...}`` with the milliseconds a step by phase and by the
scopes PERF.md section 5 quotes (``batchnorm``, ``attention``, ...), the
kernels' calls a step, the branch each traced attention forward and backward
took (``flash_fwd_branches``, ``flash_bwd_branches``) and the heads a grid
step of each traced flash kernel takes (``flash_heads_per_step``), the tiles the
traced window calls visit beside the causal call's (``flash_window_blocks``), the layout
each traced pass read its operands in (``flash_layouts``: ``in_place`` from the
fused projection, ``heads_major`` turned), the branch
each traced gated short convolution took (``gated_conv_branches``), each
causal filter under SiLU (``causal_conv_branches``), each
state-space scan (``ssd_branches``), each gated delta rule
(``delta_rule_branches``), the form each traced embedding's weight
gradient took (``embedding_grad_branches``) and each
traced grouped matmul of an expert layer by product (``grouped_matmul_branches``)
and each traced movement of rows between tokens and experts (``row_movement_branches``),
the tuning
table's entries (``tuning_entries``: the tiles each kernel shape ran with), what an
expert-parallel model counted on the device
(``moe_counts``: slots by layer and held expert, slots lost, blocks of rows
run past a layer's first, the rows each layer's last call moved beside the rows it laid out), what a model with a lightning indexer counted there
(``selection_counts``: the pairs selected and the rows searched, by layer),
the set-up
phases and the compile counters. ``--category NAME`` (an HLO category of the
device table, e.g. ``"data formatting"``) adds one JSON line
``{"category_ops": ...}`` with every operation of that category by scope and
result shape: what a category is made of. The
benchmark's cells cannot name a new per-layer metric without an edit to
their files (PERF.md section 7), so this is how those numbers are taken
meanwhile.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import run as bench  # noqa: E402 — benchmark/run.py

# further single scopes quoted in PERF.md: latent and grouped-query attention,
# the indexer, the expert layer, the gated short convolution, attention over a
# window beside attention over the whole past, the Mamba-2 mixer, the
# embedding table's gradient, and Kimi's delta attention beside a gated
# attention layer
PARTS = ("mla", "q_proj", "kv_a", "kv_b", "rope", "o_proj", "rmsnorm", "rmsnorm_bwd",
         "moe", "router", "dispatch", "experts", "combine", "shared",
         "gqa", "kv_proj", "qk_norm", "indexer", "k_proj", "weights", "scores", "select",
         "short_conv", "in_proj", "gated_conv", "gated_conv_bwd", "out_proj",
         "grouped_matmul_bwd", "attn_full", "attn_window",
         "mamba", "causal_conv", "causal_conv_bwd", "ssd", "ssd_bwd", "gated_rmsnorm",
         "gated_rmsnorm_bwd", "mlp", "embedding_bwd",
         "kda", "qkv_proj", "log_decay", "delta_rule", "delta_rule_bwd", "o_norm",
         "rmsnorm_gate", "rmsnorm_gate_bwd", "gate_proj")


class Context(bench.Context):
    """The benchmark's context, reading each trace back before deleting it."""

    tables = []
    category = None  # --category: the HLO category whose operations are listed
    steps = None
    flash_fwd = None
    flash_bwd = None
    flash_heads = None
    flash_layouts = None
    flash_window = None
    gated_conv = None
    causal_conv = None
    ssd = None
    delta_rule = None
    embedding_grad = None
    grouped_matmul = None
    row_movement = None
    tuned = None
    moe = None
    selection = None

    def say(self, **row):
        if row.get("phase") == "traced":
            Context.steps = row["steps"]
        if row.get("phase") in ("built", "first_steps"):  # set-up, up to here
            from mxnet_tpu import profiler, tuning

            row["program_setup"] = {
                "seconds": profiler.setup_seconds(),
                "compiling": profiler.setup_seconds(compiling=True),
                "since_start": self.since_start(),
                "compile_stats": tuning.compile_stats()}
        super().say(**row)

    def cleanup(self):
        from mxnet_tpu import profiler, telemetry, tuning

        for path in self._trace_dirs:
            t0 = time.perf_counter()
            agg = profiler.aggregate(path, depth=5, window="bench.trace_window",
                                     top=None if Context.category else 40)
            Context.tables.append((agg, time.perf_counter() - t0))
        Context.setup = profiler.setup_seconds()
        Context.compile_stats = tuning.compile_stats()
        Context.flash_fwd = telemetry.flash_fwd_branches()
        Context.flash_bwd = telemetry.flash_bwd_branches()
        Context.flash_heads = telemetry.flash_heads_per_step()
        Context.flash_layouts = telemetry.flash_layouts()
        Context.flash_window = telemetry.flash_window_blocks()
        Context.gated_conv = telemetry.gated_conv_branches()
        Context.causal_conv = telemetry.causal_conv_branches()
        Context.ssd = telemetry.ssd_branches()
        Context.delta_rule = telemetry.delta_rule_branches()
        Context.embedding_grad = telemetry.embedding_grad_branches()
        Context.grouped_matmul = telemetry.grouped_matmul_branches()
        Context.row_movement = telemetry.row_movement_branches()
        Context.tuned = tuning.table().entries()
        Context.moe = telemetry.moe_counts()
        Context.selection = telemetry.selection_counts()
        super().cleanup()


def scoped(agg, steps):
    """Milliseconds a step: the formulas a per-layer reader would use."""
    per = 1e3 / steps
    ph, named, busy = agg["phase_s"], agg["named_s"], agg["busy_s"]
    out = {"steps": steps, "busy_ms": busy * per,
           "forward_ms": ph["forward"] * per, "backward_ms": ph["backward"] * per,
           "optimizer_ms": (ph["optimizer"] + ph["grad_post"]) * per,
           "collective_ms": ph["collective"] * per, "other_ms": ph["other"] * per,
           "unattributed_share": 100.0 * ph["other"] / busy,
           "batchnorm_ms": (named.get("batchnorm", 0) + named.get("batchnorm_bwd", 0)) * per,
           "attention_fwd_ms": named.get("attention", 0) * per,
           "attention_bwd_ms": named.get("attention_bwd", 0) * per,
           "layernorm_ms": (named.get("layernorm", 0) + named.get("layernorm_bwd", 0)) * per,
           "part_ms": {k: named[k] * per for k in PARTS if k in named},
           "kernel_ms": {k: v * per for k, v in agg["kernel_s"].items()},
           "kernel_calls_per_step": {k: v / steps for k, v in agg["kernel_calls"].items()},
           "category_ms": {k: v * per for k, v in list(agg["category_s"].items())[:12]},
           "kind_ms": {k: v * per for k, v in list(agg["kind_s"].items())[:24]},
           "scope_ms": {k: v * per for k, v in list(agg["scope_s"].items())[:40]},
           "ops_ms": [[o["name"], o["phase"], o["scope"], o["category"],
                       o["seconds"] * per, o["calls"] / steps] for o in agg["ops"][:40]],
           "clock_offset_us": agg["clock_offset_us"], "launch_pairs": agg["launch_pairs"],
           "idle_gaps": agg["idle_gaps"]}
    return out


def category_ops(agg, steps, category):
    """Every operation of one HLO category, summed by (scope, result shape):
    milliseconds and calls a step, largest first."""
    groups = {}
    for o in agg["ops"]:
        if o["category"] == category:
            ent = groups.setdefault((o["scope"], o["phase"], o["shape"]), [0.0, 0.0, []])
            ent[0] += o["seconds"] * 1e3 / steps
            ent[1] += o["calls"] / steps
            ent[2].append(o["name"])
    rows = sorted(groups.items(), key=lambda kv: -kv[1][0])
    return {"category": category, "ms": sum(v[0] for _, v in rows),
            "rows": [{"scope": k[0], "phase": k[1], "shape": k[2], "ms": v[0],
                      "calls_per_step": v[1], "ops": sorted(v[2])[:4]} for k, v in rows]}


def main(argv):
    argv = list(argv)
    if "--category" in argv:
        at = argv.index("--category")
        Context.category = argv[at + 1]
        del argv[at:at + 2]
    bench.Context = Context
    rc = bench.main(list(argv) + ["--trace", "1"])
    from mxnet_tpu import profiler_trace  # the run has imported the program

    for agg, seconds in Context.tables:
        row = {"aggregate_seconds": seconds, "setup_seconds": Context.setup,
               "compile_stats": Context.compile_stats}
        if Context.flash_fwd:  # which forward each traced attention took
            row["flash_fwd_branches"] = Context.flash_fwd
        if Context.flash_bwd:  # and which backward
            row["flash_bwd_branches"] = Context.flash_bwd
        if Context.flash_heads:  # and how many heads a grid step each kernel
            row["flash_heads_per_step"] = Context.flash_heads
        if Context.flash_layouts:  # and whether it read the projection in place
            row["flash_layouts"] = Context.flash_layouts
        if Context.flash_window:  # and the tiles its window calls visit
            row["flash_window_blocks"] = Context.flash_window
        if Context.gated_conv:  # and which path each gated short convolution
            row["gated_conv_branches"] = Context.gated_conv
        if Context.causal_conv:  # and which each causal filter under SiLU
            row["causal_conv_branches"] = Context.causal_conv
        if Context.ssd:  # and which each state-space scan
            row["ssd_branches"] = Context.ssd
        if Context.delta_rule:  # and which each gated delta rule
            row["delta_rule_branches"] = Context.delta_rule
        if Context.embedding_grad:  # and which form each embedding's weight gradient
            row["embedding_grad_branches"] = Context.embedding_grad
        if Context.grouped_matmul:  # and which each grouped matmul, by product
            row["grouped_matmul_branches"] = Context.grouped_matmul
        if Context.row_movement:  # and which each movement of rows around them
            row["row_movement_branches"] = Context.row_movement
        if Context.tuned:  # the tiles each kernel shape ran with
            row["tuning_entries"] = Context.tuned
        if Context.moe:  # read once after the window by the cell's adapter
            row["moe_counts"] = Context.moe
        if Context.selection:  # likewise: pairs selected, rows searched, by layer
            row["selection_counts"] = Context.selection
        if agg is None:
            print("no device operations in the trace")
        else:
            print(profiler_trace.format_table(agg, top=40))
            row.update(scoped(agg, Context.steps))
        print(json.dumps({"scoped": row}), flush=True)
        if agg is not None and Context.category:
            print(json.dumps({"category_ops": category_ops(
                agg, Context.steps, Context.category)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
