"""Share of the device's busy time, in the traced part of the window, that
went to the delta-attention mixers: operations under the scope ``kda`` (the
fused projection, the op ``causal_conv_silu`` under ``causal_conv`` /
``causal_conv_bwd``, the low-rank pairs and the log-decays, the op
``gated_delta_rule`` under ``delta_rule`` / ``delta_rule_bwd``, the head's
norm-then-gate, the output projection; both passes), by the program's own
names in the trace (``mxnet_tpu.profiler.aggregate``'s ``named_s``). In
percent. Nothing where the run was not traced, where the program has no such
reader, or where no operation ran under that scope."""
NAME = "linear_attention_share.train"
UNIT = "%"
LAYER = "model step"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    from harness import program_trace

    agg = program_trace.aggregate(run)
    if not agg or not agg.get("busy_s") or not agg.get("named_s", {}).get("kda"):
        return None
    return 100.0 * agg["named_s"]["kda"] / agg["busy_s"]
