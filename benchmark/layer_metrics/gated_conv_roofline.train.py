"""Share of its roofline that the op ``gated_short_conv`` reached in the
traced part of the window, both passes together: the least time the chip
could take for them (the larger of operations / peak and bytes / bandwidth of
one layer's forward plus one layer's backward,
``flops/<family>.py:gated_conv_op``, from shapes: an elementwise op, so the
bytes decide), times the convolution layers held and the steps traced, over
the device seconds that ran under the scopes ``gated_conv`` and
``gated_conv_bwd`` (XLA's fusions of the op's ``jax.numpy`` formula) or, where
the op is a kernel, under the kernels' names (``gated_short_conv_fwd`` /
``gated_short_conv_bwd``). In percent. Nothing where the run was not traced,
the family has no such function, or nothing ran under those names."""
NAME = "gated_conv_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"
SCOPES = ("gated_conv", "gated_conv_bwd")
KERNELS = ("gated_short_conv_fwd", "gated_short_conv_bwd")


def read(run):
    from harness import peaks, program_trace
    from harness.loader import BenchError, load_module

    agg = program_trace.aggregate(run)
    steps = (run.get("trace") or {}).get("steps")
    if not agg or not steps:
        return None
    seconds = sum(agg.get("kernel_s", {}).get(k, 0.0) for k in KERNELS) \
        or sum(agg.get("named_s", {}).get(s, 0.0) for s in SCOPES)
    flops = load_module("flops", run["config"]["family"])
    if not seconds or not hasattr(flops, "gated_conv_op"):
        return None
    kind = run["device_kind"]
    floor_s = sum(max(ops / peaks.peak(kind, "bf16_flops"),
                      nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
                  for ops, nbytes in (flops.gated_conv_op(run["config"], run["traffic"], b)
                                      for b in (False, True)))
    layers = sum(1 for k in run["config"]["layer_types"] if k == "conv")
    share = 100.0 * floor_s * layers * steps / seconds
    if share > 100.0:
        raise BenchError("gated_short_conv reads %.1f%% of its roofline: operations or "
                         "bytes counted too high, or the time leaves out part of the work"
                         % share)
    return share
