"""Share of its roofline that the op ``gated_delta_rule`` reached in the traced
part of the window, both passes together: the least time the chip could take
for them (the larger of operations / peak and bytes / bandwidth of one layer's
forward, plus the same of one layer's backward,
``flops/<family>.py:delta_rule_op``, from shapes: the recurrence's count and
each operand and result once), times the delta-attention layers held
(``flops/<family>.py:kda_layers``) and the steps traced, over the device
seconds that ran under the scopes ``delta_rule`` and ``delta_rule_bwd`` (XLA's
fusions and products of the op's ``jax.numpy`` formula, a forward run again
under recomputation among them) or, where the op is a kernel, under the
kernels' names (``delta_rule_fwd`` / ``delta_rule_bwd``). In percent. Nothing
where the run was not traced, the family has no such function, or nothing ran
under those names.

``share`` is the arithmetic, for any op that runs once a delta-attention layer
and pass: ``kda_conv_roofline.train`` reads the mixer's filter with it."""
NAME = "delta_rule_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"
SCOPES = ("delta_rule", "delta_rule_bwd")
KERNELS = ("delta_rule_fwd", "delta_rule_bwd")


def share(run, op, scopes, kernels, function):
    """Percent of the roofline of ``flops.<function>`` (forward plus backward,
    a delta-attention layer) that the device seconds under ``kernels``, or
    else under ``scopes``, reached."""
    from harness import peaks, program_trace
    from harness.loader import BenchError, load_module

    agg = program_trace.aggregate(run)
    steps = (run.get("trace") or {}).get("steps")
    if not agg or not steps:
        return None
    seconds = sum(agg.get("kernel_s", {}).get(k, 0.0) for k in kernels) \
        or sum(agg.get("named_s", {}).get(s, 0.0) for s in scopes)
    flops = load_module("flops", run["config"]["family"])
    count, layers = getattr(flops, function, None), getattr(flops, "kda_layers", None)
    if not seconds or count is None or layers is None:
        return None
    kind = run["device_kind"]
    floor_s = sum(max(ops / peaks.peak(kind, "bf16_flops"),
                      nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
                  for ops, nbytes in (count(run["config"], run["traffic"], b)
                                      for b in (False, True)))
    reached = 100.0 * floor_s * layers(run["config"]) * steps / seconds
    if reached > 100.0:
        raise BenchError("%s reads %.1f%% of its roofline: operations or bytes counted "
                         "too high, or the time leaves out part of the work" % (op, reached))
    return reached


def read(run):
    return share(run, "gated_delta_rule", SCOPES, KERNELS, "delta_rule_op")
