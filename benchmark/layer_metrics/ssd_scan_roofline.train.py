"""Share of its roofline that the op ``ssd_scan`` reached in the traced part of
the window, both passes together: the least time the chip could take for them
(the larger of operations / peak and bytes / bandwidth of one layer's forward,
plus the same of one layer's backward, ``flops/<family>.py:ssd_op``, from
shapes), times the Mamba layers held and the steps traced, over the device
seconds that ran under the scopes ``ssd`` and ``ssd_bwd`` (XLA's fusions and
products of the op's ``jax.numpy`` formula) or, where the op is a kernel, under
the kernels' names (``ssd_scan_fwd`` / ``ssd_scan_bwd``). In percent. Nothing
where the run was not traced, the family has no such function, or nothing ran
under those names."""
NAME = "ssd_scan_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"
SCOPES = ("ssd", "ssd_bwd")
KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")


def share(run, op, scopes, kernels, function):
    """Percent of the roofline of ``flops.<function>`` (forward plus
    backward, a Mamba layer) that the device seconds under ``kernels``, or
    else under ``scopes``, reached."""
    from harness import peaks, program_trace
    from harness.loader import BenchError, load_module

    agg = program_trace.aggregate(run)
    steps = (run.get("trace") or {}).get("steps")
    if not agg or not steps:
        return None
    seconds = sum(agg.get("kernel_s", {}).get(k, 0.0) for k in kernels) \
        or sum(agg.get("named_s", {}).get(s, 0.0) for s in scopes)
    count = getattr(load_module("flops", run["config"]["family"]), function, None)
    if not seconds or count is None:
        return None
    kind = run["device_kind"]
    floor_s = sum(max(ops / peaks.peak(kind, "bf16_flops"),
                      nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
                  for ops, nbytes in (count(run["config"], run["traffic"], b)
                                      for b in (False, True)))
    layers = sum(1 for k in run["config"]["layer_types"] if k == "mamba")
    reached = 100.0 * floor_s * layers * steps / seconds
    if reached > 100.0:
        raise BenchError("%s reads %.1f%% of its roofline: operations or bytes counted "
                         "too high, or the time leaves out part of the work" % (op, reached))
    return reached


def read(run):
    return share(run, "ssd_scan", SCOPES, KERNELS, "ssd_op")
