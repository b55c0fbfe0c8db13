"""Share of its roofline that the window layers' forward kernel
(``window_attention_fwd``) reached in the traced part of the window: the least
time the chip could take for one call (the larger of operations / peak and
bytes / bandwidth, ``flops/<family>.py:window_attention_kernel``, from shapes:
the pairs inside the window, K/V read once a K/V head) over the device seconds
a call took (``kernel_s`` / ``kernel_calls`` by the ``pallas_call``'s own
name, so the full layer's calls are not in the mean). In percent; over 100 is
an error, as ``harness/program_trace.py:kernel_roofline_share``'s. Nothing
where the run was not traced, the family has no such function, or no kernel of
that name ran."""
NAME = "window_attention_fwd_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def share(run, kernel, backward):
    from harness import peaks, program_trace
    from harness.loader import BenchError, load_module

    agg = program_trace.aggregate(run)
    if not agg or not agg.get("kernel_s", {}).get(kernel):
        return None
    flops = load_module("flops", run["config"]["family"])
    if not hasattr(flops, "window_attention_kernel"):
        return None
    ops, nbytes = flops.window_attention_kernel(run["config"], run["traffic"], backward)
    kind = run["device_kind"]
    floor_s = max(ops / peaks.peak(kind, "bf16_flops"),
                  nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
    value = 100.0 * floor_s * agg["kernel_calls"][kernel] / agg["kernel_s"][kernel]
    if value > 100.0:
        raise BenchError("%s reads %.1f%% of its roofline: operations or bytes counted "
                         "too high, or the time leaves out part of the work" % (kernel, value))
    return value


def read(run):
    if not run.get("trace_dir"):
        return None
    return share(run, "window_attention_fwd", backward=False)
