"""The program's own reading of a run's traced window, once a run, for the
per-layer readers that want device time by scope or by kernel name:
``mxnet_tpu.profiler.aggregate(trace_dir, window="bench.trace_window")``.
Nothing where the run was not traced or the program has no such reader (a
parent commit that lacks it reports no such metric)."""
import functools


@functools.lru_cache(maxsize=2)  # a run has one traced window and several readers
def _read(trace_dir):
    try:
        from mxnet_tpu import profiler

        return profiler.aggregate(trace_dir, window="bench.trace_window")
    except (ImportError, AttributeError):
        return None


def aggregate(run):
    trace_dir = run.get("trace_dir")
    return _read(trace_dir) if trace_dir else None


def kernel_roofline_share(run, kernel, backward):
    """Percent of its roofline that the kernel ``kernel`` reached in the traced
    window: the larger of operations / peak and bytes / bandwidth of one call
    (the family's ``flops/<family>.py:attention_kernel``, from shapes) over the
    device seconds a call took (``kernel_s`` / ``kernel_calls`` by the
    ``pallas_call``'s name). Nothing where the family has no such function or
    the kernel did not run."""
    from . import peaks
    from .loader import BenchError, load_module

    agg = aggregate(run)
    if not agg or not agg.get("kernel_s", {}).get(kernel):
        return None
    flops = load_module("flops", run["config"]["family"])
    if not hasattr(flops, "attention_kernel"):
        return None
    ops, nbytes = flops.attention_kernel(run["config"], run["traffic"], backward)
    kind = run["device_kind"]
    floor_s = max(ops / peaks.peak(kind, "bf16_flops"),
                  nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
    call_s = agg["kernel_s"][kernel] / agg["kernel_calls"][kernel]
    share = 100.0 * floor_s / call_s
    if share > 100.0:
        raise BenchError("%s reads %.1f%% of its roofline: operations or bytes counted "
                         "too high, or the time leaves out part of the work" % (kernel, share))
    return share
