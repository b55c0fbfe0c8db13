"""Share of its roofline that the indexer's kernel (``indexer_select``: the
index scores and the exact top-k selection of a block of query rows) reached
in the traced part of the window, by ``harness/program_trace.py:
kernel_roofline_share``'s pattern: the larger of operations / peak and bytes /
bandwidth of one call (``flops/<family>.py:indexer_kernel``, from shapes: the
score products alone, the bisection's comparisons are no operations of the
model's) over the device seconds a call took. In percent. Nothing where the
run was not traced, the family has no such function, or no kernel of that
name ran."""
NAME = "indexer_select_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"
KERNEL = "indexer_select"


def read(run):
    from harness import peaks, program_trace
    from harness.loader import BenchError, load_module

    agg = program_trace.aggregate(run)
    if not agg or not agg.get("kernel_s", {}).get(KERNEL):
        return None
    flops = load_module("flops", run["config"]["family"])
    if not hasattr(flops, "indexer_kernel"):
        return None
    ops, nbytes = flops.indexer_kernel(run["config"], run["traffic"])
    kind = run["device_kind"]
    floor_s = max(ops / peaks.peak(kind, "bf16_flops"),
                  nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
    share = 100.0 * floor_s * agg["kernel_calls"][KERNEL] / agg["kernel_s"][KERNEL]
    if share > 100.0:
        raise BenchError("%s reads %.1f%% of its roofline: operations or bytes counted "
                         "too high, or the time leaves out part of the work" % (KERNEL, share))
    return share
