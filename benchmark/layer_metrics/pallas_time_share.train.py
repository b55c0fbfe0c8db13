"""Share of the device's busy time, in the traced part of the window, that
went to Pallas kernels: operations whose text in the trace names
``tpu_custom_call`` (``harness/trace_reduce.py``). In percent. Nothing where
the trace shows no such operation."""
NAME = "pallas_time_share.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    t = run.get("trace") or {}
    if not t.get("busy_s") or not t.get("kernel_s"):
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
