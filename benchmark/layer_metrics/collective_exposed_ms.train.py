"""Milliseconds a training step spends in collective operations on a chip's
own line of operations (all-reduce and its kin, their start and done halves
included), where no other operation runs at the same time: the part of the
exchange between chips that compute does not hide. From the traced part of the
window, averaged over the chips. Nothing on one chip."""
NAME = "collective_exposed_ms.train"
UNIT = "ms"
LAYER = "parallel"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    t = run.get("trace") or {}
    if run.get("chips", 1) < 2 or not t.get("steps") or "collective_s" not in t:
        return None
    return 1e3 * t["collective_s"] / t["steps"]
