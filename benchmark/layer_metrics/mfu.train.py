"""Model FLOP utilisation of training: the model's operations a sample
(``flops/<family>.py``, from shapes alone, recomputation not counted) times
the samples a second a chip of the untraced part of the window, over the
chip's published bf16 peak (``harness/peaks.py``). In percent."""
from harness import peaks
from harness.loader import BenchError

NAME = "mfu.train"
UNIT = "%"
LAYER = "model step"
MOVES = "train_samples_per_s"
SOURCE = "host_clock"


def read(run):
    w = run.get("window") or {}
    flops = run.get("train_flops_per_sample")
    if not flops or not w.get("samples_per_s_per_chip"):
        return None
    share = 100.0 * flops * w["samples_per_s_per_chip"] / peaks.peak(
        run["device_kind"], "bf16_flops")
    if share > 100.0:
        raise BenchError("mfu.train reads %.1f%%: operations counted too high or time "
                         "too short" % share)
    return share
