"""Launches of the program for each training step of the window."""
NAME = "launches_per_step.train"
UNIT = "launches/step"
LAYER = "entry points"
MOVES = "train_samples_per_s"
SOURCE = "program_counter"  # mxnet_tpu.profiler.launch_count


def read(run):
    w = run.get("window") or {}
    if not w.get("steps") or "launches" not in w:
        return None
    return w["launches"] / w["steps"]
