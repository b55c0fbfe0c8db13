"""Host syncs the program itself made for each training step of the window
(its in-flight window's reads; the benchmark's own waits are not counted)."""
NAME = "host_syncs_per_step.train"
UNIT = "syncs/step"
LAYER = "dispatch"
MOVES = "train_samples_per_s"
SOURCE = "program_counter"  # mxnet_tpu.profiler.host_sync_count


def read(run):
    w = run.get("window") or {}
    if not w.get("steps") or "host_syncs" not in w:
        return None
    return w["host_syncs"] / w["steps"]
