"""Seconds the compiler (or the read of its cache) took before the window."""
NAME = "compile_s"
UNIT = "s"
LAYER = "compile"
MOVES = "setup_s"
SOURCE = "program_counter"  # mxnet_tpu.tuning.compile_stats


def read(run):
    s = run.get("setup") or {}
    return s.get("compile_seconds")
