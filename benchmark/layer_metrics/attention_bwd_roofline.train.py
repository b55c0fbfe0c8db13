"""Share of its roofline that the flash attention backward kernel
(``flash_attention_bwd``) reached in the traced part of the window: see
``harness/program_trace.py:kernel_roofline_share``. In percent."""
NAME = "attention_bwd_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    from harness import program_trace

    if not run.get("trace_dir"):
        return None
    return program_trace.kernel_roofline_share(
        run, "flash_attention_bwd", backward=True)
