"""Share of its roofline that the window layers' backward kernel
(``window_attention_bwd``) reached in the traced part of the window: as
``window_attention_fwd_roofline.train``, whose arithmetic it uses, with the
backward's five products and bytes. In percent."""
NAME = "window_attention_bwd_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    from harness.loader import load_module

    if not run.get("trace_dir"):
        return None
    forward = load_module("layer_metrics", "window_attention_fwd_roofline.train")
    return forward.share(run, "window_attention_bwd", backward=True)
