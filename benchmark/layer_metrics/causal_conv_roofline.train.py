"""Share of its roofline that the op ``causal_conv_silu`` reached in the traced
part of the window, both passes together: as ``ssd_scan_roofline.train``, whose
arithmetic it uses, with ``flops/<family>.py:causal_conv_op`` (an elementwise
op, so the bytes decide) over the device seconds under the scopes
``causal_conv`` and ``causal_conv_bwd`` or, where the op is a kernel, under the
kernels' names (``causal_conv_silu_fwd`` / ``causal_conv_silu_bwd``). In
percent."""
NAME = "causal_conv_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"
SCOPES = ("causal_conv", "causal_conv_bwd")
KERNELS = ("causal_conv_silu_fwd", "causal_conv_silu_bwd")


def read(run):
    from harness.loader import load_module

    scan = load_module("layer_metrics", "ssd_scan_roofline.train")
    return scan.share(run, "causal_conv_silu", SCOPES, KERNELS, "causal_conv_op")
