"""Share of its roofline that the op ``causal_conv_silu`` reached in the traced
part of the window as a delta-attention mixer's filter (every column of the
fused q, k, v projection from column 0, no bias), both passes together: as
``delta_rule_roofline.train``, whose arithmetic it uses (the layers counted by
``flops/<family>.py:kda_layers``), with ``flops/<family>.py:causal_conv_op``
(an elementwise op, so the bytes decide) over the device seconds under the
kernels' names (``causal_conv_silu_fwd`` / ``causal_conv_silu_bwd``) or, where
the call takes XLA's branch, under the scopes ``causal_conv`` and
``causal_conv_bwd``. In percent. ``causal_conv_roofline.train`` is the same
reading for a family whose ``layer_types`` name its ``mamba`` layers, which is
how that reader counts them: a family without the key cannot list it."""
NAME = "kda_conv_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"
SCOPES = ("causal_conv", "causal_conv_bwd")
KERNELS = ("causal_conv_silu_fwd", "causal_conv_silu_bwd")


def read(run):
    from harness.loader import load_module

    delta = load_module("layer_metrics", "delta_rule_roofline.train")
    return delta.share(run, "causal_conv_silu", SCOPES, KERNELS, "causal_conv_op")
