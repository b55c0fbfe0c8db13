"""Operations of one training sequence of LFM2's sparse decoder on this chip's
share, from the shapes alone. A multiply-add counts as two operations. Matrix
products only: a convolution layer's two projections (``W_in`` 3 x hidden,
``W_out``); an attention layer's three (``W_q``, ``W_kv``, ``W_o``) and its two
attention products over the UNMASKED half of the causal square (a position
sees (T + 1) / 2 keys on average); the dense layers' SwiGLU; for an expert
layer the router and the routed experts HELD at the slots an even routing
sends them (``num_experts_per_tok * held / published`` a token: 4 x 16 / 64 =
1), not the slots a run happened to route; the tied head's product over the
sliced vocabulary; all of it three times for training. Embedding look-ups,
norms, rotary positions, softmax, SiLU, the convolutions' taps and gates (7
operations an element: 0.001 of the step), the sort and the gathers are left
out.

``attention_kernel`` gives one call of the flash kernels its operations and
the bytes it must move, and ``gated_conv_op`` one layer's ``gated_short_conv``
its own, for their roofline shares.
"""
from __future__ import annotations


def forward_flops_per_token(config, traffic):
    c, t = config, traffic["sequence"]
    h, heads, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    d = h // heads
    router_width = c["published"]["num_experts"]
    conv = 2 * (h * 3 * h + h * h)
    attn = (2 * (h * heads * d + h * 2 * kv * d + heads * d * h)
            + 2 * heads * 2 * d * (t + 1) / 2.0)
    dense = 2 * 3 * h * c["intermediate_size"]
    slots = c["num_experts_per_tok"] * c["experts_held"][1] / float(router_width)
    moe = 2 * h * router_width + 2 * 3 * h * c["moe_intermediate_size"] * slots
    mixers = sum(conv if kind == "conv" else attn for kind in c["layer_types"])
    n_dense = c["num_dense_layers"]
    n_moe = c["num_hidden_layers"] - n_dense
    return mixers + n_dense * dense + n_moe * moe + 2 * h * c["vocab_size"]


def train_flops_per_sample(config, traffic):
    return 3 * forward_flops_per_token(config, traffic) * traffic["sequence"]


def attention_kernel(config, traffic, backward):
    """(operations, bytes) of one call of ``flash_attention_fwd`` or
    ``flash_attention_bwd`` on the cell's batch: B x 32 query heads on 8 K/V
    heads of 64, two-byte operands, the causal half of a square of T, no mask
    operand. Forward: the score and value products; it reads q, k, v (K/V
    once a K/V head) and writes the output and the row statistic (float32, 4
    bytes a query). Backward: five products (scores, dv, dp, dk, dq); it
    reads q, k, v, dO and the two rows of statistics and writes dq, dk, dv
    (dk and dv once a K/V head: what a query head's float32 part costs on the
    way is the kernel's own)."""
    c = config
    b, t = traffic["batch"], traffic["sequence"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // heads
    pairs = b * heads * t * (t + 1) / 2.0
    q_bytes, kv_bytes = b * heads * t * d * 2, b * kv * t * d * 2
    if backward:
        return 2 * pairs * 5 * d, 3 * q_bytes + 4 * kv_bytes + b * heads * 2 * t * 4
    return 2 * pairs * 2 * d, 2 * q_bytes + 2 * kv_bytes + b * heads * t * 4


def gated_conv_op(config, traffic, backward):
    """(operations, bytes) of one convolution layer's ``gated_short_conv`` on
    the cell's batch: (B, T, 3 x hidden) in, (B, T, hidden) out, two-byte
    operands, K taps a channel. Forward: the gate product, K multiply-adds
    and the second gate, 2 K + 1 operations an element; it reads ``bcx`` and
    writes ``y``. Backward: ``Z`` and ``V`` again, the three gates' and the
    filter's transposes and the taps' gradient, 6 K + 3 an element; it reads
    ``bcx`` and the output's gradient and writes ``bcx``'s. The taps
    themselves are 12 KB."""
    b, t, h, k = (traffic["batch"], traffic["sequence"], config["hidden_size"],
                  config["conv_L_cache"])
    rows = b * t * h
    taps = 2 * h * k * 2
    if backward:
        return (6 * k + 3) * rows, (3 + 1 + 3) * rows * 2 + taps
    return (2 * k + 1) * rows, (3 + 1) * rows * 2 + taps // 2
