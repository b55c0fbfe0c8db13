"""Operations of one training sequence of SmallThinker's decoder on this
chip's share, from the shapes alone. A multiply-add counts as two operations.
Matrix products only: the attention's three projections (``W_q``, ``W_kv``,
``W_o``); its two products over the pairs a layer's mask leaves, the causal
half of the square in a full layer (a position sees (T + 1) / 2 keys on
average) and the pairs INSIDE THE WINDOW in a window layer (query ``t`` sees
``min(t + 1, window)`` keys: the pairs the window cuts are no work of the
model's, whatever a kernel spends on the edge blocks); the router and the
routed experts HELD at the slots an even routing sends them
(``moe_num_active_primary_experts * held / published`` a token: 6 x 16 / 64 =
1.5), not the slots a run happened to route; the untied head's product over
the sliced vocabulary; all of it three times for training. Embedding
look-ups, norms, rotary positions, softmax, ReLU, the sort and the gathers are
left out.

``attention_kernel`` gives one call of the flash kernels on the FULL layer its
operations and the bytes it must move, ``window_attention_kernel`` one call
of ``window_attention_fwd`` / ``window_attention_bwd`` its own, for their
roofline shares.
"""
from __future__ import annotations


def window_pairs(t, window):
    """Pairs a head attends in one causal sequence of ``t`` under a window:
    ``sum over rows of min(row + 1, window)``; ``window`` None is the whole
    causal half."""
    full = t if window is None else min(t, window)
    return full * (full + 1) // 2 + (t - full) * full


def layer_windows(config):
    """The window of each layer held, None where it attends the whole past."""
    return [config["sliding_window_size"] if w else None
            for w in config["sliding_window_layout"]]


def train_flops_per_sample(config, traffic):
    c, t = config, traffic["sequence"]
    h, heads, kv, d = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"])
    layers, router_width = c["num_hidden_layers"], c["published"]["moe_num_primary_experts"]
    proj = 2 * (h * heads * d + h * 2 * kv * d + heads * d * h)
    slots = c["moe_num_active_primary_experts"] * c["experts_held"][1] / float(router_width)
    moe = 2 * h * router_width + 2 * 3 * h * c["moe_ffn_hidden_size"] * slots
    pairs = sum(window_pairs(t, w) for w in layer_windows(c))
    forward = t * (layers * (proj + moe) + 2 * h * c["vocab_size"]) \
        + 2 * heads * 2 * d * pairs
    return 3 * forward


def _kernel(config, traffic, backward, window):
    """(operations, bytes) of one call of either flash kernel on the cell's
    batch: B x 28 query heads on 4 K/V heads of 128, two-byte operands, the
    pairs the mask leaves, no mask operand. Forward: the score and value
    products; it reads q, k, v (K/V once a K/V head) and writes the output and
    the row statistic (float32, 4 bytes a query). Backward: five products
    (scores, dv, dp, dk, dq); it reads q, k, v, dO and the two rows of
    statistics and writes dq, dk, dv (dk and dv once a K/V head: what a query
    head's float32 part costs on the way is the kernel's own)."""
    c = config
    b, t = traffic["batch"], traffic["sequence"]
    heads, kv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pairs = b * heads * window_pairs(t, window)
    q_bytes, kv_bytes = b * heads * t * d * 2, b * kv * t * d * 2
    if backward:
        return 2 * pairs * 5 * d, 3 * q_bytes + 4 * kv_bytes + b * heads * 2 * t * 4
    return 2 * pairs * 2 * d, 2 * q_bytes + 2 * kv_bytes + b * heads * t * 4


def attention_kernel(config, traffic, backward):
    """One call of ``flash_attention_fwd`` / ``flash_attention_bwd``: the full
    layer's, over the causal half."""
    return _kernel(config, traffic, backward, None)


def window_attention_kernel(config, traffic, backward):
    """One call of ``window_attention_fwd`` / ``window_attention_bwd``: a
    window layer's, over the pairs inside the window; the same bytes (K/V
    read once a K/V head, nothing read for the window)."""
    return _kernel(config, traffic, backward, config["sliding_window_size"])
