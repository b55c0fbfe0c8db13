"""Operations of one training sequence of Solar Open 2's hybrid sparse decoder
on this chip's share, from the shapes alone. A multiply-add counts as two
operations. Matrix products only: a delta-attention layer's projections
(``W_qkv`` to 3 H K columns, the decay's and the output gate's low-rank pairs
hidden -> K -> H K, ``W_b`` to H, ``W_o``) and its delta rule by the
RECURRENCE's count, whatever chunk the program takes (a token and head: the
decay of the (K, V) state, K V, and three K x V products, the state's read by
the key, the rank-one update and the read by the query, 2 K V each: 7 K V), so
that no later kernel, which may do less than the chunked form's products, reads
over 100 %; an attention layer's four (``W_q``, ``W_kv``, ``W_gate``, ``W_o``)
and its two attention products over the UNMASKED half of the causal square (a
position sees (T + 1) / 2 keys on average); every layer's router (all the
published outputs), its routed experts HELD at the slots an even routing sends
them (``num_experts_per_tok * held / published`` a token: 8 x 8 / 320 = 0.2),
not the slots a run happened to route, and its shared expert; the untied head's
product over the sliced vocabulary; all of it three times for training. No
recomputed operation counts, whatever the configuration's
``assumed.recomputation`` makes the step run again. Embedding look-ups, norms,
the unit length of q and k, softmax, SiLU, sigmoid, softplus, the filter's
taps, the gates, the sort and the gathers are left out.

``attention_kernel`` gives one call of the flash kernels its operations and
the bytes it must move, ``delta_rule_op`` one layer's ``gated_delta_rule`` and
``causal_conv_op`` one layer's ``causal_conv_silu`` theirs, for their roofline
shares.
"""
from __future__ import annotations


def _kda(config):
    lin = config["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def kda_layers(config):
    """The delta-attention layers held: every layer ``gqa_layers`` does not
    name."""
    return config["num_hidden_layers"] - len(config["gqa_layers"])


def delta_rule_flops_per_token(config):
    """The recurrence's count a token, all heads: 7 K V a head."""
    heads, d, _ = _kda(config)
    return 7 * heads * d * d


def forward_flops_per_token(config, traffic):
    c, t = config, traffic["sequence"]
    h, heads, kv, d = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"])
    kh, kd, _ = _kda(c)
    inner = kh * kd
    kda = (2 * (h * 3 * inner + 2 * (h * kd + kd * inner) + h * kh + inner * h)
           + delta_rule_flops_per_token(c))
    gated = heads * d if c.get("use_gqa_gate") else 0
    attn = (2 * (h * heads * d + h * 2 * kv * d + h * gated + heads * d * h)
            + 2 * heads * 2 * d * (t + 1) / 2.0)
    router_width = c["published"]["n_routed_experts"]
    width = c["moe_intermediate_size"]
    slots = c["num_experts_per_tok"] * c["experts_held"][1] / float(router_width)
    moe = (2 * h * router_width + 2 * 3 * h * width * slots
           + 2 * 3 * h * width * c["n_shared_experts"])
    n_kda = kda_layers(c)
    n_gqa = c["num_hidden_layers"] - n_kda
    return (n_kda * kda + n_gqa * attn + c["num_hidden_layers"] * moe
            + 2 * h * c["vocab_size"])


def train_flops_per_sample(config, traffic):
    return 3 * forward_flops_per_token(config, traffic) * traffic["sequence"]


def attention_kernel(config, traffic, backward):
    """(operations, bytes) of one call of ``flash_attention_fwd`` or
    ``flash_attention_bwd`` on the cell's batch: B x the query heads held on the
    K/V heads held, of ``head_dim``, two-byte operands, the causal half of a
    square of T, no mask operand. Forward: the score and value products; it
    reads q, k, v (K/V once a K/V head) and writes the output and the row
    statistic (float32, 4 bytes a query). Backward: five products (scores, dv,
    dp, dk, dq); it reads q, k, v, dO and the two rows of statistics and writes
    dq, dk, dv (dk and dv once a K/V head)."""
    c = config
    b, t = traffic["batch"], traffic["sequence"]
    heads, kv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pairs = b * heads * t * (t + 1) / 2.0
    q_bytes, kv_bytes = b * heads * t * d * 2, b * kv * t * d * 2
    if backward:
        return 2 * pairs * 5 * d, 3 * q_bytes + 4 * kv_bytes + b * heads * 2 * t * 4
    return 2 * pairs * 2 * d, 2 * q_bytes + 2 * kv_bytes + b * heads * t * 4


def delta_rule_op(config, traffic, backward):
    """(operations, bytes) of one delta-attention layer's ``gated_delta_rule``
    on the cell's batch. Forward: the recurrence's count; it reads ``q``, ``k``,
    ``v`` (B, T, H, K) in two bytes, the log-decays ``g`` (B, T, H, K) in FOUR
    (``kda_log_decay`` hands them over in float32) and ``beta`` (B, T, H), and
    writes ``o``. Backward: the transposes of the forward's products, twice its
    count (what it builds again is not counted: a floor); it reads the same and
    the output's gradient and writes the gradient of each. The chunks' opening
    states are the op's own to keep or to compute again, and are not
    counted."""
    heads, d, _ = _kda(config)
    rows = traffic["batch"] * traffic["sequence"]
    ops = delta_rule_flops_per_token(config) * rows
    operands = rows * heads * (3 * d * 2 + d * 4 + 2)
    result = rows * heads * d * 2
    if backward:
        return 2 * ops, 2 * operands + result
    return ops, operands + result


def causal_conv_op(config, traffic, backward):
    """(operations, bytes) of one delta-attention layer's ``causal_conv_silu``
    on the cell's batch: (B, T, 3 H K) in and out, two-byte operands, taps a
    channel, the bias a constant zero. Forward: the multiply-adds and the SiLU
    (4), 2 taps + 4 operations an element; it reads ``data`` and writes the
    result. Backward: the filter and the SiLU's derivative again, the filter's
    transpose and the taps' gradient, 6 taps + 9 an element; it reads ``data``
    and the output's gradient and writes ``data``'s. The taps are 24 KB."""
    heads, d, k = _kda(config)
    channels = 3 * heads * d
    rows = traffic["batch"] * traffic["sequence"] * channels
    taps = channels * k * 2
    if backward:
        return (6 * k + 9) * rows, 3 * rows * 2 + 2 * taps
    return (2 * k + 4) * rows, 2 * rows * 2 + taps
