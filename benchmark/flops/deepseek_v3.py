"""Operations of one training sequence of a DeepSeek-V3-style decoder on this
chip's share, from the shapes alone. A multiply-add counts as two operations.
Matrix products only: the four projections of latent attention (``W_q``,
``W_kva``, ``W_kvb``, ``W_o``), the two attention products counted over the
UNMASKED half of the causal square (a position sees (T + 1) / 2 keys on
average), the dense layer's SwiGLU, and for an expert layer the router, the
shared experts and the routed experts HELD at the slots an even routing sends
them: ``num_experts_per_tok * held / published`` slots a token (6 x 16 / 128 =
0.75 in the Kanana cell), not the slots a run happened to route. The head's
product over the sliced vocabulary. Embedding look-ups, norms, rotary
positions, softmax, SiLU, the sort and the gathers are left out.

``attention_kernel`` gives one call of the flash kernels its operations and
the bytes it must move, for their roofline shares: operations over the causal
half too, so a kernel that skips the masked tiles cannot read over 100 %.
"""
from __future__ import annotations


def _arch(config):
    return config, config["published"]["n_routed_experts"]


def forward_flops_per_token(config, traffic):
    c, router_width = _arch(config)
    h, heads, t = c["hidden_size"], c["num_attention_heads"], traffic["sequence"]
    dk, dv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"]
    lora, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    proj = 2 * (h * heads * dk + h * (lora + rope)
                + lora * heads * (c["qk_nope_head_dim"] + dv) + heads * dv * h)
    attn = 2 * heads * (dk + dv) * (t + 1) / 2.0
    dense = 2 * 3 * h * c["intermediate_size"]
    width = c["moe_intermediate_size"]
    held = c["experts_held"][1]
    slots = c["num_experts_per_tok"] * held / float(router_width)
    moe = 2 * h * router_width + 2 * 3 * h * width * (c["n_shared_experts"] + slots)
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    return (c["num_hidden_layers"] * (proj + attn) + n_dense * dense + n_moe * moe
            + 2 * h * c["vocab_size"])


def train_flops_per_sample(config, traffic):
    return 3 * forward_flops_per_token(config, traffic) * traffic["sequence"]


def attention_kernel(config, traffic, backward):
    """(operations, bytes) of one call of ``flash_attention_fwd`` or
    ``flash_attention_bwd`` on the cell's batch: B x heads causal squares of T
    with keys ``dk`` and values ``dv`` wide, two-byte operands. Forward: the
    score and value products; it reads q, k, v and writes the output and the
    row statistics (float32, written 128 lanes wide). Backward: five products
    (scores, dv, dp, dk, dq); it reads q, k, v, dO and the two rows of
    statistics and writes dq, dk, dv."""
    c = config
    bh = traffic["batch"] * c["num_attention_heads"]
    t = traffic["sequence"]
    dk, dv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"]
    half = t * (t + 1) / 2.0
    if backward:
        ops = 2 * bh * half * (3 * dk + 2 * dv)
        nbytes = bh * t * 2 * (2 * dk + dv + dv) + bh * t * 2 * (2 * dk + dv) + bh * 2 * t * 4
    else:
        ops = 2 * bh * half * (dk + dv)
        nbytes = bh * t * 2 * (2 * dk + dv + dv) + bh * t * 128 * 4
    return ops, nbytes
