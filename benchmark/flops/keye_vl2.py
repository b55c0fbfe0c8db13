"""Operations of one training sequence of Keye-VL-2.0's decoder on this
chip's share, from the shapes alone. A multiply-add counts as two operations.
Matrix products only: the attention's three projections (``W_q``, ``W_kv``,
``W_o``), its two products counted over the SELECTED pairs (query ``t`` reads
``min(t + 1, topk)`` keys: the pairs the indexer masks out are no work of the
model's, whatever a kernel spends on them), the router and the routed experts
HELD at the slots an even routing sends them (``num_experts_per_tok * held /
published`` a token: 8 x 16 / 128 = 1), the head's product over the sliced
vocabulary; all of these three times for training. The indexer has no
backward pass, so its projections and its scores (16 heads of 64 over the
earlier keys of the rows that have more than ``topk`` candidates: the others
need no score) count once. Embedding look-ups, norms, rotary positions,
softmax, SiLU, the selection's comparisons, the sort and the gathers are left
out.

``attention_kernel`` and ``indexer_kernel`` give one call of the flash kernels
and of ``indexer_select`` its operations and the bytes it must move, for their
roofline shares: the attention's operations over the selected pairs too, so a
share is of the model's work and cannot pass 100 % by counting masked pairs.
"""
from __future__ import annotations


def selected_pairs(t, topk):
    """Pairs a head attends in one causal sequence of ``t`` under ``topk``:
    ``sum over rows of min(row + 1, topk)``."""
    full = min(t, topk)
    return full * (full + 1) // 2 + (t - full) * topk


def searched_pairs(t, topk):
    """Pairs the indexer must score: the earlier keys of the rows that have
    more than ``topk`` candidates."""
    return t * (t + 1) // 2 - min(t, topk) * (min(t, topk) + 1) // 2


def train_flops_per_sample(config, traffic):
    c, sa = config, config["sa_config"]
    t = traffic["sequence"]
    h, heads, kv, d = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"])
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers, router_width = c["num_hidden_layers"], c["published"]["num_experts"]
    proj = 2 * (h * heads * d + h * 2 * kv * d + heads * d * h)
    slots = c["num_experts_per_tok"] * c["experts_held"][1] / float(router_width)
    moe = 2 * h * router_width + 2 * 3 * h * c["moe_intermediate_size"] * slots
    trained = t * (layers * (proj + moe) + 2 * h * c["vocab_size"])
    trained += layers * 2 * heads * 2 * d * selected_pairs(t, sa["topk"])
    indexer = layers * (t * 2 * h * (ih * idim + idim + ih)
                        + 2 * ih * idim * searched_pairs(t, sa["topk"]))
    return 3 * trained + indexer


def attention_kernel(config, traffic, backward):
    """(operations, bytes) of one call of ``flash_attention_fwd`` or
    ``flash_attention_bwd`` on the cell's batch: B x 32 query heads on 4 K/V
    heads of 128, two-byte operands, the selected pairs of a causal square of
    T. Forward: the score and value products; it reads q, k, v (K/V once a
    K/V head) and the mask (a byte a pair of the square, once a sequence) and
    writes the output and the row statistic (float32, 4 bytes a query).
    Backward: five products (scores, dv, dp, dk, dq); it reads q, k, v, dO,
    the two rows of statistics and the mask and writes dq, dk, dv."""
    c = config
    b, t = traffic["batch"], traffic["sequence"]
    heads, kv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pairs = b * heads * selected_pairs(t, c["sa_config"]["topk"])
    q_bytes, kv_bytes, mask = b * heads * t * d * 2, b * kv * t * d * 2, b * t * t
    if backward:
        return (2 * pairs * 5 * d,
                3 * q_bytes + 4 * kv_bytes + mask + b * heads * 2 * t * 4 + q_bytes)
    return 2 * pairs * 2 * d, 2 * q_bytes + 2 * kv_bytes + mask + b * heads * t * 4


def indexer_kernel(config, traffic):
    """(operations, bytes) of one call of ``indexer_select``: the score
    products of the rows searched (the bisection's comparisons are no
    operations of the model's); it reads the indexer's queries, key and head
    weights (two bytes) and writes the mask, a byte a pair of the square."""
    sa = config["sa_config"]
    b, t = traffic["batch"], traffic["sequence"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    ops = 2 * b * ih * idim * searched_pairs(t, sa["topk"])
    return ops, b * t * (ih * idim + idim + ih) * 2 + b * t * t
