"""Operations of one training sequence of Granite 4.0-H's hybrid decoder on
this chip's stage, from the shapes alone. A multiply-add counts as two
operations. Matrix products only: a Mamba-2 layer's two projections (``W_in``
to ``2 H P + 2 G N + H`` columns, ``W_out``) and the four products of its scan,
a chunk of Q tokens at a time (the scores ``C B^T`` a group and their masked
product with ``dt x`` a head, both over the LOWER TRIANGLE of a chunk's pairs,
Q (Q + 1) / 2 of them, so that a kernel which skips the masked half cannot read
over 100 %; each chunk's closing state; each token's read of its chunk's
opening state); an attention layer's three (``W_q``, ``W_kv``, ``W_o``) and its
two attention products over the UNMASKED half of the causal square (a position
sees (T + 1) / 2 keys on average); every layer's SwiGLU; the tied head's
product over the sliced vocabulary, once; all of it three times for training.
No recomputed operation counts, whatever the configuration's
``assumed.recomputation`` makes the step run again. Embedding look-ups, norms,
the multipliers, softmax, SiLU, softplus, the decays and their masks, the
filter's taps (2 K + 5 operations an element of 4352 channels: 0.0002 of the
step) and the carry of the states from chunk to chunk are left out.

``attention_kernel`` gives one call of the flash kernels its operations and
the bytes it must move, ``ssd_op`` one layer's ``ssd_scan`` and
``causal_conv_op`` one layer's ``causal_conv_silu`` theirs, for their roofline
shares.
"""
from __future__ import annotations


def _mamba(config):
    c = config
    heads, p, g, n = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"],
                      c["mamba_d_state"])
    return heads, p, g, n, heads * p, heads * p + 2 * g * n


def _chunk(config, traffic):
    return min(config["mamba_chunk_size"], traffic["sequence"])


def scan_flops_per_token(config, traffic):
    """The scan's four products, a token: the pairs of its chunk's lower
    triangle are (Q + 1) / 2 a token."""
    heads, p, g, n, _, _ = _mamba(config)
    pairs = (_chunk(config, traffic) + 1) / 2.0
    return (2 * g * n * pairs          # scores, a group
            + 2 * heads * p * pairs    # the masked scores times dt x, a head
            + 2 * heads * p * n        # the chunk's closing state
            + 2 * heads * p * n)       # the read of the opening state


def forward_flops_per_token(config, traffic):
    c, t = config, traffic["sequence"]
    h, heads, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    d = h // heads
    mh, _, _, _, inner, filtered = _mamba(c)
    mamba = 2 * (h * (inner + filtered + mh) + inner * h) + scan_flops_per_token(c, traffic)
    attn = (2 * (h * heads * d + h * 2 * kv * d + heads * d * h)
            + 2 * heads * 2 * d * (t + 1) / 2.0)
    mlp = 2 * 3 * h * c["shared_intermediate_size"]
    mixers = sum(mamba if kind == "mamba" else attn for kind in c["layer_types"])
    return mixers + c["num_hidden_layers"] * mlp + 2 * h * c["vocab_size"]


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
    (dk and dv once a K/V head)."""
    c = config
    b, t = traffic["batch"], traffic["sequence"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // heads
    pairs = b * heads * t * (t + 1) / 2.0
    q_bytes, kv_bytes = b * heads * t * d * 2, b * kv * t * d * 2
    if backward:
        return 2 * pairs * 5 * d, 3 * q_bytes + 4 * kv_bytes + b * heads * 2 * t * 4
    return 2 * pairs * 2 * d, 2 * q_bytes + 2 * kv_bytes + b * heads * t * 4


def ssd_op(config, traffic, backward):
    """(operations, bytes) of one Mamba layer's ``ssd_scan`` on the cell's
    batch, two-byte operands. Forward: the four products; it reads ``x`` (B,
    T, H, P), ``dt`` (B, T, H), ``B`` and ``C`` (B, T, G, N) and the three
    vectors of H, and writes ``y``. Backward: the two transposes of each of
    the four products (the scores and masks it builds again are not counted:
    a floor); it reads the same and the output's gradient and writes the
    gradient of each. The chunks' opening states are the op's own to keep or
    to compute again, and are not counted."""
    heads, p, g, n, _, _ = _mamba(config)
    rows = traffic["batch"] * traffic["sequence"]
    ops = scan_flops_per_token(config, traffic) * rows
    operands = rows * (heads * p + heads + 2 * g * n) * 2 + 3 * heads * 2
    result = rows * heads * p * 2
    if backward:
        return 2 * ops, 2 * operands + result
    return ops, operands + result


def causal_conv_op(config, traffic, backward):
    """(operations, bytes) of one Mamba layer's ``causal_conv_silu`` on the
    cell's batch: (B, T, H P + 2 G N) in and out, two-byte operands, K taps a
    channel. Forward: K multiply-adds, the bias and the SiLU (4), 2 K + 5
    operations an element; it reads ``data`` and writes the result. Backward:
    the filter and the SiLU's derivative again, the filter's transpose, the
    taps' and the bias's gradient, 6 K + 10 an element; it reads ``data`` and
    the output's gradient and writes ``data``'s. Taps and bias are 44 KB."""
    _, _, _, _, _, channels = _mamba(config)
    k = config["mamba_d_conv"]
    rows = traffic["batch"] * traffic["sequence"] * channels
    taps = channels * (k + 1) * 2
    if backward:
        return (6 * k + 10) * rows, 3 * rows * 2 + 2 * taps
    return (2 * k + 5) * rows, 2 * rows * 2 + taps
