"""Operations of one BERT sequence with the MLM head on every position, from
the shapes alone. A multiply-add counts as two operations. Matrix products
only: the layers' four projections and two feed-forward products, the two
attention products (scores and values, each 2 T H a token a layer), the MLM
dense and the vocabulary product. Embedding look-ups, layer norms, softmax,
GELU and the unused pooler are left out.
"""
from __future__ import annotations


def forward_flops_per_token(config, traffic):
    m = config["published"]
    h, i, v, t = m["hidden_size"], m["intermediate_size"], m["vocab_size"], traffic["sequence"]
    layer = 2 * (4 * h * h + 2 * h * i) + 2 * 2 * t * h
    return m["num_hidden_layers"] * layer + 2 * h * h + 2 * h * v


def train_flops_per_sample(config, traffic):
    return 3 * forward_flops_per_token(config, traffic) * traffic["sequence"]
