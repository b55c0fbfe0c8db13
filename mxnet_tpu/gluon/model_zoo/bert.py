"""BERT model family (GluonNLP-equivalent; the reference ecosystem ships
BERT in the separate gluon-nlp repo built on these same mxnet primitives —
bert_12_768_12 config. SURVEY §7 P8).

TPU-native choices: multi-head attention runs through the fused Pallas
flash-attention op (ops/attention.py; fed from the fused QKV projection in
place, ``flash_attention_qkv``) instead of batch_dot+softmax, the
whole encoder hybridizes into one XLA program, and shapes are static —
padding is handled by an additive attention bias from valid_length.
"""
from __future__ import annotations

import math

from ...base import MXNetError
from ..block import HybridBlock
from .. import nn

__all__ = ["tensor_parallel_rules",
           "BERTEncoder", "BERTModel", "get_bert_model", "bert_12_768_12",
           "bert_6_512_8", "bert_3_64_2"]


class BERTSelfAttention(HybridBlock):
    """Fused-QKV multi-head self-attention over flash_attention_qkv.
    ``causal=True`` turns it into decoder-style masked attention (used
    by the GPT zoo model)."""

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads != 0:
            raise MXNetError("units %d not divisible by num_heads %d"
                             % (units, num_heads))
        self._units = units
        self._num_heads = num_heads
        self._causal = causal
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, flatten=False, in_units=units,
                                prefix="qkv_")
            self.proj = nn.Dense(units, flatten=False, in_units=units,
                                 prefix="proj_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, bias=None):
        D = self._units // self._num_heads
        # the fused projection as it lies, (B, T, 3C), into the kernels and
        # (B, T, C) out of them: no (B, T, H, D) <-> (B, H, T, D) copy where
        # a head is one plain tile (ops/attention.py:flash_attention_qkv)
        out = F.flash_attention_qkv(self.qkv(x), bias,
                                    num_heads=self._num_heads,
                                    causal=self._causal,
                                    sm_scale=1.0 / math.sqrt(D))
        out = self.proj(out)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class BERTPositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, flatten=False, in_units=units,
                                  prefix="ffn1_")
            self.ffn_2 = nn.Dense(units, flatten=False, in_units=hidden_size,
                                  prefix="ffn2_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = F.gelu(self.ffn_1(x))
        out = self.ffn_2(out)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class BERTEncoderCell(HybridBlock):
    """Post-LN transformer layer, BERT-style."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 layer_norm_eps=1e-12, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attention = BERTSelfAttention(units, num_heads, dropout,
                                               prefix="attn_")
            self.ffn = BERTPositionwiseFFN(units, hidden_size, dropout,
                                           prefix="ffn_")
            self.layer_norm_1 = nn.LayerNorm(epsilon=layer_norm_eps,
                                             in_channels=units,
                                             prefix="ln1_")
            self.layer_norm_2 = nn.LayerNorm(epsilon=layer_norm_eps,
                                             in_channels=units,
                                             prefix="ln2_")

    def hybrid_forward(self, F, x, bias=None):
        out = self.layer_norm_1(x + self.attention(x, bias))
        out = self.layer_norm_2(out + self.ffn(out))
        return out


class BERTEncoder(HybridBlock):
    """Stack of encoder cells (GluonNLP BERTEncoder equivalent)."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, layer_norm_eps=1e-12, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_layers = num_layers
        with self.name_scope():
            self.cells = []
            for i in range(num_layers):
                cell = BERTEncoderCell(units, hidden_size, num_heads,
                                       dropout, layer_norm_eps,
                                       prefix="layer%d_" % i)
                self.register_child(cell, "layer%d" % i)
                self.cells.append(cell)

    def hybrid_forward(self, F, x, bias=None):
        for cell in self.cells:
            x = cell(x, bias)
        return x


class BERTModel(HybridBlock):
    """BERT with MLM + NSP heads (GluonNLP BERTModel equivalent).

    forward(inputs, token_types, valid_length=None) →
        (sequence_output (B,T,C), pooled_output (B,C))
    Use ``decode_mlm(sequence_output)`` for vocabulary scores and
    ``classify_nsp(pooled)`` for next-sentence logits.
    """

    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, vocab_size=30522, token_type_vocab_size=2,
                 max_length=512, dropout=0.1, layer_norm_eps=1e-12,
                 use_decoder=True, use_classifier=True, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._max_length = max_length
        self._vocab_size = vocab_size
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.token_type_embed = nn.Embedding(token_type_vocab_size,
                                                 units,
                                                 prefix="token_type_embed_")
            self.position_weight = self.params.get(
                "position_weight", shape=(max_length, units),
                init="normal")
            self.embed_layer_norm = nn.LayerNorm(epsilon=layer_norm_eps,
                                                 in_channels=units,
                                                 prefix="embed_ln_")
            self.embed_dropout = nn.Dropout(dropout) if dropout else None
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout, layer_norm_eps,
                                       prefix="encoder_")
            self.pooler = nn.Dense(units, activation="tanh",
                                   flatten=False, in_units=units,
                                   prefix="pooler_")
            if use_decoder:
                self.mlm_dense = nn.Dense(units, flatten=False,
                                          in_units=units, prefix="mlm_d_")
                self.mlm_ln = nn.LayerNorm(epsilon=layer_norm_eps,
                                           in_channels=units,
                                           prefix="mlm_ln_")
                # decoder ties its weight to word_embed (same (V, units)
                # param), like GluonNLP's BERTModel
                self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                            in_units=units,
                                            prefix="mlm_out_",
                                            params=self.word_embed.params)
            else:
                self.mlm_dense = None
            if use_classifier:
                self.nsp_classifier = nn.Dense(2, flatten=False,
                                               in_units=units,
                                               prefix="nsp_")
            else:
                self.nsp_classifier = None

    def hybrid_forward(self, F, inputs, token_types, valid_length=None,
                       position_weight=None):
        if hasattr(inputs, "shape"):  # eager; Symbol trace skips the check
            T = inputs.shape[1]
            if T > self._max_length:
                raise MXNetError("sequence length %d exceeds max_length %d"
                                 % (T, self._max_length))
        x = self.word_embed(inputs) + self.token_type_embed(token_types)
        # slice the learned position table to seq length without reading
        # .shape (keeps the Symbol trace path working)
        pos = F.slice_like(position_weight, F.transpose(inputs), axes=(0,))
        x = x + F.expand_dims(pos, axis=0)
        x = self.embed_layer_norm(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        bias = None
        if valid_length is not None:
            bias = F.attention_padding_bias(
                valid_length, max_len=self._max_length)
            bias = F.slice_like(
                F.transpose(bias, axes=(3, 1, 2, 0)),
                F.transpose(inputs), axes=(0,))
            bias = F.transpose(bias, axes=(3, 1, 2, 0))
        seq = self.encoder(x, bias)
        pooled = self.pooler(F.squeeze(
            F.slice(seq, begin=(None, 0, None), end=(None, 1, None)),
            axis=1))
        return seq, pooled

    def decode_mlm(self, sequence_output):
        from ...symbol.symbol import Symbol

        if isinstance(sequence_output, Symbol):
            from ... import symbol as F
        else:
            from ... import ndarray as F

        if self.mlm_dense is None:
            raise MXNetError("model built with use_decoder=False")
        h = self.mlm_ln(F.gelu(self.mlm_dense(sequence_output)))
        return self.mlm_decoder(h)

    def classify_nsp(self, pooled):
        if self.nsp_classifier is None:
            raise MXNetError("model built with use_classifier=False")
        return self.nsp_classifier(pooled)


def get_bert_model(num_layers, units, num_heads, hidden_size=None,
                   vocab_size=30522, max_length=512, dropout=0.1, **kwargs):
    if hidden_size is None:
        hidden_size = 4 * units
    return BERTModel(num_layers=num_layers, units=units,
                     hidden_size=hidden_size, num_heads=num_heads,
                     vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **kwargs)


def bert_12_768_12(**kwargs):
    """BERT-base (L=12, H=768, A=12)."""
    return get_bert_model(12, 768, 12, **kwargs)


def bert_6_512_8(**kwargs):
    """Half-depth BERT for medium budgets."""
    return get_bert_model(6, 512, 8, **kwargs)


def bert_3_64_2(**kwargs):
    """Tiny config for tests."""
    kwargs.setdefault("vocab_size", 1000)
    kwargs.setdefault("max_length", 64)
    return get_bert_model(3, 64, 2, **kwargs)


def tensor_parallel_rules():
    """Megatron-style tensor-parallel PartitionSpecs for every BERT size
    (pass to ShardedTrainStep(..., rules=...) with a ("data", "model")
    mesh). Fused QKV and FFN-in are column-parallel (output dim sharded),
    attention proj and FFN-out are row-parallel (input dim sharded) —
    GSPMD then inserts the canonical all-reduce pair per block over the
    "model" axis. Embeddings and LayerNorms stay replicated (the MLM
    decoder ties the word embedding, so sharding it would all-gather
    every step)."""
    from jax.sharding import PartitionSpec as P

    from ... import parallel

    # suffix-anchored so they cover both BERT's ffn_ffn1_* and the GPT
    # zoo model's ffn1_* parameter names (gpt.tensor_parallel_rules
    # delegates here — one rule set to maintain)
    return parallel.sharding_rule(
        (r"attn_qkv_weight$", P("model", None)),
        (r"attn_qkv_bias$", P("model")),
        (r"attn_proj_weight$", P(None, "model")),
        (r"ffn1_weight$", P("model", None)),
        (r"ffn1_bias$", P("model")),
        (r"ffn2_weight$", P(None, "model")),
    )
