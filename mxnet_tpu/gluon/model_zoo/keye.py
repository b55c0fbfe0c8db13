"""Keye-VL-2.0's language decoder (``model_type: KeyeVL2``; Kwai-Keye,
Keye-VL-2.0-30B-A3B): a Qwen3-MoE-style sparse decoder whose attention reads,
for every query, only the keys a lightning indexer chose (DeepSeek-V3.2's
sparse attention: the config's ``sa_config``).

Pre-norm blocks without biases, every layer an expert layer:
``h + GQA(x, S)``, ``x = RMSNorm(h)``, ``S = Indexer(x)``, then
``h + MoE(RMSNorm(h))``; a last RMSNorm and an untied head.

* **GQA** — ``num_attention_heads`` query heads on ``num_key_value_heads``
  K/V heads of ``head_dim``; an RMSNorm over each head of q and of k, then
  rotary positions (rotate-half, the whole head: what ``mrope_section`` gives
  a text token, whose three position ids are equal); query head ``i`` reads
  K/V head ``i // group``; the softmax runs over the selected keys alone
  (``flash_attention(mask=S, causal=True)``).
* **Indexer** — ``indexer_num_heads`` light heads of ``indexer_head_dim``
  over ONE key head: ``q = rope(x W_q)``, ``k = rope(LayerNorm(x W_k))``,
  ``w = x W_w / sqrt(heads * dim)``; the ``topk`` keys of largest
  ``sum_j w_j ReLU(q_j . k)`` among the earlier ones are ``S`` (op
  ``lightning_indexer``: exact, no gradient). The language-model loss gives
  the indexer's weights no gradient; their own alignment loss is not built.
* **MoE** — softmax over all ``num_experts`` logits, ``num_experts_per_tok``
  chosen, their weights renormalised (``norm_topk_prob``), no bias, no shared
  expert (``ops/moe.py``, as the DeepSeek-V3 family's layer but for the
  router's scoring).

Built from the config's own keys. ``experts_held=(first, count)`` gives a chip
its share of every expert layer under expert parallelism, as
``DeepseekV3Model`` takes it. A strict share lets no gradient through the
chosen experts' weights (``moe_ffn(router_gradient=False)``): without the
experts' exchange a chip has only its own experts' part of that gradient,
the whole being a sum over the chips that share the layer, and applied alone
the part pulls every token towards the experts held (measured: within 72
steps the tokens sent 4-6 of their 8 slots to the 16 experts held of 128).
With every expert held the router trains like any other weight. Beside the expert layers' counts, every layer's
indexer counts in aux state the pairs it selected and the rows it searched
(``selection_counts``): read them once a window, never a step. The vision
tower is not built: every token is text.
"""
from __future__ import annotations

import math

import numpy as np

from ...base import MXNetError
from ..block import HybridBlock
from .. import nn
from .deepseek import DeepseekMoE, moe_counts, publish_moe_counts

__all__ = ["LightningIndexer", "GroupedQueryAttention", "KeyeVL2Block",
           "KeyeVL2Model", "selection_counts", "publish_selection_counts",
           "moe_counts", "publish_moe_counts"]


class LightningIndexer(HybridBlock):
    """(B, T, units) -> the int8 mask (B, T, T) of the keys each query
    selected."""

    def __init__(self, units, num_heads, head_dim, topk, rope_theta=10000.0,
                 epsilon=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._dim, self._topk = num_heads, head_dim, int(topk)
        self._theta = float(rope_theta)
        dense = dict(flatten=False, use_bias=False, in_units=units)
        with self.name_scope():
            self.q_proj = nn.Dense(num_heads * head_dim, prefix="q_proj_", **dense)
            self.k_proj = nn.Dense(head_dim, prefix="k_proj_", **dense)
            self.k_norm = nn.LayerNorm(epsilon=epsilon, in_channels=head_dim,
                                       prefix="k_norm_")
            self.weights = nn.Dense(num_heads, prefix="weights_", **dense)
            g = self.params.get
            self.selected_pairs = g("selected_pairs", shape=(1,), dtype="int64",
                                    init="zeros", grad_req="null")
            self.rows_searched = g("rows_searched", shape=(1,), dtype="int64",
                                   init="zeros", grad_req="null")

    def cast(self, dtype):
        """The counts stay int64 under a 16-bit cast."""
        super().cast(dtype)
        self.selected_pairs.cast("int64")
        self.rows_searched.cast("int64")

    def hybrid_forward(self, F, x, selected_pairs=None, rows_searched=None):
        turn = dict(theta=self._theta, seq_axis=1, interleaved=False)
        q = F.rotary_embedding(
            F.reshape(self.q_proj(x), shape=(0, 0, self._heads, self._dim)), **turn)
        k = F.rotary_embedding(self.k_norm(self.k_proj(x)), **turn)
        w = self.weights(x) * (1.0 / math.sqrt(self._heads * self._dim))
        ret = F.lightning_indexer(q, k, w, topk=self._topk)
        if not isinstance(ret, tuple):
            return ret[0]  # symbolic trace: the counts are hidden outputs
        mask, selected, searched = ret
        # the BatchNorm running-statistics protocol, as the expert layers'
        selected_pairs._set_data(selected_pairs.data + selected.data.reshape(1))
        rows_searched._set_data(rows_searched.data + searched.data.reshape(1))
        return mask


class _HeadNorm(HybridBlock):
    """RMSNorm over each head of q and of k, one weight vector each."""

    def __init__(self, head_dim, epsilon, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = epsilon
        with self.name_scope():
            self.q_gamma = self.params.get("q_gamma", shape=(head_dim,), init="ones")
            self.k_gamma = self.params.get("k_gamma", shape=(head_dim,), init="ones")

    def hybrid_forward(self, F, q, k, q_gamma=None, k_gamma=None):
        return (F.RMSNorm(q, q_gamma, axis=-1, eps=self._eps),
                F.RMSNorm(k, k_gamma, axis=-1, eps=self._eps))


class GroupedQueryAttention(HybridBlock):
    """Causal attention of ``num_heads`` query heads on ``num_kv_heads`` K/V
    heads, over the keys ``mask`` selects (all the earlier ones without).
    ``rope_theta=None`` is attention without positions, ``head_norm=False``
    without the RMSNorm over each head of q and k, ``window`` a static
    window: a query sees itself and the ``window - 1`` keys before it,
    ``sm_scale`` what the scores are multiplied by (``head_dim ** -0.5``
    where it is None), ``output_gate=True`` a gate on every element of every
    head before the output projection, ``o_proj(attn * sigmoid(gate_proj(x)))``
    (the G1 form of Qiu et al., arXiv:2505.06708)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rope_theta=10000.0, rms_norm_eps=1e-6, head_norm=True,
                 window=None, sm_scale=None, output_gate=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise MXNetError("GroupedQueryAttention: %d query heads on %d K/V "
                             "heads" % (num_heads, num_kv_heads))
        self._heads, self._kv, self._dim = num_heads, num_kv_heads, head_dim
        self._theta = None if rope_theta is None else float(rope_theta)
        self._window = window
        self._scale = 1.0 / math.sqrt(head_dim) if sm_scale is None else float(sm_scale)
        dense = dict(flatten=False, use_bias=False)
        with self.name_scope():
            self.q_proj = nn.Dense(num_heads * head_dim, in_units=units,
                                   prefix="q_proj_", **dense)
            # keys then values, one matmul
            self.kv_proj = nn.Dense(2 * num_kv_heads * head_dim, in_units=units,
                                    prefix="kv_proj_", **dense)
            self.qk_norm = None
            if head_norm:
                self.qk_norm = _HeadNorm(head_dim, rms_norm_eps, prefix="qk_norm_")
            self.gate_proj = None
            if output_gate:
                self.gate_proj = nn.Dense(num_heads * head_dim, in_units=units,
                                          prefix="gate_proj_", **dense)
            self.o_proj = nn.Dense(units, in_units=num_heads * head_dim,
                                   prefix="o_proj_", **dense)

    def hybrid_forward(self, F, x, mask=None):
        H, G, D = self._heads, self._kv, self._dim
        q = F.reshape(self.q_proj(x), shape=(0, 0, H, D))
        kv = F.reshape(self.kv_proj(x), shape=(0, 0, 2 * G, D))
        k = F.slice_axis(kv, axis=2, begin=0, end=G)
        v = F.slice_axis(kv, axis=2, begin=G, end=None)
        if self.qk_norm is not None:
            q, k = self.qk_norm(q, k)
        if self._theta is not None:
            turn = dict(theta=self._theta, seq_axis=1, interleaved=False)
            q, k = F.rotary_embedding(q, **turn), F.rotary_embedding(k, **turn)
        out = F.flash_attention(
            F.transpose(q, axes=(0, 2, 1, 3)), F.transpose(k, axes=(0, 2, 1, 3)),
            F.transpose(v, axes=(0, 2, 1, 3)), None, mask, causal=True,
            sm_scale=self._scale, window=self._window)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -1))
        if self.gate_proj is not None:
            out = out * F.sigmoid(self.gate_proj(x))
        return self.o_proj(out)


class KeyeVL2Block(HybridBlock):
    """One pre-norm decoder block: the indexer's selection, grouped-query
    attention over it, then the expert layer."""

    def __init__(self, cfg, experts_held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps, sa = cfg["hidden_size"], cfg["rms_norm_eps"], cfg["sa_config"]
        whole = experts_held is None or experts_held[1] == cfg["num_experts"]
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="attn_norm_")
            self.indexer = LightningIndexer(
                units, sa["indexer_num_heads"], sa["indexer_head_dim"],
                sa["topk"], cfg["rope_theta"], eps, prefix="indexer_")
            self.gqa = GroupedQueryAttention(
                units, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["rope_theta"], eps, prefix="gqa_")
            self.ffn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="ffn_norm_")
            self.ffn = DeepseekMoE(
                units, cfg["moe_intermediate_size"], cfg["num_experts"],
                cfg["num_experts_per_tok"], experts_held=experts_held,
                scoring="softmax", selection_bias=False, router_gradient=whole,
                prefix="moe_")

    def hybrid_forward(self, F, h):
        x = self.attn_norm(h)
        h = h + self.gqa(x, self.indexer(x))
        return h + self.ffn(self.ffn_norm(h))


class KeyeVL2Model(HybridBlock):
    """Causal LM: token ids (B, T) -> scores (B, T, vocab_size).

    ``cfg`` holds the published config's keys (``hidden_size``,
    ``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
    ``head_dim``, ``num_experts``, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``sa_config``, ``vocab_size``, ...):
    ``num_experts`` is the router's width whatever this chip holds.
    ``experts_held`` is this chip's share of every expert layer, all of them
    by default."""

    def __init__(self, cfg, experts_held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        built = {"decoder_sparse_step": 1, "mlp_only_layers": [],
                 "norm_topk_prob": True, "attention_bias": False,
                 "use_sliding_window": False, "tie_word_embeddings": False,
                 "hidden_act": "silu"}
        for key, want in built.items():
            if cfg.get(key, want) != want:
                raise MXNetError("KeyeVL2Model: %s=%r is not built (only %r)"
                                 % (key, cfg[key], want))
        if (cfg.get("rope_scaling") or {}).get("rope_type", "default") != "default":
            raise MXNetError("KeyeVL2Model: only the default rope is built (a text "
                             "token's mrope), not %r" % (cfg["rope_scaling"],))
        if cfg["sa_config"].get("indexer_num_kv_heads", 1) != 1:
            raise MXNetError("KeyeVL2Model: the indexer has one key head")
        self._cfg = dict(cfg)
        units, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, prefix="embed_")
            self.blocks = []
            for i in range(cfg["num_hidden_layers"]):
                blk = KeyeVL2Block(cfg, experts_held, prefix="layer%d_" % i)
                self.register_child(blk, "layer%d" % i)
                self.blocks.append(blk)
            self.norm = nn.RMSNorm(epsilon=cfg["rms_norm_eps"], in_channels=units,
                                   prefix="norm_")
            self.head = nn.Dense(vocab, flatten=False, use_bias=False,
                                 in_units=units, prefix="head_")

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        for blk in self.blocks:
            h = blk(h)
        return self.head(self.norm(h))

    def moe_layers(self):
        return [b.ffn for b in self.blocks]


def selection_counts(model):
    """One read of the counts every layer's indexer keeps on the device:
    ``{"selected_pairs": [pairs per layer], "rows_searched": [rows per
    layer]}``, cumulative since the parameters were made. A host sync: call it
    once a window, never a step."""
    def read(p):
        return int(np.asarray(p.data().data)[0])  # sync-ok: windowed selection accounting read

    idx = [b.indexer for b in model.blocks]
    return {"selected_pairs": [read(i.selected_pairs) for i in idx],
            "rows_searched": [read(i.rows_searched) for i in idx]}


def publish_selection_counts(model):
    """``selection_counts`` into telemetry (``mxt_selected_pairs{layer}`` and
    ``mxt_rows_searched{layer}`` gauges); returns the counts."""
    from ... import telemetry

    counts = selection_counts(model)
    telemetry.record_selection_counts(**counts)
    return counts
