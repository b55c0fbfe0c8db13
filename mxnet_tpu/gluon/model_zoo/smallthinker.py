"""SmallThinker's sparse decoder (PowerInfer, SmallThinker-21BA3B-Instruct and
-4BA0.6B; "SmallThinker: A Family of Efficient Large Language Models Natively
Trained for Local Deployment", arXiv:2507.20984, and the family's
``modeling_smallthinker.py``): every layer an expert layer whose router reads
the ATTENTION's input, over grouped-query attention that is, layer by layer,
full and without positions or windowed and rotary.

Pre-norm blocks without biases: ``x = RMSNorm(h)``, ``h + Attention(x)``, then
``h + MoE(RMSNorm(h), router rows x)``; a last RMSNorm and an untied head.

* **attention** — ``model_zoo.keye.GroupedQueryAttention`` without its head
  norms: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``, no norm on q or k; where
  ``rope_layout[l]`` is 1 rotate-half rotary positions over the whole head
  (``rope_theta``), where it is 0 none at all (NoPE); where
  ``sliding_window_layout[l]`` is 1 a query sees itself and the
  ``sliding_window_size - 1`` keys before it (``flash_attention(window=...)``:
  a static argument of both kernels, nothing read for it), where it is 0 the
  whole causal past. The two kinds go by the scopes ``attn_window`` and
  ``attn_full``.
* **experts** — the router's logits are ``x W_r^T`` of the attention's
  normalised input, one residual add before the rows the experts see (the
  publisher places it there so that a device can fetch the experts while it
  attends); softmax over all ``moe_num_primary_experts`` logits, the
  ``moe_num_active_primary_experts`` largest chosen and renormalised
  (``norm_topk_prob``: a softmax over the chosen logits); experts are ReGLU,
  ``W_down (relu(W_gate y) * W_up y)``; no shared expert, no bias
  (``model_zoo.deepseek.DeepseekMoE`` over ``ops/moe.py``).

Built from the config's own keys. ``experts_held=(first, count)`` gives a chip
its share of every expert layer under expert parallelism, as
``DeepseekV3Model`` takes it; a strict share lets no gradient through the
chosen experts' weights, as ``KeyeVL2Model``'s block and for its reason.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock
from .. import nn
from .deepseek import DeepseekMoE, moe_counts, publish_moe_counts
from .keye import GroupedQueryAttention

__all__ = ["SmallThinkerBlock", "SmallThinkerModel", "moe_counts",
           "publish_moe_counts"]


class SmallThinkerBlock(HybridBlock):
    """One pre-norm decoder block: attention (``rope``: rotary or NoPE;
    ``window``: the last keys or all), then the expert layer, routed by the
    attention's input."""

    def __init__(self, cfg, rope, window, experts_held=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        n_routed = cfg["moe_num_primary_experts"]
        whole = experts_held is None or experts_held[1] == n_routed
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="attn_norm_")
            self.attn = GroupedQueryAttention(
                units, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["rope_theta"] if rope else None,
                head_norm=False,
                window=cfg["sliding_window_size"] if window else None,
                prefix="attn_window_" if window else "attn_full_")
            self.ffn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="ffn_norm_")
            self.ffn = DeepseekMoE(
                units, cfg["moe_ffn_hidden_size"], n_routed,
                cfg["moe_num_active_primary_experts"], experts_held=experts_held,
                scoring="softmax", selection_bias=False, router_gradient=whole,
                activation="relu", prefix="moe_")

    def hybrid_forward(self, F, h):
        x = self.attn_norm(h)
        h = h + self.attn(x)
        return h + self.ffn(self.ffn_norm(h), x)


class SmallThinkerModel(HybridBlock):
    """Causal LM: token ids (B, T) -> scores (B, T, vocab_size).

    ``cfg`` holds the published config's keys (``hidden_size``,
    ``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
    ``head_dim``, ``rope_layout``, ``rope_theta``, ``sliding_window_layout``,
    ``sliding_window_size``, ``moe_num_primary_experts``,
    ``moe_num_active_primary_experts``, ``moe_ffn_hidden_size``,
    ``rms_norm_eps``, ``vocab_size``, ...): ``moe_num_primary_experts`` is the
    router's width whatever this chip holds, and the two layouts list the
    layers built, so a chip that holds some of the published layers gives
    each the kind of its published index. ``experts_held`` is this chip's
    share of every expert layer, all of them by default."""

    def __init__(self, cfg, experts_held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        built = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
                 "tie_word_embeddings": False, "rope_scaling": None,
                 "moe_enable_early_router": True,
                 "moe_enable_secondary_experts": False,
                 "moe_num_secondary_experts": 0, "moe_shared_primary_experts": 0,
                 "attention_bias": False, "hidden_act": "relu"}
        for key, want in built.items():
            if cfg.get(key, want) != want:
                raise MXNetError("SmallThinkerModel: %s=%r is not built (only %r)"
                                 % (key, cfg[key], want))
        layers = cfg["num_hidden_layers"]
        for key in ("rope_layout", "sliding_window_layout"):
            if len(cfg[key]) != layers or set(cfg[key]) - {0, 1}:
                raise MXNetError("SmallThinkerModel: %s %r does not say 0 or 1 "
                                 "for each of %d layers" % (key, cfg[key], layers))
        self._cfg = dict(cfg)
        units, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, prefix="embed_")
            self.blocks = []
            for i in range(layers):
                blk = SmallThinkerBlock(
                    cfg, cfg["rope_layout"][i], cfg["sliding_window_layout"][i],
                    experts_held, prefix="layer%d_" % i)
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
