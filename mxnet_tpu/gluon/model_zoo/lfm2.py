"""LFM2's sparse decoder (``model_type: lfm2_moe``; Liquid AI, LFM2-8B-A1B and
LFM2-24B-A2B; the LFM2 technical report and transformers'
``modeling_lfm2_moe.py``): a stack whose token mixer is, layer by layer, a
gated short convolution or grouped-query attention (``layer_types``), over a
dense SwiGLU in the leading ``num_dense_layers`` and a sigmoid-routed expert
layer in the others.

Pre-norm blocks without biases: ``h + mixer(RMSNorm(h))``, then
``h + ffn(RMSNorm(h))``; a last RMSNorm (the publisher's ``embedding_norm``)
and a head whose weight is the embedding's.

* **conv** — ``[Bg, Cg, X] = in_proj(x)`` (3 x hidden); a causal depthwise
  filter of ``conv_L_cache`` taps over ``Bg * X``, gated by ``Cg`` (op
  ``gated_short_conv``: no activation anywhere); ``out_proj``. Linear in the
  sequence: its state in a decode step is the last ``conv_L_cache - 1``
  tokens of ``Bg * X``.
* **full_attention** — ``model_zoo.keye.GroupedQueryAttention`` with no
  selection: ``num_attention_heads`` query heads on ``num_key_value_heads``
  K/V heads of ``hidden_size / num_attention_heads``, an RMSNorm over each
  head of q and of k, rotate-half rotary over the whole head, causal.
* **experts** — ``sigmoid`` scores over all ``num_experts``, the
  ``num_experts_per_tok`` largest of ``score + expert_bias`` chosen
  (``use_expert_bias``; the bias is a buffer its own rule moves, never a
  gradient), weighted by their own scores over their sum plus 1e-6
  (``norm_topk_prob``) times ``routed_scaling_factor``; no shared expert
  (``model_zoo.deepseek.DeepseekMoE`` over ``ops/moe.py``).

Built from the config's own keys. ``layer_types`` is the list of the layers
built, one kind each, so a chip that holds some of the published layers gives
each the kind of its published index. ``experts_held=(first, count)`` gives a
chip its share of every expert layer under expert parallelism, as
``DeepseekV3Model`` takes it; a strict share lets no gradient through the
chosen experts' weights, as ``KeyeVL2Model``'s block and for its reason.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock
from .. import nn
from .deepseek import DeepseekMoE, moe_counts, publish_moe_counts
from .keye import GroupedQueryAttention

__all__ = ["ShortConv", "Lfm2MoeBlock", "Lfm2MoeModel", "moe_counts",
           "publish_moe_counts"]

_SUM_EPSILON = 1e-6  # Lfm2MoeSparseMoeBlock: weights / (their sum + 1e-6)


class ShortConv(HybridBlock):
    """(B, T, units) -> (B, T, units): the gated short convolution between
    its two projections."""

    def __init__(self, units, taps, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        dense = dict(flatten=False, use_bias=False)
        with self.name_scope():
            self.in_proj = nn.Dense(3 * units, in_units=units, prefix="in_proj_",
                                    **dense)
            # one filter a channel: Conv1d(units, units, taps, groups=units)'s
            # (units, 1, taps) without the axis of one
            self.conv_weight = self.params.get("conv_weight", shape=(units, taps))
            self.out_proj = nn.Dense(units, in_units=units, prefix="out_proj_",
                                     **dense)

    def hybrid_forward(self, F, x, conv_weight=None):
        return self.out_proj(F.gated_short_conv(self.in_proj(x), conv_weight))


class Lfm2MoeBlock(HybridBlock):
    """One pre-norm decoder block: the layer's mixer (``kind`` is ``"conv"``
    or ``"full_attention"``), then a dense SwiGLU or the expert layer."""

    def __init__(self, cfg, kind, dense, experts_held=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps = cfg["hidden_size"], cfg["norm_eps"]
        heads = cfg["num_attention_heads"]
        whole = experts_held is None or experts_held[1] == cfg["num_experts"]
        with self.name_scope():
            self.operator_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                            prefix="operator_norm_")
            if kind == "conv":
                self.mixer = ShortConv(units, cfg["conv_L_cache"],
                                       prefix="short_conv_")
            elif kind == "full_attention":
                self.mixer = GroupedQueryAttention(
                    units, heads, cfg["num_key_value_heads"], units // heads,
                    cfg["rope_parameters"]["rope_theta"], eps, prefix="gqa_")
            else:
                raise MXNetError("Lfm2MoeBlock: layer type %r is not built (conv "
                                 "or full_attention)" % (kind,))
            self.ffn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="ffn_norm_")
            if dense:
                self.ffn = nn.SwiGLU(units, cfg["intermediate_size"], prefix="ffn_")
            else:
                self.ffn = DeepseekMoE(
                    units, cfg["moe_intermediate_size"], cfg["num_experts"],
                    cfg["num_experts_per_tok"], 0,
                    cfg.get("routed_scaling_factor", 1.0), experts_held,
                    scoring="sigmoid",
                    selection_bias=cfg.get("use_expert_bias", True),
                    router_gradient=whole, sum_epsilon=_SUM_EPSILON,
                    prefix="moe_")

    def hybrid_forward(self, F, h):
        h = h + self.mixer(self.operator_norm(h))
        return h + self.ffn(self.ffn_norm(h))


class Lfm2MoeModel(HybridBlock):
    """Causal LM: token ids (B, T) -> scores (B, T, vocab_size).

    ``cfg`` holds the published config's keys (``hidden_size``,
    ``num_hidden_layers``, ``layer_types``, ``num_dense_layers``,
    ``conv_L_cache``, ``num_attention_heads``, ``num_key_value_heads``,
    ``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
    ``num_experts_per_tok``, ``norm_eps``, ``rope_parameters``,
    ``vocab_size``, ...): ``num_experts`` is the router's width whatever this
    chip holds. ``experts_held`` is this chip's share of every expert layer,
    all of them by default."""

    def __init__(self, cfg, experts_held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        built = {"conv_bias": False, "norm_topk_prob": True,
                 "tie_embedding": True, "tie_word_embeddings": True}
        for key, want in built.items():
            if cfg.get(key, want) != want:
                raise MXNetError("Lfm2MoeModel: %s=%r is not built (only %r)"
                                 % (key, cfg[key], want))
        if cfg["rope_parameters"].get("rope_type", "default") != "default":
            raise MXNetError("Lfm2MoeModel: only the default rope is built, not "
                             "%r" % (cfg["rope_parameters"],))
        kinds = list(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"]:
            raise MXNetError("Lfm2MoeModel: %d layer_types for %d layers"
                             % (len(kinds), cfg["num_hidden_layers"]))
        if cfg["hidden_size"] % cfg["num_attention_heads"]:
            raise MXNetError("Lfm2MoeModel: hidden_size %d is not whole heads of "
                             "%d" % (cfg["hidden_size"], cfg["num_attention_heads"]))
        self._cfg = dict(cfg)
        units, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, prefix="embed_")
            self.blocks = []
            for i, kind in enumerate(kinds):
                blk = Lfm2MoeBlock(cfg, kind, dense=i < cfg["num_dense_layers"],
                                   experts_held=experts_held,
                                   prefix="layer%d_" % i)
                self.register_child(blk, "layer%d" % i)
                self.blocks.append(blk)
            self.norm = nn.RMSNorm(epsilon=cfg["norm_eps"], in_channels=units,
                                   prefix="norm_")
            # the head's weight is the embedding's, one (vocab, units) parameter
            self.head = nn.Dense(vocab, flatten=False, use_bias=False,
                                 in_units=units, prefix="head_",
                                 params=self.embed.params)

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        for blk in self.blocks:
            h = blk(h)
        return self.head(self.norm(h))

    def moe_layers(self):
        return [b.ffn for b in self.blocks if isinstance(b.ffn, DeepseekMoE)]
