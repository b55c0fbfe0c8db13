"""Solar Open 2's hybrid sparse decoder (``model_type: solar_open2``; Upstage,
Solar-Open2-250B): a stack whose token mixer is, layer by layer, Kimi's delta
attention (Kimi Team, "Kimi Linear", arXiv:2510.26692, section 3 and its
``KimiDeltaAttention``) or gated grouped-query attention without positions
(``gqa_layers`` names the latter), every layer over a sigmoid-routed expert
layer with one shared expert (the family's own ``modeling_solar_open.py``,
which is GLM-4.5's).

Pre-norm blocks, RMSNorm with a weight, no bias but the output gate's:
``h + mixer(RMSNorm(h))``, then ``h + moe(RMSNorm(h))``; a last RMSNorm and an
untied head.

* **kda** — ``[q, k, v] = silu(filter(qkv_proj(u)))``, a causal depthwise
  filter of ``short_conv_kernel_size`` taps without a bias (op
  ``causal_conv_silu`` under a constant zero bias); H heads of ``head_dim``;
  the log-decays a channel ``g = -exp(A_log) * softplus(f_b(f_a(u)) +
  dt_bias)`` (op ``kda_log_decay``: ``A_log`` a head, ``dt_bias`` a channel,
  the projection a low-rank pair of rank ``head_dim``:
  ``kda_use_full_proj: false``); ``beta = 2 * sigmoid(b_proj(u))`` a head
  (``kda_allow_neg_eigval``: without it the factor is 1); the gated delta rule
  ``o = gated_delta_rule(q, k, v, g, beta)`` (op ``gated_delta_rule``, which
  takes q and k to unit length and q times ``head_dim ** -0.5`` itself);
  ``RMSNorm(o) * w * sigmoid(g_b(g_a(u)) + bias)`` a head, the norm first
  (``RMSNormSigmoidGate``); ``o_proj``. Linear in the sequence: its
  state in a decode step is the (K, V) matrix a head and the filter's last
  taps - 1 tokens.
* **gqa** — ``model_zoo.keye.GroupedQueryAttention`` with no positions
  (``use_rope: false``), no per-head norm, scores times ``head_dim ** -0.5``,
  causal, and with ``use_gqa_gate`` an output gate on every element of every
  head.
* **experts** — ``sigmoid`` scores over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of ``score + bias`` chosen (a buffer, never
  a gradient), weighted by their own scores over their sum plus 1e-20
  (``norm_topk_prob``) times ``routed_scaling_factor``, plus
  ``n_shared_experts`` shared experts on every token
  (``model_zoo.deepseek.DeepseekMoE`` over ``ops/moe.py``).

Built from the config's own keys: ``num_attention_heads``,
``num_key_value_heads`` and ``linear_attn_config.num_heads`` are the heads
BUILT, so a chip that holds a share of the heads under tensor parallelism is a
smaller count and no other code (its partial ``o_proj`` result is summed over
the chips that share the layer, which one chip does not do).
``n_routed_experts`` is the router's width whatever this chip holds, and
``experts_held=(first, count)`` its share of every expert layer, as
``DeepseekV3Model`` takes it; a strict share lets no gradient through the
chosen experts' weights, as ``KeyeVL2Model``'s block and for its reason.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock
from .. import nn
from .deepseek import DeepseekMoE, moe_counts, publish_moe_counts
from .keye import GroupedQueryAttention

__all__ = ["KimiDeltaAttention", "SolarOpen2Block", "SolarOpen2Model", "moe_counts",
           "publish_moe_counts"]

CHUNK = 64  # the op's choice, no part of the model: the config has no key for it


class KimiDeltaAttention(HybridBlock):
    """(B, T, units) -> (B, T, units): the filter, the gated delta rule and
    the head's norm-then-gate between the projections."""

    def __init__(self, units, heads, head_dim, taps, eps, neg_eigval=True,
                 chunk=CHUNK, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._dim, self._chunk = heads, head_dim, int(chunk)
        self._beta = 2.0 if neg_eigval else 1.0
        inner = heads * head_dim
        dense = dict(flatten=False, use_bias=False)
        with self.name_scope():
            self.qkv_proj = nn.Dense(3 * inner, in_units=units, prefix="qkv_proj_",
                                     **dense)
            # one filter a channel: Conv1d(C, C, taps, groups=C, bias=False)'s
            # (C, 1, taps) without the axis of one
            self.conv_weight = self.params.get("conv_weight", shape=(3 * inner, taps))
            self.f_a_proj = nn.Dense(head_dim, in_units=units, prefix="f_a_proj_", **dense)
            self.f_b_proj = nn.Dense(inner, in_units=head_dim, prefix="f_b_proj_", **dense)
            self.A_log = self.params.get("A_log", shape=(heads,), init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(inner,), init="zeros")
            self.b_proj = nn.Dense(heads, in_units=units, prefix="b_proj_", **dense)
            self.g_a_proj = nn.Dense(head_dim, in_units=units, prefix="g_a_proj_", **dense)
            self.g_b_proj = nn.Dense(inner, in_units=head_dim, flatten=False,
                                     use_bias=True, prefix="g_b_proj_")
            self.o_norm = nn.RMSNormSigmoidGate(epsilon=eps, in_channels=head_dim,
                                                prefix="o_norm_")
            self.o_proj = nn.Dense(units, in_units=inner, prefix="o_proj_", **dense)

    def hybrid_forward(self, F, u, conv_weight=None, A_log=None, dt_bias=None):
        H, D = self._heads, self._dim
        inner = H * D
        by_head = dict(shape=(0, 0, H, D))
        # the filter has no bias: a zero of the taps' type, a constant of the
        # program and no parameter
        no_bias = F.zeros_like(F.sum(conv_weight, axis=1))
        qkv = F.causal_conv_silu(self.qkv_proj(u), conv_weight, no_bias,
                                 columns=(0, 3 * inner))

        def head(i):
            return F.reshape(F.slice_axis(qkv, axis=-1, begin=i * inner,
                                          end=(i + 1) * inner), **by_head)

        g = F.kda_log_decay(self.f_b_proj(self.f_a_proj(u)), A_log, dt_bias)
        beta = F.sigmoid(self.b_proj(u)) * self._beta
        o = F.gated_delta_rule(head(0), head(1), head(2), g, beta, chunk=self._chunk)
        gate = F.reshape(self.g_b_proj(self.g_a_proj(u)), **by_head)
        return self.o_proj(F.reshape(self.o_norm(o, gate), shape=(0, 0, -1)))


class SolarOpen2Block(HybridBlock):
    """One pre-norm decoder block: the layer's mixer (``kind`` is ``"kda"`` or
    ``"gqa"``), then the expert layer."""

    def __init__(self, cfg, kind, experts_held=None, chunk=CHUNK, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        whole = experts_held is None or experts_held[1] == cfg["n_routed_experts"]
        with self.name_scope():
            self.input_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                         prefix="input_norm_")
            if kind == "kda":
                lin = cfg["linear_attn_config"]
                self.mixer = KimiDeltaAttention(
                    units, lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"], eps,
                    cfg.get("kda_allow_neg_eigval", False), chunk, prefix="kda_")
            elif kind == "gqa":
                self.mixer = GroupedQueryAttention(
                    units, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"], rope_theta=None, head_norm=False,
                    output_gate=cfg.get("use_gqa_gate", False), prefix="gqa_")
            else:
                raise MXNetError("SolarOpen2Block: layer kind %r is not built (kda or "
                                 "gqa)" % (kind,))
            self.post_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="post_norm_")
            self.ffn = DeepseekMoE(
                units, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                cfg["num_experts_per_tok"], cfg.get("n_shared_experts", 0),
                cfg.get("routed_scaling_factor", 1.0), experts_held,
                scoring="sigmoid", selection_bias=True, router_gradient=whole,
                prefix="moe_")

    def hybrid_forward(self, F, h):
        h = h + self.mixer(self.input_norm(h))
        return h + self.ffn(self.post_norm(h))


class SolarOpen2Model(HybridBlock):
    """Causal LM: token ids (B, T) -> scores (B, T, vocab_size).

    ``cfg`` holds the published config's keys (``hidden_size``,
    ``num_hidden_layers``, ``gqa_layers``, ``linear_attn_config``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``moe_intermediate_size``, ``n_routed_experts``, ``n_shared_experts``,
    ``num_experts_per_tok``, ``rms_norm_eps``, ``vocab_size``, ...):
    ``gqa_layers`` names the layers built as attention, each other one is a
    delta-attention layer. ``experts_held`` is this chip's share of every
    expert layer, all of them by default; ``chunk`` the tokens the delta rule
    takes at a time (the program's choice, no part of the model)."""

    def __init__(self, cfg, experts_held=None, chunk=CHUNK, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        built = {"use_rope": False, "tie_word_embeddings": False,
                 "first_k_dense_replace": 0, "kda_use_full_proj": False,
                 "norm_topk_prob": True}
        for key, want in built.items():
            if cfg.get(key, want) != want:
                raise MXNetError("SolarOpen2Model: %s=%r is not built (only %r)"
                                 % (key, cfg[key], want))
        lin = cfg["linear_attn_config"]
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise MXNetError("SolarOpen2Model: linear_attn_config.num_kv_heads=%r is "
                             "not built (null or the %d heads)"
                             % (lin["num_kv_heads"], lin["num_heads"]))
        layers = cfg["num_hidden_layers"]
        gqa = set(cfg["gqa_layers"])
        if not gqa <= set(range(layers)):
            raise MXNetError("SolarOpen2Model: gqa_layers %r name layers beyond the %d "
                             "built" % (sorted(gqa), layers))
        self._cfg = dict(cfg)
        units, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, prefix="embed_")
            self.blocks = []
            for i in range(layers):
                blk = SolarOpen2Block(cfg, "gqa" if i in gqa else "kda", experts_held,
                                      chunk, prefix="layer%d_" % i)
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
