"""Granite 4.0-H's hybrid decoder (``model_type: granitemoehybrid`` with no
experts; IBM, granite-4.0-h-micro; transformers'
``modeling_granitemoehybrid.py``; the mixer is Mamba-2's, Dao and Gu,
arXiv:2405.21060): a stack whose token mixer is, layer by layer, a Mamba-2
state-space mixer or grouped-query attention without positions
(``layer_types``), each over the same SwiGLU, under muP-style multipliers.

Pre-norm blocks, no bias but the filter's: ``h + r * mixer(RMSNorm(h))``, then
``h + r * mlp(RMSNorm(h))`` with ``r = residual_multiplier``; the embedding
times ``embedding_multiplier``; a last RMSNorm and a head whose weight is the
embedding's, its scores over ``logits_scaling``.

* **mamba** — ``[z, xBC, dt] = in_proj(u)`` (``H P + (H P + 2 G N) + H``
  columns: ``mamba_n_heads`` H of ``mamba_d_head`` P, ``mamba_n_groups`` G,
  ``mamba_d_state`` N); ``[x, B, C] = silu(filter(xBC) + bias)``, a causal
  depthwise filter of ``mamba_d_conv`` taps (op ``causal_conv_silu``); the
  state-space scan ``y = ssd_scan(x, dt, A_log, B, C, D, dt_bias)`` in chunks
  of ``mamba_chunk_size`` (op ``ssd_scan``; ``A_log``, ``D``, ``dt_bias`` one
  number a head); ``RMSNorm(y * silu(z))`` over all ``H P`` channels, the gate
  first (``GatedRMSNorm``); ``out_proj``. Linear in the sequence: its state in
  a decode step is the (P, N) matrix a head and the filter's last
  ``mamba_d_conv - 1`` tokens.
* **attention** — ``model_zoo.keye.GroupedQueryAttention`` with no positions
  and no per-head norm, the scores times ``attention_multiplier`` (not
  ``head_dim ** -0.5``), causal.

Built from the config's own keys. ``layer_types`` is the list of the layers
built, one kind each, so a chip that holds some of the published layers (a
pipeline stage) gives each the kind of its published index.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock
from .. import nn
from .keye import GroupedQueryAttention

__all__ = ["Mamba2Mixer", "GraniteHybridBlock", "GraniteHybridModel"]


class Mamba2Mixer(HybridBlock):
    """(B, T, units) -> (B, T, units): the filter, the scan and the gated norm
    between their two projections."""

    def __init__(self, units, heads, head_dim, groups, state, taps, chunk, eps,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if heads % groups:
            raise MXNetError("Mamba2Mixer: %d heads are not whole groups of %d"
                             % (heads, groups))
        self._heads, self._dim, self._groups, self._state = heads, head_dim, groups, state
        self._chunk = chunk
        inner, filtered = heads * head_dim, heads * head_dim + 2 * groups * state
        dense = dict(flatten=False, use_bias=False)
        with self.name_scope():
            self.in_proj = nn.Dense(inner + filtered + heads, in_units=units,
                                    prefix="in_proj_", **dense)
            # one filter a channel: Conv1d(C, C, taps, groups=C)'s (C, 1, taps)
            # without the axis of one
            self.conv_weight = self.params.get("conv_weight", shape=(filtered, taps))
            self.conv_bias = self.params.get("conv_bias", shape=(filtered,),
                                             init="zeros")
            self.A_log = self.params.get("A_log", shape=(heads,), init="zeros")
            self.D = self.params.get("D", shape=(heads,), init="ones")
            self.dt_bias = self.params.get("dt_bias", shape=(heads,), init="zeros")
            self.norm = nn.GatedRMSNorm(epsilon=eps, in_channels=inner, prefix="norm_")
            self.out_proj = nn.Dense(units, in_units=inner, prefix="out_proj_",
                                     **dense)

    def hybrid_forward(self, F, u, conv_weight=None, conv_bias=None, A_log=None,
                       D=None, dt_bias=None):
        H, P, G, N = self._heads, self._dim, self._groups, self._state
        inner = H * P
        zxbcdt = self.in_proj(u)
        cut = _columns(F, zxbcdt)
        z, dt = cut(0, inner), cut(2 * inner + 2 * G * N, None)
        # the filter reads xBC where in_proj left it (a kernel is handed no slice
        # that XLA does not first write out)
        xbc = F.causal_conv_silu(zxbcdt, conv_weight, conv_bias,
                                 columns=(inner, 2 * inner + 2 * G * N))
        cut = _columns(F, xbc)
        y = F.ssd_scan(
            F.reshape(cut(0, inner), shape=(0, 0, H, P)), dt, A_log,
            F.reshape(cut(inner, inner + G * N), shape=(0, 0, G, N)),
            F.reshape(cut(inner + G * N, None), shape=(0, 0, G, N)), D, dt_bias,
            chunk=self._chunk)
        return self.out_proj(self.norm(F.reshape(y, shape=(0, 0, -1)), z))


def _columns(F, data):
    """``cut(begin, end)``: columns of ``data``'s last axis."""
    return lambda begin, end: F.slice_axis(data, axis=-1, begin=begin, end=end)


class GraniteHybridBlock(HybridBlock):
    """One pre-norm decoder block: the layer's mixer (``kind`` is ``"mamba"``
    or ``"attention"``), then the SwiGLU, each times the residual
    multiplier."""

    def __init__(self, cfg, kind, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self._residual = float(cfg.get("residual_multiplier", 1.0))
        with self.name_scope():
            self.input_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                         prefix="input_norm_")
            if kind == "mamba":
                self.mixer = Mamba2Mixer(
                    units, cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
                    cfg["mamba_chunk_size"], eps, prefix="mamba_")
            elif kind == "attention":
                heads = cfg["num_attention_heads"]
                self.mixer = GroupedQueryAttention(
                    units, heads, cfg["num_key_value_heads"],
                    cfg.get("head_dim") or units // heads, rope_theta=None,
                    head_norm=False, sm_scale=cfg.get("attention_multiplier"),
                    prefix="gqa_")
            else:
                raise MXNetError("GraniteHybridBlock: layer type %r is not built "
                                 "(mamba or attention)" % (kind,))
            self.post_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="post_norm_")
            self.mlp = nn.SwiGLU(units, cfg["shared_intermediate_size"], prefix="mlp_")

    def hybrid_forward(self, F, h):
        h = h + self.mixer(self.input_norm(h)) * self._residual
        return h + self.mlp(self.post_norm(h)) * self._residual


class GraniteHybridModel(HybridBlock):
    """Causal LM: token ids (B, T) -> scores (B, T, vocab_size).

    ``cfg`` holds the published config's keys (``hidden_size``,
    ``num_hidden_layers``, ``layer_types``, ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_n_groups``, ``mamba_d_state``, ``mamba_d_conv``,
    ``mamba_chunk_size``, ``num_attention_heads``, ``num_key_value_heads``,
    ``shared_intermediate_size``, ``embedding_multiplier``,
    ``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``,
    ``rms_norm_eps``, ``vocab_size``, ...)."""

    def __init__(self, cfg, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        built = {"num_local_experts": 0, "tie_word_embeddings": True,
                 "position_embedding_type": "nope", "attention_bias": False,
                 "mamba_proj_bias": False, "mamba_conv_bias": True,
                 "hidden_act": "silu", "normalization_function": "rmsnorm"}
        for key, want in built.items():
            if cfg.get(key, want) != want:
                raise MXNetError("GraniteHybridModel: %s=%r is not built (only %r)"
                                 % (key, cfg[key], want))
        kinds = list(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"]:
            raise MXNetError("GraniteHybridModel: %d layer_types for %d layers"
                             % (len(kinds), cfg["num_hidden_layers"]))
        if cfg["mamba_n_heads"] % cfg["mamba_n_groups"]:
            raise MXNetError("GraniteHybridModel: mamba_n_groups %d does not divide "
                             "the %d heads" % (cfg["mamba_n_groups"], cfg["mamba_n_heads"]))
        if cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
                != cfg.get("mamba_expand", 2) * cfg["hidden_size"]:
            raise MXNetError("GraniteHybridModel: %d heads of %d are not mamba_expand "
                             "%r times hidden_size %d"
                             % (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                                cfg.get("mamba_expand", 2), cfg["hidden_size"]))
        self._cfg = dict(cfg)
        self._embedding = float(cfg.get("embedding_multiplier", 1.0))
        self._logits = 1.0 / float(cfg.get("logits_scaling", 1.0))
        units, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, prefix="embed_")
            self.blocks = []
            for i, kind in enumerate(kinds):
                blk = GraniteHybridBlock(cfg, kind, prefix="layer%d_" % i)
                self.register_child(blk, "layer%d" % i)
                self.blocks.append(blk)
            self.norm = nn.RMSNorm(epsilon=cfg["rms_norm_eps"], in_channels=units,
                                   prefix="norm_")
            # the head's weight is the embedding's, one (vocab, units) parameter
            self.head = nn.Dense(vocab, flatten=False, use_bias=False,
                                 in_units=units, prefix="head_",
                                 params=self.embed.params)

    def hybrid_forward(self, F, x):
        h = self.embed(x) * self._embedding
        for blk in self.blocks:
            h = blk(h)
        return self.head(self.norm(h)) * self._logits

