"""DeepSeek-V3-style sparse decoder (``model_type: deepseek_v3``: DeepSeek-V3,
arXiv:2412.19437, and the models that publish that config, such as
Kanana-2-30B-A3B): latent attention (MLA), one or more leading dense
layers, then layers of routed experts with shared experts, sigmoid scores,
a selection bias in place of an auxiliary loss, and no dropped tokens.

Pre-norm blocks without biases: ``h + MLA(RMSNorm(h))``, then
``h + FFN(RMSNorm(h))`` with SwiGLU in the dense layers and the expert layer
(``ops/moe.py``) in the others; a last RMSNorm and an untied head.

The model is built from the config's own keys. ``experts_held=(first,
count)`` gives a chip its share of every expert layer under expert
parallelism: the router still scores all ``n_routed_experts``, this chip
computes its own experts' part and the shared expert, and what the other
experts would have added is left out. The router is trained like every
other weight; a strict share that is trained ALONE, without the exchange
that sums the shares' gradients over the chips, has only its own experts'
part of the router's gradient, and whoever runs it so decides what to do
about that (the benchmark's cell freezes the router by ``grad_req``).
Every expert layer counts, in aux state carried through the step like
BatchNorm's running statistics, the slots each held expert got, the slots
it did not compute and the blocks of rows it ran past the first, and beside
those sums its last call's rows moved and rows laid out (``moe_counts``):
read them once a window, never a step.
"""
from __future__ import annotations

import math

import numpy as np

from ...base import MXNetError
from ...ops.moe import rows_moved as _moe_rows_moved
from ..block import HybridBlock
from .. import nn

__all__ = ["MLAttention", "DeepseekMoE", "DeepseekV3Block", "DeepseekV3Model",
           "moe_counts", "publish_moe_counts"]


class MLAttention(HybridBlock):
    """Multi-head latent attention. Queries are ``[nope, rope]`` per head;
    keys and values of all heads come from one ``kv_lora_rank``-wide latent
    (normed), and every head shares one rotary key. Keys are
    ``qk_nope_head_dim + qk_rope_head_dim`` wide, values ``v_head_dim``."""

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta=10000.0,
                 rope_interleave=True, rms_norm_eps=1e-6, q_lora_rank=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if q_lora_rank is not None:
            raise MXNetError("MLAttention: a low-rank query (q_lora_rank=%r) "
                             "is not built" % (q_lora_rank,))
        self._heads = num_heads
        self._nope, self._rope, self._v = (qk_nope_head_dim, qk_rope_head_dim,
                                           v_head_dim)
        self._lora = kv_lora_rank
        self._theta, self._interleave = float(rope_theta), bool(rope_interleave)
        dense = dict(flatten=False, use_bias=False)
        with self.name_scope():
            self.q_proj = nn.Dense(num_heads * (self._nope + self._rope),
                                   in_units=units, prefix="q_proj_", **dense)
            self.kv_a = nn.Dense(kv_lora_rank + self._rope, in_units=units,
                                 prefix="kv_a_", **dense)
            self.kv_a_norm = nn.RMSNorm(epsilon=rms_norm_eps,
                                        in_channels=kv_lora_rank,
                                        prefix="kv_a_norm_")
            self.kv_b = nn.Dense(num_heads * (self._nope + self._v),
                                 in_units=kv_lora_rank, prefix="kv_b_", **dense)
            self.o_proj = nn.Dense(units, in_units=num_heads * self._v,
                                   prefix="o_proj_", **dense)

    def _turn(self, F, x):
        return F.rotary_embedding(x, theta=self._theta, seq_axis=1,
                                  interleaved=self._interleave)

    def hybrid_forward(self, F, x):
        H, nope, rope, dv = self._heads, self._nope, self._rope, self._v
        q = F.reshape(self.q_proj(x), shape=(0, 0, H, nope + rope))
        q = F.concat(F.slice_axis(q, axis=-1, begin=0, end=nope),
                     self._turn(F, F.slice_axis(q, axis=-1, begin=nope, end=None)),
                     dim=-1)
        ckr = self.kv_a(x)  # (B, T, lora + rope)
        latent = self.kv_a_norm(F.slice_axis(ckr, axis=-1, begin=0, end=self._lora))
        k_rope = self._turn(F, F.slice_axis(ckr, axis=-1, begin=self._lora, end=None))
        kv = F.reshape(self.kv_b(latent), shape=(0, 0, H, nope + dv))
        # the one rotary key, copied to every head beside its own nope part
        k_rope = F.broadcast_axis(F.expand_dims(k_rope, axis=2), axis=2, size=H)
        k = F.concat(F.slice_axis(kv, axis=-1, begin=0, end=nope), k_rope, dim=-1)
        v = F.slice_axis(kv, axis=-1, begin=nope, end=None)
        out = F.flash_attention(
            F.transpose(q, axes=(0, 2, 1, 3)), F.transpose(k, axes=(0, 2, 1, 3)),
            F.transpose(v, axes=(0, 2, 1, 3)), causal=True,
            sm_scale=1.0 / math.sqrt(nope + rope))
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -1))
        return self.o_proj(out)


class DeepseekMoE(HybridBlock):
    """The expert layer of a chip that holds ``experts_held=(first, count)``
    of ``n_routed_experts``: op ``moe_ffn``. ``n_shared_experts`` shared
    experts are one SwiGLU of that many times the width. ``scoring`` is the
    router's (``"sigmoid"``, or ``"softmax"`` over all the experts);
    ``selection_bias=False`` is a router without the bias buffer;
    ``router_gradient=False`` lets no gradient through the chosen experts'
    weights (op ``moe_ffn``); ``sum_epsilon`` is what the model adds to the
    chosen scores' sum before dividing by it; ``activation`` is the gate of
    the experts' units (``"silu"``: SwiGLU; ``"relu"``: ReGLU). Called with a
    second input, ``router_rows``, the router reads those rows and not the
    experts' (a model whose router sits before its attention)."""

    def __init__(self, units, moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, n_shared_experts=0,
                 routed_scaling_factor=1.0, experts_held=None,
                 scoring="sigmoid", selection_bias=True, router_gradient=True,
                 sum_epsilon=1e-20, activation="silu", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        first, count = experts_held or (0, n_routed_experts)
        if first < 0 or count < 1 or first + count > n_routed_experts:
            raise MXNetError("experts_held=%r is no share of %d experts"
                             % (experts_held, n_routed_experts))
        self._static = dict(top_k=num_experts_per_tok, n_routed=n_routed_experts,
                            experts_held=(first, count),
                            scaling=routed_scaling_factor, scoring=scoring,
                            router_gradient=router_gradient,
                            sum_epsilon=sum_epsilon, activation=activation)
        self._bias = bool(selection_bias)
        width, shared = moe_intermediate_size, n_shared_experts * moe_intermediate_size
        with self.name_scope():
            g = self.params.get
            self.router_weight = g("router_weight", shape=(n_routed_experts, units))
            # e_score_correction_bias: a buffer the training recipe moves
            # by its own rule, never by a gradient; float32 as published
            if self._bias:
                self.router_bias = g("router_bias", shape=(n_routed_experts,),
                                     init="zeros", grad_req="null")
            self.gate_weight = g("gate_weight", shape=(count, units, width))
            self.up_weight = g("up_weight", shape=(count, units, width))
            self.down_weight = g("down_weight", shape=(count, width, units))
            self._shared = bool(shared)
            if shared:
                self.shared_gate_weight = g("shared_gate_weight", shape=(shared, units))
                self.shared_up_weight = g("shared_up_weight", shape=(shared, units))
                self.shared_down_weight = g("shared_down_weight", shape=(units, shared))
            self.expert_load = g("expert_load", shape=(count,), dtype="int32",
                                 init="zeros", grad_req="null")
            self.slots_lost = g("slots_lost", shape=(1,), dtype="int32",
                                init="zeros", grad_req="null")
            self.blocks_run = g("blocks_run", shape=(1,), dtype="int32",
                                init="zeros", grad_req="null")
            # the last call's, not a sum: rows moved, rows laid out
            self.rows_moved = g("rows_moved", shape=(2,), dtype="int32",
                                init="zeros", grad_req="null")

    def cast(self, dtype):
        """The selection bias stays float32 and the counts int32 under a
        16-bit cast (as BatchNorm's running statistics stay float32); the
        router's weights are cast like the others, trained or not."""
        super().cast(dtype)
        if self._bias:
            self.router_bias.cast("float32")
        self.expert_load.cast("int32")
        self.slots_lost.cast("int32")
        self.blocks_run.cast("int32")
        self.rows_moved.cast("int32")

    def hybrid_forward(self, F, x, router_rows=None, router_weight=None,
                       router_bias=None, gate_weight=None, up_weight=None,
                       down_weight=None, shared_gate_weight=None,
                       shared_up_weight=None, shared_down_weight=None,
                       expert_load=None, slots_lost=None, blocks_run=None,
                       rows_moved=None):
        logits = None
        if router_rows is not None:
            logits = F.moe_router_logits(router_rows, router_weight)
        ret = F.moe_ffn(x, router_weight, router_bias, gate_weight, up_weight,
                        down_weight, shared_gate_weight, shared_up_weight,
                        shared_down_weight, logits, **self._static)
        if not isinstance(ret, tuple):
            return ret  # symbolic trace: the counts are hidden outputs
        out, load, lost, ran = ret
        # the BatchNorm running-statistics protocol: _set_data on the traced
        # wrapper rebinds the aux output of the donated step
        expert_load._set_data(expert_load.data + load.data)
        slots_lost._set_data(slots_lost.data + lost.data.reshape(1))
        blocks_run._set_data(blocks_run.data + ran.data.reshape(1))
        rows_moved._set_data(_moe_rows_moved(
            load.data, ran.data, math.prod(x.shape[:-1]), x.shape[-1], x.data.dtype,
            self._static["top_k"], self._static["n_routed"]))
        return out


class DeepseekV3Block(HybridBlock):
    """One pre-norm decoder block: MLA, then a dense SwiGLU or the expert
    layer."""

    def __init__(self, cfg, dense, experts_held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="attn_norm_")
            self.mla = MLAttention(
                units, cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], cfg["rope_theta"],
                cfg.get("rope_interleave", True), eps, cfg.get("q_lora_rank"),
                prefix="mla_")
            self.ffn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="ffn_norm_")
            if dense:
                self.ffn = nn.SwiGLU(units, cfg["intermediate_size"], prefix="ffn_")
            else:
                self.ffn = DeepseekMoE(
                    units, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                    cfg["num_experts_per_tok"], cfg.get("n_shared_experts", 0),
                    cfg.get("routed_scaling_factor", 1.0), experts_held,
                    prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.mla(self.attn_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class DeepseekV3Model(HybridBlock):
    """Causal LM: token ids (B, T) -> scores (B, T, vocab_size).

    ``cfg`` holds the published config's keys (``hidden_size``,
    ``num_hidden_layers``, ``first_k_dense_replace``, ``n_routed_experts``,
    ``vocab_size``, ...): ``n_routed_experts`` is the router's width whatever
    this chip holds. ``experts_held`` is this chip's share of every expert
    layer, all of them by default."""

    def __init__(self, cfg, experts_held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        built = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "norm_topk_prob": True}
        for key, want in built.items():
            if cfg.get(key, want) != want:
                raise MXNetError("DeepseekV3Model: %s=%r is not built (only %r)"
                                 % (key, cfg[key], want))
        if cfg.get("rope_scaling") is not None:
            raise MXNetError("DeepseekV3Model: rope_scaling is not built")
        self._cfg = dict(cfg)
        units, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, prefix="embed_")
            self.blocks = []
            for i in range(cfg["num_hidden_layers"]):
                blk = DeepseekV3Block(
                    cfg, dense=i < cfg.get("first_k_dense_replace", 0),
                    experts_held=experts_held, prefix="layer%d_" % i)
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
        return [b.ffn for b in self.blocks if isinstance(b.ffn, DeepseekMoE)]


def moe_counts(model):
    """One read of the counts every expert layer of ``model`` keeps on the
    device: ``{"expert_load": [[slots of each held expert] per layer],
    "slots_lost": total, "blocks_run": total, "rows_moved": [[moved, laid
    out] per layer]}``, the first three cumulative since the parameters were
    made, the last what each layer's last call (a step's) moved between
    tokens and experts, both passes, beside what it laid out
    (``ops.moe.rows_moved``). A host sync: call it once a window (epoch end, a
    benchmark's teardown), never a step."""
    layers = model.moe_layers()
    load = [np.asarray(m.expert_load.data().data) for m in layers]  # sync-ok: windowed moe accounting read
    lost = sum(int(np.asarray(m.slots_lost.data().data)[0]) for m in layers)  # sync-ok: windowed moe accounting read
    ran = sum(int(np.asarray(m.blocks_run.data().data)[0]) for m in layers)  # sync-ok: windowed moe accounting read
    rows = [np.asarray(m.rows_moved.data().data) for m in layers]  # sync-ok: windowed moe accounting read
    return {"expert_load": [[int(v) for v in row] for row in load],
            "slots_lost": lost, "blocks_run": ran,
            "rows_moved": [[int(v) for v in row] for row in rows]}


def publish_moe_counts(model):
    """``moe_counts`` into telemetry (``mxt_moe_expert_slots{layer,expert}``
    gauges, ``mxt_moe_slots_lost`` and ``mxt_moe_blocks_run`` gauges,
    ``mxt_moe_rows{layer,rows=moved|laid_out}`` gauges); returns the counts."""
    from ... import telemetry

    counts = moe_counts(model)
    telemetry.record_moe_counts(**counts)
    return counts
