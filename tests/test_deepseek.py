"""The DeepSeek-V3 family at a tiny size on the CPU, against the benchmark's
plain reference (``benchmark/references/deepseek_v3.py``: float32, "highest",
scores materialised, a Python loop over experts): rotary positions, RMSNorm,
attention whose values are narrower than its keys (kernel in interpret mode
and the XLA branches), latent attention, the expert layer under routings that
send every slot, no slot and some slots to the experts held, the share test,
and the whole model's first steps through ``ShardedTrainStep`` against the
benchmark's follower, with the fp8 control.

Tolerances: everything here runs in float32 ("highest" is the suite's matmul
precision), so program and reference differ by summation order alone:
1e-5 relative on values, 2e-4 on gradients that pass a softmax or a sort.
The model-level test runs the program in bfloat16 and is held as the
benchmark holds a cell: by limits between the sound reading and the control's.
"""
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo import deepseek as zoo
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import moe as M
from mxnet_tpu.ops import nn as N

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import compare, loader, train_reference  # noqa: E402

ref = loader.load_module("references", "deepseek_v3")
F32 = jnp.float32

TINY = dict(hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
            num_attention_heads=2, kv_lora_rank=16, q_lora_rank=None,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=1e4, rope_interleave=True, rms_norm_eps=1e-6,
            intermediate_size=48, moe_intermediate_size=24, n_routed_experts=16,
            num_experts_per_tok=3, n_shared_experts=2, routed_scaling_factor=2.448,
            vocab_size=50)


def _config(held=(0, 16), **over):
    """A configuration file's content around TINY: the experts held stand at
    the top level, the router's width under ``published``."""
    c = dict(TINY, **over)
    c.update(family="deepseek_v3", dtype="float32", experts_held=list(held),
             published={"n_routed_experts": c["n_routed_experts"]},
             optimizer={"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                        "beta2": 0.95, "epsilon": 1e-8})
    c["n_routed_experts"] = held[1]
    return c


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-30)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * scale, \
        (np.max(np.abs(got - want)) / scale, tol)


def _normal(seed, shape, std=1.0):
    return std * jax.random.normal(jax.random.PRNGKey(seed), shape, F32)


# -- rotary positions, RMSNorm, SwiGLU ---------------------------------------
@pytest.mark.parametrize("interleaved", [True, False])
def test_rotary_positions_against_the_reference(interleaved):
    x = _normal(1, (2, 3, 12, 8))  # (B, H, T, D), position along T
    got, vjp = jax.vjp(lambda a: N.rotary_embedding(a, theta=1e4, interleaved=interleaved,
                                                    seq_axis=2), x)
    want, ref_vjp = jax.vjp(lambda a: ref.rope(a, 1e4, interleaved), x)
    _close(got, want, 1e-5)
    ct = _normal(2, x.shape)
    _close(vjp(ct)[0], ref_vjp(ct)[0], 1e-5)
    # position along another axis: (B, T, H, D) as the model keeps it
    moved = N.rotary_embedding(jnp.moveaxis(x, 1, 2), theta=1e4, interleaved=interleaved,
                               seq_axis=1)
    _close(jnp.moveaxis(moved, 2, 1), want, 1e-5)


def test_interleaved_rotary_keeps_products_of_the_paired_form():
    """The interleaved op leaves its result as [evens, odds]; the products of
    a query and a key turned alike are those of the textbook paired form."""
    q, k = _normal(3, (5, 8)), _normal(4, (5, 8))
    turn = functools.partial(N.rotary_embedding, theta=1e4, interleaved=True, seq_axis=0)
    pos = np.arange(5)[:, None] * (1e4 ** (-np.arange(4) * 2 / 8.0))[None, :]

    def paired(x):  # rotate each pair (2j, 2j+1) in place
        x = np.asarray(x, np.float64).reshape(5, 4, 2)
        out = np.stack([x[..., 0] * np.cos(pos) - x[..., 1] * np.sin(pos),
                        x[..., 1] * np.cos(pos) + x[..., 0] * np.sin(pos)], axis=-1)
        return out.reshape(5, 8)

    _close(turn(q) @ turn(k).T, paired(q) @ paired(k).T, 1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rmsnorm_against_the_reference(dtype):
    x = _normal(5, (3, 7, 32)).astype(dtype)
    g = (1.0 + _normal(6, (32,), 0.1)).astype(dtype)
    got, vjp = jax.vjp(lambda a, b: N.rms_norm(a, b, eps=1e-6), x, g)
    want, ref_vjp = jax.vjp(lambda a, b: ref.rms_norm(a, b, 1e-6), x.astype(F32),
                            g.astype(F32))
    ct = _normal(7, x.shape)
    # bfloat16: the op rounds its result (and the gradients) once, 2**-8
    tol = 1e-5 if dtype == "float32" else 1.0 / 128
    assert got.dtype == x.dtype
    _close(got.astype(F32), want, tol)
    for a, b in zip(vjp(ct.astype(dtype)), ref_vjp(ct.astype(dtype).astype(F32))):
        _close(a.astype(F32), b, 2 * tol)


def test_gluon_rmsnorm_and_swiglu_blocks():
    x = nd.array(np.asarray(_normal(8, (4, 6, 32))))
    norm = mx.gluon.nn.RMSNorm(in_channels=32)
    ffn = mx.gluon.nn.SwiGLU(32, 48)
    norm.initialize()
    ffn.initialize()
    _close(norm(x).asnumpy(), ref.rms_norm(x.data, jnp.ones(32), 1e-6), 1e-5)
    p = {k.split("_", 1)[1]: v.data().data for k, v in ffn.collect_params().items()}
    want = ref.swiglu(x.data, p["gate_weight"], p["up_weight"], p["down_weight"])
    _close(ffn(x).asnumpy(), want, 1e-5)
    assert sorted(p) == ["down_weight", "gate_weight", "up_weight"]  # no bias


# -- attention whose values are narrower than its keys --------------------------
@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The dispatch as on a TPU, its two kernels in interpret mode."""
    fwd, bwd = A._flash_forward_pallas, A._flash_backward_pallas
    monkeypatch.setattr(A, "on_tpu", lambda: True)
    monkeypatch.setattr(A, "_flash_forward_pallas",
                        lambda *a, interpret=False, **kw: fwd(*a, interpret=True, **kw))
    monkeypatch.setattr(A, "_flash_backward_pallas",
                        lambda *a, interpret=False, **kw: bwd(*a, interpret=True, **kw))


def _qkv(dk=24, dv=16, t=160, seed=10):
    return (_normal(seed, (1, 2, t, dk)), _normal(seed + 1, (1, 2, t, dk)),
            _normal(seed + 2, (1, 2, t, dv)), _normal(seed + 3, (1, 2, t, dv)))


def _attention_case(causal, branch, monkeypatch):
    q, k, v, do = _qkv()
    sm = q.shape[-1] ** -0.5
    want, ref_vjp = jax.vjp(lambda a, b, c: A._attention_reference(a, b, c, None, causal, sm),
                            q, k, v)
    before = mx.telemetry.flash_bwd_branches().get(branch, 0)
    if branch == "chunked":  # K/V that do not fit: the scan, then chunks
        monkeypatch.setattr(A, "_VMEM_KV_BYTES", 1024)
    got, vjp = jax.vjp(lambda a, b, c: A.flash_attention(a, b, c, causal=causal), q, k, v)
    assert got.shape == v.shape[:2] + (q.shape[2], v.shape[3])
    _close(got, want, 1e-5)
    for a, b in zip(vjp(do), ref_vjp(do)):
        _close(a, b, 2e-4)
    assert mx.telemetry.flash_bwd_branches().get(branch, 0) == before + 1


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("branch", ["materialised", "chunked"])
def test_flash_attention_value_width_in_xla(causal, branch, monkeypatch):
    _attention_case(causal, branch, monkeypatch)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_value_width_in_the_kernels(causal, monkeypatch,
                                                    interpreted_kernels):
    _attention_case(causal, "kernel", monkeypatch)


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 128), (64, 64), (32, 16), (160, 32)])
def test_causal_forward_kernel_stops_at_the_diagonal(bq, bk):
    """The forward kernel visits no K/V block right of the diagonal; rows of a
    ragged last block, and Tq < Tk (bottom-right aligned), read as before."""
    q, k, v, _ = _qkv(t=160)
    for tq in (160, 96):
        sm = q.shape[-1] ** -0.5
        out, lse = A._flash_forward_pallas(q[:, :, :tq], k, v, None, True, sm, bq, bk, True)
        _close(out, A._attention_reference(q[:, :, :tq], k, v, None, True, sm), 1e-5)
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, :tq], k) * sm
        mask = jnp.tril(jnp.ones((tq, 160), bool), k=160 - tq)
        _close(lse, jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1), 1e-5)


def test_backward_kernel_asks_for_vmem_only_past_the_default():
    # BERT's call (512 x 64) and everything inside 1 MB of Q + dO: the default
    assert A._bwd_vmem_limit(512, 64, 64, 512, 512, 2) is None
    assert A._bwd_vmem_limit(128, 64, 64, 128, 128, 2) is None
    assert A._bwd_vmem_limit(2048, 64, 64, 512, 512, 2) is None
    # the Kanana cell's head: 4096 rows, keys 192, values 128
    limit = A._bwd_vmem_limit(4096, 192, 128, 512, 512, 2)
    assert 16 * 2 ** 20 < limit < 64 * 2 ** 20
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    q, v = shape((2, 32, 4096, 192)), shape((2, 32, 4096, 128))
    assert A._kv_fits_vmem(q, v) and A._qdo_fits_vmem(q, v)
    assert not A._kv_fits_vmem(shape((2, 32, 8192, 192)), shape((2, 32, 8192, 128)))


# -- latent attention -------------------------------------------------------------
def _mla_params(a, seed=20):
    h, heads = a["hidden_size"], a["num_attention_heads"]
    nope, rp, dv, lora = (a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"],
                          a["kv_lora_rank"])
    shapes = {"q.w": (heads * (nope + rp), h), "kv_a.w": (lora + rp, h),
              "kv_a_norm.g": (lora,), "kv_b.w": (heads * (nope + dv), lora),
              "o.w": (h, heads * dv)}
    return {k: (1.0 + _normal(seed + i, s, 0.1)) if k.endswith(".g")
            else _normal(seed + i, s, 0.2) for i, (k, s) in enumerate(shapes.items())}


def _mla_block(p):
    blk = zoo.MLAttention(TINY["hidden_size"], TINY["num_attention_heads"],
                          TINY["kv_lora_rank"], TINY["qk_nope_head_dim"],
                          TINY["qk_rope_head_dim"], TINY["v_head_dim"],
                          TINY["rope_theta"], True, TINY["rms_norm_eps"], prefix="mla_")
    blk.initialize()
    names = {"q.w": "mla_q_proj_weight", "kv_a.w": "mla_kv_a_weight",
             "kv_a_norm.g": "mla_kv_a_norm_gamma", "kv_b.w": "mla_kv_b_weight",
             "o.w": "mla_o_proj_weight"}
    params = blk.collect_params()
    for leaf, name in names.items():
        params[name].set_data(nd.array(np.asarray(p[leaf])))
    return blk, {leaf: params[name] for leaf, name in names.items()}


def _mla_case():
    a = ref.arch(_config())
    p = _mla_params(a)
    x = _normal(30, (2, 40, TINY["hidden_size"]))
    ct = _normal(31, x.shape)
    want, ref_vjp = jax.vjp(lambda pp, xx: ref.mla(pp, xx, a, head_block=1), p, x)
    blk, params = _mla_block(p)
    xn = nd.array(np.asarray(x))
    xn.attach_grad()
    with mx.autograd.record():
        out = blk(xn)
    out.backward(nd.array(np.asarray(ct)))
    _close(out.asnumpy(), want, 1e-5)
    dp, dx = ref_vjp(ct)
    _close(xn.grad.asnumpy(), dx, 2e-4)
    for leaf, prm in params.items():
        _close(prm.grad().asnumpy(), dp[leaf], 2e-4)


def test_latent_attention_against_the_reference_in_xla():
    _mla_case()


def test_latent_attention_against_the_reference_in_the_kernels(interpreted_kernels):
    _mla_case()


def test_a_low_rank_query_is_refused_not_guessed():
    with pytest.raises(mx.base.MXNetError):
        zoo.MLAttention(32, 2, 16, 16, 8, 16, q_lora_rank=24)


# -- the expert layer ---------------------------------------------------------------
def _moe_params(a, held, seed=40, shared=True):
    h, w, width = a["hidden_size"], a["moe_intermediate_size"], a["router_width"]
    s = a["n_shared_experts"] * w
    shapes = {"router.w": (width, h), "router.bias": (width,),
              "experts.gate": (held[1], h, w), "experts.up": (held[1], h, w),
              "experts.down": (held[1], w, h)}
    if shared:
        shapes.update({"shared.gate.w": (s, h), "shared.up.w": (s, h),
                       "shared.down.w": (h, s)})
    return {k: _normal(seed + i, sh, 0.1 if k == "router.bias" else 0.3)
            for i, (k, sh) in enumerate(shapes.items())}


def _run_moe(p, x, a, held, bound=None):
    """The pure function on (..., H) tokens; ``bound`` is ``moe_ffn_raw``'s
    own argument for tests (the op and the blocks take the default bound of
    their shapes, which no tiny size overflows)."""
    args = [p["router.w"], p["router.bias"], p["experts.gate"], p["experts.up"],
            p["experts.down"], p.get("shared.gate.w"), p.get("shared.up.w"),
            p.get("shared.down.w")]
    y, load, lost, ran = M.moe_ffn_raw(
        x.reshape(-1, x.shape[-1]), *args, top_k=a["num_experts_per_tok"],
        n_routed=a["router_width"], experts_held=held,
        scaling=a["routed_scaling_factor"], slots_bound=bound)
    return y.reshape(x.shape), load, lost, ran


ROUTINGS = {
    # experts 4..9 held of 16, top-3: the bias sends every slot to held
    # experts, no slot to them, or leaves the choice to the scores
    "every_slot_held": lambda b: b.at[4:10].add(10.0),
    "no_slot_held": lambda b: b.at[4:10].add(-10.0),
    "some_slots_held": lambda b: b,
}


@pytest.mark.parametrize("bound", [None, 16], ids=["one_block", "overflow_blocks"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_expert_layer_against_the_reference(routing, bound):
    held = (4, 6)
    a = ref.arch(_config(held))
    p = _moe_params(a, held)
    p["router.bias"] = ROUTINGS[routing](p["router.bias"])
    x = _normal(50, (2, 24, a["hidden_size"]))  # 48 tokens, 144 slots
    ct = _normal(51, x.shape)
    flat = x.reshape(-1, x.shape[-1])
    want, ref_vjp = jax.vjp(lambda pp, xx: ref.moe(pp, xx, a), p, flat)
    (got, load, lost, ran), vjp = jax.vjp(
        lambda pp, xx: _run_moe(pp, xx, a, held, bound), p, x)
    _close(got.reshape(want.shape), want, 1e-5)
    dp, dx = vjp((ct,) + tuple(jnp.zeros(c.shape, jax.dtypes.float0)
                               for c in (load, lost, ran)))
    want_dp, want_dx = ref_vjp(ct.reshape(want.shape))
    _close(dx.reshape(want_dx.shape), want_dx, 2e-4)
    for leaf in p:
        if leaf == "router.bias":  # a buffer: no gradient in either
            assert not np.any(np.asarray(want_dp[leaf])) and not np.any(np.asarray(dp[leaf]))
        else:
            _close(dp[leaf], want_dp[leaf], 2e-4)
    # the counts: slots by held expert as the reference routes them, none lost
    weights = np.asarray(ref.routing(p, flat, a))
    assert list(np.asarray(load)) == list((weights[:, 4:10] > 0).sum(axis=0))
    assert int(lost) == 0
    total = int(np.asarray(load).sum())
    assert {"every_slot_held": total == 144, "no_slot_held": total == 0,
            "some_slots_held": 0 < total < 144}[routing]
    if bound and routing == "every_slot_held":
        assert total > 4 * bound  # the further blocks did the rest, exactly


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bound", [None, 512], ids=["one_block", "further_blocks"])
def test_expert_layer_through_the_kernels_equals_ragged_dot(bound, dtype,
                                                            grouped_matmul_kernels):
    """The layer at whole 128-lane widths and whole row tiles, where the rule
    of ``ops/grouped_matmul.py`` engages: value and every gradient through the
    kernels (interpret mode) against the same layer through ``ragged_dot``,
    within the operands' rounding. With 512 rows a block and every slot held,
    the first block's kept residuals AND the recomputed further blocks take
    the kernels."""
    from mxnet_tpu import telemetry

    held = (4, 6)
    a = ref.arch(_config(held, hidden_size=128, moe_intermediate_size=128))
    p = _moe_params(a, held)
    p["router.bias"] = ROUTINGS["every_slot_held"](p["router.bias"])
    x = _normal(52, (512, a["hidden_size"]))  # 1536 slots, all held
    ct = _normal(53, x.shape)
    p, x = jax.tree_util.tree_map(lambda v: v.astype(dtype), (p, x))

    def run():
        (y, load, lost, ran), vjp = jax.vjp(
            lambda pp, xx: _run_moe(pp, xx, a, held, bound), p, x)
        return y, vjp((ct.astype(dtype),) + tuple(
            jnp.zeros(c.shape, jax.dtypes.float0) for c in (load, lost, ran))), lost, ran

    want, want_grads, _, _ = run()
    grouped_matmul_kernels()
    before = telemetry.grouped_matmul_branches()
    got, got_grads, lost, ran = run()
    after = telemetry.grouped_matmul_branches()
    # gate, up and down, once for block 0 and once inside each loop's body
    calls = 3 if bound is None else 6
    for product in ("fwd", "dx", "dw"):
        assert after[product]["kernel"] - before.get(product, {}).get("kernel", 0) >= calls
    assert int(lost) == 0 and int(ran) == (0 if bound is None else 2)
    tol = 1e-5 if dtype == "float32" else 2e-2
    _close(got, want, tol)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        if np.any(np.asarray(w, np.float32)):
            _close(g, w, tol)


def test_slots_lost_counts_the_rows_of_a_block_that_did_not_run(monkeypatch):
    """The count is taken from the work done: the slots held less the rows
    handed to the grouped matmuls of the blocks that ran. With the trip count
    of the loop over the further blocks made 0, everything past the first
    block's 16 rows reads as lost, and the result is no longer the
    reference's."""
    held = (4, 6)
    a = ref.arch(_config(held))
    p = _moe_params(a, held)
    p["router.bias"] = ROUTINGS["every_slot_held"](p["router.bias"])
    x = _normal(50, (48, a["hidden_size"]))  # 144 slots, all held
    want = ref.moe(p, x, a)
    y, load, lost, ran = _run_moe(p, x, a, held, 16)
    assert int(lost) == 0 and int(load.sum()) == 144 and int(ran) == 8
    _close(y, want, 1e-5)
    monkeypatch.setattr(M, "_further_blocks", lambda held, bound: jnp.zeros((), jnp.int32))
    y, load, lost, ran = _run_moe(p, x, a, held, 16)
    assert int(load.sum()) == 144 and int(lost) == 144 - 16 and int(ran) == 0
    assert not np.allclose(np.asarray(y), np.asarray(want), rtol=1e-2, atol=1e-2)


def _routed_case(routing, tokens=48):
    held = (4, 6)
    a = ref.arch(_config(held))
    p = _moe_params(a, held)
    p["router.bias"] = ROUTINGS[routing](p["router.bias"])
    return a, held, p, _normal(50, (tokens, a["hidden_size"]))


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_blocks_run_counts_the_further_blocks_that_hold_a_row(routing):
    """None under the layers' own bound (no tiny size overflows it); with 16
    rows a block, as many as the slots held need past the first."""
    a, held, p, x = _routed_case(routing)
    _, load, lost, ran = _run_moe(p, x, a, held)
    assert int(ran) == 0 and int(lost) == 0
    _, load, lost, ran = _run_moe(p, x, a, held, 16)
    total = int(load.sum())
    assert int(ran) == max(-(-total // 16) - 1, 0) and int(lost) == 0
    assert int(ran) == {"every_slot_held": 8, "no_slot_held": 0}.get(routing, int(ran))


def _moe_gradient(p, x, a, held, bound):
    ct = _normal(51, x.shape)
    return jax.grad(lambda pp, xx: jnp.sum(_run_moe(pp, xx, a, held, bound)[0] * ct),
                    argnums=(0, 1))(p, x)


def test_a_further_block_that_holds_no_row_leaves_the_gradient_untouched():
    """Two blocks of 72 rows, and a routing whose held slots fill only the
    first: the loop over the further blocks makes no trip in either pass, so
    every gradient is, bit for bit, that of one block (its extra rows are
    zeros that the sums pass over)."""
    a, held, p, x = _routed_case("some_slots_held")
    _, load, _, ran = _run_moe(p, x, a, held, 72)
    assert 0 < int(load.sum()) <= 72 and int(ran) == 0
    two, one = (_moe_gradient(p, x, a, held, bound) for bound in (72, 144))
    for got, want in zip(jax.tree_util.tree_leaves(two), jax.tree_util.tree_leaves(one)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _eqns_in_loops(jaxpr, inside=False):
    """(equation, whether a ``while`` or ``scan`` body holds it) of a jaxpr
    and every jaxpr nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        loop = inside or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_in_loops(sub, loop)


def test_the_gradient_builds_no_zero_weight_stacks_inside_a_loop():
    """Four blocks of 36 rows. A block that does not run must write nothing:
    the gradient's loop over the further blocks carries the weight stacks'
    cotangents and adds to them, and no equation inside a loop broadcasts a
    value to a weight stack's shape (the zeros a skipped ``cond`` branch
    wrote, three stacks a block a layer, were 6 ms a layer on the chip)."""
    a, held, p, x = _routed_case("some_slots_held")
    jaxpr = jax.make_jaxpr(lambda pp, xx: _moe_gradient(pp, xx, a, held, 36))(p, x)
    stacks = {p[k].shape for k in ("experts.gate", "experts.up", "experts.down")}
    eqns = list(_eqns_in_loops(jaxpr.jaxpr))
    loops = [e for e, _ in eqns if e.primitive.name == "while"]
    assert len(loops) == 2  # one a pass, their trip count read from the data
    carried = [{v.aval.shape for v in e.outvars} for e in loops]
    assert any(stacks <= shapes for shapes in carried)
    written = [e for e, inside in eqns if inside and e.primitive.name == "broadcast_in_dim"
               and e.outvars[0].aval.shape in stacks]
    assert not written, written


def test_both_passes_of_the_expert_layer_carry_its_scopes(monkeypatch):
    """Device time is attributed by the names in the compiled step: every
    grouped matmul of the forward, of the backward (whose rule is written by
    hand) and of the blocks recomputed there stands under ``moe`` and
    ``experts``, and the backward's count as backward."""
    from mxnet_tpu import profiler_trace

    a, held, p, x = _routed_case("every_slot_held")
    args = [p[k] for k in ("router.w", "router.bias", "experts.gate", "experts.up",
                           "experts.down")]

    def loss(x, *args):
        with jax.named_scope("forward"):
            y = M.moe_ffn(x, *args, top_k=3, experts_held=held)[0]
        return jnp.sum(y * y)

    # the op takes the layers' own bound: a tile of 8 rows makes it 112 of 144 slots
    monkeypatch.setattr(M, "_BOUND_TILE", 8)
    text = jax.jit(jax.grad(loss, argnums=(0, 3))).lower(x, *args).as_text(debug_info=True)
    names = [m for m in re.findall(r'loc\("([^"]+)"', text) if "/ragged_dot" in m]
    assert names
    phases = {}
    for name in names:
        scopes = profiler_trace.scopes_of(name)
        assert "moe" in scopes and "experts" in scopes, name
        phases.setdefault(profiler_trace.phase_of("fusion", name), set()).add(
            "body" in name.split("/"))
    # both passes, in line (block 0) and in a loop's body (the further blocks)
    assert phases == {"forward": {False, True}, "backward": {False, True}}, phases


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Eight chips hold two of sixteen experts each. Their parts of the
    result, with the shared experts (which every chip computes alike) counted
    once, add up to the plain reference's result for the whole layer."""
    whole = (0, 16)
    a = ref.arch(_config(whole))
    p = _moe_params(a, whole)
    x = _normal(60, (40, a["hidden_size"]))
    want = ref.moe(p, x, a)
    total, slots = jnp.zeros_like(want), 0
    for chip in range(8):
        held = (2 * chip, 2)
        part = {k: (v[2 * chip:2 * chip + 2] if k.startswith("experts.") else v)
                for k, v in p.items() if not k.startswith("shared.")}
        y, load, lost, _ = _run_moe(part, x, a, held)
        # the reference given the same share gives the same part
        _close(y, ref.moe(part, x, ref.arch(_config(held))), 1e-5)
        total, slots = total + y, slots + int(load.sum())
        assert int(lost) == 0
    only_shared = ref.swiglu(x, p["shared.gate.w"], p["shared.up.w"], p["shared.down.w"])
    _close(total + only_shared, want, 1e-5)
    assert slots == 40 * a["num_experts_per_tok"]  # every slot computed once


def test_default_slots_bound_is_twice_an_even_share_in_whole_tiles():
    assert M.default_slots_bound(8192, 6, 128, 16) == 12288
    assert M.default_slots_bound(8192, 6, 128, 128) == 8192 * 6
    assert M.default_slots_bound(48, 3, 16, 6) == 144  # never more than the slots


def test_gluon_expert_layer_counts_in_aux_state_and_keeps_its_types():
    moe = zoo.DeepseekMoE(32, 24, 16, 3, n_shared_experts=2, routed_scaling_factor=2.448,
                          experts_held=(4, 6), prefix="moe_")
    moe.initialize()
    moe.cast("bfloat16")
    types = {k.split("moe_", 1)[1]: str(np.dtype(v.dtype)) for k, v in
             moe.collect_params().items()}
    assert types["router_bias"] == "float32" and types["expert_load"] == "int32"
    assert types["slots_lost"] == "int32" and types["gate_weight"] == "bfloat16"
    assert types["blocks_run"] == "int32"
    # the router is trained whatever the share; the selection bias never is
    assert moe.router_weight.grad_req == "write" and moe.router_bias.grad_req == "null"
    x = nd.array(np.asarray(_normal(70, (2, 8, 32)))).astype("bfloat16")
    for _ in range(2):
        moe(x)
    load = np.asarray(moe.expert_load.data().data)
    assert load.sum() > 0 and load.sum() % 2 == 0  # two equal passes, summed
    with pytest.raises(mx.base.MXNetError):
        zoo.DeepseekMoE(32, 24, 16, 3, experts_held=(12, 6))


# -- the whole model through ShardedTrainStep, against the follower -------------------
def _first_steps(cell="kanana2_a3b_train_s4096", faults=(), router_trained=False):
    """The stand-in of the benchmark's cell at the tiny size: the program's
    first steps through ShardedTrainStep, the plain reference's, and the
    reference's under each fault in the program's place. The cell freezes
    the routers (``assumed.router_trained`` false: the adapter sets their
    ``grad_req``, the reference stops their gradient); ``router_trained``
    runs both sides with them trained, as the model zoo's block is."""
    c = loader.resolve_cell(cell, rehearse=True)
    config = loader.load_json("configs", c["config"])
    assert config["assumed"]["router_trained"] is False
    config["assumed"] = dict(config["assumed"], router_trained=router_trained)
    traffic = loader.load_json("traffic", c["traffic"])
    model = loader.load_module("models", config["family"])
    runner = loader.load_module("runners", c["runner"])
    opt = train_reference.effective_optimizer(config, traffic)
    params, pool = ref.init(config, 5), ref.batches(config, traffic, 5)
    prog = model.build(config, traffic, params, jax.devices()[:1], opt)
    first, later = runner.first_steps(prog, [prog.batch(x, y) for x, y in pool], params,
                                      traffic)
    plain = train_reference.first_steps(ref, config, opt, params, pool,
                                        program_gradient=first["first_gradient"],
                                        keep_gradient=True)
    want = plain.pop("first_gradient")
    routers = {k: (float(np.abs(np.asarray(first["first_gradient"][k], "float32")).sum()),
                   float(np.abs(np.asarray(v, "float32")).sum()))
               for k, v in want.items() if k.endswith("router.w")}
    out = {"cell": c, "program": compare.training_numbers(first, plain), "later": later,
           "router_gradients": routers,
           "zero_counts": prog.zero_counts(), "published": prog.after_window(),
           "entry": prog.entry}
    for fault in faults:
        low = train_reference.first_steps(ref, config, opt, params, pool, quant=fault,
                                          keep_gradient=True)
        rel, norms = train_reference.gradient_distance(low.pop("first_gradient"), want)
        out[fault] = compare.training_numbers(
            low, dict(plain, grad_rel_diff=rel, grad_diff_norms=norms))
    return out


@pytest.fixture(scope="module")
def first_steps():
    return _first_steps(faults=("fp8",))


def test_model_trains_through_sharded_step_like_the_follower(first_steps):
    rows = compare.judge(first_steps["program"], first_steps["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert first_steps["later"] == 0  # nothing compiled after the first call
    assert getattr(first_steps["entry"], "fused", True)
    assert first_steps["zero_counts"] == {"routed_slots_lost": 0}
    layers = first_steps["published"]["expert_slots"]
    assert len(layers) == 2 and all(len(row) == 4 and sum(row) > 0 for row in layers)
    # the cell's routers are frozen, in the reference too
    assert sorted(first_steps["router_gradients"].values()) == [(0.0, 0.0)] * 2


def test_model_with_its_routers_trained_follows_the_reference_too():
    got = _first_steps(router_trained=True)
    rows = compare.judge(got["program"], got["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert got["later"] == 0 and got["zero_counts"] == {"routed_slots_lost": 0}
    # (the program's, the reference's) of each expert layer: both train them
    assert len(got["router_gradients"]) == 2
    assert all(mine > 0 and 0.5 < mine / plain < 2
               for mine, plain in got["router_gradients"].values())


def test_the_fp8_control_fails_a_limit_the_program_meets(first_steps):
    def value(numbers, name):
        return next(v for n, v, _ in numbers if n == name)

    sound = value(first_steps["program"], "grad_rel_diff")
    control = value(first_steps["fp8"], "grad_rel_diff")
    assert control > 3 * sound, (sound, control)
    limits = dict(first_steps["cell"]["limits"], grad_rel_diff=(sound * control) ** 0.5)
    assert all(r["ok"] for r in compare.judge(first_steps["program"], limits))
    assert not all(r["ok"] for r in compare.judge(first_steps["fp8"], limits))


def test_overflow_blocks_inside_the_donated_step_change_nothing(monkeypatch):
    """A routing that sends every slot to the experts held overflows the
    layers' own bound (twice an even share; its tile made 8 rows here, since
    a tile of 512 holds every slot of a tiny size): the further blocks, a
    loop whose trip count the step reads from its own routing, recomputed in
    the hand-written backward and added into the first block's cotangents,
    train to the same losses through ``ShardedTrainStep`` as one block does,
    lose no slot, and are counted in aux state (``blocks_run``)."""
    from mxnet_tpu import parallel

    x = nd.array(np.random.RandomState(0).randint(0, 50, (2, 16)).astype("float32"))
    y = nd.array(np.random.RandomState(1).randint(0, 50, (2, 16)).astype("float32"))
    assert M.default_slots_bound(32, 3, 16, 6) == 96  # one block: all the slots
    losses = {}
    for tile in (M._BOUND_TILE, 8):
        monkeypatch.setattr(M, "_BOUND_TILE", tile)
        mx.random.seed(3)
        net = zoo.DeepseekV3Model(dict(TINY), experts_held=(2, 6))
        net.initialize(mx.init.Normal(0.2))
        for moe in net.moe_layers():
            bias = np.zeros(16, "float32")
            bias[2:8] = 10.0
            moe.router_bias.set_data(nd.array(bias))
        mesh = parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1])
        step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                         "adam", {"learning_rate": 1e-2}, mesh=mesh)
        before = zoo.moe_counts(net)
        losses[tile] = [float(step(x, y).asnumpy()) for _ in range(3)]
        counts = zoo.moe_counts(net)
        assert counts["slots_lost"] == 0
        got = [sum(b) - sum(a) for a, b in zip(before["expert_load"], counts["expert_load"])]
        assert got == [3 * 96] * 2  # every slot of every step, in both layers
        # 96 slots over 72 rows: one further block a layer a step, or none
        assert counts["blocks_run"] - before["blocks_run"] == (6 if tile == 8 else 0)
    assert M.default_slots_bound(32, 3, 16, 6) == 72  # 96 slots: two blocks
    np.testing.assert_allclose(losses[8], losses[512], rtol=1e-5)
    assert losses[512][2] < losses[512][0]


def test_model_is_built_from_the_configs_keys_and_refuses_what_it_lacks():
    net = zoo.DeepseekV3Model(dict(TINY), experts_held=(0, 4))
    assert len(net.blocks) == 3 and len(net.moe_layers()) == 2
    assert isinstance(net.blocks[0].ffn, mx.gluon.nn.SwiGLU)
    net.initialize()
    assert net(nd.array(np.zeros((2, 8), "float32"))).shape == (2, 8, 50)
    counts = zoo.publish_moe_counts(net)
    assert counts["slots_lost"] == 0 and len(counts["expert_load"]) == 2
    assert counts["blocks_run"] == 0
    assert mx.telemetry.moe_counts() == counts
    for key, bad in (("scoring_func", "softmax"), ("n_group", 8),
                     ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(mx.base.MXNetError):
            zoo.DeepseekV3Model(dict(TINY, **{key: bad}))
