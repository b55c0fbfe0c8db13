"""SmallThinker's sparse decoder at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/references/smallthinker.py``: float32,
"highest", the window as a mask): the static ``window`` of ``flash_attention``
through both kernels (interpret mode) and through every XLA branch, with 7
query heads a K/V head; ``moe_ffn``'s ready-made logits and ReGLU; the share
test; the layers by the two layouts; and the whole model's first steps
through ``ShardedTrainStep`` against the benchmark's follower, with a window
one key short and the fp8 control.

Tolerances: float32 throughout but for the model-level run in bfloat16, which
is held as the benchmark holds a cell.
"""
import copy
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.gluon.model_zoo import smallthinker as zoo
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import moe as M

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import compare, loader, train_reference  # noqa: E402

ref = loader.load_module("references", "smallthinker")
F32 = jnp.float32
CELL = "smallthinker_a3b_train_s8192"


def _close(got, want, tol, floor=1e-30):
    """Within ``tol`` of the largest entry of ``want`` (``floor`` at least: a
    window of one key has a gradient of exact zeros)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), floor)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * scale, \
        (np.max(np.abs(got - want)) / scale, tol)


# -- the window in both kernels and in every XLA branch -----------------------------
def _qkv(t, heads=7, kv=1, d=32, tk=None, seed=3, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, heads, t, d), F32)
    k = jax.random.normal(ks[1], (batch, kv, tk or t, d), F32)
    v = jax.random.normal(ks[2], (batch, kv, tk or t, d), F32)
    do = jax.random.normal(ks[3], (batch, heads, t, d), F32)
    return q, k, v, do


def _masked(q, k, v, window):
    """The plain formula with the window as a mask, nothing of the op's: a
    query sees itself and the ``window - 1`` keys before it."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    k, v = (jnp.repeat(z, h // k.shape[1], axis=1) for z in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
    i = jnp.arange(t)[:, None] + (tk - t)
    j = jnp.arange(tk)[None, :]
    seen = jnp.logical_and(j <= i, i - j < window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def _want(q, k, v, do, window):
    out, vjp = jax.vjp(lambda *a: _masked(*a, window), q, k, v)
    return (out,) + vjp(do)


# windows smaller than, equal to and larger than T, at a length that is no
# multiple of the block, and one that leaves whole blocks between the edges
@pytest.mark.parametrize("t,window,blocks", [
    (300, 100, (128, 128)), (300, 300, (128, 128)), (300, 1000, (128, 128)),
    (300, 1, (128, 128)), (300, 129, (128, 128)), (640, 300, (128, 128)),
    (512, 200, (128, 256)), (512, 200, (256, 128))])
def test_window_kernels_forward_and_gradients_against_the_masked_reference(
        t, window, blocks):
    """Both kernels in interpret mode, 7 query heads on one K/V head: output,
    dq, dk, dv against ``jax.vjp`` of the plain masked formula."""
    q, k, v, do = _qkv(t)
    sm = q.shape[-1] ** -0.5
    out, lse = A._flash_forward_pallas(q, k, v, None, True, sm, *blocks, True,
                                       window=window)
    dq, dk, dv, _ = A._flash_backward_pallas(q, k, v, None, out, lse, do, True, sm,
                                             *blocks, True, window=window)
    for got, want in zip((out, dq, dk, dv), _want(q, k, v, do, window)):
        _close(got, want, 2e-5, floor=1.0)


def test_window_kernel_with_more_keys_than_queries():
    """Bottom-right aligned, as ``causal``: query i stands at key i + Tk - Tq."""
    q, k, v, do = _qkv(256, heads=2, tk=384)
    sm = q.shape[-1] ** -0.5
    out, lse = A._flash_forward_pallas(q, k, v, None, True, sm, 128, 128, True,
                                       window=100)
    grads = A._flash_backward_pallas(q, k, v, None, out, lse, do, True, sm, 128, 128,
                                     True, window=100)[:3]
    for got, want in zip((out,) + grads, _want(q, k, v, do, 100)):
        _close(got, want, 2e-5)


@pytest.mark.parametrize("causal,window,tq,tk,pad", [
    (False, None, 5, 7, 0), (False, None, 5, 7, 3), (True, None, 6, 6, 0),
    (True, None, 4, 9, 0), (True, None, 4, 9, 2), (True, 3, 6, 6, 0),
    (True, 3, 4, 9, 3), (True, 1, 7, 7, 1)])
def test_who_sees_whom_is_one_rule_against_a_loop(causal, window, tq, tk, pad):
    """``A._visible``, the one statement of the keys a query sees that all
    six branches of the attention combine, against a loop over every pair:
    ``tk`` keys padded by ``pad``, the query at ``row + tk - tq``; from
    ``arange``s (the XLA branches) and from a kernel's iotas alike."""
    import functools

    want = np.array([[c < tk and (not causal or (
        c <= r + tk - tq and (window is None or r + tk - tq - c < window)))
        for c in range(tk + pad)] for r in range(tq)])
    kv_len = tk if pad else None  # a caller with no padded key passes none
    shape = (tq, tk + pad)
    for row, col in (
            (jnp.arange(tq)[:, None], jnp.arange(tk + pad)[None, :]),
            (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
             jax.lax.broadcasted_iota(jnp.int32, shape, 1))):
        seen = A._visible(row, col, tk - tq, kv_len, causal, window)
        assert len(seen) == bool(pad) + causal + (window is not None)
        got = functools.reduce(jnp.logical_and, seen, jnp.ones(shape, bool))
        np.testing.assert_array_equal(np.broadcast_to(got, shape), want)
    if causal and not pad:
        np.testing.assert_array_equal(A._visible_band(tq, tk, window), want)


def test_the_window_call_visits_fewer_blocks_than_the_causal_one():
    """From shapes and blocks alone; at the cell's shapes 108 of 136 tiles
    (79.4 %) for 75.0 % of the pairs; counted a traced call, both kernels."""
    assert A.window_blocks(8192, 8192, 512, 512, 4096) == (108, 136)
    assert A.window_blocks(8192, 8192, 512, 512, 8192) == (136, 136)
    assert A.window_blocks(300, 300, 128, 128, 100) == (5, 6)
    assert A.window_blocks(300, 300, 128, 128, 1) == (3, 6)
    pairs = 4096 * 4097 // 2 + 4096 * 4096
    assert pairs / (8192 * 8193 // 2) == pytest.approx(0.75, abs=1e-3)
    # brute force over the tiles that hold a visible pair
    for t, tk, bq, bk, w in ((300, 300, 128, 128, 100), (256, 384, 128, 128, 100),
                             (640, 640, 128, 256, 300)):
        i = np.arange(t)[:, None] + (tk - t)
        j = np.arange(tk)[None, :]
        band, tril = (j <= i) & (i - j < w), j <= i
        def tiles(m):
            return sum(bool(m[a:a + bq, b:b + bk].any())
                       for a in range(0, t, bq) for b in range(0, tk, bk))
        assert A.window_blocks(t, tk, bq, bk, w) == (tiles(band), tiles(tril))
    before = telemetry.flash_window_blocks()
    q, k, v, _ = _qkv(300, heads=1)
    A._flash_forward_pallas(q, k, v, None, True, 1.0, 128, 128, True, window=100)
    after = telemetry.flash_window_blocks()["fwd"]
    assert after["visited"] - before.get("fwd", {}).get("visited", 0) == 5
    assert after["causal"] - before.get("fwd", {}).get("causal", 0) == 6
    assert 'mxt_flash_window_blocks_total{kernel="fwd",tiles="visited"}' \
        in telemetry.render_prometheus()


@pytest.mark.parametrize("window", [40, 128, 129])
def test_window_in_the_chunked_xla_branches(window):
    """The scan forward and the chunked backward, the branches a K/V too long
    for VMEM takes, at a chunk the window crosses."""
    q, k, v, do = _qkv(300, heads=4, kv=2)
    sm = q.shape[-1] ** -0.5
    out, lse = A._attention_scan_fwd(q, k, v, None, True, sm, chunk=128, window=window)
    grads = A._bwd_chunked(q, k, v, None, out, lse, do, True, sm, chunk=128,
                           window=window)[:3]
    for got, want in zip((out,) + grads, _want(q, k, v, do, window)):
        _close(got, want, 2e-5)


class _Attend(mx.gluon.HybridBlock):
    def __init__(self, **kwargs):
        super().__init__()
        self._kwargs = kwargs

    def hybrid_forward(self, F, q, k, v):
        return F.flash_attention(q, k, v, **self._kwargs)


@pytest.mark.parametrize("mode", ["imperative", "hybridized"])
def test_the_operator_takes_the_window_and_counts_branches_of_its_own(mode):
    """``flash_attention(causal=True, window=W)`` on the CPU: the reference
    forward and the materialised backward, counted as ``window_`` branches; a
    window that reaches every key is the plain causal call."""
    from mxnet_tpu import autograd as ag

    q, k, v, do = _qkv(48, heads=4, kv=2, d=8)
    fwd0, bwd0 = telemetry.flash_fwd_branches(), telemetry.flash_bwd_branches()
    tiles0 = telemetry.flash_window_blocks()
    net = _Attend(causal=True, window=10)
    if mode == "hybridized":
        net.hybridize()
    args = [nd.NDArray(a) for a in (q, k, v)]
    for a in args:
        a.attach_grad()
    with ag.record():
        y = net(*args)
    y.backward(nd.NDArray(do))
    for got, want in zip([y] + [a.grad for a in args], _want(q, k, v, do, 10)):
        _close(got.asnumpy(), want, 2e-5)
    fwd, bwd = telemetry.flash_fwd_branches(), telemetry.flash_bwd_branches()
    assert fwd["window_reference"] > fwd0.get("window_reference", 0)
    assert bwd["window_materialised"] > bwd0.get("window_materialised", 0)
    # an XLA branch bounds no loop: what it walks counts on both sides
    for kernel, tiles in telemetry.flash_window_blocks().items():
        was = tiles0.get(kernel, {})
        grew = tiles["visited"] - was.get("visited", 0)
        assert grew >= 1 and grew == tiles["causal"] - was.get("causal", 0)
    # every key inside the window: the causal call, counted as one
    plain = fwd.get("reference", 0)
    wide = nd.flash_attention(*args, causal=True, window=48)
    assert telemetry.flash_fwd_branches()["reference"] == plain + 1
    assert telemetry.flash_fwd_branches()["window_reference"] == fwd["window_reference"]
    _close(wide.asnumpy(), nd.flash_attention(*args, causal=True).asnumpy(), 0)


def test_a_window_wants_causal_and_its_own_key():
    q, k, v, _ = _qkv(16, heads=2, kv=2, d=8)
    for bad in (dict(window=4), dict(causal=True, window=0)):
        with pytest.raises(mx.base.MXNetError):
            A.flash_attention(q, k, v, **bad)
    with pytest.raises(mx.base.MXNetError):  # fewer keys than queries
        A.flash_attention(q, k[:, :, :8], v[:, :, :8], causal=True, window=4)


@pytest.fixture
def flash_kernels(monkeypatch):
    """``ops/attention.py`` dispatches as on a TPU, both kernels in interpret
    mode at 128-row blocks."""
    fwd, bwd = A._flash_forward_pallas, A._flash_backward_pallas
    monkeypatch.setattr(A, "on_tpu", lambda: True)
    monkeypatch.setattr(A, "_tuned_config", lambda *a, **kw: {
        "backend": "pallas", "block_q": 128, "block_k": 128})
    monkeypatch.setattr(A, "_flash_forward_pallas", lambda *a, interpret=False, **kw:
                        fwd(*a, interpret=True, **kw))
    monkeypatch.setattr(A, "_flash_backward_pallas", lambda *a, interpret=False, **kw:
                        bwd(*a, interpret=True, **kw))


def test_the_dispatch_hands_the_window_to_both_kernels(flash_kernels):
    """Through the operator's ``custom_vjp`` as on the chip: the window calls
    count as ``window_kernel`` both ways, and give the masked formula's
    output and gradients; the call without a window still counts ``kernel``."""
    q, k, v, do = _qkv(300, heads=7, kv=1)
    fwd0, bwd0 = telemetry.flash_fwd_branches(), telemetry.flash_bwd_branches()
    out, vjp = jax.vjp(lambda *a: A.flash_attention(*a, causal=True, window=100),
                       q, k, v)
    for got, want in zip((out,) + vjp(do), _want(q, k, v, do, 100)):
        _close(got, want, 2e-5)
    fwd, bwd = telemetry.flash_fwd_branches(), telemetry.flash_bwd_branches()
    assert fwd["window_kernel"] == fwd0.get("window_kernel", 0) + 1
    assert bwd["window_kernel"] == bwd0.get("window_kernel", 0) + 1
    jax.vjp(lambda *a: A.flash_attention(*a, causal=True), q, k, v)[1](do)
    assert telemetry.flash_fwd_branches()["kernel"] == fwd.get("kernel", 0) + 1
    assert telemetry.flash_bwd_branches()["kernel"] == bwd.get("kernel", 0) + 1
    assert telemetry.flash_fwd_branches()["window_kernel"] == fwd["window_kernel"]


def test_the_window_kernels_go_by_names_of_their_own_and_read_no_mask():
    """``window_attention_fwd`` / ``window_attention_bwd`` in the traced
    program, three operands in (q, k, v) and five in the backward: nothing is
    read for the window; the call without one keeps the parent's names."""
    q, k, v, do = _qkv(256, heads=2)
    lse = jnp.zeros(q.shape[:3], F32)

    def calls(window):
        f = jax.make_jaxpr(lambda q, k, v: A._flash_forward_pallas(
            q, k, v, None, True, 1.0, 128, 128, True, window=window))(q, k, v)
        b = jax.make_jaxpr(lambda q, k, v, o, l, do: A._flash_backward_pallas(
            q, k, v, None, o, l, do, True, 1.0, 128, 128, True, window=window))(
                q, k, v, q, lse, do)
        return [next(e for e in j.jaxpr.eqns if e.primitive.name == "pallas_call")
                for j in (f, b)]

    for window, names in ((100, ("window_attention_fwd", "window_attention_bwd")),
                          (None, ("flash_attention_fwd", "flash_attention_bwd"))):
        fwd, bwd = calls(window)
        assert (fwd.params["name"], bwd.params["name"]) == names
        assert len(fwd.invars) == 3 and len(bwd.invars) == 5


# -- the expert layer: ready-made logits, ReGLU, the shares ------------------------------
def _arch(held=(0, 8), **over):
    c = dict(hidden_size=32, num_hidden_layers=4, num_attention_heads=7,
             num_key_value_heads=1, head_dim=8, rms_norm_eps=1e-6, rope_theta=1.5e6,
             rope_scaling=None, rope_layout=[0, 1, 1, 1],
             sliding_window_layout=[0, 1, 1, 1], sliding_window_size=6,
             moe_ffn_hidden_size=24, moe_num_active_primary_experts=3,
             moe_num_primary_experts=held[1], moe_primary_router_apply_softmax=True,
             norm_topk_prob=True, tie_word_embeddings=False, vocab_size=50,
             experts_held=list(held),
             published={"moe_num_primary_experts": 8})
    c.update(over)
    return c


def _moe_params(a, held, seed=40):
    h, i = a["hidden_size"], a["moe_ffn_hidden_size"]
    key = jax.random.PRNGKey(seed)
    shapes = {"router.w": (a["router_width"], h), "experts.gate": (8, h, i),
              "experts.up": (8, h, i), "experts.down": (8, i, h)}
    p = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, j), s, F32)
         for j, (n, s) in enumerate(shapes.items())}
    first, count = held
    return {n: (v[first:first + count] if n.startswith("experts.") else v)
            for n, v in p.items()}


def _run_moe(p, y, x, a, held, **over):
    """``moe_ffn`` as the model's block calls it: the logits made from the
    rows ``x`` the router reads, the experts fed ``y``."""
    kw = dict(top_k=a["moe_num_active_primary_experts"], n_routed=a["router_width"],
              experts_held=held, scoring="softmax", activation="relu",
              router_logits=M.moe_router_logits(x, p["router.w"]))
    kw.update(over)
    return M.moe_ffn(y, p["router.w"], None, p["experts.gate"], p["experts.up"],
                     p["experts.down"], **kw)


def _direct(p, y, x, top_k, act):
    """A direct sum over the experts, nothing of the op's or the reference's:
    softmax over the logits of ``x``, the largest ``top_k`` renormalised."""
    s = np.asarray(jax.nn.softmax(x @ p["router.w"].T, axis=-1), np.float64)
    out = np.zeros(y.shape, np.float64)
    y = np.asarray(y, np.float64)
    for n in range(y.shape[0]):
        chosen = np.argsort(-s[n])[:top_k]
        for e in chosen:
            g = y[n] @ np.asarray(p["experts.gate"][e], np.float64)
            u = y[n] @ np.asarray(p["experts.up"][e], np.float64)
            out[n] += s[n, e] / s[n, chosen].sum() * (
                (act(g) * u) @ np.asarray(p["experts.down"][e], np.float64))
    return out


def test_ready_made_logits_and_relu_against_a_direct_sum_over_the_experts():
    a = ref.arch(_arch())
    p = _moe_params(a, (0, 8))
    y = jax.random.normal(jax.random.PRNGKey(41), (40, 32), F32)
    x = jax.random.normal(jax.random.PRNGKey(42), (40, 32), F32)
    out, load, lost, _ = _run_moe(p, y, x, a, (0, 8))
    _close(out, _direct(p, y, x, 3, lambda g: np.maximum(g, 0.0)), 1e-5)
    assert int(load.sum()) == 40 * 3 and int(lost) == 0
    # the router reads x: routed by its own rows the layer gives another result
    own = _run_moe(p, y, y, a, (0, 8))[0]
    assert np.max(np.abs(np.asarray(own - out))) > 1e-2
    # SiLU under the same routing is the other activation, not this one
    silu = _run_moe(p, y, x, a, (0, 8), activation="silu")[0]
    _close(silu, _direct(p, y, x, 3, lambda g: g / (1.0 + np.exp(-g))), 1e-5)
    with pytest.raises(ValueError):
        _run_moe(p, y, x, a, (0, 8), activation="gelu")
    # the gradient reaches the logits' maker where the router trains
    g = jax.grad(lambda w: jnp.sum(_run_moe(dict(p, **{"router.w": w}), y, x, a,
                                            (0, 8))[0]))(p["router.w"])
    assert np.asarray(g).any()
    g = jax.grad(lambda w: jnp.sum(_run_moe(dict(p, **{"router.w": w}), y, x, a, (0, 8),
                                            router_gradient=False)[0]))(p["router.w"])
    assert not np.asarray(g).any()


@pytest.mark.parametrize("scoring,bias", [("sigmoid", True), ("softmax", False)])
def test_the_defaults_are_the_layer_as_it_was_bit_for_bit(scoring, bias):
    """Kanana's, Keye's and LFM2's calls name neither argument: the default
    call equals the call that states ``activation="silu"`` and hands in the
    logits of its own rows, value and gradients to the last bit, and its
    traced program holds no ReLU."""
    a = ref.arch(_arch())
    p = _moe_params(a, (2, 4))
    rb = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (8,), F32) if bias else None
    x = jax.random.normal(jax.random.PRNGKey(43), (24, 32), F32)
    ct = jax.random.normal(jax.random.PRNGKey(44), (24, 32), F32)
    kw = dict(top_k=3, n_routed=8, experts_held=(2, 4), scoring=scoring)

    def run(xx, pp, **more):
        return jnp.sum(M.moe_ffn(xx, pp["router.w"], rb, pp["experts.gate"],
                                 pp["experts.up"], pp["experts.down"], **kw,
                                 **more)[0] * ct)

    want = jax.value_and_grad(run, (0, 1))(x, p)
    got = jax.value_and_grad(lambda xx, pp: run(
        xx, pp, activation="silu",
        router_logits=M.router_product(xx, pp["router.w"])), (0, 1))(x, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    text = str(jax.make_jaxpr(run)(x, p))
    assert "logistic" in text and "relu" not in text  # SiLU, and no ReLU
    assert "relu" in str(jax.make_jaxpr(
        lambda xx, pp: run(xx, pp, activation="relu"))(x, p))


@pytest.mark.parametrize("chips", [4, 2])
def test_shares_of_the_experts_add_up_to_the_uncut_layer(chips):
    """``chips`` chips hold 8 / chips of eight experts each, as the cell's four
    hold 16 of 64. Their parts of the result (the model has no shared expert;
    the router, which all compute alike from the attention's input, is counted
    once: it is the same routing in every part) add up to the plain
    reference's result for the whole layer, and every slot is computed once."""
    whole, each = (0, 8), 8 // chips
    a = ref.arch(_arch(whole))
    p = _moe_params(a, whole)
    y = jax.random.normal(jax.random.PRNGKey(60), (40, 32), F32)
    x = jax.random.normal(jax.random.PRNGKey(61), (40, 32), F32)
    want = ref.moe(p, y, x, a)
    _close(want, _direct(p, y, x, 3, lambda g: np.maximum(g, 0.0)), 1e-5)
    total, slots = jnp.zeros_like(want), 0
    for chip in range(chips):
        held = (each * chip, each)
        part = _moe_params(a, held)  # the same seeded layer, this chip's experts
        out, load, lost, _ = _run_moe(part, y, x, a, held)
        # the reference given the same share gives the same part
        _close(out, ref.moe(part, y, x, ref.arch(_arch(held))), 1e-5)
        total, slots = total + out, slots + int(load.sum())
        assert int(lost) == 0
    _close(total, want, 1e-5)
    assert slots == 40 * a["moe_num_active_primary_experts"]


# -- the model's blocks ----------------------------------------------------------
def _tiny_model(dtype="float32", seed=5, held=(4, 4), **over):
    config = _arch(held, family="smallthinker", dtype=dtype,
                   assumed={"router_trained": False}, **over)
    params = ref.init(config, seed)
    net = zoo.SmallThinkerModel(dict(config, moe_num_primary_experts=8),
                                experts_held=held)
    net.initialize()
    net.cast(dtype)
    model = loader.load_module("models", "smallthinker")
    names = model.leaf_names(config, net.prefix)
    values = {leaf: params[leaf].astype(net.collect_params()[name].dtype)
              for leaf, name in names.items()}
    model.common.set_parameters(net.collect_params(), names, values)
    return config, params, net, names


def _program(net, x, y):
    from mxnet_tpu import autograd as ag

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    with ag.record():
        scores = net(nd.NDArray(x))
        loss = loss_fn(scores, nd.NDArray(y)).mean()
    loss.backward()
    return scores, loss


def _batch(seed=7, t=24):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (2, t + 1), 0, 50)
    return ids[:, :-1].astype(F32), ids[:, 1:].astype(F32)


def test_model_forward_and_every_leafs_gradient_against_the_reference():
    """float32 on both sides: the zoo's decoder (a full layer without
    positions, three window layers with rotary, the router fed from the
    attention's input, ReGLU experts, an untied head) and the plain reference
    give the same loss and the same gradient of every leaf; the routers' are
    zero on a strict share."""
    config, params, net, names = _tiny_model()
    x, y = _batch()
    with jax.default_matmul_precision("highest"):
        want, grads = ref.value_and_grad(config, params, x, y)
        logits = ref.logits(config, params, x)
    scores, loss = _program(net, x, y)
    net_params = net.collect_params()
    _close(scores.asnumpy(), logits, 1e-5)
    _close(loss.asnumpy(), want, 1e-5)
    assert set(names) == set(grads)
    assert names["l0.q.w"].endswith("layer0_attn_full_q_proj_weight")
    assert names["l1.q.w"].endswith("layer1_attn_window_q_proj_weight")
    for leaf, name in names.items():
        got = net_params[name].grad().asnumpy()
        if leaf.endswith("router.w"):
            assert not got.any() and not np.asarray(grads[leaf]).any(), leaf
        else:
            _close(got, grads[leaf], 5e-4)
    counts = zoo.publish_moe_counts(net)
    assert len(counts["expert_load"]) == 4 and counts["slots_lost"] == 0
    assert telemetry.moe_counts() == counts


@pytest.mark.parametrize("fault", ["window_one_key_short", "window_off",
                                   "rotary_in_the_full_layer", "no_rotary_anywhere",
                                   "router_fed_the_experts_rows"])
def test_a_reference_with_the_layer_written_otherwise_is_not_the_program(fault):
    """The controls: the window one key short or off, positions given to the
    NoPE layer or taken from the window layers, the router reading the
    experts' rows. Each moves the loss and the gradient past the tolerance the
    sound comparison meets, so the test above tells them apart."""
    config, params, net, names = _tiny_model()
    x, y = _batch()
    other = copy.deepcopy(config)
    if fault == "window_one_key_short":
        other["reference"] = {"window_short": 1}
    elif fault == "window_off":
        other["sliding_window_layout"] = [0, 0, 0, 0]
    elif fault == "rotary_in_the_full_layer":
        other["rope_layout"] = [1, 1, 1, 1]
    elif fault == "no_rotary_anywhere":
        other["rope_layout"] = [0, 0, 0, 0]
    with jax.default_matmul_precision("highest"):
        if fault == "router_fed_the_experts_rows":
            moe = ref.moe
            try:
                ref.moe = lambda p, yy, xx, a, quant=None: moe(p, yy, yy, a, quant)
                _, grads = ref.value_and_grad(other, params, x, y)
            finally:
                ref.moe = moe
        else:
            _, grads = ref.value_and_grad(other, params, x, y)
    _program(net, x, y)
    net_params = net.collect_params()
    worst = max(
        np.max(np.abs(net_params[name].grad().asnumpy() - np.asarray(grads[leaf])))
        / max(np.max(np.abs(np.asarray(grads[leaf]))), 1e-30)
        for leaf, name in names.items() if not leaf.endswith("router.w"))
    assert worst > 20 * 5e-4, worst


def test_layers_take_their_kind_from_the_two_layouts():
    """NoPE and rotary, full and window, BY LAYER: a model whose layouts say
    otherwise builds other attention, and a window layer's output ignores
    what lies before its window while a full layer's does not."""
    net = zoo.SmallThinkerModel(_arch())
    kinds = [(b.attn._theta, b.attn._window, b.attn.name.partition("_attn_")[2])
             for b in net.blocks]
    assert kinds == [(None, None, "full"), (1.5e6, 6, "window"),
                     (1.5e6, 6, "window"), (1.5e6, 6, "window")]
    mixed = zoo.SmallThinkerModel(_arch(rope_layout=[1, 0, 1, 0],
                                        sliding_window_layout=[1, 1, 0, 0]))
    assert [(b.attn._theta is not None, b.attn._window) for b in mixed.blocks] == [
        (True, 6), (False, 6), (True, None), (False, None)]
    for window, moved in ((6, False), (None, True)):
        attn = zoo.GroupedQueryAttention(32, 7, 1, 8, 1.5e6, head_norm=False,
                                         window=window)
        attn.initialize()
        x = jax.random.normal(jax.random.PRNGKey(9), (1, 20, 32), F32)
        a = attn(nd.NDArray(x)).asnumpy()
        b = attn(nd.NDArray(x.at[0, 3].add(1.0))).asnumpy()
        assert np.array_equal(a[0, :3], b[0, :3])            # the past
        assert np.abs(a[0, 3:9] - b[0, 3:9]).max() > 1e-4    # inside the window
        assert bool(np.abs(a[0, 9:] - b[0, 9:]).max() > 1e-6) is moved


def test_the_config_is_checked_and_a_whole_model_trains_its_router():
    for key, bad in (("moe_primary_router_apply_softmax", False),
                     ("norm_topk_prob", False), ("tie_word_embeddings", True),
                     ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
                     ("moe_enable_early_router", False),
                     ("moe_enable_secondary_experts", True),
                     ("rope_layout", [0, 1, 1]), ("sliding_window_layout", [0, 1, 2, 1])):
        with pytest.raises(mx.base.MXNetError):
            zoo.SmallThinkerModel(dict(_arch(), **{key: bad}))
    with pytest.raises(mx.base.MXNetError):
        zoo.GroupedQueryAttention(32, 7, 2, 8)
    whole = zoo.SmallThinkerModel(_arch())
    assert len(whole.moe_layers()) == 4
    static = whole.blocks[1].ffn._static
    assert static["router_gradient"] is True and static["activation"] == "relu"
    assert static["scoring"] == "softmax" and not whole.blocks[1].ffn._bias
    share = zoo.SmallThinkerModel(dict(_arch((4, 4)), moe_num_primary_experts=8),
                                  experts_held=(4, 4))
    assert share.blocks[1].ffn._static["router_gradient"] is False
    assert not any("norm_q" in n or "qk_norm" in n for n in share.collect_params())


def test_the_step_carries_the_scopes_of_both_kinds_of_attention():
    """Device time is attributed by the names in the compiled step: the full
    layer's attention under ``attn_full``, the window layers' under
    ``attn_window``, both passes; the router's product under ``moe/router``."""
    from mxnet_tpu import parallel, profiler_trace

    _, _, net, _ = _tiny_model()
    x = jnp.zeros((1, 24), F32)
    mesh = parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                     "adam", {"learning_rate": 1e-3}, mesh=mesh)
    step(nd.NDArray(x), nd.NDArray(x))
    common = loader.load_module("models", "gluon_common")
    prog = common.TrainProgram(step, {}, None, None, None, 1, {})
    prog.record_next_step()
    step(nd.NDArray(x), nd.NDArray(x))
    jitted, (args, kwargs) = prog._recorded
    text = jitted.lower(*args, **kwargs).as_text(debug_info=True)
    names, phases = set(), {}
    for name in re.findall(r'loc\("([^"]+)"', text):
        scopes = profiler_trace.scopes_of(name)
        names.update("/".join(scopes[i:j]) for i in range(len(scopes))
                     for j in range(i + 1, len(scopes) + 1))
        for s in scopes:
            phases.setdefault(s, set()).add(profiler_trace.phase_of("fusion", name))
    for want in ("attn_full/q_proj", "attn_full/attention", "attn_full/attention_bwd",
                 "attn_window/kv_proj", "attn_window/attention",
                 "attn_window/attention_bwd", "attn_window/o_proj", "moe/router",
                 "moe/experts"):
        assert want in names, want
    assert {"forward", "backward"} <= phases["attn_window"]
    assert {"forward", "backward"} <= phases["attn_full"]


# -- the whole model through ShardedTrainStep, against the follower -------------------
def _first_steps(faults=()):
    c = loader.resolve_cell(CELL, rehearse=True)
    config = loader.load_json("configs", c["config"])
    assert config["assumed"]["router_trained"] is False
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
    frozen = {k: float(np.abs(np.asarray(first["first_gradient"][k], "float32")).sum())
              + float(np.abs(np.asarray(v, "float32")).sum())
              for k, v in want.items() if k.endswith("router.w")}
    out = {"cell": c, "config": config, "program": compare.training_numbers(first, plain),
           "later": later, "frozen": frozen, "zero_counts": prog.zero_counts(),
           "published": prog.after_window(), "entry": prog.entry}
    for fault in faults:
        other, quant = config, None
        if fault == "fp8":
            quant = "fp8"
        else:  # the window one key short, in float32
            other = dict(config, reference=dict(config["reference"], window_short=1))
        low = train_reference.first_steps(ref, other, opt, params, pool, quant=quant,
                                          keep_gradient=True)
        rel, norms = train_reference.gradient_distance(low.pop("first_gradient"), want)
        out[fault] = compare.training_numbers(
            low, dict(plain, grad_rel_diff=rel, grad_diff_norms=norms))
    return out


@pytest.fixture(scope="module")
def first_steps():
    return _first_steps(faults=("fp8", "window_short"))


def test_model_trains_through_sharded_step_like_the_follower(first_steps):
    rows = compare.judge(first_steps["program"], first_steps["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert first_steps["later"] == 0  # nothing compiled after the first call
    assert getattr(first_steps["entry"], "fused", True)
    # on the CPU the XLA branches are the path, and are not held against it
    assert first_steps["zero_counts"] == {"routed_slots_lost": 0}
    # the four routers are frozen, in the reference too
    assert len(first_steps["frozen"]) == 4 and not any(first_steps["frozen"].values())


def test_the_adapter_publishes_the_slots_and_the_tiles_the_readers_take(first_steps):
    slots = first_steps["published"]["expert_slots"]
    assert len(slots) == 4 and all(len(r) == 4 and sum(r) > 0 for r in slots)
    reader = loader.load_module("layer_metrics", "expert_load_max_over_mean.train")
    assert reader.read({"program": first_steps["published"]}) >= 1.0
    tiles = loader.load_module("layer_metrics", "window_blocks_visited_share.train")
    assert tiles.read({"program": {"window_blocks": {
        "fwd": {"visited": 324, "causal": 408},
        "bwd": {"visited": 324, "causal": 408}}}}) == pytest.approx(79.41, abs=0.01)
    assert tiles.read({"program": {}}) is None and tiles.read({}) is None
    model = loader.load_module("models", "smallthinker")
    assert model.window_calls_off_kernel() > 0  # the CPU's path; 0 on the chip


@pytest.mark.parametrize("fault", ["fp8", "window_short"])
def test_a_control_fails_a_limit_the_program_meets(first_steps, fault):
    """The reference in fp8, and the float32 reference with every window one
    key short: each reads a first gradient farther from the sound reference
    than the bfloat16 program does, by a limit set between the two."""
    def value(numbers, name):
        return next(v for n, v, _ in numbers if n == name)

    sound = value(first_steps["program"], "grad_rel_diff")
    control = value(first_steps[fault], "grad_rel_diff")
    assert control > 3 * sound, (sound, control)
    limits = dict(first_steps["cell"]["limits"], grad_rel_diff=(sound * control) ** 0.5)
    assert all(r["ok"] for r in compare.judge(first_steps["program"], limits))
    assert not all(r["ok"] for r in compare.judge(first_steps[fault], limits))
