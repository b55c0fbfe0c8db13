"""LFM2's sparse decoder at a tiny size on the CPU, against the benchmark's
plain reference (``benchmark/references/lfm2_moe.py``: float32, "highest",
the convolution as its plain formula over a padded sequence): the op
``gated_short_conv`` with its hand-written backward, its causality, the sigmoid
router with the publisher's 1e-6, the share test, the scopes and the counter,
and the whole model's first steps through ``ShardedTrainStep`` against the
benchmark's follower, with the fp8 control.

Tolerances: float32 throughout but for the op's bfloat16 cases (whose
multiply-adds are float32 inside, so one rounding of the result) and the
model-level run in bfloat16, which is held as the benchmark holds a cell.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.gluon.model_zoo import lfm2 as zoo
from mxnet_tpu.ops import gated_conv as G
from mxnet_tpu.ops import moe as M

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import compare, loader, train_reference  # noqa: E402

ref = loader.load_module("references", "lfm2_moe")
F32 = jnp.float32
CELL = "lfm2_a2b_train_s8192"


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-30)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * scale, \
        (np.max(np.abs(got - want)) / scale, tol)


# -- the op ------------------------------------------------------------------------
def _conv_inputs(dtype, b=2, t=12, c=8, k=3, seed=11):
    key = jax.random.PRNGKey(seed)
    bcx = jax.random.normal(key, (b, t, 3 * c), F32).astype(dtype)
    w = (0.5 * jax.random.normal(jax.random.fold_in(key, 1), (c, k), F32)).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 2), (b, t, c), F32).astype(dtype)
    return bcx, w, g


def _plain(bcx, w):
    """The formula, in float32, with nothing of the op's: V[t] = sum_j w[:, j]
    Z[t - (K - 1) + j] over a sequence padded with zeros before its start."""
    return ref.gated_conv(bcx.astype(F32), w.astype(F32))


class _Conv(mx.gluon.HybridBlock):
    def hybrid_forward(self, F, bcx, w):
        return F.gated_short_conv(bcx, w)


@pytest.mark.parametrize("mode", ["imperative", "hybridized"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_short_conv_forward_and_all_three_gradients(dtype, mode):
    """Against ``jax.grad`` of the plain formula: the output, the gradient of
    ``bcx`` (its three parts: both gates and the filter's input) and of the
    taps, through the op's own backward."""
    from mxnet_tpu import autograd as ag

    bcx, w, g = _conv_inputs(dtype)
    tol = 1e-5 if dtype == "float32" else 1.2e-2
    want = _plain(bcx, w)
    want_bcx, want_w = jax.grad(
        lambda a, b: jnp.sum(_plain(a, b) * g.astype(F32)), argnums=(0, 1))(
            bcx.astype(F32), w.astype(F32))
    net = _Conv()
    if mode == "hybridized":
        net.hybridize()
    x, taps = nd.NDArray(bcx), nd.NDArray(w)
    x.attach_grad()
    taps.attach_grad()
    with ag.record():
        y = net(x, taps)
    y.backward(nd.NDArray(g))
    assert str(y.dtype) == dtype and y.shape == want.shape
    _close(y.asnumpy().astype(np.float32), want, tol)
    c = w.shape[0]
    got = x.grad.asnumpy().astype(np.float32)
    for part in range(3):  # dBg, dCg, dX
        _close(got[..., part * c:(part + 1) * c],
               want_bcx[..., part * c:(part + 1) * c], tol)
    _close(taps.grad.asnumpy().astype(np.float32), want_w, tol)


def test_gated_short_conv_symbolic_and_its_shape_errors():
    bcx, w, _ = _conv_inputs("float32")
    sym = mx.sym.gated_short_conv(mx.sym.Variable("bcx"), mx.sym.Variable("w"))
    exe = sym.bind(mx.cpu(), {"bcx": nd.NDArray(bcx), "w": nd.NDArray(w)})
    _close(exe.forward()[0].asnumpy(), _plain(bcx, w), 1e-5)
    with pytest.raises(mx.base.MXNetError):
        G.gated_short_conv(bcx[..., :-1], w)
    with pytest.raises(mx.base.MXNetError):
        G.gated_short_conv(bcx[:, :2], w)  # three taps on two tokens


def test_the_backward_keeps_bcx_alone():
    """What the forward hands the backward: the operands, nothing computed."""
    bcx, w, _ = _conv_inputs("bfloat16")
    _, res = G._gated_conv_fwd(bcx, w)
    assert len(res) == 2 and res[0] is bcx and res[1] is w


@pytest.mark.parametrize("at", [0, 5, 11])
def test_causality_a_token_moves_no_earlier_output_and_no_other_sequence(at):
    bcx, w, _ = _conv_inputs("float32")
    moved = bcx.at[1, at].add(1.0)
    a, b = G.gated_short_conv(bcx, w), G.gated_short_conv(moved, w)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))       # the other sequence
    assert np.array_equal(np.asarray(a[1, :at]), np.asarray(b[1, :at]))  # the past
    later = np.abs(np.asarray(a[1, at:]) - np.asarray(b[1, at:])).sum(-1)
    assert later[0] > 0  # the token itself, and at most K - 1 after it
    assert not later[3:].any()


def test_gated_conv_counts_one_traced_call_by_branch():
    bcx, w, _ = _conv_inputs("float32")
    before = telemetry.gated_conv_branches().get("xla", 0)
    f = jax.jit(G.gated_short_conv)
    f(bcx, w), f(bcx, w), f(bcx, w)
    assert telemetry.gated_conv_branches()["xla"] == before + 1
    assert 'mxt_gated_conv_total{branch="xla"}' in telemetry.render_prometheus()


# -- the router and the shares -----------------------------------------------------------
def _arch(held=(0, 8), **over):
    c = dict(hidden_size=32, num_hidden_layers=3, num_dense_layers=1,
             layer_types=["conv", "full_attention", "conv"], conv_L_cache=3,
             conv_bias=False, num_attention_heads=4, num_key_value_heads=2,
             norm_eps=1e-5, norm_topk_prob=True, use_expert_bias=True,
             intermediate_size=48, moe_intermediate_size=24, num_experts_per_tok=3,
             routed_scaling_factor=1, vocab_size=50, num_experts=held[1],
             rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
             experts_held=list(held), published={"num_experts": 8})
    c.update(over)
    return c


def _moe_params(a, held, seed=40):
    h, i = a["hidden_size"], a["moe_intermediate_size"]
    key = jax.random.PRNGKey(seed)
    shapes = {"router.w": (a["router_width"], h), "router.bias": (a["router_width"],),
              "experts.gate": (8, h, i), "experts.up": (8, h, i),
              "experts.down": (8, i, h)}
    p = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, j), s, F32)
         for j, (n, s) in enumerate(shapes.items())}
    first, count = held
    return {n: (v[first:first + count] if n.startswith("experts.") else v)
            for n, v in p.items()}


def _run_moe(p, x, a, held):
    return M.moe_ffn(x, p["router.w"], p["router.bias"], p["experts.gate"],
                     p["experts.up"], p["experts.down"],
                     top_k=a["num_experts_per_tok"], n_routed=a["router_width"],
                     experts_held=held, scoring="sigmoid", sum_epsilon=ref.SUM_EPSILON)


def test_the_router_adds_the_publishers_epsilon_and_the_default_stays():
    a = ref.arch(_arch())
    p = _moe_params(a, (0, 8))
    x = jax.random.normal(jax.random.PRNGKey(41), (40, 32), F32)
    idx, weights = M.route(x, p["router.w"], p["router.bias"], 3, 1.0, "sigmoid", 1e-6)
    dense = np.zeros((40, 8), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(weights), axis=1)
    _close(dense, ref.routing(p, x, a), 1e-6)
    # the epsilon is in the sum: at one float32 can show, a token's weights add
    # up to s / (s + epsilon), not to 1
    _, loose = M.route(x, p["router.w"], p["router.bias"], 3, 1.0, "sigmoid", 0.5)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ p["router.w"].T)),
                           np.asarray(idx), axis=1).sum(-1)
    _close(np.asarray(loose).sum(-1), s / (s + 0.5), 1e-6)
    # DeepSeek-V3's 1e-20 is what every other caller gets
    _, default = M.route(x, p["router.w"], p["router.bias"], 3, 1.0)
    _close(np.asarray(default).sum(-1), np.ones(40), 2e-7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_expert_layer_through_the_kernels_equals_ragged_dot(dtype, grouped_matmul_kernels):
    """``moe_ffn`` as the model calls it (its own bound on the rows, no shared
    expert, the publisher's epsilon) at whole 128-lane widths: value and
    gradients through the grouped-matmul kernels (interpret mode) against
    ``ragged_dot``'s, within the operands' rounding."""
    from mxnet_tpu import telemetry

    a = ref.arch(_arch(hidden_size=128, moe_intermediate_size=256))
    p = {n: v.astype(dtype) for n, v in _moe_params(a, (0, 8)).items()}
    x = jax.random.normal(jax.random.PRNGKey(43), (512, 128), F32).astype(dtype)
    ct = jax.random.normal(jax.random.PRNGKey(44), (512, 128), F32)

    def run():
        return jax.value_and_grad(lambda pp, xx: jnp.sum(
            _run_moe(pp, xx, a, (0, 8))[0].astype(F32) * ct), (0, 1))(p, x)

    want = run()
    grouped_matmul_kernels()
    got = run()
    assert telemetry.grouped_matmul_branches()["dw"]["kernel"] >= 3
    assert int(_run_moe(p, x, a, (0, 8))[2]) == 0  # no slot lost
    tol = 1e-5 if dtype == "float32" else 2e-2
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        if np.any(np.asarray(w, np.float32)):
            _close(g, w, tol)


@pytest.mark.parametrize("chips", [4, 2])
def test_shares_of_the_experts_add_up_to_the_uncut_layer(chips):
    """``chips`` chips hold 8 / chips of eight experts each, as the cell's four
    hold 16 of 64. Their parts of the result (the model has no shared expert;
    the router, which all compute alike, is counted once: it is the same
    routing in every part) add up to the plain reference's result for the
    whole layer, and every slot is computed once."""
    whole, each = (0, 8), 8 // chips
    a = ref.arch(_arch(whole))
    p = _moe_params(a, whole)
    x = jax.random.normal(jax.random.PRNGKey(60), (40, 32), F32)
    want = ref.moe(p, x, a)
    total, slots = jnp.zeros_like(want), 0
    for chip in range(chips):
        held = (each * chip, each)
        part = _moe_params(a, held)  # the same seeded layer, this chip's experts
        y, load, lost, _ = _run_moe(part, x, a, held)
        # the reference given the same share gives the same part
        _close(y, ref.moe(part, x, ref.arch(_arch(held))), 1e-5)
        total, slots = total + y, slots + int(load.sum())
        assert int(lost) == 0
    _close(total, want, 1e-5)
    assert slots == 40 * a["num_experts_per_tok"]


# -- the model's blocks ----------------------------------------------------------
def _tiny_model(dtype="float32", seed=5, held=(4, 4)):
    config = _arch(held, family="lfm2_moe", dtype=dtype,
                   assumed={"router_trained": False, "conv_tap_std": 0.3})
    params = ref.init(config, seed)
    net = zoo.Lfm2MoeModel(dict(config, num_experts=8), experts_held=held)
    net.initialize()
    net.cast(dtype)
    model = loader.load_module("models", "lfm2_moe")
    names = model.leaf_names(config, net.prefix)
    values = {leaf: params[leaf].astype(net.collect_params()[name].dtype)
              for leaf, name in names.items()}
    model.common.set_parameters(net.collect_params(), names, values)
    return config, params, net, names


def test_model_forward_and_every_leafs_gradient_against_the_reference():
    """float32 on both sides: the zoo's decoder (both mixers, a dense and two
    sparse layers, the head tied to the embedding) and the plain reference
    give the same loss and the same gradient of every leaf; the routers' and
    the selection bias's are zero on a strict share."""
    from mxnet_tpu import autograd as ag

    config, params, net, names = _tiny_model()
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 25), 0, 50)
    x, y = ids[:, :-1].astype(F32), ids[:, 1:].astype(F32)
    with jax.default_matmul_precision("highest"):
        want, grads = ref.value_and_grad(config, params, x, y)
        logits = ref.logits(config, params, x)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    net_params = net.collect_params()
    assert not any(name.endswith("head_weight") for name in net_params)  # tied
    with ag.record():
        scores = net(nd.NDArray(x))
        loss = loss_fn(scores, nd.NDArray(y)).mean()
    loss.backward()
    _close(scores.asnumpy(), logits, 1e-5)
    _close(loss.asnumpy(), want, 1e-5)
    assert set(names) == set(grads)
    for leaf, name in names.items():
        if leaf.endswith("router.bias"):  # a buffer: the program keeps no gradient
            assert net_params[name].grad_req == "null" and not np.asarray(grads[leaf]).any()
            continue
        got = net_params[name].grad().asnumpy()
        if leaf.endswith("router.w"):
            assert not got.any() and not np.asarray(grads[leaf]).any(), leaf
        else:
            _close(got, grads[leaf], 5e-4)
    counts = zoo.publish_moe_counts(net)
    assert len(counts["expert_load"]) == 2 and counts["slots_lost"] == 0
    assert telemetry.moe_counts() == counts


def test_the_config_is_checked_and_a_whole_model_trains_its_router():
    for key, bad in (("conv_bias", True), ("norm_topk_prob", False),
                     ("tie_embedding", False),
                     ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
                     ("layer_types", ["conv", "window", "conv"]),
                     ("layer_types", ["conv", "conv"])):
        with pytest.raises(mx.base.MXNetError):
            zoo.Lfm2MoeModel(dict(_arch(), **{key: bad}))
    whole = zoo.Lfm2MoeModel(_arch())
    assert [type(b.mixer).__name__ for b in whole.blocks] == [
        "ShortConv", "GroupedQueryAttention", "ShortConv"]
    assert [type(b.ffn).__name__ for b in whole.blocks] == [
        "SwiGLU", "DeepseekMoE", "DeepseekMoE"]
    assert len(whole.moe_layers()) == 2
    assert whole.blocks[1].ffn._static["router_gradient"] is True
    assert whole.blocks[1].ffn._static["sum_epsilon"] == 1e-6
    share = zoo.Lfm2MoeModel(dict(_arch((4, 4)), num_experts=8), experts_held=(4, 4))
    assert share.blocks[1].ffn._static["router_gradient"] is False
    share.initialize()
    share.cast("bfloat16")
    assert str(share.blocks[1].ffn.router_bias.dtype) == "float32"
    assert str(share.blocks[0].mixer.conv_weight.dtype) == "bfloat16"


def test_the_step_carries_the_scopes_of_both_mixers():
    """Device time is attributed by the names in the compiled step: the
    convolution mixer's two projections and both halves of the op under
    ``short_conv``, attention's under ``gqa``, the expert layer's under
    ``moe``."""
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
    for want in ("short_conv/in_proj", "short_conv/gated_conv",
                 "short_conv/gated_conv_bwd", "short_conv/out_proj", "gqa/q_proj",
                 "gqa/attention", "gqa/attention_bwd", "moe/router", "ffn/gate"):
        assert want in names, want
    assert phases["gated_conv_bwd"] == {"backward"}
    assert "forward" in phases["gated_conv"]


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
              for k, v in want.items() if k.rpartition(".")[0].endswith("router")}
    out = {"cell": c, "config": config, "program": compare.training_numbers(first, plain),
           "later": later, "frozen": frozen, "zero_counts": prog.zero_counts(),
           "published": prog.after_window(), "entry": prog.entry}
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
    # routers and selection biases are frozen, in the reference too
    assert len(first_steps["frozen"]) == 2 * 2 and not any(first_steps["frozen"].values())


def test_the_adapter_publishes_the_slots_the_readers_take(first_steps):
    slots = first_steps["published"]["expert_slots"]
    assert len(slots) == 2 and all(len(r) == 4 and sum(r) > 0 for r in slots)
    reader = loader.load_module("layer_metrics", "expert_load_max_over_mean.train")
    assert reader.read({"program": first_steps["published"]}) >= 1.0


def test_the_fp8_control_fails_a_limit_the_program_meets(first_steps):
    def value(numbers, name):
        return next(v for n, v, _ in numbers if n == name)

    sound = value(first_steps["program"], "grad_rel_diff")
    control = value(first_steps["fp8"], "grad_rel_diff")
    assert control > 3 * sound, (sound, control)
    limits = dict(first_steps["cell"]["limits"], grad_rel_diff=(sound * control) ** 0.5)
    assert all(r["ok"] for r in compare.judge(first_steps["program"], limits))
    assert not all(r["ok"] for r in compare.judge(first_steps["fp8"], limits))
