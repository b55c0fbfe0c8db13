"""Keye-VL-2.0's decoder at a tiny size on the CPU, against the benchmark's
plain reference (``benchmark/references/keye_vl2.py``: float32, "highest",
scores materialised, its own ``lax.top_k``): the lightning indexer's exact
selection (the XLA path and the kernel in interpret mode, as a SET against
``lax.top_k`` in float32), the softmax router, the share test, the counters,
the scopes, and the whole model's first steps through ``ShardedTrainStep``
against the benchmark's follower, with the fp8 control.

Tolerances: float32 throughout but for the model-level run in bfloat16, which
is held as the benchmark holds a cell. Selection tests use operands whose
scores are exact in float32 (small integers, weights powers of two), so that
the order of a sum cannot move a key across the threshold and ties abound.
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
from mxnet_tpu.gluon.model_zoo import keye as zoo
from mxnet_tpu.ops import indexer as X
from mxnet_tpu.ops import moe as M

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import compare, loader, train_reference  # noqa: E402

ref = loader.load_module("references", "keye_vl2")
F32 = jnp.float32
CELL = "keye_vl2_a3b_train_s8192"


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-30)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * scale, \
        (np.max(np.abs(got - want)) / scale, tol)


# -- the selection ---------------------------------------------------------------
def _index_inputs(seed, B, T, Hi, Di, exact):
    rng = np.random.RandomState(seed)
    if exact:  # every score a small dyadic number: exact in any order
        q = rng.randint(-2, 3, (B, T, Hi, Di))
        k = rng.randint(-1, 2, (B, T, Di))
        w = rng.choice([-0.5, 0.25, 0.5, 1.0], (B, T, Hi))
    else:
        q, k, w = rng.randn(B, T, Hi, Di), rng.randn(B, T, Di), rng.randn(B, T, Hi)
    return tuple(jnp.asarray(a, F32) for a in (q, k, w))


def _top_k_sets(q, k, w, topk):
    """The truth: each row's ``lax.top_k`` over its earlier keys' float32
    scores, as a 0/1 matrix."""
    B, T = q.shape[:2]
    want = np.zeros((B, T, T), np.int8)
    for b in range(B):
        scores = np.asarray(X._scores(jnp.moveaxis(q[b], 1, 0), k[b], w[b].T[:, :, None]))
        for t in range(T):
            if t + 1 <= topk:
                want[b, t, :t + 1] = 1
            else:
                _, idx = jax.lax.top_k(jnp.asarray(scores[t, :t + 1]), topk)
                want[b, t, np.asarray(idx)] = 1
    return want


_SELECT_CASES = {
    # (B, T, Hi, Di, topk, exact scores, kernel block_q, block_k)
    "ties_at_the_threshold": (2, 96, 3, 8, 16, True, 32, 32),
    "t_not_a_multiple_of_the_tile": (1, 100, 2, 8, 24, True, 32, 64),
    "random_scores": (2, 80, 3, 8, 16, False, 16, 16),
    "topk_off_the_block_boundary": (1, 72, 2, 8, 20, True, 16, 8),
    "every_row_has_fewer_than_topk": (1, 40, 2, 8, 64, True, 32, 32),
    "one_block_holds_the_sequence": (1, 48, 2, 8, 8, True, 64, 64),
}


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("case", sorted(_SELECT_CASES))
def test_selection_is_lax_top_k_as_a_set(case, path):
    B, T, Hi, Di, topk, exact, bq, bk = _SELECT_CASES[case]
    q, k, w = _index_inputs(3, B, T, Hi, Di, exact)
    want = _top_k_sets(q, k, w, topk)
    if case == "ties_at_the_threshold":  # the case does hold such rows
        scores = np.asarray(X._scores(jnp.moveaxis(q[0], 1, 0), k[0], w[0].T[:, :, None]))
        rows = [np.sort(scores[t, :t + 1])[::-1] for t in range(topk, T)]
        assert sum(r[topk - 1] == r[topk] for r in rows) > T // 4
    if path == "xla":
        mask, selected, searched = X.lightning_indexer(q, k, w, topk=topk)
        assert mask.dtype == jnp.int8 and mask.shape == (B, T, T)
        assert int(selected) == int(want.sum())
        assert int(searched) == B * max(T - topk, 0)
    elif T <= topk:  # no row is searched: the op never builds the kernel
        mask = X.lightning_indexer(q, k, w, topk=topk)[0]
    else:
        mask = X._select_pallas(jnp.transpose(q, (0, 2, 1, 3)), k,
                                jnp.transpose(w, (0, 2, 1)), topk, True,
                                block_q=bq, block_k=bk)
    assert np.array_equal(np.asarray(mask), want)
    # every searched row holds exactly topk keys, every other all it can see
    assert np.array_equal(want.sum(-1)[0], np.minimum(np.arange(T) + 1, topk))


def test_selection_keeps_operands_in_their_type_and_carries_no_gradient():
    q, k, w = (a.astype(jnp.bfloat16) for a in _index_inputs(4, 1, 64, 2, 8, False))
    jaxpr = jax.make_jaxpr(X._scores)(jnp.moveaxis(q[0], 1, 0), k[0], w[0].T[:, :, None])
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2  # one a head: bf16 operands, float32 accumulation
    for e in dots:
        assert [str(v.aval.dtype) for v in e.invars] == ["bfloat16"] * 2
        assert str(e.outvars[0].aval.dtype) == "float32"
    grads = jax.grad(lambda q_, k_, w_: jnp.sum(
        X.lightning_indexer(q_, k_, w_, topk=16)[0].astype(F32)), argnums=(0, 1, 2))(
            *(a.astype(F32) for a in (q, k, w)))
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)
    with pytest.raises(mx.base.MXNetError):
        X.lightning_indexer(q, k[:, :32], w, topk=16)


# -- the softmax router ----------------------------------------------------------
def _arch(held=(0, 16), **over):
    c = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, rope_theta=1e7, rms_norm_eps=1e-6,
             moe_intermediate_size=24, num_experts_per_tok=3, vocab_size=50,
             num_experts=held[1], experts_held=list(held),
             published={"num_experts": 16},
             sa_config={"indexer_num_heads": 2, "indexer_head_dim": 8, "topk": 8})
    c.update(over)
    return c


def _moe_params(a, held, seed=40):
    h, i = a["hidden_size"], a["moe_intermediate_size"]
    key = jax.random.PRNGKey(seed)
    shapes = {"router.w": (a["router_width"], h), "experts.gate": (16, h, i),
              "experts.up": (16, h, i), "experts.down": (16, i, h)}
    p = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, j), s, F32)
         for j, (n, s) in enumerate(shapes.items())}
    first, count = held
    return {n: (v[first:first + count] if n.startswith("experts.") else v)
            for n, v in p.items()}


def _run_moe(p, x, a, held):
    return M.moe_ffn(x, p["router.w"], None, p["experts.gate"], p["experts.up"],
                     p["experts.down"], top_k=a["num_experts_per_tok"],
                     n_routed=a["router_width"], experts_held=held, scoring="softmax")


def test_softmax_router_against_the_reference_and_sigmoid_stays_the_default():
    a = ref.arch(_arch())
    p = _moe_params(a, (0, 16))
    x = jax.random.normal(jax.random.PRNGKey(41), (40, 32), F32)
    idx, weights = M.route(x, p["router.w"], None, 3, 1.0, "softmax")
    dense = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(weights), axis=1)
    _close(dense, ref.routing(p, x, a), 1e-5)
    _close(np.asarray(weights).sum(-1), np.ones(40), 1e-6)  # renormalised
    # sigmoid with a bias is what it was: the default's trace names no softmax
    bias = jnp.zeros((16,), F32)
    default = str(jax.make_jaxpr(lambda x_: M.route(x_, p["router.w"], bias, 3, 2.0))(x))
    assert "logistic" in default and "exp" not in default.replace("expand", "")
    with pytest.raises(ValueError):
        M.route(x, p["router.w"], None, 3, 1.0, "argmax")

    def loss(fn):
        return jax.grad(lambda x_, p_: jnp.sum(jnp.square(fn(p_, x_))), argnums=(0, 1))(x, p)

    got = loss(lambda p_, x_: _run_moe(p_, x_, a, (0, 16))[0])
    want = loss(lambda p_, x_: ref.moe(p_, x_, a))
    _close(got[0], want[0], 2e-4)
    for name in want[1]:
        _close(got[1][name], want[1][name], 2e-4)


def test_shares_of_the_experts_add_up_to_the_uncut_layer_under_the_softmax_router():
    """Eight chips hold two of sixteen experts each. Their parts of the result
    (the model has no shared expert) add up to the plain reference's result
    for the whole layer, and every slot is computed once."""
    whole = (0, 16)
    a = ref.arch(_arch(whole))
    p = _moe_params(a, whole)
    x = jax.random.normal(jax.random.PRNGKey(60), (40, 32), F32)
    want = ref.moe(p, x, a)
    total, slots = jnp.zeros_like(want), 0
    for chip in range(8):
        held = (2 * chip, 2)
        part = {k: (v[2 * chip:2 * chip + 2] if k.startswith("experts.") else v)
                for k, v in p.items()}
        y, load, lost, _ = _run_moe(part, x, a, held)
        # the reference given the same share gives the same part
        _close(y, ref.moe(part, x, ref.arch(_arch(held))), 1e-5)
        total, slots = total + y, slots + int(load.sum())
        assert int(lost) == 0
    _close(total, want, 1e-5)
    assert slots == 40 * a["num_experts_per_tok"]


# -- the model's blocks ----------------------------------------------------------
def _tiny_model(dtype="float32", seed=5, held=(4, 4)):
    config = _arch(held, family="keye_vl2", dtype=dtype,
                   assumed={"router_trained": False, "indexer_trained": False})
    params = ref.init(config, seed)
    net = zoo.KeyeVL2Model(dict(config, num_experts=16), experts_held=held)
    net.initialize()
    net.cast(dtype)
    model = loader.load_module("models", "keye_vl2")
    names = model.leaf_names(config, net.prefix)
    model.common.set_parameters(net.collect_params(), names, params)
    return config, params, net, names


def test_model_forward_and_every_leafs_gradient_against_the_reference():
    """float32 on both sides: the zoo's decoder and the plain reference give
    the same loss and the same gradient of every leaf, the frozen ones' zero
    (no gradient passes through the selection)."""
    from mxnet_tpu import autograd as ag

    config, params, net, names = _tiny_model()
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 25), 0, 50)
    x, y = ids[:, :-1].astype(F32), ids[:, 1:].astype(F32)
    # a strict share (4 of 16 experts): no gradient through the routing weights,
    # in the zoo's block as in the reference under ``router_trained: false``
    with jax.default_matmul_precision("highest"):
        want, grads = ref.value_and_grad(config, params, x, y)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    net_params = net.collect_params()
    with ag.record():
        loss = loss_fn(net(nd.NDArray(x)), nd.NDArray(y)).mean()
    loss.backward()
    _close(loss.asnumpy(), want, 1e-5)
    assert set(names) == set(grads)
    for leaf, name in names.items():
        got = net_params[name].grad().asnumpy()
        if leaf.split(".")[-2].startswith("index_") or leaf.endswith("router.w"):
            assert not got.any() and not np.asarray(grads[leaf]).any(), leaf
        else:
            _close(got, grads[leaf], 5e-4)
    # the selection engaged: 24 positions under a top-8
    counts = zoo.publish_selection_counts(net)
    per_layer = 2 * sum(min(t + 1, 8) for t in range(24))
    assert counts == {"selected_pairs": [per_layer] * 2, "rows_searched": [2 * 16] * 2}
    assert telemetry.selection_counts() == counts


def test_counts_keep_their_type_under_a_cast_and_the_config_is_checked():
    _, _, net, _ = _tiny_model("bfloat16")
    idx = net.blocks[0].indexer
    assert str(idx.selected_pairs.dtype) == "int64" == str(idx.rows_searched.dtype)
    assert str(idx.q_proj.weight.dtype) == "bfloat16"
    assert not hasattr(net.blocks[0].ffn, "router_bias")
    for key, bad in (("norm_topk_prob", False), ("mlp_only_layers", [0]),
                     ("rope_scaling", {"rope_type": "yarn"})):
        with pytest.raises(mx.base.MXNetError):
            zoo.KeyeVL2Model(dict(_arch(), num_experts=16, **{key: bad}))
    with pytest.raises(mx.base.MXNetError):
        zoo.GroupedQueryAttention(32, 6, 4, 8)


def test_the_step_carries_the_scopes_of_attention_and_indexer(monkeypatch):
    """Device time is attributed by the names in the compiled step: the
    projections, the head norms, rotary positions and both attention passes
    under ``gqa``; the indexer's projections and its kernel (here in
    interpret mode), which computes the scores and selects, under
    ``indexer``, in the forward pass alone."""
    from mxnet_tpu import profiler_trace

    kernel = X._select_pallas
    monkeypatch.setattr(X, "on_tpu", lambda: True)
    monkeypatch.setattr(X, "_select_pallas",
                        lambda q, k, w, topk, interpret: kernel(q, k, w, topk, True))
    _, _, net, _ = _tiny_model()
    x = jnp.zeros((1, 24), F32)
    from mxnet_tpu import parallel

    mesh = parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                     "adam", {"learning_rate": 1e-3}, mesh=mesh)
    step(nd.NDArray(x), nd.NDArray(x))
    names = set()
    common = loader.load_module("models", "gluon_common")
    prog = common.TrainProgram(step, {}, None, None, None, 1, {})
    prog.record_next_step()
    step(nd.NDArray(x), nd.NDArray(x))
    jitted, (args, kwargs) = prog._recorded
    text = jitted.lower(*args, **kwargs).as_text(debug_info=True)
    for name in re.findall(r'loc\("([^"]+)"', text):
        scopes = profiler_trace.scopes_of(name)
        names.update("/".join(scopes[i:j]) for i in range(len(scopes))
                     for j in range(i + 1, len(scopes) + 1))
    for want in ("gqa/q_proj", "gqa/kv_proj", "gqa/qk_norm", "gqa/rope", "gqa/attention",
                 "gqa/attention_bwd", "gqa/o_proj", "indexer/q_proj", "indexer/k_proj",
                 "indexer/weights", "indexer/rope", "indexer/select", "moe/router"):
        assert want in names, want
    backward = [n for n in re.findall(r'loc\("([^"]+)"', text)
                if "indexer" in profiler_trace.scopes_of(n)
                and profiler_trace.phase_of("fusion", n) == "backward"]
    assert not backward, backward[:3]


# -- the whole model through ShardedTrainStep, against the follower -------------------
def _first_steps(faults=()):
    c = loader.resolve_cell(CELL, rehearse=True)
    config = loader.load_json("configs", c["config"])
    assert config["assumed"]["router_trained"] is config["assumed"]["indexer_trained"] is False
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
              for k, v in want.items() if ".index_" in k or k.endswith("router.w")}
    out = {"cell": c, "config": config, "traffic": traffic,
           "program": compare.training_numbers(first, plain), "later": later,
           "frozen": frozen, "zero_counts": prog.zero_counts(),
           "published": prog.after_window(), "entry": prog.entry, "steps": prog.steps}
    for fault in faults:
        low = train_reference.first_steps(ref, config, opt, params, pool, quant=fault,
                                          keep_gradient=True)
        rel, norms = train_reference.gradient_distance(low.pop("first_gradient"), want)
        out[fault] = compare.training_numbers(
            low, dict(plain, grad_rel_diff=rel, grad_diff_norms=norms))
        with jax.default_matmul_precision("highest"):
            sound, low_sel = (ref.first_selection(config, params, pool[0][0], q)
                              for q in (None, fault))
        out[fault + "_selection_mismatch"] = float(
            jnp.sum(jnp.logical_and(low_sel, ~sound)) / jnp.sum(low_sel))
    return out


@pytest.fixture(scope="module")
def first_steps():
    return _first_steps(faults=("fp8",))


def test_model_trains_through_sharded_step_like_the_follower(first_steps):
    rows = compare.judge(first_steps["program"], first_steps["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert first_steps["later"] == 0  # nothing compiled after the first call
    assert getattr(first_steps["entry"], "fused", True)
    assert first_steps["zero_counts"] == {
        "routed_slots_lost": 0, "selected_pairs_off_the_shapes_count": 0,
        "selection_mismatch_over_its_limit": 0.0}
    # routers and indexers are frozen, in the reference too
    assert len(first_steps["frozen"]) == 2 * 6 and not any(first_steps["frozen"].values())


def test_the_adapter_publishes_the_counts_the_readers_take(first_steps):
    got, t = first_steps["published"], first_steps["traffic"]["sequence"]
    topk = first_steps["config"]["sa_config"]["topk"]
    a_step = first_steps["traffic"]["batch"] * first_steps["config"]["num_hidden_layers"]
    assert got["steps_counted"] == first_steps["steps"] == 5
    assert got["selected_pairs"] == 5 * a_step * sum(min(i + 1, topk) for i in range(t))
    assert got["rows_searched"] == 5 * a_step * (t - topk)
    assert got["causal_pairs"] == 5 * a_step * t * (t + 1) // 2
    assert 0.0 <= got["selection_mismatch"] <= got["selection_mismatch_limit"]
    assert len(got["expert_slots"]) == 2 and all(sum(r) > 0 for r in got["expert_slots"])
    kept = loader.load_module("layer_metrics", "attention_pairs_kept_share.train")
    share = kept.read({"program": got})
    assert abs(share - 100.0 * got["selected_pairs"] / got["causal_pairs"]) < 1e-9
    assert 50.0 < share < 100.0  # 32 positions under a top-16
    assert kept.read({"program": {}}) is None and kept.read({}) is None


def test_the_fp8_control_fails_a_limit_the_program_meets(first_steps):
    def value(numbers, name):
        return next(v for n, v, _ in numbers if n == name)

    sound = value(first_steps["program"], "grad_rel_diff")
    control = value(first_steps["fp8"], "grad_rel_diff")
    assert control > 3 * sound, (sound, control)
    limits = dict(first_steps["cell"]["limits"], grad_rel_diff=(sound * control) ** 0.5)
    assert all(r["ok"] for r in compare.judge(first_steps["program"], limits))
    assert not all(r["ok"] for r in compare.judge(first_steps["fp8"], limits))
    # a selection made from fp8 index scores strays further than bfloat16's
    assert first_steps["fp8_selection_mismatch"] > first_steps["published"]["selection_mismatch"]
