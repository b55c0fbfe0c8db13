"""Unified 4D (dp×tp×pp×ep) parallelism acceptance: pipeline stages
and experts are SHARDINGS inside ShardedTrainStep's single donated
launch (parallel/unified.py) — the microbatched pipeline schedule runs
as masked ticks inside the program and Switch-MoE routing dispatches
with capacity-factor einsums, so ``launches_per_step`` stays 1 while
the math matches the serial composition of the stages, the dense
per-token routing, and the same math split into launches BIT-exactly."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.sharded import sharding_rule
from mxnet_tpu.test_utils import with_seed


def _mesh4d():
    return parallel.make_mesh((2, 1, 2, 2), ("dp", "tp", "pp", "ep"))


def _block(**kw):
    cfg = dict(num_stages=2, num_experts=2, in_units=8, hidden=8,
               expert_hidden=16, num_classes=8, num_microbatches=4)
    cfg.update(kw)
    net = parallel.PipelineMoEBlock(**cfg)
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# references, in plain jax.numpy: the stages applied one after another,
# every expert applied densely to every token and masked by the routing
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(2, 3))
def _serial_reference(vals, x, num_microbatches, capacity_factor):
    """``(logits, expert_load, drops)`` of the block's math with no
    schedule, no dispatch tensors and no mesh: stage after stage over
    the whole batch; a token keeps its argmax expert's output, scaled by
    its gate, when fewer than ``capacity`` tokens of its microbatch
    chose that expert before it, and passes through unchanged if not."""
    s_stages, d, e_experts = vals["router_w"].shape
    m = num_microbatches
    mb = x.shape[0] // m
    capacity = parallel.moe_capacity(mb, e_experts, capacity_factor)
    h = x @ vals["w_in"] + vals["b_in"]
    load = jnp.zeros((e_experts,))
    for s in range(s_stages):
        hd = jnp.tanh(h @ vals["stage_w"][s] + vals["stage_b"][s])
        gates = jax.nn.softmax(hd @ vals["router_w"][s], axis=-1)
        chosen = jax.nn.one_hot(jnp.argmax(gates, axis=-1), e_experts)
        before = jnp.cumsum(chosen.reshape(m, mb, e_experts), axis=1) \
            .reshape(-1, e_experts) - 1.0
        kept = chosen * (before < capacity)                  # (B, E)
        dense = jnp.stack([
            jax.nn.relu(hd @ vals["expert_w1"][s, e]
                        + vals["expert_b1"][s, e])
            @ vals["expert_w2"][s, e] + vals["expert_b2"][s, e]
            for e in range(e_experts)], axis=1)              # (B, E, D)
        h = hd + (dense * (kept * gates)[..., None]).sum(axis=1)
        load = load + kept.sum(axis=0)
    logits = h @ vals["w_out"] + vals["b_out"]
    return logits, load, s_stages * x.shape[0] - load.sum()


def _placed(mesh_shape, **kw):
    """A block with its parameters placed as its rules say on a
    dp x tp x pp x ep mesh of ``mesh_shape``: ``(mesh, net, vals)``."""
    mesh = parallel.make_mesh(mesh_shape, ("dp", "tp", "pp", "ep"))
    net = _block(**kw).rebind_mesh(mesh)
    # weights wide enough that eight tanh stages still carry a signal
    # and the experts' share of it stands clear of the tolerances
    rng = np.random.RandomState(11)
    for name, p in net._p.items():
        half = 0.1 if name.startswith(("b_", "stage_b", "expert_b")) else 0.5
        p.set_data(nd.array(rng.uniform(-half, half, p.shape)
                            .astype(np.float32)))
    parallel.shard_params(net.collect_params(), mesh,
                          rules=net.sharding_rules(mesh))
    return mesh, net, net.param_values()


def _on_mesh(mesh, num_microbatches, capacity_factor):
    def fwd(vals, x):
        return parallel.pipeline_moe_forward(
            vals, x, num_microbatches, capacity_factor, mesh=mesh,
            dp="dp", pp="pp", ep="ep")
    return fwd


def _batch(seed, n=16, width=8):
    return jnp.asarray(np.random.RandomState(seed)
                       .uniform(-1, 1, (n, width)).astype(np.float32))


def _no_drops(num_experts):
    """The capacity factor that gives every expert room for a whole
    microbatch, so that nothing is dropped."""
    return float(num_experts)


@pytest.mark.parametrize("S,M", [(2, 2), (4, 8), (8, 8)])
def test_pipeline_forward_parity(S, M):
    """The microbatched schedule over a pp axis of S devices computes
    the serial composition of the S stages."""
    mesh, _, vals = _placed((-1, 1, S, 1), num_stages=S,
                            num_microbatches=M)
    x = _batch(1)
    logits, load, drops = jax.jit(_on_mesh(mesh, M, _no_drops(2)))(vals, x)
    ref, ref_load, _ = _serial_reference(vals, x, M, _no_drops(2))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(ref_load))
    assert float(drops) == 0.0 and float(load.sum()) == S * 16


def _grads(fwd, vals, x):
    def loss(v):
        return (fwd(v, x)[0] ** 2).sum()
    return jax.jit(jax.grad(loss))(vals)


def test_pipeline_grad_parity():
    """The backward through the unrolled ticks (the reverse roll over
    pp) gives the serial composition's gradients, stage by stage."""
    S, M = 4, 8
    mesh, _, vals = _placed((1, 1, S, 2), num_stages=S, num_microbatches=M)
    x = _batch(3)
    got = _grads(_on_mesh(mesh, M, _no_drops(2)), vals, x)
    want = _grads(lambda v, xb: _serial_reference(v, xb, M, _no_drops(2)),
                  vals, x)
    for name in ("w_in", "stage_w", "stage_b", "w_out"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   rtol=1e-4, atol=1e-6, err_msg=name)
        assert np.abs(np.asarray(want[name])).max() > 0, name


def _train(net, mesh, steps, optimizer="sgd", lr=0.5, **kw):
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
        {"learning_rate": lr}, mesh=mesh, rules=net.sharding_rules(mesh),
        **kw)
    rng = np.random.RandomState(5)
    x = nd.array(rng.uniform(-1, 1, (16, 8)).astype(np.float32))
    y = nd.array(rng.randint(0, 8, (16,)).astype(np.float32))
    losses = [step(x, y)]  # builds the program and places its inputs
    n0 = profiler.launch_count()
    losses += [step(x, y) for _ in range(steps - 1)]
    assert profiler.launch_count() - n0 == steps - 1  # 1 program a step
    return [float(l.asscalar()) for l in losses]


def test_pipeline_trains():
    """SGD through four pipeline stages on a pp axis of four."""
    mesh, net, _ = _placed((1, 1, 4, 2), num_stages=4, num_microbatches=4)
    losses = _train(net, mesh, 30)
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.75 * losses[0], losses


def test_pipeline_validation():
    net = _block()
    vals = net.param_values()
    # 5 rows do not divide into the default of one microbatch a stage
    with pytest.raises(mx.MXNetError, match="not divisible"):
        parallel.pipeline_moe_forward(vals, jnp.zeros((5, 8)), None, 1.25)
    # a stage count that is a MULTIPLE of the pp extent is refused (it
    # would put two stages on a device): one stage a device, or pp = 1
    mesh = _mesh4d()
    with pytest.raises(mx.MXNetError, match="does not match 4 pipeline"):
        _block(num_stages=4).rebind_mesh(mesh)
    _block(num_stages=4).rebind_mesh(
        parallel.make_mesh((8, 1, 1, 1), ("dp", "tp", "pp", "ep")))
    # rules need a mesh to name axes of
    with pytest.raises(mx.MXNetError, match="needs a mesh"):
        net.sharding_rules()
    # a mesh with no pp axis: the role resolves to None, no rule names
    # it, and the same program runs with that parallelism off
    flat = parallel.make_mesh((4, 2), ("data", "model"))
    assert parallel.resolve_mesh_axis(flat, "pp") is None
    assert net.sharding_rules(flat) == []
    x = _batch(7)
    off = jax.jit(lambda v, xb: parallel.pipeline_moe_forward(
        v, xb, 4, 1.25, mesh=flat, dp="data"))(vals, x)
    on = parallel.pipeline_moe_forward(vals, x, 4, 1.25)
    np.testing.assert_allclose(np.asarray(off[0]), np.asarray(on[0]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("E", [2, 4])
def test_moe_matches_dense_routing(E):
    """With room for every token, each is processed by its argmax
    expert and scaled by its gate: the dispatch and combine einsums over
    an ep axis of E devices equal the dense per-token computation."""
    mesh, _, vals = _placed((-1, 1, 2, E), num_experts=E)
    x = _batch(1)
    logits, load, drops = jax.jit(_on_mesh(mesh, 4, _no_drops(E)))(vals, x)
    ref, ref_load, _ = _serial_reference(vals, x, 4, _no_drops(E))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(ref_load))
    assert float(drops) == 0.0  # nothing dropped
    assert float(load.sum()) == 2 * 16  # every token once a stage


def test_moe_capacity_drops_tokens():
    """Below demand, a token past its expert's capacity keeps only its
    residual path, and the counters say how many did."""
    mesh, net, vals = _placed((2, 1, 2, 2))
    # a zero router ties every gate and argmax takes the first: all 4
    # tokens of a microbatch go to expert 0, which has room for 1
    vals = dict(vals, router_w=jnp.zeros_like(vals["router_w"]))
    x = _batch(3)
    logits, load, drops = jax.jit(_on_mesh(mesh, 4, 0.5))(vals, x)
    assert parallel.moe_capacity(4, 2, 0.5) == 1
    # 2 stages x 4 microbatches keep one token each; the rest drop
    assert np.asarray(load).tolist() == [8.0, 0.0]
    assert float(drops) == 2 * 16 - 8
    ref, ref_load, ref_drops = _serial_reference(vals, x, 4, 0.5)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    assert float(ref_drops) == float(drops)
    # and with room for all, the same tokens give another answer
    full, _, none = jax.jit(_on_mesh(mesh, 4, 2.0))(vals, x)
    assert float(none) == 0.0
    assert not np.allclose(np.asarray(full), np.asarray(logits))


def test_moe_grads_and_training():
    """Router and expert gradients through the dispatch equal the dense
    computation's, and adam through four experts on an ep axis of four
    brings the loss down."""
    mesh, net, vals = _placed((1, 1, 2, 4), num_experts=4)
    x = _batch(5)
    got = _grads(_on_mesh(mesh, 4, _no_drops(4)), vals, x)
    want = _grads(lambda v, xb: _serial_reference(v, xb, 4, _no_drops(4)),
                  vals, x)
    for name in ("router_w", "expert_w1", "expert_b1", "expert_w2",
                 "expert_b2"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   rtol=1e-4, atol=1e-6, err_msg=name)
        assert np.abs(np.asarray(want[name])).max() > 0, name
    losses = _train(net, mesh, 30, optimizer="adam", lr=0.05)
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.75 * losses[0], losses


def test_moe_validation():
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    # fewer experts than the ep extent: a device would hold none. The
    # step refuses when it binds the block to its mesh
    wide = parallel.make_mesh((1, 1, 2, 4), ("dp", "tp", "pp", "ep"))
    net = _block(num_experts=2)
    with pytest.raises(mx.MXNetError,
                       match="2 experts do not shard over 'ep' axis "
                             "extent 4"):
        parallel.ShardedTrainStep(net, loss_fn, "sgd",
                                  {"learning_rate": 0.1}, mesh=wide,
                                  rules=net.sharding_rules(wide))
    # a role that is not one of dp, tp, pp, ep is a programming error
    with pytest.raises(KeyError):
        parallel.resolve_mesh_axis(wide, "nope")
    # a mesh with pp but no ep axis: stages are pinned, experts are not
    piped = parallel.make_mesh((4, 2), ("data", "pipe"))
    assert parallel.resolve_mesh_axis(piped, "ep") is None
    rules = net.sharding_rules(piped)
    assert [spec for _, spec in rules] == [P("pipe")]
    assert not any(pat.search("expert_w1") for pat, _ in rules)


# ---------------------------------------------------------------------------
# acceptance: one launch, bit-exact vs the same math split into launches
# ---------------------------------------------------------------------------
_AB_BATCH, _AB_HIDDEN, _AB_ITERS = 16, 16, 2
_AB_MICRO, _AB_CF, _AB_LR = 4, 1.25, 0.05


def _split_into_launches(vals, mesh, x, y, steps):
    """The reference of the A/B: the unified step's math from the same
    placed parameters, stepped as the launches it replaced: one jitted
    ``value_and_grad`` and then one optimizer operator a parameter.
    Returns the loss of every step."""
    def loss_of(v, xb, yb):
        logits, _, _ = parallel.pipeline_moe_forward(
            v, xb, _AB_MICRO, _AB_CF, mesh=mesh, dp="dp", pp="pp", ep="ep")
        # gluon/loss.py SoftmaxCrossEntropyLoss, operation for operation
        pred = jax.nn.log_softmax(logits, axis=-1)
        idx = jnp.clip(yb.astype(jnp.int32), 0, logits.shape[-1] - 1)
        lp = jnp.take_along_axis(pred, idx[:, None], axis=-1)
        return jnp.mean(jnp.mean(-lp, axis=1))

    grad_fn = jax.jit(jax.value_and_grad(loss_of))
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    ys = jax.device_put(y, NamedSharding(mesh, P("dp")))
    vals = {k: nd.NDArray(v) for k, v in vals.items()}
    losses = []
    for _ in range(steps):
        loss, grads = grad_fn({k: v.data for k, v in vals.items()}, xs, ys)
        for k, w in vals.items():
            nd.sgd_update(w, nd.NDArray(grads[k]), lr=_AB_LR, out=w)
        losses.append(loss)
    return losses


def test_unified_vs_islands_bit_exact_one_launch():
    """The one-launch 4D step trains BIT-exactly like the same math
    split into a forward-and-backward launch plus one optimizer launch a
    parameter, at one launch a step against more than one, and with no
    more host syncs in the loop."""
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (_AB_BATCH, _AB_HIDDEN)).astype(np.float32)
    y = rng.randint(0, 8, (_AB_BATCH,)).astype(np.float32)
    mx.random.seed(7)
    mesh = _mesh4d()
    net = _block(in_units=_AB_HIDDEN, hidden=_AB_HIDDEN,
                 expert_hidden=2 * _AB_HIDDEN,
                 num_microbatches=_AB_MICRO, capacity_factor=_AB_CF)
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": _AB_LR}, mesh=mesh,
        rules=net.sharding_rules(mesh), zero_stage=1)
    steps = 1 + _AB_ITERS
    # the split leg never touches net: it steps copies of the placed
    # initial parameters
    n0, h0 = profiler.launch_count(), profiler.host_sync_count()
    split = _split_into_launches(net.param_values(), mesh, x, y, steps)
    split_launches = profiler.launch_count() - n0
    split_syncs = profiler.host_sync_count() - h0

    xa, ya = nd.array(x), nd.array(y)
    unified = [step(xa, ya)]  # builds the program; lands in the series
    n0, h0 = profiler.launch_count(), profiler.host_sync_count()
    unified += [step(xa, ya) for _ in range(_AB_ITERS)]
    assert profiler.launch_count() - n0 == _AB_ITERS  # 1 a step
    assert split_launches >= steps * len(net.param_values()) > steps
    assert profiler.host_sync_count() - h0 == split_syncs == 0

    assert [float(v) for v in split] \
        == [float(v.asscalar()) for v in unified]  # bit-exact


# ---------------------------------------------------------------------------
# satellite 3 regression: ep-sharded params must not silently replicate
# ---------------------------------------------------------------------------
@with_seed()
def test_expert_state_shardings_survive_save_load(tmp_path):
    """Optimizer state of a rule-sharded expert weight stays P(pp, ep)
    — at build, through training, and across save_states/load_states
    (regression: the state path consulted only `_zero_shardings`, so a
    non-ZeRO-eligible-but-rule-sharded param's adam moments silently
    replicated, 4× the per-device bytes they should be)."""
    mesh = _mesh4d()
    net = _block()
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01}, mesh=mesh,
        rules=net.sharding_rules(mesh), zero_stage=2)
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (16, 8)).astype(np.float32))
    y = nd.array(rng.randint(0, 8, (16,)).astype(np.float32))
    step(x, y)

    name = [n for n in step._train_names if n.endswith("expert_w1")][0]
    want = P("pp", "ep")
    # rule-sharded → excluded from ZeRO, pinned to the rule's spec
    assert step._zero_shardings[name] is None
    assert step._state_shardings[name].spec == want
    for s in step._states[name]:
        assert s.sharding.spec == want
    # the param itself is placed per the rule too (not replicated)
    w = net.collect_params()[name].data().data
    assert w.sharding.spec == want
    assert w.addressable_shards[0].data.shape[:2] == (1, 1)

    ck = str(tmp_path / "states.bin")
    step.save_states(ck)
    step.load_states(ck)
    for s in step._states[name]:
        assert s.sharding.spec == want, \
            "expert state replicated by load_states"
    # and a dense (non-rule) param still rides ZeRO over dp
    dense = [n for n in step._train_names if n.endswith("w_in")][0]
    assert step._zero_shardings[dense] is not None
    loss = step(x, y)
    assert np.isfinite(float(loss.asscalar()))


# ---------------------------------------------------------------------------
# typed validation: bad rules and mismatched meshes fail loudly
# ---------------------------------------------------------------------------
def test_sharding_rule_validation_typed_errors():
    mesh = _mesh4d()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def dense():
        net = nn.HybridSequential(prefix="p4err_")
        with net.name_scope():
            net.add(nn.Dense(8, in_units=8))
        net.initialize()
        return net

    # a rule naming an axis the mesh doesn't have is a typed error,
    # not a silent replication
    with pytest.raises(mx.MXNetError, match="names mesh axis"):
        parallel.ShardedTrainStep(
            dense(), loss_fn, "sgd", {"learning_rate": 0.1}, mesh=mesh,
            rules=sharding_rule((r".*weight$", P("nonexistent"))))
    # so is a rule with more dims than the parameter
    with pytest.raises(mx.MXNetError, match="rank"):
        parallel.ShardedTrainStep(
            dense(), loss_fn, "sgd", {"learning_rate": 0.1}, mesh=mesh,
            rules=sharding_rule((r".*bias$", P("pp", "ep", "dp"))))
    # pp extent must equal the stage count (or 1)
    mesh_pp4 = parallel.make_mesh((1, 1, 4, 2), ("dp", "tp", "pp", "ep"))
    with pytest.raises(mx.MXNetError, match="pipeline"):
        _block().rebind_mesh(mesh_pp4)
    # experts must divide the ep extent
    with pytest.raises(mx.MXNetError, match="experts"):
        _block(num_experts=3).rebind_mesh(mesh)


# ---------------------------------------------------------------------------
# on-device router accounting: conservation, no per-step host syncs
# ---------------------------------------------------------------------------
@with_seed()
def test_moe_accounting_conserves_tokens():
    """Every (stage, token) routing slot is accounted exactly once:
    sum(expert_load) + drops == stages * batch * steps. The counters
    ride the aux-carry (grad_req='null') protocol, so the read is one
    deferred host transfer per telemetry window, not a per-step sync."""
    mesh = _mesh4d()
    net = _block()
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=mesh,
        rules=net.sharding_rules(mesh), zero_stage=1)
    # mesh telemetry covers the new axes (gauge iterates mesh.shape)
    from mxnet_tpu import telemetry

    fam = telemetry.registry().get("mxt_mesh_axis_size")
    assert fam.labels("pp").value == 2
    assert fam.labels("ep").value == 2
    rng = np.random.RandomState(2)
    steps, batch = 3, 16
    x = nd.array(rng.uniform(-1, 1, (batch, 8)).astype(np.float32))
    y = nd.array(rng.randint(0, 8, (batch,)).astype(np.float32))
    for _ in range(steps):
        step(x, y)
    moe = parallel.publish_moe_telemetry(net)
    total = sum(moe["expert_load"]) + moe["drops"]
    assert total == net.num_stages * batch * steps
    assert all(v >= 0 for v in moe["expert_load"])
    # second publish in the same window: the prometheus counter only
    # ever advances by the DELTA (no double count on re-publish)
    from mxnet_tpu import telemetry

    c0 = telemetry.registry().get("mxt_moe_router_drops_total").value
    again = parallel.publish_moe_telemetry(net)
    assert again["drops"] == moe["drops"]  # cumulative, unchanged
    assert again["expert_load"] == moe["expert_load"]
    assert telemetry.registry().get(
        "mxt_moe_router_drops_total").value == c0


@with_seed()
def test_pipeline_moe_forward_batch_divisibility():
    net = _block()
    vals = net.param_values()
    import jax.numpy as jnp

    x = jnp.zeros((10, 8), jnp.float32)  # 10 % 4 != 0
    with pytest.raises(mx.MXNetError, match="microbatch"):
        parallel.pipeline_moe_forward(vals, x, 4, 1.25)


def test_block_params_ride_structural_checkpoint_walk():
    """Regression: every PipelineMoEBlock weight is registered as a
    block ATTRIBUTE, not just in the internal dict — save_parameters
    (and the elastic-reshard spill) walk _reg_params, and a dict-only
    param silently dropped out of every checkpoint, so a reshard
    restored INITIAL weights."""
    net = _block()
    walked = net._collect_params_with_prefix()
    assert len(walked) == len(net.collect_params()) == 13
    for k in ("w_in", "stage_w", "router_w", "expert_w1", "w_out",
              "expert_load"):
        assert k in walked, k


def test_moe_capacity():
    assert parallel.moe_capacity(8, 2, 1.0) == 4
    assert parallel.moe_capacity(8, 2, 1.25) == 5
    assert parallel.moe_capacity(1, 8, 1.0) == 1  # floor of 1


# ---------------------------------------------------------------------------
# 4-axis mesh construction defaults + axis-role synonyms
# ---------------------------------------------------------------------------
def test_make_mesh_4d_default_names_and_synonyms():
    m = parallel.make_mesh((2, 1, 2, 2))
    assert m.axis_names == ("data", "model", "pipe", "expert")
    assert dict(m.shape) == {"data": 2, "model": 1, "pipe": 2,
                             "expert": 2}
    # rank-2 shapes keep the classic names; no-arg keeps (n, 1)
    assert parallel.make_mesh((4, 2)).axis_names == ("data", "model")
    assert dict(parallel.make_mesh().shape) == {"data": 8, "model": 1}
    # synonyms resolve per ROLE, whatever the mesh spelled them
    assert parallel.resolve_mesh_axis(m, "dp") == "data"
    assert parallel.resolve_mesh_axis(m, "pp") == "pipe"
    assert parallel.resolve_mesh_axis(m, "ep") == "expert"
    short = parallel.make_mesh((2, 1, 2, 2), ("dp", "tp", "pp", "ep"))
    assert parallel.resolve_mesh_axis(short, "dp") == "dp"
    assert parallel.resolve_mesh_axis(short, "ep") == "ep"
    two = parallel.make_mesh((4, 2), ("data", "model"))
    assert parallel.resolve_mesh_axis(two, "pp") is None
