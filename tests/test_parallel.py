"""Parallel/sharding tests on the 8-device CPU mesh (conftest forces
xla_force_host_platform_device_count=8 — the SURVEY §4 pattern for testing
multi-device semantics without hardware)."""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn
from mxnet_tpu import parallel
from mxnet_tpu.test_utils import assert_almost_equal, with_seed


def test_make_mesh_shapes():
    mesh = parallel.make_mesh()
    assert mesh.devices.size == 8
    mesh2 = parallel.make_mesh((4, 2), ("data", "model"))
    assert mesh2.shape == {"data": 4, "model": 2}
    mesh3 = parallel.make_mesh((-1, 2), ("data", "model"))
    assert mesh3.shape == {"data": 4, "model": 2}
    with pytest.raises(mx.MXNetError):
        parallel.make_mesh((3, 2), ("a", "b"))


def _mlp():
    net = nn.HybridSequential(prefix="ptest_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=4))
        net.add(nn.Dense(3, in_units=16))
    net.initialize()
    return net


@with_seed()
def test_sharded_step_data_parallel_matches_single():
    """The sharded dp step must produce the same update as an eager
    single-device step (allreduce-by-construction)."""
    np.random.seed(0)
    x = np.random.uniform(-1, 1, (16, 4)).astype(np.float32)
    y = np.random.randint(0, 3, (16,)).astype(np.float32)

    mx.random.seed(7)
    net_a = _mlp()
    mx.random.seed(7)
    net_b = _mlp()
    for (na, pa), (nb, pb) in zip(sorted(net_a.collect_params().items()),
                                  sorted(net_b.collect_params().items())):
        assert_almost_equal(pa.data().asnumpy(), pb.data().asnumpy())

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    # eager reference step
    trainer = mx.gluon.Trainer(net_a.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    with mx.autograd.record():
        loss_a = loss_fn(net_a(nd.array(x)), nd.array(y)).mean()
    loss_a.backward()
    trainer.step(1)  # rescale 1/1: ShardedTrainStep loss is already a mean

    # sharded step over the 8-device data axis
    mesh = parallel.make_mesh(axis_names=("data",))
    step = parallel.ShardedTrainStep(net_b, loss_fn, "sgd",
                                     {"learning_rate": 0.1}, mesh=mesh)
    loss_b = step(nd.array(x), nd.array(y))

    assert abs(float(loss_a.asscalar()) - float(loss_b.asscalar())) < 1e-5
    for (na, pa), (nb, pb) in zip(sorted(net_a.collect_params().items()),
                                  sorted(net_b.collect_params().items())):
        assert_almost_equal(pa.data().asnumpy(), pb.data().asnumpy(),
                            rtol=1e-4, atol=1e-5)


@with_seed()
def test_sharded_step_tensor_parallel():
    """dp×tp mesh with Megatron-sharded Dense layers still trains."""
    net = _mlp()
    mesh = parallel.make_mesh((4, 2), ("data", "model"))
    rules = parallel.sharding_rule(
        (r"dense0_weight", P("model", None)),
        (r"dense0_bias", P("model")),
        (r"dense1_weight", P(None, "model")),
    )
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh, rules=rules)
    # the weight is actually sharded over the model axis
    w = sorted(net.collect_params().items())[1][1]  # dense0_weight
    assert "model" in str(w.data().data.sharding.spec)

    x = np.random.uniform(-1, 1, (8, 4)).astype(np.float32)
    y = np.random.randint(0, 3, (8,)).astype(np.float32)
    losses = [float(step(nd.array(x), nd.array(y)).asscalar())
              for _ in range(10)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # it learns


@with_seed()
def test_sharded_step_adam_and_batchnorm_aux():
    """Adam path + BatchNorm running-stat carry through the jitted step."""
    net = nn.HybridSequential(prefix="pbn_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Dense(2, in_units=8))
    net.initialize()
    net(nd.zeros((2, 4)))

    params = dict(net.collect_params().items())
    rm_name = [n for n in params if n.endswith("running_mean")][0]
    rm_before = params[rm_name].data().asnumpy().copy()

    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01})
    x = np.random.uniform(1, 2, (8, 4)).astype(np.float32)
    y = np.random.randint(0, 2, (8,)).astype(np.float32)
    for _ in range(3):
        loss = step(nd.array(x), nd.array(y))
    assert np.isfinite(float(loss.asscalar()))
    rm_after = params[rm_name].data().asnumpy()
    assert not np.allclose(rm_before, rm_after)  # stats updated in-program


def test_graft_entry_contract():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


# ---------------------------------------------------------------------------
# sequence parallelism (ring + Ulysses) on the 8-device CPU mesh
# ---------------------------------------------------------------------------
def test_ring_attention_matches_reference():
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _attention_reference
    from mxnet_tpu.ops.attention import make_padding_bias

    mesh = parallel.make_mesh((8,), ("sp",))
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 4, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    for causal in (False, True):
        out = parallel.ring_attention(q, k, v, mesh=mesh, seq_axis="sp",
                                      causal=causal)
        ref = _attention_reference(q, k, v, None, causal, 0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    # padding bias rides the ring with K/V
    bias = make_padding_bias(jnp.asarray([40, 64]), T)
    out = parallel.ring_attention(q, k, v, bias=bias, mesh=mesh,
                                  seq_axis="sp")
    ref = _attention_reference(q, k, v, bias, False, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_reference():
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _attention_reference

    mesh = parallel.make_mesh((8,), ("sp",))
    rng = np.random.RandomState(1)
    B, H, T, D = 2, 8, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    for causal in (False, True):
        out = parallel.ulysses_attention(q, k, v, mesh=mesh, seq_axis="sp",
                                         causal=causal)
        ref = _attention_reference(q, k, v, None, causal, 0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_flow():
    """Ring attention is differentiable through shard_map + ppermute."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _attention_reference

    mesh = parallel.make_mesh((4,), ("sp",),
                              devices=jax.devices()[:4])
    rng = np.random.RandomState(2)
    B, H, T, D = 1, 2, 32, 8
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))

    def loss_ring(q_, k_, v_):
        return jnp.sum(parallel.ring_attention(
            q_, k_, v_, mesh=mesh, seq_axis="sp") ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_attention_reference(q_, k_, v_, None, False,
                                            1.0 / np.sqrt(D)) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_ring_attention_grads_causal_bias():
    """Backward through the custom VJP: causal mask + bias riding the ring."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _attention_reference
    from mxnet_tpu.ops.attention import make_padding_bias

    mesh = parallel.make_mesh((4,), ("sp",), devices=jax.devices()[:4])
    rng = np.random.RandomState(3)
    B, H, T, D = 2, 2, 32, 8
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    bias = make_padding_bias(jnp.asarray([20, 32]), T)

    def loss_ring(q_, k_, v_, b_):
        return jnp.sum(parallel.ring_attention(
            q_, k_, v_, bias=b_, mesh=mesh, seq_axis="sp",
            causal=True) ** 2)

    def loss_ref(q_, k_, v_, b_):
        return jnp.sum(_attention_reference(q_, k_, v_, b_, True,
                                            1.0 / np.sqrt(D)) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_ring_attention_backward_memory_is_o_t_over_n():
    """The VJP residuals must be O(T/n) per shard — NOT the O(T^2/n) that
    naive autodiff of the unrolled ring produces by saving every hop's
    (B, H, Tl, Tl) probability block (round-1 ADVICE #1)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import sequence as seq

    n = 8
    mesh = parallel.make_mesh((n,), ("sp",))
    B, H, T, D = 1, 2, 8 * n, 8  # T = 8·n per the verdict's test spec
    spec = P(None, None, "sp", None)

    def fwd_residuals(q_, k_, v_):
        _, res = seq._ring_core_fwd(q_, k_, v_, None, "sp", True,
                                    0.35, n)
        return [r for r in res if r is not None]

    out_specs = [spec] * 4 + [P(None, None, "sp")]  # q,k,v,out + lse
    shapes = jax.eval_shape(
        jax.shard_map(fwd_residuals, mesh=mesh,
                      in_specs=(spec, spec, spec), out_specs=out_specs),
        *[jax.ShapeDtypeStruct((B, H, T, D), jnp.float32)] * 3)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    # per-shard budget: q,k,v,out (B*H*Tl*D each) + lse (B*H*Tl), n shards.
    # The old path saved n extra (B,H,Tl,Tl) blocks per shard on top.
    tl = T // n
    budget = n * (4 * B * H * tl * D + B * H * tl)
    assert total <= budget, (total, budget)


def test_sync_batchnorm_global_stats_on_mesh():
    """SyncBatchNorm's design claim (basic_layers.py): inside the SPMD
    sharded step the batch is a global array, so BN batch stats are
    global — an 8-way sharded step must update running stats and params
    identically to a single-device run over the same global batch."""
    np.random.seed(1)
    x = np.random.uniform(-2, 2, (16, 6, 5, 5)).astype(np.float32)
    y = np.random.randint(0, 3, (16,)).astype(np.float32)

    def build():
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Conv2D(8, kernel_size=3, padding=1,
                                   use_bias=False),
                mx.gluon.nn.SyncBatchNorm(),
                mx.gluon.nn.Activation("relu"),
                mx.gluon.nn.Flatten(),
                mx.gluon.nn.Dense(3))
        net.initialize()
        net(nd.array(x))  # resolve shapes
        return net

    mx.random.seed(3)
    net_a = build()
    mx.random.seed(3)
    net_b = build()

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net_a.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    with mx.autograd.record():
        loss_a = loss_fn(net_a(nd.array(x)), nd.array(y)).mean()
    loss_a.backward()
    trainer.step(1)

    mesh = parallel.make_mesh(axis_names=("data",))
    step = parallel.ShardedTrainStep(net_b, loss_fn, "sgd",
                                     {"learning_rate": 0.1}, mesh=mesh)
    loss_b = step(nd.array(x), nd.array(y))

    assert abs(float(loss_a.asscalar()) - float(loss_b.asscalar())) < 1e-5
    pa = dict(net_a.collect_params().items())
    pb = dict(net_b.collect_params().items())
    for (ka, va), (kb, vb) in zip(sorted(pa.items()), sorted(pb.items())):
        assert_almost_equal(va.data().asnumpy(), vb.data().asnumpy(),
                            rtol=1e-4, atol=1e-5)
    # running stats specifically: the sharded step must have used GLOBAL
    # batch stats (a per-shard implementation would disagree here)
    rm_a = [v.data().asnumpy() for k, v in sorted(pa.items())
            if k.endswith("running_mean")]
    rm_b = [v.data().asnumpy() for k, v in sorted(pb.items())
            if k.endswith("running_mean")]
    for a, b in zip(rm_a, rm_b):
        assert_almost_equal(a, b, rtol=1e-4, atol=1e-6)
    assert any(np.abs(a).max() > 0 for a in rm_a)  # stats actually moved


def test_bert_tensor_parallel_rules_match_replicated():
    """model_zoo.bert.tensor_parallel_rules: a dp2 x tp4 sharded BERT
    step must produce the same loss/params as pure dp (GSPMD inserts the
    Megatron all-reduce pair; numerics must agree)."""
    from mxnet_tpu.gluon import Block, model_zoo

    class MLM(Block):
        def __init__(self, bert):
            super().__init__(prefix="tpmlm_")
            with self.name_scope():
                self.bert = bert

        def forward(self, x):
            seq, _ = self.bert(x, nd.zeros_like(x))
            return self.bert.decode_mlm(seq)

    def build():
        mx.random.seed(11)
        net = MLM(model_zoo.bert.bert_3_64_2(use_classifier=False,
                                             dropout=0.0))
        net.initialize()
        return net

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, 1000, (8, 12)).astype("f4"))
    y = nd.array(rng.randint(0, 1000, (8, 12)).astype("f4"))

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    class SeqLoss:
        def __call__(self, out, label):
            return loss_fn(out.reshape((-1, out.shape[-1])),
                           label.reshape((-1,)))

    net_dp = build()
    net_dp(x)
    mesh_dp = parallel.make_mesh(axis_names=("data",))
    step_dp = parallel.ShardedTrainStep(net_dp, SeqLoss(), "sgd",
                                        {"learning_rate": 0.1},
                                        mesh=mesh_dp)
    loss_a = step_dp(x, y)

    net_tp = build()
    net_tp(x)
    mesh_tp = parallel.make_mesh((2, 4), ("data", "model"))
    step_tp = parallel.ShardedTrainStep(
        net_tp, SeqLoss(), "sgd", {"learning_rate": 0.1}, mesh=mesh_tp,
        rules=model_zoo.bert.tensor_parallel_rules())
    loss_b = step_tp(x, y)

    assert abs(float(loss_a.asscalar()) - float(loss_b.asscalar())) < 1e-4
    pa = dict(net_dp.collect_params().items())
    pb = dict(net_tp.collect_params().items())
    for (ka, va), (kb, vb) in zip(sorted(pa.items()), sorted(pb.items())):
        assert_almost_equal(va.data().asnumpy(), vb.data().asnumpy(),
                            rtol=2e-3, atol=2e-4)


@with_seed()
def test_sharded_step_zero1_update_sharding():
    """shard_update=True (ZeRO-1, arXiv:2004.13336): adam states shard
    dim-0 over the data axis, numerics match the unsharded step."""
    np.random.seed(1)
    x = np.random.uniform(-1, 1, (16, 4)).astype(np.float32)
    y = np.random.randint(0, 3, (16,)).astype(np.float32)

    mx.random.seed(9)
    net_a = _mlp()
    mx.random.seed(9)
    net_b = _mlp()

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(axis_names=("data",))
    step_ref = parallel.ShardedTrainStep(net_a, loss_fn, "adam",
                                         {"learning_rate": 0.01},
                                         mesh=mesh)
    step_z = parallel.ShardedTrainStep(net_b, loss_fn, "adam",
                                       {"learning_rate": 0.01},
                                       mesh=mesh, shard_update=True)

    # eligible states (dim0 % 8 == 0) are sharded over the data axis;
    # biases of width 3 (indivisible) stay replicated
    sharded = replicated = 0
    for n in step_z._train_names:
        z = step_z._zero_shardings[n]
        for s in step_z._states[n]:
            if z is not None:
                assert "data" in str(s.sharding.spec)
                # per-device shard really is 1/8 of the state
                assert s.addressable_shards[0].data.shape[0] \
                    == s.shape[0] // 8
                sharded += 1
            else:
                replicated += 1
    assert sharded > 0  # the path is actually exercised

    for _ in range(3):
        la = step_ref(nd.array(x), nd.array(y))
        lb = step_z(nd.array(x), nd.array(y))
    assert abs(float(la.asscalar()) - float(lb.asscalar())) < 1e-5
    for (na, pa), (nb, pb) in zip(sorted(net_a.collect_params().items()),
                                  sorted(net_b.collect_params().items())):
        assert_almost_equal(pa.data().asnumpy(), pb.data().asnumpy(),
                            rtol=1e-4, atol=1e-5)


@with_seed()
def test_sharded_step_zero1_composes_with_tp():
    """ZeRO-1 over the data axis composes with Megatron tp rules: params
    the rules shard stay out of the update-sharding set."""
    net = _mlp()
    mesh = parallel.make_mesh((4, 2), ("data", "model"))
    rules = parallel.sharding_rule((r"dense0_weight", P("model", None)))
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01}, mesh=mesh, rules=rules,
        shard_update=True)
    zs = step._zero_shardings
    w_tp = [n for n in step._train_names if "dense0_weight" in n][0]
    assert zs[w_tp] is None  # tp-sharded param excluded from ZeRO
    assert any(z is not None for z in zs.values())
    x = np.random.uniform(-1, 1, (8, 4)).astype(np.float32)
    y = np.random.randint(0, 3, (8,)).astype(np.float32)
    losses = [float(step(nd.array(x), nd.array(y)).asscalar())
              for _ in range(3)]
    assert losses[-1] < losses[0]


@with_seed()
def test_sharded_step_fsdp_style_param_sharding():
    """FSDP/ZeRO-3-style: rules shard the PARAMS over the data axis;
    GSPMD all-gathers at use and keeps grads/updates sharded. Numerics
    must match the replicated step exactly."""
    np.random.seed(2)
    x = np.random.uniform(-1, 1, (16, 4)).astype(np.float32)
    y = np.random.randint(0, 3, (16,)).astype(np.float32)

    mx.random.seed(11)
    net_a = _mlp()
    mx.random.seed(11)
    net_b = _mlp()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(axis_names=("data",))

    step_ref = parallel.ShardedTrainStep(net_a, loss_fn, "adam",
                                         {"learning_rate": 0.01},
                                         mesh=mesh)
    # dense0_weight is (16, 4): dim0 divides the 8-way axis — shard it
    # over the SAME axis the batch uses. dense1_weight (3, 16) is left
    # out of the rule ON PURPOSE: rules apply unconditionally (no
    # divisibility fallback on this path), so a matching rule on an
    # indivisible dim would error rather than silently replicate
    rules = parallel.sharding_rule((r"dense0_weight", P("data", None)))
    step_f = parallel.ShardedTrainStep(net_b, loss_fn, "adam",
                                       {"learning_rate": 0.01},
                                       mesh=mesh, rules=rules)
    w = [p for n, p in sorted(net_b.collect_params().items())
         if "dense0_weight" in n][0]
    assert "data" in str(w.data().data.sharding.spec)
    # each device holds 1/8 of the sharded weight (the FSDP memory win)
    assert w.data().data.addressable_shards[0].data.shape[0] \
        == w.shape[0] // 8

    for _ in range(3):
        la = step_ref(nd.array(x), nd.array(y))
        lb = step_f(nd.array(x), nd.array(y))
    assert abs(float(la.asscalar()) - float(lb.asscalar())) < 1e-5
    for (na, pa), (nb, pb) in zip(sorted(net_a.collect_params().items()),
                                  sorted(net_b.collect_params().items())):
        assert_almost_equal(pa.data().asnumpy(), pb.data().asnumpy(),
                            rtol=1e-4, atol=1e-5)
    # the sharding must SURVIVE training — output propagation regressions
    # would otherwise replicate the param after step 1 with identical
    # numerics, silently losing the memory win this test locks in
    assert "data" in str(w.data().data.sharding.spec)
    assert w.data().data.addressable_shards[0].data.shape[0] \
        == w.shape[0] // 8


@with_seed()
def test_sharded_step_zero1_composes_with_remat():
    """shard_update and remat both rewrite the step program — together
    they must still train and keep states sharded."""
    net = _mlp()
    mesh = parallel.make_mesh(axis_names=("data",))
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01}, mesh=mesh, remat="full",
        shard_update=True)
    assert any(z is not None for z in step._zero_shardings.values())
    x = np.random.uniform(-1, 1, (16, 4)).astype(np.float32)
    y = np.random.randint(0, 3, (16,)).astype(np.float32)
    losses = [float(step(nd.array(x), nd.array(y)).asscalar())
              for _ in range(4)]
    assert all(np.isfinite(losses)) and min(losses[1:]) < losses[0]
    for n in step._train_names:
        if step._zero_shardings[n] is not None:
            for s in step._states[n]:
                assert "data" in str(s.sharding.spec)  # survived updates


def test_sharded_step_one_device_mesh_compiles_once():
    """On a one-device mesh a fresh buffer is "equivalent" to its mesh
    placement, and skipping the placement made the step's inputs change
    type after step 1: the whole program traced and compiled twice.
    shard_params now places whatever is not already on a mesh."""
    from mxnet_tpu import profiler, tuning

    mx.random.seed(0)
    net = nn.HybridSequential(prefix="onedev_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    mesh = parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.L2Loss(), "adam", {"learning_rate": 1e-2},
        mesh=mesh)
    for p in net.collect_params().values():
        assert isinstance(p.data().data.sharding, jax.sharding.NamedSharding)
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (8, 8)).astype(np.float32))
    y = nd.array(rng.uniform(-1, 1, (8, 4)).astype(np.float32))
    step(x, y).wait_to_read()
    c0, l0 = tuning.compile_stats()["compiles"], profiler.launch_count()
    step(x, y).wait_to_read()
    assert step._jit._cache_size() == 1
    assert tuning.compile_stats()["compiles"] == c0
    assert profiler.launch_count() - l0 == 1
