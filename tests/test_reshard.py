"""Multi-host GSPMD scale-out tests on the 8-device CPU mesh: the ZeRO
weight-update-sharding ladder (stages 1/2/3, arXiv 2004.13336), sharded
checkpoint/resume across mesh shapes, and elastic in-place mesh
resharding fused with the membership layer (parallel/reshard.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.membership import MembershipTable
from mxnet_tpu.resilience import CheckpointManager
from mxnet_tpu.test_utils import with_seed


def _bn_mlp(prefix, in_units=8):
    """Dims all divisible by 8 so every trainable tensor is
    ZeRO-eligible at dp=8 (BN gamma/beta included; running stats are
    aux and stay replicated)."""
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, in_units=in_units), nn.BatchNorm(),
                nn.Activation("relu"), nn.Dense(8, in_units=16))
    net.initialize()
    net(nd.zeros((2, in_units)))
    return net


def _params_np(net):
    return {n: p.data().asnumpy()
            for n, p in net.collect_params().items()}


def _gauge_value(name, *labels):
    fam = telemetry.registry().get(name)
    if fam is None:
        return None
    return fam.labels(*labels).value if labels else fam.value


# ---------------------------------------------------------------------------
# ZeRO-2/3 acceptance: bit-exact vs replicated, bytes shrink ~dp×
# ---------------------------------------------------------------------------
@with_seed()
@pytest.mark.parametrize("opt,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
])
def test_zero_stages_bit_exact_vs_replicated(opt, opt_params):
    """Acceptance: ZeRO-2 and ZeRO-3 train BIT-EXACT (<=1e-6 over 5
    steps, sgd-mom + adam, BatchNorm aux carried) vs the replicated
    stage-0 baseline on the 8-device mesh — the ladder only changes
    layout/collectives, never math. (Stage 1 parity is pinned by the
    legacy shard_update tests in test_parallel.py.)"""
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (16, 8)).astype(np.float32)
    y = rng.randint(0, 8, (16,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(axis_names=("data",))

    mx.random.seed(5)
    ref_net = _bn_mlp("zref%s_" % opt)
    ref = parallel.ShardedTrainStep(ref_net, loss_fn, opt,
                                    dict(opt_params), mesh=mesh,
                                    zero_stage=0)
    for _ in range(5):
        l_ref = ref(nd.array(x), nd.array(y))
    ref_params = _params_np(ref_net)
    # BN aux actually moved (the stats ride the fused program)
    rm = [v for n, v in ref_params.items() if n.endswith("running_mean")]
    assert any(np.abs(a).max() > 0 for a in rm)

    for stage in (2, 3):
        mx.random.seed(5)
        net = _bn_mlp("z%d%s_" % (stage, opt))
        step = parallel.ShardedTrainStep(net, loss_fn, opt,
                                         dict(opt_params), mesh=mesh,
                                         zero_stage=stage)
        for _ in range(5):
            loss = step(nd.array(x), nd.array(y))
        assert abs(float(loss.asscalar()) - float(l_ref.asscalar())) \
            <= 1e-6, "stage %d loss diverged" % stage
        for n, v in _params_np(net).items():
            ref_v = ref_params[n.replace("z%d%s_" % (stage, opt),
                                         "zref%s_" % opt)]
            np.testing.assert_allclose(
                v, ref_v, rtol=1e-6, atol=1e-6,
                err_msg="stage %d param %s" % (stage, n))


@with_seed()
def test_zero_stage_per_device_bytes_shrink():
    """The memory claim itself: optimizer-state bytes/device shrink dp×
    at stages 1-3, param bytes/device shrink at stage 3 only (aux BN
    stats stay replicated by design)."""
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(axis_names=("data",))
    dp = 8
    sizes = {}
    for stage in (0, 1, 2, 3):
        mx.random.seed(7)
        net = _bn_mlp("zb%d_" % stage)
        step = parallel.ShardedTrainStep(net, loss_fn, "adam",
                                         {"learning_rate": 0.01},
                                         mesh=mesh, zero_stage=stage)
        sizes[stage] = step.per_device_bytes()
        # states for eligible params truly live sharded on device
        if stage >= 1:
            for n in step._train_names:
                z = step._zero_shardings[n]
                assert z is not None, n  # every trainable is eligible
                for s in step._states[n]:
                    assert s.addressable_shards[0].data.shape[0] \
                        == s.shape[0] // dp
    # adam m+v: every trainable eligible -> exactly dp× smaller
    assert sizes[1]["opt_state_bytes"] * dp == sizes[0]["opt_state_bytes"]
    assert sizes[2]["opt_state_bytes"] * dp == sizes[0]["opt_state_bytes"]
    assert sizes[3]["opt_state_bytes"] * dp == sizes[0]["opt_state_bytes"]
    # params replicate until stage 3; aux stays replicated at stage 3 so
    # the shrink is ~dp× on the trainables only
    assert sizes[1]["param_bytes"] == sizes[0]["param_bytes"]
    assert sizes[2]["param_bytes"] == sizes[0]["param_bytes"]
    assert sizes[3]["param_bytes"] < sizes[0]["param_bytes"] / (dp / 2)
    # the gauges mxt_top's mesh section reads are live
    assert _gauge_value("mxt_mesh_devices") == 8
    assert _gauge_value("mxt_zero_stage") == 3
    assert _gauge_value("mxt_per_device_opt_bytes") \
        == sizes[3]["opt_state_bytes"]


@with_seed()
def test_zero_stage_composes_with_tp_rules_and_validates():
    """tp-rule-sharded params are excluded from ZeRO at every stage;
    zero_stage outside 0..3 is a typed error; the legacy shard_update
    flag maps to stage 2."""
    mesh = parallel.make_mesh((4, 2), ("data", "model"))
    rules = parallel.sharding_rule((r"dense0_weight", P("model", None)))
    net = _bn_mlp("ztp_")
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01}, mesh=mesh, rules=rules, zero_stage=3)
    w_tp = [n for n in step._train_names if "dense0_weight" in n][0]
    assert step._zero_shardings[w_tp] is None
    assert "model" in str(
        net.collect_params()[w_tp].data().data.sharding.spec)
    assert any(z is not None for z in step._zero_shardings.values())

    with pytest.raises(mx.MXNetError):
        parallel.ShardedTrainStep(
            _bn_mlp("zbad_"), mx.gluon.loss.SoftmaxCrossEntropyLoss(),
            "sgd", {}, mesh=mesh, zero_stage=4)

    legacy = parallel.ShardedTrainStep(
        _bn_mlp("zleg_"), mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        "adam", {"learning_rate": 0.01},
        mesh=parallel.make_mesh(axis_names=("data",)), shard_update=True)
    assert legacy.zero_stage == 2


# ---------------------------------------------------------------------------
# shard_params satellite: batched placement, already-placed skipped
# ---------------------------------------------------------------------------
@with_seed()
def test_shard_params_skips_already_placed():
    """The resume-path fix: a second shard_params pass over an
    already-placed net moves NOTHING (same buffers), and a partial
    change moves only the changed entries."""
    net = _bn_mlp("sp_")
    mesh = parallel.make_mesh(axis_names=("data",))
    params = net.collect_params()
    moved = parallel.shard_params(params, mesh)
    assert moved == len(params)
    before = {n: p.data().data for n, p in params.items()}
    assert parallel.shard_params(params, mesh) == 0  # all skipped
    for n, p in params.items():
        assert p.data().data is before[n]  # buffers untouched
    # re-rule one param: exactly one placement happens
    rules = parallel.sharding_rule((r"dense1_weight", P(None, "data")))
    assert parallel.shard_params(params, mesh, rules) == 1


# ---------------------------------------------------------------------------
# sharded save/resume across mesh shapes (satellite 3)
# ---------------------------------------------------------------------------
@with_seed()
def test_sharded_save_resume_onto_different_mesh(tmp_path):
    """CheckpointManager.save() on a sharded step, then resume() onto a
    DIFFERENT dp×tp mesh shape: weights restore bit-exactly (shards as
    the transfer format — the same path the elastic reshard rides) and
    training continues."""
    rng = np.random.RandomState(2)
    x = rng.uniform(-1, 1, (8, 8)).astype(np.float32)
    y = rng.randint(0, 8, (8,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    rules = parallel.sharding_rule((r"dense0_weight", P("model", None)))

    mx.random.seed(11)
    net_a = _bn_mlp("cka_")
    mesh_a = parallel.make_mesh((4, 2), ("data", "model"))
    step_a = parallel.ShardedTrainStep(net_a, loss_fn, "adam",
                                       {"learning_rate": 0.01},
                                       mesh=mesh_a, rules=rules,
                                       zero_stage=2)
    for _ in range(3):
        step_a(nd.array(x), nd.array(y))
    mgr_a = CheckpointManager(str(tmp_path), net=net_a, trainer=step_a,
                              prefix="shck")
    mgr_a.save(step=step_a.step_count)
    want = _params_np(net_a)

    # fresh process-analog: new net + step on a (2, 4) mesh
    mx.random.seed(99)  # deliberately different init — resume overwrites
    net_b = _bn_mlp("cka_")
    mesh_b = parallel.make_mesh((2, 4), ("data", "model"))
    step_b = parallel.ShardedTrainStep(net_b, loss_fn, "adam",
                                       {"learning_rate": 0.01},
                                       mesh=mesh_b, rules=rules,
                                       zero_stage=2)
    mgr_b = CheckpointManager(str(tmp_path), net=net_b, trainer=step_b,
                              prefix="shck")
    state = mgr_b.resume()
    assert state is not None and state.step == 3
    assert step_b.step_count == 3
    for n, v in _params_np(net_b).items():
        assert np.array_equal(v, want[n]), n  # bit-exact restore
    # placements follow the NEW mesh: tp rule now shards 4-way
    w = net_b.collect_params()[
        [n for n in want if "dense0_weight" in n][0]]
    assert w.data().data.addressable_shards[0].data.shape[0] \
        == w.shape[0] // 4
    # and the step still trains on the new mesh shape
    loss = step_b(nd.array(x), nd.array(y))
    assert np.isfinite(float(loss.asscalar()))


# ---------------------------------------------------------------------------
# survivor-mesh planning units
# ---------------------------------------------------------------------------
def test_host_device_map_and_plan_survivor_mesh():
    mesh = parallel.make_mesh((4, 2), ("data", "model"))
    hm = parallel.HostDeviceMap.from_mesh(mesh, 4)
    assert hm.num_hosts == 4
    # losing host 2 drops exactly its tp pair, order preserved
    devs = hm.devices_for_survivors({2})
    assert len(devs) == 6
    flat = list(mesh.devices.reshape(-1))
    assert devs == flat[:4] + flat[6:]

    small = parallel.plan_survivor_mesh(mesh, {2}, hm)
    assert dict(small.shape) == {"data": 3, "model": 2}
    assert small.axis_names == mesh.axis_names
    # two losses -> (2, 2); no loss -> None (nothing changes)
    small2 = parallel.plan_survivor_mesh(mesh, {1, 2}, hm)
    assert dict(small2.shape) == {"data": 2, "model": 2}
    assert parallel.plan_survivor_mesh(mesh, set(), hm) is None
    # a map that can't keep tp whole is a typed error
    hm_odd = parallel.HostDeviceMap(8, list(mesh.devices.reshape(-1)))
    with pytest.raises(mx.MXNetError):
        parallel.plan_survivor_mesh(mesh, {0}, hm_odd)
    # every host dead is typed too
    with pytest.raises(mx.MXNetError):
        hm.devices_for_survivors({0, 1, 2, 3})
    with pytest.raises(mx.MXNetError):
        parallel.HostDeviceMap(3)  # 8 devices don't split 3 ways


# ---------------------------------------------------------------------------
# elastic reshard acceptance
# ---------------------------------------------------------------------------
@with_seed()
def test_elastic_reshard_acceptance(tmp_path):
    """Acceptance: 8-device (4×2) mesh training; the membership reaper
    fences one data-parallel rank mid-run; survivors reshard IN PLACE
    to (3×2) and continue. The resulting weights match a from-checkpoint
    restart on the smaller mesh BIT-exactly, with zero full-job restarts
    and the resharding event visible in telemetry.

    Runs ISOLATED in a fresh interpreter: in a full-suite session the
    in-place mesh rebuild lands on an XLA CPU client already carrying
    hundreds of compiled programs, which intermittently segfaults at
    interpreter teardown (ROADMAP standing item). A clean process keeps
    the acceptance deterministic without masking real failures — the
    inner run's verdict is asserted, not swallowed."""
    if os.environ.get("MXT_RESHARD_ACCEPTANCE_INNER") != "1":
        env = dict(os.environ)
        env["MXT_RESHARD_ACCEPTANCE_INNER"] = "1"
        env.setdefault("JAX_PLATFORMS", "cpu")
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-x",
             "%s::test_elastic_reshard_acceptance"
             % os.path.abspath(__file__),
             "-p", "no:cacheprovider", "-p", "no:xdist",
             "-p", "no:randomly"],
            env=env, timeout=600, capture_output=True, text=True)
        assert r.returncode == 0, \
            "isolated reshard acceptance failed (rc=%d)\n%s\n%s" \
            % (r.returncode, r.stdout[-4000:], r.stderr[-2000:])
        return
    spill = str(tmp_path / "reshard_spill")
    rng = np.random.RandomState(1)
    # batch 12: divisible by dp=4 before and dp=3 after the reshard
    x = rng.uniform(-1, 1, (12, 6)).astype(np.float32)
    y = rng.randint(0, 6, (12,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def build():
        mx.random.seed(3)
        net = nn.HybridSequential(prefix="ers_")
        with net.name_scope():
            net.add(nn.Dense(24, activation="relu", in_units=6),
                    nn.Dense(6, in_units=24))
        net.initialize()
        return net

    ev0 = _gauge_value("mxt_reshard_events_total") or 0

    # ---- path A: live run with an in-place reshard -------------------
    net_a = build()
    mesh = parallel.make_mesh((4, 2), ("data", "model"))
    step_a = parallel.ShardedTrainStep(net_a, loss_fn, "adam",
                                       {"learning_rate": 0.01},
                                       mesh=mesh, zero_stage=2)
    hm = parallel.HostDeviceMap.from_mesh(mesh, 4)
    ctrl = parallel.ElasticReshardController(step_a, hm, spill_dir=spill)
    table = MembershipTable()
    ctrl.attach(table)
    gens = {w: table.register(w, now=0.0)[0] for w in range(4)}

    losses_a = []
    for _ in range(3):
        assert ctrl.maybe_reshard() is None  # healthy: no-op
        losses_a.append(float(step_a(nd.array(x),
                                     nd.array(y)).asscalar()))
    # worker 2 goes silent; the reaper fences it and (via the death
    # listener) the controller learns without being polled
    for w in (0, 1, 3):
        table.heartbeat(w, gens[w], now=100.0)
    assert table.reap(10.0, now=100.0) == [2]
    assert ctrl.pending == {2}
    event = ctrl.maybe_reshard()
    assert event is not None
    assert event["old_shape"] == {"data": 4, "model": 2}
    assert event["new_shape"] == {"data": 3, "model": 2}
    assert event["lost_workers"] == [2]
    assert event["step"] == 3
    assert dict(step_a.mesh.shape) == {"data": 3, "model": 2}
    # ZeRO eligibility re-decided for dp=3: 24-wide tensors shard, the
    # 6-wide head falls back replicated (24 % 3 == 0, 6 % 3 == 0 — use
    # dim0 checks directly)
    for n in step_a._train_names:
        d = net_a.collect_params()[n].data().data
        if d.shape[0] % 3 == 0:
            assert step_a._zero_shardings[n] is not None, n
    for _ in range(2):
        loss_a = step_a(nd.array(x), nd.array(y))
    weights_a = _params_np(net_a)

    # telemetry: the reshard event is visible
    assert (_gauge_value("mxt_reshard_events_total") or 0) == ev0 + 1
    assert _gauge_value("mxt_mesh_devices") == 6
    assert _gauge_value("mxt_mesh_axis_size", "data") == 3

    # ---- path B: from-checkpoint restart on the smaller mesh ---------
    net_b = build()
    mesh_b = parallel.plan_survivor_mesh(mesh, {2}, hm)
    step_b = parallel.ShardedTrainStep(net_b, loss_fn, "adam",
                                       {"learning_rate": 0.01},
                                       mesh=mesh_b, zero_stage=2)
    mgr = CheckpointManager(spill, net=net_b, trainer=step_b,
                            prefix="reshard")
    state = mgr.resume()
    assert state is not None and state.step == 3
    for _ in range(2):
        loss_b = step_b(nd.array(x), nd.array(y))

    assert float(loss_a.asscalar()) == float(loss_b.asscalar())
    for n, v in _params_np(net_b).items():
        assert np.array_equal(v, weights_a[n]), \
            "in-place reshard diverged from restart at %s" % n


@pytest.mark.chaos
@with_seed()
def test_elastic_reshard_4d_acceptance(tmp_path):
    """Acceptance (4D): a (2,1,2,2) dp×tp×pp×ep mesh trains the unified
    pipeline+MoE step; the reaper fences one dp rank (seeded victim —
    swept by tools/chaos_matrix.sh via MXT_CHAOS_SEED); survivors
    reshard IN PLACE to (1,1,2,2) — pp preserved, experts REMAPPED onto
    the survivor devices with unchanged local shard shapes, ZeRO
    re-decided — and the result matches a from-checkpoint restart on
    the survivor mesh BIT-exactly. Same interpreter isolation as
    test_elastic_reshard_acceptance (in-place mesh rebuild on a hot XLA
    CPU client)."""
    if os.environ.get("MXT_RESHARD_4D_INNER") != "1":
        env = dict(os.environ)
        env["MXT_RESHARD_4D_INNER"] = "1"
        env.setdefault("JAX_PLATFORMS", "cpu")
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-x",
             "%s::test_elastic_reshard_4d_acceptance"
             % os.path.abspath(__file__),
             "-p", "no:cacheprovider", "-p", "no:xdist",
             "-p", "no:randomly"],
            env=env, timeout=600, capture_output=True, text=True)
        assert r.returncode == 0, \
            "isolated 4D reshard acceptance failed (rc=%d)\n%s\n%s" \
            % (r.returncode, r.stdout[-4000:], r.stderr[-2000:])
        return
    spill = str(tmp_path / "reshard4d_spill")
    victim = int(os.environ.get("MXT_CHAOS_SEED", "1")) % 2
    rng = np.random.RandomState(4)
    # batch 16 / 4 microbatches = 4-token slices: divide dp=2 and dp=1
    x = rng.uniform(-1, 1, (16, 8)).astype(np.float32)
    y = rng.randint(0, 8, (16,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def build():
        mx.random.seed(5)
        net = parallel.PipelineMoEBlock(
            num_stages=2, num_experts=2, in_units=8, hidden=8,
            expert_hidden=16, num_classes=8, num_microbatches=4,
            prefix="ers4d_")
        net.initialize()
        return net

    # ---- path A: live run with an in-place 4D reshard ----------------
    net_a = build()
    mesh = parallel.make_mesh((2, 1, 2, 2), ("dp", "tp", "pp", "ep"))
    step_a = parallel.ShardedTrainStep(
        net_a, loss_fn, "adam", {"learning_rate": 0.01}, mesh=mesh,
        rules=net_a.sharding_rules(mesh), zero_stage=2)
    # 2 hosts × 4 devices: each host holds one full dp rank (a whole
    # tp×pp×ep block), so losing a host shrinks dp 2 -> 1
    hm = parallel.HostDeviceMap.from_mesh(mesh, 2)
    ctrl = parallel.ElasticReshardController(step_a, hm, spill_dir=spill)
    table = MembershipTable()
    ctrl.attach(table)
    gens = {w: table.register(w, now=0.0)[0] for w in range(2)}

    for _ in range(3):
        assert ctrl.maybe_reshard() is None
        step_a(nd.array(x), nd.array(y))
    table.heartbeat(1 - victim, gens[1 - victim], now=100.0)
    assert table.reap(10.0, now=100.0) == [victim]
    assert ctrl.pending == {victim}
    event = ctrl.maybe_reshard()
    assert event is not None
    assert event["old_shape"] == {"dp": 2, "tp": 1, "pp": 2, "ep": 2}
    assert event["new_shape"] == {"dp": 1, "tp": 1, "pp": 2, "ep": 2}
    assert event["lost_workers"] == [victim]
    assert dict(step_a.mesh.shape) == {"dp": 1, "tp": 1, "pp": 2,
                                       "ep": 2}
    # experts remapped onto the 4 survivor devices: sharding spec and
    # LOCAL shard shapes unchanged (ep extent survived the shrink)
    ew = [n for n in step_a._train_names
          if n.endswith("expert_w1")][0]
    d = net_a.collect_params()[ew].data().data
    assert d.sharding.spec == P("pp", "ep")
    assert len(d.sharding.device_set) == 4
    assert d.addressable_shards[0].data.shape[:2] == (1, 1)
    survivors = set(step_a.mesh.devices.reshape(-1))
    assert {s.device for s in d.addressable_shards} <= survivors
    # ZeRO re-decided against the SURVIVOR mesh: rule-sharded expert
    # params stay excluded, dense params' zero shardings now name the
    # new mesh (dp extent 1 — effectively replicated, still dp-owned)
    assert step_a._zero_shardings[ew] is None
    for n in step_a._train_names:
        z = step_a._zero_shardings[n]
        if z is not None:
            assert z.mesh.shape == step_a.mesh.shape, n
    # the resharded 4D program lowers ahead of the next step
    assert step_a.aot_warmup() is True
    for _ in range(2):
        loss_a = step_a(nd.array(x), nd.array(y))
    weights_a = _params_np(net_a)

    # ---- path B: from-checkpoint restart on the survivor mesh --------
    net_b = build()
    mesh_b = parallel.plan_survivor_mesh(mesh, {victim}, hm)
    assert dict(mesh_b.shape) == {"dp": 1, "tp": 1, "pp": 2, "ep": 2}
    step_b = parallel.ShardedTrainStep(
        net_b, loss_fn, "adam", {"learning_rate": 0.01}, mesh=mesh_b,
        rules=net_b.sharding_rules(mesh_b), zero_stage=2)
    mgr = CheckpointManager(spill, net=net_b, trainer=step_b,
                            prefix="reshard")
    state = mgr.resume()
    assert state is not None and state.step == 3
    for _ in range(2):
        loss_b = step_b(nd.array(x), nd.array(y))

    assert float(loss_a.asscalar()) == float(loss_b.asscalar())
    for n, v in _params_np(net_b).items():
        assert np.array_equal(v, weights_a[n]), \
            "4D in-place reshard diverged from restart at %s" % n


@with_seed()
def test_reshard_controller_poll_view_and_cumulative_losses():
    """Worker-side wiring (no table attach): poll a membership view;
    a second loss after a reshard plans against the ORIGINAL host map
    cumulatively."""
    net = nn.HybridSequential(prefix="pv_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
    net.initialize()
    net(nd.zeros((2, 4)))
    mesh = parallel.make_mesh((8,), ("data",))
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh, zero_stage=1)
    hm = parallel.HostDeviceMap.from_mesh(mesh, 8)
    ctrl = parallel.ElasticReshardController(step, hm)
    x = nd.array(np.random.uniform(-1, 1, (8, 4)).astype(np.float32))
    y = nd.array(np.random.randint(0, 8, (8,)).astype(np.float32))
    step(x, y)
    ctrl.poll_view({"dead": {5: 6}, "members": {}})
    ev = ctrl.maybe_reshard()
    assert ev is not None and ev["devices"] == 7
    assert ev["lost_workers"] == [5]
    # second death: cumulative plan from the original 8-slot map
    ctrl.poll_view({"dead": {5: 6, 1: 2}, "members": {}})
    ev2 = ctrl.maybe_reshard()
    assert ev2 is not None and ev2["devices"] == 6
    assert ev2["lost_workers"] == [1, 5]
    # batch 6 divides the new dp=6
    loss = step(nd.array(np.random.uniform(-1, 1, (6, 4)).astype("f4")),
                nd.array(np.random.randint(0, 8, (6,)).astype("f4")))
    assert np.isfinite(float(loss.asscalar()))


# ---------------------------------------------------------------------------
# AOT warm-start of the (resharded) step
# ---------------------------------------------------------------------------
@with_seed()
def test_sharded_step_aot_warmup_and_signature():
    """The step registers with tuning: a stepped instance records its
    batch signature and aot_warmup() compiles without touching data;
    warmup(steps=[...]) reports it (the reshard path calls exactly
    this, tagged reason='reshard')."""
    from mxnet_tpu import tuning

    tuning.reset()  # drop signatures recorded by earlier tests
    net = nn.HybridSequential(prefix="aw_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
    net.initialize()
    net(nd.zeros((2, 4)))
    mesh = parallel.make_mesh((8,), ("data",))
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh, zero_stage=2)
    assert step.aot_warmup() is False  # no batch signature yet
    x = nd.array(np.random.uniform(-1, 1, (8, 4)).astype(np.float32))
    y = nd.array(np.random.randint(0, 8, (8,)).astype(np.float32))
    step(x, y)
    sigs = tuning.signatures("sharded_step")
    assert any(tuple(s["x_shape"]) == (8, 4) for s in sigs)
    assert step.aot_warmup() is True
    summary = tuning.warmup(steps=[step], kernels=False,
                            include_live=False, reason="reshard")
    assert "ShardedTrainStep" in summary["entries"]
    assert summary["reason"] == "reshard"
    # warm compile + traced call agree (no numerics drift)
    loss = step(x, y)
    assert np.isfinite(float(loss.asscalar()))


# ---------------------------------------------------------------------------
# fused single-device step refuses mesh-sharded nets
# ---------------------------------------------------------------------------
@with_seed()
def test_cached_train_step_ineligible_on_mesh_sharded_params():
    from mxnet_tpu.gluon.train_step import CachedTrainStep

    net = nn.HybridSequential(prefix="el_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
    net.initialize()
    net(nd.zeros((2, 4)))
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    assert CachedTrainStep.eligible(trainer, net) is None  # single dev ok
    mesh = parallel.make_mesh(axis_names=("data",))
    parallel.shard_params(net.collect_params(), mesh)
    reason = CachedTrainStep.eligible(trainer, net)
    assert reason is not None and "mesh-sharded" in reason


# ---------------------------------------------------------------------------
# launch-line mesh env (tools/launch.py --mesh)
# ---------------------------------------------------------------------------
def test_make_mesh_reads_env(monkeypatch):
    monkeypatch.setenv("MXT_MESH_SHAPE", "4,2")
    mesh = parallel.make_mesh()
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    monkeypatch.setenv("MXT_MESH_SHAPE", "-1,2")
    mesh = parallel.make_mesh()
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    monkeypatch.setenv("MXT_MESH_SHAPE", "8")
    mesh = parallel.make_mesh()  # rank-1 shape trims the axis names
    assert dict(mesh.shape) == {"data": 8}
    monkeypatch.setenv("MXT_MESH_AXES", "dp")
    mesh = parallel.make_mesh()
    assert dict(mesh.shape) == {"dp": 8}
    # explicit shape argument still wins over the env
    mesh = parallel.make_mesh((2, 4), ("a", "b"))
    assert dict(mesh.shape) == {"a": 2, "b": 4}
    # launch.py exports exactly these vars
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "launch", os.path.join(os.path.dirname(__file__), "..",
                               "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)

    class A:
        mesh = "16,2"
        mesh_axes = "data,model"
        zero_stage = 2

    extra = launch._mesh_env(A())
    assert extra == {"MXT_MESH_SHAPE": "16,2",
                     "MXT_MESH_AXES": "data,model",
                     "MXT_ZERO_STAGE": "2"}
    env = launch._worker_env({}, "127.0.0.1:1", 2, 1, extra)
    assert env["MXT_MESH_SHAPE"] == "16,2"
    assert env["MXT_ZERO_STAGE"] == "2"


def test_zero_stage_env_default(monkeypatch):
    monkeypatch.setenv("MXT_ZERO_STAGE", "2")
    net = nn.HybridSequential(prefix="ze_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
    net.initialize()
    net(nd.zeros((2, 4)))
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1},
        mesh=parallel.make_mesh(axis_names=("data",)))
    assert step.zero_stage == 2


# ---------------------------------------------------------------------------
# mxt_top mesh section + lint list
# ---------------------------------------------------------------------------
def test_mxt_top_mesh_section_renders_only_with_gauges():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxt_top", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "mxt_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)

    base = {("mxt_step_latency_seconds_count", frozenset()): 10.0}
    frame = top.render(base, None, 0)
    assert "mesh" not in frame  # no gauges -> no mesh section

    samples = dict(base)
    samples[("mxt_mesh_devices", frozenset())] = 6.0
    samples[("mxt_mesh_axis_size", frozenset({("axis", "data")}))] = 3.0
    samples[("mxt_mesh_axis_size", frozenset({("axis", "model")}))] = 2.0
    samples[("mxt_zero_stage", frozenset())] = 2.0
    samples[("mxt_per_device_param_bytes", frozenset())] = 2 * 1024.0
    samples[("mxt_per_device_opt_bytes", frozenset())] = 1536.0
    samples[("mxt_reshard_events_total", frozenset())] = 1.0
    frame = top.render(samples, None, 0)
    assert "mesh" in frame and "6 dev" in frame
    assert "data=3" in frame and "model=2" in frame
    assert "zero=2" in frame
    assert "2.0KB" in frame and "1.5KB" in frame
    assert "reshards" in frame and "1" in frame
    assert "moe load" not in frame  # no moe gauges -> no moe line

    # the 4D mesh renders all four axes + the moe accounting line
    samples[("mxt_mesh_axis_size", frozenset({("axis", "pipe")}))] = 2.0
    samples[("mxt_mesh_axis_size",
             frozenset({("axis", "expert")}))] = 2.0
    samples[("mxt_moe_expert_load", frozenset({("expert", "0")}))] = 90.0
    samples[("mxt_moe_expert_load", frozenset({("expert", "1")}))] = 84.0
    samples[("mxt_moe_router_drops_total", frozenset())] = 18.0
    frame = top.render(samples, None, 0)
    assert "pipe=2" in frame and "expert=2" in frame
    assert "moe load" in frame
    assert "e0=90" in frame and "e1=84" in frame
    assert "drops=18" in frame


def test_mxt_top_jsonl_metrics_snapshot(tmp_path):
    """--jsonl mode surfaces metrics-snapshot rows (regression: tell()
    inside file iteration raised OSError and silently dropped EVERY
    row) and parses the snapshot's unquoted labels so the mesh axes
    render."""
    import importlib.util
    import json as _json

    spec = importlib.util.spec_from_file_location(
        "mxt_top", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "mxt_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)

    path = tmp_path / "t.jsonl"
    path.write_text(_json.dumps({
        "kind": "metrics",
        "data": {"mxt_mesh_devices": 6,
                 "mxt_mesh_axis_size{axis=data}": 3,
                 "mxt_zero_stage": 2}}) + "\n")
    src = top.JsonlSource(str(path))
    samples = src.sample()
    assert top.metric_sum(samples, "mxt_mesh_devices") == 6
    assert top.metric_sum(samples, "mxt_mesh_axis_size", axis="data") == 3
    frame = top.render(samples, None, 0)
    assert "6 dev" in frame and "data=3" in frame


def test_host_sync_lint_covers_parallel_modules():
    """Lint-list regression: the GSPMD layer is policed; the scan is
    clean (control-plane syncs are annotated)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_host_syncs", os.path.join(
            os.path.dirname(__file__), "..",
            "tools", "check_host_syncs.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    for rel in ("mxnet_tpu/parallel/mesh.py",
                "mxnet_tpu/parallel/sharded.py",
                "mxnet_tpu/parallel/reshard.py",
                "mxnet_tpu/parallel/unified.py"):
        assert rel in m.SCAN
    root = os.path.join(os.path.dirname(__file__), "..")
    assert m.check(root) == []


# ---------------------------------------------------------------------------
# the whole ladder on one model: layout changes, never the math
# ---------------------------------------------------------------------------
def test_zero_stage_ladder_equal_losses_bytes_shrink():
    """The SAME 3-layer MLP (no aux state, every dim divisible by dp)
    stepped with adam at ZeRO stages 0-3 on the 8-device mesh: the loss
    after three steps is the same number at every stage, a device's
    optimizer-state bytes are exactly 8x smaller from stage 1 on, and
    its parameter bytes exactly 8x smaller at stage 3 only."""
    batch, hidden, steps = 16, 64, 3
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, 64)).astype(np.float32)
    y = rng.randint(0, 8, (batch,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(axis_names=("data",))
    losses, sizes = {}, {}
    for stage in (0, 1, 2, 3):
        mx.random.seed(7)
        net = nn.HybridSequential(prefix="zl%d_" % stage)
        with net.name_scope():
            net.add(nn.Dense(hidden, activation="relu", in_units=64),
                    nn.Dense(hidden, activation="relu", in_units=hidden),
                    nn.Dense(8, in_units=hidden))
        net.initialize()
        step = parallel.ShardedTrainStep(net, loss_fn, "adam",
                                         {"learning_rate": 1e-3},
                                         mesh=mesh, zero_stage=stage)
        for _ in range(steps):
            loss = step(nd.array(x), nd.array(y))
        losses[stage] = round(float(loss.asscalar()), 7)
        sizes[stage] = step.per_device_bytes()
    assert len(set(losses.values())) == 1, losses
    opt = {k: v["opt_state_bytes"] for k, v in sizes.items()}
    par = {k: v["param_bytes"] for k, v in sizes.items()}
    assert opt[0] == 8 * opt[1] == 8 * opt[2] == 8 * opt[3] > 0, opt
    assert par[0] == par[1] == par[2] == 8 * par[3] > 0, par
