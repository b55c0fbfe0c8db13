"""Training-health plane (mxnet_tpu/health.py): per-layer stats computed
INSIDE the donated step, staged through the InflightWindow, anomaly
detection at retirement, the declarative rules engine, the fleet skew
watch.

The load-bearing properties:

- arming MXT_HEALTH adds ZERO host syncs: sync counts are bit-equal on
  vs off (the stat row rides the window's staged value channel, and in
  guard mode the guard bit packs into the row's last column so flags
  and stats retire from the SAME stacked read);
- numerics are untouched: losses and weights bit-identical on vs off,
  guard on and off, fused and sharded;
- a seeded ``grad_spike`` chaos fault is detected (typed event +
  counter) within one window retirement of the firing step.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, health, nd, profiler, resilience, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, nn

_loss_fn = mx.gluon.loss.L2Loss()


@pytest.fixture(autouse=True)
def _drained(tmp_path, monkeypatch):
    """Leave no in-flight tokens, fault rules, default-rule state, or
    cwd post-mortem dumps behind for the next test (NaN-injection tests
    trip the nonfinite anomaly, whose post-mortem defaults to cwd)."""
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    yield
    engine.wait_all()
    resilience.reset_faults()
    health.reset()


def _make(prefix, health_on, guard=False, monkeypatch=None):
    monkeypatch.setenv("MXT_HEALTH", "1" if health_on else "0")
    monkeypatch.setenv("MXT_SKIP_NONFINITE", "1" if guard else "0")
    mx.random.seed(11)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    return net, tr, tr.fuse_step(net, _loss_fn)


def _batches(n, nan_at=None, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for t in range(n):
        x = rng.uniform(-1, 1, (8, 8)).astype(np.float32)
        y = rng.uniform(-1, 1, (8, 4)).astype(np.float32)
        if t == nan_at:
            x[0, 0] = np.nan
        out.append((nd.array(x), nd.array(y)))
    return out


def _weights(net):
    return [p.data().asnumpy().copy()
            for _, p in sorted(net.collect_params().items())]


# ---------------------------------------------------------------------------
# stat packing: layout + on-device row
# ---------------------------------------------------------------------------
def test_stat_layout_columns():
    cols = health.stat_layout(["a", "b"])
    assert cols == ["loss", "grad_norm:a", "grad_norm:b",
                    "param_norm:a", "param_norm:b",
                    "update_ratio:a", "update_ratio:b", "nonfinite"]
    assert len(cols) == 3 * 2 + 2


def test_stat_row_values_and_guard_bit():
    import jax.numpy as jnp

    loss = jnp.array([1.0, 3.0], jnp.float32)
    g = (jnp.array([3.0, 4.0], jnp.float32),)
    old = (jnp.array([1.0, 0.0], jnp.float32),)
    new = (jnp.array([0.0, 0.0], jnp.float32),)
    row = np.asarray(health.stat_row(loss, g, old, new))
    assert row.shape == (3 * 1 + 2,)
    assert row[0] == pytest.approx(2.0)        # mean loss
    assert row[1] == pytest.approx(5.0)        # grad L2
    assert row[2] == pytest.approx(0.0)        # new param norm
    assert row[3] == pytest.approx(1.0)        # ||new-old||/||old||
    assert row[4] == 0.0                        # no guard mask -> 0
    # the guard bit packs ONLY this step's (newest) mask bit
    row = np.asarray(health.stat_row(
        loss, g, old, new, mask=jnp.uint32(0b101)))
    assert row[4] == 1.0
    row = np.asarray(health.stat_row(
        loss, g, old, new, mask=jnp.uint32(0b10)))
    assert row[4] == 0.0


# ---------------------------------------------------------------------------
# the zero-sync contract: fused step, guard off and on
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("guard,nan_at", [(False, None), (True, 4)])
def test_fused_step_health_sync_and_numeric_parity(monkeypatch, guard,
                                                   nan_at):
    """10 steps at window K=4 with the health plane off vs on: host
    sync counts BIT-EQUAL, losses and weights BIT-IDENTICAL, and the
    guard's skip bookkeeping unchanged (the stat row is an extra
    output of the same program, never a second read — in guard mode
    the non-finite flag retires from the row's own last column)."""
    def run(health_on):
        net, tr, step = _make("hp%d%d_" % (health_on, guard),
                              health_on, guard=guard,
                              monkeypatch=monkeypatch)
        data = _batches(10, nan_at=nan_at)
        losses = []
        s0 = profiler.host_sync_count()
        with engine.bulk(4):
            for x, y in data:
                losses.append(step(x, y))
            nd.waitall()
        syncs = profiler.host_sync_count() - s0
        assert step.fused, getattr(step, "_fallback_reason", None)
        out = [v.asnumpy() for v in losses]
        return out, _weights(net), syncs, step, tr._optimizer.num_update

    off_l, off_w, off_s, _, off_n = run(False)
    on_l, on_w, on_s, step, on_n = run(True)
    assert off_s == on_s, \
        "health plane added host syncs: %d -> %d" % (off_s, on_s)
    assert off_n == on_n  # guard skip bookkeeping identical
    for a, b in zip(off_l, on_l):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(off_w, on_w):
        np.testing.assert_array_equal(a, b)
    # the monitor consumed every retired row exactly once
    assert step._health_mon is not None
    assert step._health_mon._seen == 10


def test_health_off_builds_no_monitor(monkeypatch):
    _, _, step = _make("hoff_", False, monkeypatch=monkeypatch)
    assert step._health_mon is None


def test_fused_step_health_gauges_published(monkeypatch):
    net, _, step = _make("hg_", True, monkeypatch=monkeypatch)
    with engine.bulk(2):
        for x, y in _batches(4):
            step(x, y)
        nd.waitall()
    reg = telemetry.registry()
    assert reg.get("mxt_health_loss_ema") is not None
    fam = reg.get("mxt_health_grad_norm")
    layers = {v[0] for v in fam.children() if v[0].startswith("hg_")}
    # one per-layer series per trainable parameter of the 2-Dense net
    assert layers == set(step._health_mon.layer_names)
    assert len(layers) == 4


# ---------------------------------------------------------------------------
# detectors (host-side, synthetic rows through the real consume path)
# ---------------------------------------------------------------------------
def _row(loss, gnorms, uratio=0.01, bit=0.0):
    l = len(gnorms)
    return np.array([loss] + list(gnorms) + [1.0] * l
                    + [uratio] * l + [bit], dtype=np.float64)


def _events(stream):
    from mxnet_tpu import diagnostics

    return [e for e in diagnostics.recorder().events()
            if e.get("kind") == "health_anomaly"
            and e.get("stream") == stream]


def test_loss_spike_detector(monkeypatch):
    monkeypatch.setenv("MXT_HEALTH_POSTMORTEM", "0")
    mon = health.HealthMonitor(["d0"], stream="t_spike")
    rng = np.random.RandomState(0)
    for i in range(12):  # noisy-but-sane warmup (sd must be > 0)
        mon.consume(i, _row(1.0 + 0.02 * rng.randn(), [0.5]))
    assert mon.anomaly_count == 0
    mon.consume(12, _row(50.0, [0.5]))
    assert mon.anomaly_count == 1
    evs = _events("t_spike")
    assert evs and evs[-1]["detector"] == "loss_spike"
    assert evs[-1]["layer"] == "loss" and evs[-1]["step"] == 12


def test_grad_explosion_and_nonfinite(monkeypatch):
    monkeypatch.setenv("MXT_HEALTH_POSTMORTEM", "0")
    mon = health.HealthMonitor(["d0", "d1"], stream="t_exp")
    mon.consume(0, _row(1.0, [0.5, 0.5]))
    mon.consume(1, _row(1.0, [0.5, 5e6]))   # > MXT_HEALTH_EXPLODE
    mon.consume(2, _row(1.0, [np.inf, 0.5]))
    kinds = [(e["detector"], e["layer"]) for e in _events("t_exp")]
    assert ("grad_explosion", "d1") in kinds
    assert ("grad_explosion", "d0") in kinds
    fam = telemetry.registry().get("mxt_health_anomalies_total")
    assert fam.labels("grad_explosion", "d1").value >= 1


def test_dead_layer_needs_consecutive_run(monkeypatch):
    monkeypatch.setenv("MXT_HEALTH_POSTMORTEM", "0")
    monkeypatch.setenv("MXT_HEALTH_DEAD_STEPS", "3")
    mon = health.HealthMonitor(["d0"], stream="t_dead")
    for i in range(2):
        mon.consume(i, _row(1.0, [1e-12]))
    mon.consume(2, _row(1.0, [0.5]))         # run broken
    assert mon.anomaly_count == 0
    for i in range(3, 6):
        mon.consume(i, _row(1.0, [1e-12]))
    assert mon.anomaly_count == 1            # fires exactly once at 3
    assert _events("t_dead")[-1]["detector"] == "dead_layer"


def test_guard_hook_routes_explosions(monkeypatch):
    monkeypatch.setenv("MXT_HEALTH_POSTMORTEM", "0")
    calls = []
    monkeypatch.setenv("MXT_HEALTH_GUARD_HOOK", "0")
    mon = health.HealthMonitor(["d0"], stream="t_hk0",
                               guard_hook=lambda: calls.append(1))
    mon.consume(0, _row(1.0, [5e6]))
    assert not calls                          # hook gated off by default
    monkeypatch.setenv("MXT_HEALTH_GUARD_HOOK", "1")
    mon = health.HealthMonitor(["d0"], stream="t_hk1",
                               guard_hook=lambda: calls.append(1))
    mon.consume(0, _row(1.0, [5e6]))
    assert calls == [1]


# ---------------------------------------------------------------------------
# seeded grad_spike chaos: detection within one retirement window
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_grad_spike_detected_within_one_window(monkeypatch, tmp_path):
    """MXT_FAULT=grad_spike seeds ONE gradient spike after dispatch 3;
    the detectors catch it (typed flight-recorder event + counter + one
    post-mortem) no later than one InflightWindow retirement after the
    firing step. The spike itself compiles into the step program and
    fires with the health plane OFF too — watching never changes the
    numerics, so losses match bit-exactly watched vs unwatched."""
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("MXT_CHAOS_SEED",
                       os.environ.get("MXT_CHAOS_SEED", "42"))
    K, steps, after = 4, 12, 3

    def run(health_on, prefix):
        monkeypatch.setenv(
            "MXT_FAULT", "grad_spike:layer=0,after=%d,scale=1e6,n=1"
            % after)
        resilience.reset_faults()
        net, _, step = _make(prefix, health_on,
                             monkeypatch=monkeypatch)
        losses = []
        with engine.bulk(K):
            for x, y in _batches(steps):
                losses.append(step(x, y))
            nd.waitall()
        return [v.asnumpy() for v in losses], step

    watched_l, step = run(True, "csp1_")
    mon = step._health_mon
    assert mon.anomaly_count > 0, "seeded grad spike never detected"
    evs = _events("fused_step")
    assert evs, "no typed health_anomaly event recorded"
    first = min(e["step"] for e in evs)
    assert first <= after + 1 + K, \
        "detection step %d later than one window after the spike" % first
    assert any(e["detector"] == "grad_explosion" for e in evs)
    fam = telemetry.registry().get("mxt_health_anomalies_total")
    assert sum(c.value for _, c in fam.children().items()) > 0
    assert list(tmp_path.glob("mxt-postmortem-*.json")), \
        "anomaly post-mortem not dumped"

    unwatched_l, _ = run(False, "csp0_")
    for a, b in zip(watched_l, unwatched_l):
        np.testing.assert_array_equal(a, b)


def test_grad_spike_scale_host_side(monkeypatch):
    monkeypatch.setenv("MXT_FAULT",
                       "grad_spike:layer=0,after=3,scale=1e5,n=1")
    monkeypatch.setenv("MXT_CHAOS_SEED", "42")
    resilience.reset_faults()
    scales = [health.grad_spike_scale(i) for i in range(1, 10)]
    assert all(s == 1.0 for s in scales[:3])  # before after=3: never
    assert scales.count(1e5) == 1             # n=1: exactly one firing
    resilience.reset_faults()
    monkeypatch.delenv("MXT_FAULT")
    resilience.reset_faults()
    assert health.grad_spike_scale(99) == 1.0  # no rule -> no-op


# ---------------------------------------------------------------------------
# rules engine
# ---------------------------------------------------------------------------
def _uname(base):
    _uname.n += 1
    return "%s_%d" % (base, _uname.n)


_uname.n = 0


def test_threshold_rule():
    name = _uname("t_health_skew")
    telemetry.gauge(name, "t").set(2.0)
    r = health.HealthRule("skew_hi", name, kind="threshold", op=">",
                          value=1.5)
    v = r.evaluate()
    assert v["ok"] is False and v["value"] == 2.0
    telemetry.gauge(name, "t").set(1.0)
    assert r.evaluate()["ok"] is True


def test_threshold_rule_no_data_is_none():
    r = health.HealthRule("nodata", _uname("t_health_missing"))
    v = r.evaluate()
    assert v["ok"] is None and v["detail"] == "no data"


def test_burn_rate_rule():
    name = _uname("t_health_burn")
    c = telemetry.counter(name, "t")
    c.inc(0)  # materialize the series (a never-bumped counter is no-data)
    r = health.HealthRule("burn", name, kind="burn_rate", op=">",
                          value=0.0)
    assert r.evaluate(now=100.0)["ok"] is None  # warming (1 sample)
    c.inc(5)
    v = r.evaluate(now=101.0)
    assert v["ok"] is False and v["value"] == pytest.approx(5.0)
    v = r.evaluate(now=102.0)                   # flat -> burn stopped
    assert v["ok"] is True


def test_trend_rule_slope_over_window():
    name = _uname("t_health_trend")
    g = telemetry.gauge(name, "t")
    r = health.HealthRule("rising", name, kind="trend", op=">",
                          value=0.0, window=60.0)
    g.set(1.0)
    r.evaluate(now=0.0)
    g.set(1.5)
    r.evaluate(now=10.0)
    g.set(2.0)
    v = r.evaluate(now=20.0)
    assert v["ok"] is False
    assert v["value"] == pytest.approx(0.05)    # slope over the window
    g.set(0.5)
    assert r.evaluate(now=30.0)["ok"] is True


def test_rule_validation_typed_errors():
    with pytest.raises(MXNetError):
        health.HealthRule("bad", "m", kind="gradient")
    with pytest.raises(MXNetError):
        health.HealthRule("bad", "m", op="!=")


def test_rule_engine_publishes_verdict_gauges():
    name = _uname("t_health_eng")
    telemetry.gauge(name, "t").set(9.0)
    eng = health.RuleEngine()
    eng.add(health.HealthRule("eng_hi", name, kind="threshold", op=">",
                              value=1.0))
    eng.evaluate()
    fam = telemetry.registry().get("mxt_health_rule_ok")
    assert fam.labels("eng_hi").value == 0.0    # breached
    telemetry.gauge(name, "t").set(0.5)
    eng.evaluate()
    assert fam.labels("eng_hi").value == 1.0


def test_default_rules_cover_training_and_serving():
    names = {r.name for r in health.default_engine().rules()}
    assert {"train_anomaly_burn", "loss_rising", "step_skew",
            "moe_router_drop_burn"} <= names
    # the serving SLO rules join the same engine
    assert "serving_p99_latency" in names


# ---------------------------------------------------------------------------
# fleet skew watch
# ---------------------------------------------------------------------------
def _member_export(step_ms, fingerprint):
    return {"families": [
        {"name": "mxt_health_host_step_ms", "kind": "gauge", "help": "",
         "labelnames": [], "children": [[[], step_ms]]},
        {"name": "mxt_health_grad_fingerprint", "kind": "gauge",
         "help": "", "labelnames": [], "children": [[[], fingerprint]]},
    ]}


def test_fleet_skew_straggler_and_divergence():
    from mxnet_tpu import diagnostics, telemetry_fleet

    freg = telemetry_fleet.FleetRegistry()
    freg.ingest("host-a", _member_export(10.0, 1.00))
    freg.ingest("host-b", _member_export(11.0, 1.01))
    freg.ingest("host-c", _member_export(40.0, 1.00))  # straggler
    freg.ingest("host-d", _member_export(10.5, 9.00))  # divergent
    v = health.fleet_skew(freg, skew_ratio=1.5, divergence=0.5)
    assert v["slowest"] == "host-c"
    assert v["stragglers"] == ["host-c"]
    assert v["divergent"] == ["host-d"]
    assert v["ok"] is False and v["skew_ratio"] > 1.5
    reg = telemetry.registry()
    assert reg.get("mxt_health_step_skew_ratio").value == \
        pytest.approx(v["skew_ratio"])
    assert reg.get("mxt_health_slowest_host_step_ms") \
        .labels("host-c").value == 40.0
    assert reg.get("mxt_health_fleet_ok").value == 0.0
    assert any(e.get("kind") == "health_fleet_skew"
               for e in diagnostics.recorder().events())


def test_fleet_skew_healthy_fleet():
    from mxnet_tpu import telemetry_fleet

    freg = telemetry_fleet.FleetRegistry()
    for m, ms in (("a", 10.0), ("b", 10.4), ("c", 9.8)):
        freg.ingest(m, _member_export(ms, 2.0))
    v = health.fleet_skew(freg, skew_ratio=1.5, divergence=0.5)
    assert v["ok"] is True and not v["stragglers"]
    assert telemetry.registry().get("mxt_health_fleet_ok").value == 1.0


def test_fleet_member_values_per_host_view():
    from mxnet_tpu import telemetry_fleet

    freg = telemetry_fleet.FleetRegistry()
    freg.ingest("a", _member_export(5.0, 1.0))
    freg.ingest("b", _member_export(7.0, 1.0), stale=True)
    vals = freg.member_values("mxt_health_host_step_ms")
    assert vals == {"a": 5.0}                  # stale members drop out
    assert freg.member_values("mxt_health_host_step_ms",
                              include_stale=True) == {"a": 5.0,
                                                      "b": 7.0}
    assert freg.member_values("mxt_no_such_metric") == {}


# ---------------------------------------------------------------------------
# /health route + mxt_top section
# ---------------------------------------------------------------------------
def test_health_route_payload_and_status():
    status, ctype, body = health.handle_health()
    assert ctype == "application/json"
    doc = json.loads(body)
    assert {"status", "rules", "anomalies", "breached"} <= set(doc)
    # the LB contract: 200 iff the payload itself says ok
    assert (status == 200) == (doc["status"] == "ok")
    rule_names = {r["rule"] for r in doc["rules"]}
    assert "train_anomaly_burn" in rule_names


def test_health_route_served_over_http():
    import urllib.request

    srv = telemetry.start_http_server(0)
    port = srv.server_address[1]
    url = "http://127.0.0.1:%d/health" % port
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            code, body = r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:       # 503 = degraded, still JSON
        code, body = e.code, e.read().decode("utf-8")
    assert code in (200, 503)
    doc = json.loads(body)
    assert doc["status"] in ("ok", "degraded")


def _mxt_top():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import mxt_top
    finally:
        sys.path.pop(0)
    return mxt_top


def test_mxt_top_health_section_golden():
    top = _mxt_top()
    text = "\n".join([
        "mxt_health_loss_ema 0.421",
        "mxt_health_host_step_ms 12.5",
        "mxt_health_step_skew_ratio 2.10",
        'mxt_health_anomalies_total{kind="grad_explosion",layer="d1"} 3',
        'mxt_health_anomalies_total{kind="loss_spike",layer="loss"} 1',
        'mxt_health_rule_ok{rule="loss_rising"} 1',
        'mxt_health_rule_ok{rule="step_skew"} 0',
    ]) + "\n"
    frame = top.render(top.parse_prometheus(text), None, 0)
    assert "health loss ema" in frame
    assert "0.421" in frame and "12.5" in frame
    assert "step skew" in frame and "2.10" in frame
    assert "grad_explosion:d1=3" in frame
    assert "loss_spike:loss=1" in frame
    assert "rules" in frame and "1 ok / 1 breached" in frame
    assert "step_skew" in frame                # the breached rule named
    # a run with the health plane dark renders NO health noise
    bare = top.render(top.parse_prometheus("up 1\n"), None, 0)
    assert "health loss ema" not in bare


# ---------------------------------------------------------------------------
# lint: the health plane itself stays sync-clean
# ---------------------------------------------------------------------------
def test_health_host_sync_lint_enforced():
    spec = importlib.util.spec_from_file_location(
        "check_host_syncs", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "check_host_syncs.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    assert "mxnet_tpu/health.py" in m.SCAN
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = [b for b in m.check(root) if b[0] == "mxnet_tpu/health.py"]
    assert not bad, bad


# ---------------------------------------------------------------------------
# sharded step parity + the reshard standing item with health armed
# ---------------------------------------------------------------------------
def test_sharded_step_health_numeric_parity(monkeypatch):
    """ShardedTrainStep with health on vs off: losses bit-equal, every
    retired row consumed. The health stream adds exactly the sanctioned
    one-deferred-read-per-K budget and NOTHING when dark (the stream
    only exists when armed)."""
    from mxnet_tpu import parallel

    def run(health_on):
        monkeypatch.setenv("MXT_HEALTH", "1" if health_on else "0")
        mx.random.seed(7)
        net = nn.HybridSequential(prefix="shh%d_" % health_on)
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu", in_units=4),
                    nn.Dense(3, in_units=16))
        net.initialize()
        mesh = parallel.make_mesh(axis_names=("data",))
        step = parallel.ShardedTrainStep(
            net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh)
        rng = np.random.RandomState(0)
        losses = []
        with engine.bulk(4):
            for _ in range(8):
                x = rng.uniform(-1, 1, (16, 4)).astype(np.float32)
                y = rng.randint(0, 3, (16,)).astype(np.float32)
                losses.append(step(nd.array(x), nd.array(y)))
            out = [float(v.asscalar()) for v in losses]
            nd.waitall()
        return out, step

    off_l, off_step = run(False)
    on_l, on_step = run(True)
    assert off_l == on_l
    assert off_step._health_mon is None and off_step._stream is None
    assert on_step._health_mon._seen == 8
    assert on_step._health_mon.stream == "sharded_step"


def test_reshard_acceptance_with_health_armed():
    """The elastic-reshard acceptance (tests/test_reshard.py standing
    item: subprocess-isolated, inner verdict asserted) still passes
    with the health plane armed — the stat row is an extra step output,
    not part of the spill/restore payload."""
    env = dict(os.environ)
    env["MXT_HEALTH"] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    test = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_reshard.py")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x",
         "%s::test_elastic_reshard_acceptance" % test,
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=env, timeout=600, capture_output=True, text=True)
    assert r.returncode == 0, \
        "reshard acceptance regressed with MXT_HEALTH=1 (rc=%d)\n%s\n%s" \
        % (r.returncode, r.stdout[-4000:], r.stderr[-2000:])


# ---------------------------------------------------------------------------
# the A/B on adam + softmax cross-entropy over deferred shapes, with the
# seeded spike as its third leg
# ---------------------------------------------------------------------------
def test_training_health_on_against_off_adam(monkeypatch):
    """The same fused step (three Dense layers with deferred input
    widths, adam, softmax cross-entropy, window 4) with the health plane
    off, on, and on under a seeded ``grad_spike``: the loss streams of
    off and on are byte-equal, the host syncs of the six counted steps
    are equal, and the spike is detected."""
    batch, hidden, warmup, iters = 8, 32, 2, 6
    monkeypatch.setenv("MXT_CHAOS_SEED", "0")

    def run(tag, armed, fault=None):
        monkeypatch.setenv("MXT_HEALTH", "1" if armed else "0")
        if fault:
            monkeypatch.setenv("MXT_FAULT", fault)
        else:
            monkeypatch.delenv("MXT_FAULT", raising=False)
        resilience.reset_faults()
        health.reset()
        mx.random.seed(0)
        net = nn.Sequential(prefix="hab_%s_" % tag)
        with net.name_scope():
            net.add(nn.Dense(hidden, activation="relu"),
                    nn.Dense(hidden, activation="relu"), nn.Dense(10))
        net.initialize()
        tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-3})
        step = tr.fuse_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
        rng = np.random.RandomState(0)
        x = nd.array(rng.uniform(-1, 1, (batch, 32)).astype(np.float32))
        y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
        with engine.bulk(4):
            for _ in range(warmup):
                step(x, y).wait_to_read()
            h0 = profiler.host_sync_count()
            losses = [step(x, y) for _ in range(iters)]
            nd.waitall()
            syncs = profiler.host_sync_count() - h0
        blob = b"".join(v.asnumpy().astype(np.float32).tobytes()
                        for v in losses)
        mon = step._health_mon
        return syncs, blob, mon.anomaly_count if mon is not None else 0

    off_syncs, off_blob, _ = run("off", False)
    on_syncs, on_blob, quiet = run("on", True)
    assert off_syncs == on_syncs, (off_syncs, on_syncs)
    assert off_blob == on_blob
    assert quiet == 0
    _, _, anomalies = run(
        "spike", True, fault="grad_spike:layer=0,after=2,scale=1e6,n=1")
    assert anomalies > 0, "seeded grad spike never detected"
