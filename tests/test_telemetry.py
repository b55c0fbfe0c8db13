"""Telemetry subsystem (mxnet_tpu/telemetry.py): typed metrics registry,
step-phase spans, distributed RPC tracing, and export.

The load-bearing properties:

- registry semantics: log-bucket histograms, label dedup (same labels →
  the SAME child), kind/schema mismatch is a hard error, everything
  survives a thread hammer;
- the step timeline costs ZERO new host syncs: a fused run with the
  JSONL sink on performs exactly as many device reads as with it off,
  and every dispatched step retires exactly once;
- a trace id injected at a KVStore push is observable in the
  server-side span log of a real in-process AsyncParamServer round-trip;
- the JSONL sink is flushed (durably on disk) by ``nd.waitall()``;
- ``render_prometheus()`` is format-stable and exposes the acceptance
  metrics (step latency, dispatch depth, RPC latency, lost workers,
  skipped non-finite steps).
"""
import json
import os
import threading
import uuid

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, profiler, resilience, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, nn

_loss_fn = mx.gluon.loss.L2Loss()


@pytest.fixture(autouse=True)
def _drained():
    """Leave no in-flight tokens behind for the next test."""
    yield
    engine.wait_all()


def _uname(base):
    """Registry-unique metric name (the default registry is process
    global; tests must not collide with each other or the framework)."""
    return "%s_%s" % (base, uuid.uuid4().hex[:8])


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_histogram_buckets_merge_quantile():
    h = telemetry.Histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["counts"] == [1, 1, 1, 1]  # one per bucket + one +Inf
    assert snap["count"] == 4 and snap["sum"] == 555.5

    other = telemetry.Histogram("h2", buckets=(1.0, 10.0, 100.0))
    other.observe(2.0)
    h.merge(other)
    assert h.snapshot()["counts"] == [1, 2, 1, 1]
    assert h.snapshot()["count"] == 5
    assert h.quantile(0.5) == 10.0  # rank 2.5 lands in the (1,10] bucket

    mismatched = telemetry.Histogram("h3", buckets=(2.0, 20.0))
    with pytest.raises(MXNetError):
        h.merge(mismatched)

    # boundary values are inclusive (Prometheus le semantics)
    edge = telemetry.Histogram("h4", buckets=(1.0, 10.0))
    edge.observe(1.0)
    assert edge.snapshot()["counts"][0] == 1

    # default buckets are log-scale and cover us .. minutes
    assert telemetry.DEFAULT_BUCKETS[0] == 1e-6
    assert telemetry.DEFAULT_BUCKETS[-1] > 600


def test_registry_dedup_and_mismatch():
    name = _uname("requests_total")
    fam = telemetry.counter(name, "x", ("code",))
    assert telemetry.counter(name, "ignored", ("code",)) is fam
    # label dedup: identical label values return the SAME child cell
    assert fam.labels(code="200") is fam.labels(code="200")
    assert fam.labels(code="200") is not fam.labels(code="500")
    with pytest.raises(MXNetError):
        telemetry.counter(name, labelnames=("other",))  # schema mismatch
    with pytest.raises(MXNetError):
        telemetry.gauge(name)  # kind mismatch
    with pytest.raises(MXNetError):
        fam.labels(nope="1")  # unknown label
    with pytest.raises(MXNetError):
        fam.labels()  # missing label


def test_registry_thread_hammer():
    n_threads, per_thread = 8, 2000
    c = telemetry.counter(_uname("hammer_total"))
    g = telemetry.gauge(_uname("hammer_gauge"))
    h = telemetry.histogram(_uname("hammer_seconds"), labelnames=("p",))

    def hammer(tid):
        cell = h.labels(p=str(tid % 2))
        for i in range(per_thread):
            c.inc()
            g.inc()
            cell.observe(1e-5 * (i % 7 + 1))

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert c.value == total
    assert g.value == total
    got = sum(h.labels(p=s).snapshot()["count"] for s in ("0", "1"))
    assert got == total


def test_render_prometheus_golden():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("requests_total", "Total requests.", ("code",))
    c.labels(code="200").inc(3)
    c.labels(code="500").inc()
    reg.gauge("queue_depth", "Depth.").set(2)
    h = reg.histogram("latency_seconds", "Latency.", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    expected = "\n".join([
        '# HELP latency_seconds Latency.',
        '# TYPE latency_seconds histogram',
        'latency_seconds_bucket{le="0.1"} 1',
        'latency_seconds_bucket{le="1"} 2',
        'latency_seconds_bucket{le="+Inf"} 3',
        'latency_seconds_sum 5.55',
        'latency_seconds_count 3',
        '# HELP queue_depth Depth.',
        '# TYPE queue_depth gauge',
        'queue_depth 2',
        '# HELP requests_total Total requests.',
        '# TYPE requests_total counter',
        'requests_total{code="200"} 3',
        'requests_total{code="500"} 1',
    ]) + "\n"
    assert reg.render_prometheus() == expected


# ---------------------------------------------------------------------------
# step-phase timeline: 3-step fused run
# ---------------------------------------------------------------------------
def _make_net(prefix):
    mx.random.seed(7)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    return net, tr


def _fused_syncs(prefix):
    """Host syncs over a 3-step fused window (compile/warmup excluded)."""
    net, tr = _make_net(prefix)
    step = tr.fuse_step(net, _loss_fn)
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (8, 8)).astype(np.float32))
    y = nd.array(rng.uniform(-1, 1, (8, 4)).astype(np.float32))
    with engine.bulk(2):
        step(x, y)
        nd.waitall()  # build + compile + land the warmup token
        h0 = profiler.host_sync_count()
        for _ in range(3):
            step(x, y)
        nd.waitall()
        return profiler.host_sync_count() - h0


def test_step_timeline_three_step_run_no_new_syncs(monkeypatch, tmp_path):
    path = str(tmp_path / "spans.jsonl")

    def latency_count():
        return telemetry.histogram(
            "mxt_step_latency_seconds",
            labelnames=("stream",)).labels("fused_step") \
            .snapshot()["count"]

    monkeypatch.delenv("MXT_TELEMETRY_JSONL", raising=False)
    syncs_off = _fused_syncs("tl_off_")

    monkeypatch.setenv("MXT_TELEMETRY_JSONL", path)
    n0 = latency_count()
    syncs_on = _fused_syncs("tl_on_")

    # telemetry (registry + JSONL sink) adds ZERO host syncs to the hot
    # path: identical runs read the device identically either way
    assert syncs_on == syncs_off

    # every dispatched step retired exactly once into the latency
    # histogram (warmup + 3 timed steps)
    assert latency_count() - n0 == 4

    telemetry.flush()
    rows = [json.loads(line) for line in open(path)]
    retire = [r for r in rows if r.get("kind") == "span"
              and r.get("name") == "retire"
              and r.get("stream") == "fused_step"]
    # exactly ONE retire span per step, in dispatch order
    assert [r["step"] for r in retire] == [1, 2, 3, 4]
    phases = {r.get("name") for r in rows if r.get("kind") == "span"}
    assert {"dispatch", "in_flight", "retire"} <= phases
    # the dispatch-depth occupancy histogram saw the window fill
    occ = telemetry.registry().get("mxt_dispatch_depth_occupancy")
    assert occ is not None and occ.snapshot()["count"] >= 4


def test_dataloader_data_wait_phase():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    x = np.arange(32, dtype="f4").reshape(8, 4)
    loader = DataLoader(ArrayDataset(x), batch_size=4)
    h = telemetry.histogram("mxt_step_phase_seconds",
                            labelnames=("phase",)).labels("data_wait")
    n0 = h.snapshot()["count"]
    batches = list(loader)
    assert len(batches) == 2
    assert h.snapshot()["count"] - n0 == 2  # one data_wait per batch


# ---------------------------------------------------------------------------
# distributed RPC tracing
# ---------------------------------------------------------------------------
def test_rpc_trace_roundtrip_through_real_server():
    from mxnet_tpu import async_server

    srv = async_server.AsyncParamServer("127.0.0.1", 0)
    port = srv._sock.getsockname()[1]
    cli = async_server.AsyncClient("127.0.0.1", port)
    tid = "feedface%08x" % os.getpid()
    try:
        telemetry.clear_rpc_spans()
        with telemetry.trace_scope(tid) as scoped:
            assert scoped == tid
            cli.request("init", "0", np.ones((2, 2)))
            cli.request("push", "0", np.full((2, 2), 3.0))
            pulled = cli.request("pull", "0")
        np.testing.assert_array_equal(pulled, np.full((2, 2), 3.0))
        spans = telemetry.rpc_spans()
        srv_push = [s for s in spans if s["side"] == "server"
                    and s["op"] == "push"]
        cli_push = [s for s in spans if s["side"] == "client"
                    and s["op"] == "push"]
        # the injected trace id crossed the wire and is observable in
        # the SERVER-side span log for that very RPC
        assert srv_push and srv_push[-1]["trace_id"] == tid
        assert cli_push and cli_push[-1]["trace_id"] == tid
        # client and server logged the SAME attempt span
        assert cli_push[-1]["span_id"] == srv_push[-1]["span_id"]
        assert srv_push[-1]["status"] == "ok"
        assert srv_push[-1]["bytes"] and srv_push[-1]["latency_s"] >= 0
        # every op of the scope shares the one trace (init/push/pull)
        scoped_ops = {s["op"] for s in spans if s["trace_id"] == tid}
        assert {"init", "push", "pull"} <= scoped_ops
    finally:
        cli.close()
        srv.close()

    # per-op RPC metrics landed for both sides
    fam = telemetry.registry().get("mxt_kvstore_rpc_latency_seconds")
    assert fam.labels("server", "push").snapshot()["count"] >= 1
    assert fam.labels("client", "pull").snapshot()["count"] >= 1


def test_rpc_spans_without_explicit_trace():
    """AsyncClient generates a trace per request when no scope is
    installed — frames are never untraced."""
    from mxnet_tpu import async_server

    srv = async_server.AsyncParamServer("127.0.0.1", 0)
    port = srv._sock.getsockname()[1]
    cli = async_server.AsyncClient("127.0.0.1", port)
    try:
        telemetry.clear_rpc_spans()
        cli.request("init", "k", np.zeros(3))
        spans = [s for s in telemetry.rpc_spans()
                 if s["side"] == "server" and s["op"] == "init"]
        assert spans and spans[-1]["trace_id"]
    finally:
        cli.close()
        srv.close()


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------
def test_jsonl_sink_flush_on_waitall(monkeypatch, tmp_path):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXT_TELEMETRY_JSONL", path)
    telemetry.emit_event("unit_test_event", payload=42)
    nd.waitall()  # the barrier flushes the sink
    rows = [json.loads(line) for line in open(path)]
    mine = [r for r in rows if r.get("kind") == "unit_test_event"]
    assert mine and mine[0]["payload"] == 42
    assert "ts" in mine[0]


def test_render_exposes_acceptance_metrics():
    """render_prometheus() carries at least: step latency, dispatch
    depth, KVStore RPC latency, lost workers, skipped non-finite
    steps."""
    from mxnet_tpu import membership

    telemetry.record_step_retired("selftest", 1, 1e-3)
    telemetry.record_rpc("server", "push", seconds=1e-4, nbytes=64,
                         trace=("t", "s", 0), key="0")
    resilience.record_skipped_step(0)
    membership.record_lost_workers(0)
    profiler.set_gauge("dispatch_depth", 0)
    text = telemetry.render_prometheus()
    for needed in ("mxt_step_latency_seconds_bucket",
                   "dispatch_depth",
                   "mxt_kvstore_rpc_latency_seconds_bucket",
                   "lost_workers",
                   "skipped_nonfinite_steps",
                   "mxt_host_syncs_total",
                   "mxt_xla_launches_total"):
        assert needed in text, "missing %s in exposition" % needed


def test_moe_counts_are_published_whole():
    """What an expert-parallel model counted on the device comes back as it
    was recorded, and each count is a gauge of the exposition: slots by
    layer and held expert, slots lost, further blocks run."""
    counts = {"expert_load": [[3, 0, 5], [1, 2, 4]], "slots_lost": 0, "blocks_run": 7}
    telemetry.record_moe_counts(**counts)
    assert telemetry.moe_counts() == counts
    text = telemetry.render_prometheus()
    assert 'mxt_moe_expert_slots{layer="1",expert="2"} 4' in text
    assert "mxt_moe_slots_lost 0" in text and "mxt_moe_blocks_run 7" in text


def test_http_endpoint_serves_metrics():
    import urllib.request

    srv = telemetry.start_http_server(0)
    port = srv.server_address[1]
    assert telemetry.http_port() == port
    telemetry.counter(_uname("http_probe_total")).inc()
    with urllib.request.urlopen("http://127.0.0.1:%d/metrics" % port,
                                timeout=5) as r:
        body = r.read().decode("utf-8")
    assert "# TYPE" in body and "http_probe_total" in body


def test_mxt_top_parses_exposition():
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import mxt_top
    finally:
        sys.path.pop(0)
    text = ('a_total{x="1"} 3\n'
            'lat_bucket{le="0.1"} 1\n'
            'lat_bucket{le="+Inf"} 4\n'
            'lat_count 4\n')
    s = mxt_top.parse_prometheus(text)
    assert mxt_top.metric_sum(s, "a_total") == 3
    p50, p99 = mxt_top.histogram_quantiles(s, "lat", (0.5, 0.99))
    assert p50 == 0.1 or p50 is not None


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------
def test_monitor_default_stat_single_batched_read():
    mon = mx.monitor.Monitor(interval=1)
    mon.tic()
    rng = np.random.RandomState(3)
    arrs = [nd.array(rng.normal(size=(4, 5)).astype("f4"))
            for _ in range(6)]
    h0 = profiler.host_sync_count()
    for i, a in enumerate(arrs):
        mon.stat_helper("tap%d" % i, a)
    assert profiler.host_sync_count() == h0  # stats stay on device
    stats = mon.toc()
    assert profiler.host_sync_count() - h0 == 1  # ONE read per tap batch
    assert len(stats) == 6
    for (_, _, v), a in zip(stats, arrs):
        np.testing.assert_allclose(
            v, np.abs(a.asnumpy()).mean(), rtol=1e-6)


def test_speedometer_jsonl_async_health_fields(tmp_path):
    path = str(tmp_path / "rows.jsonl")
    speedo = mx.callback.Speedometer(8, frequent=2, jsonl=path,
                                     config="telemetry_test")

    class _P:
        epoch = 0
        eval_metric = None
        nbatch = 0

    for i in range(5):
        p = _P()
        p.nbatch = i
        profiler.record_host_sync()
        profiler.record_launch(2)
        speedo(p)
    rows = [json.loads(line) for line in open(path)]
    assert len(rows) == 2  # batches 2 and 4
    for row in rows:
        assert "dispatch_depth" in row
        assert row["launches_per_step"] >= 1.0
        assert row["host_syncs_per_step"] >= 0.0
    # reset-aware: a counter reset mid-window must not go negative
    profiler.reset_host_sync_count()
    profiler.reset_launch_count()
    p = _P()
    p.nbatch = 6
    speedo(p)
    rows = [json.loads(line) for line in open(path)]
    assert rows[-1]["host_syncs_per_step"] >= 0.0
    assert rows[-1]["launches_per_step"] >= 0.0


def test_fused_step_telemetry_on_against_off(monkeypatch, tmp_path):
    """The same fused step with the telemetry JSONL sink off and on:
    the sink adds no launch and no host sync to a step, it wrote events,
    and the exported page carries both step counters."""
    batch, hidden, warmup, iters = 8, 16, 1, 6
    sink = tmp_path / "events.jsonl"

    def run(tag, sink_on):
        if sink_on:
            monkeypatch.setenv("MXT_TELEMETRY_JSONL", str(sink))
        else:
            monkeypatch.delenv("MXT_TELEMETRY_JSONL", raising=False)
        mx.random.seed(0)
        net = nn.Sequential(prefix="tab_%s_" % tag)
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
            n0, h0 = profiler.launch_count(), profiler.host_sync_count()
            for _ in range(iters):
                step(x, y)
            nd.waitall()
            return (profiler.launch_count() - n0,
                    profiler.host_sync_count() - h0)

    off = run("off", False)
    on = run("on", True)
    assert on == off, (on, off)
    assert on[0] == iters  # one launch a step, sink or no sink
    telemetry.flush()
    assert sum(1 for _ in open(sink)) > 0
    page = telemetry.render_prometheus()
    for name in ("mxt_xla_launches_total", "mxt_host_syncs_total"):
        assert name in page, name


def test_profiler_shims_ride_registry():
    """counter_value/set_gauge still work AND the values show in the
    Prometheus exposition (the registry is the one storage)."""
    name = _uname("shim_counter")
    ctr = profiler.Counter(None, name, 0)
    ctr.increment(5)
    assert profiler.counter_value(name) == 5
    assert name in profiler._counters  # the live-view back-compat path
    gname = _uname("shim_gauge")
    profiler.set_gauge(gname, 7)
    assert profiler.gauge_value(gname) == 7
    text = telemetry.render_prometheus()
    assert name in text and gname in text


def test_flash_heads_per_step_counts_traces_and_trace_cell_prints_it(
        monkeypatch, capsys, tmp_path):
    """``mxt_flash_heads_per_step{kernel, heads}``: one count a traced flash
    kernel under the heads its grid step takes, none a call of the compiled
    program; ``tools/trace_cell.py`` reads it with the branches and prints it
    on its ``scoped`` line."""
    import importlib.util
    import types

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    q = jnp.ones((4, 4, 128, 16), jnp.float32)
    lse = jnp.zeros((4, 4, 128), jnp.float32)

    def delta(fn):
        before = telemetry.flash_heads_per_step()
        fn()
        after = telemetry.flash_heads_per_step()
        return {(k, h): n - before.get(k, {}).get(h, 0)
                for k, by in after.items() for h, n in by.items()
                if n != before.get(k, {}).get(h, 0)}

    fwd = jax.jit(lambda q_, g: A._flash_forward_pallas(
        q_, q_, q_, None, False, 0.25, 128, 128, True, heads_per_step=g)[0],
        static_argnums=1)
    assert delta(lambda: (fwd(q, 4), fwd(q, 4), fwd(q, 4))) == {("fwd", "4"): 1}
    bwd = jax.jit(lambda q_: A._flash_backward_pallas(
        q_, q_, q_, None, q_, lse, q_, False, 0.25, 128, 128, True,
        heads_per_step=2)[0])
    assert delta(lambda: (bwd(q), bwd(q))) == {("bwd", "2"): 1}
    # the rule, not a forced count: 16 grid steps are too few for it
    assert delta(lambda: A._flash_forward_pallas(
        q, q, q, None, False, 0.25, 128, 128, True)) == {("fwd", "1"): 1}
    assert 'mxt_flash_heads_per_step{kernel="fwd",heads="4"}' in \
        telemetry.render_prometheus()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_cell_under_test", os.path.join(root, "tools", "trace_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    args = types.SimpleNamespace(seed=0, seconds=1.0, trace=1, rehearse=True)

    def fake_run(argv):  # the run itself: an empty trace, and its clean-up
        ctx = tool.Context(args, {}, {}, {}, [])
        ctx._trace_dirs.append(str(tmp_path))
        ctx.cleanup()
        return 0

    monkeypatch.setattr(tool.bench, "main", fake_run)
    capsys.readouterr()
    assert tool.main([]) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"scoped"')][-1]
    heads = json.loads(line)["scoped"]["flash_heads_per_step"]
    assert heads["fwd"]["4"] >= 1 and heads["bwd"]["2"] >= 1


def test_flash_layout_counts_traces_and_trace_cell_prints_it(
        monkeypatch, capsys, tmp_path):
    """``mxt_flash_layout_total{kernel, layout}``: one count a traced flash
    pass under the layout its operands lie in (``in_place`` where
    ``flash_attention_qkv`` reads the fused projection as it lies,
    ``heads_major`` for ``flash_attention``'s (B, H, T, D) operands, a
    fallen-back ``flash_attention_qkv`` among them), none a call of the
    compiled program; ``tools/trace_cell.py`` prints it on its ``scoped``
    line beside ``flash_heads_per_step``."""
    import importlib.util
    import types

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    def delta(fn):
        before = telemetry.flash_layouts()
        fn()
        after = telemetry.flash_layouts()
        return {(k, l): n - before.get(k, {}).get(l, 0)
                for k, by in after.items() for l, n in by.items()
                if n != before.get(k, {}).get(l, 0)}

    qkv = jnp.ones((2, 128, 3 * 2 * 64), jnp.float32)
    loss = lambda x: A.flash_attention_qkv(x, num_heads=2).sum()  # noqa: E731
    # the CPU: the operator falls back, and says so, once a trace
    fwd = jax.jit(loss)
    assert delta(lambda: (fwd(qkv), fwd(qkv), fwd(qkv))) == {
        ("fwd", "heads_major"): 1}
    grad = jax.jit(jax.grad(loss))
    assert delta(lambda: (grad(qkv), grad(qkv))) == {
        ("fwd", "heads_major"): 1, ("bwd", "heads_major"): 1}
    q = jnp.ones((2, 2, 128, 64), jnp.float32)
    assert delta(lambda: A.flash_attention(q, q, q)) == {("fwd", "heads_major"): 1}
    # a TPU (the kernels in interpret mode here) at a shape the rule takes
    with monkeypatch.context() as m:
        m.setattr(A, "on_tpu", lambda: True)
        m.setattr(A, "_HEAD_MIN_GRID", 4)
        for name in ("_qkv_forward_pallas", "_qkv_backward_pallas"):
            m.setattr(A, name, _interpreted(getattr(A, name)))
        grad = jax.jit(jax.grad(loss))
        assert delta(lambda: (grad(qkv), grad(qkv))) == {
            ("fwd", "in_place"): 1, ("bwd", "in_place"): 1}
    assert 'mxt_flash_layout_total{kernel="bwd",layout="in_place"}' in \
        telemetry.render_prometheus()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_cell_under_test", os.path.join(root, "tools", "trace_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    args = types.SimpleNamespace(seed=0, seconds=1.0, trace=1, rehearse=True)

    def fake_run(argv):  # the run itself: an empty trace, and its clean-up
        ctx = tool.Context(args, {}, {}, {}, [])
        ctx._trace_dirs.append(str(tmp_path))
        ctx.cleanup()
        return 0

    monkeypatch.setattr(tool.bench, "main", fake_run)
    capsys.readouterr()
    assert tool.main([]) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"scoped"')][-1]
    layouts = json.loads(line)["scoped"]["flash_layouts"]
    assert layouts["fwd"]["in_place"] >= 1 and layouts["bwd"]["heads_major"] >= 1


def _interpreted(kernel_call):
    """An in-place ``pallas_call`` builder with ``interpret`` forced on."""
    def call(*args, interpret=False, **kwargs):
        return kernel_call(*args, interpret=True, **kwargs)
    return call
