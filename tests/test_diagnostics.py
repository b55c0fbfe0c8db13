"""Flight recorder & diagnostics (mxnet_tpu/diagnostics.py): ring
buffer, hang watchdog, HBM ledger, goodput accounting, post-mortems,
and the /debug/* routes.

The load-bearing properties:

- the flight recorder is a bounded ring tapped off telemetry events —
  ordering preserved, oldest dropped first, every existing event source
  (spans, RPC spans, checkpoint/reshard/membership events) lands in it;
- the watchdog detects a deliberately-frozen in-flight window with a
  FAKE clock (no sleeps): stall reports carry thread stacks, window
  state, and the recorder tail, dump a parseable post-mortem, and
  re-arm on progress;
- seeded ``MXT_FAULT`` ``worker_freeze``/``kv_drop`` chaos ends in a
  TYPED outcome (stall report with post-mortem; KVStoreError with a
  flight event) instead of a silent hang, and ``abort`` mode dies with
  WATCHDOG_EXIT_CODE that ``tools/launch.py --respawn`` heals;
- the HBM ledger covers params/optimizer/inflight pools on a live
  fused run AND kv_cache on a serving run, peaks are monotone,
  reconciliation degrades gracefully on CPU, and a forced allocation
  failure re-raises annotated with the ledger snapshot;
- goodput arithmetic is exact under injected checkpoint+reshard pauses;
- diagnostics add ZERO host syncs to a fused 3-step run (armed vs
  disarmed parity).
"""
import glob
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request
import uuid

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import diagnostics as dg
from mxnet_tpu import engine, nd, profiler, resilience, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.resilience import KVStoreError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_loss_fn = mx.gluon.loss.L2Loss()


def _seed():
    """Injector seed — swept by tools/chaos_matrix.sh via MXT_CHAOS_SEED."""
    return int(os.environ.get("MXT_CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _clean():
    """Recorder tap installed (an earlier disable() may have removed
    it), window drained on exit, goodput epoch restored."""
    dg.recorder()
    yield
    engine.wait_all()
    dg.reset_goodput()


def _subenv(tmp_path, **extra):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               MXT_POSTMORTEM_DIR=str(tmp_path))
    env.pop("MXT_WATCHDOG_TIMEOUT", None)
    env.update(extra)
    return env


def _postmortems(tmp_path):
    return sorted(glob.glob(os.path.join(str(tmp_path),
                                         "mxt-postmortem-*.json")))


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_ring_bounds_ordering():
    r = dg.FlightRecorder(size=8)
    for i in range(20):
        r.record("e", i=i)
    assert len(r) == 8
    assert r.recorded == 20
    assert [e["i"] for e in r.events()] == list(range(12, 20))
    assert [e["i"] for e in r.events(last=3)] == [17, 18, 19]
    assert all(e["kind"] == "e" and "ts" in e for e in r.events())
    r.clear()
    assert len(r) == 0
    with pytest.raises(MXNetError):
        dg.FlightRecorder(size=0)


def test_recorder_taps_every_telemetry_event():
    rec = dg.recorder()
    marker = "tap_probe_%s" % uuid.uuid4().hex[:8]
    telemetry.emit_event(marker, foo="bar")
    dg.record_event(marker, foo="baz")  # the diagnostics spelling
    evs = [e for e in rec.events() if e["kind"] == marker]
    assert [e["foo"] for e in evs] == ["bar", "baz"]


# ---------------------------------------------------------------------------
# progress sources + hang watchdog (fake clock — zero sleeps)
# ---------------------------------------------------------------------------
def test_pending_scope_and_progress_counters():
    name = "unit_rpc_%s" % uuid.uuid4().hex[:6]
    with dg.pending_scope(name):
        count, pend = dg.progress_counts()[name]
        assert (count, pend) == (0, 1)
    assert dg.progress_counts()[name][1] == 0
    dg.progress(name)
    dg.progress(name)
    assert dg.progress_counts()[name][0] == 2
    dg.unregister_source(name)
    assert name not in dg.progress_counts()


def test_watchdog_fake_clock_detects_frozen_window(monkeypatch, tmp_path):
    import jax.numpy as jnp

    nd.waitall()  # only OUR stream may be pending below
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    w = engine.InflightWindow(name="frozen_test")
    with engine.bulk(4):
        w.push(jnp.float32(1.0))  # 1 push < K: stays in flight forever
    assert w.pending == 1

    wd = dg.Watchdog(timeout=5.0, action="report", interval=1.0,
                     clock=lambda: 0.0)
    assert wd.check(now=0.0) == []          # first sight seeds
    assert wd.check(now=4.0) == []          # under the timeout
    stalled = wd.check(now=10.0)
    assert "engine_retire" in stalled
    rep = wd.stall_reports[-1]
    assert rep["pending"] == 1 and rep["action"] == "report"
    # the report carries the frozen window's state...
    assert any(s["name"] == "frozen_test" and s["pending"] == 1
               for s in rep["windows"])
    # ...every thread's stack (this function is on the main one)...
    flat = "\n".join("\n".join(s) for s in rep["threads"].values())
    assert "test_watchdog_fake_clock_detects_frozen_window" in flat
    # ...and the flight-recorder tail (the push's dispatch span)
    assert rep["flight_recorder_tail"]

    # the stall counter and the post-mortem landed
    fam = telemetry.registry().get("mxt_watchdog_stalls_total")
    assert fam is not None and fam.labels("engine_retire").value >= 1
    pms = _postmortems(tmp_path)
    assert pms
    doc = json.load(open(pms[-1]))
    assert doc["reason"] == "watchdog:engine_retire"
    assert any(s["name"] == "frozen_test" for s in doc["windows"])

    # within one timeout window the stall re-reports at most once
    assert "engine_retire" in wd.check(now=11.0)
    assert len(wd.stall_reports) == 1
    # progress re-arms: draining the window moves the retire counter
    w.flush()
    assert wd.check(now=12.0) == []


def test_watchdog_suppressed_during_profiler_capture():
    """A profiler capture pauses every loop by design; the watchdog
    must re-arm instead of reporting (abort mode would otherwise kill
    a healthy replica for being profiled)."""
    name = "cap_%s" % uuid.uuid4().hex[:6]
    dg.register_source(name, pending_fn=lambda: 1)
    try:
        wd = dg.Watchdog(timeout=1.0, action="report", interval=1.0,
                         dump=False, clock=lambda: 0.0)
        wd.check(now=0.0)
        assert dg._trace_lock.acquire(blocking=False)
        try:
            assert wd.check(now=100.0) == []  # capture in flight: re-arm
        finally:
            dg._trace_lock.release()
        # the re-arm reset the stall clock: still nothing at +100+eps
        assert name not in wd.check(now=100.5)
        # ...but a real stall after the capture still reports
        assert name in wd.check(now=200.0)
    finally:
        dg.unregister_source(name)


def test_watchdog_idle_source_never_stalls():
    name = "idle_%s" % uuid.uuid4().hex[:6]
    dg.register_source(name, pending_fn=lambda: 0)
    try:
        wd = dg.Watchdog(timeout=1.0, action="report", interval=1.0,
                         dump=False, clock=lambda: 0.0)
        wd.check(now=0.0)
        assert name not in wd.check(now=100.0)
    finally:
        dg.unregister_source(name)


def test_watchdog_config_validation(monkeypatch):
    monkeypatch.delenv("MXT_WATCHDOG_TIMEOUT", raising=False)
    with pytest.raises(MXNetError):
        dg.Watchdog()  # no timeout anywhere
    with pytest.raises(MXNetError):
        dg.Watchdog(timeout=1.0, action="explode")


def test_thread_stacks_contents():
    stacks = dg.thread_stacks()
    assert any("MainThread" in name for name in stacks)
    flat = "\n".join("\n".join(s) for s in stacks.values())
    assert "test_thread_stacks_contents" in flat


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------
def test_hbm_ledger_set_release_peak_and_export():
    pool = "testpool_%s" % uuid.uuid4().hex[:6]
    led = dg.ledger()
    assert led.set(pool, "a", 100) == 100
    assert led.set(pool, "b", 50) == 150
    assert led.set(pool, "a", 10) == 60       # replace, not accumulate
    snap = led.snapshot()[pool]
    assert snap["bytes"] == 60
    assert snap["peak_bytes"] == 150          # watermark is monotone
    assert snap["entries"] == {"a": 10, "b": 50}
    assert led.release(pool, "a") == 10
    assert led.pool_bytes(pool) == 50
    text = telemetry.render_prometheus()
    assert 'mxt_hbm_bytes{pool="%s"} 50' % pool in text
    assert 'mxt_hbm_peak_bytes{pool="%s"} 150' % pool in text
    led.release(pool, "b")
    assert led.pool_bytes(pool) == 0


def test_hbm_reconcile_tolerates_missing_device_stats():
    pool = "recon_%s" % uuid.uuid4().hex[:6]
    dg.hbm_set(pool, "x", 4096)
    try:
        out = dg.reconcile()
        assert out["ledger_bytes"] >= 4096
        # CPU backends report no memory_stats: reconciliation degrades
        # to ledger-only instead of failing (on TPU delta_bytes is real)
        if out["device_bytes_in_use"] is None:
            assert out["delta_bytes"] is None
            assert out["within_tolerance"] is True
        else:
            assert out["delta_bytes"] == \
                out["device_bytes_in_use"] - out["ledger_bytes"]
    finally:
        dg.hbm_release(pool, "x")


def _fused_run(prefix, steps=3):
    mx.random.seed(7)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    step = tr.fuse_step(net, _loss_fn)
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (8, 8)).astype(np.float32))
    y = nd.array(rng.uniform(-1, 1, (8, 4)).astype(np.float32))
    with engine.bulk(2):
        step(x, y)
        nd.waitall()  # build + compile + land the warmup token
        h0 = profiler.host_sync_count()
        for _ in range(steps):
            step(x, y)
        nd.waitall()
        return step, profiler.host_sync_count() - h0


def test_hbm_pools_cover_fused_and_serving_runs():
    # live fused-step run: params + optimizer registered at first
    # dispatch, the window's staged bytes under inflight_window
    step, _ = _fused_run("hbm_fused_")
    snap = dg.ledger().snapshot()
    key = step._sig_entry()
    assert snap["params"]["entries"][key] > 0
    assert snap["optimizer"]["entries"][key] > 0
    assert "inflight_window" in snap

    # live serving run: the KV page pool + the replica's weights
    from mxnet_tpu import serving

    model = serving.TinyDecoder(vocab=64, num_layers=1, num_heads=1,
                                head_dim=8, max_len=64)
    cache = serving.PagedKVCache(1, 1, 8, num_pages=8, page_size=8)
    eng = serving.DecodeEngine(model, slots=2, cache=cache,
                               prefill_buckets=(8,), max_context=32)
    sched = serving.ContinuousBatcher(eng)
    sched.submit(serving.Request([3, 5, 7], max_new_tokens=3))
    done = sched.run()
    assert len(done) == 1 and done[0].state == "completed"
    snap = dg.ledger().snapshot()
    assert snap["kv_cache"]["bytes"] >= \
        cache.k_pages.nbytes + cache.v_pages.nbytes
    assert snap["params"]["entries"]["decode_engine"] > 0
    # the decode loop registered with the watchdog and made progress
    assert dg.progress_counts()["serving_decode"][0] > 0


def test_oom_reraises_annotated_with_ledger(monkeypatch, tmp_path):
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    pool = "oomtest_%s" % uuid.uuid4().hex[:6]
    dg.hbm_set(pool, "big", 123456)
    err = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "9437184 bytes.")
    try:
        with pytest.raises(MXNetError) as ei:
            try:
                raise err
            except Exception as e:
                dg.reraise_if_oom(e, "unit_site")
                raise
        msg = str(ei.value)
        assert "HBM ledger" in msg and pool in msg and "unit_site" in msg
        assert ei.value.__cause__ is err
        # a non-OOM error passes through untouched
        assert dg.reraise_if_oom(ValueError("boom"), "unit_site") is None
        # the ring recorded the oom event with the pool breakdown
        oom = [e for e in dg.recorder().events() if e["kind"] == "oom"]
        assert oom and oom[-1]["site"] == "unit_site"
        assert oom[-1]["hbm"][pool] == 123456
    finally:
        dg.hbm_release(pool, "big")


def test_fused_step_dispatch_oom_annotated():
    step, _ = _fused_run("oom_fused_", steps=1)

    def raiser(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    step._jit = raiser
    x = nd.array(np.zeros((8, 8), np.float32))
    y = nd.array(np.zeros((8, 4), np.float32))
    with pytest.raises(MXNetError, match="fused_step"):
        step(x, y)


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------
def test_goodput_arithmetic_with_injected_pauses():
    dg.reset_goodput(start=0.0)
    dg.record_lost("checkpoint", 2.0)
    dg.record_lost("checkpoint", 1.0)
    dg.record_lost("reshard", 1.5)
    snap = dg.goodput_snapshot(now=10.0)
    assert snap["elapsed_s"] == 10.0
    assert snap["lost_by_cause"]["checkpoint"] == 3.0
    assert snap["lost_by_cause"]["reshard"] == 1.5
    assert snap["lost_s"] == pytest.approx(4.5)
    assert snap["goodput_ratio"] == pytest.approx(0.55)
    # ratio floors at 0 when lost exceeds elapsed (clock skew)
    assert dg.goodput_snapshot(now=1.0)["goodput_ratio"] == 0.0
    # the counters exported
    text = telemetry.render_prometheus()
    assert 'mxt_lost_seconds_total{cause="checkpoint"}' in text
    assert "mxt_goodput_ratio" in text


def test_checkpoint_pause_lands_in_goodput(tmp_path):
    net = nn.Sequential(prefix="gp_ckpt_%s_" % uuid.uuid4().hex[:6])
    with net.name_scope():
        net.add(nn.Dense(4, in_units=4))
    net.initialize()
    dg.reset_goodput()
    mgr = resilience.CheckpointManager(str(tmp_path / "ck"), net=net)
    mgr.save(step=1)
    snap = dg.goodput_snapshot()
    assert snap["lost_by_cause"].get("checkpoint", 0.0) > 0.0
    # ...and the save event rode the flight recorder via the tap
    assert any(e["kind"] == "checkpoint_save"
               for e in dg.recorder().events())


# ---------------------------------------------------------------------------
# /debug/* routes
# ---------------------------------------------------------------------------
def _endpoint():
    if telemetry.http_port() is None:
        telemetry.start_http_server(0)
    return "http://127.0.0.1:%d" % telemetry.http_port()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def test_debug_routes_roundtrip(monkeypatch, tmp_path):
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    base = _endpoint()
    dg.record_event("debug_probe", n=1)

    status, ctype, body = _get(base + "/debug/stacks")
    assert status == 200 and "text/plain" in ctype
    assert b"MainThread" in body

    status, ctype, body = _get(base + "/debug/memory")
    assert status == 200 and "json" in ctype
    doc = json.loads(body)
    assert "hbm" in doc and "reconcile" in doc and "goodput" in doc

    status, ctype, body = _get(base + "/debug/flightrecorder")
    assert status == 200
    doc = json.loads(body)
    assert any(e["kind"] == "debug_probe" for e in doc["events"])
    assert "progress_sources" in doc and "windows" in doc

    status, _, body = _get(base + "/debug/postmortem")
    assert status == 200
    assert os.path.exists(json.loads(body)["path"])

    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(base + "/debug/nonsense")
    assert ei.value.code == 404

    # /metrics (any non-debug path) still serves the exposition
    status, _, body = _get(base + "/")
    assert status == 200 and b"# TYPE" in body


def test_debug_trace_returns_profile_archive():
    import jax.numpy as jnp

    base = _endpoint()
    # some device work for the profiler to see
    (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()
    status, ctype, body = _get(base + "/debug/trace?ms=10")
    assert status == 200 and ctype == "application/zip"
    assert body[:2] == b"PK" and len(body) > 100  # a real zip archive


# ---------------------------------------------------------------------------
# post-mortems (subprocess: handlers + unhandled exception)
# ---------------------------------------------------------------------------
_EXCEPT_WORKER = """
import mxnet_tpu as mx
from mxnet_tpu import diagnostics as dg
dg.enable(handlers=True)  # no watchdog timeout: recorder + handlers only
dg.record_event("about_to_die", step=3)
raise ValueError("chaos-test unhandled")
"""


def test_postmortem_on_unhandled_exception_subprocess(tmp_path):
    script = tmp_path / "worker_exc.py"
    script.write_text(_EXCEPT_WORKER)
    proc = subprocess.run(
        [sys.executable, str(script)], env=_subenv(tmp_path),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "chaos-test unhandled" in proc.stderr
    pms = _postmortems(tmp_path)
    assert len(pms) == 1
    doc = json.load(open(pms[0]))
    assert doc["reason"] == "unhandled:ValueError"
    assert any(e["kind"] == "about_to_die" for e in doc["events"])
    assert doc["threads"] and doc["config"]["MXT_POSTMORTEM_DIR"] == \
        str(tmp_path)


# ---------------------------------------------------------------------------
# chaos: seeded faults end in typed, diagnosable outcomes
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_worker_freeze_ends_in_watchdog_stall(monkeypatch, tmp_path):
    """The silent zombie (seeded worker_freeze: beats stop, process
    lives) becomes a typed watchdog stall report with a parseable
    post-mortem — detection on a FAKE clock, only the freeze itself
    takes (milliseconds of) real time."""
    from mxnet_tpu import async_server
    from mxnet_tpu.membership import WorkerMembership

    monkeypatch.setenv("MXT_HEARTBEAT_INTERVAL", "0.02")
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv(
        "MXT_FAULT",
        "worker_freeze:worker=0,after=1,p=1.0,seed=%d" % _seed())
    resilience.reset_faults()
    srv = async_server.AsyncParamServer("127.0.0.1", 0)
    try:
        port = srv._sock.getsockname()[1]
        m = WorkerMembership("127.0.0.1", port, 0)
        m.register()
        m.start_heartbeats()
        deadline = time.monotonic() + 10.0
        while not m.frozen and time.monotonic() < deadline:
            time.sleep(0.01)  # bounded poll, not an unconditional sleep
        assert m.frozen, "worker_freeze fault never fired"

        wd = dg.Watchdog(timeout=5.0, action="report", interval=1.0,
                         clock=lambda: 0.0)
        wd.check(now=0.0)
        stalled = wd.check(now=10.0)
        assert "membership_beat_w0" in stalled
        rep = wd.stall_reports[-1]
        assert rep["pending"] == 1
        pms = _postmortems(tmp_path)
        assert pms
        doc = json.load(open(pms[-1]))
        assert doc["reason"] == "watchdog:membership_beat_w0"
        assert doc["progress_sources"]["membership_beat_w0"]["pending"] \
            == 1
        m.stop()
        assert "membership_beat_w0" not in dg.progress_counts()
    finally:
        monkeypatch.delenv("MXT_FAULT")
        resilience.reset_faults()
        srv.close()


@pytest.mark.chaos
def test_kv_drop_ends_typed_with_flight_event(monkeypatch, tmp_path):
    """Seeded kv_drop exhausts the retry budget into a typed
    KVStoreError (never a hang) AND leaves a kv_retry_exhausted event
    in the flight recorder; the on-demand post-mortem carries it."""
    monkeypatch.setenv("MXT_FAULT", "kv_drop:p=1.0,seed=%d" % _seed())
    monkeypatch.setenv("MXT_KV_RETRIES", "1")
    monkeypatch.setenv("MXT_KV_RETRY_BASE", "0.001")
    monkeypatch.setenv("MXT_KV_RETRY_MAX", "0.002")
    monkeypatch.setenv("MXT_POSTMORTEM_DIR", str(tmp_path))
    resilience.reset_faults()
    try:
        with pytest.raises(KVStoreError):
            resilience.kv_retry("push", "w0", lambda: "ok")
        evs = [e for e in dg.recorder().events()
               if e["kind"] == "kv_retry_exhausted"]
        assert evs and evs[-1]["op"] == "push" and evs[-1]["key"] == "w0"
        path = dg.dump_postmortem(reason="chaos:kv_drop")
        doc = json.load(open(path))
        assert any(e["kind"] == "kv_retry_exhausted"
                   for e in doc["events"])
    finally:
        resilience.reset_faults()


_ABORT_WORKER = """
import glob, os, sys, time
pmdir = os.environ["MXT_POSTMORTEM_DIR"]
import mxnet_tpu as mx  # MXT_WATCHDOG_TIMEOUT (launcher --watchdog) autostarts
from mxnet_tpu import diagnostics as dg
if glob.glob(os.path.join(pmdir, "mxt-postmortem-*.json")):
    sys.exit(0)  # the respawned incarnation: the watchdog did its job
assert dg.watchdog() is not None, "launcher did not arm the watchdog"
dg.register_source("wedge", pending_fn=lambda: 1)  # work that never moves
deadline = time.time() + 30
while time.time() < deadline:
    time.sleep(0.05)  # the watchdog abort must interrupt this
sys.exit(7)  # watchdog failed to fire
"""


@pytest.mark.chaos
def test_watchdog_abort_is_typed_and_respawnable(tmp_path):
    """abort mode: the stall dumps a post-mortem then dies with
    WATCHDOG_EXIT_CODE; tools/launch.py --respawn recognizes the typed
    death and restarts the worker with its original rank/env — the
    second incarnation finds the post-mortem and exits clean."""
    script = tmp_path / "worker_wedge.py"
    script.write_text(_ABORT_WORKER)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "1", "--respawn", "--max-restarts", "1",
         "--watchdog", "0.4", "--watchdog-action", "abort",
         sys.executable, str(script)],
        env=_subenv(tmp_path), capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the launcher logged the typed death...
    assert "watchdog abort" in proc.stderr
    assert "rc=%d" % dg.WATCHDOG_EXIT_CODE in proc.stderr
    # ...and the post-mortem exists, parses, and names the stall
    pms = _postmortems(tmp_path)
    assert pms
    doc = json.load(open(pms[0]))
    assert doc["reason"] == "watchdog:wedge"
    assert doc["extra"]["stall"]["source"] == "wedge"
    assert doc["config"]["MXT_WATCHDOG_ACTION"] == "abort"


# ---------------------------------------------------------------------------
# zero host syncs + satellites
# ---------------------------------------------------------------------------
def test_diagnostics_add_zero_host_syncs():
    """A fused 3-step run performs
    IDENTICAL device reads with the diagnostics layer fully armed
    (recorder tap + watchdog daemon + ledger) vs disarmed."""
    dg.disable()
    try:
        _, syncs_off = _fused_run("dz_off_")
    finally:
        dg.recorder()  # tap back on
    wd = dg.enable(timeout=3600.0, action="report", handlers=False)
    try:
        assert wd is not None
        _, syncs_on = _fused_run("dz_on_")
    finally:
        dg.disable()
        dg.recorder()
    assert syncs_on == syncs_off


def test_mxt_top_renders_memory_and_goodput_sections():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import mxt_top
    finally:
        sys.path.pop(0)
    text = (
        'mxt_hbm_bytes{pool="params"} 1048576\n'
        'mxt_hbm_peak_bytes{pool="params"} 2097152\n'
        'mxt_hbm_bytes{pool="kv_cache"} 524288\n'
        'mxt_goodput_ratio 0.875\n'
        'mxt_lost_seconds_total{cause="checkpoint"} 12.5\n'
        'mxt_lost_seconds_total{cause="compile"} 3.25\n'
        'mxt_watchdog_stalls_total{source="engine_retire"} 2\n')
    samples = mxt_top.parse_prometheus(text)
    frame = mxt_top.render(samples, None, 0)
    assert "hbm params" in frame and "1.0MB" in frame \
        and "(peak 2.0MB)" in frame
    assert "hbm kv_cache" in frame
    assert "goodput" in frame and "0.875" in frame
    # top lost causes, largest first
    assert frame.index("checkpoint 12.50s") < frame.index("compile 3.25s")
    assert "watchdog stalls  2" in frame
    # a trainer without the diagnostics layer shows no memory noise
    bare = mxt_top.render(mxt_top.parse_prometheus("up 1\n"), None, 0)
    assert "hbm" not in bare and "goodput" not in bare


def test_host_sync_lint_covers_diagnostics():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_host_syncs as lint
    finally:
        sys.path.pop(0)
    assert "mxnet_tpu/diagnostics.py" in lint.SCAN
    assert lint.SCAN["mxnet_tpu/diagnostics.py"] == lint._ALL
    bad = lint.check(ROOT)
    assert bad == [], "unmarked sync points: %r" % bad


def test_window_states_snapshot():
    import jax.numpy as jnp

    w = engine.InflightWindow(name="ws_probe")
    staged = jnp.arange(4, dtype=jnp.float32)
    with engine.bulk(4):
        w.push(jnp.float32(0.0), value=staged)
    states = {s["name"]: s for s in engine.window_states()}
    st = states["ws_probe"]
    assert st["pending"] == 1 and st["staged"] == 1
    assert st["held_bytes"] == staged.nbytes  # the staged f32[4]
    w.flush()
    st = {s["name"]: s for s in engine.window_states()}["ws_probe"]
    assert st["pending"] == 0 and st["held_bytes"] == 0
