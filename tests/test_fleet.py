"""Fault-tolerant serving fleet (mxnet_tpu/serving/fleet.py +
serving/router.py): membership-backed replica pool, SLO-aware routing,
hedged dispatch, failover with idempotency tokens, drain/rejoin, and
kill-mid-run survival.

Fleet tests run IN-PROCESS (serving.local_serving_fleet — a real
coordinator async server on loopback, real membership registrations and
heartbeats, replicas driven co-operatively by the router) so every
scenario is deterministic: fake clocks for the hedge timing, seeded
MXT_FAULT rules (replica_kill / replica_slow) for the chaos cells swept
by tools/chaos_matrix.sh via MXT_CHAOS_SEED.
"""
import os
import time

import numpy as np
import pytest

from mxnet_tpu import serving, tuning
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import KVStoreError
from mxnet_tpu.serving import fleet as fleet_mod
from mxnet_tpu.serving import (ContinuousBatcher, DecodeEngine,
                               FleetRouter, PagedKVCache, Request,
                               StaleReplicaError, TinyDecoder)


def _seed():
    return int(os.environ.get("MXT_CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch, tmp_path):
    """Dead replicas must surface in milliseconds, not the production
    30s retry budget; every test gets its own tuning table."""
    monkeypatch.setenv("MXT_KV_RETRIES", "1")
    monkeypatch.setenv("MXT_KV_RETRY_BASE", "0.02")
    monkeypatch.setenv("MXT_KV_RETRY_MAX", "0.05")
    monkeypatch.setenv("MXT_TUNE_TABLE", str(tmp_path / "tune.json"))
    tuning.reset()
    yield
    tuning.reset()


MODEL = TinyDecoder(vocab=64, num_layers=1, num_heads=2, head_dim=8,
                    max_len=256)
PARAMS = MODEL.init_params(3)

_FREE_ENGINES = []  # drained engines recycled across tests (trace cost)


def _factory():
    while _FREE_ENGINES:
        eng = _FREE_ENGINES.pop()
        if eng.cache.pages_in_use() == 0 and not eng._seq_of_slot:
            return eng
    return DecodeEngine(
        MODEL, params=PARAMS, slots=2,
        cache=PagedKVCache(1, 2, 8, num_pages=64, page_size=8),
        prefill_buckets=(16,), max_context=64)


def _fleet(n, now_fn=time.monotonic, warm=False):
    return serving.local_serving_fleet(n, _factory, now_fn=now_fn,
                                       warm=warm)


def _close(pool, srv):
    for h in pool.replicas():
        if h.engine is not None and h.state != "dead":
            _FREE_ENGINES.append(h.engine)
        try:
            h.close()
        except Exception:  # noqa: BLE001 — killed handles
            pass
    srv.close()


def _ref(prompt, n):
    return MODEL.reference_decode(PARAMS, list(prompt), n)


def _traffic(router, n, seed, max_plen=12, max_new=6, prefix="t"):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = int(rng.randint(1, max_plen))
        mnew = int(rng.randint(2, max_new))
        out.append(router.submit(
            rng.randint(1, 64, plen).tolist(), max_new_tokens=mnew,
            token="%s%d" % (prefix, i)))
    return out


# ---------------------------------------------------------------------------
# the acceptance scenario: kill one replica mid-run
# ---------------------------------------------------------------------------
def test_fleet_kill_one_replica_acceptance():
    """2-replica fleet under mixed-length traffic, one replica killed
    mid-run: every accepted request completes with token-exact output
    vs an unkilled 1-replica oracle, failover counter > 0, p99
    bounded, and no request is decoded twice (idempotency token
    asserted — a replay returns the recorded result with zero new
    decode steps)."""
    # the unkilled 1-replica oracle over the same traffic
    pool1, srv1 = _fleet(1)
    r1 = FleetRouter(pool1)
    oracle = _traffic(r1, 8, seed=_seed())
    r1.run(max_steps=2000)
    assert all(rr.state == "completed" for rr in oracle)
    _close(pool1, srv1)

    pool, srv = _fleet(2)
    router = FleetRouter(pool)
    reqs = _traffic(router, 8, seed=_seed())
    for _ in range(4):   # let traffic spread over both replicas
        router.step()
    assert any(1 in rr.copies for rr in reqs), "nothing on replica 1"
    pool.get(1).kill()   # SIGKILL emulation: no deregister, mid-flight
    router.run(max_steps=2000)

    lats = []
    for rr, orr in zip(reqs, oracle):
        assert rr.state == "completed", (rr.token, rr.state)
        assert rr.result == orr.result == _ref(rr.prompt,
                                               rr.max_new_tokens)
        assert rr.commits == 1          # committed exactly once
        lats.append(rr.t_finish - rr.t_submit)
    assert sum(rr.failovers for rr in reqs) > 0
    assert all(rr.committed_by == 0 for rr in reqs
               if rr.failovers)        # survivors decoded the orphans
    lats.sort()
    assert lats[int(0.99 * (len(lats) - 1))] < 60.0  # p99 bounded

    # idempotency: replaying a completed token returns the recorded
    # result and decodes NOTHING
    steps0 = sum(h.batcher.steps for h in pool.replicas()
                 if h.batcher is not None)
    again = router.submit(reqs[0].prompt, token=reqs[0].token)
    assert again is reqs[0] and again.result == reqs[0].result
    assert router.replays == 1
    assert sum(h.batcher.steps for h in pool.replicas()
               if h.batcher is not None) == steps0
    _close(pool, srv)


def test_router_load_aware_dispatch():
    """Dispatch follows the queue-depth/active-slot gauges: 4 requests
    over 2 idle 2-slot replicas spread 2/2, never 4/0."""
    pool, srv = _fleet(2)
    router = FleetRouter(pool)
    reqs = _traffic(router, 4, seed=1, prefix="l")
    router.step()
    placed = [next(iter(rr.copies)) for rr in reqs]
    assert placed.count(0) == 2 and placed.count(1) == 2, placed
    router.run(max_steps=2000)
    assert all(rr.state == "completed" for rr in reqs)
    _close(pool, srv)


def test_no_routable_replicas_is_typed_error():
    pool, srv = _fleet(1)
    router = FleetRouter(pool)
    pool.get(0).kill()
    router.submit([5], max_new_tokens=2)
    with pytest.raises(KVStoreError):
        router.run(max_steps=50)
    _close(pool, srv)


# ---------------------------------------------------------------------------
# hedged dispatch (fake clock)
# ---------------------------------------------------------------------------
def test_hedge_fires_at_delay_first_completion_wins():
    """A request stalled past the hedge delay is duplicated onto the
    second replica; the first completion wins (committed once) and the
    loser is cancelled through the eviction path."""
    clock = [0.0]
    pool, srv = _fleet(2, now_fn=lambda: clock[0])
    router = FleetRouter(pool, now_fn=lambda: clock[0],
                         hedge_delay=1.0, hedge_budget=4)
    rr = router.submit([5, 9, 2], max_new_tokens=3, token="h1")
    router.step()
    rid0 = next(iter(rr.copies))
    h0 = pool.get(rid0)
    loser = h0._copies[rr.copies[rid0]]
    h0.slow_until = 1e9            # brownout: no decode progress
    router.step()
    assert rr.hedges == 0          # below the delay: no hedge yet
    clock[0] = 1.5
    router.step()
    assert rr.hedges == 1 and len(rr.copies) == 2  # fired at the delay
    router.run(max_steps=2000)
    assert rr.state == "completed" and rr.commits == 1
    assert rr.committed_by != rid0
    assert rr.result == _ref(rr.prompt, 3)
    assert loser.state == "evicted"  # loser cancelled, pages freed
    h0.slow_until = 0.0
    _close(pool, srv)


def test_hedge_budget_bounds_load():
    """hedge_budget=0 disables hedging outright — a brownout cannot
    recruit extra fleet load."""
    clock = [0.0]
    pool, srv = _fleet(2, now_fn=lambda: clock[0])
    router = FleetRouter(pool, now_fn=lambda: clock[0],
                         hedge_delay=0.1, hedge_budget=0)
    rr = router.submit([7], max_new_tokens=2, token="h2")
    router.step()
    clock[0] = 50.0
    router.step()
    assert rr.hedges == 0 and len(rr.copies) == 1
    router.run(max_steps=2000)
    assert rr.state == "completed"
    _close(pool, srv)


def test_hedge_delay_derived_from_slo():
    """Without an explicit delay, the hedge point is SLO-derived: half
    the per-request deadline (or the router's slo)."""
    pool, srv = _fleet(1)
    router = FleetRouter(pool, slo=2.0)
    a = router.submit([5], max_new_tokens=2, deadline=1.0)
    b = router.submit([5], max_new_tokens=2)
    assert a.hedge_delay == pytest.approx(0.5)   # half its deadline
    assert b.hedge_delay == pytest.approx(1.0)   # half the router slo
    router.run(max_steps=2000)
    no_slo = FleetRouter(pool)
    c = no_slo.submit([5], max_new_tokens=2)
    assert c.hedge_delay is None                 # nothing to derive
    no_slo.run(max_steps=2000)
    _close(pool, srv)


# ---------------------------------------------------------------------------
# fencing: a zombie's late reply is refused typed
# ---------------------------------------------------------------------------
def test_fenced_zombie_late_reply_refused_typed():
    """A replica fenced by the reaper whose process keeps decoding: its
    late completion raises StaleReplicaError at the accept gate, is
    counted, and is never committed — the failover copy wins."""
    pool, srv = _fleet(2)
    router = FleetRouter(pool)
    rr = router.submit([9, 1], max_new_tokens=2, token="z1")
    router.step()
    rid = next(iter(rr.copies))
    hz = pool.get(rid)
    hz.member.fenced = True   # the verdict the beat loop observes
    # the zombie decodes to completion anyway
    for _ in range(8):
        hz.batcher.step()
    hz.batcher.drain()
    # the accept gate is the typed refusal (any reply, any copy)
    with pytest.raises(StaleReplicaError):
        router.accept(hz, "any#0", "completed", [1, 2])
    # ...and the router's natural path collects the zombie's REAL
    # completion, refuses it typed (counted), marks the replica dead,
    # and fails over: the survivor's commit is the only one
    router.run(max_steps=2000)
    assert router.stale_replies >= 1
    assert rr.state == "completed" and rr.commits == 1
    assert rr.committed_by != rid
    assert rr.result == _ref(rr.prompt, 2)
    assert hz.state == "dead"
    _close(pool, srv)


def test_membership_reaper_death_listener():
    """The coordinator's reaper declares a silent replica dead; the
    pool's death listener (MembershipTable.add_death_listener reuse)
    hands it to the router's next step."""
    pool, srv = _fleet(2)
    h1 = pool.get(1)
    h1.member._stop.set()          # beats silently stop (zombie)
    if h1.member._thread is not None:
        h1.member._thread.join(timeout=5.0)
    future = time.monotonic() + 100.0
    srv.membership.heartbeat(fleet_mod._replica_member_id(0),
                             pool.get(0).generation, now=future)
    dead = srv.membership.reap(5.0, now=future)
    assert fleet_mod._replica_member_id(1) in dead
    assert pool.poll_deaths() == [1]
    assert h1.state == "dead"
    _close(pool, srv)


# ---------------------------------------------------------------------------
# drain + AOT-warm rejoin
# ---------------------------------------------------------------------------
def test_drain_migrates_queue_and_rejoin_serves_warm(tmp_path,
                                                     monkeypatch):
    """Graceful drain: queued copies migrate to peers, running ones
    finish, the replica deregisters clean; a rejoin rebuilds a FRESH
    engine that AOT-warms through tuning.warmup() + the shared compile
    cache and serves with ZERO request-path cache-miss compiles."""
    from jax._src import compilation_cache as _cc

    # the JAX variable outranks ours (tuning.setup_compile_cache): clear
    # it so the test owns its cache directory under either environment
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXT_COMPILE_CACHE_DIR", str(tmp_path / "xla"))
    _cc.reset_cache()

    def fresh_factory():
        return DecodeEngine(
            MODEL, params=PARAMS, slots=2,
            cache=PagedKVCache(1, 2, 8, num_pages=64, page_size=8),
            prefill_buckets=(16,), max_context=64)

    pool, srv = serving.local_serving_fleet(2, fresh_factory, warm=True)
    router = FleetRouter(pool)
    reqs = _traffic(router, 6, seed=2, prefix="d")
    router.step()
    n_live = len(srv.membership.view()["members"])
    router.drain(1)
    router.run(max_steps=2000)
    assert all(rr.state == "completed" for rr in reqs)
    assert all(rr.result == _ref(rr.prompt, rr.max_new_tokens)
               for rr in reqs)
    h1 = pool.get(1)
    assert h1.state == "drained"
    # deregistered clean: not a lost worker, just gone from the view
    view = srv.membership.view()
    assert fleet_mod._replica_member_id(1) not in view["members"]
    assert fleet_mod._replica_member_id(1) not in view["dead"]
    assert len(view["members"]) == n_live - 1

    # hot-spare rejoin: fresh engine + fresh in-memory jit caches — the
    # shared DISK cache must cover the whole request path
    _cc.reset_cache()
    h1.rejoin(warm=True)
    assert h1.state == "routable" and h1.generation is not None
    c0 = tuning.compile_stats()
    more = [router.submit([3, 1, 4, 1], max_new_tokens=3,
                          token="dr%d" % i) for i in range(4)]
    router.run(max_steps=2000)
    c1 = tuning.compile_stats()
    assert all(rr.state == "completed" for rr in more)
    assert any(rr.committed_by == 1 for rr in more)
    assert c1["cache_misses"] - c0["cache_misses"] == 0, \
        "rejoined replica compiled on the request path"
    _close(pool, srv)


# ---------------------------------------------------------------------------
# scheduler cancel hook (the hedge-loser / drain-migration primitive)
# ---------------------------------------------------------------------------
def test_scheduler_cancel_queued_and_running():
    eng = _factory()
    sched = ContinuousBatcher(eng)
    a = sched.submit(Request([3, 4], max_new_tokens=8))
    b = sched.submit(Request([5], max_new_tokens=8))
    c = sched.submit(Request([7], max_new_tokens=8))  # queued (2 slots)
    sched.step()
    assert a.state == "running" and c.state == "queued"
    assert sched.cancel(c) and c.state == "evicted"
    assert sched.cancel(a) and a.state == "evicted"
    assert not sched.cancel(a)          # idempotent
    assert eng.cache.pages_in_use() <= 2  # a's pages freed
    sched.run()
    assert b.state == "completed"
    assert b.output_tokens == _ref([5], 8)
    _FREE_ENGINES.append(eng)


# ---------------------------------------------------------------------------
# standalone replica role (srv_* ops over the async transport)
# ---------------------------------------------------------------------------
def test_remote_replica_and_serving_host():
    from mxnet_tpu.async_server import AsyncParamServer

    srv = AsyncParamServer("127.0.0.1", 0)
    port = srv._sock.getsockname()[1]
    eng = _factory()
    host = fleet_mod.ServingHost(ContinuousBatcher(eng))
    srv.attach_serving(host)
    rem = fleet_mod.RemoteReplica(0, "127.0.0.1", port, slots=eng.slots)
    assert rem.submit_copy("c1", [3, 1, 4], 3) == "queued"
    assert rem.load() == {"queue": 1, "active": 0, "slots": 2}
    assert rem.queued_copies() == ["c1"]
    while host.step():
        pass
    assert rem.poll() == [("c1", "completed", _ref([3, 1, 4], 3))]
    # drain closes admission remotely
    rem.drain_start()
    assert not host.admitting
    rem.close()
    srv.close()
    _FREE_ENGINES.append(eng)


def test_standalone_replica_discovered_and_routed():
    """The full standalone role: serve_replica() registers endpoint +
    capacity meta at the coordinator, ReplicaPool.refresh() discovers
    it as a RemoteReplica, and the router completes a request over the
    srv_* transport (the replica's own decode-loop thread drives)."""
    from mxnet_tpu.async_server import AsyncParamServer

    coord_srv = AsyncParamServer("127.0.0.1", 0)
    coord = ("127.0.0.1", coord_srv._sock.getsockname()[1])
    eng = _factory()
    rep_srv, host, member, stop = fleet_mod.serve_replica(
        eng, coord, index=0)
    try:
        pool = fleet_mod.ReplicaPool(coordinator=coord,
                                     server=coord_srv)
        pool.refresh()
        assert isinstance(pool.get(0), fleet_mod.RemoteReplica)
        assert pool.get(0).capacity == eng.slots
        router = FleetRouter(pool)
        rr = router.submit([3, 1, 4], max_new_tokens=3, token="rm1")
        deadline = time.monotonic() + 30.0
        while not rr.done and time.monotonic() < deadline:
            router.step()
            time.sleep(0.01)
        assert rr.state == "completed"
        assert rr.result == _ref([3, 1, 4], 3)
        pool.close()
    finally:
        stop()
        coord_srv.close()


def test_serving_host_rejects_while_draining():
    from mxnet_tpu.async_server import AsyncParamServer

    srv = AsyncParamServer("127.0.0.1", 0)
    port = srv._sock.getsockname()[1]
    eng = _factory()
    host = fleet_mod.ServingHost(ContinuousBatcher(eng))
    srv.attach_serving(host)
    rem = fleet_mod.RemoteReplica(0, "127.0.0.1", port, slots=eng.slots)
    rem.drain_start()
    with pytest.raises(MXNetError):
        rem.submit_copy("c9", [1, 2], 2)
    rem.close()
    srv.close()
    _FREE_ENGINES.append(eng)


# ---------------------------------------------------------------------------
# chaos cells (swept per seed by tools/chaos_matrix.sh)
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_chaos_replica_kill_failover(monkeypatch):
    """Seeded replica_kill mid-run: deterministic kill at a router
    tick, zero lost requests, token-exact failover."""
    from mxnet_tpu import resilience

    monkeypatch.setenv(
        "MXT_FAULT",
        "replica_kill:replica=1,after=2,n=1,seed=%d" % _seed())
    resilience.reset_faults()
    try:
        pool, srv = _fleet(2)
        router = FleetRouter(pool)
        # budgets long enough that replica 1's copies are mid-decode at
        # its 2nd tick, whatever the seed — the kill is always mid-run
        rng = np.random.RandomState(_seed())
        reqs = [router.submit(rng.randint(1, 64, 4).tolist(),
                              max_new_tokens=8, token="ck%d" % i)
                for i in range(6)]
        router.run(max_steps=2000)
        assert pool.get(1).state == "dead"
        assert all(rr.state == "completed" for rr in reqs)
        assert all(rr.result == _ref(rr.prompt, rr.max_new_tokens)
                   for rr in reqs)
        assert sum(rr.failovers for rr in reqs) > 0
        _close(pool, srv)
    finally:
        resilience.reset_faults()


@pytest.mark.chaos
def test_chaos_replica_slow_hedges(monkeypatch):
    """Seeded replica_slow brownout under a fake clock: the hedge fires
    at the delay and the fleet completes everything on the healthy
    replica."""
    from mxnet_tpu import resilience

    monkeypatch.setenv(
        "MXT_FAULT",
        "replica_slow:replica=0,ms=60000,after=1,n=1,seed=%d" % _seed())
    resilience.reset_faults()
    try:
        clock = [0.0]
        pool, srv = _fleet(2, now_fn=lambda: clock[0])
        router = FleetRouter(pool, now_fn=lambda: clock[0],
                             hedge_delay=1.0, hedge_budget=4)
        reqs = [router.submit([5, 9, 2], max_new_tokens=3,
                              token="cs%d" % i) for i in range(2)]
        router.step()
        clock[0] = 2.0
        for _ in range(40):
            if all(rr.done for rr in reqs):
                break
            router.step()
        router.flush()
        assert all(rr.state == "completed" for rr in reqs)
        assert all(rr.result == _ref(rr.prompt, 3) for rr in reqs)
        # whoever was browned out lost every race it was hedged on
        slow = [h for h in pool.replicas() if h.slow_until > 0]
        assert slow and all(rr.committed_by != slow[0].index
                            for rr in reqs if rr.hedges)
        _close(pool, srv)
    finally:
        resilience.reset_faults()


# ---------------------------------------------------------------------------
# telemetry + lint
# ---------------------------------------------------------------------------
def test_fleet_modules_lint_enforced():
    """fleet.py and router.py stay on the static host-sync scan list."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_host_syncs", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "check_host_syncs.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    for rel in ("mxnet_tpu/serving/fleet.py",
                "mxnet_tpu/serving/router.py"):
        assert rel in m.SCAN
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = [b for b in m.check(root)
           if b[0].startswith("mxnet_tpu/serving/")]
    assert not bad, bad


def test_mxt_top_fleet_section():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxt_top", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "mxt_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    samples = {
        ("mxt_fleet_replicas", frozenset({("state", "routable")})): 2,
        ("mxt_fleet_replicas", frozenset({("state", "dead")})): 1,
        ("mxt_fleet_dispatch_total", frozenset({("replica", "0")})): 9,
        ("mxt_fleet_hedges_total", frozenset({("replica", "0")})): 2,
        ("mxt_fleet_failovers_total", frozenset({("replica", "1")})): 3,
    }
    frame = mod.render(samples, None, 0)
    assert "fleet replicas" in frame
    assert "disp/hedge/fail" in frame
    # a process with no fleet gauges renders no fleet noise
    assert "fleet replicas" not in mod.render({}, None, 0)


def test_fleet_metrics_published():
    """The router publishes the ISSUE's telemetry surface: replica
    state gauges, per-replica dispatch counters, latency histogram."""
    from mxnet_tpu import telemetry

    pool, srv = _fleet(1)
    router = FleetRouter(pool)
    rr = router.submit([5, 1], max_new_tokens=2, token="m1")
    router.run(max_steps=2000)
    assert rr.state == "completed"
    reg = telemetry.registry()
    fam = reg.get("mxt_fleet_replicas")
    assert fam is not None
    fam = reg.get("mxt_fleet_dispatch_total")
    assert fam is not None and sum(
        ch.value for ch in fam.children().values()) >= 1
    assert reg.get("mxt_fleet_request_latency_seconds") is not None
    _close(pool, srv)
