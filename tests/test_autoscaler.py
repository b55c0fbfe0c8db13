"""SLO-driven autoscaler + multi-tenant QoS (serving/autoscaler.py +
serving/qos.py): the control loop that closes the PR 13 observability
loop, plus the priority/quota layer that keeps tenants honest under a
flash crowd.

Covers the FleetAutoscaler's hysteresis (scale UP on backlog, DOWN only
after a calm streak, cooldown between actions, typed floor/ceiling
refusals with `refused` events), the warming→routable spare lifecycle
under the seeded ``replica_spawn_slow`` rule (a slow spare never stalls
the router), the PR 18 lifecycle-race bugfix (``router.drain`` of a
warming / already-draining replica is a typed error, not a silent
no-op), the seeded ``traffic_storm`` flash crowd (deterministic per
MXT_CHAOS_SEED) with the zero-lost accounting acceptance, per-tenant
quotas (typed OverQuotaError, refunds on finish, replays never
re-charge), priority-aware dispatch + preemption ordering (bulk evicted
strictly before interactive; the preempted request re-enqueues and
replays token-exact), decode-worker fleet resize, the mxt_top
autoscale/tenant section, and the host-sync lint gate over both new
modules.
"""
import os
import sys
import time

import numpy as np
import pytest

from mxnet_tpu import resilience, serving, telemetry, tuning
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (DecodeEngine, FleetRouter, PagedKVCache,
                               TinyDecoder)
from mxnet_tpu.serving import metrics as _m
from mxnet_tpu.serving.autoscaler import (AutoscalerError, FleetAutoscaler,
                                          TrafficGenerator)
from mxnet_tpu.serving.fleet import ROUTABLE, WARMING, LocalReplica
from mxnet_tpu.serving.qos import (OverQuotaError, QosPolicy, TenantSpec,
                                   PRIORITY_CLASSES)


def _seed():
    return int(os.environ.get("MXT_CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch, tmp_path):
    """Failovers must surface in milliseconds, not the production 30s
    retry budget; every test gets its own tuning table and a clean
    trace-span log (the autoscaler records decision spans)."""
    monkeypatch.setenv("MXT_KV_RETRIES", "1")
    monkeypatch.setenv("MXT_KV_RETRY_BASE", "0.02")
    monkeypatch.setenv("MXT_KV_RETRY_MAX", "0.05")
    monkeypatch.setenv("MXT_TUNE_TABLE", str(tmp_path / "tune.json"))
    tuning.reset()
    telemetry.clear_trace_spans()
    yield
    telemetry.clear_trace_spans()
    tuning.reset()


MODEL = TinyDecoder(vocab=64, num_layers=1, num_heads=2, head_dim=8,
                    max_len=256)
PARAMS = MODEL.init_params(3)

_FREE_ENGINES = []  # drained engines recycled across tests (compile cost)


def _factory():
    while _FREE_ENGINES:
        eng = _FREE_ENGINES.pop()
        if eng.cache.pages_in_use() == 0 and not eng._seq_of_slot:
            return eng
    return DecodeEngine(
        MODEL, params=PARAMS, slots=2,
        cache=PagedKVCache(1, 2, 8, num_pages=64, page_size=8),
        prefill_buckets=(16,), max_context=64)


def _fleet(n, now_fn=time.monotonic):
    return serving.local_serving_fleet(n, _factory, now_fn=now_fn,
                                       warm=False)


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


def _scaler(router, clock_now, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("cooldown", 0.0)
    kw.setdefault("queue_high", 1.0)
    kw.setdefault("occ_low", 1.0)
    kw.setdefault("calm_ticks", 10 ** 6)
    kw.setdefault("warm", False)
    return FleetAutoscaler(router, _factory, now_fn=clock_now, **kw)


def _span_names(scaler):
    scaler._collector.scrape()
    return {s["name"] for s in scaler._collector.spans(scaler.trace_id)}


# ---------------------------------------------------------------------------
# bugfix regression: drain vs the replica lifecycle
# ---------------------------------------------------------------------------
def test_drain_warming_spare_is_typed_error():
    """Draining a spare still warming must refuse typed — the old
    silent no-op let the spare register AFTER the drain and serve
    anyway (the lifecycle race this PR fixes)."""
    clock = [0.0]
    pool, srv = _fleet(1, now_fn=lambda: clock[0])
    router = FleetRouter(pool, now_fn=lambda: clock[0])
    spare = LocalReplica(1, _factory, coordinator=pool.coordinator,
                         now_fn=lambda: clock[0])
    spare.prepare(warm=False)
    pool.add(spare)
    with pytest.raises(MXNetError, match="warming"):
        router.drain(1)
    assert spare.state == WARMING  # the refusal touched nothing
    spare.go_routable()
    pool.publish()
    router.drain(1)  # routable now: the same call succeeds
    assert spare.state != ROUTABLE
    _close(pool, srv)


def test_double_drain_is_typed_error():
    pool, srv = _fleet(2)
    router = FleetRouter(pool)
    router.drain(1)
    with pytest.raises(MXNetError, match="drain"):
        router.drain(1)  # draining: no admission left to stop
    router.step()        # empty replica finishes its drain
    with pytest.raises(MXNetError, match="drain"):
        router.drain(1)  # drained: still a typed error, not a no-op
    assert len(pool.routable()) == 1
    _close(pool, srv)


# ---------------------------------------------------------------------------
# the control loop: up on backlog, down after calm, typed at the rails
# ---------------------------------------------------------------------------
def test_scale_up_on_backlog_all_complete():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(1, now_fn=now)
    router = FleetRouter(pool, now_fn=now)
    scaler = _scaler(router, now)
    rng = np.random.RandomState(_seed() + 1)
    reqs = [router.submit(rng.randint(1, 64, 4).tolist(),
                          max_new_tokens=3, token="up%d" % i)
            for i in range(8)]
    assert scaler.step() == "up"  # queue 8 >= queue_high * capacity
    assert scaler.replica_target() == 2
    assert len(pool.routable()) == 2  # no spawn delay: routable at once
    guard = 0
    while router.step() and guard < 3000:
        clock[0] += 0.05
        scaler.step()
        guard += 1
    assert guard < 3000
    assert 2 <= len(pool.routable()) <= scaler.max_replicas
    for rr in reqs:
        assert rr.state == "completed"
        assert rr.result == _ref(rr.prompt, 3)
    ups = [d for d in scaler.decisions if d["direction"] == "up"]
    assert ups and ups[0]["seq"] == 1
    assert "queue=" in ups[0]["reason"]
    # the decision is a first-class event on the fleet trace timeline
    names = _span_names(scaler)
    assert "scale_up" in names
    assert "replica_routable" in names
    scaler.close()
    _close(pool, srv)


def test_scale_down_needs_calm_streak_and_cooldown_no_flap():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(2, now_fn=now)
    router = FleetRouter(pool, now_fn=now)
    scaler = _scaler(router, now, cooldown=1.0, calm_ticks=3)
    # hysteresis: two calm ticks are not enough
    assert scaler.step() is None
    clock[0] += 0.1
    assert scaler.step() is None
    clock[0] += 0.1
    assert scaler.step() == "down"  # third consecutive calm evaluation
    assert len(pool.routable()) == 1
    router.step()  # the drained-empty replica deregisters
    # cooldown + floor: never a second action, never below min_replicas
    for _ in range(8):
        clock[0] += 0.5
        assert scaler.step() is None
    assert len(pool.routable()) == 1
    assert [d["direction"] for d in scaler.decisions] == ["down"]
    scaler.close()
    _close(pool, srv)


def test_hot_sample_resets_calm_streak():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(2, now_fn=now)
    router = FleetRouter(pool, now_fn=now)
    scaler = _scaler(router, now, max_replicas=2, calm_ticks=3)
    assert scaler.step() is None
    assert scaler.step() is None  # calm streak at 2
    rng = np.random.RandomState(_seed() + 4)
    reqs = [router.submit(rng.randint(1, 64, 4).tolist(),
                          max_new_tokens=2, token="hot%d" % i)
            for i in range(8)]
    scaler.step()  # hot: resets calm (and may scale up — that's fine)
    while router.step():
        clock[0] += 0.05
    # calm again, but the streak starts OVER: two ticks stay hold
    assert scaler.step() is None
    assert scaler.step() is None
    assert not any(d["direction"] == "down" for d in scaler.decisions)
    assert all(rr.state == "completed" for rr in reqs)
    scaler.close()
    _close(pool, srv)


def test_scale_to_explicit_and_typed_refusals():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(1, now_fn=now)
    router = FleetRouter(pool, now_fn=now)
    scaler = _scaler(router, now)
    refused0 = _m.autoscale_events_total().labels("refused").value
    assert scaler.scale_to(3) == 3
    assert len(pool.routable()) == 3
    with pytest.raises(AutoscalerError, match="refused"):
        scaler.scale_to(0)  # an operator typo cannot black-hole the fleet
    with pytest.raises(AutoscalerError, match="refused"):
        scaler.scale_to(4)
    assert _m.autoscale_events_total().labels("refused").value \
        == refused0 + 2
    assert scaler.scale_to(1) == 1
    router.step()  # drained replicas deregister
    assert len(pool.routable()) == 1
    with pytest.raises(AutoscalerError, match="floor"):
        scaler._scale_down(None, now())  # the loop-level guard, typed too
    seq = [d["direction"] for d in scaler.decisions]
    assert seq.count("refused") == 3
    assert seq.count("up") == 2 and seq.count("down") == 2
    scaler.close()
    _close(pool, srv)


def test_autoscaler_ctor_bounds_typed():
    pool, srv = _fleet(1)
    router = FleetRouter(pool)
    with pytest.raises(AutoscalerError, match="floor"):
        FleetAutoscaler(router, _factory, min_replicas=0)
    with pytest.raises(AutoscalerError, match="below its floor"):
        FleetAutoscaler(router, _factory, min_replicas=3, max_replicas=2)
    _close(pool, srv)


# ---------------------------------------------------------------------------
# chaos: slow spare warm-up + the seeded flash crowd
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_spawn_slow_spare_never_stalls_router(monkeypatch):
    monkeypatch.setenv("MXT_FAULT", "replica_spawn_slow:ms=500")
    resilience.reset_faults()
    try:
        clock = [0.0]
        now = lambda: clock[0]  # noqa: E731
        pool, srv = _fleet(1, now_fn=now)
        router = FleetRouter(pool, now_fn=now)
        scaler = _scaler(router, now, max_replicas=2)
        rng = np.random.RandomState(_seed() + 2)
        reqs = [router.submit(rng.randint(1, 64, 4).tolist(),
                              max_new_tokens=3, token="sl%d" % i)
                for i in range(6)]
        assert scaler.step() == "up"
        spare = pool.get(1)
        assert spare.state == WARMING  # held by the 500ms warm horizon
        assert len(pool.routable()) == 1
        # the router keeps serving off the seed replica the whole time
        for _ in range(6):
            clock[0] += 0.05  # stays under the horizon
            router.step()
            assert scaler.step() is None  # one spare warming: no pile-on
        assert spare.state == WARMING
        done_during_warm = sum(1 for rr in reqs if rr.done)
        assert done_during_warm > 0
        clock[0] += 1.0  # past the horizon: the next tick promotes
        scaler.step()
        assert spare.state == ROUTABLE
        assert len(pool.routable()) == 2
        guard = 0
        while router.step() and guard < 2000:
            clock[0] += 0.05
            guard += 1
        for rr in reqs:
            assert rr.state == "completed"
            assert rr.result == _ref(rr.prompt, 3)
        assert "replica_routable" in _span_names(scaler)
        scaler.close()
        _close(pool, srv)
    finally:
        monkeypatch.delenv("MXT_FAULT", raising=False)
        resilience.reset_faults()


@pytest.mark.chaos
def test_traffic_storm_deterministic_and_tenant_tagged(monkeypatch):
    monkeypatch.setenv("MXT_FAULT",
                       "traffic_storm:rps=40,after=3,tenant=bulk")
    resilience.reset_faults()
    try:
        pool, srv = _fleet(1)

        def offer(prefix):
            router = FleetRouter(pool)
            gen = TrafficGenerator(router, rate=1.0, seed=_seed() + 7,
                                   vocab=64, max_requests=10,
                                   prefix=prefix)
            t = 0.0
            while gen.total_offered() < 10 and t < 30.0:
                gen.tick(t)
                t += 0.1
            return gen, t

        g1, t1 = offer("s1")
        g2, t2 = offer("s2")
        assert g1.storm is not None and g1.storm[0] == 40
        assert g1.total_offered() == 10
        # the storm is deterministic: same seed, same arrivals
        assert [rr.prompt for rr in g1.submitted] \
            == [rr.prompt for rr in g2.submitted]
        assert t1 == t2
        # ... and it IS a storm: 10 arrivals land far faster than the
        # 1 rps base rate could deliver them
        assert t1 < 3.0
        # storm traffic carries the rule's tenant tag
        assert any(rr.tenant == "bulk" for rr in g1.submitted)
        assert {rr.tenant for rr in g1.submitted} <= {None, "bulk"}
        _close(pool, srv)
    finally:
        monkeypatch.delenv("MXT_FAULT", raising=False)
        resilience.reset_faults()


@pytest.mark.chaos
def test_flash_crowd_scales_up_zero_lost(monkeypatch):
    """The acceptance loop: a seeded flash crowd hits the 1-replica
    floor, the autoscaler grows the fleet, and EVERY offered request is
    accounted — submitted == completed + typed-rejected, nothing lost,
    the scale-up visible as spans on the fleet trace timeline."""
    monkeypatch.setenv("MXT_FAULT", "traffic_storm:rps=60,after=2")
    resilience.reset_faults()
    try:
        clock = [0.0]
        now = lambda: clock[0]  # noqa: E731
        pool, srv = _fleet(1, now_fn=now)
        router = FleetRouter(pool, now_fn=now)
        scaler = _scaler(router, now, cooldown=0.3)
        gen = TrafficGenerator(router, rate=2.0, seed=_seed() + 3,
                               vocab=64, prompt_len=(2, 8),
                               max_new_tokens=4, max_requests=14,
                               prefix="fc")
        guard = 0
        while guard < 4000 and (gen.total_offered() < 14
                                or router._queue or router._inflight):
            clock[0] += 0.05
            gen.tick(clock[0])
            router.step()
            scaler.step()
            guard += 1
        assert guard < 4000
        assert gen.total_offered() == 14
        completed = [rr for rr in gen.submitted
                     if rr.state == "completed"]
        # zero lost: offered == committed + typed-rejected
        assert len(completed) + gen.rejected == 14
        for rr in completed:
            assert rr.result == _ref(rr.prompt, 4)
        assert any(d["direction"] == "up" for d in scaler.decisions)
        assert len(pool.routable()) > 1
        assert "scale_up" in _span_names(scaler)
        scaler.close()
        _close(pool, srv)
    finally:
        monkeypatch.delenv("MXT_FAULT", raising=False)
        resilience.reset_faults()


# ---------------------------------------------------------------------------
# multi-tenant QoS: quotas, priority dispatch, preemption
# ---------------------------------------------------------------------------
def test_qos_parse_and_priority_classes():
    qos = QosPolicy.parse("interactive:bulk")
    assert qos.tenants() == ["bulk", "interactive"]
    assert qos.priority_of("interactive") == PRIORITY_CLASSES["interactive"]
    assert qos.priority_of("bulk") == PRIORITY_CLASSES["bulk"]
    # name=class spelling, integer classes, typed on garbage
    qos2 = QosPolicy.parse("web=interactive,batch=7")
    assert qos2.priority_of("web") == 0
    assert qos2.priority_of("batch") == 7
    with pytest.raises(MXNetError, match="neither"):
        QosPolicy.parse("x=fastest")
    with pytest.raises(MXNetError):
        TenantSpec("t", max_requests=0)


def test_over_quota_typed_refund_and_replay_never_recharges():
    pool, srv = _fleet(1)
    qos = QosPolicy()
    qos.add_tenant("bulk", max_requests=2)
    router = FleetRouter(pool, qos=qos)
    rej0 = _m.tenant_rejected_total().labels("bulk").value
    rng = np.random.RandomState(_seed() + 5)
    prompts = [rng.randint(1, 64, 4).tolist() for _ in range(3)]
    rr0 = router.submit(prompts[0], max_new_tokens=3, token="q0",
                        tenant="bulk")
    router.submit(prompts[1], max_new_tokens=3, token="q1",
                  tenant="bulk")
    with pytest.raises(OverQuotaError) as ei:
        router.submit(prompts[2], max_new_tokens=3, token="q2",
                      tenant="bulk")
    assert ei.value.tenant == "bulk"
    assert "NOT enqueued" in str(ei.value)
    assert _m.tenant_rejected_total().labels("bulk").value == rej0 + 1
    assert qos.outstanding("bulk")[0] == 2
    router.run()
    # finish refunds the charge: the refused prompt now admits
    assert qos.outstanding("bulk") == (0, 0)
    rr2 = router.submit(prompts[2], max_new_tokens=3, token="q2",
                        tenant="bulk")
    router.run()
    assert rr2.state == "completed"
    assert rr2.result == _ref(prompts[2], 3)
    # an idempotent replay answers from the record — never re-charges
    again = router.submit(prompts[0], max_new_tokens=3, token="q0",
                          tenant="bulk")
    assert again is rr0
    assert qos.outstanding("bulk") == (0, 0)
    _close(pool, srv)


def test_token_quota_axis_typed():
    qos = QosPolicy()
    qos.add_tenant("bulk", max_tokens=20)
    qos.admit("bulk", 15)
    with pytest.raises(OverQuotaError, match="token quota"):
        qos.admit("bulk", 10)
    qos.release("bulk", 15)
    qos.admit("bulk", 10)  # refunded budget admits again
    assert qos.outstanding("bulk") == (1, 10)


def test_interactive_overtakes_queued_bulk():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(1, now_fn=now)
    router = FleetRouter(pool, now_fn=now, qos=QosPolicy())
    rng = np.random.RandomState(_seed() + 6)
    for i in range(3):
        router.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=3,
                      token="b%d" % i, tenant="bulk")
    router.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=3,
                  token="i0", tenant="interactive")
    while router.step():
        clock[0] += 0.05
    order = [rr.token for rr in router.finished]
    # 2 decode slots: the late interactive arrival seats in the FIRST
    # admission wave, ahead of bulk requests queued before it
    assert order.index("i0") <= 1
    _close(pool, srv)


def test_preemption_bulk_evicted_before_interactive_replay_exact():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(1, now_fn=now)
    router = FleetRouter(pool, now_fn=now, qos=QosPolicy())
    pre0 = _m.tenant_preempted_total().labels("bulk").value
    rng = np.random.RandomState(_seed() + 8)
    pb = rng.randint(1, 64, 4).tolist()
    pi1 = rng.randint(1, 64, 4).tolist()
    pi2 = rng.randint(1, 64, 4).tolist()
    bulk = router.submit(pb, max_new_tokens=8, token="pb", tenant="bulk")
    int1 = router.submit(pi1, max_new_tokens=8, token="pi1",
                         tenant="interactive")
    for _ in range(3):  # both seat (2 slots) and decode a few tokens
        router.step()
        clock[0] += 0.05
    assert bulk.state == "dispatched" and int1.state == "dispatched"
    int2 = router.submit(pi2, max_new_tokens=4, token="pi2",
                         tenant="interactive")
    guard = 0
    while router.step() and guard < 2000:
        clock[0] += 0.05
        guard += 1
    # ordering: the bulk request was evicted to seat interactive work —
    # the running interactive request was NEVER touched
    assert bulk.preemptions == 1
    assert int1.preemptions == 0 and int2.preemptions == 0
    assert _m.tenant_preempted_total().labels("bulk").value == pre0 + 1
    # late, never lost: the preempted request re-enqueued and replayed
    # from scratch, token-exact
    for rr, (prompt, n) in ((bulk, (pb, 8)), (int1, (pi1, 8)),
                            (int2, (pi2, 4))):
        assert rr.state == "completed"
        assert rr.result == _ref(prompt, n)
    _close(pool, srv)


def test_qos_isolation_interactive_latency_bounded_under_bulk_flood():
    """The acceptance assert: a bulk tenant saturating admission leaves
    interactive completion within a bounded multiple of unloaded, and
    over-quota bulk is refused typed."""
    def run(nbulk):
        clock = [0.0]
        pool, srv = _fleet(1, now_fn=lambda: clock[0])
        qos = QosPolicy()
        qos.add_tenant("bulk", max_requests=4)
        router = FleetRouter(pool, now_fn=lambda: clock[0], qos=qos)
        rng = np.random.RandomState(11)
        refused = 0
        for i in range(nbulk):
            try:
                router.submit(rng.randint(1, 64, 6).tolist(),
                              max_new_tokens=6, tenant="bulk",
                              token="bg%d-%d" % (nbulk, i))
            except OverQuotaError:
                refused += 1
        inter = [router.submit(rng.randint(1, 64, 4).tolist(),
                               max_new_tokens=3, tenant="interactive",
                               token="in%d-%d" % (nbulk, i))
                 for i in range(2)]
        steps0 = router.steps
        guard = 0
        while not all(rr.done for rr in inter) and guard < 2000:
            router.step()
            clock[0] += 0.05
            guard += 1
        steps_inter = router.steps - steps0
        while router.step():
            clock[0] += 0.05
        assert all(rr.state == "completed" for rr in inter)
        _close(pool, srv)
        return steps_inter, refused

    base, _ = run(0)
    loaded, refused = run(6)
    assert refused == 2  # quota 4, offered 6: the excess refused typed
    assert loaded <= 4 * max(base, 1), (loaded, base)


# ---------------------------------------------------------------------------
# decode-worker fleets: resize + the autoscaler's watermark loop
# ---------------------------------------------------------------------------
def test_worker_fleet_resize_typed_floor_and_cooperative_shrink(tmp_path):
    from mxnet_tpu import recordio
    from mxnet_tpu.data_plane import (ArrayDecoder, ChunkLedger,
                                      DecodeWorkerFleet, ShardManifest)

    rec = str(tmp_path / "part-0.rec")
    idx = str(tmp_path / "part-0.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for gid in range(40):
        w.write_idx(gid, recordio.pack(
            recordio.IRHeader(0, float(gid), gid, 0),
            np.full((4,), gid, np.float32).tobytes()))
    w.close()
    man = ShardManifest([rec], chunk_records=10)
    ledger = ChunkLedger()
    ledger.begin_epoch(man.manifest_id, 0, man.owners(0, 1, seed=1))
    fleet = DecodeWorkerFleet(man, ledger, 0,
                              ArrayDecoder((4,), "float32"), 5,
                              num_workers=1, buffer_batches=2)
    with pytest.raises(MXNetError, match="at least one"):
        fleet.resize(0)
    fleet.start()
    fleet.resize(2)  # grow spawns the missing worker immediately
    assert fleet.num_workers == 2
    got = []
    for data, labels, ids, cid, _ in fleet.batches():
        got.append(ids)
        if len(got) == 2:
            fleet.resize(1)  # shrink mid-stream: cooperative, no loss
    assert fleet.num_workers == 1
    # exactly-once survives the resize: every record delivered once
    assert sorted(i for ids in got for i in ids) \
        == sorted(man.record_ids())
    fleet.close()
    assert fleet.live_workers() == 0


class _FakeWorkerQueue:
    def __init__(self, qsize, maxsize):
        self._n, self.maxsize = qsize, maxsize

    def qsize(self):
        return self._n


class _FakeWorkerFleet:
    """Duck-typed DecodeWorkerFleet: just the watermark surface the
    autoscaler reads (``_q``, ``num_workers``, ``live_workers``,
    ``resize``)."""

    def __init__(self, qsize, maxsize=8, num_workers=2):
        self._q = _FakeWorkerQueue(qsize, maxsize)
        self.num_workers = num_workers
        self.resized = []

    def live_workers(self):
        return self.num_workers

    def resize(self, n):
        self.resized.append(n)
        self.num_workers = n


def test_autoscaler_scales_worker_fleets_on_watermarks():
    clock = [0.0]
    now = lambda: clock[0]  # noqa: E731
    pool, srv = _fleet(1, now_fn=now)
    router = FleetRouter(pool, now_fn=now)
    scaler = _scaler(router, now, cooldown=1.0)
    starved = scaler.attach_worker_fleet(_FakeWorkerFleet(qsize=0))
    flooded = scaler.attach_worker_fleet(_FakeWorkerFleet(qsize=8,
                                                          num_workers=3))
    scaler.step()
    # empty buffer = starving consumer -> grow; full = producers far
    # ahead -> shrink. Each fleet scales INDEPENDENTLY, one worker at
    # a time.
    assert starved.resized == [3]
    assert flooded.resized == [2]
    # per-fleet cooldown: an immediate second tick holds both
    scaler.step()
    assert starved.resized == [3] and flooded.resized == [2]
    clock[0] += 1.5
    scaler.step()
    assert starved.resized == [3, 4]
    assert flooded.resized == [2, 1]
    # floor of 1: no further shrink is ever attempted
    clock[0] += 1.5
    scaler.step()
    assert flooded.resized == [2, 1]
    dirs = [d["direction"] for d in scaler.decisions]
    assert "workers_up" in dirs and "workers_down" in dirs
    scaler.close()
    _close(pool, srv)


# ---------------------------------------------------------------------------
# mxt_top: the autoscale / tenant section (gated on the gauges)
# ---------------------------------------------------------------------------
def _mxt_top():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import mxt_top
    finally:
        sys.path.pop(0)
    return mxt_top


def test_mxt_top_autoscale_section_golden():
    top = _mxt_top()
    text = "\n".join([
        "mxt_autoscale_target_replicas 3",
        'mxt_autoscale_events_total{direction="up"} 2',
        'mxt_autoscale_events_total{direction="refused"} 1',
        'mxt_autoscale_last_decision{direction="up"} 3',
        'mxt_autoscale_last_decision{direction="refused"} 2',
        'mxt_tenant_admitted_total{tenant="bulk"} 5',
        'mxt_tenant_rejected_total{tenant="bulk"} 2',
        'mxt_tenant_preempted_total{tenant="bulk"} 1',
        'mxt_tenant_inflight_requests{tenant="bulk"} 0',
        'mxt_tenant_admitted_total{tenant="interactive"} 4',
    ]) + "\n"
    frame = top.render(top.parse_prometheus(text), None, 0)
    assert "autoscale" in frame
    assert "target 3" in frame
    assert "up 2" in frame and "refused 1" in frame
    # the max decision seq wins: "up" (#3) is the most recent
    assert "last decision" in frame and "up (#3)" in frame
    assert "tenant bulk" in frame
    assert "adm 5" in frame and "rej 2" in frame and "pre 1" in frame
    assert "tenant interactive" in frame
    # an unscaled single-tenant fleet renders NO control-loop noise
    bare = top.render(top.parse_prometheus("up 1\n"), None, 0)
    assert "autoscale" not in bare
    assert "tenant" not in bare


# ---------------------------------------------------------------------------
# lint: the control loop stays host-pure
# ---------------------------------------------------------------------------
def test_autoscaler_qos_lint_enforced():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_host_syncs", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "check_host_syncs.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    assert "mxnet_tpu/serving/autoscaler.py" in m.SCAN
    assert "mxnet_tpu/serving/qos.py" in m.SCAN
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = [b for b in m.check(root)
           if b[0] in ("mxnet_tpu/serving/autoscaler.py",
                       "mxnet_tpu/serving/qos.py")]
    assert not bad, bad
