"""Pod-scale streaming data plane (mxnet_tpu/data_plane/ — ISSUE 14):
shard manifest determinism, exactly-once chunk leasing with stale-lease
fencing, cross-host work stealing, backpressure, per-host data_wait
telemetry, mid-epoch checkpoint cursors, and the wire path over a real
AsyncParamServer.

Multi-host scenarios run IN-PROCESS (N loaders sharing one ChunkLedger,
consumed on real threads) — no subprocesses, bounded polls. The
chaos-marked cells (data_host_kill / data_worker_slow) are swept per
seed by tools/chaos_matrix.sh via MXT_CHAOS_SEED.
"""
import os
import pickle
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, data_plane, recordio
from mxnet_tpu.base import MXNetError
from mxnet_tpu.data_plane import (ArrayDecoder, ChunkLedger, ImageDecoder,
                                  RemoteLedger, ShardManifest,
                                  StaleLeaseError, StreamingDataLoader)
from mxnet_tpu.membership import StaleWorkerError


def _seed():
    return int(os.environ.get("MXT_CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _clean_fault():
    yield
    config.set_default("MXT_FAULT", "")


def make_shards(tmp_path, n_shards=2, per_shard=40, dim=4):
    """Indexed array-record shards with GLOBALLY unique keys; record
    payload = np.full(dim, global_id) so content identifies the record."""
    shards = []
    gid = 0
    for s in range(n_shards):
        rec = str(tmp_path / ("part-%d.rec" % s))
        idx = str(tmp_path / ("part-%d.idx" % s))
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for _ in range(per_shard):
            w.write_idx(gid, recordio.pack(
                recordio.IRHeader(0, float(gid), gid, 0),
                np.full((dim,), gid, np.float32).tobytes()))
            gid += 1
        w.close()
        shards.append(rec)
    return shards


def _loader(man, ledger=None, host=0, hosts=1, seed=3, workers=1, **kw):
    return StreamingDataLoader(
        man, 4, ArrayDecoder((4,), "float32"), host_id=host,
        num_hosts=hosts, ledger=ledger, seed=seed, num_workers=workers,
        to_device=False, **kw)


def _consume_parallel(loaders):
    """Drain each loader on its own thread; returns {host: [batches]}."""
    out = {}

    def run(ldr, h):
        out[h] = list(iter(ldr))

    ts = [threading.Thread(target=run, args=(ldr, h))
          for h, ldr in loaders.items()]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
        assert not t.is_alive(), "host consumer hung"
    return out


# --------------------------------------------------------------------------
# manifest
# --------------------------------------------------------------------------
def test_manifest_deterministic_plan(tmp_path):
    shards = make_shards(tmp_path, per_shard=40)
    m1 = ShardManifest(shards, chunk_records=8)
    m2 = ShardManifest(shards, chunk_records=8)
    assert m1.manifest_id == m2.manifest_id
    assert m1.num_records == 80 and m1.num_chunks == 10
    # identical plan from identical coordinates, on any instance
    assert m1.epoch_order(2, seed=7) == m2.epoch_order(2, seed=7)
    assert m1.epoch_chunk(3, 2, seed=7) == m2.epoch_chunk(3, 2, seed=7)
    # epochs reshuffle both levels
    assert m1.epoch_order(0, seed=7) != m1.epoch_order(1, seed=7)
    assert m1.epoch_chunk(3, 0, seed=7).keys \
        != m1.epoch_chunk(3, 1, seed=7).keys
    # chunks partition the keyspace exactly, and every host table
    # covers every chunk exactly once
    owners = m1.owners(0, 3, seed=7)
    dealt = sorted(c for cids in owners.values() for c in cids)
    assert dealt == list(range(m1.num_chunks))
    keys = sorted(k for cid in range(m1.num_chunks)
                  for k in m1.epoch_chunk(cid, 0).keys)
    assert keys == sorted(k for _, k in m1.record_ids())
    # a different chunking is a DIFFERENT manifest (fencing identity)
    assert ShardManifest(shards, chunk_records=16).manifest_id \
        != m1.manifest_id


def test_recordio_reader_pickles_across_process_boundary(tmp_path):
    """Satellite: MXIndexedRecordIO seek/read_idx after __setstate__ —
    pickled-across-process readers are how process decode workers
    receive shard handles; the __getstate__ path was untested."""
    shards = make_shards(tmp_path, n_shards=1, per_shard=10)
    idx = os.path.splitext(shards[0])[0] + ".idx"
    r = recordio.MXIndexedRecordIO(idx, shards[0], "r")
    want = r.read_idx(7)
    # open reader: the clone must reopen and seek correctly
    clone = pickle.loads(pickle.dumps(r))
    assert clone.is_open
    assert clone.read_idx(7) == want
    clone.seek(3)
    assert clone.read() == r.read_idx(3)
    assert clone.keys == r.keys and clone.idx == r.idx
    clone.close()
    # closed reader: stays closed through the round-trip, reopenable
    r.close()
    closed_clone = pickle.loads(pickle.dumps(r))
    assert not closed_clone.is_open
    closed_clone.open()
    closed_clone.handle.seek(closed_clone.idx[7])
    assert closed_clone.read() == want
    closed_clone.close()


# --------------------------------------------------------------------------
# ledger
# --------------------------------------------------------------------------
def _ledger2(man, seed=1):
    led = ChunkLedger()
    led.begin_epoch(man.manifest_id, 0, man.owners(0, 2, seed=seed))
    return led


def test_ledger_lease_commit_exactly_once(tmp_path):
    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    led = _ledger2(man)
    (cid, tok), = led.lease(0, 1)
    assert led.commit(0, cid, tok) is True
    # at-least-once transport replay: same token is idempotent
    assert led.commit(0, cid, tok) is False
    # a different lease generation on a committed chunk is a zombie
    with pytest.raises(StaleLeaseError):
        led.commit(0, cid, tok + 1)
    # begin_epoch is idempotent/first-wins: joining does not reset
    assert led.begin_epoch(man.manifest_id, 0,
                           man.owners(0, 2, seed=1)) is False
    assert led.stats()["committed"] == 1
    # a DIFFERENT manifest for the same epoch is typed
    with pytest.raises(MXNetError):
        led.begin_epoch("deadbeef", 0, man.owners(0, 2, seed=1))


def test_ledger_steal_slowest_peer_and_reclaim(tmp_path):
    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    led = _ledger2(man)
    # drain host 0's queue; steals then come from host 1 (the slowest —
    # i.e. most-pending — live peer), popped from ITS tail
    own = led.lease(0, 10)
    assert len(own) == 5
    pending1 = led.stats()["pending"][1]
    stolen = led.steal(0, 1)
    assert len(stolen) == 1 and stolen[0][2] == 1
    assert led.stats()["pending"][1] == pending1 - 1
    assert led.stats()["steals"] == 1
    # fencing host 1 reclaims its pending AND leased-uncommitted chunks
    (c1, t1), = led.lease(1, 1)
    n = led.fence_host(1)
    assert n == led.stats()["reclaimable"] > 0
    re_stolen = led.steal(0, 100)
    assert {g[0] for g in re_stolen} >= {c1}
    assert all(g[2] == -1 for g in re_stolen)  # reclaim pool, not a peer
    # a fenced host can neither lease nor steal
    with pytest.raises(StaleLeaseError):
        led.lease(1, 1)
    with pytest.raises(StaleLeaseError):
        led.steal(1, 1)


def test_ledger_stale_lease_fencing_typed(tmp_path):
    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    led = _ledger2(man)
    (cid, tok), = led.lease(0, 1)
    led.fence_host(0)
    # the zombie's commit is refused even before anyone re-leases
    with pytest.raises(StaleLeaseError):
        led.commit(0, cid, tok)
    # the thief re-leases under a BUMPED generation and commits fine
    grants = {g[0]: g[1] for g in led.steal(1, 100)}
    assert grants[cid] > tok
    assert led.commit(1, cid, grants[cid]) is True
    # ... after which the zombie's replay is still typed
    with pytest.raises(StaleLeaseError):
        led.commit(0, cid, tok)
    assert led.stats()["stale_refused"] >= 2


# --------------------------------------------------------------------------
# end-to-end streaming
# --------------------------------------------------------------------------
def test_single_host_exactly_once_and_deterministic(tmp_path):
    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    runs = []
    for _ in range(2):
        batches = list(iter(_loader(man, workers=2)))
        ids = sorted(i for b in batches for i in b.ids)
        assert ids == sorted(man.record_ids())
        runs.append(batches)
    # same (manifest, seed, epoch) => bit-identical batches per chunk
    by_chunk = {}
    for b in runs[0]:
        by_chunk.setdefault(b.chunk_id, []).append(b)
    for b in runs[1]:
        ref = by_chunk[b.chunk_id].pop(0)
        assert np.array_equal(b.data, ref.data)
        assert np.array_equal(b.label, ref.label)
    # payload content matches the record id (decode correctness)
    b0 = runs[0][0]
    for j, (_, key) in enumerate(b0.ids):
        assert np.all(b0.data[j] == key)
        assert b0.label[j] == key


def test_two_host_acceptance_exactly_once_bit_identical(tmp_path):
    """ISSUE acceptance: 2 in-process hosts over a shared manifest
    consume every sample exactly once per epoch (sorted union of
    consumed record ids == dataset, no duplicates), bit-identical batch
    contents to the single-process iterator under the same epoch seed."""
    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    single = list(iter(_loader(man, workers=2)))
    led = ChunkLedger()
    out = _consume_parallel({
        0: _loader(man, ledger=led, host=0, hosts=2),
        1: _loader(man, ledger=led, host=1, hosts=2)})
    union = [i for h in out for b in out[h] for i in b.ids]
    assert sorted(union) == sorted(man.record_ids())
    assert len(union) == len(set(union))  # no duplicates
    by_chunk = {}
    for b in single:
        by_chunk.setdefault(b.chunk_id, []).append(b)
    for h in out:
        for b in out[h]:
            ref = by_chunk[b.chunk_id].pop(0)
            assert np.array_equal(b.data, ref.data)
            assert np.array_equal(b.label, ref.label)
            assert b.ids == ref.ids
    assert all(not v for v in by_chunk.values())
    # second epoch reshuffles but stays exactly-once
    b2 = list(iter(_loader(man, workers=1, start_epoch=1)))
    assert sorted(i for b in b2 for i in b.ids) == sorted(man.record_ids())
    assert [b.chunk_id for b in b2] != [b.chunk_id for b in single] or \
        any(b.ids != r.ids for b, r in zip(b2, single))


def test_backpressure_bounded_buffer_and_its_host_bytes(tmp_path):
    from mxnet_tpu import diagnostics

    man = ShardManifest(make_shards(tmp_path, per_shard=24),
                        chunk_records=8)
    ldr = _loader(man, workers=2, buffer_batches=2)
    it = iter(ldr)
    first = next(it)
    # give the workers time to run ahead as far as they ever could
    ldr.fleet._stop.wait(0.25)
    depth = ldr.fleet._q.qsize()
    assert depth <= 2, "buffer exceeded its bound (no backpressure)"
    from mxnet_tpu import telemetry

    def buffered():
        return telemetry.gauge("mxt_data_buffer_bytes", "",
                               ("host",)).labels("0").value

    # numpy batches on the host: a gauge of their own, and nothing in the
    # HBM ledger, which counts device memory
    assert buffered() == depth * (4 * 4 * 4 + 4 * 4)
    pool = diagnostics.ledger().snapshot().get("prefetch", {})
    assert not any("data-plane" in k for k in pool.get("entries", {}))
    rest = list(it)
    ids = sorted(i for b in [first] + rest for i in b.ids)
    assert ids == sorted(man.record_ids())
    assert buffered() == 0  # released at the epoch's end
    page = telemetry.render_prometheus()
    assert 'mxt_data_queue_depth{host="0"} 0' in page


def test_data_wait_telemetry_per_host(tmp_path):
    from mxnet_tpu import telemetry

    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    led = ChunkLedger()
    _consume_parallel({0: _loader(man, ledger=led, host=0, hosts=2),
                       1: _loader(man, ledger=led, host=1, hosts=2)})
    page = telemetry.render_prometheus()
    # host-labeled gauges/counters: the fleet collector scrapes these
    # for free (registry families, no reserved labels)
    for h in ("0", "1"):
        assert 'mxt_data_records_total{host="%s"}' % h in page
        assert 'mxt_data_wait_seconds_total{host="%s"}' % h in page
        assert 'mxt_data_records_per_second{host="%s"}' % h in page
    # the data_wait phase span feeds the EXISTING histogram (goodput's
    # lost-time tap hangs off the same span)
    assert "mxt_step_phase_seconds" in page
    assert 'phase="data_wait"' in page


def test_cursor_resume_sample_exact(tmp_path):
    """A killed-and-resumed host restarts mid-epoch with no loss and no
    duplication: fully-consumed chunks are never re-decoded, a partial
    chunk's consumed head is dropped on replay (decode determinism
    makes the continuation sample-exact)."""
    man = ShardManifest(make_shards(tmp_path, n_shards=1, per_shard=64),
                        chunk_records=16)
    full = list(iter(_loader(man, seed=5)))
    l1 = _loader(man, seed=5)
    it = iter(l1)
    head = [next(it) for _ in range(6)]  # 1.5 chunks
    cur = l1.cursor()
    it.close()  # the crash point
    assert cur["epoch"] == 0 and (cur["committed"] or cur["partial"])
    l2 = _loader(man, seed=5).restore_cursor(cur)
    tail = list(iter(l2))
    ids = sorted(i for b in head + tail for i in b.ids)
    assert ids == sorted(man.record_ids())
    by_chunk = {}
    for b in full:
        by_chunk.setdefault(b.chunk_id, []).append(b)
    for b in head + tail:
        ref = by_chunk[b.chunk_id].pop(0)
        assert np.array_equal(b.data, ref.data)
    assert all(not v for v in by_chunk.values())
    # the cursor is JSON-serializable (rides CheckpointManager extra=)
    import json

    json.dumps(cur)
    # a cursor from another dataset is refused typed
    (tmp_path / "o").mkdir()
    other = ShardManifest(make_shards(tmp_path / "o", per_shard=8),
                          chunk_records=8)
    with pytest.raises(MXNetError):
        _loader(other).restore_cursor(cur)


# --------------------------------------------------------------------------
# wire path (async server transport)
# --------------------------------------------------------------------------
def test_remote_ledger_over_async_server(tmp_path):
    from mxnet_tpu.async_server import AsyncClient, AsyncParamServer

    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    srv = AsyncParamServer("127.0.0.1", 0)
    try:
        port = srv._sock.getsockname()[1]
        srv.attach_data_plane(ChunkLedger())
        ledgers = {h: RemoteLedger(AsyncClient("127.0.0.1", port,
                                               timeout=5.0))
                   for h in (0, 1)}
        out = _consume_parallel({
            h: _loader(man, ledger=ledgers[h], host=h, hosts=2)
            for h in (0, 1)})
        union = [i for h in out for b in out[h] for i in b.ids]
        assert sorted(union) == sorted(man.record_ids())
        assert len(union) == len(set(union))
        # cursor round-trips over the wire too
        cur = ledgers[0].cursor()
        assert cur["committed"] and cur["epoch"] == 0
        # zombie fencing is typed ACROSS the transport: the 'stale'
        # reply surfaces as StaleWorkerError on the zombie's side
        srv.data_plane.begin_epoch(man.manifest_id, 1,
                                   man.owners(1, 2, seed=3))
        (cid, tok), = ledgers[0].lease(0, 1)
        ledgers[0].fence_host(0)
        with pytest.raises(StaleWorkerError):
            ledgers[0].commit(0, cid, tok)
        for led in ledgers.values():
            led.close()
    finally:
        srv.close()


def test_membership_reap_fences_data_ledger(tmp_path):
    """The membership reaper's death listener reclaims a dead host's
    chunks — the wiring attach_data_plane installs."""
    from mxnet_tpu.async_server import AsyncParamServer

    man = ShardManifest(make_shards(tmp_path), chunk_records=8)
    srv = AsyncParamServer("127.0.0.1", 0)
    try:
        led = srv.attach_data_plane(ChunkLedger())
        led.begin_epoch(man.manifest_id, 0, man.owners(0, 2, seed=1))
        led.lease(1, 1)
        srv.membership.register(1, now=0.0)
        srv.membership.reap(timeout=1.0, now=100.0)  # rank 1 is dead
        stats = led.stats()
        assert 1 in stats["fenced"]
        assert stats["reclaimable"] > 0
    finally:
        srv.close()


# --------------------------------------------------------------------------
# chaos cells (swept per seed by tools/chaos_matrix.sh)
# --------------------------------------------------------------------------
@pytest.mark.chaos
def test_chaos_host_kill_steal_and_zombie_refusal(tmp_path):
    """ISSUE acceptance: host killed mid-epoch -> epoch completes with
    0 lost / 0 duplicated samples, steal counter > 0, and the stale
    zombie commit is refused typed."""
    man = ShardManifest(make_shards(tmp_path, per_shard=40),
                        chunk_records=8)
    config.set_default(
        "MXT_FAULT",
        "data_host_kill:host=1,after=2,n=1,seed=%d" % _seed())
    led = ChunkLedger()
    # Host 0 must not drain host 1's queue before host 1 has committed
    # the two chunks its death waits for (a host that is dry steals from
    # a LIVE peer's tail too, and a host 1 left with nothing to commit
    # is never killed). Backpressure is the barrier: with room for one
    # batch, host 0's worker blocks inside its second chunk until its
    # consumer drains it, and its consumer waits for host 1 to be dead.
    survivor = _loader(man, ledger=led, host=0, hosts=2, buffer_batches=1)
    victim = _loader(man, ledger=led, host=1, hosts=2)
    out = {0: [], 1: []}
    t = threading.Thread(target=lambda: out[1].extend(victim))
    t.start()
    it = iter(survivor)
    out[0].append(next(it))  # both fleets lease from the ledger now
    t.join(60)
    assert not t.is_alive(), "host consumer hung"
    assert victim.fleet.killed
    assert led.stats()["reclaimable"] == 3  # 5 owned less 2 committed
    out[0].extend(it)
    stats = led.stats()
    assert stats["committed"] == stats["total"]  # epoch completed
    assert stats["steals"] == 3                  # survivor took them all
    assert stats["fenced"] == [1]
    assert len({b.chunk_id for b in out[1]}) == 2  # its committed prefix
    # exactly-once across the union of what BOTH consumers received
    # (the killed host dies at a chunk-commit boundary, so its consumed
    # prefix is exactly its committed chunks)
    union = [i for h in out for b in out[h] for i in b.ids]
    assert sorted(union) == sorted(man.record_ids())  # 0 lost
    assert len(union) == len(set(union))              # 0 duplicated
    # the zombie's stale lease commit is refused typed
    with pytest.raises(StaleLeaseError):
        led.commit(1, out[1][0].chunk_id if out[1] else 0, 10 ** 6)


@pytest.mark.chaos
def test_chaos_worker_slow_triggers_steal_bounded_wait(tmp_path):
    """Slow host -> the healthy peer's steal fires and the epoch
    completes exactly-once; the healthy host never waits on the slow
    peer's chunks — it steals them."""
    man = ShardManifest(make_shards(tmp_path, per_shard=40),
                        chunk_records=8)
    config.set_default(
        "MXT_FAULT",
        "data_worker_slow:host=1,ms=60,seed=%d" % _seed())
    led = ChunkLedger()
    loaders = {0: _loader(man, ledger=led, host=0, hosts=2, workers=2),
               1: _loader(man, ledger=led, host=1, hosts=2)}
    out = _consume_parallel(loaders)
    stats = led.stats()
    assert stats["committed"] == stats["total"]
    assert stats["steals"] > 0, "steal never fired against the slow host"
    union = [i for h in out for b in out[h] for i in b.ids]
    assert sorted(union) == sorted(man.record_ids())
    assert len(union) == len(set(union))
    # bounded by what was done, not by a clock: the healthy host served
    # more chunks than the five it owned (it stole the slow host's tail
    # instead of waiting for it), and the slow host fewer
    served = {h: len({b.chunk_id for b in out[h]}) for h in out}
    assert served[0] > 5 > served[1], served
    assert served[0] + served[1] == stats["total"] == 10


# --------------------------------------------------------------------------
# integration satellites
# --------------------------------------------------------------------------
def test_streaming_jpeg_two_hosts_one_epoch(tmp_path):
    """The whole feed path over a tiny JPEG RecordIO set (48 images of
    96 x 96 in two shards; resize 48, random crop 32, batches of 8, one
    chunk a batch) as two in-process hosts on one ledger, the second
    with one decode worker: every record is delivered once as a float32
    NHWC batch on the device, each host's ``data_wait`` is recorded, and
    the ledger carries its steal counter."""
    from mxnet_tpu import telemetry

    rng = np.random.RandomState(0)
    shards, gid = [], 0
    for s in range(2):
        rec = str(tmp_path / ("part-%d.rec" % s))
        w = recordio.MXIndexedRecordIO(
            str(tmp_path / ("part-%d.idx" % s)), rec, "w")
        for _ in range(24):
            img = np.kron(rng.randint(0, 255, (8, 8, 3)),
                          np.ones((12, 12, 1))).astype(np.uint8)
            w.write_idx(gid, recordio.pack_img(
                recordio.IRHeader(0, float(gid % 10), gid, 0), img,
                img_fmt=".jpg", quality=90))
            gid += 1
        w.close()
        shards.append(rec)
    man = ShardManifest(shards, chunk_records=8)
    decoder = ImageDecoder((3, 32, 32), rand_crop=True, resize=48,
                           layout="NHWC", dtype="float32")
    led = ChunkLedger()

    def waited(h):
        fam = telemetry.registry().get("mxt_data_wait_seconds_total")
        return fam.labels(str(h)).value if fam is not None else 0.0

    before = {h: waited(h) for h in (0, 1)}
    out = _consume_parallel({
        h: StreamingDataLoader(man, 8, decoder, host_id=h, num_hosts=2,
                               ledger=led, seed=0,
                               num_workers=2 if h == 0 else 1)
        for h in (0, 1)})
    union = [i for h in out for b in out[h] for i in b.ids]
    assert sorted(union) == sorted(man.record_ids())  # every record
    assert len(union) == len(set(union)) == 48        # once
    for b in out[0] + out[1]:
        assert isinstance(b.data, mx.nd.NDArray)
        assert b.data.shape == (8, 32, 32, 3) and b.data.dtype == np.float32
        assert [int(v) for v in b.label.asnumpy()] \
            == [key % 10 for _, key in b.ids]
    stats = led.stats()
    assert stats["committed"] == stats["total"] == 6
    assert isinstance(stats["steals"], int) and stats["steals"] >= 0
    for h in (0, 1):
        if out[h]:
            assert waited(h) > before[h], "host %d: no data_wait" % h


# --------------------------------------------------------------------------
# the pipeline's spans in the profiler's trace (ISSUE 39)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_epochs(tmp_path_factory):
    """Two epochs of a tiny seeded set under ``jax.profiler.trace`` on the
    CPU, through the device put and the prefetcher, with what the older
    readers of the consumer's wait received meanwhile."""
    import jax

    from mxnet_tpu import profiler_trace, telemetry

    root = tmp_path_factory.mktemp("traced")
    man = ShardManifest(make_shards(root, per_shard=24), chunk_records=8)
    ldr = StreamingDataLoader(
        man, 4, ArrayDecoder((4,), "float32"), host_id=0, num_hosts=1, seed=5,
        num_workers=2, buffer_batches=2, prefetch_to_device=True)

    def total(name):
        return telemetry.counter(name, "", ("host",)).labels("0").value

    names = ("mxt_data_wait_seconds_total", "mxt_data_read_seconds_total",
             "mxt_data_decode_seconds_total", "mxt_data_put_wait_seconds_total")
    before = {n: total(n) for n in names}
    phases, record_phase = [], telemetry.record_phase

    def tap(phase, seconds, **kw):
        if phase == "data_wait" and kw.get("stream") == "data_plane":
            phases.append((kw["step"], seconds))
        record_phase(phase, seconds, **kw)

    telemetry.record_phase = tap
    try:
        with jax.profiler.trace(str(root / "trace")):
            yielded = [list(iter(ldr)), list(iter(ldr))]
    finally:
        telemetry.record_phase = record_phase
    return {"spans": profiler_trace.host_spans(str(root / "trace")),
            "yielded": yielded, "phases": phases, "manifest": man,
            "counters": {n: total(n) - before[n] for n in names}}


def _named(spans, name):
    return [(s, e, a) for n, s, e, a, _ in spans if n == "mxt.data." + name]


def test_every_batch_has_its_spans_under_one_batch_value(traced_epochs):
    from mxnet_tpu import profiler_trace

    spans, yielded = traced_epochs["spans"], traced_epochs["yielded"]
    assert [len(e) for e in yielded] == [12, 12]
    n_batches = 24
    tags = [a["batch"] for _, _, a in _named(spans, "got")]
    assert len(set(tags)) == n_batches
    # "<epoch>:<chunk>:<k>": both epochs, every chunk, both batches of a chunk
    assert {t.split(":")[0] for t in tags} == {"0", "1"}
    assert {int(t.split(":")[2]) for t in tags} == {0, 1}
    for kind in ("decode", "put", "h2d", "got"):
        assert sorted(a["batch"] for _, _, a in _named(spans, kind)) == sorted(tags), kind
    for tag in tags:  # in the order a batch passes them
        starts = [[s for s, _, a in _named(spans, kind) if a["batch"] == tag]
                  for kind in ("decode", "put", "h2d", "got")]
        assert all(len(s) == 1 for s in starts) and starts == sorted(starts), tag
    # the chunk in the tag is the chunk the loader reports, in the order yielded
    chunk_of = {(int(a["batch"].split(":")[0]), a["n"]): int(a["batch"].split(":")[1])
                for _, _, a in _named(spans, "got")}
    for ep, epoch in enumerate(yielded):
        assert [b.chunk_id for b in epoch] == \
            [chunk_of[ep, i + 1] for i in range(len(epoch))]
    # one wait a got, under its n (and one more an epoch, for the stream's end)
    waits = [(a["n"], a["epoch"]) for _, _, a in _named(spans, "wait")]
    assert sorted(waits) == sorted([(n, ep) for ep in (0, 1) for n in range(1, 14)])
    assert all(set(a) == {"n", "epoch", "depth"} for _, _, a in _named(spans, "wait"))
    _, calls = profiler_trace.span_totals(spans)
    assert calls["mxt.data.decode"] == n_batches
    assert calls["mxt.data.commit"] == 2 * traced_epochs["manifest"].num_chunks
    # the arguments the issue names
    assert all(set(a) == {"wid", "batch", "records", "bytes_in"} and a["records"] == 4
               and a["bytes_in"] > 0 for _, _, a in _named(spans, "decode"))
    assert all(set(a) == {"wid", "batch", "depth"} for _, _, a in _named(spans, "put"))
    assert all(a["bytes"] == 4 * 4 * 4 + 4 * 4 for _, _, a in _named(spans, "h2d"))
    # the consumer's spans on one thread, the workers' on others
    by_thread = {}
    for name, _, _, _, thread in spans:
        by_thread.setdefault(name.rpartition(".")[2], set()).add(thread)
    (consumer,) = by_thread["wait"]
    assert all(by_thread[k] == {consumer} for k in ("got", "h2d", "epoch_begin", "epoch_end"))
    assert all(consumer not in by_thread[k] for k in ("lease", "decode", "commit", "put"))
    leases = [a for _, _, a in _named(spans, "lease")]
    assert sum(a.get("granted", 0) for a in leases) == calls["mxt.data.commit"]
    assert all(a.get("stolen", 0) == 0 for a in leases)  # one host: nothing to steal


def test_an_epochs_turn_is_two_spans(traced_epochs):
    spans = traced_epochs["spans"]
    begins, ends = _named(spans, "epoch_begin"), _named(spans, "epoch_end")
    assert [a["epoch"] for _, _, a in begins] == [0, 1]
    assert [a["epoch"] for _, _, a in ends] == [0, 1]
    # between the two epochs: the first one's end, then the second one's begin
    assert begins[0][1] <= ends[0][0] and ends[0][1] <= begins[1][0]
    gots = sorted(s for s, _, _ in _named(spans, "got"))
    assert sum(ends[0][1] <= s for s in gots) == 12 == sum(s <= ends[0][0] for s in gots)
    # every worker span of an epoch lies between its begin's start and its end's end
    for kind in ("lease", "decode", "commit", "put"):
        for s, e, a in _named(spans, kind):
            ep = int(a["batch"].split(":")[0]) if "batch" in a else int(s >= begins[1][0])
            assert begins[ep][0] <= s and e <= ends[ep][1], (kind, a)


def test_one_clock_read_feeds_the_counter_the_phase_and_the_span(traced_epochs):
    """``dt`` is taken once: the phase's observations add up to the counter's
    rise to the last bit, and each lies inside its span (the two open and
    close together, so the span is the longer by the annotation's own cost)."""
    phases = traced_epochs["phases"]
    assert [n for n, _ in phases] == list(range(1, 13)) * 2
    total = 0.0
    for _, dt in phases:
        total += dt
    assert traced_epochs["counters"]["mxt_data_wait_seconds_total"] == \
        pytest.approx(total, rel=1e-12)
    spans = sorted((a["epoch"], a["n"], (e - s) / 1e12)
                   for s, e, a in _named(traced_epochs["spans"], "wait") if a["n"] <= 12)
    assert len(spans) == len(phases)
    for (_, n, span_s), (step, dt) in zip(spans, phases):
        assert n == step and span_s >= dt - 1e-6
    assert sum(s for _, _, s in spans) - total < 0.05


def test_read_and_decode_seconds_lie_inside_the_decode_spans(traced_epochs):
    c = traced_epochs["counters"]
    decode_spans = sum(e - s for s, e, _ in _named(traced_epochs["spans"], "decode")) / 1e12
    inside = c["mxt_data_read_seconds_total"] + c["mxt_data_decode_seconds_total"]
    assert 0 < c["mxt_data_read_seconds_total"] and 0 < c["mxt_data_decode_seconds_total"]
    assert inside <= decode_spans
    put_spans = sum(e - s for s, e, _ in _named(traced_epochs["spans"], "put")) / 1e12
    assert 0 < put_spans <= c["mxt_data_put_wait_seconds_total"] + 1e-6


def test_resume_replay_stays_out_of_the_waits_count(tmp_path):
    """A resumed epoch pulls the consumed head of a partial chunk again and
    drops it: no ``data_wait`` observation, no ``n``, as before the spans."""
    from mxnet_tpu import telemetry

    man = ShardManifest(make_shards(tmp_path, n_shards=1, per_shard=16), chunk_records=8)
    first = _loader(man)
    it = iter(first)
    head = [next(it)]
    cursor = first.cursor()
    it.close()
    assert cursor["partial"]
    seen, record_phase = [], telemetry.record_phase

    def tap(phase, seconds, **kw):
        if phase == "data_wait":
            seen.append(kw["step"])
        record_phase(phase, seconds, **kw)

    telemetry.record_phase = tap
    try:
        rest = list(iter(_loader(man).restore_cursor(cursor)))
    finally:
        telemetry.record_phase = record_phase
    assert sorted(i for b in head + rest for i in b.ids) == sorted(man.record_ids())
    assert seen == [1, 2, 3]  # four batches pulled, the replayed one uncounted


def test_per_process_dataloader_names_the_same_wait(tmp_path):
    import jax

    from mxnet_tpu import gluon, profiler_trace

    data = gluon.data.ArrayDataset(np.arange(12, dtype=np.float32).reshape(6, 2))
    with jax.profiler.trace(str(tmp_path)):
        got = list(gluon.data.DataLoader(data, batch_size=2))
    assert len(got) == 3
    waits = [sp[3] for sp in profiler_trace.host_spans(str(tmp_path))
             if sp[0] == "mxt.data.wait"]
    assert [a["n"] for a in waits] == [1, 2, 3, 4]  # the fourth finds the end


def test_trace_input_rehearses_on_the_cpu():
    """``tools/trace_input.py --rehearse``: a 64-record set and a two-layer
    net through the tool's own path, its JSON line with every key, and its two
    checks deciding the exit code (3: passed, on the CPU, never a result)."""
    import json
    import subprocess
    import sys

    tool = os.path.join(os.path.dirname(__file__), "..", "tools", "trace_input.py")
    run = subprocess.run(
        [sys.executable, tool, "--rehearse", "--seed", "3000000019", "--seconds", "0.6",
         "--trace-seconds", "0.3", "--workers", "2", "--buffer-batches", "2"],
        capture_output=True, text=True, timeout=120)
    assert run.returncode == 3, run.stderr[-2000:]
    row = json.loads(run.stdout.strip().splitlines()[-1])
    assert row["rehearse"] and row["device"]["platform"] == "cpu" and row["wrong"] == []
    assert row["images_per_s"] is None  # no speed from a CPU
    assert row["launches_per_step"] == 1.0  # the cast and the normalisation inside it
    for key in ("steps", "workers", "buffer_batches", "records", "stored_bytes_per_record",
                "read_us_per_record", "decode_us_per_record", "data_wait_share"):
        assert key in row, key
    traced = row["traced"]
    for key in ("images_per_s", "steps", "busy_s", "window_s", "idle_share",
                "idle_by_span_s", "worker_share_by_state", "consumer_share_by_span",
                "host_span_calls",
                "h2d_ms_per_batch", "h2d_MB_per_s", "data_wait_ms", "epoch_turns",
                "epoch_turn_ms_longest", "clock_offset_us"):
        assert key in traced, key
    calls = traced["host_span_calls"]
    assert calls["mxt.data.got"] == calls["mxt.step.dispatch"] == traced["steps"]
    assert traced["epoch_turns"] >= 1


def test_check_host_syncs_covers_data_plane():
    """Lint regression: the data-plane modules are SCANNED (a removal
    would silently drop coverage) and currently clean — worker-boundary
    numpy is sync-ok annotated, the feed path has no unmarked syncs."""
    import importlib.util

    root = os.path.join(os.path.dirname(__file__), "..")
    spec = importlib.util.spec_from_file_location(
        "check_host_syncs",
        os.path.join(root, "tools", "check_host_syncs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for rel in ("mxnet_tpu/data_plane/manifest.py",
                "mxnet_tpu/data_plane/ledger.py",
                "mxnet_tpu/data_plane/workers.py",
                "mxnet_tpu/data_plane/loader.py"):
        assert rel in mod.SCAN, "%s dropped from the sync lint" % rel
    assert mod.check(root) == []


def test_mxt_top_data_section():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxt_top", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "mxt_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    samples = {
        ("mxt_data_records_per_second", frozenset({("host", "0")})): 900.0,
        ("mxt_data_records_per_second", frozenset({("host", "1")})): 400.0,
        ("mxt_data_queue_depth", frozenset({("host", "0")})): 3,
        ("mxt_data_queue_depth", frozenset({("host", "1")})): 0,
        ("mxt_data_buffer_bytes", frozenset({("host", "0")})): 3 * 1024 * 1024,
        ("mxt_data_steals_total", frozenset({("host", "0")})): 4,
        ("mxt_data_stale_leases_total", frozenset({("host", "1")})): 1,
        ("mxt_data_wait_seconds_total", frozenset({("host", "1")})): 2.5,
    }
    frame = mod.render(samples, None, 0)
    assert "data rec/s" in frame and "h0 900" in frame
    assert "steals 4" in frame and "stale refused 1" in frame
    assert "h0 3 (3.0MB on the host)" in frame  # numpy batches, not HBM
    assert "data_wait share" in frame
    # a process without a data plane renders no data noise
    assert "data rec/s" not in mod.render({}, None, 0)
