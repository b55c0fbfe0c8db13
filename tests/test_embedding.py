"""Distributed sparse embedding parameter server (mxnet_tpu/embedding/
+ the kvstore 'dist_embedding' type + gluon.Trainer routing).

Fleet tests run IN-PROCESS (embedding.local_fleet — real sockets on
loopback, real membership registrations, no subprocesses) with bounded
polls and millisecond retry budgets — no wall-clock sleeps. The
chaos-marked cells (embedding_server_kill) are swept per seed by
tools/chaos_matrix.sh via MXT_CHAOS_SEED.
"""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import embedding, nd
from mxnet_tpu import optimizer as opt
from mxnet_tpu.base import MXNetError
from mxnet_tpu.membership import StaleWorkerError


def _seed():
    return int(os.environ.get("MXT_CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch):
    """Dead servers must surface in milliseconds, not the production
    30s retry budget; membership stays on (fencing active)."""
    monkeypatch.setenv("MXT_KV_RETRIES", "1")
    monkeypatch.setenv("MXT_KV_RETRY_BASE", "0.02")
    monkeypatch.setenv("MXT_KV_RETRY_MAX", "0.05")
    monkeypatch.setenv("MXT_MEMBERSHIP", "1")
    yield


@pytest.fixture
def fleet2():
    fleet, handles = embedding.local_fleet(2, worker_id=0, timeout=3.0)
    yield fleet, handles
    fleet.close()
    # non-coordinator servers first: their graceful deregister needs
    # server 0 (the fleet coordinator) still listening
    for h in reversed(handles):
        try:
            h.close()
        except Exception:  # noqa: BLE001 — killed handles
            pass


def _counter_total(name):
    from mxnet_tpu import telemetry

    fam = telemetry.registry().get(name)
    if fam is None:
        return 0.0
    return float(sum(ch.value for ch in fam.children().values()))


# ---------------------------------------------------------------------------
# consistent-hash ring
# ---------------------------------------------------------------------------
def test_hash_ring_balance_and_stability():
    ring = embedding.HashRing(vnodes=64).rebuild([0, 1, 2, 3])
    ids = np.arange(20000)
    owners = np.array([ring.owner(i) for i in ids])
    counts = np.bincount(owners, minlength=4)
    # vnodes smooth placement: no server owns more than ~2x its share
    assert counts.min() > 0 and counts.max() < 2 * len(ids) / 4
    # removing one server remaps ONLY that server's rows
    ring.rebuild([0, 1, 3])
    moved = sum(1 for i in ids if owners[i] != 2
                and ring.owner(i) != owners[i])
    assert moved == 0
    # determinism: a fresh ring over the same member set routes the same
    ring2 = embedding.HashRing(vnodes=64).rebuild([0, 1, 3])
    assert all(ring.owner(i) == ring2.owner(i) for i in ids[:500])


def test_route_covers_batch_one_group_per_server():
    ring = embedding.HashRing(vnodes=16).rebuild(["a", "b"])
    ids = np.random.RandomState(_seed()).randint(0, 10000, size=300)
    routed = ring.route(ids)
    assert set(routed) <= {"a", "b"}
    all_pos = np.sort(np.concatenate(list(routed.values())))
    assert np.array_equal(all_pos, np.arange(len(ids)))


# ---------------------------------------------------------------------------
# hot-row cache
# ---------------------------------------------------------------------------
def test_hot_row_cache_lru_and_telemetry():
    from mxnet_tpu import diagnostics

    cache = embedding.HotRowCache("t_unit", capacity=4, dim=2)
    assert diagnostics.ledger().pool_bytes("hot_row_cache") >= 4 * 2 * 4
    rows = np.arange(12, dtype=np.float32).reshape(6, 2)
    cache.insert([0, 1, 2, 3], rows[:4])
    hit_pos, hit_slots, miss_pos = cache.lookup([0, 2, 9])
    assert len(hit_pos) == 2 and list(miss_pos) == [2]
    got = np.asarray(cache.gather(hit_slots))
    assert np.allclose(got, rows[[0, 2]])
    # 0 and 2 are now most-recent; inserting two new rows evicts 1, 3
    cache.insert([4, 5], rows[4:6])
    assert len(cache) == 4
    _, _, miss = cache.lookup([1, 3])
    assert len(miss) == 2
    _, _, miss = cache.lookup([0, 2, 4, 5])
    assert len(miss) == 0
    cache.invalidate([0])
    _, _, miss = cache.lookup([0])
    assert len(miss) == 1
    assert 0.0 < cache.hit_ratio < 1.0
    cache.close()
    assert diagnostics.ledger().pool_bytes("hot_row_cache") == 0 or \
        "t_unit" not in diagnostics.ledger().snapshot().get(
            "hot_row_cache", {}).get("entries", {})


# ---------------------------------------------------------------------------
# sharded push/pull
# ---------------------------------------------------------------------------
def test_push_pull_roundtrip_two_servers(fleet2):
    fleet, _ = fleet2
    init = np.random.RandomState(_seed()).randn(64, 8).astype(np.float32)
    tbl = embedding.ShardedEmbedding(fleet, "rt", (64, 8), cache_rows=16)
    tbl.init(init)
    fleet.set_optimizer(opt.create("sgd", learning_rate=0.5))
    ids = np.array([1, 5, 5, 40])  # duplicate combines client-side
    got = np.asarray(tbl.pull(ids))
    assert got.shape == (4, 8)
    assert np.allclose(got, init[ids])
    g = np.ones((4, 8), np.float32)
    tbl.push(ids, g)  # id 5 contributes twice -> grad 2.0
    after = np.asarray(tbl.pull(np.array([1, 5, 40, 0])))
    exp = init.copy()
    exp[[1, 40]] -= 0.5
    exp[5] -= 0.5 * 2.0
    assert np.allclose(after[:3], exp[[1, 5, 40]], atol=1e-6)
    assert np.allclose(after[3], init[0])
    tbl.close()


def test_batched_ops_cost_one_rpc_per_server(fleet2):
    fleet, _ = fleet2
    tbl = embedding.ShardedEmbedding(fleet, "rpc", (1000, 4),
                                     cache_rows=0)
    tbl.init(np.zeros((1000, 4), np.float32))
    ids = np.arange(500)  # spans both servers for sure
    routed = fleet.ring.route(ids)
    assert len(routed) == 2
    r0 = _counter_total("mxt_embedding_rpcs_total")
    tbl.pull(ids)
    pulls = _counter_total("mxt_embedding_rpcs_total") - r0
    assert pulls == len(routed)  # <=1 RPC per destination server
    r0 = _counter_total("mxt_embedding_rpcs_total")
    tbl.push(ids, np.ones((500, 4), np.float32))
    pushes = _counter_total("mxt_embedding_rpcs_total") - r0
    assert pushes == len(routed)
    tbl.close()


def test_sparse_path_compile_count_bucket_bounded():
    """The PR-11 finding fixed: varying data-dependent unique-row
    counts replay pow2-bucketed programs instead of recompiling the
    sparse path per step (PERF.md measured ~320 compiles/8 steps) —
    after a short shape warmup, steps with FRESH row counts inside the
    same buckets compile NOTHING. One server (multi-server scatter
    threads can race-compile the same program — concurrency noise) and
    no hot-row cache (its hit/miss split drifts as the LRU fills,
    legitimately minting a new smaller bucket mid-run; the cache
    bucket path is covered by the cache tests) keep the lap exact."""
    import jax

    from mxnet_tpu import tuning

    # hermetic: earlier suites can leave jax's bounded eager-dispatch
    # caches near eviction, which would charge THEIR evictions to this
    # test's measured lap
    jax.clear_caches()
    fleet, handles = embedding.local_fleet(1, worker_id=0, timeout=3.0)
    tbl = embedding.ShardedEmbedding(fleet, "cc", (4096, 8),
                                     cache_rows=0)
    tbl.init_lazy(seed=1)
    fleet.set_optimizer(opt.create("sgd", learning_rate=0.1))
    rng = np.random.RandomState(_seed())

    def step(vocab):
        # batch size FIXED (the training-loop shape); the UNIQUE count
        # is data-dependent via the draw range — the exact shape class
        # that used to mint fresh programs every step
        ids = rng.randint(0, vocab, 320).astype(np.int64)
        rows = tbl.pull(ids)
        tbl.push(ids, np.asarray(rows) * 0.01)

    vocabs = (3000, 500, 1500, 420, 2500)
    for _ in range(2):  # warm every bucket this distribution visits
        for vocab in vocabs:
            step(vocab)
    c0 = tuning.compile_stats()
    for vocab in vocabs:  # fresh draws -> fresh unique/hit/miss counts
        step(vocab)
    c1 = tuning.compile_stats()
    fresh = c1["compiles"] - c0["compiles"]
    assert fresh == 0, \
        "sparse path compiled %d fresh programs for same-bucket shapes" \
        % fresh
    tbl.close()
    fleet.close()
    for h in handles:
        h.close()


def test_cache_write_back_on_push(fleet2):
    fleet, _ = fleet2
    tbl = embedding.ShardedEmbedding(fleet, "wb", (50, 4), cache_rows=32)
    tbl.init(np.zeros((50, 4), np.float32))
    fleet.set_optimizer(opt.create("sgd", learning_rate=1.0))
    ids = np.arange(10)
    tbl.pull(ids)  # cold: misses fill the cache
    tbl.push(ids, np.ones((10, 4), np.float32))  # reply writes back
    r0 = _counter_total("mxt_embedding_rpcs_total")
    after = np.asarray(tbl.pull(ids))
    # the post-push pull is served ENTIRELY from the device cache...
    assert _counter_total("mxt_embedding_rpcs_total") == r0
    # ...with the server-updated values, not the stale pre-push rows
    assert np.allclose(after, -1.0)
    tbl.close()


def test_lazy_init_never_materializes_table(fleet2):
    fleet, handles = fleet2
    tbl = embedding.ShardedEmbedding(fleet, "lazy", (10 ** 6, 8),
                                     cache_rows=64)
    tbl.init_lazy(seed=3, scale=0.5)
    ids = np.array([0, 123456, 999999])
    rows = np.asarray(tbl.pull(ids))
    assert rows.shape == (3, 8) and np.abs(rows).max() > 0
    # deterministic: a second pull through a fresh fleet-side path
    # (cache bypass) returns identical values
    rows2 = np.asarray(tbl.pull(ids))
    assert np.allclose(rows, rows2)
    # only the touched rows exist anywhere in the fleet
    resident = sum(h.store.rows_resident() for h in handles)
    assert resident == 3
    tbl.close()


# ---------------------------------------------------------------------------
# generation + ring-epoch fencing for sparse pushes
# ---------------------------------------------------------------------------
def test_fenced_worker_sparse_push_refused_typed():
    fleet, handles = embedding.local_fleet(1, worker_id=7, timeout=3.0)
    try:
        tbl = embedding.ShardedEmbedding(fleet, "f", (20, 4),
                                         cache_rows=0)
        tbl.init(np.zeros((20, 4), np.float32))
        fleet.set_optimizer(opt.create("sgd", learning_rate=1.0))
        tbl.push([1], np.ones((1, 4), np.float32))
        # a second incarnation of worker 7 registers: the first fleet's
        # generation is fenced — its delayed gradient rows must be
        # refused typed and must not touch the weights
        fleet2 = embedding.EmbeddingFleet(coordinator=fleet.coordinator,
                                          timeout=3.0)
        fleet2.refresh()
        fleet2.register_worker(7)
        with pytest.raises(StaleWorkerError, match="fenced"):
            tbl.push([1], np.full((1, 4), 100.0, np.float32))
        tbl2 = embedding.ShardedEmbedding(fleet2, "f", (20, 4),
                                          cache_rows=0)
        vals = np.asarray(tbl2.pull([1]))
        assert np.allclose(vals, -1.0)  # only the live push landed
        fleet2.close()
    finally:
        fleet.close()
        for h in reversed(handles):
            h.close()


def test_reshard_inherited_rows_adopt_ring_epoch():
    """A server that inherits rows (emb_load) adopts the sender's ring
    epoch: a push stamped from BEFORE the reshard is refused typed; the
    client-side heal path refreshes the ring and re-sends under the
    current epoch."""
    from mxnet_tpu.embedding.store import EmbeddingStore

    store = EmbeddingStore()
    store.handle("emb_init", "t",
                 ((10, 2), "float32", np.arange(10),
                  np.zeros((10, 2), np.float32), 0))
    # reshard at epoch 5 hands rows to this server
    store.handle("emb_load", "t",
                 (np.array([3]), np.ones((1, 2), np.float32), 5))
    with pytest.raises(StaleWorkerError, match="stale ring epoch"):
        store.handle("emb_push", "t",
                     (np.array([3]), np.ones((1, 2), np.float32), 4))
    # rows untouched by the stale frame; a current-epoch push applies
    _, (found, rows, _) = store.handle("emb_pull", "t",
                                       (np.array([3]), 5))
    assert np.allclose(rows, 1.0)
    store.handle("emb_push", "t",
                 (np.array([3]), np.ones((1, 2), np.float32), 5))


def test_snapshot_crc_detects_corruption(tmp_path):
    from mxnet_tpu.embedding.store import EmbeddingStore

    store = EmbeddingStore(snapshot_dir=str(tmp_path), server_id=0)
    store.handle("emb_init", "t",
                 ((4, 2), "float32", np.arange(4),
                  np.ones((4, 2), np.float32), 0))
    path = store.save_snapshot()
    # round-trips clean
    restored = EmbeddingStore(snapshot_dir=str(tmp_path), server_id=0)
    assert restored.rows_resident() == 4
    with open(path, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff")
    with pytest.raises(MXNetError, match="CRC"):
        EmbeddingStore(snapshot_dir=str(tmp_path), server_id=0)


# ---------------------------------------------------------------------------
# kvstore 'dist_embedding' + gluon.Trainer
# ---------------------------------------------------------------------------
def test_kvstore_dist_embedding_api(monkeypatch):
    from mxnet_tpu import config, kvstore

    monkeypatch.setenv("MXT_EMBEDDING_LOCAL_SERVERS", "2")
    monkeypatch.setenv("MXT_EMBEDDING_CACHE_ROWS", "8")
    del config  # env vars read at kvstore creation
    kv = kvstore.create("dist_embedding")
    try:
        init = np.arange(40, dtype=np.float32).reshape(10, 4)
        kv.init("0", nd.array(init))
        kv.set_optimizer(opt.create("sgd", learning_rate=1.0))
        from mxnet_tpu.sparse import row_sparse_array

        grad = row_sparse_array(
            (np.ones((2, 4), np.float32), np.array([2, 7])), shape=(10, 4))
        kv.push("0", grad)
        out = nd.array(init.copy())
        kv.row_sparse_pull("0", out=out, row_ids=nd.array([2, 7]))
        got = np.asarray(out.data)
        exp = init.copy()
        exp[[2, 7]] -= 1.0
        assert np.allclose(got, exp)  # touched rows updated, rest kept
        with pytest.raises(MXNetError, match="row_sparse_pull"):
            kv.pull("0", out=out)
    finally:
        kv.close()


def _train_wide_deep(kvstore_name, iters=3, seed=0):
    mx.random.seed(0)
    from mxnet_tpu.gluon import model_zoo

    net = model_zoo.wide_deep(wide_vocab=500, deep_vocab=200, embed_dim=8,
                              hidden=(16,), classes=2, sparse_grad=True)
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    tr = mx.gluon.Trainer(net.collect_params(), "adam",
                          {"learning_rate": 1e-2}, kvstore=kvstore_name)
    rng = np.random.RandomState(seed)
    losses = []
    for _ in range(iters):
        xw = nd.array(rng.randint(0, 500, (24, 8)).astype("f4"))
        xd = nd.array(rng.randint(0, 200, (24, 4)).astype("f4"))
        y = nd.array(rng.randint(0, 2, (24,)).astype("f4"))
        with mx.autograd.record():
            out = net(xw, xd)
            loss = loss_fn(out, y).mean()
        loss.backward()
        tr.step(24)
        losses.append(float(loss.asnumpy()))
    # keyed by position: gluon name prefixes auto-increment per model
    # instantiation (widedeep0_, widedeep1_, ...) within one process
    weights = {i: np.asarray(p.data().data)
               for i, p in enumerate(tr._params)}
    kv = tr._kvstore
    stats = {}
    if kv is not None and kv.type == "dist_embedding":
        for key, t in kv._emb_tables.items():
            if t.cache is not None:
                stats[key] = (t.cache.hit_ratio, len(t.cache),
                              t.cache.capacity)
        kv.close()
    return np.asarray(losses), weights, stats


def test_wide_deep_dist_embedding_loss_parity(monkeypatch):
    """ACCEPTANCE: Wide&Deep with sharded tables and a hot-row cache
    SMALLER than the table trains loss-equal (<=1e-5) vs the
    single-process dense-KVStore baseline — with the dense towers on
    the fused step and only the hot set resident device-side."""
    base_losses, base_w, _ = _train_wide_deep("local", seed=_seed())
    monkeypatch.setenv("MXT_EMBEDDING_LOCAL_SERVERS", "2")
    monkeypatch.setenv("MXT_EMBEDDING_CACHE_ROWS", "64")  # < 500-row table
    emb_losses, emb_w, stats = _train_wide_deep("dist_embedding",
                                                seed=_seed())
    assert np.abs(base_losses - emb_losses).max() <= 1e-5
    for name in base_w:
        assert np.allclose(base_w[name], emb_w[name], atol=1e-5), name
    assert stats, "no sharded tables were created"
    for _, (ratio, resident, cap) in stats.items():
        assert cap == 64 and resident <= cap
        assert ratio > 0.0  # the write-back path produced device hits


# ---------------------------------------------------------------------------
# fan-out arithmetic + console + lint satellites
# ---------------------------------------------------------------------------
def test_embedding_fan_out_arithmetic_one_and_two_servers():
    """The same zipf-skewed pull/push traffic over a lazily initialised
    table against fleets of one and two servers: every touched row lives
    on exactly one server as the ring routes it, every server holds rows
    and applied updates, an operation costs at most one RPC a server,
    bytes moved both ways, and the hot-row cache both hit and missed.
    Whether two servers are FASTER is not a question for a shared CPU
    (ROADMAP S10)."""
    vocab, dim, batch, steps = 20000, 64, 2048, 4
    for n in (1, 2):
        fleet, handles = embedding.local_fleet(n, worker_id=0, timeout=3.0)
        name = "fan_out_%d" % n
        tbl = embedding.ShardedEmbedding(fleet, name, (vocab, dim),
                                         cache_rows=4096)
        try:
            tbl.init_lazy(seed=0, scale=0.01)
            fleet.set_optimizer(opt.create("sgd", learning_rate=0.1))
            rng = np.random.RandomState(0)
            touched = set()
            r0 = _counter_total("mxt_embedding_rpcs_total")
            b0 = _counter_total("mxt_embedding_bytes_total")
            for _ in range(steps):
                ids = (rng.zipf(1.2, size=batch) % vocab).astype(np.int64)
                touched.update(int(i) for i in ids)
                rows = tbl.pull(ids)
                tbl.push(ids, rows * 0.01)
            rpcs = _counter_total("mxt_embedding_rpcs_total") - r0
            assert 0 < rpcs / (2.0 * steps) <= n  # pull + push = 1 step
            assert _counter_total("mxt_embedding_bytes_total") > b0
            held = {h.index: h.store.rows_resident() for h in handles}
            routed = fleet.ring.route(np.array(sorted(touched)))
            assert held == {sid: len(idx) for sid, idx in routed.items()}
            assert sum(held.values()) == len(touched)
            assert len(held) == n and all(v > 0 for v in held.values())
            assert all(h.store.info()[name]["num_update"] > 0
                       for h in handles)
            assert 0.0 < tbl.cache.hit_ratio < 1.0
        finally:
            tbl.close()
            fleet.close()
            for h in reversed(handles):  # the coordinator last
                h.close()


def test_mxt_top_embedding_section():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxt_top", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "mxt_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    samples = {
        ("mxt_embedding_rows_resident", frozenset({("table", "t")})): 512,
        ("mxt_embedding_cache_hits_total",
         frozenset({("table", "t")})): 90,
        ("mxt_embedding_cache_misses_total",
         frozenset({("table", "t")})): 10,
        ("mxt_embedding_cache_evictions_total",
         frozenset({("table", "t")})): 3,
    }
    frame = mod.render(samples, None, 0)
    assert "emb rows res." in frame
    assert "0.900" in frame  # hit ratio
    # a process with no embedding gauges renders no embedding noise
    assert "emb rows res." not in mod.render({}, None, 0)


def test_merge_mixed_dense_sparse_reduces_on_device():
    """Satellite: kvstore._merge mixed dense/row_sparse lists reduce
    over the index union on device (no per-value asnumpy densify)."""
    from mxnet_tpu.kvstore import KVStore
    from mxnet_tpu.sparse import row_sparse_array

    kv = KVStore("local")
    dense = nd.array(np.ones((6, 3), np.float32))
    rsp = row_sparse_array(
        (np.full((2, 3), 2.0, np.float32), np.array([1, 4])), shape=(6, 3))
    merged = kv._merge([dense, rsp, dense])
    got = np.asarray(merged.data)
    exp = np.full((6, 3), 2.0, np.float32)
    exp[[1, 4]] += 2.0
    assert np.allclose(got, exp)
    # all-sparse stays sparse (index union)
    m2 = kv._merge([rsp, rsp])
    assert m2.stype == "row_sparse"
    assert np.allclose(np.asarray(m2._values), 4.0)


def test_host_sync_lint_covers_embedding_and_kvstore():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_host_syncs", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "check_host_syncs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for rel in ("mxnet_tpu/kvstore.py", "mxnet_tpu/embedding/client.py",
                "mxnet_tpu/embedding/cache.py",
                "mxnet_tpu/embedding/store.py",
                "mxnet_tpu/embedding/hashing.py"):
        assert rel in mod.SCAN, rel
    root = os.path.join(os.path.dirname(__file__), "..")
    assert mod.check(root) == []


# ---------------------------------------------------------------------------
# chaos: embedding_server_kill (swept by tools/chaos_matrix.sh)
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_embedding_server_kill_remap_rejoin():
    """Kill one embedding server mid-train: the ring remaps its rows to
    the survivors (worker-side re-seed via emb_load), training
    continues, and a restarted server rejoins from its shard snapshot —
    every transition typed, no hang."""
    snap = tempfile.mkdtemp()
    rng = np.random.RandomState(_seed())
    fleet, handles = embedding.local_fleet(2, snapshot_dir=snap,
                                           worker_id=0, timeout=3.0)
    rejoined = None
    try:
        mirror = rng.randn(40, 4).astype(np.float32).copy()
        tbl = embedding.ShardedEmbedding(
            fleet, "ck", (40, 4), cache_rows=8,
            recover=lambda ids: mirror[np.asarray(ids, dtype=np.int64)])
        tbl.init(mirror)
        fleet.set_optimizer(opt.create("sgd", learning_rate=0.1))

        def step():
            ids = rng.randint(0, 40, size=16).astype(np.int64)
            rows = tbl.pull(ids)
            tbl.push(ids, np.asarray(rows) * 0.01)
            # keep the worker-side mirror current (the trainer's dense
            # buffer plays this role on the gluon path)
            got = np.asarray(tbl.pull(ids)).reshape(-1, 4)
            mirror[np.unique(ids)] = np.asarray(
                tbl.pull(np.unique(ids))).reshape(-1, 4)
            return got

        for _ in range(3):
            step()
        fleet.snapshot()  # both shards persist
        handles[1].kill()  # SIGKILL-shaped: socket gone, beats stop
        for _ in range(3):  # remap to survivor + re-seed, no hang
            step()
        assert fleet.live_servers() == [0]
        # rejoin: new server process (new port), same id + snapshot dir
        rejoined = embedding.start_local_server(
            1, coordinator=fleet.coordinator, snapshot_dir=snap)
        assert rejoined.store.rows_resident() > 0  # shard restored
        fleet.refresh()
        assert fleet.live_servers() == [0, 1]
        for _ in range(3):  # rows flow through the rejoined server
            step()
        full = np.asarray(tbl.pull(np.arange(40))).reshape(40, 4)
        assert np.isfinite(full).all()
        assert np.allclose(full, mirror, atol=1e-5)
    finally:
        fleet.close()
        # rejoined first: its graceful deregister needs the coordinator
        # (server 0) alive
        if rejoined is not None:
            rejoined.close()
        for h in handles[:1]:
            h.close()


# --------------------------------------------------------------------------
# the Embedding op's weight gradient (ops/embedding_grad.py): the grouped
# product over the table's tiles in interpret mode on the CPU, against a
# float32 reference and against XLA's scatter-add; the rule; the counter
# --------------------------------------------------------------------------
import hashlib  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.ops import embedding_grad as EG  # noqa: E402
from mxnet_tpu.ops.indexing import embedding as embedding_op  # noqa: E402

# (vocab, width, tokens, draw) by what the walk over the tiles has to get right
GRAD_CASES = {
    "uniform": (1000, 256, 512, "uniform"),
    "zipf": (1000, 256, 512, "zipf"),
    "every_id_equal": (1000, 256, 512, "equal"),
    "out_of_range": (1000, 256, 512, "out_of_range"),
    "vocab_37984_not_whole_tiles": (37984, 128, 512, "uniform"),
    "vocab_under_a_tile": (50, 128, 256, "uniform"),
    "tied": (300, 128, 256, "uniform"),
}


def _ids(draw, vocab, tokens, seed=0):
    rs = np.random.RandomState(seed)
    if draw == "zipf":
        return np.minimum(rs.zipf(1.1, tokens) - 1, vocab - 1)
    if draw == "equal":
        return np.full(tokens, vocab // 3)
    if draw == "out_of_range":  # clipped to [0, vocab) as the forward clips
        return rs.randint(-vocab, 2 * vocab, tokens)
    return rs.randint(0, vocab, tokens)


def _engage(monkeypatch, grouped_matmul_kernels):
    """From here on the op dispatches as on a TPU, the kernel interpreted, a
    table of any size taken (the CPU holds none over the rule's 128 MiB)."""
    grouped_matmul_kernels()
    monkeypatch.setattr(EG, "on_tpu", lambda: True)
    monkeypatch.setattr(EG, "TABLE_BYTES", 0)


def _far(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(GRAD_CASES))
def test_embedding_grad_by_the_kernel_against_float32_and_xlas_scatter(
        case, dtype, monkeypatch, grouped_matmul_kernels):
    """``jax.grad`` through the ``Embedding`` op where the rule engages: each
    row the float32 sum of its tokens' cotangents rounded once (ids out of
    range clipped as the forward clips them, a last tile of 96 rows, a table
    under one tile), no further from the float32 reference than XLA's
    scatter-add, which rounds at every repeat; a tied table (the head's
    product beside the look-up) gets the sum of both cotangents. In float32
    the op stays XLA's (on the chip the product would round the cotangent), so
    there the walk is held through ``table_grad`` itself, to float32's digits."""
    vocab, width, tokens, draw = GRAD_CASES[case]
    dt, f32 = jnp.dtype(dtype), jnp.float32
    ids = jnp.asarray(_ids(draw, vocab, tokens).reshape(2, tokens // 2), f32)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    w = jax.random.normal(ks[0], (vocab, width), f32).astype(dt)
    g = jax.random.normal(ks[1], (2, tokens // 2, width), f32).astype(dt)
    x = jax.random.normal(ks[2], (64, width), f32).astype(dt)
    tied = case == "tied"

    def loss(w, g=g, x=x):
        out = jnp.sum(embedding_op(ids, w, vocab, width) * g)
        return out + jnp.sum(jnp.dot(x, w.T)) if tied else out

    xla = jax.grad(loss)(w)  # here: a CPU, so jnp.take's own transpose
    want = np.asarray(jax.grad(lambda w: loss(w, g.astype(f32), x.astype(f32)))(w.astype(f32)))
    clipped = np.clip(np.asarray(ids, np.int64).reshape(-1), 0, vocab - 1)
    rows = np.zeros((vocab, width), np.float64)
    np.add.at(rows, clipped, np.asarray(g, np.float64).reshape(tokens, width))
    if not tied:
        assert _far(want, rows) < 1e-5  # the reference is the sum it says

    _engage(monkeypatch, grouped_matmul_kernels)
    before = telemetry.embedding_grad_branches()
    if dt == f32:
        assert "scatter-add" in str(jax.make_jaxpr(jax.grad(loss))(w))  # the op: XLA's
        assert telemetry.embedding_grad_branches()["xla"] == before.get("xla", 0) + 1
        got = EG.table_grad(jnp.asarray(clipped, jnp.int32), g.reshape(tokens, width), vocab,
                            interpret=True)
        assert got.dtype == dt and _far(got, rows) < 1e-5
        return
    text = str(jax.make_jaxpr(jax.grad(loss))(w))
    assert "grouped_matmul_dw" in text and "scatter" not in text
    assert "layout_constraint" in text  # the cotangent held as rows, as the scatter held it
    got = jax.grad(loss)(w)
    assert telemetry.embedding_grad_branches()["kernel"] == before.get("kernel", 0) + 2
    assert got.dtype == dt and got.shape == (vocab, width)
    assert _far(got, want) <= 2.0 ** -8 * (2 if tied else 1)  # one rounding (the head's too)
    assert _far(got, want) <= _far(xla, want) + 1e-6
    assert np.array_equal(np.asarray(embedding_op(ids, w)), np.asarray(jnp.take(
        w, jnp.asarray(clipped.reshape(2, -1)), axis=0)))  # the forward is jnp.take


# what each ineligible call traced to before the op had a backward of its own
_TAKE_DIGESTS = {"cpu": "09bbe45db5da7826", "width_100": "c162dc0d44153666",
                 "float32": "1d0a1a359290ca81", "tokens_100": "1d8fb51b8ceaac0e",
                 "sparse_grad": "09bbe45db5da7826", "table_under_128_mib": "09bbe45db5da7826"}
_TAKE_CALLS = {"cpu": (512, 256, 512, False, "bfloat16"),
               "width_100": (512, 100, 512, False, "bfloat16"),
               "tokens_100": (512, 256, 100, False, "bfloat16"),
               "float32": (512, 256, 512, False, "float32"),
               "sparse_grad": (512, 256, 512, True, "bfloat16"),
               "table_under_128_mib": (512, 256, 512, False, "bfloat16")}


@pytest.mark.parametrize("case", sorted(_TAKE_CALLS))
def test_an_ineligible_embedding_traces_to_jnp_take_and_counts_xla(case, monkeypatch):
    """The CPU, a width of 100, 100 tokens, float32, ``sparse_grad=True``, a
    table XLA's form is cheap at: the call and
    its gradient trace to the jaxpr they traced to before (``jnp.take`` and
    JAX's transpose of it, letter for letter), and count ``xla``."""
    vocab, width, tokens, sparse, dtype = _TAKE_CALLS[case]
    if case != "cpu":
        monkeypatch.setattr(EG, "on_tpu", lambda: True)
    if case != "table_under_128_mib":
        monkeypatch.setattr(EG, "TABLE_BYTES", 0)
    w = jnp.zeros((vocab, width), dtype)
    ids = jnp.zeros((2, tokens // 2), jnp.float32)
    g = jnp.zeros(ids.shape + (width,), dtype)
    before = telemetry.embedding_grad_branches()
    jaxpr = jax.make_jaxpr(jax.grad(lambda w: jnp.sum(
        embedding_op(ids, w, sparse_grad=sparse).astype(jnp.float32)
        * g.astype(jnp.float32))))(w)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    assert "custom_vjp" not in text and "pallas_call" not in text and "scatter-add" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _TAKE_DIGESTS[case]
    after = telemetry.embedding_grad_branches()
    assert after["xla"] == before.get("xla", 0) + 1
    assert after.get("kernel", 0) == before.get("kernel", 0)
    assert 'mxt_embedding_grad_total{branch="xla"}' in telemetry.render_prometheus()


_RULE = {
    "smallthinker_cell": ((37984, 2560, 8192, "bfloat16"), True),
    "published_vocabulary": ((151936, 2560, 8192, "bfloat16"), True),
    "lfm2_published": ((65536, 2048, 8192, "bfloat16"), True),
    "granite_cell_under_128_mib": ((25088, 2048, 8192, "bfloat16"), False),
    "bert_word_table_under_128_mib": ((30522, 768, 16384, "bfloat16"), False),
    "bert_token_types": ((2, 768, 16384, "bfloat16"), False),
    "float32": ((30522, 768, 16384, "float32"), False),
    "odd_width": ((37984, 100, 8192, "bfloat16"), False),
    "few_tokens": ((37984, 2560, 100, "bfloat16"), False),
    "no_tokens": ((37984, 2560, 0, "bfloat16"), False),
    "at_128_mib": ((32768, 2048, 8192, "bfloat16"), False),
    "float16": ((37984, 2560, 8192, "float16"), False),
    "blocks_past_vmem": ((37984, 131072, 8192, "bfloat16"), False),
}


@pytest.mark.parametrize("case", sorted(_RULE))
def test_the_embedding_rule_reads_the_shapes_and_the_device(case, monkeypatch):
    shape, want = _RULE[case]
    assert not EG.kernel_takes(*shape)  # here: a CPU
    monkeypatch.setattr(EG, "on_tpu", lambda: True)
    assert EG.kernel_takes(*shape) == want
