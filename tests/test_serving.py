"""Inference serving stack (mxnet_tpu/serving/ + the ragged paged
attention kernel in ops/attention.py).

Covers the PR-7 acceptance surface on CPU: paged-attention parity
against the ragged dense reference (interpret mode, odd/mixed lengths
incl. 1 and 257 and the {1, 17, 257, 512} mixed batch), KV-page
alloc/free/reuse/defrag invariants, continuous-batching scheduler
join/retire/deadline-eviction, the zero-host-sync decode loop, AOT-warm
decode (zero cache-miss compiles in a warmed replica), and token-exact
end-to-end parity with the cache-free dense decode oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import engine as eng_mod
from mxnet_tpu import nd, profiler, serving, tuning
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import attention as A
from mxnet_tpu.serving import (ContinuousBatcher, DecodeEngine,
                               PagedKVCache, Request, StaticBatcher,
                               TinyDecoder)


@pytest.fixture(autouse=True)
def _fresh_table(monkeypatch, tmp_path):
    """Every test gets its own on-disk tune table (and a clean
    in-memory instance — table() swaps on path change)."""
    monkeypatch.setenv("MXT_TUNE_TABLE", str(tmp_path / "tune.json"))
    tuning.reset()
    yield
    tuning.reset()


def _pack_pages(k, v, page_size, rng, extra_pages=4):
    """Dense (B, H, T, D) K/V -> shuffled page pools + page table."""
    B, H, T, D = k.shape
    assert T % page_size == 0
    max_pages = T // page_size
    P = B * max_pages + extra_pages
    perm = rng.permutation(P)
    pt = perm[:B * max_pages].reshape(B, max_pages).astype(np.int32)
    k_pages = rng.normal(size=(P, page_size, H, D)).astype("f4")
    v_pages = rng.normal(size=(P, page_size, H, D)).astype("f4")
    for b in range(B):
        kt = k[b].transpose(1, 0, 2)  # (T, H, D)
        vt = v[b].transpose(1, 0, 2)
        for j in range(max_pages):
            k_pages[pt[b, j]] = kt[j * page_size:(j + 1) * page_size]
            v_pages[pt[b, j]] = vt[j * page_size:(j + 1) * page_size]
    return k_pages, v_pages, pt


# ---------------------------------------------------------------------------
# kernel parity: ragged paged attention vs the ragged dense reference
# ---------------------------------------------------------------------------
def test_ragged_reference_matches_manual_softmax():
    """The oracle itself, pinned against per-sequence numpy softmax."""
    rng = np.random.RandomState(0)
    lengths = [1, 5, 12]
    B, H, T, D = len(lengths), 2, 16, 8
    q = rng.normal(size=(B, H, D)).astype("f4")
    k = rng.normal(size=(B, H, T, D)).astype("f4")
    v = rng.normal(size=(B, H, T, D)).astype("f4")
    out = np.array(A.ragged_attention_reference(
        jnp.array(q), jnp.array(k), jnp.array(v),
        jnp.array(lengths, dtype=jnp.int32)))
    scale = 1.0 / np.sqrt(D)
    for b, L in enumerate(lengths):
        for h in range(H):
            s = (q[b, h] @ k[b, h, :L].T) * scale
            p = np.exp(s - s.max())
            p /= p.sum()
            want = p @ v[b, h, :L]
            np.testing.assert_allclose(out[b, h], want, atol=1e-5)


@pytest.mark.parametrize("page_size,lengths,blocks", [
    (16, (1, 17, 257, 512), (4,)),   # the acceptance mixed batch
    (8, (1, 7, 63, 64), (1, 2)),     # odd lengths on a small page
])
def test_paged_attention_parity_interpret(page_size, lengths, blocks):
    """Pallas kernel (interpret) and XLA gather path vs the ragged
    dense reference, <= 1e-5, ragged batch, shuffled page table."""
    rng = np.random.RandomState(1)
    B, H, D = len(lengths), 4, 32
    T = -(-max(lengths) // page_size) * page_size
    q = rng.normal(size=(B, H, D)).astype("f4")
    k = rng.normal(size=(B, H, T, D)).astype("f4")
    v = rng.normal(size=(B, H, T, D)).astype("f4")
    k_pages, v_pages, pt = _pack_pages(k, v, page_size, rng)
    cl = jnp.array(lengths, dtype=jnp.int32)
    ref = np.array(A.ragged_attention_reference(
        jnp.array(q), jnp.array(k), jnp.array(v), cl))

    got_xla = np.array(A._paged_gather_reference(
        jnp.array(q), jnp.array(k_pages), jnp.array(v_pages),
        jnp.array(pt), cl, 1.0 / np.sqrt(D)))
    np.testing.assert_allclose(got_xla, ref, atol=1e-5)

    for block_h in blocks:
        got = np.array(A._paged_decode_pallas(
            jnp.array(q), jnp.array(k_pages), jnp.array(v_pages),
            jnp.array(pt), cl, 1.0 / np.sqrt(D), block_h,
            interpret=True))
        np.testing.assert_allclose(got, ref, atol=1e-5,
                                   err_msg="block_h=%d" % block_h)


def test_paged_op_routes_and_records():
    """The public op: CPU routes to the gather reference, interpret=True
    forces the kernel, a signature lands for warmup replay, and the
    tuning table holds a decode-bucket entry."""
    rng = np.random.RandomState(2)
    B, H, D, S = 2, 2, 16, 8
    q = jnp.array(rng.normal(size=(B, H, D)).astype("f4"))
    kp = jnp.array(rng.normal(size=(10, S, H, D)).astype("f4"))
    vp = jnp.array(rng.normal(size=(10, S, H, D)).astype("f4"))
    pt = jnp.array([[0, 1, 2], [3, 4, 5]], dtype=jnp.int32)
    cl = jnp.array([5, 23], dtype=jnp.int32)
    out = nd.ragged_paged_attention(q, kp, vp, pt, cl)
    got_i = A.ragged_paged_attention(q, kp, vp, pt, cl, interpret=True)
    np.testing.assert_allclose(np.array(out.data), np.array(got_i),
                               atol=1e-5)
    sigs = tuning.signatures("paged_attention")
    assert any(s["q_shape"] == [B, H, D] for s in sigs)
    keys = [k for k in tuning.table().entries() if k.startswith("paged|")]
    assert keys, "resolve_paged recorded no decode-bucket entry"
    summary = tuning.warmup(include_live=False)
    assert "paged_attention" in summary["entries"]
    assert not summary["errors"]


def test_paged_candidates_and_bucketing():
    cands = tuning.paged_candidates(8, 64, 16, jnp.float32)
    assert cands and all(8 % bh == 0 or bh <= 8 for bh in cands)
    for bh in cands:
        assert 8 % bh == 0 and bh >= 1
    ent = tuning.heuristic_paged((4, 8, 64), 16, 32, "float32")
    assert ent["backend"] in ("pallas", "xla")
    assert ent["block_h"] in cands
    # page-table growth inside one pow2 bucket must not churn new keys
    k1 = tuning.paged_key((4, 8, 64), 16, 17, "float32")
    k2 = tuning.paged_key((4, 8, 64), 16, 31, "float32")
    assert k1 == k2


# ---------------------------------------------------------------------------
# paged KV cache invariants
# ---------------------------------------------------------------------------
def test_kv_cache_alloc_free_reuse():
    cache = PagedKVCache(1, 2, 8, num_pages=8, page_size=16)
    assert cache.available() == 8
    assert cache.reserve("a", 40)          # 3 pages promised
    assert cache.available() == 5
    assert cache.pages_of("a") == []
    p0 = cache.alloc_page("a")
    assert cache.pages_in_use() == 1 and cache.available() == 5
    cache.alloc_for("a", 40)
    assert len(cache.pages_of("a")) == 3
    with pytest.raises(MXNetError):
        cache.alloc_page("a")              # quota exhausted
    with pytest.raises(MXNetError):
        cache.reserve("a", 16)             # double reservation
    with pytest.raises(MXNetError):
        cache.alloc_page("ghost")          # no reservation
    assert not cache.reserve("b", 16 * 6)  # 6 > 5 available
    assert cache.reserve("b", 16 * 5)
    assert cache.available() == 0
    freed = cache.free("a")
    assert freed == 3 and cache.available() == 3
    # freed pages recycle (p0 comes back before untouched high ids)
    cache.reserve("c", 16)
    assert cache.alloc_page("c") == p0
    with pytest.raises(MXNetError):
        cache.reserve("huge", 16 * 9)      # can never fit: typed error


def test_kv_cache_defrag_preserves_content_and_compacts():
    cache = PagedKVCache(2, 2, 4, num_pages=8, page_size=8)
    rng = np.random.RandomState(3)
    for seq, ntok in (("a", 16), ("b", 24), ("c", 8)):
        cache.reserve(seq, ntok)
        cache.alloc_for(seq, ntok)
    # fill every allocated page with distinct values
    fill = {}
    for seq in ("a", "b", "c"):
        for p in cache.pages_of(seq):
            val = rng.normal(size=(2, 8, 2, 4)).astype("f4")
            fill[(seq, cache.pages_of(seq).index(p))] = val
            cache.k_pages = cache.k_pages.at[:, p].set(jnp.array(val))
    before = {seq: [np.array(cache.k_pages[:, p])
                    for p in cache.pages_of(seq)]
              for seq in ("a", "b", "c")}
    cache.free("b")                        # pages 2,3,4 fragment the pool
    moved = cache.defrag()
    assert moved > 0
    used = sorted(p for s in ("a", "c") for p in cache.pages_of(s))
    assert used == list(range(len(used))), "pool not compacted"
    for seq in ("a", "c"):
        for old, p in zip(before[seq], cache.pages_of(seq)):
            np.testing.assert_array_equal(old, np.array(
                cache.k_pages[:, p]))
    assert cache.defrag() == 0             # idempotent when compact


# ---------------------------------------------------------------------------
# end-to-end: engine + scheduler vs the dense cache-free oracle
# ---------------------------------------------------------------------------
_ENGINES = {}  # config -> (model, params, engine): reused when drained


def _tiny_engine(layers=2, heads=2, hdim=8, slots=4, pages=64,
                 page_size=8, max_context=128, seed=3, buckets=(16,),
                 fresh=False):
    """Build (or reuse) a tiny serving engine. Tests run serially and
    always drain their traffic, so an engine whose cache is empty and
    whose slots are all free is safe to hand to the next test — reuse
    skips re-tracing the decode/prefill programs (suite time matters:
    the tier-1 gate is dot count under a timeout)."""
    key = (layers, heads, hdim, slots, pages, page_size, max_context,
           seed, buckets)
    if not fresh and key in _ENGINES:
        model, params, eng = _ENGINES[key]
        if eng.cache.pages_in_use() == 0 and not eng._seq_of_slot:
            return model, params, eng
    model = TinyDecoder(vocab=64, num_layers=layers, num_heads=heads,
                        head_dim=hdim, max_len=256)
    params = model.init_params(seed)
    eng = DecodeEngine(
        model, params=params, slots=slots,
        cache=PagedKVCache(layers, heads, hdim, num_pages=pages,
                           page_size=page_size),
        prefill_buckets=buckets, max_context=max_context)
    if not fresh:
        _ENGINES[key] = (model, params, eng)
    return model, params, eng


def test_continuous_batching_matches_dense_oracle():
    """Join/retire through slot churn: 6 mixed-length requests through
    4 slots, every output token-for-token equal to the quadratic
    cache-free dense reference decode."""
    model, params, eng = _tiny_engine()
    sched = ContinuousBatcher(eng)
    rng = np.random.RandomState(0)
    reqs = []
    for plen, mnew in [(3, 6), (9, 4), (1, 8), (14, 3), (5, 5), (2, 7)]:
        r = Request(rng.randint(1, 64, plen).tolist(),
                    max_new_tokens=mnew)
        reqs.append(r)
        sched.submit(r)
    done = sched.run()
    assert len(done) == 6 and sched.steps < 50
    for r in reqs:
        assert r.state == "completed"
        ref = model.reference_decode(params, r.prompt, r.max_new_tokens)
        assert r.output_tokens == ref, r.id
        assert r.t_finish is not None and r.t_first is not None


def test_eos_stops_early():
    model, params, eng = _tiny_engine(layers=1)
    prompt = [5, 9, 2]
    ref = model.reference_decode(params, prompt, 10)
    eos = ref[2]  # an EOS the greedy stream will certainly emit
    stop = ref.index(eos) + 1  # ...at its FIRST occurrence
    sched = ContinuousBatcher(eng)
    r = sched.submit(Request(prompt, max_new_tokens=10, eos_id=eos))
    sched.run()
    assert r.state == "completed"
    assert r.output_tokens == ref[:stop]
    assert r.output_tokens[-1] == eos


def test_deadline_eviction_running_and_queued():
    clock = [0.0]
    model, params, eng = _tiny_engine(layers=1, slots=1)
    sched = ContinuousBatcher(eng, now_fn=lambda: clock[0])
    slow = sched.submit(Request([3, 4], max_new_tokens=50, deadline=5.0))
    queued = sched.submit(Request([7], max_new_tokens=4, deadline=1.0))
    ok = sched.submit(Request([9], max_new_tokens=2))
    sched.step()                     # admits `slow` into the only slot
    assert slow.state == "running"
    clock[0] = 2.0
    sched.step()                     # queued's 1s deadline blown
    assert queued.state == "evicted"
    clock[0] = 6.0
    sched.step()                     # slow's 5s deadline blown mid-decode
    assert slow.state == "evicted"
    assert eng.cache.pages_in_use() == 0 or ok.state == "running"
    sched.run()
    assert ok.state == "completed"
    assert ok.output_tokens == model.reference_decode(params, [9], 2)
    states = {r.state for r in (slow, queued)}
    assert states == {"evicted"}


def test_static_batcher_waits_for_batch():
    """Static admission only opens at batch boundaries — with 2 slots
    and 3 requests the third starts strictly after the first batch's
    longest member, and total steps exceed the continuous schedule."""
    model, params, e1 = _tiny_engine(layers=1, slots=2)
    reqs = [([3, 4], 8), ([5], 2), ([6, 1], 3)]

    def run(cls, eng):
        s = cls(eng)
        rs = [s.submit(Request(p, max_new_tokens=m)) for p, m in reqs]
        s.run()
        return rs, s.steps

    rs_s, steps_static = run(StaticBatcher, e1)
    _, _, e2 = _tiny_engine(layers=1, slots=2)
    rs_c, steps_cont = run(ContinuousBatcher, e2)
    for a, b in zip(rs_s, rs_c):
        assert a.state == b.state == "completed"
        assert a.output_tokens == b.output_tokens
    assert steps_static > steps_cont


def test_rejects_impossible_requests():
    model, params, eng = _tiny_engine(pages=4, page_size=8,
                                      max_context=32)
    sched = ContinuousBatcher(eng)
    r1 = sched.submit(Request([1] * 30, max_new_tokens=10))  # > context
    r2 = sched.submit(Request([1] * 20, max_new_tokens=20))  # > pool
    assert r1.state == "rejected" and r2.state == "rejected"
    assert not sched._queue


# ---------------------------------------------------------------------------
# the async contract: zero per-step host syncs, deferred token delivery
# ---------------------------------------------------------------------------
def test_zero_host_sync_decode_loop():
    """The acceptance bound: <= 1 host sync per K decode steps once the
    loop is steady (the window's stacked deferred read is the only
    device->host transfer)."""
    model, params, eng = _tiny_engine(layers=1, slots=2)
    sched = ContinuousBatcher(eng)
    sched.submit(Request([5, 9, 2], max_new_tokens=40))
    for _ in range(4):                    # admit + absorb prefill read
        sched.step()
    with eng_mod.bulk(4):
        h0 = profiler.host_sync_count()
        for _ in range(12):
            sched.step()
        syncs = profiler.host_sync_count() - h0
    assert syncs <= 12 // 4 + 1, \
        "decode loop performed %d host syncs over 12 steps at K=4" % syncs
    sched.run()


def test_window_values_protocol():
    got = []
    w = eng_mod.InflightWindow(
        name="vals", on_values=lambda n, row: got.append((n, int(row[0]))))
    with eng_mod.bulk(3):
        for i in range(7):
            t = jnp.array([i], jnp.int32)
            w.push(t, value=t)
        assert w.pending > 0
        w.flush()
    assert got == [(i + 1, i) for i in range(7)]
    assert w.pending == 0
    with pytest.raises(MXNetError):
        w.push(jnp.zeros((1,), jnp.uint32),
               flags=jnp.zeros((), jnp.uint32),
               value=jnp.zeros((1,), jnp.int32))


def test_waitall_drains_serving_window():
    model, params, eng = _tiny_engine(layers=1, slots=1)
    sched = ContinuousBatcher(eng)
    r = sched.submit(Request([5], max_new_tokens=6))
    with eng_mod.bulk(8):
        for _ in range(7):
            sched.step()
        nd.waitall()                      # the global barrier drains it
        assert eng.window.pending == 0
    sched.run()
    assert r.state == "completed"


def test_serving_modules_lint_enforced():
    """The decode hot path stays on the static host-sync scan list."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "check_host_syncs", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "check_host_syncs.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    for rel in ("mxnet_tpu/serving/engine.py",
                "mxnet_tpu/serving/scheduler.py",
                "mxnet_tpu/serving/kv_cache.py",
                "mxnet_tpu/serving/model.py"):
        assert rel in m.SCAN


# ---------------------------------------------------------------------------
# AOT warm decode: a warmed replica pays zero request-path JIT
# ---------------------------------------------------------------------------
def test_aot_warm_decode_zero_cache_misses(tmp_path, monkeypatch):
    """Replica A (cold) warms + serves, seeding the persistent compile
    cache; replica B (same shapes, fresh in-memory caches) warms and
    serves the same traffic with ZERO cache-miss compiles — every
    request-path program replays from disk."""
    from jax._src import compilation_cache as _cc

    # the JAX variable outranks ours (tuning.setup_compile_cache): clear
    # it so the test owns its cache directory under either environment
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXT_COMPILE_CACHE_DIR", str(tmp_path / "xla"))

    def traffic(eng):
        sched = ContinuousBatcher(eng)
        rng = np.random.RandomState(0)
        for plen, mnew in [(3, 3), (9, 2), (1, 4)]:
            sched.submit(Request(rng.randint(1, 64, plen).tolist(),
                                 max_new_tokens=mnew))
        return sched.run()

    _cc.reset_cache()
    _, _, cold = _tiny_engine(layers=1, slots=2, fresh=True)
    # decode step + one fused admission program per prefill bucket
    assert cold.aot_warmup() >= 2
    traffic(cold)

    _cc.reset_cache()               # in-process stand-in for process B
    _, _, warm = _tiny_engine(layers=1, slots=2, fresh=True)
    warm.aot_warmup()
    c0 = tuning.compile_stats()
    out = traffic(warm)
    c1 = tuning.compile_stats()
    assert len(out) == 3
    assert c1["cache_misses"] - c0["cache_misses"] == 0, \
        "warm replica compiled on the request path"
    assert c1["cache_hits"] >= c0["cache_hits"]


def test_engine_defrag_keeps_serving():
    """Defrag mid-traffic: pages move, tables re-emit, decode output
    stays oracle-exact."""
    model, params, eng = _tiny_engine(layers=1, slots=2, pages=32)
    sched = ContinuousBatcher(eng)
    a = sched.submit(Request([3, 1, 4, 1, 5], max_new_tokens=8))
    b = sched.submit(Request([9, 2], max_new_tokens=8))
    for _ in range(3):
        sched.step()
    eng.flush()          # settle in-flight steps before moving pages
    eng.defrag()
    sched.run()
    for r in (a, b):
        assert r.state == "completed"
        assert r.output_tokens == model.reference_decode(
            params, r.prompt, r.max_new_tokens)
