"""Kernel tuning (cost model + table) + persistent compile cache (mxnet_tpu/tuning/).

Covers the PR-6 acceptance surface on CPU: shape-aware tiling-legal
configs for arbitrary (odd) shapes with interpret-mode parity against
the XLA reference, tune-table persistence (round-trip, corrupted/stale
fallback), warmup compile-counter behavior, and the zero-JIT-resume
two-process A/B over a shared persistent compilation cache.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, nd, tuning
from mxnet_tpu.ops import attention as A
from mxnet_tpu.test_utils import with_seed


@pytest.fixture(autouse=True)
def _fresh_table(monkeypatch, tmp_path):
    """Every test gets its own on-disk tune table (and therefore a
    clean in-memory instance — table() swaps on path change)."""
    monkeypatch.setenv("MXT_TUNE_TABLE", str(tmp_path / "tune.json"))
    tuning.reset()
    yield
    tuning.reset()


# ---------------------------------------------------------------------------
# shape-aware configs: legality + odd-shape parity (partial-block regression)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tq,tk,d", [
    (257, 257, 32),   # the classic non-multiple sequence
    (100, 100, 64),
    (257, 129, 32),   # rectangular (cross-attention shaped)
    (7, 7, 16),       # smaller than one sublane tile
    (1024, 1024, 64),
])
def test_attention_candidates_tiling_legal(tq, tk, d):
    cands = tuning.attention_candidates(tq, tk, d, jnp.float32)
    assert cands, "no candidates for (%d, %d, %d)" % (tq, tk, d)
    for bq, bk in cands:
        assert bq % 8 == 0 and bq >= 8, (bq, bk)
        assert bk % 8 == 0 and bk >= 8, (bq, bk)
    ent = tuning.heuristic_attention((2, 2, tq, d), tk, "float32", False)
    assert (ent["block_q"], ent["block_k"]) in cands
    assert ent["backend"] in ("pallas", "xla")


@with_seed()
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk", [(257, 257), (100, 100), (129, 257)])
def test_flash_odd_shapes_match_reference(causal, tq, tk):
    """The shape-aware config path must make the Pallas kernel (run in
    interpret mode on CPU) agree with the XLA reference at non-multiple
    shapes — the partial-block error class an early chip run hit."""
    rng = np.random.RandomState(0)
    B, H, D = 1, 2, 32
    q = jnp.asarray(rng.normal(size=(B, H, tq, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, tk, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, tk, D)).astype("f4"))
    cfg = tuning.resolve_attention(q.shape, tk, "float32", causal)
    assert cfg["block_q"] % 8 == 0 and cfg["block_k"] % 8 == 0
    ref = A._attention_reference(q, k, v, None, causal, 0.125)
    out, _ = A._flash_forward_pallas(
        q, k, v, None, causal, 0.125, cfg["block_q"], cfg["block_k"],
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# MXT_TUNE_MODE: two values, one meaning each, in every resolver alike
# ---------------------------------------------------------------------------
_RESOLVERS = {
    "flash": (lambda: tuning.resolve_attention((1, 2, 192, 32), 192, "float32", True),
              lambda: tuning.heuristic_attention((1, 2, 192, 32), 192, "float32", True)),
    "paged": (lambda: tuning.resolve_paged((8, 12, 64), 16, 64, "bfloat16"),
              lambda: tuning.heuristic_paged((8, 12, 64), 16, 64, "bfloat16")),
    "quant": (lambda: tuning.resolve_quant("decode_matmul", 768, 3072, "float32"),
              lambda: tuning.heuristic_quant("decode_matmul", 768, 3072, "float32")),
}


@pytest.mark.parametrize("family", sorted(_RESOLVERS))
def test_tune_mode_off_is_the_cost_model_and_leaves_no_table_entry(family, monkeypatch):
    resolve, cost_model = _RESOLVERS[family]
    monkeypatch.setenv("MXT_TUNE_MODE", "off")
    assert resolve() == cost_model()
    assert tuning.table().entries() == {}
    # an entry written by hand is what 'heuristic' serves and 'off' does not read
    monkeypatch.setenv("MXT_TUNE_MODE", "heuristic")
    resolve()
    (key,) = tuning.table().entries()
    tuning.table().record(key, dict(cost_model(), source="measured"))
    assert resolve()["source"] == "measured"
    monkeypatch.setenv("MXT_TUNE_MODE", "off")
    assert resolve() == cost_model()


def test_tune_mode_refuses_a_value_it_does_not_know(monkeypatch):
    from mxnet_tpu.base import MXNetError

    config.set_default("MXT_TUNE_MODE", "heuristic")  # what both harnesses set
    for gone in ("auto", "measure", "pinned"):
        monkeypatch.setenv("MXT_TUNE_MODE", gone)
        for resolve, _ in _RESOLVERS.values():
            with pytest.raises(MXNetError, match="heuristic, off"):
                resolve()
    assert tuning.table().entries() == {}


# ---------------------------------------------------------------------------
# the kernels' rules: platform, type and shape, and ONE statement of the chip
# ---------------------------------------------------------------------------
def _rules():
    from mxnet_tpu.ops import causal_conv_pallas as CC
    from mxnet_tpu.ops import embedding_grad as EG
    from mxnet_tpu.ops import grouped_matmul as GM
    from mxnet_tpu.ops import row_gather as RG

    # module, the rule at a cell's shape, the bytes that call holds in VMEM
    return {
        "grouped_matmul": (  # LFM2's gate / up product, 16384 rows laid out
            GM, lambda: GM._kernel_takes("fwd", 16384, 2048, 1536, "bfloat16"),
            lambda: GM._gmm_vmem(GM.ROW_TILE, 2048, 1536, 2)),
        "row_gather": (  # SmallThinker's sum of 24576 rows back to 8192 x 6
            RG, lambda: RG.kernel_takes(24576, 8192, 6, 2560, "bfloat16"),
            lambda: RG._vmem(6, 2560, 2)),
        "causal_conv": (  # Granite's filter: 4352 columns after the gate's 4096
            CC, lambda: CC.kernel_takes((1, 8192, 4352), 4, "bfloat16", begin=4096),
            lambda: CC._vmem(*CC._tiles(8192, 4352, 4096)[0], 4, 2)),
        "embedding_grad": (  # SmallThinker's 37984-row table, 8192 tokens
            EG, lambda: EG.kernel_takes(37984, 2560, 8192, "bfloat16"),
            lambda: GM._tgmm_vmem(GM._DW_ROWS, EG.TILE, 2560, 2)),
    }


@pytest.mark.parametrize("family", ["causal_conv", "embedding_grad", "grouped_matmul",
                                    "row_gather"])
def test_a_kernels_rule_reads_the_chip_from_one_place(family, monkeypatch):
    """Each ``kernel_takes`` admits its cell's shape where it can see a TPU
    and refuses it on a chip whose ceiling is one byte under what the call
    holds: ``ops/chip.py`` is the one statement every rule reads."""
    from mxnet_tpu.ops import chip

    module, takes, held = _rules()[family]
    assert not takes()  # the CPU
    monkeypatch.setattr(module, "on_tpu", lambda: True)
    assert takes()
    assert held() <= chip.VMEM_CEILING
    monkeypatch.setattr(chip, "VMEM_CEILING", held())
    assert takes()
    monkeypatch.setattr(chip, "VMEM_CEILING", held() - 1)
    assert not takes()


# ---------------------------------------------------------------------------
# tune table: round-trip, corruption, staleness, measured precedence
# ---------------------------------------------------------------------------
def test_tune_table_roundtrip(tmp_path):
    path = str(tmp_path / "t.json")
    t = tuning.TuneTable(path)
    key = tuning.attn_key((2, 4, 257, 64), 257, "float32", True)
    ent = {"backend": "pallas", "block_q": 64, "block_k": 128,
           "source": "measured", "score": 1.25}
    t.record(key, ent)
    t.record_signature("flash_attention", {"q_shape": [2, 4, 257, 64]})
    assert t.save() == path

    t2 = tuning.TuneTable(path)  # fresh registry, same file
    assert t2.load_error is None
    got = t2.lookup(key)
    assert got == ent
    assert t2.signatures("flash_attention") == [{"q_shape": [2, 4, 257, 64]}]
    # same decisions through the resolve path: the stored entry wins
    # (no re-measure, no heuristic overwrite)
    assert t2.peek(key)["block_q"] == 64


def test_tune_table_corrupted_falls_back(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write("{not json at all")
    t = tuning.TuneTable(path)
    assert t.load_error is not None
    assert t.entries() == {}
    # resolution still works — heuristic path answers
    ent = tuning.heuristic_attention((1, 1, 64, 32), 64, "float32", False)
    assert ent["source"] == "heuristic"


def test_tune_table_stale_version_falls_back(tmp_path):
    path = str(tmp_path / "stale.json")
    with open(path, "w") as f:
        json.dump({"version": tuning.TABLE_VERSION + 1,
                   "entries": {"k": {"backend": "pallas"}},
                   "signatures": {}}, f)
    t = tuning.TuneTable(path)
    assert t.load_error is not None and "version" in t.load_error
    assert t.entries() == {}
    # and the save path writes the CURRENT version back out
    t.record("k2", {"backend": "xla", "source": "heuristic"})
    t.save()
    with open(path) as f:
        assert json.load(f)["version"] == tuning.TABLE_VERSION


def test_measured_entry_not_downgraded():
    t = tuning.TuneTable()
    t.record("k", {"backend": "pallas", "block_q": 32, "block_k": 32,
                   "source": "measured"})
    out = t.record("k", {"backend": "xla", "block_q": 8, "block_k": 8,
                         "source": "heuristic"})
    assert out["source"] == "measured" and out["block_q"] == 32
    assert t.peek("k")["source"] == "measured"


def test_resolve_records_and_hits_counters():
    from mxnet_tpu import telemetry

    def counts():
        reg = telemetry.registry()
        h = reg.get("mxt_tune_cache_hits_total")
        m = reg.get("mxt_tune_cache_misses_total")
        return (int(h.value) if h else 0, int(m.value) if m else 0)

    h0, m0 = counts()
    shape = (1, 2, 192, 32)
    ent1 = tuning.resolve_attention(shape, 192, "float32", False)
    h1, m1 = counts()
    assert m1 == m0 + 1  # first sight of the bucket: miss
    ent2 = tuning.resolve_attention(shape, 192, "float32", False)
    h2, m2 = counts()
    assert h2 == h1 + 1 and m2 == m1  # second: table hit
    assert ent1 == ent2  # same decision both times


# ---------------------------------------------------------------------------
# signatures + warmup (compile-counter asserts, CPU-runnable)
# ---------------------------------------------------------------------------
@with_seed()
def test_flash_dispatch_records_signature():
    q = nd.array(np.random.RandomState(0).normal(
        size=(1, 2, 24, 8)).astype("f4"))
    nd.flash_attention(q, q, q)
    sigs = tuning.signatures("flash_attention")
    assert any(s["q_shape"] == [1, 2, 24, 8] for s in sigs)


@with_seed()
def test_warmup_compiles_recorded_signatures():
    """tuning.warmup() AOT-compiles every recorded kernel signature —
    the compile counter must move, and the summary must say what was
    warmed."""
    q = nd.array(np.random.RandomState(0).normal(
        size=(1, 1, 16, 8)).astype("f4"))
    nd.flash_attention(q, q, q)  # records the signature
    before = tuning.compile_stats()
    summary = tuning.warmup(include_live=False)
    after = tuning.compile_stats()
    assert "flash_attention" in summary["entries"]
    assert not summary["errors"], summary["errors"]
    assert summary["compiles"] >= 2  # fwd + grad programs at least
    assert after["compiles"] - before["compiles"] == summary["compiles"]


@with_seed()
def test_step_aot_warmup_compiles_and_steps(tmp_path, monkeypatch):
    """CachedTrainStep.aot_warmup compiles the fused program without
    touching weights; the subsequent real steps run fused and match a
    twin that never warmed up."""
    from mxnet_tpu.gluon import Trainer, nn as gnn

    def build(prefix):
        mx.random.seed(7)
        net = gnn.Sequential(prefix=prefix)
        with net.name_scope():
            # explicit in_units: no deferred init, so the pre-warmup
            # weight snapshot below can read the arrays directly
            net.add(gnn.Dense(16, activation="relu", in_units=6),
                    gnn.Dense(4, in_units=16))
        net.initialize()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9})
        step = tr.fuse_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
        return net, step

    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (8, 6)).astype("f4"))
    y = nd.array(rng.randint(0, 4, (8,)).astype("f4"))

    net_a, step_a = build("warm_")
    w_before = {n: p.data().asnumpy()
                for n, p in net_a.collect_params().items()}
    c0 = tuning.compile_stats()
    assert step_a.aot_warmup(x, y) == 1
    c1 = tuning.compile_stats()
    assert c1["compiles"] > c0["compiles"]
    for n, p in net_a.collect_params().items():  # weights untouched
        np.testing.assert_array_equal(w_before[n], p.data().asnumpy())

    net_b, step_b = build("warm_")  # same seed + prefix = same init
    la = [float(step_a(x, y).mean().asnumpy()) for _ in range(3)]
    lb = [float(step_b(x, y).mean().asnumpy()) for _ in range(3)]
    assert step_a.fused and step_b.fused
    np.testing.assert_allclose(la, lb, rtol=0, atol=0)


def test_fused_update_aot_warmup():
    """The Trainer's _FusedUpdate AOT-compiles from live param shapes."""
    from mxnet_tpu.gluon import Parameter, Trainer

    from mxnet_tpu.gluon.trainer import _FusedUpdate

    p = Parameter("w", shape=(4, 3))
    p.initialize()
    tr = Trainer([p], "adam", {"learning_rate": 1e-3}, kvstore=None)
    tr._init_kvstore()
    assert _FusedUpdate.eligible(tr)
    fused = _FusedUpdate(tr)  # what trainer.step builds on first call
    c0 = tuning.compile_stats()
    assert fused.aot_warmup() >= 1
    assert tuning.compile_stats()["compiles"] > c0["compiles"]


@with_seed()
def test_warmup_second_pass_hits_persistent_cache(tmp_path, monkeypatch):
    """With MXT_COMPILE_CACHE_DIR set, re-warming the same signatures
    serves the compiles from the persistent cache (hits, not misses)."""
    from jax._src import compilation_cache as _cc

    # the JAX variable outranks ours (tuning.setup_compile_cache): clear
    # it so the test owns its cache directory under either environment
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXT_COMPILE_CACHE_DIR", str(tmp_path / "xla"))
    # unique shape for this test: other tests may have compiled the
    # common ones already, and JAX's in-memory cache layer would then
    # swallow the hit/miss events this test observes
    q = nd.array(np.random.RandomState(0).normal(
        size=(1, 3, 40, 8)).astype("f4"))
    nd.flash_attention(q, q, q)
    _cc.reset_cache()  # route compiles through the (fresh) disk cache
    s1 = tuning.warmup(include_live=False)
    assert s1["cache_misses"] >= 2  # cold: fwd + grad really compiled
    # drop the in-memory layer again so the second pass must go to
    # disk — the in-process stand-in for a fresh replica
    _cc.reset_cache()
    s2 = tuning.warmup(include_live=False)
    assert s2["cache_hits"] >= 2  # fwd + grad replayed from disk
    assert s2["cache_misses"] == 0


# ---------------------------------------------------------------------------
# the acceptance A/B: zero hot-path JIT in a warm-started second process
# ---------------------------------------------------------------------------
_CW_SCRIPT = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, tuning
from mxnet_tpu.gluon import Trainer, nn

mx.random.seed(0)
net = nn.Sequential(prefix="zj_")
with net.name_scope():
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
net.initialize()
tr = Trainer(net.collect_params(), "sgd",
             {"learning_rate": 0.1, "momentum": 0.9})
step = tr.fuse_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
rng = np.random.RandomState(0)
x = nd.array(rng.uniform(-1, 1, (8, 6)).astype(np.float32))
y = nd.array(rng.randint(0, 4, (8,)).astype(np.float32))
step.aot_warmup(x, y)
pre = tuning.compile_stats()
losses = []
for _ in range(3):
    losses.append(float(step(x, y).mean().asnumpy()))
nd.waitall()
post = tuning.compile_stats()
print("ROW " + json.dumps({
    "losses": losses, "fused": step.fused,
    "hot_cache_misses": post["cache_misses"] - pre["cache_misses"],
    "hot_compile_s": post["compile_seconds"] - pre["compile_seconds"],
    "total_misses": post["cache_misses"]}))
"""


def test_zero_jit_resume_second_process(tmp_path):
    """PR acceptance: with a warm persistent cache + tune table, a
    second process running the canonical fused-step loop performs zero
    hot-path JIT compiles (every backend compile in its hot loop is a
    persistent-cache hit), with identical numerics."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "MXT_COMPILE_CACHE_DIR": str(tmp_path / "xla"),
                "MXT_TUNE_TABLE": str(tmp_path / "tune.json")})
    env.pop("XLA_FLAGS", None)  # no 8-device CPU mesh in the children
    # the JAX variable outranks ours (tuning.setup_compile_cache)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run():
        r = subprocess.run(
            [sys.executable, "-c", _CW_SCRIPT],
            env=env, capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for line in r.stdout.splitlines():
            if line.startswith("ROW "):
                return json.loads(line[4:])
        raise AssertionError("no ROW in output: %s"
                             % (r.stderr or r.stdout)[-800:])

    cold = run()
    warm = run()
    assert cold["fused"] and warm["fused"]
    # the acceptance bit: ZERO real JIT compiles on the warm hot path
    assert warm["hot_cache_misses"] == 0, warm
    # and the warm process's tune table came from disk: same numerics
    np.testing.assert_allclose(cold["losses"], warm["losses"],
                               rtol=0, atol=0)
    # the cold process really did pay compiles (sanity of the A/B)
    assert cold["total_misses"] > 0


def test_jax_cache_dir_variable_outranks_everything(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache lives there: setup()
    sets no directory in code, whatever it is passed, and cache_dir()
    reports the environment's."""
    import jax

    from mxnet_tpu.tuning import compile_cache

    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setenv("MXT_COMPILE_CACHE_DIR", str(tmp_path / "ours"))
    monkeypatch.setattr(compile_cache, "_setup_dir", None)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup(str(tmp_path / "argument")) == env_dir
    assert compile_cache.cache_dir() == env_dir
    assert jax.config.jax_compilation_cache_dir == before


def test_attention_key_names_kv_heads_and_a_mask_and_leaves_dense_keys_alone():
    """An entry tuned for a dense call is never taken for a grouped or a
    masked one: both get fields of their own, and a dense call's key is what
    it always was."""
    shape = (1, 32, 8192, 128)
    dense = tuning.attn_key(shape, 8192, "bfloat16", True, kind="k")
    assert dense == "flash|bh32|q8192|k8192|d128|bfloat16|c1|k"
    assert tuning.attn_key(shape, 8192, "bfloat16", True, kind="k", kv_heads=32) == dense
    grouped = tuning.attn_key(shape, 8192, "bfloat16", True, kind="k", kv_heads=4)
    masked = tuning.attn_key(shape, 8192, "bfloat16", True, kind="k", kv_heads=4,
                             masked=True)
    assert grouped == "flash|bh32|q8192|k8192|d128|bfloat16|c1|g8|k"
    assert masked == "flash|bh32|q8192|k8192|d128|bfloat16|c1|g8|m1|k"
    assert len({dense, grouped, masked}) == 3


def test_flash_signature_and_warmup_carry_kv_heads_and_the_mask(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A
    import importlib

    W = importlib.import_module("mxnet_tpu.tuning.warmup")

    seen = []
    monkeypatch.setattr(tuning, "record_signature", lambda kind, spec: seen.append((kind, spec)))
    q = jnp.zeros((1, 8, 32, 16), jnp.float32)
    kv = jnp.zeros((1, 2, 32, 16), jnp.float32)
    mask = jnp.ones((1, 32, 32), jnp.int8)
    A.flash_attention(q, kv, kv, None, mask, causal=True)
    kind, spec = seen[-1]
    assert kind == "flash_attention" and spec["k_shape"] == [1, 2, 32, 16]
    assert spec["mask_dtype"] == "int8" and spec["bias_shape"] is None
    A.flash_attention(q, q, q)
    assert seen[-1][1]["mask_dtype"] is None
    # the replay compiles forward and gradient of what was recorded
    calls = []
    core = A._flash_core
    monkeypatch.setattr(A, "_flash_core", lambda *a: calls.append(a) or core(*a))
    assert W._warm_flash(spec) == "flash_attention"
    q_, k_, v_, b_, m_ = calls[0][:5]
    assert k_.shape == (1, 2, 32, 16) and b_ is None
    assert m_.shape == (1, 32, 32) and str(m_.dtype) == "int8"
