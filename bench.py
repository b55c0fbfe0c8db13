"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline config: ResNet-50 training throughput (images/sec/chip), the
SURVEY §6 headline. A second config (BERT-base MLM, tokens/sec/chip,
BASELINE config 3) is also measured; all configs append JSONL rows to
bench_results.jsonl with the BASELINE.md-required fields plus MFU
(model flops / chip peak, looked up by device_kind in _PEAK_BF16_FLOPS).

The platform is whatever backend JAX installed. No accelerator is an
error — unless ``BENCH_PLATFORM=cpu`` asks for the CPU smoke, whose toy
shapes say what the program counts and nothing about its speed, and
whose rows carry platform="cpu". A configuration that fails fails the
run (exit 1). A chip belongs to one process, so the configurations that
start children of their own are reported as not run, in words, when the
parent holds a TPU. This file is due to be replaced by a cell list
(ROADMAP S1/D3); chip_smoke.py is the quick proof that the program
starts on the chip.

vs_baseline: BASELINE.json's published table is empty (mount was empty at
survey time), so the ratio is computed against the public MXNet-era
V100 fp32 figure (~390 img/s, docs/faq/perf.md) as the stand-in
denominator; see BASELINE.md.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_IMG_S = 390.0  # MXNet ResNet-50 V100 fp32 (unverified, BASELINE.md)
# bf16 peak FLOP/s of one chip by jax device_kind (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s). A kind that is not here is an
# error, not a default.
_PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
_HERE = os.path.dirname(os.path.abspath(__file__))
JSONL_PATH = os.path.join(_HERE, "bench_results.jsonl")
# the persistent compile cache when JAX_COMPILATION_CACHE_DIR names none:
# one fixed, git-ignored path shared with chip_smoke.py (the path is part
# of the cache key, so a directory that moves never hits)
_CACHE_DIR = os.path.join(_HERE, ".jax_cache")

# Wall-clock budget: the chip tool gives a call a time limit, and a kill
# loses every row. Every config is cost-gated against a global deadline
# and the bench exits with whatever rows completed, naming the rest.
_T0 = time.monotonic()
_BUDGET = float(os.environ.get("BENCH_BUDGET", "1500"))

# conservative per-config wall-clock estimates (compile + warmup +
# window); to be re-measured on the attached chip
_CONFIG_COST = {"resnet50": 420, "bert": 300, "lstm_ptb": 200,
                "wide_deep": 200, "lenet": 150, "pipeline": 150,
                "async_ab": 90, "telemetry_ab": 60, "diag_ab": 60,
                "cold_warm": 120, "serving": 150, "zero_stage": 90,
                "embedding_ab": 90, "serving_fleet": 120,
                "speculative": 120, "kv_quant": 90, "fleet_obs": 90,
                "streaming_input": 90, "prefix_reuse": 120,
                "autoscale": 150, "parallel_4d": 90,
                "training_health": 60}

class _NotRun(Exception):
    """A configuration that cannot run here says why, in words; main
    reports it under "not_run" and it does not fail the run."""


def _child_needs_no_chip(platform):
    """Called where a configuration is about to start child processes: a
    chip belongs to one process, so under a parent that holds a TPU the
    children cannot have it (and a CPU child's row has no place under a
    TPU headline)."""
    if platform != "cpu":
        raise _NotRun("starts child processes, and the chip belongs to "
                      "the parent")


def _remaining():
    return _BUDGET - (time.monotonic() - _T0)


def _init_backend():
    """The platform JAX installed. ``BENCH_PLATFORM`` forces one (the CPU
    smoke); finding no accelerator without it is an error, never a
    fallback."""
    import jax

    forced = os.environ.get("BENCH_PLATFORM")
    if forced:
        jax.config.update("jax_platforms", forced)
    platform = jax.default_backend()
    if platform == "cpu" and forced != "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator (default_backend() is 'cpu'); "
            "a device metric is never taken on the CPU — set "
            "BENCH_PLATFORM=cpu for the toy-shape CPU smoke")
    return platform


def _emit_jsonl(row):
    with open(JSONL_PATH, "a") as f:
        f.write(json.dumps(row) + "\n")


def _timed_steps(step, x, y, iters, warmup):
    # Warmup syncs every step (surfaces compile/runtime errors eagerly and
    # never leaves a deep queue if we die). The timed window dispatches
    # steps back-to-back and syncs once per SYNC_EVERY: a per-step
    # wait_to_read measures host round-trips, not device throughput.
    # Real training is pipelined the same way — the reference's async
    # engine never syncs per step either (SURVEY §3.1); the queue stays
    # bounded by iters, which is <= 50 everywhere.
    # Returns (wall seconds, framework launch dispatches, host syncs) for
    # the timed window — the launch count (profiler.launch_count) makes
    # fusion health visible per row (a fused step is exactly 1/step), and
    # the host-sync count makes ASYNC health visible: a K-deep engine
    # window shows <= 1/K framework reads per step.
    from mxnet_tpu import profiler, tuning

    sync_every = int(os.environ.get("BENCH_SYNC_EVERY", "0"))  # 0 = window end
    if not sync_every and iters > 50:
        sync_every = 50  # bound the un-synced queue
    # compile + tune-cache accounting spans warmup AND the timed window:
    # the warmup steps are where a cold config pays its JIT, and the row
    # must expose that cost (cold-vs-warm is invisible in step_time_ms —
    # by the timed window everything is compiled either way)
    c0 = tuning.compile_stats()
    tc0 = _tune_cache_counts()
    loss = None
    for _ in range(warmup):
        loss = step(x, y)
        loss.wait_to_read()
    t0 = time.perf_counter()
    l0 = profiler.launch_count()
    h0 = profiler.host_sync_count()
    for i in range(iters):
        loss = step(x, y)
        if sync_every and (i + 1) % sync_every == 0:
            loss.wait_to_read()
    loss.wait_to_read()
    c1 = tuning.compile_stats()
    tc1 = _tune_cache_counts()
    extras = {
        "compile_time_ms": round(
            (c1["compile_seconds"] - c0["compile_seconds"]) * 1e3, 1),
        "compiles": c1["compiles"] - c0["compiles"],
        "tune_cache": {"hits": tc1[0] - tc0[0],
                       "misses": tc1[1] - tc0[1]},
    }
    return (time.perf_counter() - t0, profiler.launch_count() - l0,
            profiler.host_sync_count() - h0, extras)


def _tune_cache_counts():
    """(hits, misses) of the tuning-table lookup counters."""
    from mxnet_tpu import telemetry

    reg = telemetry.registry()
    out = []
    for name in ("mxt_tune_cache_hits_total", "mxt_tune_cache_misses_total"):
        fam = reg.get(name)
        out.append(int(fam.value) if fam is not None else 0)
    return tuple(out)


def _step_stats(dt, launches, syncs, iters, extras=None):
    """The per-row fusion-health fields every _timed_steps config emits."""
    row = {
        "step_time_ms": round(dt / iters * 1e3, 3),
        "launches_per_step": round(launches / iters, 2),
        "host_syncs_per_step": round(syncs / iters, 3),
    }
    if extras:
        row.update(extras)
    return row


def _mfu(samples_per_sec, flops_per_sample, platform):
    if not flops_per_sample or platform == "cpu":
        return None
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_BF16_FLOPS:
        raise RuntimeError("no published peak for device kind %r: add it "
                           "to _PEAK_BF16_FLOPS with its source" % (kind,))
    return round(samples_per_sec * flops_per_sample
                 / _PEAK_BF16_FLOPS[kind], 4)


def bench_resnet50(platform, dtype, batch=None, remat="env"):
    """remat: "env" reads BENCH_REMAT; "none" forces no remat (the
    variant sweep needs to express 'explicitly off' even when the stage
    env sets BENCH_REMAT); any other value is a remat policy name."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import model_zoo
    from mxnet_tpu import parallel

    small = platform == "cpu"
    if batch is None:
        batch = int(os.environ.get("BENCH_BATCH", "8" if small else "64"))
    if remat == "env":
        remat = os.environ.get("BENCH_REMAT") or None
    elif remat == "none":
        remat = None
    iters = int(os.environ.get("BENCH_ITERS", "3" if small else "20"))
    warmup = int(os.environ.get("BENCH_WARMUP", "1" if small else "3"))
    # channels-last is the MXU-native layout (gluon/nn/layout.py); NCHW
    # stays selectable for A/B runs
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")

    mx.random.seed(0)
    from mxnet_tpu.gluon import nn as _nn
    with _nn.layout_scope(layout):
        net = model_zoo.get_model("resnet50_v1", classes=1000)
    net.initialize()
    if dtype == "bfloat16":
        net.cast("bfloat16")  # MXU-native; BN stats stay f32 inside the op

    in_shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    x0 = nd.zeros(in_shape, dtype=dtype)
    net(x0)  # resolve deferred shapes eagerly

    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        remat=remat)

    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, in_shape).astype(np.float32))
    x = x.astype(dtype)
    y = nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32))

    dt, launches, syncs, extras = _timed_steps(step, x, y, iters, warmup)
    img_s = batch * iters / dt

    dump = os.environ.get("BENCH_DUMP_HLO")
    # post-run: one AOT compile, shared with the MFU accounting — but a
    # compile can take minutes, so only start it with real headroom
    if dump and _remaining() > 300:
        try:
            step.dump_hlo(x, y, dump)
        except Exception as e:  # noqa: BLE001 — diagnostics only
            print("bench: HLO dump failed: %r" % (e,), file=sys.stderr)
    elif dump:
        print("bench: skipping HLO dump — %.0fs budget left" % _remaining(),
              file=sys.stderr)

    flops_per_img = step.flops_per_step(x, y)
    if flops_per_img:
        flops_per_img /= batch
    else:
        flops_per_img = 3 * 8.2e9  # fwd ~4.1 GMACs @224; train ≈ 3x fwd

    row = {
        "config": "resnet50_v1_train", "chips": 1, "batch_size": batch,
        "dtype": dtype, "layout": layout,
        "remat": remat,
        "images_or_tokens_per_sec_per_chip": round(img_s, 2),
        "mfu": _mfu(img_s, flops_per_img, platform), "platform": platform,
        "flops_per_sample": flops_per_img,
        **_step_stats(dt, launches, syncs, iters, extras),
    }
    _emit_jsonl(row)
    return img_s, row


def bench_bert_mlm(platform, dtype):
    """BERT-base MLM pretraining step throughput (BASELINE config 3)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Block, model_zoo
    from mxnet_tpu import parallel

    small = platform == "cpu"
    seq_len = int(os.environ.get("BENCH_BERT_SEQLEN", "32" if small
                                 else "128"))
    batch = int(os.environ.get("BENCH_BERT_BATCH", "4" if small else "32"))
    iters = int(os.environ.get("BENCH_BERT_ITERS", "2" if small else "10"))
    warmup = int(os.environ.get("BENCH_BERT_WARMUP", "1" if small else "2"))

    mx.random.seed(0)
    if small:
        bert = model_zoo.bert.bert_3_64_2(use_classifier=False, dropout=0.0)
        vocab = 1000
    else:
        bert = model_zoo.bert.bert_12_768_12(use_classifier=False,
                                             dropout=0.0,
                                             max_length=seq_len)
        vocab = 30522

    class _MLMNet(Block):
        """Single-input wrapper so ShardedTrainStep can drive BERT:
        token ids in, vocabulary scores out (all positions)."""

        def __init__(self, bert_model):
            super().__init__(prefix="bench_mlm_")
            with self.name_scope():
                self.bert = bert_model

        def forward(self, x):
            from mxnet_tpu import nd as F

            seq, _ = self.bert(x, F.zeros_like(x))
            return self.bert.decode_mlm(seq)

    net = _MLMNet(bert)
    net.initialize()
    if dtype == "bfloat16":
        net.cast("bfloat16")

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, vocab, (batch, seq_len)).astype(np.float32))
    y = nd.array(rng.randint(0, vocab, (batch, seq_len)).astype(np.float32))
    net(x)  # resolve deferred shapes

    # BENCH_BERT_PATH selects what a user script gets (SURVEY §3.1):
    #   trainer    — the CANONICAL Gluon loop (hybridize + record/backward
    #                + fused donated Trainer.step): forward launch +
    #                per-node backward walk + 1 optimizer launch
    #   fused_step — the same canonical API through Trainer.fuse_step
    #                (gluon.CachedTrainStep): the WHOLE step is one
    #                donated launch, like ShardedTrainStep but without
    #                leaving the Gluon surface
    #   sharded    — ShardedTrainStep (default; the headline config)
    # A sharded step provides the flop accounting for ALL paths (same
    # model/loss/optimizer); on the trainer/fused_step paths it is built
    # only AFTER the timed window so its Adam state doesn't inflate HBM
    # use during the measurement.
    path = os.environ.get("BENCH_BERT_PATH", "sharded")

    def make_sharded():
        return parallel.ShardedTrainStep(
            net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-4})

    if path == "trainer":
        from mxnet_tpu import autograd as ag

        bert.hybridize()  # _MLMNet is a plain Block; the BERT core jits
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
        trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                                   {"learning_rate": 1e-4})

        def step(xb, yb):
            with ag.record():
                loss = loss_fn(net(xb), yb).mean()
            loss.backward()
            trainer.step(1)
            return loss
        sharded = None
    elif path == "fused_step":
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
        trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                                   {"learning_rate": 1e-4})
        step = trainer.fuse_step(net, loss_fn)
        sharded = None
    else:
        sharded = step = make_sharded()

    dt, launches, syncs, extras = _timed_steps(step, x, y, iters, warmup)
    tok_s = batch * seq_len * iters / dt

    flops_per_tok = (sharded or make_sharded()).flops_per_step(x, y)
    if flops_per_tok:
        flops_per_tok /= batch * seq_len

    config_name = {"trainer": "bert_base_mlm_train_gluon",
                   "fused_step": "bert_base_mlm_train_fused_step"}.get(
                       path, "bert_base_mlm_train")
    row = {
        "config": config_name, "chips": 1,
        "batch_size": batch,
        "seq_len": seq_len, "dtype": dtype,
        "images_or_tokens_per_sec_per_chip": round(tok_s, 2),
        "mfu": _mfu(tok_s, flops_per_tok, platform), "platform": platform,
        "flops_per_sample": flops_per_tok,
        **_step_stats(dt, launches, syncs, iters, extras),
    }
    _emit_jsonl(row)
    return tok_s, row


def bench_lenet_mnist(platform, dtype):
    """LeNet-5 on MNIST-shaped data via Gluon (BASELINE config 1)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu import parallel

    small = platform == "cpu"
    batch = int(os.environ.get("BENCH_LENET_BATCH", "32" if small
                               else "256"))
    iters = int(os.environ.get("BENCH_LENET_ITERS", "3" if small else "20"))
    warmup = int(os.environ.get("BENCH_LENET_WARMUP", "1" if small
                                else "3"))

    mx.random.seed(0)
    net = nn.HybridSequential(prefix="lenet_")
    with net.name_scope():
        net.add(nn.Conv2D(20, kernel_size=5, activation="tanh"),
                nn.MaxPool2D(pool_size=2, strides=2),
                nn.Conv2D(50, kernel_size=5, activation="tanh"),
                nn.MaxPool2D(pool_size=2, strides=2),
                nn.Flatten(),
                nn.Dense(500, activation="tanh"),
                nn.Dense(10))
    net.initialize()
    if dtype == "bfloat16":
        net.cast("bfloat16")

    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(0, 1, (batch, 1, 28, 28)).astype(np.float32))
    x = x.astype(dtype)
    y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
    net(x)

    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9})

    dt, launches, syncs, extras = _timed_steps(step, x, y, iters, warmup)
    img_s = batch * iters / dt
    flops = step.flops_per_step(x, y)
    if flops:
        flops /= batch

    row = {
        "config": "lenet_mnist_train", "chips": 1, "batch_size": batch,
        "dtype": dtype,
        "images_or_tokens_per_sec_per_chip": round(img_s, 2),
        "mfu": _mfu(img_s, flops, platform), "platform": platform,
        "flops_per_sample": flops,
        **_step_stats(dt, launches, syncs, iters, extras),
    }
    _emit_jsonl(row)
    return img_s, row


def bench_lstm_ptb(platform, dtype):
    """LSTM language model, PTB 'medium' shape (BASELINE config 4;
    fused lax.scan RNN, ref: src/operator/rnn.cc cuDNN fused RNN)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Block, nn, rnn
    from mxnet_tpu import parallel

    small = platform == "cpu"
    seq_len = int(os.environ.get("BENCH_LSTM_SEQLEN", "8" if small
                                 else "35"))
    batch = int(os.environ.get("BENCH_LSTM_BATCH", "4" if small else "32"))
    iters = int(os.environ.get("BENCH_LSTM_ITERS", "2" if small else "10"))
    warmup = int(os.environ.get("BENCH_LSTM_WARMUP", "1" if small else "2"))
    hidden = 64 if small else 650
    layers = 1 if small else 2
    vocab = 1000 if small else 10000

    mx.random.seed(0)

    class _LM(Block):
        def __init__(self):
            super().__init__(prefix="ptb_")
            with self.name_scope():
                self.embed = nn.Embedding(vocab, hidden)
                self.lstm = rnn.LSTM(hidden_size=hidden, num_layers=layers,
                                     layout="NTC")
                self.decoder = nn.Dense(vocab, flatten=False)

        def forward(self, x):
            return self.decoder(self.lstm(self.embed(x)))

    net = _LM()
    net.initialize()
    if dtype == "bfloat16":
        net.cast("bfloat16")

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, vocab, (batch, seq_len)).astype(np.float32))
    y = nd.array(rng.randint(0, vocab, (batch, seq_len)).astype(np.float32))
    net(x)

    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 1.0})

    dt, launches, syncs, extras = _timed_steps(step, x, y, iters, warmup)
    tok_s = batch * seq_len * iters / dt
    flops_per_tok = step.flops_per_step(x, y)
    if flops_per_tok:
        flops_per_tok /= batch * seq_len

    row = {
        "config": "lstm_ptb_train", "chips": 1, "batch_size": batch,
        "seq_len": seq_len, "dtype": dtype,
        "wavefront": bool(__import__("mxnet_tpu").config.get(
            "MXT_RNN_WAVEFRONT")),
        "images_or_tokens_per_sec_per_chip": round(tok_s, 2),
        "mfu": _mfu(tok_s, flops_per_tok, platform), "platform": platform,
        "flops_per_sample": flops_per_tok,
        **_step_stats(dt, launches, syncs, iters, extras),
    }
    _emit_jsonl(row)
    return tok_s, row


def bench_wide_deep(platform, dtype):
    """Wide&Deep CTR throughput (BASELINE config 5; ref:
    example/sparse/wide_deep). The jitted step keeps embeddings dense
    (XLA scatter-add); the framework-level sparse path is covered by
    tests/test_sparse.py."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Block, model_zoo
    from mxnet_tpu import parallel

    small = platform == "cpu"
    batch = int(os.environ.get("BENCH_WD_BATCH", "16" if small else "2048"))
    iters = int(os.environ.get("BENCH_WD_ITERS", "2" if small else "20"))
    warmup = int(os.environ.get("BENCH_WD_WARMUP", "1" if small else "3"))
    n_wide, n_deep = 8, 4
    wide_vocab = 1000 if small else 100000
    deep_vocab = 500 if small else 10000

    mx.random.seed(0)
    wd = model_zoo.wide_deep(
        wide_vocab=wide_vocab, deep_vocab=deep_vocab,
        embed_dim=16, hidden=(64, 32), classes=2, sparse_grad=False)

    class _Packed(Block):
        """Single-input wrapper: columns [0:n_wide) are wide ids, the
        rest deep ids — lets ShardedTrainStep drive the two towers."""

        def __init__(self):
            super().__init__(prefix="wd_pack_")
            with self.name_scope():
                self.wd = wd

        def forward(self, x):
            return self.wd(x[:, :n_wide], x[:, n_wide:])

    net = _Packed()
    net.initialize()
    if dtype == "bfloat16":
        net.cast("bfloat16")

    rng = np.random.RandomState(0)
    xw = rng.randint(0, wide_vocab, (batch, n_wide))
    xd = rng.randint(0, deep_vocab, (batch, n_deep))
    x = nd.array(np.concatenate([xw, xd], axis=1).astype(np.float32))
    y = nd.array(rng.randint(0, 2, (batch,)).astype(np.float32))
    net(x)

    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3})

    dt, launches, syncs, extras = _timed_steps(step, x, y, iters, warmup)
    samp_s = batch * iters / dt
    flops = step.flops_per_step(x, y)
    if flops:
        flops /= batch

    # MFU is near-meaningless for this config (tiny gemms, lookup-bound);
    # the device-side metric that matters is embedding traffic: per
    # sample, each id costs a gather (fwd) + scatter-add (bwd) row of
    # embed_dim (deep) / 1 (wide logistic weights), at the table dtype
    # (bf16 after net.cast, else f32).
    esize = 2 if dtype == "bfloat16" else 4  # net.cast covers the tables
    emb_bytes_per_sample = 2 * esize * (n_wide * 1 + n_deep * 16)
    row = {
        "config": "wide_deep_train", "chips": 1, "batch_size": batch,
        "dtype": dtype,
        "images_or_tokens_per_sec_per_chip": round(samp_s, 2),
        "mfu": _mfu(samp_s, flops, platform), "platform": platform,
        "flops_per_sample": flops,
        "embedding_bytes_per_sec": round(samp_s * emb_bytes_per_sample),
        **_step_stats(dt, launches, syncs, iters, extras),
    }
    _emit_jsonl(row)
    return samp_s, row


def bench_input_pipeline(platform, dtype):
    """Host-feed ceiling (SURVEY hard part #4): JPEG
    decode + augment + batch through ImageRecordIter on ImageNet-shaped
    records, NO model — measures whether the host can out-feed the
    chip's train rate (target ≥2× config-2's img/s). Pure host work;
    the `platform` tag records the host context it ran under."""
    import shutil
    import tempfile

    import numpy as np

    from mxnet_tpu import recordio
    from mxnet_tpu.io import ImageRecordIter

    del dtype
    n_img = int(os.environ.get("BENCH_PIPE_IMAGES", "192"))
    batch = int(os.environ.get("BENCH_PIPE_BATCH", "64"))
    threads = int(os.environ.get("BENCH_PIPE_THREADS",
                                 str(max(1, (os.cpu_count() or 1)))))
    epochs = int(os.environ.get("BENCH_PIPE_EPOCHS", "3"))

    tmp = tempfile.mkdtemp(prefix="mxt_pipe_bench_")
    try:
        frec, fidx = os.path.join(tmp, "i.rec"), os.path.join(tmp, "i.idx")
        w = recordio.MXIndexedRecordIO(fidx, frec, "w")
        rng = np.random.RandomState(0)
        # piecewise-smooth synthetic photos: JPEG entropy (and therefore
        # decode cost) in the ballpark of natural images, unlike pure
        # noise which decodes slow and unlike flat color which is free
        for i in range(n_img):
            base = rng.randint(0, 255, (8, 8, 3))
            img = np.kron(base, np.ones((32, 32, 1)))
            img = np.clip(img + rng.randint(0, 12, img.shape),
                          0, 255).astype(np.uint8)  # no uint8 wraparound
            w.write_idx(i, recordio.pack_img(
                recordio.IRHeader(0, float(i % 1000), i, 0), img,
                img_fmt=".jpg", quality=90))
        w.close()

        it = ImageRecordIter(
            path_imgrec=frec, path_imgidx=fidx,
            data_shape=(3, 224, 224), batch_size=batch, shuffle=True,
            rand_crop=True, rand_mirror=True,
            preprocess_threads=threads)
        # warm epoch (thread spin-up, page cache), then timed epochs
        for b in it:
            pass
        it.reset()
        seen = 0
        t0 = time.perf_counter()
        for _ in range(epochs):
            for b in it:
                seen += b.data[0].shape[0]
            it.reset()
        dt = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    img_s = seen / dt
    row = {
        "config": "input_pipeline_only", "chips": 0, "batch_size": batch,
        "dtype": "uint8->float32", "preprocess_threads": threads,
        "host_cores": os.cpu_count(),
        "images_or_tokens_per_sec_per_chip": round(img_s, 2),
        "mfu": None, "platform": platform,
        "flops_per_sample": None,
        "note": "host-only: decode(224x224 jpeg)+augment+batch, no model",
    }
    _emit_jsonl(row)
    return img_s, row


def bench_streaming_input(platform, dtype):
    """Streaming data plane A/B (mxnet_tpu/data_plane/): the SAME
    synthetic recordio shards consumed by (a) the per-process gluon
    DataLoader (locked shared reader + per-sample decode in
    ``__getitem__`` — the pattern the data plane replaces) and (b) the
    chunk-leased decode-worker fleet as TWO in-process hosts sharing one
    lease ledger. Both legs run the full feed path (decode + augment +
    batchify + NDArray device wrap) and report consumer-observed
    ``data_wait`` per step; the plane leg also reports the ledger's
    steal count. The plane's per-core edge is algorithmic, not just
    parallel: chunk-sequential reads, decode straight into preallocated
    batch slots (no per-sample Python/np.stack pass), and JPEG
    draft-mode DCT downscaling when a resize target is set. Legs are
    shape-warm: each runs one discarded warm epoch first (the PR 12
    bench gotcha)."""
    import shutil
    import tempfile
    import threading

    import numpy as np

    from mxnet_tpu import data_plane, recordio
    from mxnet_tpu.gluon.data import DataLoader, Dataset
    from mxnet_tpu.io.io import _crop, _resize_short
    from mxnet_tpu.recordio import unpack_img

    del dtype  # host decode A/B: uint8 jpeg -> float32 both ways
    n_img = int(os.environ.get("BENCH_SIAB_IMAGES", "192"))
    hw = int(os.environ.get("BENCH_SIAB_HW", "192"))
    resize = int(os.environ.get("BENCH_SIAB_RESIZE", "96"))
    crop = int(os.environ.get("BENCH_SIAB_CROP", "64"))
    batch = int(os.environ.get("BENCH_SIAB_BATCH", "32"))
    epochs = int(os.environ.get("BENCH_SIAB_EPOCHS", "2"))
    workers = int(os.environ.get("BENCH_SIAB_WORKERS", "2"))
    chunk = int(os.environ.get("BENCH_SIAB_CHUNK", "32"))

    tmp = tempfile.mkdtemp(prefix="mxt_siab_bench_")
    try:
        rng = np.random.RandomState(0)
        shards = []
        gid = 0
        for s in range(2):
            frec = os.path.join(tmp, "part-%d.rec" % s)
            fidx = os.path.join(tmp, "part-%d.idx" % s)
            w = recordio.MXIndexedRecordIO(fidx, frec, "w")
            for _ in range(n_img // 2):
                base = rng.randint(0, 255, (8, 8, 3))
                img = np.kron(base, np.ones((hw // 8, hw // 8, 1)))
                img = np.clip(img + rng.randint(0, 12, img.shape),
                              0, 255).astype(np.uint8)
                w.write_idx(gid, recordio.pack_img(
                    recordio.IRHeader(0, float(gid % 10), gid, 0), img,
                    img_fmt=".jpg", quality=90))
                gid += 1
            w.close()
            shards.append(frec)

        class _RecDataset(Dataset):
            """The per-process pattern: one shared (locked) reader,
            per-sample decode in __getitem__."""

            def __init__(self, recs):
                self._readers = []
                self._index = []
                self._lock = threading.Lock()
                for si, r in enumerate(recs):
                    rd = recordio.MXIndexedRecordIO(
                        os.path.splitext(r)[0] + ".idx", r, "r")
                    self._readers.append(rd)
                    self._index.extend((si, k) for k in rd.keys)
                self._rng = np.random.RandomState(0)

            def __len__(self):
                return len(self._index)

            def __getitem__(self, i):
                si, k = self._index[i]
                with self._lock:
                    raw = self._readers[si].read_idx(k)
                header, img = unpack_img(raw)
                img = _resize_short(img, resize)
                img = _crop(img, crop, crop, rand=True, rng=self._rng)
                return img.astype(np.float32), np.float32(header.label)

        def leg_loader():
            ds = _RecDataset(shards)
            n_batches = [0]

            def one_epoch():
                dl = DataLoader(ds, batch_size=batch, shuffle=True,
                                num_workers=workers, thread_pool=True,
                                last_batch="keep")
                seen = 0
                for b in dl:
                    seen += b[0].shape[0]
                    n_batches[0] += 1
                return seen

            one_epoch()  # warm: thread spin-up, page cache
            n_batches[0] = 0
            seen = 0
            t0 = time.perf_counter()
            for _ in range(epochs):
                seen += one_epoch()
            dt = time.perf_counter() - t0
            return seen / dt, dt / max(1, n_batches[0])

        manifest = data_plane.ShardManifest(shards, chunk_records=chunk)
        decoder = data_plane.ImageDecoder(
            (3, crop, crop), rand_crop=True, resize=resize,
            layout="NHWC", dtype="float32")

        def plane_epoch(seed, epoch):
            """One epoch as TWO in-process hosts over a shared ledger
            (each host: `workers` decode threads), aggregate img/s."""
            ledger = data_plane.ChunkLedger()
            counts = {}
            waits = {}

            def host(h):
                # heterogeneous hosts (host 1 decodes with ONE worker):
                # the realistic slow-peer scenario — host 0 runs dry
                # first and steals host 1's tail, so the row's steal
                # count exercises the cross-host path
                loader = data_plane.StreamingDataLoader(
                    manifest, batch, decoder, host_id=h, num_hosts=2,
                    ledger=ledger, seed=seed, start_epoch=epoch,
                    num_workers=workers if h == 0 else 1)
                seen = nb = 0
                for b in loader:
                    seen += b.data.shape[0]
                    nb += 1
                counts[h] = seen
                waits[h] = nb

            ts = [threading.Thread(target=host, args=(h,))
                  for h in (0, 1)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            stats = ledger.stats()
            # fleet-level input latency: wall time per DELIVERED batch
            # (the same definition the baseline leg's single consumer
            # measures — its loop time per batch)
            wait = dt / max(1, sum(waits.values()))
            return sum(counts.values()) / dt, wait, stats

        def leg_plane():
            plane_epoch(0, 0)  # warm
            seen_rate = steals = 0
            waits = []
            for e in range(epochs):
                r, w, stats = plane_epoch(0, e + 1)
                seen_rate += r
                waits.append(w)
                steals += stats["steals"]
            return seen_rate / epochs, max(waits), steals

        loader_img_s, loader_wait = leg_loader()
        plane_img_s, plane_wait, steals = leg_plane()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    speedup = plane_img_s / loader_img_s if loader_img_s else 0.0
    row = {
        "config": "streaming_input_ab", "chips": 0, "batch_size": batch,
        "dtype": "uint8->float32", "platform": platform,
        "host_cores": os.cpu_count(), "decode_workers": workers,
        "hosts": 2, "chunk_records": chunk,
        "dataloader_img_per_sec": round(loader_img_s, 2),
        "data_plane_img_per_sec": round(plane_img_s, 2),
        "dataloader_data_wait_ms_per_step": round(loader_wait * 1e3, 3),
        "data_plane_data_wait_ms_per_step": round(plane_wait * 1e3, 3),
        "steal_count": int(steals),
        "images_or_tokens_per_sec_per_chip": round(plane_img_s, 2),
        "mfu": None, "flops_per_sample": None,
        "streaming_input_speedup": round(speedup, 4),
        "note": "host decode A/B on %dx%d jpeg -> resize %d -> crop %d; "
                "plane uses jpeg draft-mode DCT downscale + slot decode "
                "(deterministic; pixel values differ from the full-res "
                "decode+resize baseline by construction)"
                % (hw, hw, resize, crop),
    }
    _emit_jsonl(row)
    return speedup, row


def bench_async_ab(platform, dtype):
    """Async dispatch A/B (engine.py): the SAME fused Gluon step with the
    non-finite guard compiled in, run with the in-flight window at K=1
    (synchronous: every step's flag read back immediately) and at K=4
    (deferred: one mask read retires 4 steps' flags). The delta is pure
    dispatch/round-trip overhead: what a blocking host read per step
    costs on the attached chip is not measured yet."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, nd, profiler
    from mxnet_tpu.gluon import Trainer, nn

    del dtype  # f32: the A/B isolates dispatch, not math throughput
    batch = int(os.environ.get("BENCH_AB_BATCH", "64"))
    hidden = int(os.environ.get("BENCH_AB_HIDDEN", "256"))
    iters = int(os.environ.get("BENCH_AB_ITERS", "40"))
    warmup = int(os.environ.get("BENCH_AB_WARMUP", "3"))
    window = int(os.environ.get("BENCH_AB_INFLIGHT", "4"))

    prev_guard = os.environ.get("MXT_SKIP_NONFINITE")
    os.environ["MXT_SKIP_NONFINITE"] = "1"
    try:
        def run(k):
            mx.random.seed(0)
            net = nn.Sequential(prefix="ab%d_" % k)
            with net.name_scope():
                net.add(nn.Dense(hidden, activation="relu"),
                        nn.Dense(hidden, activation="relu"),
                        nn.Dense(10))
            net.initialize()
            tr = Trainer(net.collect_params(), "adam",
                         {"learning_rate": 1e-3})
            step = tr.fuse_step(net,
                                mx.gluon.loss.SoftmaxCrossEntropyLoss())
            rng = np.random.RandomState(0)
            x = nd.array(rng.uniform(-1, 1, (batch, 32)).astype(np.float32))
            y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
            with engine.bulk(k):
                for _ in range(warmup):
                    step(x, y).wait_to_read()
                t0 = time.perf_counter()
                h0 = profiler.host_sync_count()
                for _ in range(iters):
                    step(x, y)
                nd.waitall()
                dt = time.perf_counter() - t0
                syncs = profiler.host_sync_count() - h0
            return dt / iters * 1e3, syncs / iters

        sync_ms, sync_sps = run(1)
        async_ms, async_sps = run(window)
    finally:
        if prev_guard is None:
            os.environ.pop("MXT_SKIP_NONFINITE", None)
        else:
            os.environ["MXT_SKIP_NONFINITE"] = prev_guard

    speedup = sync_ms / async_ms if async_ms else 0.0
    row = {
        "config": "fused_step_async_ab", "chips": 1, "batch_size": batch,
        "dtype": "float32", "platform": platform,
        "inflight_window": window,
        "sync_step_time_ms": round(sync_ms, 3),
        "async_step_time_ms": round(async_ms, 3),
        "host_syncs_per_step_sync": round(sync_sps, 3),
        "host_syncs_per_step_async": round(async_sps, 3),
        "images_or_tokens_per_sec_per_chip": round(
            batch * 1e3 / async_ms, 2),
        "mfu": None, "flops_per_sample": None,
        "async_speedup": round(speedup, 3),
    }
    _emit_jsonl(row)
    return speedup, row


def bench_telemetry_ab(platform, dtype):
    """Telemetry overhead A/B (telemetry.py): the SAME fused Gluon step
    run with the telemetry JSONL sink OFF and then ON. The registry's
    histograms/spans are host-side wall-clock only, so the contract is
    (a) IDENTICAL host_syncs_per_step both ways — telemetry adds zero
    device reads to the hot path — and (b) <= ~3% step-time overhead
    with the sink on (=~0 when disabled: the sink check is one dict
    lookup). The row self-reports both so the driver can gate on them."""
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, nd, profiler, telemetry
    from mxnet_tpu.gluon import Trainer, nn

    del dtype  # f32: the A/B isolates instrumentation, not math
    batch = int(os.environ.get("BENCH_TAB_BATCH", "64"))
    hidden = int(os.environ.get("BENCH_TAB_HIDDEN", "256"))
    iters = int(os.environ.get("BENCH_TAB_ITERS", "40"))
    warmup = int(os.environ.get("BENCH_TAB_WARMUP", "3"))
    window = int(os.environ.get("BENCH_TAB_INFLIGHT", "4"))

    jsonl = tempfile.mktemp(prefix="mxt_bench_telemetry_",
                            suffix=".jsonl")
    prev_sink = os.environ.get("MXT_TELEMETRY_JSONL")

    def run(tag, sink_on):
        if sink_on:
            os.environ["MXT_TELEMETRY_JSONL"] = jsonl
        else:
            os.environ.pop("MXT_TELEMETRY_JSONL", None)
        try:
            mx.random.seed(0)
            net = nn.Sequential(prefix="tab_%s_" % tag)
            with net.name_scope():
                net.add(nn.Dense(hidden, activation="relu"),
                        nn.Dense(hidden, activation="relu"),
                        nn.Dense(10))
            net.initialize()
            tr = Trainer(net.collect_params(), "adam",
                         {"learning_rate": 1e-3})
            step = tr.fuse_step(net,
                                mx.gluon.loss.SoftmaxCrossEntropyLoss())
            rng = np.random.RandomState(0)
            x = nd.array(rng.uniform(-1, 1,
                                     (batch, 32)).astype(np.float32))
            y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
            with engine.bulk(window):
                for _ in range(warmup):
                    step(x, y).wait_to_read()
                t0 = time.perf_counter()
                h0 = profiler.host_sync_count()
                for _ in range(iters):
                    step(x, y)
                nd.waitall()
                dt = time.perf_counter() - t0
                syncs = profiler.host_sync_count() - h0
            return dt / iters * 1e3, syncs / iters
        finally:
            if prev_sink is None:
                os.environ.pop("MXT_TELEMETRY_JSONL", None)
            else:
                os.environ["MXT_TELEMETRY_JSONL"] = prev_sink

    off_ms, off_sps = run("off", False)
    on_ms, on_sps = run("on", True)
    telemetry.flush()
    try:
        with open(jsonl) as f:
            events = sum(1 for _ in f)
        os.remove(jsonl)
    except OSError:
        events = 0

    overhead = on_ms / off_ms if off_ms else 0.0
    row = {
        "config": "fused_step_telemetry_ab", "chips": 1,
        "batch_size": batch, "dtype": "float32", "platform": platform,
        "inflight_window": window,
        "telemetry_off_step_time_ms": round(off_ms, 3),
        "telemetry_on_step_time_ms": round(on_ms, 3),
        "host_syncs_per_step_off": round(off_sps, 3),
        "host_syncs_per_step_on": round(on_sps, 3),
        "jsonl_events": events,
        "images_or_tokens_per_sec_per_chip": round(
            batch * 1e3 / on_ms, 2),
        "mfu": None, "flops_per_sample": None,
        "telemetry_overhead": round(overhead, 4),
    }
    _emit_jsonl(row)
    return overhead, row


def bench_diagnostics_ab(platform, dtype):
    """Diagnostics overhead A/B (diagnostics.py): the SAME fused Gluon
    step run with the diagnostics layer disarmed (no flight-recorder
    tap, no watchdog) and then fully armed (flight recorder + watchdog
    daemon in report mode + HBM ledger, which is always on). The
    contract mirrors the telemetry A/B: (a) IDENTICAL host_syncs_per_step
    both ways — the watchdog observes heartbeat counters and the ledger
    observes shape metadata, so diagnostics add ZERO device reads to the
    hot path — and (b) step-time overhead within noise. The row
    self-reports both so the driver can gate on them."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import diagnostics, engine, nd, profiler
    from mxnet_tpu.gluon import Trainer, nn

    del dtype  # f32: the A/B isolates instrumentation, not math
    batch = int(os.environ.get("BENCH_DAB_BATCH", "64"))
    hidden = int(os.environ.get("BENCH_DAB_HIDDEN", "256"))
    iters = int(os.environ.get("BENCH_DAB_ITERS", "40"))
    warmup = int(os.environ.get("BENCH_DAB_WARMUP", "3"))
    window = int(os.environ.get("BENCH_DAB_INFLIGHT", "4"))

    def run(tag, armed):
        if armed:
            # recorder tap + watchdog thread (timeout far above any
            # real step so it never fires mid-bench)
            diagnostics.enable(timeout=3600.0, action="report",
                               handlers=False)
        else:
            diagnostics.disable()
        try:
            mx.random.seed(0)
            net = nn.Sequential(prefix="dab_%s_" % tag)
            with net.name_scope():
                net.add(nn.Dense(hidden, activation="relu"),
                        nn.Dense(hidden, activation="relu"),
                        nn.Dense(10))
            net.initialize()
            tr = Trainer(net.collect_params(), "adam",
                         {"learning_rate": 1e-3})
            step = tr.fuse_step(net,
                                mx.gluon.loss.SoftmaxCrossEntropyLoss())
            rng = np.random.RandomState(0)
            x = nd.array(rng.uniform(-1, 1,
                                     (batch, 32)).astype(np.float32))
            y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
            with engine.bulk(window):
                for _ in range(warmup):
                    step(x, y).wait_to_read()
                t0 = time.perf_counter()
                h0 = profiler.host_sync_count()
                for _ in range(iters):
                    step(x, y)
                nd.waitall()
                dt = time.perf_counter() - t0
                syncs = profiler.host_sync_count() - h0
            return dt / iters * 1e3, syncs / iters
        finally:
            diagnostics.disable()

    off_ms, off_sps = run("off", False)
    on_ms, on_sps = run("on", True)
    ring_events = len(diagnostics.recorder())
    diagnostics.disable()

    overhead = on_ms / off_ms if off_ms else 0.0
    row = {
        "config": "diagnostics_overhead_ab", "chips": 1,
        "batch_size": batch, "dtype": "float32", "platform": platform,
        "inflight_window": window,
        "diagnostics_off_step_time_ms": round(off_ms, 3),
        "diagnostics_on_step_time_ms": round(on_ms, 3),
        "host_syncs_per_step_off": round(off_sps, 3),
        "host_syncs_per_step_on": round(on_sps, 3),
        "flight_recorder_events": ring_events,
        "hbm_pools": sorted(diagnostics.ledger().snapshot()),
        "images_or_tokens_per_sec_per_chip": round(
            batch * 1e3 / on_ms, 2),
        "mfu": None, "flops_per_sample": None,
        "diagnostics_overhead": round(overhead, 4),
    }
    _emit_jsonl(row)
    return overhead, row


_COLD_WARM_CODE = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", os.environ["BENCH_CW_PLATFORM"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, tuning
from mxnet_tpu.gluon import Trainer, nn

mx.random.seed(0)
net = nn.Sequential(prefix="cw_")
with net.name_scope():
    net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
net.initialize()
tr = Trainer(net.collect_params(), "sgd",
             {"learning_rate": 0.1, "momentum": 0.9})
step = tr.fuse_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
rng = np.random.RandomState(0)
x = nd.array(rng.uniform(-1, 1, (32, 16)).astype(np.float32))
y = nd.array(rng.randint(0, 10, (32,)).astype(np.float32))
# BOTH legs AOT-warm-start so the code paths (and so the cache keys)
# are identical: the cold leg pays full XLA here, the warm leg replays
# deserializations from the shared on-disk cache
w0 = tuning.compile_stats()
t0 = time.perf_counter()
step.aot_warmup(x, y)
warmup_s = time.perf_counter() - t0
w1 = tuning.compile_stats()
pre = tuning.compile_stats()
t0 = time.perf_counter()
for _ in range(5):
    step(x, y)
nd.waitall()
dt = time.perf_counter() - t0
post = tuning.compile_stats()
print("CWROW " + json.dumps({
    "step_time_ms": dt / 5 * 1e3,
    "warmup_ms": warmup_s * 1e3,
    "warmup_compile_ms": (w1["compile_seconds"]
                          - w0["compile_seconds"]) * 1e3,
    "warmup_cache_misses": w1["cache_misses"] - w0["cache_misses"],
    "hot_compiles": post["compiles"] - pre["compiles"],
    "hot_compile_ms": (post["compile_seconds"]
                       - pre["compile_seconds"]) * 1e3,
    "hot_cache_misses": post["cache_misses"] - pre["cache_misses"],
    "total_compile_ms": post["compile_seconds"] * 1e3,
    "cache_hits": post["cache_hits"],
    "cache_misses": post["cache_misses"]}))
"""


def bench_embedding_ab(platform, dtype):
    """embedding_server_ab (embedding/): the SAME zipf-skewed
    pull/push row traffic driven against an in-process sharded
    embedding fleet of 1 and then 2 servers. Reports
    `embedding_bytes_per_sec` (the PERF.md r5 device-side metric, here
    measured over the fleet transport), the hot-row cache hit ratio,
    and RPCs/step — the scaling claim is bytes/sec increasing with
    server count (each server applies its shard's sparse updates on its
    own connection thread, so the fan-out overlaps)."""
    import numpy as np

    from mxnet_tpu import embedding, telemetry
    from mxnet_tpu import optimizer as opt

    del dtype  # row traffic is f32: the A/B isolates fleet scaling
    small = platform == "cpu"
    vocab = int(os.environ.get("BENCH_EMB_VOCAB",
                               "50000" if small else "500000"))
    dim = int(os.environ.get("BENCH_EMB_DIM", "64"))
    # 16k rows/step: the PERF.md-recorded geometry where the server-side
    # sparse apply (the part that scales with the fleet) dominates the
    # per-RPC fixed cost — smaller batches mostly measure transport
    batch = int(os.environ.get("BENCH_EMB_BATCH", "16384"))
    iters = int(os.environ.get("BENCH_EMB_ITERS", "8" if small else "20"))
    # shape warmup: with the pow2 row-count buckets the first few steps
    # compile one program per touched bucket and the timed lap replays
    # them — 3 laps cover the unique/hit/miss buckets this geometry
    # visits, so the A/B measures transport+apply, not XLA compiles
    # (the pre-bucket rows measured ~320 compiles over 8 steps)
    warmup = int(os.environ.get("BENCH_EMB_WARMUP", "3"))
    cache_rows = int(os.environ.get("BENCH_EMB_CACHE", "8192"))

    def counter_total(name):
        fam = telemetry.registry().get(name)
        if fam is None:
            return 0.0
        return float(sum(ch.value for ch in fam.children().values()))

    def run(n_servers):
        fleet, handles = embedding.local_fleet(n_servers, worker_id=0)
        tbl = embedding.ShardedEmbedding(
            fleet, "bench_emb_%d" % n_servers, (vocab, dim),
            cache_rows=cache_rows)
        # lazy init: rows materialize server-side on first touch — the
        # full table never exists on this worker (the >=10x-HBM shape)
        tbl.init_lazy(seed=0, scale=0.01)
        fleet.set_optimizer(opt.create("sgd", learning_rate=0.1))
        rng = np.random.RandomState(0)

        def sample():
            # zipf-skewed ids: a hot set the cache can hold plus a
            # long cold tail that keeps the fleet busy
            return (rng.zipf(1.2, size=batch) % vocab).astype(np.int64)

        from mxnet_tpu import tuning

        try:
            for _ in range(warmup):
                ids = sample()
                rows = tbl.pull(ids)
                tbl.push(ids, rows * 0.01)
            b0 = counter_total("mxt_embedding_bytes_total")
            r0 = counter_total("mxt_embedding_rpcs_total")
            c0 = tuning.compile_stats()
            t0 = time.perf_counter()
            for _ in range(iters):
                ids = sample()
                rows = tbl.pull(ids)
                tbl.push(ids, rows * 0.01)
            dt = time.perf_counter() - t0
            c1 = tuning.compile_stats()
            nbytes = counter_total("mxt_embedding_bytes_total") - b0
            rpcs = counter_total("mxt_embedding_rpcs_total") - r0
            return {
                "bytes_per_sec": nbytes / dt if dt else 0.0,
                "samples_per_sec": batch * iters / dt if dt else 0.0,
                "rpcs_per_step": rpcs / (2.0 * iters),  # pull+push = 1 step
                "hit_ratio": tbl.cache.hit_ratio,
                # bucket-bounded claim: compiles in the TIMED lap (the
                # pre-bucket code recompiled the sparse path per step)
                "measured_compiles": c1["compiles"] - c0["compiles"],
                "measured_compile_ms": round(
                    (c1["compile_seconds"] - c0["compile_seconds"]) * 1e3),
            }
        finally:
            tbl.close()
            fleet.close()
            # non-coordinator servers first (deregister needs server 0)
            for h in reversed(handles):
                h.close()

    def best(n_servers, reps=2):
        # best-of-reps per leg: the legs run sequentially, so one
        # scheduler hiccup would otherwise skew the ratio either way
        runs = [run(n_servers) for _ in range(reps)]
        return max(runs, key=lambda r: r["bytes_per_sec"])

    one = best(1)
    two = best(2)
    scaling = two["bytes_per_sec"] / one["bytes_per_sec"] \
        if one["bytes_per_sec"] else 0.0
    row = {
        "config": "embedding_server_ab", "chips": 0, "batch_size": batch,
        "dtype": "float32", "platform": platform, "mfu": None,
        "vocab": vocab, "embed_dim": dim, "cache_rows": cache_rows,
        "embedding_bytes_per_sec": round(two["bytes_per_sec"]),
        "embedding_bytes_per_sec_1srv": round(one["bytes_per_sec"]),
        "embedding_bytes_per_sec_2srv": round(two["bytes_per_sec"]),
        "server_scaling_x": round(scaling, 3),
        "cache_hit_ratio_1srv": round(one["hit_ratio"], 4),
        "cache_hit_ratio_2srv": round(two["hit_ratio"], 4),
        "rpcs_per_step_1srv": round(one["rpcs_per_step"], 2),
        "rpcs_per_step_2srv": round(two["rpcs_per_step"], 2),
        "samples_per_sec_2srv": round(two["samples_per_sec"], 1),
        "measured_compiles_1srv": one["measured_compiles"],
        "measured_compiles_2srv": two["measured_compiles"],
        "measured_compile_ms_2srv": two["measured_compile_ms"],
    }
    _emit_jsonl(row)
    return scaling, row


def bench_serving_fleet(platform, dtype):
    """serving_fleet_ab (serving/fleet.py + router.py): the SAME
    mixed-length traffic routed through a 1-replica and a 2-replica
    membership-backed serving fleet (SLO-aware router, load-aware
    placement), plus a kill-one-replica-mid-run chaos cell on the
    2-replica fleet — the row records tokens/s and request p50/p99 per
    fleet size and asserts-by-record that the kill cell loses ZERO
    accepted requests (every one completes via failover, idempotency-
    deduped, `kill_failovers` > 0)."""
    import numpy as np

    from mxnet_tpu import serving

    del dtype  # f32: the A/B isolates routing, not math throughput
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", "4"))
    n_req = int(os.environ.get("BENCH_FLEET_REQUESTS", "16"))
    layers, heads, hdim = 2, 2, 16
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)

    def factory():
        return serving.DecodeEngine(
            model, params=params, slots=slots,
            cache=serving.PagedKVCache(layers, heads, hdim,
                                       num_pages=256, page_size=16),
            prefill_buckets=(64,), max_context=128)

    def traffic(router):
        rng = np.random.RandomState(11)
        out = []
        for i in range(n_req):
            plen = int(rng.randint(4, 49))
            mnew = int(rng.randint(4, 17))
            out.append(router.submit(
                rng.randint(1, 512, plen).tolist(),
                max_new_tokens=mnew, token="fb-%d" % i))
        return out

    def run(n, kill_at=None):
        pool, srv = serving.local_serving_fleet(n, factory)
        router = serving.FleetRouter(pool)
        try:
            reqs = traffic(router)
            t0 = time.perf_counter()
            if kill_at is not None:
                while router.step() and router.steps < kill_at:
                    pass
                pool.get(n - 1).kill()
            router.run(max_steps=20000)
            dt = time.perf_counter() - t0
            done = [r for r in reqs if r.state == "completed"]
            tokens = sum(len(r.result) for r in done)
            lats = sorted(r.t_finish - r.t_submit for r in done)
            pick = lambda q: lats[min(len(lats) - 1,
                                      int(q * len(lats)))] \
                if lats else 0.0
            return {
                "tokens_per_sec": tokens / dt if dt else 0.0,
                "completed": len(done),
                "lost": len(reqs) - len(done),
                "p50_ms": pick(0.50) * 1e3, "p99_ms": pick(0.99) * 1e3,
                "failovers": sum(r.failovers for r in reqs),
                "hedges": sum(r.hedges for r in reqs),
            }
        finally:
            for h in pool.replicas():
                try:
                    h.close()
                except Exception:  # noqa: BLE001 — killed handles
                    pass
            srv.close()

    one = run(1)
    two = run(2)
    killed = run(2, kill_at=6)
    scaling = two["tokens_per_sec"] / one["tokens_per_sec"] \
        if one["tokens_per_sec"] else 0.0
    row = {
        "config": "serving_fleet_ab", "chips": 1, "batch_size": slots,
        "dtype": "float32", "platform": platform, "requests": n_req,
        "images_or_tokens_per_sec_per_chip": round(
            two["tokens_per_sec"], 2),
        "tokens_per_sec_1rep": round(one["tokens_per_sec"], 2),
        "tokens_per_sec_2rep": round(two["tokens_per_sec"], 2),
        "replica_scaling_x": round(scaling, 3),
        "p99_ms_1rep": round(one["p99_ms"], 2),
        "p99_ms_2rep": round(two["p99_ms"], 2),
        "kill_completed": killed["completed"],
        "kill_lost_requests": killed["lost"],
        "kill_failovers": killed["failovers"],
        "kill_p99_ms": round(killed["p99_ms"], 2),
        "kill_tokens_per_sec": round(killed["tokens_per_sec"], 2),
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return scaling, row


def bench_fleet_observability(platform, dtype):
    """fleet_observability_ab (telemetry_fleet.py): the SAME
    mixed-length traffic routed through a 2-replica membership-backed
    fleet with the fleet collector scraping on a background thread vs
    observability idle. The collector reads registries and wall clocks
    — never the device — so the row asserts-by-record that serving-path
    host-sync counts per decode step are IDENTICAL and records the
    tokens/s overhead ratio (target >= 0.97x)."""
    import numpy as np

    from mxnet_tpu import profiler, serving, telemetry_fleet

    del dtype  # f32: the A/B isolates observability overhead
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", "4"))
    n_req = int(os.environ.get("BENCH_FLEET_REQUESTS", "16"))
    layers, heads, hdim = 2, 2, 16
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)

    def factory():
        return serving.DecodeEngine(
            model, params=params, slots=slots,
            cache=serving.PagedKVCache(layers, heads, hdim,
                                       num_pages=256, page_size=16),
            prefill_buckets=(64,), max_context=128)

    def run(collect):
        pool, srv = serving.local_serving_fleet(2, factory)
        router = serving.FleetRouter(pool)
        coll = None
        if collect:
            coll = telemetry_fleet.FleetCollector(server=srv)
            coll.refresh()
            coll.start(interval=0.05)
        try:
            rng = np.random.RandomState(11)
            reqs = []
            for i in range(n_req):
                plen = int(rng.randint(4, 49))
                mnew = int(rng.randint(4, 17))
                reqs.append(router.submit(
                    rng.randint(1, 512, plen).tolist(),
                    max_new_tokens=mnew, token="fo-%d" % i))
            h0 = profiler.host_sync_count()
            t0 = time.perf_counter()
            router.run(max_steps=20000)
            dt = time.perf_counter() - t0
            syncs = profiler.host_sync_count() - h0
            steps = sum(h.batcher.steps for h in pool.replicas())
            done = [r for r in reqs if r.state == "completed"]
            tokens = sum(len(r.result) for r in done)
            scrapes = 0
            if coll is not None:
                coll.scrape()  # at least one full pass is guaranteed
                scrapes = coll.scrapes
            return {
                "tokens_per_sec": tokens / dt if dt else 0.0,
                "completed": len(done),
                "syncs_per_step": syncs / max(1, steps),
                "scrapes": scrapes,
            }
        finally:
            if coll is not None:
                coll.close()
            for h in pool.replicas():
                try:
                    h.close()
                except Exception:  # noqa: BLE001 — teardown best effort
                    pass
            srv.close()

    run(False)  # discarded warmup leg: both timed legs run shape-warm
    base = run(False)
    obs = run(True)
    ratio = obs["tokens_per_sec"] / base["tokens_per_sec"] \
        if base["tokens_per_sec"] else 0.0
    row = {
        "config": "fleet_observability_ab", "chips": 1,
        "batch_size": slots, "dtype": "float32", "platform": platform,
        "requests": n_req,
        "images_or_tokens_per_sec_per_chip": round(
            obs["tokens_per_sec"], 2),
        "idle_tokens_per_sec": round(base["tokens_per_sec"], 2),
        "collector_tokens_per_sec": round(obs["tokens_per_sec"], 2),
        "observability_overhead_x": round(ratio, 3),
        "syncs_per_step_idle": round(base["syncs_per_step"], 4),
        "syncs_per_step_collector": round(obs["syncs_per_step"], 4),
        "sync_parity": base["syncs_per_step"] == obs["syncs_per_step"],
        "collector_scrapes": obs["scrapes"],
        "completed_idle": base["completed"],
        "completed_collector": obs["completed"],
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return ratio, row


def bench_training_health_ab(platform, dtype):
    """training_health_ab (health.py): the SAME fused Gluon step run
    with the per-layer training-health plane OFF and then ON. The stat
    row (grad/param norms, update ratios, loss stats) is computed
    INSIDE the donated step and rides the InflightWindow's staged value
    channel, so the contract is the strongest of the observability
    A/Bs: (a) host_syncs_per_step BIT-EQUAL both ways (parity
    asserted-by-record), (b) per-step losses BIT-IDENTICAL (the row is
    an extra output, never a feedback path), (c) overhead ratio
    recorded (target >= 0.97x at accelerator scale — on a ~1ms CPU toy
    step the extra norm outputs are a visible fraction; on a real step
    they are noise). A third seeded leg injects a
    ``grad_spike`` chaos fault and records that the anomaly detectors
    fired — the end-to-end proof that the plane actually watches."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, health, nd, profiler, resilience
    from mxnet_tpu.gluon import Trainer, nn

    del dtype  # f32: the A/B isolates instrumentation, not math
    batch = int(os.environ.get("BENCH_HAB_BATCH", "64"))
    hidden = int(os.environ.get("BENCH_HAB_HIDDEN", "256"))
    iters = int(os.environ.get("BENCH_HAB_ITERS", "40"))
    warmup = int(os.environ.get("BENCH_HAB_WARMUP", "3"))
    window = int(os.environ.get("BENCH_HAB_INFLIGHT", "4"))

    prev = {k: os.environ.get(k)
            for k in ("MXT_HEALTH", "MXT_FAULT", "MXT_CHAOS_SEED",
                      "MXT_HEALTH_POSTMORTEM")}

    def _restore():
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience.reset_faults()
        health.reset()

    def run(tag, armed, fault=None):
        os.environ["MXT_HEALTH"] = "1" if armed else "0"
        if fault:
            os.environ["MXT_FAULT"] = fault
            os.environ["MXT_CHAOS_SEED"] = "0"
            # counting anomalies, not collecting dumps — don't litter
            # the bench cwd with post-mortem files
            os.environ["MXT_HEALTH_POSTMORTEM"] = "0"
        else:
            os.environ.pop("MXT_FAULT", None)
        resilience.reset_faults()
        health.reset()
        try:
            mx.random.seed(0)
            net = nn.Sequential(prefix="hab_%s_" % tag)
            with net.name_scope():
                net.add(nn.Dense(hidden, activation="relu"),
                        nn.Dense(hidden, activation="relu"),
                        nn.Dense(10))
            net.initialize()
            tr = Trainer(net.collect_params(), "adam",
                         {"learning_rate": 1e-3})
            step = tr.fuse_step(net,
                                mx.gluon.loss.SoftmaxCrossEntropyLoss())
            rng = np.random.RandomState(0)
            x = nd.array(rng.uniform(-1, 1,
                                     (batch, 32)).astype(np.float32))
            y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
            losses = []
            with engine.bulk(window):
                for _ in range(warmup):
                    step(x, y).wait_to_read()
                t0 = time.perf_counter()
                h0 = profiler.host_sync_count()
                for _ in range(iters):
                    losses.append(step(x, y))
                nd.waitall()
                dt = time.perf_counter() - t0
                syncs = profiler.host_sync_count() - h0
            # loss reads happen OUTSIDE the timed/sync-counted region
            blob = b"".join(np.asarray(v.asnumpy(), dtype=np.float32)
                            .tobytes() for v in losses)
            anomalies = (step._health_mon.anomaly_count
                         if getattr(step, "_health_mon", None) else 0)
            return dt / iters * 1e3, syncs / iters, blob, anomalies
        finally:
            _restore()

    off_ms, off_sps, off_blob, _ = run("off", False)
    on_ms, on_sps, on_blob, _ = run("on", True)
    spike_after = max(2, warmup)
    _, _, _, spike_anoms = run(
        "spike", True,
        fault="grad_spike:layer=0,after=%d,scale=1e6,n=1" % spike_after)

    overhead = on_ms / off_ms if off_ms else 0.0
    row = {
        "config": "training_health_ab", "chips": 1,
        "batch_size": batch, "dtype": "float32", "platform": platform,
        "inflight_window": window,
        "health_off_step_time_ms": round(off_ms, 3),
        "health_on_step_time_ms": round(on_ms, 3),
        "host_syncs_per_step_off": round(off_sps, 3),
        "host_syncs_per_step_on": round(on_sps, 3),
        "sync_parity": off_sps == on_sps,  # bit-equal, not tolerance
        "losses_equal": off_blob == on_blob,  # bit-identical streams
        "spike_anomalies": spike_anoms,
        "spike_detected": spike_anoms > 0,
        "images_or_tokens_per_sec_per_chip": round(
            batch * 1e3 / on_ms, 2) if on_ms else 0.0,
        "mfu": None, "flops_per_sample": None,
        "training_health_overhead": round(overhead, 4),
    }
    _emit_jsonl(row)
    return overhead, row


def bench_speculative(platform, dtype):
    """speculative_ab (serving/speculative.py): the SAME mixed-length
    traffic decoded by the plain engine and by the speculative engine
    (1-layer truncated draft of the 4-layer target, draft_k proposals
    verified in one wide launch). Records tokens/s both ways, the
    acceptance rate, host syncs/step, and asserts-by-record that the
    two engines' token streams are IDENTICAL (greedy token-exact —
    speculation changes the schedule, never the output)."""
    import numpy as np

    from mxnet_tpu import profiler, serving

    del dtype  # f32: the A/B isolates scheduling, not math throughput
    slots = int(os.environ.get("BENCH_SPEC_SLOTS", "8"))
    n_req = int(os.environ.get("BENCH_SPEC_REQUESTS", "24"))
    draft_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    layers, heads, hdim = 4, 2, 32
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)
    draft, dparams = model.truncated(params, 1)

    def traffic(n):
        rng = np.random.RandomState(7)
        return [(rng.randint(1, 512, int(rng.randint(4, 97))).tolist(),
                 int(rng.randint(8, 65))) for _ in range(n)]

    def run(spec):
        if spec:
            eng = serving.SpeculativeEngine(
                model, draft, params=params, draft_params=dparams,
                draft_k=draft_k, slots=slots,
                cache=serving.PagedKVCache(layers, heads, hdim,
                                           num_pages=128, page_size=16),
                draft_cache=serving.PagedKVCache(
                    1, heads, hdim, num_pages=128, page_size=16),
                prefill_buckets=(64, 128), max_context=176)
        else:
            eng = serving.DecodeEngine(
                model, params=params, slots=slots,
                cache=serving.PagedKVCache(layers, heads, hdim,
                                           num_pages=128, page_size=16),
                prefill_buckets=(64, 128), max_context=176)
        eng.aot_warmup()
        warm = serving.ContinuousBatcher(eng)
        for p, m in traffic(6):
            warm.submit(serving.Request(p, max_new_tokens=m))
        warm.run()
        best = None
        for _ in range(3):  # best-of-3: steady-state, box-noise-proof
            sched = serving.ContinuousBatcher(eng)
            reqs = [sched.submit(serving.Request(p, max_new_tokens=m))
                    for p, m in traffic(n_req)]
            h0 = profiler.host_sync_count()
            t0 = time.perf_counter()
            sched.run(max_steps=50000)
            dt = time.perf_counter() - t0
            syncs = profiler.host_sync_count() - h0
            toks = sum(len(r.output_tokens) for r in reqs)
            lap = {"streams": [r.output_tokens for r in reqs],
                   "tokens_per_sec": toks / dt if dt else 0.0,
                   "steps": sched.steps,
                   "host_syncs_per_step": syncs / max(1, sched.steps)}
            if best is None or lap["tokens_per_sec"] \
                    > best["tokens_per_sec"]:
                best = lap
        return best

    def counter_total(name):
        from mxnet_tpu import telemetry

        fam = telemetry.registry().get(name)
        if fam is None:
            return 0.0
        return float(sum(ch.value for ch in fam.children().values()))

    base = run(False)
    p0 = counter_total("mxt_serving_spec_proposed_tokens_total")
    a0 = counter_total("mxt_serving_spec_accepted_tokens_total")
    spec = run(True)
    proposed = counter_total(
        "mxt_serving_spec_proposed_tokens_total") - p0
    accepted = counter_total(
        "mxt_serving_spec_accepted_tokens_total") - a0
    speedup = spec["tokens_per_sec"] / base["tokens_per_sec"] \
        if base["tokens_per_sec"] else 0.0
    row = {
        "config": "speculative_ab", "chips": 1, "batch_size": slots,
        "dtype": "float32", "platform": platform, "requests": n_req,
        "draft_k": draft_k,
        "images_or_tokens_per_sec_per_chip": round(
            spec["tokens_per_sec"], 2),
        "baseline_tokens_per_sec": round(base["tokens_per_sec"], 2),
        "speculative_tokens_per_sec": round(spec["tokens_per_sec"], 2),
        "speculative_speedup": round(speedup, 3),
        "token_exact": base["streams"] == spec["streams"],
        "acceptance_rate": round(accepted / proposed, 4)
        if proposed else None,
        "baseline_steps": base["steps"],
        "speculative_steps": spec["steps"],
        "host_syncs_per_step": round(spec["host_syncs_per_step"], 3),
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return speedup, row


def bench_kv_quant(platform, dtype):
    """kv_quant_ab (serving/kv_cache.py quantized pools): the SAME
    short-sequence flood served from an f32 KV pool and from an int8
    pool holding the SAME DEVICE BYTE BUDGET — the quantized pool packs
    ~3-4x the pages, so admission keeps ~3-4x the sequences resident
    concurrently (the capacity half), at bounded output divergence and
    unchanged decode-loop syncs/step (the quality/async halves)."""
    import numpy as np

    from mxnet_tpu import profiler, serving

    del dtype
    # slots exceed what the f32 pool can seat at this byte budget: the
    # POOL is the binding resource, so resident concurrency measures
    # page capacity (the quantized pool's whole point), not slot count
    slots = int(os.environ.get("BENCH_KVQ_SLOTS", "48"))
    n_req = int(os.environ.get("BENCH_KVQ_REQUESTS", "64"))
    budget = int(os.environ.get("BENCH_KVQ_BYTES", str(768 << 10)))
    layers, heads, hdim = 2, 2, 32
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)

    def traffic(n):
        rng = np.random.RandomState(11)
        return [(rng.randint(1, 512, int(rng.randint(8, 33))).tolist(),
                 int(rng.randint(8, 25))) for _ in range(n)]

    def run(quantized):
        pages = serving.PagedKVCache.pages_for_budget(
            budget, layers, heads, hdim, page_size=16,
            quantized=quantized)
        cache = serving.PagedKVCache(layers, heads, hdim,
                                     num_pages=pages, page_size=16,
                                     quantized=quantized)
        eng = serving.DecodeEngine(model, params=params, slots=slots,
                                   cache=cache,
                                   prefill_buckets=(64,),
                                   max_context=64)
        eng.aot_warmup()
        warm = serving.ContinuousBatcher(eng)
        warm.submit(serving.Request([1, 2, 3], max_new_tokens=4))
        warm.run()
        sched = serving.ContinuousBatcher(eng)
        reqs = [sched.submit(serving.Request(p, max_new_tokens=m))
                for p, m in traffic(n_req)]
        peak = 0
        h0 = profiler.host_sync_count()
        t0 = time.perf_counter()
        while (sched._queue or sched._slot_req) and sched.steps < 20000:
            sched.step()
            peak = max(peak, len(cache._quota))
        sched.drain()
        dt = time.perf_counter() - t0
        syncs = profiler.host_sync_count() - h0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {"streams": [r.output_tokens for r in reqs],
                "pages": pages, "peak_resident": peak,
                "tokens_per_sec": toks / dt if dt else 0.0,
                "page_bytes": cache.page_bytes,
                "host_syncs_per_step": syncs / max(1, sched.steps)}

    f32 = run(False)
    q8 = run(True)
    total = sum(len(s) for s in f32["streams"])
    same = sum(sum(1 for x, y in zip(a, b) if x == y)
               for a, b in zip(f32["streams"], q8["streams"]))
    ratio = q8["peak_resident"] / f32["peak_resident"] \
        if f32["peak_resident"] else 0.0
    row = {
        "config": "kv_quant_ab", "chips": 1, "batch_size": slots,
        "dtype": "float32", "platform": platform, "requests": n_req,
        "byte_budget": budget,
        "pages_f32": f32["pages"], "pages_int8": q8["pages"],
        "page_bytes_f32": f32["page_bytes"],
        "page_bytes_int8": q8["page_bytes"],
        "peak_resident_f32": f32["peak_resident"],
        "peak_resident_int8": q8["peak_resident"],
        "resident_ratio": round(ratio, 3),
        "token_agreement": round(same / total, 4) if total else None,
        "tokens_per_sec_f32": round(f32["tokens_per_sec"], 2),
        "tokens_per_sec_int8": round(q8["tokens_per_sec"], 2),
        "images_or_tokens_per_sec_per_chip": round(
            q8["tokens_per_sec"], 2),
        "host_syncs_per_step_f32": round(
            f32["host_syncs_per_step"], 3),
        "host_syncs_per_step_int8": round(
            q8["host_syncs_per_step"], 3),
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return ratio, row


def bench_prefix_reuse(platform, dtype):
    """prefix_reuse_ab (serving/prefix.py + kv_cache refcounts): the
    SAME prefix-heavy traffic (every request opens with one shared
    system prompt — BENCH_PFX_SYSLEN tokens) served with the prefix
    cache off and on.
    A hit points the new sequence's page table at the already-resident
    prefix pages (copy-on-write on divergence) and prefills only the
    suffix — so the A/B measures tokens/s, admission latency p50/p99,
    and (at a fixed page budget) how many sequences stay resident
    concurrently. One extra leg runs the reuse-on pool quantized: int8
    pages times shared prefixes compound into the resident-capacity
    headline. Token-exact by record on the f32 legs (masked suffix
    attention over stored pages is bit-identical to full prefill)."""
    import numpy as np

    from mxnet_tpu import serving

    del dtype  # f32 A/B isolates admission scheduling, not math
    slots = int(os.environ.get("BENCH_PFX_SLOTS", "8"))
    n_req = int(os.environ.get("BENCH_PFX_REQUESTS", "16"))
    sys_len = int(os.environ.get("BENCH_PFX_SYSLEN", "256"))
    layers, heads, hdim = 4, 2, 32
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)
    rng0 = np.random.RandomState(3)
    system = rng0.randint(1, 512, sys_len).tolist()

    def traffic(n):
        rng = np.random.RandomState(13)
        reqs = [(system + rng.randint(1, 512,
                                      int(rng.randint(1, 33))).tolist(),
                 8) for _ in range(n)]
        # request 0 ends page-aligned, and every 8th request replays it
        # verbatim: the FULL-match path (share every page, copy-on-write
        # the tail page before the first decode write) stays live in the
        # A/B, not just in unit tests
        reqs[0] = (system + rng.randint(1, 512, 16).tolist(), 8)
        for i in range(7, n, 8):
            reqs[i] = reqs[0]
        return reqs

    def counter_total(name):
        from mxnet_tpu import telemetry

        fam = telemetry.registry().get(name)
        if fam is None:
            return 0.0
        return float(sum(ch.value for ch in fam.children().values()))

    def run(reuse, quantized=False, num_pages=512, nslots=None,
            nreq=None):
        cache = serving.PagedKVCache(layers, heads, hdim,
                                     num_pages=num_pages, page_size=16,
                                     quantized=quantized)
        eng = serving.DecodeEngine(model, params=params,
                                   slots=nslots or slots, cache=cache,
                                   prefill_buckets=(32, 512),
                                   max_context=320, prefix_cache=reuse)
        eng.aot_warmup()
        warm = serving.ContinuousBatcher(eng)
        wt = traffic(2)
        # warm every admission program the lap will hit: the plain
        # prefill (miss), the partial-hit suffix prefill, and the
        # full-match replay (its COW + last-page program)
        for p, m in (wt[0], wt[0], wt[1]):
            warm.submit(serving.Request(p, max_new_tokens=m))
        warm.run()
        best = None
        for _ in range(3):  # best-of-3: steady-state, box-noise-proof
            if eng.prefix is not None:
                eng.prefix.clear()  # every lap starts cold
            sched = serving.ContinuousBatcher(eng)
            reqs = [sched.submit(serving.Request(p, max_new_tokens=m))
                    for p, m in traffic(nreq or n_req)]
            peak = 0
            t0 = time.perf_counter()
            while (sched._queue or sched._slot_req) \
                    and sched.steps < 50000:
                sched.step()
                peak = max(peak, len(cache._quota))
            sched.drain()
            dt = time.perf_counter() - t0
            toks = sum(len(r.output_tokens) for r in reqs)
            admit = sorted(r.t_first - r.t_submit for r in reqs
                           if r.t_first is not None)
            lap = {"streams": [r.output_tokens for r in reqs],
                   "tokens_per_sec": toks / dt if dt else 0.0,
                   "peak_resident": peak,
                   "admit_p50": admit[len(admit) // 2]
                   if admit else None,
                   "admit_p99": admit[min(len(admit) - 1,
                                          int(len(admit) * 0.99))]
                   if admit else None}
            if best is None or lap["tokens_per_sec"] \
                    > best["tokens_per_sec"]:
                best = lap
        return best

    base = run(False)
    h0 = counter_total("mxt_serving_prefix_hits_total")
    m0 = counter_total("mxt_serving_prefix_misses_total")
    c0 = counter_total("mxt_serving_cow_copies_total")
    on = run(True)
    hits = counter_total("mxt_serving_prefix_hits_total") - h0
    misses = counter_total("mxt_serving_prefix_misses_total") - m0
    cows = counter_total("mxt_serving_cow_copies_total") - c0
    # capacity legs: a page pool too small to seat everyone without
    # sharing — resident concurrency is what reuse (and int8 x reuse)
    # buys at a FIXED device byte budget
    cap_pages = int(os.environ.get("BENCH_PFX_CAP_PAGES", "48"))
    budget = cap_pages * serving.PagedKVCache(
        layers, heads, hdim, num_pages=1, page_size=16).page_bytes
    cap_off = run(False, num_pages=cap_pages, nslots=24, nreq=24)
    cap_on = run(True, num_pages=cap_pages, nslots=24, nreq=24)
    q_pages = serving.PagedKVCache.pages_for_budget(
        budget, layers, heads, hdim, page_size=16, quantized=True)
    cap_q = run(True, quantized=True, num_pages=q_pages, nslots=24,
                nreq=24)
    speedup = on["tokens_per_sec"] / base["tokens_per_sec"] \
        if base["tokens_per_sec"] else 0.0
    resident_ratio = cap_on["peak_resident"] / cap_off["peak_resident"] \
        if cap_off["peak_resident"] else 0.0
    resident_q = cap_q["peak_resident"] / cap_off["peak_resident"] \
        if cap_off["peak_resident"] else 0.0
    row = {
        "config": "prefix_reuse_ab", "chips": 1, "batch_size": slots,
        "dtype": "float32", "platform": platform, "requests": n_req,
        "system_prompt_tokens": sys_len,
        "images_or_tokens_per_sec_per_chip": round(
            on["tokens_per_sec"], 2),
        "baseline_tokens_per_sec": round(base["tokens_per_sec"], 2),
        "reuse_tokens_per_sec": round(on["tokens_per_sec"], 2),
        "prefix_reuse_speedup": round(speedup, 3),
        "token_exact": base["streams"] == on["streams"],
        "admit_p50_off": round(base["admit_p50"], 5)
        if base["admit_p50"] is not None else None,
        "admit_p50_on": round(on["admit_p50"], 5)
        if on["admit_p50"] is not None else None,
        "admit_p99_off": round(base["admit_p99"], 5)
        if base["admit_p99"] is not None else None,
        "admit_p99_on": round(on["admit_p99"], 5)
        if on["admit_p99"] is not None else None,
        "prefix_hit_ratio": round(hits / (hits + misses), 4)
        if hits + misses else None,
        "cow_copies": int(cows),
        "cap_page_budget_bytes": budget,
        "peak_resident_off": cap_off["peak_resident"],
        "peak_resident_on": cap_on["peak_resident"],
        "peak_resident_int8": cap_q["peak_resident"],
        "resident_ratio": round(resident_ratio, 3),
        "resident_int8_ratio": round(resident_q, 3),
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return speedup, row


def bench_autoscale(platform, dtype):
    """autoscale_ab (serving/autoscaler.py + qos.py): a seeded flash
    crowd (the traffic_storm fault rule) hits a fleet held at its
    1-replica floor while the autoscaler watches the merged fleet page.
    Asserts-by-record: the fleet scales UP (up decisions > 0, visible
    as scale_up spans on the autoscaler's trace track in the Perfetto
    fleet timeline), EVERY offered request is accounted — submitted ==
    completed + typed-rejected, zero lost — and the p99 of the LAST
    half of completions (after the spare went routable) recovers to
    within the SLO. A second cell is the QoS isolation assert: a bulk
    tenant saturates admission, its over-quota submits are refused
    typed (OverQuotaError), and the interactive tenant's p99 stays
    within a bounded multiple of the unloaded p99."""
    import numpy as np

    from mxnet_tpu import resilience, serving

    del dtype  # f32: the A/B isolates the control loop, not math
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", "4"))
    n_req = int(os.environ.get("BENCH_AUTOSCALE_REQUESTS", "24"))
    window = float(os.environ.get("BENCH_AUTOSCALE_WINDOW", "120"))
    layers, heads, hdim = 2, 2, 16
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)

    def factory():
        return serving.DecodeEngine(
            model, params=params, slots=slots,
            cache=serving.PagedKVCache(layers, heads, hdim,
                                       num_pages=256, page_size=16),
            prefill_buckets=(64,), max_context=128)

    def close_fleet(pool, srv):
        for h in pool.replicas():
            try:
                h.close()
            except Exception:  # noqa: BLE001 — drained/killed handles
                pass
        srv.close()

    def pick(lats, q):
        return lats[min(len(lats) - 1, int(q * len(lats)))] \
            if lats else 0.0

    # -- phase A: unloaded p99 at the floor — the yardstick both the
    # SLO and the QoS isolation multiple are calibrated against
    pool, srv = serving.local_serving_fleet(1, factory)
    router = serving.FleetRouter(pool)
    try:
        rng = np.random.RandomState(7)
        base = []
        for i in range(6):
            base.append(router.submit(
                rng.randint(1, 512, 8).tolist(), max_new_tokens=6,
                token="base-%d" % i))
            router.run(max_steps=20000)
        blats = sorted(r.t_finish - r.t_submit for r in base
                       if r.state == "completed")
        p99_base = pick(blats, 0.99)
    finally:
        close_fleet(pool, srv)
    slo = max(8 * p99_base, 0.25)

    # -- phase B: flash crowd, autoscaler closing the loop
    old_fault = os.environ.get("MXT_FAULT")
    os.environ["MXT_FAULT"] = "traffic_storm:rps=200,after=2"
    resilience.reset_faults()
    pool, srv = serving.local_serving_fleet(1, factory)
    router = serving.FleetRouter(pool, slo=slo)
    scaler = serving.FleetAutoscaler(
        router, factory, slo=slo, min_replicas=1, max_replicas=3,
        cooldown=0.25, queue_high=1.0, calm_ticks=10 ** 6)
    gen = serving.TrafficGenerator(
        router, rate=5.0, seed=3, vocab=512, prompt_len=(4, 16),
        max_new_tokens=6, max_requests=n_req)
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < window:
            gen.tick(router._now())
            router.step()
            scaler.step()
            if gen.total_offered() >= n_req \
                    and all(r.done for r in gen.submitted):
                break
        storm_dt = time.perf_counter() - t0
        done = [r for r in gen.submitted if r.state == "completed"]
        lost = len(gen.submitted) - len(done)
        tokens = sum(len(r.result) for r in done)
        by_finish = sorted(done, key=lambda r: r.t_finish)
        tail = sorted(r.t_finish - r.t_submit
                      for r in by_finish[len(by_finish) // 2:])
        p99_tail = pick(tail, 0.99)
        up_events = sum(1 for d in scaler.decisions
                        if d["direction"] == "up")
        replicas_end = len(pool.routable())
        scaler._collector.scrape()
        span_names = {s.get("name")
                      for s in scaler._collector.spans(scaler.trace_id)}
        on_timeline = "scale_up" in span_names
    finally:
        scaler.close()
        close_fleet(pool, srv)
        if old_fault is None:
            os.environ.pop("MXT_FAULT", None)
        else:
            os.environ["MXT_FAULT"] = old_fault
        resilience.reset_faults()

    # -- phase C: QoS isolation — bulk saturates admission, interactive
    # rides the priority queue, over-quota bulk is refused typed
    qos = serving.QosPolicy.parse("interactive:bulk")
    qos.add_tenant("bulk", max_requests=3)
    pool, srv = serving.local_serving_fleet(1, factory)
    router = serving.FleetRouter(pool, qos=qos)
    try:
        rng = np.random.RandomState(5)
        bulk_ok = bulk_refused = 0
        for i in range(12):
            try:
                router.submit(rng.randint(1, 512, 12).tolist(),
                              max_new_tokens=8, token="blk-%d" % i,
                              tenant="bulk")
                bulk_ok += 1
            except serving.OverQuotaError:
                bulk_refused += 1
        inter = [router.submit(rng.randint(1, 512, 8).tolist(),
                               max_new_tokens=6, token="int-%d" % i,
                               tenant="interactive")
                 for i in range(6)]
        router.run(max_steps=40000)
        ilats = sorted(r.t_finish - r.t_submit for r in inter
                       if r.state == "completed")
        p99_inter = pick(ilats, 0.99)
    finally:
        close_fleet(pool, srv)

    recovery = slo / p99_tail if p99_tail else 0.0
    row = {
        "config": "autoscale_ab", "chips": 1, "batch_size": slots,
        "dtype": "float32", "platform": platform, "requests": n_req,
        "images_or_tokens_per_sec_per_chip": round(
            tokens / storm_dt if storm_dt else 0.0, 2),
        "slo_s": round(slo, 4),
        "p99_base_ms": round(p99_base * 1e3, 2),
        "p99_storm_tail_ms": round(p99_tail * 1e3, 2),
        "slo_recovery_x": round(recovery, 3),
        "replicas_start": 1, "replicas_end": replicas_end,
        "scale_up_events": up_events,
        "scale_up_span_on_timeline": on_timeline,
        "submitted": len(gen.submitted),
        "typed_rejected": gen.rejected,
        "completed": len(done), "lost_requests": lost,
        "qos_bulk_admitted": bulk_ok,
        "qos_bulk_refused_typed": bulk_refused,
        "p99_interactive_ms": round(p99_inter * 1e3, 2),
        "qos_isolation_x": round(p99_inter / p99_base, 3)
        if p99_base else None,
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return recovery, row


def bench_cold_warm(platform, dtype):
    """Cold-vs-warm start A/B (tuning/): the SAME canonical fused-step
    loop run in two fresh processes sharing one persistent compile cache
    + tune table. Process 1 is the cold path (every XLA compile is a
    cache miss, paid in-loop); process 2 AOT-warm-starts via
    ``step.aot_warmup`` and must show ~0 hot-loop compile time and ZERO
    hot-loop cache misses — the zero-JIT-resume acceptance, self-
    reported per bench round."""
    import shutil

    del dtype  # f32 — the A/B isolates compilation, not math
    _child_needs_no_chip(platform)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        raise _NotRun("needs a compile cache it may empty first; "
                      "JAX_COMPILATION_CACHE_DIR names the environment's own")
    # one fixed directory, emptied for the cold leg: the path is part of
    # the cache key, so both legs must see the same one
    tmp = os.path.join(_CACHE_DIR, "cold_warm")
    shutil.rmtree(tmp, ignore_errors=True)
    env = dict(os.environ)
    env.update({"MXT_COMPILE_CACHE_DIR": os.path.join(tmp, "xla"),
                "MXT_TUNE_TABLE": os.path.join(tmp, "tune.json"),
                "BENCH_CW_PLATFORM": platform})

    def run():
        r = subprocess.run([sys.executable, "-c", _COLD_WARM_CODE],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        for line in r.stdout.splitlines():
            if line.startswith("CWROW "):
                return json.loads(line[len("CWROW "):])
        raise RuntimeError("cold/warm subprocess produced no row: %s"
                           % (r.stderr or r.stdout)[-400:])

    cold = run()  # fresh cache: warmup + hot loop pay real XLA
    warm = run()  # same code, warm cache: must show ~0 compile time
    shutil.rmtree(tmp, ignore_errors=True)
    cold_total = cold["warmup_compile_ms"] + cold["hot_compile_ms"]
    warm_total = warm["warmup_compile_ms"] + warm["hot_compile_ms"]
    ratio = cold_total / warm_total if warm_total else 0.0
    row = {
        "config": "cold_vs_warm_start", "chips": 1, "batch_size": 32,
        "dtype": "float32", "platform": platform,
        "cold_compile_ms": round(cold_total, 1),
        "cold_warmup_ms": round(cold["warmup_ms"], 1),
        "cold_cache_misses": cold["cache_misses"],
        "warm_compile_ms": round(warm_total, 1),
        "warm_warmup_ms": round(warm["warmup_ms"], 1),
        "warm_hot_compile_ms": round(warm["hot_compile_ms"], 1),
        "warm_hot_cache_misses": warm["hot_cache_misses"],
        "warm_cache_misses": warm["cache_misses"],
        "warm_cache_hits": warm["cache_hits"],
        "cold_step_time_ms": round(cold["step_time_ms"], 3),
        "warm_step_time_ms": round(warm["step_time_ms"], 3),
        # the acceptance bit: a warm-started process's fused-step loop
        # performs zero real JIT compiles (cache misses) on the hot path
        "zero_jit_resume": warm["hot_cache_misses"] == 0,
        "images_or_tokens_per_sec_per_chip": round(
            32 * 1e3 / warm["step_time_ms"], 2) if warm["step_time_ms"]
        else 0.0,
        "mfu": None, "flops_per_sample": None,
        "cold_warm_compile_ratio": round(ratio, 2),
    }
    _emit_jsonl(row)
    return ratio, row


def _zero_stage_measure():
    """The zero_stage_ab measurement body: the SAME 3-layer MLP sharded
    step at ZeRO stages 0-3 on the CURRENT jax backend (the caller is
    responsible for putting it on an 8-device mesh — bench_zero_stages
    shells into a subprocess with a forced CPU mesh; the tier-1 smoke
    test, already on that mesh, calls this in-process)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import nn

    batch = int(os.environ.get("BENCH_ZERO_BATCH", "64"))
    hidden = int(os.environ.get("BENCH_ZERO_HIDDEN", "512"))
    iters = int(os.environ.get("BENCH_ZERO_ITERS", "10"))
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, 64)).astype(np.float32)
    y = rng.randint(0, 8, (batch,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    out = {"batch": batch, "hidden": hidden}
    losses = {}
    for stage in (0, 1, 2, 3):
        mx.random.seed(7)
        net = nn.HybridSequential(prefix="z%d_" % stage)
        with net.name_scope():
            net.add(nn.Dense(hidden, activation="relu", in_units=64),
                    nn.Dense(hidden, activation="relu", in_units=hidden),
                    nn.Dense(8, in_units=hidden))
        net.initialize()
        mesh = parallel.make_mesh(axis_names=("data",))
        step = parallel.ShardedTrainStep(net, loss_fn, "adam",
                                         {"learning_rate": 1e-3},
                                         mesh=mesh, zero_stage=stage)
        loss = step(nd.array(x), nd.array(y))
        loss.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(nd.array(x), nd.array(y))
        loss.wait_to_read()
        dt = (time.perf_counter() - t0) / iters
        b = step.per_device_bytes()
        out["z%d" % stage] = {
            "step_time_ms": round(dt * 1e3, 3),
            "opt_bytes_per_device": b["opt_state_bytes"],
            "param_bytes_per_device": b["param_bytes"]}
        losses["z%d" % stage] = round(float(loss.asscalar()), 7)
    out["losses"] = losses
    return out


_ZERO_STAGE_CODE = r'''
import json, os, sys
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["MXT_BENCH_DIR"])
import bench
print("ZROW " + json.dumps(bench._zero_stage_measure()))
'''


def bench_zero_stages(platform, dtype, _data=None):
    """ZeRO weight-update-sharding A/B (parallel/sharded.py, arXiv
    2004.13336): the SAME 3-layer MLP fused SPMD step on the 8-device
    CPU mesh at ZeRO stages 0/1/2/3. The contract: identical losses at
    every stage (layout, never math), per-device OPTIMIZER-STATE bytes
    shrink ~dp× from stage 1 on (reduce-scatter + sharded update from
    stage 2), and per-device PARAM bytes shrink ~dp× at stage 3
    (FSDP-style storage). Runs in a subprocess so the forced 8-device
    CPU mesh never disturbs the parent's backend."""
    del dtype  # f32 — the A/B isolates memory/layout, not math throughput
    data = _data  # tests (already on the 8-dev mesh) measure in-process
    if data is None:
        _child_needs_no_chip(platform)
        env = dict(os.environ)
        env["MXT_BENCH_DIR"] = os.path.dirname(os.path.abspath(__file__))
        r = subprocess.run([sys.executable, "-c", _ZERO_STAGE_CODE],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        for line in r.stdout.splitlines():
            if line.startswith("ZROW "):
                data = json.loads(line[len("ZROW "):])
        if data is None:
            raise RuntimeError("zero-stage subprocess produced no row: %s"
                               % (r.stderr or r.stdout)[-400:])
    shrink_opt = data["z0"]["opt_bytes_per_device"] / max(
        1, data["z2"]["opt_bytes_per_device"])
    shrink_par = data["z0"]["param_bytes_per_device"] / max(
        1, data["z3"]["param_bytes_per_device"])
    row = {
        "config": "zero_stage_ab", "chips": 8,
        "batch_size": data["batch"], "dtype": "float32",
        "platform": "cpu",  # always the virtual CPU mesh (subprocess)
        "stages": {k: data[k] for k in ("z0", "z1", "z2", "z3")},
        "losses_equal": len(set(data["losses"].values())) == 1,
        "opt_bytes_shrink_z2": round(shrink_opt, 2),
        "param_bytes_shrink_z3": round(shrink_par, 2),
        "step_time_ms": data["z2"]["step_time_ms"],
        "images_or_tokens_per_sec_per_chip": round(
            data["batch"] * 1e3 / data["z2"]["step_time_ms"] / 8, 2)
        if data["z2"]["step_time_ms"] else 0.0,
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return shrink_opt, row


def _parallel_4d_measure():
    """The parallel_4d_ab measurement body: the SAME pp=2/ep=2 toy LM
    stepped two ways on ONE (2,1,2,2) dp×tp×pp×ep mesh — the island
    composition (one value_and_grad launch plus one eager fused-optimizer
    launch per parameter: the pre-unification dispatch shape) vs the
    unified ShardedTrainStep (the whole schedule + MoE + loss + update
    as its single donated jit). Both legs run exactly
    ``pipeline_moe_forward`` and the same loss/update op math from the
    same placed initial params, so the loss series must match
    bit-for-bit: the A/B isolates launch structure, never math. (The
    genuinely different island programs — shard_map pipeline_apply +
    moe_apply on their own sub-meshes — can't be bit-compared, which is
    why the baseline here is the same math split into launches.)"""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel, profiler
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.parallel import unified as _u

    batch = int(os.environ.get("BENCH_4D_BATCH", "16"))
    hidden = int(os.environ.get("BENCH_4D_HIDDEN", "16"))
    iters = int(os.environ.get("BENCH_4D_ITERS", "20"))
    stages, experts, micro, cf, lr = 2, 2, 4, 1.25, 0.05
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, hidden)).astype(np.float32)
    y = rng.randint(0, 8, (batch,)).astype(np.float32)

    mx.random.seed(7)
    mesh = parallel.make_mesh((2, 1, 2, 2), ("dp", "tp", "pp", "ep"))
    net = parallel.PipelineMoEBlock(
        num_stages=stages, num_experts=experts, in_units=hidden,
        hidden=hidden, expert_hidden=2 * hidden, num_classes=8,
        num_microbatches=micro, capacity_factor=cf)
    net.initialize()
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": lr}, mesh=mesh, rules=net.sharding_rules(mesh),
        zero_stage=1)
    vals0 = net.param_values()  # placed initial params, pre-first-step

    # --- island leg: same math, pre-unification launch structure ------
    def island_loss(vals, xb, yb):
        logits, _, _ = _u.pipeline_moe_forward(
            vals, xb, micro, cf, mesh=mesh, dp="dp", pp="pp", ep="ep")
        # gluon/loss.py SoftmaxCrossEntropyLoss math, op for op
        pred = jax.nn.log_softmax(logits, axis=-1)
        idx = jnp.clip(yb.astype(jnp.int32), 0, logits.shape[-1] - 1)
        lp = jnp.take_along_axis(pred, idx[:, None], axis=-1)
        return jnp.mean(jnp.mean(-lp, axis=1))

    grad_fn = jax.jit(jax.value_and_grad(island_loss))
    sgd = get_op("sgd_update").fn
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    ys = jax.device_put(y, NamedSharding(mesh, P("dp")))

    def island_step(vals):
        loss, grads = grad_fn(vals, xs, ys)  # launch 1: fwd+bwd
        # one eager fused-optimizer launch PER parameter — the island tax
        return loss, {k: sgd(vals[k], grads[k], lr=lr) for k in vals}

    vals = dict(vals0)
    island_losses = []
    l, vals = island_step(vals)  # compile lap (lands in the series too)
    island_losses.append(l)
    island_ms, island_syncs = float("inf"), 0
    for _ in range(3):  # best-of-3 windows: the 8-thread CPU rendezvous
        h0 = profiler.host_sync_count()  # is jittery per window
        t0 = time.perf_counter()
        for _ in range(iters):
            l, vals = island_step(vals)
            island_losses.append(l)
        island_syncs = max(island_syncs, profiler.host_sync_count() - h0)
        l.block_until_ready()
        island_ms = min(island_ms, (time.perf_counter() - t0) / iters * 1e3)
    island_launches = 1 + len(vals0)

    # --- unified leg: ONE donated jit (island leg never mutated net).
    # Inputs convert ONCE, like the island leg's device_put above — the
    # A/B measures launch structure, not host->device feeding.
    xa, ya = nd.array(x), nd.array(y)
    unified_losses = [step(xa, ya)]
    unified_ms, unified_syncs = float("inf"), 0
    n0 = profiler.launch_count()
    for _ in range(3):
        h0 = profiler.host_sync_count()
        t0 = time.perf_counter()
        for _ in range(iters):
            unified_losses.append(step(xa, ya))
        unified_syncs = max(unified_syncs,
                            profiler.host_sync_count() - h0)
        unified_losses[-1].wait_to_read()
        unified_ms = min(unified_ms,
                         (time.perf_counter() - t0) / iters * 1e3)
    unified_launches = (profiler.launch_count() - n0) // (3 * iters)

    il = [float(v) for v in island_losses]  # sync-ok: post-loop reads
    ul = [float(v.asscalar()) for v in unified_losses]  # sync-ok: post-loop
    moe = parallel.publish_moe_telemetry(net)
    pdb = step.per_device_bytes()
    return {
        "batch": batch, "hidden": hidden, "iters": iters,
        "mesh": {"dp": 2, "tp": 1, "pp": 2, "ep": 2},
        "island_step_time_ms": round(island_ms, 3),
        "unified_step_time_ms": round(unified_ms, 3),
        "island_launches_per_step": island_launches,
        "unified_launches_per_step": int(unified_launches),
        "island_hot_loop_syncs": int(island_syncs),
        "unified_hot_loop_syncs": int(unified_syncs),
        "losses_island": [round(v, 7) for v in il],
        "losses_unified": [round(v, 7) for v in ul],
        "losses_equal": il == ul,  # bit-exact, not tolerance
        "param_bytes_per_device": pdb["param_bytes"],
        "opt_bytes_per_device": pdb["opt_state_bytes"],
        "moe_expert_load": moe["expert_load"],
        "moe_router_drops": moe["drops"],
    }


_PARALLEL_4D_CODE = r'''
import json, os, sys
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["MXT_BENCH_DIR"])
import bench
print("P4DROW " + json.dumps(bench._parallel_4d_measure()))
'''


def bench_parallel_4d(platform, dtype, _data=None):
    """Unified 4D parallelism A/B (parallel/unified.py): pipeline + MoE
    as shardings inside the one-launch sharded step vs the same math
    stepped as launch islands, on the 8-device CPU mesh. The contract:
    bit-identical loss series (layout and launch structure, never math),
    ``launches_per_step == 1`` for the unified leg, sync parity on the
    hot loop (zero host syncs both legs), and the unified leg at least
    matching the island composition's step time. Runs in a subprocess
    so the forced 8-device CPU mesh never disturbs the parent backend."""
    del dtype  # f32 — the A/B isolates launch structure, not math
    data = _data  # tests (already on the 8-dev mesh) measure in-process
    if data is None:
        _child_needs_no_chip(platform)
        env = dict(os.environ)
        env["MXT_BENCH_DIR"] = os.path.dirname(os.path.abspath(__file__))
        r = subprocess.run([sys.executable, "-c", _PARALLEL_4D_CODE],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        for line in r.stdout.splitlines():
            if line.startswith("P4DROW "):
                data = json.loads(line[len("P4DROW "):])
        if data is None:
            raise RuntimeError("parallel_4d subprocess produced no row: %s"
                               % (r.stderr or r.stdout)[-400:])
    speedup = (data["island_step_time_ms"] / data["unified_step_time_ms"]
               if data["unified_step_time_ms"] else 0.0)
    row = {
        "config": "parallel_4d_ab", "chips": 8,
        "batch_size": data["batch"], "dtype": "float32",
        "platform": "cpu",  # always the virtual CPU mesh (subprocess)
        "mesh": data["mesh"],
        "island_step_time_ms": data["island_step_time_ms"],
        "unified_step_time_ms": data["unified_step_time_ms"],
        "step_time_ms": data["unified_step_time_ms"],
        "launches_per_step": data["unified_launches_per_step"],
        "island_launches_per_step": data["island_launches_per_step"],
        "losses_equal": data["losses_equal"],
        "sync_parity": (data["island_hot_loop_syncs"]
                        == data["unified_hot_loop_syncs"]),
        "param_bytes_per_device": data["param_bytes_per_device"],
        "opt_bytes_per_device": data["opt_bytes_per_device"],
        "moe_expert_load": data["moe_expert_load"],
        "moe_router_drops": data["moe_router_drops"],
        "unified_speedup": round(speedup, 2),
        "images_or_tokens_per_sec_per_chip": round(
            data["batch"] * 1e3 / data["unified_step_time_ms"] / 8, 2)
        if data["unified_step_time_ms"] else 0.0,
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    return speedup, row


def bench_serving(platform, dtype):
    """Serving stack (mxnet_tpu/serving/): mixed-length synthetic
    traffic through the paged-KV decode engine, once under the
    continuous batcher (recompose every step) and once under the
    static batcher (admission only at batch boundaries). Emits two
    rows: `serving_decode` (continuous-mode tokens/s, request p50/p99,
    KV-page occupancy) and the `serving_continuous_vs_static_ab` proof
    row. Useful tokens only — idle static slots earn nothing, which is
    exactly the measured difference."""
    import numpy as np

    from mxnet_tpu import profiler, serving

    del dtype  # f32: the A/B isolates scheduling, not math throughput
    slots = int(os.environ.get("BENCH_SERVING_SLOTS", "8"))
    n_req = int(os.environ.get("BENCH_SERVING_REQUESTS", "24"))
    layers, heads, hdim = 2, 4, 16
    model = serving.TinyDecoder(vocab=512, num_layers=layers,
                                num_heads=heads, head_dim=hdim,
                                max_len=512)
    params = model.init_params(0)

    def make_requests():
        rng = np.random.RandomState(7)
        out = []
        for _ in range(n_req):
            plen = int(rng.randint(4, 97))
            mnew = int(rng.randint(4, 49))
            out.append(serving.Request(
                rng.randint(1, 512, plen).tolist(),
                max_new_tokens=mnew))
        return out

    def run(batcher_cls):
        cache = serving.PagedKVCache(layers, heads, hdim, num_pages=512,
                                     page_size=16)
        eng = serving.DecodeEngine(model, params=params, slots=slots,
                                   cache=cache, prefill_buckets=(64, 128),
                                   max_context=256)
        eng.aot_warmup()
        # warm lap: absorb eager-glue compiles so the timed lap measures
        # scheduling, not JIT
        warm = batcher_cls(eng)
        warm.submit(serving.Request([1, 2, 3], max_new_tokens=4))
        warm.run()
        sched = batcher_cls(eng)
        for r in make_requests():
            sched.submit(r)
        peak_pages = 0
        h0 = profiler.host_sync_count()
        t0 = time.perf_counter()
        while (sched._queue or sched._slot_req) and sched.steps < 20000:
            sched.step()
            peak_pages = max(peak_pages, cache.pages_in_use())
        sched.drain()
        dt = time.perf_counter() - t0
        syncs = profiler.host_sync_count() - h0
        done = [r for r in sched.completed if r.state == "completed"]
        tokens = sum(len(r.output_tokens) for r in done)
        lats = sorted(r.t_finish - r.t_submit for r in done
                      if r.t_finish is not None)
        pick = lambda q: lats[min(len(lats) - 1, int(q * len(lats)))] \
            if lats else 0.0
        return {
            "tokens_per_sec": tokens / dt if dt else 0.0,
            "completed": len(done), "steps": sched.steps,
            "p50_ms": pick(0.50) * 1e3, "p99_ms": pick(0.99) * 1e3,
            "peak_kv_pages": peak_pages,
            "host_syncs_per_step": syncs / max(1, sched.steps),
        }

    cont = run(serving.ContinuousBatcher)
    stat = run(serving.StaticBatcher)
    speedup = cont["tokens_per_sec"] / stat["tokens_per_sec"] \
        if stat["tokens_per_sec"] else 0.0

    row = {
        "config": "serving_decode", "chips": 1, "batch_size": slots,
        "dtype": "float32", "platform": platform,
        "requests": n_req,
        "images_or_tokens_per_sec_per_chip": round(
            cont["tokens_per_sec"], 2),
        "request_p50_ms": round(cont["p50_ms"], 2),
        "request_p99_ms": round(cont["p99_ms"], 2),
        "peak_kv_pages": cont["peak_kv_pages"],
        "host_syncs_per_step": round(cont["host_syncs_per_step"], 3),
        "decode_steps": cont["steps"],
        "mfu": None, "flops_per_sample": None,
    }
    _emit_jsonl(row)
    row_ab = {
        "config": "serving_continuous_vs_static_ab", "chips": 1,
        "batch_size": slots, "dtype": "float32", "platform": platform,
        "requests": n_req,
        "continuous_tokens_per_sec": round(cont["tokens_per_sec"], 2),
        "static_tokens_per_sec": round(stat["tokens_per_sec"], 2),
        "continuous_steps": cont["steps"],
        "static_steps": stat["steps"],
        "images_or_tokens_per_sec_per_chip": round(
            cont["tokens_per_sec"], 2),
        "mfu": None, "flops_per_sample": None,
        "continuous_speedup": round(speedup, 3),
    }
    _emit_jsonl(row_ab)
    return speedup, row_ab


def main():
    platform = _init_backend()
    import jax

    from mxnet_tpu import tuning

    tuning.setup_compile_cache(_CACHE_DIR)
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    configs = os.environ.get(
        "BENCH_CONFIGS",
        "resnet50,bert,lstm_ptb,wide_deep,lenet,pipeline,async_ab,"
        "telemetry_ab,diag_ab,cold_warm,serving,zero_stage,parallel_4d,"
        "embedding_ab,serving_fleet,speculative,kv_quant,fleet_obs,"
        "streaming_input,prefix_reuse,autoscale,training_health"
    ).split(",")

    # headline priority: resnet50 (the SURVEY §6 headline) > bert > rest
    metric_info = {
        "resnet50": ("resnet50_train_throughput", "images/sec/chip",
                     bench_resnet50),
        "bert": ("bert_base_mlm_throughput", "tokens/sec/chip",
                 bench_bert_mlm),
        "lstm_ptb": ("lstm_ptb_train_throughput", "tokens/sec/chip",
                     bench_lstm_ptb),
        "wide_deep": ("wide_deep_train_throughput", "samples/sec/chip",
                      bench_wide_deep),
        "lenet": ("lenet_mnist_train_throughput", "images/sec/chip",
                  bench_lenet_mnist),
        "pipeline": ("input_pipeline_throughput", "images/sec/host",
                     bench_input_pipeline),
        "async_ab": ("async_dispatch_speedup", "x (sync/async step time)",
                     bench_async_ab),
        "telemetry_ab": ("telemetry_overhead", "x (on/off step time)",
                         bench_telemetry_ab),
        "diag_ab": ("diagnostics_overhead", "x (on/off step time)",
                    bench_diagnostics_ab),
        "cold_warm": ("cold_warm_compile_ratio",
                      "x (cold/warm compile time)", bench_cold_warm),
        "serving": ("serving_continuous_vs_static",
                    "x (continuous/static tokens/s)", bench_serving),
        "zero_stage": ("zero_opt_bytes_shrink",
                       "x (replicated/ZeRO-2 opt bytes per device)",
                       bench_zero_stages),
        "parallel_4d": ("parallel_4d_unified_speedup",
                        "x (island/unified 4D step time, bit-exact)",
                        bench_parallel_4d),
        "embedding_ab": ("embedding_server_scaling",
                         "x (2srv/1srv embedding bytes/sec)",
                         bench_embedding_ab),
        "serving_fleet": ("serving_fleet_scaling",
                          "x (2rep/1rep fleet tokens/s)",
                          bench_serving_fleet),
        "speculative": ("speculative_decode_speedup",
                        "x (speculative/plain tokens/s, token-exact)",
                        bench_speculative),
        "kv_quant": ("kv_quant_resident_ratio",
                     "x (int8/f32 resident sequences at equal bytes)",
                     bench_kv_quant),
        "fleet_obs": ("fleet_observability_overhead",
                      "x (collector-on/off fleet tokens/s)",
                      bench_fleet_observability),
        "streaming_input": ("streaming_input_speedup",
                            "x (data plane/per-process DataLoader img/s)",
                            bench_streaming_input),
        "prefix_reuse": ("prefix_reuse_speedup",
                         "x (reuse-on/off tokens/s, token-exact)",
                         bench_prefix_reuse),
        "autoscale": ("autoscale_slo_recovery",
                      "x (SLO / post-scale p99 — >=1 means recovered)",
                      bench_autoscale),
        "training_health": ("training_health_overhead",
                            "x (on/off step time, syncs bit-equal)",
                            bench_training_health_ab),
    }
    headline = None
    errors = []
    skipped = []
    not_run = []
    best_resnet = None
    for name in ("resnet50", "bert", "lstm_ptb", "wide_deep", "lenet",
                 "pipeline", "async_ab", "telemetry_ab", "diag_ab",
                 "cold_warm", "serving", "zero_stage", "parallel_4d",
                 "embedding_ab", "serving_fleet", "speculative",
                 "kv_quant", "fleet_obs", "streaming_input",
                 "prefix_reuse", "autoscale", "training_health"):
        if name not in configs:
            continue
        cost = float(os.environ.get("BENCH_COST_%s" % name.upper(),
                                    _CONFIG_COST[name]))
        if _remaining() < cost:
            skipped.append(name)
            print("bench: skipping %s — %.0fs left < %.0fs estimate "
                  "(BENCH_BUDGET=%s)" % (name, _remaining(), cost, _BUDGET),
                  file=sys.stderr, flush=True)
            continue
        metric, unit, fn = metric_info[name]
        try:
            val, row = fn(platform, dtype)
            if name == "resnet50":
                best_resnet = (val, row)
            if headline is None:
                headline = {
                    "metric": metric,
                    "value": round(val, 2),
                    "unit": unit,
                    # only resnet50 has a (stand-in) published baseline
                    "vs_baseline": round(val / BASELINE_IMG_S, 3)
                    if name == "resnet50" else 0.0,
                    "mfu": row["mfu"],
                    "platform": platform,
                    "device_kind": jax.devices()[0].device_kind,
                    "device_count": len(jax.devices()),
                }
        except _NotRun as e:
            not_run.append("%s (%s)" % (name, e))
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            errors.append("%s: %r" % (name, e))

    # perf-round lever sweep on TRULY leftover budget (after every
    # standard config had its chance): batch/remat resnet variants, with
    # the headline updated to the BEST resnet row (the official number
    # should reflect the best landed configuration)
    if platform == "tpu" and best_resnet is not None:
        variants = os.environ.get("BENCH_RESNET_VARIANTS", "256:,256:full")
        base_cost = float(os.environ.get("BENCH_COST_RESNET50",
                                         _CONFIG_COST["resnet50"]))
        for spec in [s for s in variants.split(",") if s]:
            vb, _, vr = spec.partition(":")
            # per-step work scales with batch; same iters -> same scaling
            cost = base_cost * max(1.0, int(vb) / 64.0) + 30
            if _remaining() < cost:
                skipped.append("resnet50@%s" % spec)
                continue
            try:
                v2, row2 = bench_resnet50(platform, dtype, batch=int(vb),
                                          remat=vr or "none")
                if v2 > best_resnet[0]:
                    best_resnet = (v2, row2)
            except Exception as e:  # noqa: BLE001
                errors.append("resnet50@%s: %r" % (spec, e))
        if headline is not None and \
                headline["metric"] == "resnet50_train_throughput":
            val, row = best_resnet
            headline["value"] = round(val, 2)
            headline["vs_baseline"] = round(val / BASELINE_IMG_S, 3)
            headline["mfu"] = row["mfu"]

    if headline is None:
        first = next((c for c in ("resnet50", "bert", "lstm_ptb",
                                  "wide_deep", "lenet") if c in configs),
                     "resnet50")
        metric, unit, _ = metric_info[first]
        headline = {"metric": metric, "value": 0.0,
                    "unit": unit, "vs_baseline": 0.0,
                    "platform": platform,
                    "error": "; ".join(errors)[-800:]}
    elif errors:
        headline["partial_errors"] = "; ".join(errors)[-400:]
    if skipped:
        headline["skipped_configs"] = ",".join(skipped)
    if not_run:
        headline["not_run"] = "; ".join(not_run)
    print(json.dumps(headline))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
