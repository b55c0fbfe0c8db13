"""LeNet-5 on MNIST via Gluon (ref: example/image-classification/
train_mnist.py + gluon examples). Uses the real MNIST files if
--data-dir has them, else synthetic digits so the example always runs.

Run:  python examples/train_mnist_gluon.py --epochs 2 --batch-size 256

Demonstrates the fused train step (gluon.CachedTrainStep). Before —
one launch for the forward, one per tape node for the backward, one for
the optimizer::

    with autograd.record():
        out = net(data)
        loss = loss_fn(out, label)
    loss.backward()
    trainer.step(batch_size)

After — the WHOLE step is one donated XLA launch (identical numerics;
ineligible configs fall back to the loop above automatically)::

    step = trainer.fuse_step(net, loss_fn, return_outputs=True)
    loss, out = step(data, label, batch_size)

Pass --no-fused-step (or set MXT_FUSED_STEP=0) to run the eager loop.

Async dispatch (engine.py): the fused step never blocks on a host read
— the engine keeps up to K steps in flight and defers flag/bookkeeping
reads (bit-exact numerics; metrics accumulate on device)::

    with mx.engine.bulk(8):          # or MXT_MAX_INFLIGHT=8
        for data, label in batches:
            loss, out = step(data, label, batch_size)
            metric.update([label], [out])   # device-side running sums
    mx.nd.waitall()                  # barrier: land deferred counters
    print(metric.get())              # the ONE host read

Pass --inflight K to set the window here (0 keeps the MXT_MAX_INFLIGHT
default of 2; 1 forces synchronous per-step reads).

Telemetry (telemetry.py): --telemetry turns on the JSONL event sink and
the Prometheus endpoint, then prints how to watch the run live::

    python examples/train_mnist_gluon.py --telemetry &
    python tools/mxt_top.py --url http://127.0.0.1:9109   # live console
    # or, offline: python tools/mxt_top.py --jsonl mnist_telemetry.jsonl

The console shows steps/s, host_syncs/step (≤ 1/K when the async window
is healthy), launches/step (1.0 = fully fused), dispatch depth, and the
skipped-step counter — all without adding a single host sync to the
training loop.

Warm start (tuning/): --warmup AOT-compiles the fused step before the
first batch; with the persistent compile cache a SECOND run pays zero
JIT anywhere in the epoch loop::

    MXT_COMPILE_CACHE_DIR=$PWD/.jax_cache python examples/train_mnist_gluon.py --warmup
    MXT_COMPILE_CACHE_DIR=$PWD/.jax_cache python examples/train_mnist_gluon.py --warmup
    # second run prints: warmup: N compiles (~0.0s XLA, cache N hit / 0 miss)

(one fixed directory — the path is part of the cache key; where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used instead and
``MXT_COMPILE_CACHE_DIR`` is ignored)
"""
import argparse

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import nn


def lenet(pad_to=None):
    """Classic widths by default; ``pad_to=dp`` rounds each layer width
    up to a multiple of dp so ZeRO's dim-0 sharding applies to every
    tensor (the classic 20/50/500 widths don't divide an 8-way data
    axis, which would silently leave everything replicated)."""
    def w(units):
        if not pad_to or pad_to <= 1:
            return units
        return ((units + pad_to - 1) // pad_to) * pad_to

    net = nn.HybridSequential()
    net.add(nn.Conv2D(w(20), kernel_size=5, activation="tanh"),
            nn.MaxPool2D(2, 2),
            nn.Conv2D(w(50), kernel_size=5, activation="tanh"),
            nn.MaxPool2D(2, 2),
            nn.Flatten(),
            nn.Dense(w(500), activation="tanh"),
            nn.Dense(10))
    return net


def load_data(args):
    try:
        from mxnet_tpu.gluon.data.vision import MNIST

        train = MNIST(root=args.data_dir, train=True)
        x = np.stack([np.asarray(im) for im, _ in train]).astype("f4")
        y = np.asarray([lbl for _, lbl in train]).astype("f4")
        x = x.reshape(-1, 1, 28, 28) / 255.0
        return x, y
    except Exception:
        print("MNIST files not found — using synthetic digits")
        # LEARNABLE synthetic task (not random labels): 10 smooth
        # prototypes + noise, so the printed accuracy is a real
        # convergence signal (mirrors tests/test_tpu_smoke.py's
        # train-tier bar)
        rng = np.random.RandomState(0)
        protos = np.repeat(np.repeat(rng.rand(10, 1, 7, 7), 4, axis=2),
                           4, axis=3).astype("f4")
        y = rng.randint(0, 10, (4096,))
        x = (protos[y] + rng.normal(0, 0.35, (4096, 1, 28, 28))
             ).astype("f4")
        return x, y.astype("f4")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--data-dir", default="data/mnist")
    p.add_argument("--no-hybridize", dest="hybridize",
                   action="store_false", default=True,
                   help="run the eager (non-jitted) path")
    p.add_argument("--no-fused-step", dest="fused_step",
                   action="store_false", default=True,
                   help="use the eager record/backward/step loop instead "
                        "of the one-launch fused train step")
    p.add_argument("--inflight", type=int, default=0,
                   help="async dispatch window depth K (engine.bulk): the "
                        "host runs up to K fused steps ahead, deferring "
                        "host reads; 0 = MXT_MAX_INFLIGHT default, "
                        "1 = synchronous")
    p.add_argument("--telemetry", action="store_true",
                   help="write telemetry JSONL (mnist_telemetry.jsonl), "
                        "serve Prometheus metrics on 127.0.0.1:9109, and "
                        "print the tools/mxt_top.py invocation to watch "
                        "the run live")
    p.add_argument("--health", action="store_true",
                   help="arm the training-health plane (health.py): "
                        "per-layer grad/param norms + update ratios + "
                        "loss stats computed INSIDE the fused step, "
                        "anomaly detectors at window retirement, and "
                        "the default SLO rules — zero extra host "
                        "syncs per step")
    p.add_argument("--warmup", action="store_true",
                   help="AOT-compile the fused step before the first "
                        "batch (tuning.warmup). With MXT_COMPILE_CACHE_DIR "
                        "set, a second run replays every compile from the "
                        "persistent cache — zero JIT in the epoch loop")
    p.add_argument("--sharded", action="store_true",
                   help="train under parallel.ShardedTrainStep on a "
                        "device mesh (GSPMD data parallel; honors "
                        "MXT_MESH_SHAPE from tools/launch.py --mesh). "
                        "The batch size must divide the data axis")
    p.add_argument("--zero-stage", type=int, default=None,
                   choices=(0, 1, 2, 3),
                   help="with --sharded: ZeRO weight-update sharding "
                        "stage (1 shards optimizer states over the data "
                        "axis, 2 adds gradient reduce-scatter + sharded "
                        "updates, 3 shards the params FSDP-style); "
                        "default MXT_ZERO_STAGE or 0")
    p.add_argument("--watchdog", type=float, nargs="?", const=30.0,
                   default=None, metavar="SECONDS",
                   help="arm the diagnostics layer (flight recorder + "
                        "post-mortem handlers) with a hang watchdog: no "
                        "training progress for SECONDS (default 30) "
                        "dumps thread stacks + the flight-recorder tail "
                        "to an mxt-postmortem-*.json; "
                        "MXT_WATCHDOG_ACTION=abort turns a hang into a "
                        "typed, respawnable death")
    args = p.parse_args()

    if args.watchdog is not None:
        from mxnet_tpu import diagnostics

        diagnostics.enable(timeout=args.watchdog)
        print("watchdog: armed (%.0fs, action=%s); post-mortems -> %s"
              % (args.watchdog, mx.config.get("MXT_WATCHDOG_ACTION"),
                 mx.config.get("MXT_POSTMORTEM_DIR")))

    if args.telemetry:
        os.environ.setdefault("MXT_TELEMETRY_JSONL",
                              "mnist_telemetry.jsonl")
        from mxnet_tpu import telemetry

        srv = telemetry.start_http_server(
            int(os.environ.get("MXT_TELEMETRY_PORT", "9109")))
        print("telemetry: JSONL -> %s ; live console:\n"
              "  python tools/mxt_top.py --url http://127.0.0.1:%d"
              % (os.environ["MXT_TELEMETRY_JSONL"],
                 srv.server_address[1]))

    if args.health:
        # must be set BEFORE fuse_step builds: the stat row compiles
        # into the one donated step program (MXT_HEALTH=1 equivalent)
        os.environ["MXT_HEALTH"] = "1"
        from mxnet_tpu import health

        health.default_engine()  # seeds the standing rule set
        print("health: armed — per-layer stats ride the inflight "
              "window; curl /health on the telemetry port for the "
              "rules verdict")

    mx.random.seed(42)
    if args.sharded:
        import jax

        net = lenet(pad_to=len(jax.devices()))
    else:
        net = lenet()
    net.initialize(init=mx.init.Xavier())
    if args.hybridize and not args.sharded:
        net.hybridize()  # whole net -> one XLA program

    x, y = load_data(args)
    train_iter = mx.io.NDArrayIter(x, y, args.batch_size, shuffle=True)

    if args.sharded:
        # GSPMD scale-out: ONE sharded program over the mesh — the same
        # script runs 1 CPU device, the 8-device test mesh, or an
        # N-host pod (tools/launch.py --mesh 16,2 --zero-stage 2 sets
        # MXT_MESH_SHAPE/MXT_ZERO_STAGE; make_mesh() reads them)
        from mxnet_tpu import parallel

        net(nd.zeros((2, 1, 28, 28)))  # resolve deferred shapes
        mesh = parallel.make_mesh() if os.environ.get("MXT_MESH_SHAPE") \
            else parallel.make_mesh(axis_names=("data",))
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
        sstep = parallel.ShardedTrainStep(
            net, loss_fn, "sgd",
            {"learning_rate": args.lr, "momentum": 0.9}, mesh=mesh,
            zero_stage=args.zero_stage)
        b = sstep.per_device_bytes()
        print("sharded: mesh %s, ZeRO stage %d, per-device bytes "
              "params=%d opt=%d" % (dict(mesh.shape), sstep.zero_stage,
                                    b["param_bytes"],
                                    b["opt_state_bytes"]))
        for epoch in range(args.epochs):
            train_iter.reset()
            losses = []
            for batch in train_iter:
                loss = sstep(batch.data[0], batch.label[0])
                losses.append(loss)
            nd.waitall()
            print("epoch %d: mean loss %.4f"
                  % (epoch, float(np.mean([float(l.asscalar())
                                           for l in losses]))))
        if args.health:
            from mxnet_tpu import health

            hp = health.render_health()
            print("health: %s — loss ema %s, %d anomaly kind(s)"
                  % (hp["status"], hp.get("loss_ema"),
                     len(hp.get("anomalies") or {})))
        return

    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": args.lr, "momentum": 0.9})
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()
    speedo = mx.callback.Speedometer(args.batch_size, frequent=20)

    # forward + backward + optimizer as ONE donated XLA launch; outputs
    # ride along as extra results of the same program so the metric needs
    # no second forward
    step = trainer.fuse_step(net, loss_fn, return_outputs=True) \
        if args.fused_step else None

    if args.warmup and step is not None:
        # AOT warm-start (tuning/warmup.py): compile the whole fused
        # step from the batch signature before touching any data. With
        # MXT_COMPILE_CACHE_DIR set, run this script twice — the second
        # run's summary shows cache hits and ~0 compile seconds
        from mxnet_tpu import tuning

        x_sig = nd.zeros((args.batch_size, 1, 28, 28))
        y_sig = nd.zeros((args.batch_size,))
        step.aot_warmup(x_sig, y_sig)
        summary = tuning.warmup()
        print("warmup: %d compiles (%.2fs XLA, cache %d hit / %d miss)"
              % (summary["compiles"], summary["compile_seconds"],
                 summary["cache_hits"], summary["cache_misses"]))

    import contextlib

    # async dispatch: inside engine.bulk(K) the fused step defers its
    # host reads and Accuracy accumulates on device — the loop below
    # performs NO per-batch device->host round-trip
    window = mx.engine.bulk(args.inflight) if args.inflight \
        else contextlib.nullcontext()
    with window:
        for epoch in range(args.epochs):
            train_iter.reset()
            metric.reset()
            for i, batch in enumerate(train_iter):
                data, label = batch.data[0], batch.label[0]
                if step is not None:
                    loss, out = step(data, label, args.batch_size)
                else:
                    with autograd.record():
                        out = net(data)
                        loss = loss_fn(out, label)
                    loss.backward()
                    trainer.step(args.batch_size)
                metric.update([label], [out])
                speedo(mx.model.BatchEndParam(epoch=epoch, nbatch=i,
                                              eval_metric=metric,
                                              locals=None))
            nd.waitall()  # barrier: land deferred flags/counters
            print("epoch %d: train acc %.4f" % (epoch, metric.get()[1]))

    if args.health:
        from mxnet_tpu import health

        hp = health.render_health()
        print("health: %s — loss ema %s, %d anomaly kind(s), "
              "%d rule(s) evaluated"
              % (hp["status"], hp.get("loss_ema"),
                 len(hp.get("anomalies") or ()),
                 len(hp.get("rules") or ())))


if __name__ == "__main__":
    main()
