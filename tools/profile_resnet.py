"""Capture a profiler trace of the ResNet-50 train step on the real chip
(run it there through the chip tool; only the process that holds the
chip can trace it).

Usage: python tools/profile_resnet.py [--batch 64] [--steps 8]
                                      [--out profiles/resnet50]
Writes a Perfetto trace directory via mx.profiler (jax.profiler
underneath) and prints its path.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--out", default="profiles/resnet50")
    p.add_argument("--platform", default=None,
                   help="force a platform (e.g. cpu for a smoke run)")
    args = p.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel, profiler
    from mxnet_tpu.gluon import model_zoo, nn

    mx.random.seed(0)
    with nn.layout_scope("NHWC"):
        net = model_zoo.get_model("resnet50_v1", classes=1000)
    net.initialize()
    net.cast("bfloat16")
    x = nd.zeros((args.batch, 224, 224, 3), dtype="bfloat16")
    net(x)

    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9})

    rng = np.random.RandomState(0)
    xb = nd.array(rng.uniform(-1, 1, x.shape).astype(np.float32))
    xb = xb.astype("bfloat16")
    yb = nd.array(rng.randint(0, 1000, (args.batch,)).astype(np.float32))

    # warm up (compile) OUTSIDE the trace, syncing eagerly
    for _ in range(2):
        step(xb, yb).wait_to_read()

    profiler.set_config(filename=args.out, profile_all=True)
    profiler.start()
    # sync EVERY step: each step's device timeline stands alone in the
    # trace, which is what the backward analysis needs (the gaps between
    # steps are the host's read, not the device's).
    for _ in range(args.steps):
        step(xb, yb).wait_to_read()
    trace_dir = profiler.dump()
    print("trace:", trace_dir)


if __name__ == "__main__":
    main()
