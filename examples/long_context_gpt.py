"""Long-context GPT with sequence parallelism — zero model changes.

The flagship long-context flow: the stock model-zoo GPT runs with its
attention sequence-sharded over a mesh via `parallel.sequence_scope` —
each device holds T/n of the sequence and KV blocks rotate around the
ring (ICI neighbor traffic on real TPU hardware; virtual CPU devices
here). Memory per device for attention state drops O(T) -> O(T/n).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python examples/long_context_gpt.py --devices 8 --seq-len 1024
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--devices", type=int, default=8,
                   help="sequence shards (virtual CPU devices here)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--batch-size", type=int, default=2)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--tpu", action="store_true",
                   help="run on the attached TPU devices (default: a "
                        "mesh of virtual CPU devices)")
    args = p.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % args.devices).strip()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, parallel
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_mini

    assert args.seq_len % args.devices == 0, \
        "seq-len must divide by the shard count"

    mx.random.seed(0)
    net = gpt_mini(dropout=0.0, max_length=args.seq_len)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-4})
    loss_fn = gluon.loss.SoftmaxCELoss()

    mesh = parallel.make_mesh(
        (args.devices,), ("sp",),
        devices=jax.devices()[:args.devices])

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randint(
        0, 1000, (args.batch_size, args.seq_len)).astype(np.float32))
    y = mx.nd.array(np.roll(x.asnumpy(), -1, axis=1))

    print("T=%d over %d sequence shards (T/n = %d per device)"
          % (args.seq_len, args.devices,
             args.seq_len // args.devices))
    with parallel.sequence_scope(mesh, "sp"):
        for step in range(args.steps):
            tic = time.time()
            with autograd.record():
                logits = net(x)  # stock model — attention rides the ring
                loss = loss_fn(
                    logits.reshape((-1, logits.shape[-1])),
                    y.reshape((-1,)))
            loss.backward()
            trainer.step(args.batch_size)
            print("step %d: loss %.4f (%.2fs)"
                  % (step, float(loss.mean().asnumpy()),
                     time.time() - tic))


if __name__ == "__main__":
    main()
