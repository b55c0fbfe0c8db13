#!/usr/bin/env python3
"""ResNet-50 fed by the host, with the input pipeline's spans read back.

    python3 tools/trace_input.py --seed 7 --seconds 30 --workers 8 --buffer-batches 8

What no benchmark cell does: the step of ``resnet50_train_b256`` (bf16, NHWC,
batch 256, SGD with momentum, ``Trainer.fuse_step``; the sizes come from
``benchmark/configs/resnet50_v1.json`` and ``benchmark/traffic/train_b256.json``,
read and not edited) takes its batches from ``StreamingDataLoader`` over a
RecordIO data set made here from ``--seed``: 16384 JPEGs of 256 x 341 at
quality 90 in 4 indexed shards (what ``tools/im2rec.py --resize 256`` stores),
a seeded low-frequency pattern plus noise so that a record is 30-45 KB, labels
uniform over the classes. The decoder crops 224 x 224 at random, mirrors at
random and emits uint8 NHWC; the cast and the normalisation run inside the
step's one launch (``--host-normalize``: float32 and the mean and std on the
decode threads, for what that costs). Steps are dispatched as the benchmark's
``_drive`` dispatches them, at most ``max_inflight`` ahead.

After the compile and as many unmeasured steps as batches can lie decoded
ahead of the consumer (buffer, prefetch, one a worker, the steps in flight),
the window runs ``--seconds``; with ``--trace 1`` (the default) its last
``--trace-seconds`` are under the profiler, inside the spans a benchmark
runner would put there (``bench.trace_window``, ``bench.dispatch_step``,
``bench.wait``), and read back with ``mx.profiler.aggregate``
(``--python-tracer 0`` leaves out the profiler's record of every Python call,
which slows the decode threads; ``--keep-trace FILE`` keeps the ``.xplane.pb``).
One JSON line comes out: images a second of the untraced and the traced part,
the device's busy time, ``idle_by_span_s``, the workers' seconds by state
over workers x window and the consumer's by span, read and decode seconds a
record, the device put's milliseconds a batch (the span, which closes when
the put is issued; and once, after the window on an idle host, to the array
ready on the device), the consumer's wait a step, the epochs' turns, the
clocks' offset. The random weights are compared with
nothing. The exit code is 1 where the spans of one batch do not share its
``batch`` value or the idle time does not add up, 3 after ``--rehearse`` (a
64-record set and a two-layer net on the CPU: every number a count or null,
never a speed), 2 where no TPU was found.
"""
import argparse
import collections
import io
import json
import multiprocessing
import os
import shutil
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
SHARDS = 4
# ImageNet's channel means and deviations on the 0-255 scale
MEAN, STD = (123.68, 116.779, 103.939), (58.393, 57.12, 57.375)
STATES = ("lease", "decode", "commit", "put")
CHAIN = ("decode", "put", "h2d", "got")  # the order a batch passes them in


# -- the data set ---------------------------------------------------------
def _encode(job):
    """JPEG bytes of records ``lo`` to ``hi`` (a worker process: numpy and
    PIL only). Each record's pixels come from (seed, index) alone, so any
    split over the workers gives the same files."""
    import numpy as np
    from PIL import Image

    seed, lo, hi, height, width = job
    pool = np.random.default_rng([seed, 1 << 40]).standard_normal(
        (2 * height, 2 * width, 3), np.float32)
    out = []
    for i in range(lo, hi):
        rng = np.random.default_rng([seed, i])
        coarse = rng.integers(0, 256, (4, 6, 3), dtype=np.uint8)
        img = np.asarray(Image.fromarray(coarse).resize((width, height), Image.BICUBIC),
                         np.float32)
        y, x = rng.integers(0, height), rng.integers(0, width)
        img += rng.uniform(10.0, 19.0) * pool[y:y + height, x:x + width]
        buf = io.BytesIO()
        Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
            buf, "JPEG", quality=90)
        out.append(buf.getvalue())
    return out


def make_dataset(root, seed, records, height, width, classes):
    """Write the shards with their ``.idx`` through ``mxnet_tpu.recordio``;
    returns their paths and the stored bytes a record."""
    import numpy as np

    from mxnet_tpu import recordio

    jobs = [(seed, lo, min(lo + 128, records), height, width)
            for lo in range(0, records, 128)]
    procs = min(os.cpu_count() or 1, len(jobs), 32)
    labels = np.random.default_rng([seed, 1 << 41]).integers(0, classes, records)
    per_shard = -(-records // SHARDS)
    paths, stored, gid = [], 0, 0
    # spawn: the parent has imported JAX, and its threads do not survive a fork
    with multiprocessing.get_context("spawn").Pool(procs) as pool:
        writer = None
        for chunk in pool.imap(_encode, jobs):
            for jpeg in chunk:
                if gid % per_shard == 0:
                    if writer is not None:
                        writer.close()
                    paths.append(os.path.join(root, "part-%d.rec" % len(paths)))
                    writer = recordio.MXIndexedRecordIO(
                        paths[-1][:-4] + ".idx", paths[-1], "w")
                writer.write_idx(gid, recordio.pack(
                    recordio.IRHeader(0, float(labels[gid]), gid, 0), jpeg))
                stored += len(jpeg)
                gid += 1
        writer.close()
    return paths, stored / records


# -- the step -------------------------------------------------------------
def build_step(config, rehearse, host_normalized):
    """The cell's entry point around the zoo's ResNet-50 behind one block that
    casts and normalises the loader's pixels, so that both stay in the step's
    launch. ``benchmark/models/resnet.py:build`` takes no such block, so the
    net is built as it builds it, from the same file's sizes."""
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.vision import resnet as zoo

    m, dtype = config["published"], config["dtype"]

    class Fed(nn.HybridBlock):
        def __init__(self, body):
            super().__init__(prefix="fed_")
            with self.name_scope():
                self.body = body

        def hybrid_forward(self, F, x):
            x = x.astype(dtype)
            if not host_normalized:
                x = (x - nd.array(MEAN, dtype=dtype)) / nd.array(STD, dtype=dtype)
            return self.body(x)

    with nn.layout_scope(config["layout"]):
        if rehearse:
            body = nn.HybridSequential()
            body.add(nn.Conv2D(8, 3, strides=2), nn.GlobalAvgPool2D(), nn.Flatten(),
                     nn.Dense(m["classes"]))
        else:
            body = zoo.ResNetV1(zoo.BottleneckV1, list(m["layers"]), list(m["channels"]),
                                classes=m["classes"])
        net = Fed(body)
    net.initialize()
    net.cast(dtype)
    net.hybridize()
    net(nd.zeros((1, m["image"], m["image"], 3),
                 dtype="float32" if host_normalized else "uint8"))  # deferred shapes
    opt = config["optimizer"]
    trainer = gluon.Trainer(net.collect_params(), opt["name"],
                            {"learning_rate": opt["learning_rate"],
                             "momentum": opt["momentum"]})
    return trainer.fuse_step(net, gluon.loss.SoftmaxCrossEntropyLoss())


def drive(step, batches, seconds, steps, max_inflight):
    """``benchmark/runners/train_steps.py:_drive`` with the batch taken from the
    loader, under the same two spans: dispatch for ``seconds`` (or ``steps``
    steps), at most ``max_inflight`` ahead, then wait for the last. (steps,
    seconds)."""
    import jax
    from jax.profiler import TraceAnnotation

    inflight = collections.deque()
    t0, n = time.perf_counter(), 0
    while (n < steps) if steps else (time.perf_counter() - t0 < seconds):
        batch = next(batches)
        with TraceAnnotation("bench.dispatch_step"):
            inflight.append(step(batch.data, batch.label))
        n += 1
        if len(inflight) > max_inflight:
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(inflight.popleft().data)
    with TraceAnnotation("bench.wait"):
        jax.block_until_ready(inflight[-1].data)
    return n, time.perf_counter() - t0


def put_to_ready_ms(decoder, batch):
    """Milliseconds from ``nd.array`` of one batch of the decoder's shape and
    type to the array ready on the device, on an idle host (after the
    window, the decode threads gone), the median of five: the span
    ``mxt.data.h2d`` closes when the put is issued, and the runtime's own
    threads lay the batch out for the device and copy it after that."""
    import jax
    import numpy as np

    from mxnet_tpu import nd

    data = np.zeros((batch,) + tuple(decoder.sample_shape), decoder.sample_dtype)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(nd.array(data, dtype=data.dtype).data)
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def epochs(loader):
    """A user's ``for epoch: for batch in loader`` as one stream."""
    while True:
        yield from loader


# -- the reading ----------------------------------------------------------
def check_batches(spans):
    """What is wrong with the spans of the batches in a trace, as a list of
    sentences. A batch passes decode, put, h2d and got in that order, once
    each and under one ``batch`` value. A batch lies in the buffer for longer
    than a short trace lasts, so a trace may hold only the first or only the
    last of them, but never both ends without the middle; some batch has to
    show decode with put, and some h2d with got. Every ``got`` but the first
    (whose wait may have opened before the trace did) has a ``wait`` with its
    ``n``."""
    seen, waits, gots = {}, set(), []
    for name, start, _, args, _ in spans:
        kind = name.rpartition(".")[2]
        if name == "mxt.data.wait":
            waits.add(args.get("n"))
        if name.startswith("mxt.data.") and kind in CHAIN:
            seen.setdefault(args.get("batch"), []).append((CHAIN.index(kind), start))
        if name == "mxt.data.got":
            gots.append(args.get("n"))
    wrong, pairs = [], set()
    for tag, found in seen.items():
        kinds = [k for k, _ in found]
        if tag is None or len(set(kinds)) != len(kinds) \
                or sorted(kinds) != list(range(min(kinds), max(kinds) + 1)) \
                or [k for k, _ in sorted(found, key=lambda f: (f[1], f[0]))] != sorted(kinds):
            wrong.append("batch %r has the spans %s" % (tag, [CHAIN[k] for k in kinds]))
        pairs.update(k for k in (0, 2) if k in kinds and k + 1 in kinds)
    wrong += ["no batch has both %s and %s in the trace" % CHAIN[k:k + 2]
              for k in (0, 2) if k not in pairs]
    wrong += ["got n=%r follows no wait of that n" % n for n in gots[1:] if n not in waits]
    return wrong


def percentile(values, q):
    values = sorted(values)
    return values[min(len(values) - 1, int(q / 100.0 * len(values)))] if values else None


def read_trace(trace_dir, workers, steps):
    """The traced window's numbers (module docstring) and what is wrong with
    them. On the CPU there is no device plane: the host spans alone."""
    from mxnet_tpu import profiler, profiler_trace

    agg = profiler.aggregate(trace_dir, window="bench.trace_window")
    spans = agg["spans"] if agg else profiler_trace.host_spans(trace_dir)
    wrong = check_batches(spans)
    out = {"busy_s": None, "window_s": None, "idle_share": None, "idle_by_span_s": None,
           "idle_gaps": None, "clock_offset_us": None, "launch_pairs": None}
    if agg is not None:
        out.update({k: agg[k] for k in out if k in agg})
        idle = agg["window_s"] - agg["busy_s"]
        out["idle_share"] = idle / agg["window_s"]
        if abs(sum(agg["idle_by_span_s"].values()) - idle) > 1e-6:
            wrong.append("idle_by_span_s adds up to %r, the window less busy is %r"
                         % (sum(agg["idle_by_span_s"].values()), idle))
    (lo, hi), = [sp[1:3] for sp in spans if sp[0] == "bench.trace_window"]
    span_s, calls = profiler_trace.span_totals(spans, lo, hi)
    window = (hi - lo) / 1e12
    pool = workers * window
    by_state = {k: span_s.get("mxt.data." + k, 0.0) / pool for k in STATES}
    by_state["remainder"] = 1.0 - sum(by_state.values())
    out["worker_share_by_state"] = by_state
    # the consumer's thread: the spans that do not nest in one another there
    consumer = {k: span_s.get(k, 0.0) / window
                for k in ("mxt.data.wait", "bench.dispatch_step", "bench.wait")}
    consumer["remainder"] = 1.0 - sum(consumer.values())
    out["consumer_share_by_span"] = consumer
    out["host_span_s"], out["host_span_calls"] = span_s, calls
    batches = calls.get("mxt.data.h2d", 0)
    h2d_bytes = sum(sp[3].get("bytes", 0) for sp in spans
                    if sp[0] == "mxt.data.h2d" and lo <= sp[1] < hi)
    h2d_s = span_s.get("mxt.data.h2d", 0.0)
    out["h2d_ms_per_batch"] = 1e3 * h2d_s / batches if batches else None
    out["h2d_MB_per_s"] = h2d_bytes / h2d_s / 1e6 if h2d_s else None
    waits = [(e - s) / 1e9 for n, s, e, _, _ in spans if n == "mxt.data.wait"]
    out["data_wait_ms"] = {"per_step": 1e3 * span_s.get("mxt.data.wait", 0.0) / steps,
                           "mean": statistics.fmean(waits) if waits else None,
                           "p50": percentile(waits, 50), "p99": percentile(waits, 99),
                           "spans": len(waits)}
    begins = [sp[1:3] for sp in spans if sp[0] == "mxt.data.epoch_begin"]
    turns = [(min(e for s, e in begins if s >= end) - end) / 1e9
             for n, end, _, _, _ in spans
             if n == "mxt.data.epoch_end" and any(s >= end for s, _ in begins)]
    out["epoch_turns"] = len(turns)
    out["epoch_turn_ms_longest"] = max(turns) if turns else None
    return out, wrong


def counter(name, host="0"):
    from mxnet_tpu import telemetry

    return telemetry.counter(name, "", ("host",)).labels(host).value


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace-seconds", type=float, default=5.0)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1,
                    help="1: the profiler's defaults, as the benchmark and "
                         "mx.profiler trace; 0: without its record of every Python call")
    ap.add_argument("--keep-trace", metavar="FILE", default=None,
                    help="copy the trace's .xplane.pb there before it is deleted")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--buffer-batches", type=int, default=8)
    ap.add_argument("--host-normalize", action="store_true",
                    help="float32 pixels, mean and std on the decode threads")
    ap.add_argument("--rehearse", action="store_true",
                    help="a tiny stand-in on the CPU; never a result")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import data_plane, profiler, tuning

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("trace_input: JAX found no accelerator (%s): nothing was run" % dev.platform,
              file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    name = "rehearse_resnet" if args.rehearse else "resnet50_v1"
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "train_b256.json")) as f:
        traffic = json.load(f)
    batch, records, height, width = traffic["batch"], 16384, 256, 341
    if args.rehearse:
        batch, records, height, width = 8, 64, 40, 53
    else:
        tuning.setup_compile_cache(os.path.join(REPO, ".jax_cache"))
    image, classes = config["published"]["image"], config["published"]["classes"]
    mx.random.seed(args.seed)

    with tempfile.TemporaryDirectory(prefix="trace_input_") as root:
        t0 = time.perf_counter()
        shards, stored = make_dataset(root, args.seed, records, height, width, classes)
        dataset_s = time.perf_counter() - t0
        decoder = data_plane.ImageDecoder(
            (3, image, image), rand_crop=True, rand_mirror=True, layout="NHWC",
            **({"dtype": "float32", "mean": MEAN, "std": STD} if args.host_normalize
               else {"dtype": "uint8"}))
        loader = data_plane.StreamingDataLoader(
            data_plane.ShardManifest(shards, chunk_records=batch if args.rehearse else None),
            batch, decoder, host_id=0, num_hosts=1, seed=args.seed,
            num_workers=args.workers, buffer_batches=args.buffer_batches,
            prefetch_to_device=True)
        step = build_step(config, args.rehearse, args.host_normalize)
        stream = epochs(loader)
        ahead = traffic["max_inflight"]
        try:
            t0 = time.perf_counter()
            drive(step, stream, 0, 2, ahead)  # the compile
            compile_s = time.perf_counter() - t0
            launches0 = profiler.launch_count()
            settle, _ = drive(step, stream, 0,
                              args.buffer_batches + 2 + args.workers + ahead, ahead)
            launches = (profiler.launch_count() - launches0) / settle
            setup_s = time.perf_counter() - t_start
            before = {k: counter("mxt_data_%s_total" % k)
                      for k in ("read_seconds", "decode_seconds", "put_wait_seconds",
                                "wait_seconds", "records")}
            traced_s = min(args.trace_seconds, args.seconds / 2.0) if args.trace else 0.0
            steps, seconds = drive(step, stream, args.seconds - traced_s, 0, ahead)
            after = {k: counter("mxt_data_%s_total" % k) for k in before}
            traced, wrong = None, []
            if args.trace:
                trace_dir = os.path.join(root, "trace")
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = args.python_tracer
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                try:
                    with jax.profiler.TraceAnnotation("bench.trace_window"):
                        t_steps, t_seconds = drive(step, stream, traced_s, 0, ahead)
                finally:
                    jax.profiler.stop_trace()
                traced, wrong = read_trace(trace_dir, args.workers, t_steps)
                if args.keep_trace:
                    from mxnet_tpu import profiler_trace

                    shutil.copyfile(profiler_trace.find_xplane(trace_dir), args.keep_trace)
                traced.update(steps=t_steps, seconds=t_seconds,
                              images_per_s=t_steps * batch / t_seconds)
        finally:
            stream.close()  # the epoch's generator: the fleet's threads end here
        ready_ms = put_to_ready_ms(decoder, batch)
    d = {k: after[k] - before[k] for k in before}
    row = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rehearse": args.rehearse, "seed": args.seed, "workers": args.workers,
        "buffer_batches": args.buffer_batches, "host_normalize": args.host_normalize,
        "python_tracer": args.python_tracer,
        "batch": batch, "records": records, "stored_bytes_per_record": stored,
        "host_cpus": os.cpu_count(), "dataset_s": dataset_s, "compile_s": compile_s,
        "setup_s": setup_s, "settle_steps": settle, "launches_per_step": launches,
        "steps": steps, "seconds": seconds, "images_per_s": steps * batch / seconds,
        "read_us_per_record": 1e6 * d["read_seconds"] / max(d["records"], 1),
        "decode_us_per_record": 1e6 * d["decode_seconds"] / max(d["records"], 1),
        "put_wait_s_per_worker_s": d["put_wait_seconds"] / (args.workers * seconds),
        "data_wait_share": d["wait_seconds"] / seconds,
        "h2d_to_ready_ms_idle_host": ready_ms,
        "traced": traced, "wrong": wrong,
    }
    if args.rehearse:  # a CPU number never stands under the name of a speed
        for key in ("images_per_s", "read_us_per_record", "decode_us_per_record",
                    "put_wait_s_per_worker_s", "data_wait_share",
                    "h2d_to_ready_ms_idle_host", "dataset_s",
                    "compile_s", "setup_s", "seconds"):
            row[key] = None
        if traced:
            for key in ("images_per_s", "seconds", "h2d_ms_per_batch", "h2d_MB_per_s",
                        "data_wait_ms", "epoch_turn_ms_longest", "worker_share_by_state",
                        "consumer_share_by_span", "host_span_s"):
                traced[key] = None
    print(json.dumps(row), flush=True)
    for line in wrong:
        print("trace_input: %s" % line, file=sys.stderr)
    return 1 if wrong else 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
