"""Per-host decode-worker fleet — the threaded decode engine of the
streaming data plane (ref: src/io/iter_image_recordio_2.cc's
preprocess_threads + src/io/iter_prefetcher.h's bounded ThreadedIter,
rebuilt around chunk leases instead of a per-process cursor).

``MXT_DATA_WORKERS`` threads per host each run the same loop:

    lease a chunk from the host's own partition
      → (dry) steal from the reclaim pool / the slowest live peer
      → decode the chunk's records into batches (host-side numpy —
        the one layer of this system that is SUPPOSED to touch host
        memory; JPEG decode releases the GIL, the per-record Python
        around it does not: on a TPU v5e's host two threads gave 1.63
        times one and more gave nothing, PERF.md Findings, PR 39)
      → COMMIT the chunk (exactly-once point — a stale lease is
        refused typed and the batches are dropped, never fed)
      → enqueue the batches into the host's bounded buffer

The buffer is the backpressure boundary: ``MXT_DATA_BUFFER_BATCHES``
bounds how far decode may run ahead of the consumer, its resident HOST
bytes are the ``mxt_data_buffer_bytes`` gauge (numpy buffers: they are
not in the HBM ledger, which counts device memory), and a full buffer
blocks the workers instead of OOMing the host. The consumer side
(:class:`~.loader.StreamingDataLoader`) stamps the time it spends
waiting on this queue as the ``mxt.data.wait`` span and, from the same
clock read, the ``data_wait`` phase — goodput accounting and ``mxt_top``
attribute input-boundness per host from it.

What a worker is doing is in the profiler's trace, one
``jax.profiler.TraceAnnotation`` a state (about a microsecond each with
no trace running): ``mxt.data.lease`` (the ledger's grant or steal and
the 5 ms poll after an empty one; ``wid``, ``granted``, ``stolen``),
``mxt.data.decode`` (ONE batch's records read and decoded; ``wid``,
``batch``, ``records``, ``bytes_in``), ``mxt.data.commit`` and
``mxt.data.put`` (the enqueue, whose length is the backpressure;
``wid``, ``batch``, ``depth``). ``batch`` is ``"<epoch>:<chunk>:<k>"``,
the k-th batch of the chunk: it rides with the batch through the buffer,
and the consumer's ``mxt.data.h2d`` and ``mxt.data.got`` carry it too.

Decoding is deterministic by construction: a chunk's record order and
augmentation draws derive from (manifest, seed, epoch, chunk) — never
from the host or worker that runs it — so work stealing moves bytes,
not numerics.

Chaos hooks (seeded ``MXT_FAULT`` rules):

- ``data_host_kill:host=I[,after=K]`` — host I's fleet dies at its
  K-th chunk-commit boundary: workers stop, the host fences itself in
  the ledger (standing in for the membership reaper), survivors steal
  the reclaimed chunks.
- ``data_worker_slow:host=I,ms=N`` — host I's decode slows by N ms per
  chunk (steal bait: peers should pick up its tail).
"""
from __future__ import annotations

import queue as _queue
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation as _span

from ..base import MXNetError
from ..membership import StaleWorkerError
from .manifest import _chunk_seed

__all__ = ["DecodeWorkerFleet", "ImageDecoder", "ArrayDecoder"]

_EOS = object()  # end-of-stream sentinel: last exiting worker enqueues it


# --------------------------------------------------------------------------
# record decoders
# --------------------------------------------------------------------------
class ImageDecoder:
    """JPEG/PNG image record decoder + augmenter — the hot subset of
    ImageRecordIter's pipeline (resize, rand_crop, rand_mirror, crop to
    data_shape, mean/std normalization), emitted straight into a
    preallocated batch slot. ``data_shape`` stays (C, H, W) in both
    layouts, like the reference API."""

    def __init__(self, data_shape, rand_crop=False, rand_mirror=False,
                 resize=-1, mean=None, std=None, layout="NHWC",
                 dtype="float32"):
        if layout not in ("NCHW", "NHWC"):
            raise MXNetError("ImageDecoder layout must be NCHW or NHWC, "
                             "got %r" % (layout,))
        self.data_shape = tuple(data_shape)
        self.rand_crop = bool(rand_crop)
        self.rand_mirror = bool(rand_mirror)
        self.resize = int(resize)
        self.layout = layout
        self.dtype = np.dtype(dtype)
        self.mean = None if mean is None \
            else np.array(mean, dtype=np.float32)
        self.std = None if std is None else np.array(std, dtype=np.float32)
        if self.dtype == np.uint8 and (mean is not None or std is not None):
            raise MXNetError("dtype='uint8' emits raw pixels; normalize "
                             "on device instead of passing mean/std")

    @property
    def sample_shape(self):
        c, h, w = self.data_shape
        return (c, h, w) if self.layout == "NCHW" else (h, w, c)

    @property
    def sample_dtype(self):
        return self.dtype

    def decode(self, raw, slot, rng):
        """Decode one record into ``slot`` (a view into the batch
        buffer); returns the label. Host-side numpy by design — this IS
        the worker boundary the data plane exists to parallelize.

        With a ``resize`` target the JPEG is decoded in DRAFT mode:
        libjpeg's DCT-domain 1/2 / 1/4 / 1/8 scaling decodes straight to
        the smallest power-of-two scale still >= the target, then the
        remaining factor is a cheap bilinear resize — a 2-4x decode
        saving on ImageNet-shaped records vs the per-process iterator's
        full-resolution decode + resize (this is where the
        ``streaming_input_ab`` bench's per-core win comes from; at
        scale 1 the bytes match the non-draft path exactly)."""
        import io as _io

        from PIL import Image

        from ..io.io import _crop, _resize_short
        from ..recordio import unpack

        header, payload = unpack(raw)
        pil = Image.open(_io.BytesIO(payload))
        if self.resize > 0:
            pil.draft("RGB", (self.resize, self.resize))
        pil = pil.convert("RGB")
        img = np.asarray(pil)  # sync-ok: PIL decode, host numpy by design
        if self.resize > 0 and min(img.shape[0], img.shape[1]) \
                != self.resize:
            img = _resize_short(img, self.resize)
        c, h, w = self.data_shape
        img = _crop(img, h, w, rand=self.rand_crop, rng=rng)
        if self.rand_mirror and rng.rand() < 0.5:
            img = img[:, ::-1, :]
        if self.layout == "NCHW":
            slot[...] = np.transpose(img, (2, 0, 1))
        else:
            slot[...] = img
        if self.mean is not None or self.std is not None:
            mean = 0.0 if self.mean is None else self.mean
            std = 1.0 if self.std is None else self.std
            if self.layout == "NCHW":
                slot -= np.reshape(mean, (-1, 1, 1))
                slot /= np.reshape(std, (-1, 1, 1))
            else:
                slot -= mean
                slot /= std
        label = header.label
        if isinstance(label, np.ndarray):
            label = float(label[0])  # sync-ok: host numpy label scalar
        return label


class ArrayDecoder:
    """Raw-array record decoder: the payload is ``shape`` of ``dtype``
    bytes (no image codec) — the cheap path for tests and non-vision
    records packed with :func:`~mxnet_tpu.recordio.pack`."""

    def __init__(self, shape, dtype="float32"):
        self.sample_shape = tuple(shape)
        self.sample_dtype = np.dtype(dtype)

    def decode(self, raw, slot, rng):
        del rng
        from ..recordio import unpack

        header, s = unpack(raw)
        slot[...] = np.frombuffer(
            s, dtype=self.sample_dtype).reshape(self.sample_shape)
        label = header.label
        if isinstance(label, np.ndarray):
            label = float(label[0])  # sync-ok: host numpy label scalar
        return label


# --------------------------------------------------------------------------
# per-host telemetry (host-labeled so the fleet collector's merged page
# attributes input-boundness per host with zero extra wiring)
# --------------------------------------------------------------------------
def _host_metrics(host):
    from .. import telemetry

    lbl = str(int(host))
    return {
        "records": telemetry.counter(
            "mxt_data_records_total",
            "Records decoded by the data-plane worker fleet.",
            ("host",)).labels(lbl),
        "bytes": telemetry.counter(
            "mxt_data_bytes_total",
            "Decoded batch bytes produced by the data-plane fleet.",
            ("host",)).labels(lbl),
        "steals": telemetry.counter(
            "mxt_data_steals_total",
            "Chunks this host stole from peers (dry lease queue).",
            ("host",)).labels(lbl),
        "stale": telemetry.counter(
            "mxt_data_stale_leases_total",
            "Chunk commits refused as stale (zombie lease generations).",
            ("host",)).labels(lbl),
        "depth": telemetry.gauge(
            "mxt_data_queue_depth",
            "Decoded batches buffered ahead of the consumer.",
            ("host",)).labels(lbl),
        "rate": telemetry.gauge(
            "mxt_data_records_per_second",
            "Decode throughput of this host's worker fleet (epoch "
            "running average).", ("host",)).labels(lbl),
        "buffered": telemetry.gauge(
            "mxt_data_buffer_bytes",
            "Host bytes of decoded batches buffered ahead of the "
            "consumer (numpy, not device memory).",
            ("host",)).labels(lbl),
        "read_s": telemetry.counter(
            "mxt_data_read_seconds_total",
            "Worker seconds inside the record reader (read_idx), "
            "summed over this host's workers.", ("host",)).labels(lbl),
        "decode_s": telemetry.counter(
            "mxt_data_decode_seconds_total",
            "Worker seconds inside decoder.decode, summed over this "
            "host's workers.", ("host",)).labels(lbl),
        "put_wait_s": telemetry.counter(
            "mxt_data_put_wait_seconds_total",
            "Worker seconds enqueueing into the bounded buffer "
            "(backpressure), summed over this host's workers.",
            ("host",)).labels(lbl),
    }


class DecodeWorkerFleet:
    """N decode workers feeding one host's bounded batch buffer."""

    def __init__(self, manifest, ledger, host_id, decoder, batch_size,
                 epoch=0, seed=0, num_workers=None, buffer_batches=None,
                 steal=None):
        from .. import config

        self.manifest = manifest
        self.ledger = ledger
        self.host = int(host_id)
        self.decoder = decoder
        self.batch_size = int(batch_size)
        self.epoch = int(epoch)
        self.seed = int(seed)
        if self.batch_size > manifest.chunk_records:
            raise MXNetError(
                "batch_size %d exceeds chunk_records %d — batches never "
                "cross a chunk boundary (that is what makes stolen "
                "chunks decode bit-identically)"
                % (self.batch_size, manifest.chunk_records))
        self.num_workers = int(num_workers if num_workers is not None
                               else config.get("MXT_DATA_WORKERS"))
        depth = int(buffer_batches if buffer_batches is not None
                    else config.get("MXT_DATA_BUFFER_BATCHES"))
        self.steal_enabled = bool(config.get("MXT_DATA_STEAL")
                                  if steal is None else steal)
        self._q = _queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._threads = []
        self._live = 0
        self._wids = set()      # worker ids currently running (resize())
        self._commits = 0       # chunks this fleet committed
        self._records = 0
        self._buffered_bytes = 0
        self._t0 = None
        self.killed = False     # data_host_kill fired
        self.fenced = False     # a commit came back stale — we are dead
        self._errors = []       # worker exceptions, re-raised to consumer
        self._m = _host_metrics(self.host)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._threads:
            return self
        self._t0 = time.perf_counter()
        self._live = self.num_workers
        self._wids = set(range(self.num_workers))
        for wid in range(self.num_workers):
            t = threading.Thread(
                target=self._run, args=(wid,), daemon=True,
                name="data-decode-h%d-w%d" % (self.host, wid))
            self._threads.append(t)
            t.start()
        return self

    def live_workers(self):
        """Worker threads currently decoding (retired, dead, and
        not-yet-started workers excluded) — the autoscaler's 'did the
        last resize land' signal."""
        with self._lock:
            return len(self._wids)

    def resize(self, n):
        """Grow or shrink the decode-worker fleet in place.

        Growing spawns the missing worker ids immediately; shrinking is
        cooperative — surplus workers (``wid >= n``) retire at their
        next chunk boundary, so a shrink never abandons a leased chunk
        mid-decode (the commit still lands, the batches still feed).
        ``n < 1`` refuses typed: a host keeps at least one decode
        worker while it lives (``close()`` is how a fleet stops)."""
        n = int(n)
        if n < 1:
            raise MXNetError(
                "DecodeWorkerFleet.resize(%d): a live host keeps at "
                "least one decode worker — use close() to stop the "
                "fleet" % (n,))
        with self._lock:
            self.num_workers = n
            if not self._threads or self._stop.is_set():
                return self  # not started yet: start() spawns n
            spawn = [wid for wid in range(n) if wid not in self._wids]
            for wid in spawn:
                self._wids.add(wid)
                self._live += 1
        for wid in spawn:
            t = threading.Thread(
                target=self._run, args=(wid,), daemon=True,
                name="data-decode-h%d-w%d" % (self.host, wid))
            self._threads.append(t)
            t.start()
        return self

    def kill(self):
        """Simulate this host's death at a chunk boundary: stop the
        workers and fence the host in the ledger (what the membership
        reaper's death listener does for a real dead process) so
        survivors reclaim its unconsumed chunks."""
        self.killed = True
        self._stop.set()
        try:
            self.ledger.fence_host(self.host)
        except (MXNetError, OSError, ConnectionError):
            pass  # a truly dead host wouldn't manage to fence itself

    def close(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)
        self._m["buffered"].set(0)
        self._m["depth"].set(0)

    # -- chaos hooks -------------------------------------------------------
    def _chaos(self):
        """Consult the seeded fault rules at the chunk boundary; returns
        True when this host just died (data_host_kill)."""
        from .. import resilience

        inj = resilience.fault_point()
        rule = inj.rule("data_host_kill")
        if rule is not None \
                and int(rule.get("host", -1)) == self.host \
                and self._commits >= int(rule.get("after", 0)) \
                and inj.should("data_host_kill"):
            self.kill()
            return True
        rule = inj.rule("data_worker_slow")
        if rule is not None and int(rule.get("host", -1)) == self.host \
                and inj.should("data_worker_slow"):
            ms = float(rule.get("ms", 20.0))  # sync-ok: fault-rule scalar
            time.sleep(ms / 1e3)
        return False

    # -- worker loop -------------------------------------------------------
    def _run(self, wid):
        readers = {}
        try:
            while not self._stop.is_set():
                if wid >= self.num_workers:
                    return  # retired by resize(): shrink lands at a
                    # chunk boundary, never mid-decode
                if self._chaos():
                    return
                with _span("mxt.data.lease", wid=wid) as span:
                    try:
                        grants = self.ledger.lease(self.host, 1)
                        stolen = False
                        if not grants and self.steal_enabled:
                            grants = self.ledger.steal(self.host, 1)
                            stolen = bool(grants)
                    except StaleWorkerError:
                        self.fenced = True
                        self._m["stale"].inc()
                        return
                    span.set_metadata(granted=len(grants),
                                      stolen=int(stolen))
                    if not grants:
                        if self.ledger.finished():
                            return
                        # everything left is leased to live peers: poll —
                        # a late death can still reclaim work for us
                        self._stop.wait(0.005)
                        continue
                if stolen:
                    self._m["steals"].inc(len(grants))
                for grant in grants:
                    self._process(wid, grant[0], grant[1], readers)
                    if self._stop.is_set():
                        return
        except BaseException as e:  # noqa: BLE001 — re-raised in batches()
            # a dead worker must not silently truncate the epoch: the
            # consumer re-raises this instead of ending cleanly
            self._errors.append(e)
            self._stop.set()
        finally:
            for r in readers.values():
                r.close()
            with self._lock:
                self._live -= 1
                self._wids.discard(wid)
                last = self._live <= 0
            if last:
                # wake the consumer immediately instead of letting it
                # discover the drained fleet on a poll timeout; bounded
                # put so a full buffer under a stopped consumer cannot
                # wedge the worker (the poll fallback still ends the
                # stream then)
                try:
                    self._q.put(_EOS, timeout=0.05)
                except _queue.Full:
                    pass

    def _process(self, wid, chunk_id, token, readers):
        chunk = self.manifest.epoch_chunk(chunk_id, self.epoch, self.seed)
        reader = readers.get(chunk.shard_id)
        if reader is None:
            reader = readers[chunk.shard_id] = \
                self.manifest.open_reader(chunk.shard_id)
        # augmentation draws: a pure function of the chunk coordinates,
        # consumed sequentially over the chunk's records — the thief
        # reproduces the owner's batches bit for bit
        rng = np.random.RandomState(_chunk_seed(
            self.manifest.manifest_id, self.seed, self.epoch, chunk_id,
            tag="augment"))
        bs = self.batch_size
        batches = []
        keys = chunk.keys
        clock = time.perf_counter
        for k, lo in enumerate(range(0, len(keys), bs)):
            part = keys[lo:lo + bs]
            tag = "%d:%d:%d" % (self.epoch, chunk.chunk_id, k)
            with _span("mxt.data.decode", wid=wid, batch=tag,
                       records=len(part)) as span:
                data = np.empty(
                    (len(part),) + tuple(self.decoder.sample_shape),
                    self.decoder.sample_dtype)
                labels = np.empty((len(part),), np.float32)
                ids = []
                read_s = decode_s = 0.0
                bytes_in = 0
                for j, key in enumerate(part):
                    t0 = clock()
                    raw = reader.read_idx(key)
                    t1 = clock()
                    labels[j] = self.decoder.decode(raw, data[j], rng)
                    decode_s += clock() - t1
                    read_s += t1 - t0
                    bytes_in += len(raw)
                    ids.append((chunk.shard_id, key))
                span.set_metadata(bytes_in=bytes_in)
            self._m["read_s"].inc(read_s)
            self._m["decode_s"].inc(decode_s)
            batches.append((data, labels, ids, chunk.chunk_id, tag))
        # commit BEFORE enqueue: the exactly-once point. If the commit
        # comes back stale this host was fenced (or the chunk re-leased
        # to a thief) — feeding the batches anyway would duplicate the
        # new leaseholder's work, so they are dropped on the floor.
        try:
            with _span("mxt.data.commit", wid=wid, chunk=chunk.chunk_id):
                self.ledger.commit(self.host, chunk.chunk_id, token)
        except StaleWorkerError:
            self.fenced = True
            self._m["stale"].inc()
            self._stop.set()
            return
        self._commits += 1
        nrec = len(keys)
        nbytes = sum(b[0].nbytes + b[1].nbytes for b in batches)
        self._m["records"].inc(nrec)
        self._m["bytes"].inc(nbytes)
        with self._lock:
            self._records += nrec
            dt = time.perf_counter() - self._t0
        if dt > 0:
            self._m["rate"].set(self._records / dt)
        for b in batches:
            self._put(wid, b)
            if self._stop.is_set():
                return

    # -- bounded buffer (the backpressure boundary) ------------------------
    def _publish_buffer(self):
        """The buffer's depth and its HOST bytes (numpy batches waiting
        for the consumer), as gauges."""
        self._m["buffered"].set(self._buffered_bytes)
        self._m["depth"].set(self._q.qsize())

    def _put(self, wid, batch):
        t0 = time.perf_counter()
        with _span("mxt.data.put", wid=wid, batch=batch[4],
                   depth=self._q.qsize()):
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.05)
                    break
                except _queue.Full:
                    continue  # backpressure: decode blocks, never OOMs
            else:
                return
        self._m["put_wait_s"].inc(time.perf_counter() - t0)
        with self._lock:
            self._buffered_bytes += batch[0].nbytes + batch[1].nbytes
        self._publish_buffer()

    def batches(self):
        """Consumer side: yield (data, labels, ids, chunk_id, batch tag)
        until the epoch is globally finished and this host's buffer
        drained."""
        while True:
            try:
                batch = self._q.get(timeout=0.02)
            except _queue.Empty:
                with self._lock:
                    workers_done = self._live <= 0
                if workers_done and self._q.empty():
                    batch = _EOS
                else:
                    continue
            if batch is _EOS:
                if self._errors and not self.killed and not self.fenced:
                    raise MXNetError(
                        "data-plane decode worker died: %r"
                        % (self._errors[0],)) from self._errors[0]
                return
            with self._lock:
                self._buffered_bytes -= batch[0].nbytes + batch[1].nbytes
            self._publish_buffer()
            yield batch
