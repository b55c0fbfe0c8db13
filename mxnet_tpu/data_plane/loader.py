"""StreamingDataLoader — the consumer face of the pod-scale data plane
(ref: ImageRecordIter/io.DataIter usage: ``for batch in it`` with
``batch.data``/``batch.label``, rebuilt over the chunk-leased worker
fleet instead of a per-process cursor).

One loader per host. Per epoch it:

1. derives the deterministic chunk partition from the shared
   (manifest, seed, epoch) and installs it in the lease ledger
   (idempotent — whichever host gets there first wins, the rest join);
2. restores its checkpoint cursor, if any, so a resumed host skips the
   chunks it already consumed (no loss, no duplication — the data twin
   of PR 8's step cursor, riding ``CheckpointManager.save(extra=...)``);
3. starts the decode-worker fleet and yields :class:`StreamBatch`es,
   stamping the time it spends WAITING on the fleet's buffer as the
   ``mxt.data.wait`` span of the profiler's trace (``n``, ``epoch``,
   ``depth``; the zero-length ``mxt.data.got`` after it carries ``n``
   and the batch's ``batch`` tag, which a span cannot know when it
   opens) and, from the same clock read, the ``data_wait`` phase
   (telemetry + goodput pick it up through the existing tap) plus a
   host-labeled seconds counter so ``mxt_top`` and the fleet collector
   attribute input-boundness per host. The device put is
   ``mxt.data.h2d`` (``batch``, ``bytes``: the host's cost of the put,
   the copy itself is asynchronous), and an epoch's turn is two spans:
   ``mxt.data.epoch_begin`` (ledger install, fleet construction and
   start) and ``mxt.data.epoch_end`` (the fleet's close).

The feed path into the device stays sync-free: batches convert to
NDArrays with one device put each and optionally ride the existing
:class:`~mxnet_tpu.gluon.data.dataloader._DevicePrefetcher` so batch
N+1's H2D transfer overlaps the step running on batch N.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation as _span

from ..base import MXNetError
from .ledger import ChunkLedger
from .workers import DecodeWorkerFleet

__all__ = ["StreamingDataLoader", "StreamBatch"]


class StreamBatch:
    """One streamed batch: ``data``/``label`` NDArrays plus provenance
    (which chunk produced it and the (shard, key) record ids inside) —
    the provenance is what the exactly-once tests and the event-log
    trainer (ROADMAP 4) consume."""

    __slots__ = ("data", "label", "ids", "chunk_id")

    def __init__(self, data, label, ids, chunk_id):
        self.data = data
        self.label = label
        self.ids = ids
        self.chunk_id = chunk_id


class StreamingDataLoader:
    """Multi-host streaming loader over a :class:`ShardManifest`.

    ``ledger`` is shared: the in-process :class:`ChunkLedger` default
    serves one host (or N in-process hosts in tests); pass a
    :class:`~.ledger.RemoteLedger` to share the coordinator's ledger
    over the authenticated async transport. ``host_id``/``num_hosts``
    default from the launch line (``MXT_WORKER_ID``/``MXT_NUM_WORKERS``
    — the same topology ``MXT_MESH_SHAPE`` rides in on), so the same
    script streams on 1 host or a pod with zero new configuration.
    """

    def __init__(self, manifest, batch_size, decoder, host_id=None,
                 num_hosts=None, ledger=None, seed=0, start_epoch=0,
                 num_workers=None, buffer_batches=None, steal=None,
                 prefetch_to_device=False, to_device=True):
        from .. import config

        self.manifest = manifest
        self.batch_size = int(batch_size)
        self.decoder = decoder
        self.host = int(config.get("MXT_WORKER_ID")
                        if host_id is None else host_id)
        self.num_hosts = int(config.get("MXT_NUM_WORKERS")
                             if num_hosts is None else num_hosts)
        if self.host >= self.num_hosts:
            raise MXNetError(
                "host_id %d out of range for %d hosts"
                % (self.host, self.num_hosts))
        self.ledger = ledger if ledger is not None else ChunkLedger()
        self.seed = int(seed)
        self.epoch = int(start_epoch)
        self._num_workers = num_workers
        self._buffer_batches = buffer_batches
        self._steal = steal
        self._prefetch_to_device = bool(prefetch_to_device)
        self._to_device = bool(to_device)
        self._resume_cursor = None
        self.fleet = None  # live fleet of the epoch being iterated
        # consumer-side consumption bookkeeping: which chunks this host
        # has FULLY yielded, and how many batches of the in-flight ones
        self._consumed = {}   # chunk_id -> batches yielded
        self._complete = set()
        self._skip = {}       # chunk_id -> batches to drop on resume

    # -- checkpoint cursor -------------------------------------------------
    def _chunk_batches(self, chunk_id):
        n = self.manifest.chunk_records_of(chunk_id)
        return (n + self.batch_size - 1) // self.batch_size

    def cursor(self):
        """JSON-serializable mid-epoch cursor — pass to
        ``CheckpointManager.save(extra=loader.cursor())`` next to the
        step cursor. It tracks CONSUMER-side consumption (what this
        host's training loop actually received), not the ledger's
        decode-side commits: ``committed`` chunks were fully yielded and
        are never re-decoded on resume; a ``partial`` chunk is
        re-decoded (chunk contents are a pure function of the epoch
        coordinates) and its first N batches are dropped, so the resumed
        stream continues sample-exact — no loss, no duplication."""
        partial = {str(c): n for c, n in self._consumed.items()
                   if c not in self._complete and n > 0}
        return {"manifest_id": self.manifest.manifest_id,
                "epoch": self.epoch, "seed": self.seed,
                "committed": sorted(self._complete),
                "partial": partial}

    def restore_cursor(self, cursor):
        """Arm a checkpoint cursor: the next epoch iteration re-installs
        its epoch, pre-commits its fully-consumed chunks in the ledger,
        and drops the already-consumed head of the partial ones."""
        if cursor:
            if str(cursor.get("manifest_id")) != self.manifest.manifest_id:
                raise MXNetError(
                    "data-plane cursor manifest %r does not match this "
                    "loader's manifest %r"
                    % (cursor.get("manifest_id"),
                       self.manifest.manifest_id))
            self._resume_cursor = dict(cursor)
            self.epoch = int(cursor["epoch"])
            self.seed = int(cursor.get("seed", self.seed))
        return self

    # CheckpointManager-style aliases (PR 2/8 trainer protocol naming)
    save_states = cursor
    load_states = restore_cursor

    def stats(self):
        return self.ledger.stats()

    # -- iteration ---------------------------------------------------------
    def __iter__(self):
        return self._epoch_iter()

    def _begin_epoch(self):
        owners = self.manifest.owners(self.epoch, self.num_hosts,
                                      self.seed)
        committed = ()
        self._consumed = {}
        self._complete = set()
        self._skip = {}
        cur = self._resume_cursor
        if cur is not None and int(cur.get("epoch", -1)) == self.epoch:
            committed = [int(c) for c in cur.get("committed", ())]
            self._complete = set(committed)
            self._consumed = {c: self._chunk_batches(c)
                              for c in committed}
            self._skip = {int(c): int(n)
                          for c, n in cur.get("partial", {}).items()}
            # partial chunks resume their consumption count at the
            # skip point so completion still triggers at the true tail
            self._consumed.update(self._skip)
            self._resume_cursor = None
        self.ledger.begin_epoch(self.manifest.manifest_id, self.epoch,
                                owners, committed=committed)
        if committed:
            # peers may have installed the epoch first (begin_epoch is
            # first-wins) — merge the cursor into the live table too
            self.ledger.restore({"manifest_id": self.manifest.manifest_id,
                                 "epoch": self.epoch,
                                 "committed": list(committed)})

    def _device_batches(self, fleet):
        from ..ndarray import ndarray as _nd

        for data, labels, ids, cid, tag in fleet.batches():
            if self._to_device:
                with _span("mxt.data.h2d", batch=tag,
                           bytes=data.nbytes + labels.nbytes):
                    data = _nd.array(data, dtype=data.dtype)
                    labels = _nd.array(labels, dtype=labels.dtype)
            yield data, labels, ids, cid, tag

    def _epoch_iter(self):
        from .. import telemetry

        epoch = self.epoch
        with _span("mxt.data.epoch_begin", epoch=epoch):
            self._begin_epoch()
            fleet = DecodeWorkerFleet(
                self.manifest, self.ledger, self.host, self.decoder,
                self.batch_size, epoch=epoch, seed=self.seed,
                num_workers=self._num_workers,
                buffer_batches=self._buffer_batches, steal=self._steal)
            self.fleet = fleet
            wait_counter = telemetry.counter(
                "mxt_data_wait_seconds_total",
                "Seconds the consumer spent blocked on the data plane "
                "(per-host data_wait attribution).",
                ("host",)).labels(str(self.host))
            base = self._device_batches(fleet.start())
        if self._prefetch_to_device and self._to_device:
            from ..gluon.data.dataloader import _DevicePrefetcher

            base = _DevicePrefetcher(base, 2, True)
        it = iter(base)
        n = 0
        try:
            while True:
                # one interval, measured once: the span and ``dt`` open
                # and close together, and ``dt`` feeds both older readers
                with _span("mxt.data.wait", n=n + 1, epoch=epoch,
                           depth=fleet._q.qsize()):
                    t0 = time.perf_counter()
                    batch = next(it, None)
                    dt = time.perf_counter() - t0
                if batch is None:
                    return
                data, labels, ids, cid, tag = batch
                skip = self._skip.get(cid, 0)
                if skip > 0:
                    # resume replay: this chunk's head was consumed
                    # before the checkpoint — drop the re-decoded copy
                    # (decode is deterministic, so what follows is the
                    # sample-exact continuation); its wait stays out of
                    # the counts, and its span has no ``got``
                    self._skip[cid] = skip - 1
                    continue
                got = self._consumed.get(cid, 0) + 1
                self._consumed[cid] = got
                if got >= self._chunk_batches(cid):
                    self._complete.add(cid)
                n += 1
                with _span("mxt.data.got", n=n, batch=tag):
                    pass
                telemetry.record_phase("data_wait", dt,
                                       stream="data_plane", step=n)
                wait_counter.inc(dt)
                yield StreamBatch(data, labels, ids, cid)
        finally:
            with _span("mxt.data.epoch_end", epoch=epoch):
                fleet.close()
            if not fleet.killed and not fleet.fenced:
                self.epoch += 1
