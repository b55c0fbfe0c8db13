"""Pod-scale streaming data plane (ROADMAP 3 — the MXNet 1.x data stack
``ImageRecordIter`` / ``io.DataIter`` over recordio shards, rebuilt
TPU-native and multi-host).

One TPU v5e chip trains ResNet-50 on 2372 images a second from
device-resident batches, which is what a host has to decode and deliver;
this package's decode threads deliver 1277 on that chip's host (median
of six seeds at 4 threads; 742 at one thread, and no more past two: the
interpreter lock; ``tools/trace_input.py``, PR 39's chip runs in
``PERF.md``) — input is the ceiling, and PR 9's goodput accounting bills
the loss as ``data_wait``. This package replaces the per-process cursor
with a leased, stealable chunk keyspace:

- :class:`~.manifest.ShardManifest` — recordio shards sliced into
  deterministic chunks, partitioned across the mesh's hosts from the
  launch-line topology (``MXT_NUM_WORKERS``/``MXT_MESH_SHAPE``) with an
  epoch-seeded shuffle; chunk contents are a pure function of
  (manifest, seed, epoch), never of the decoding host.
- :class:`~.ledger.ChunkLedger` — exactly-once chunk consumption via
  lease generations (PR 10 ring-epoch style fencing: a zombie host's
  stale commit is refused typed), host fencing that reclaims a dead
  host's chunks for survivors, and cross-host work stealing; shared
  in-process or over the authenticated async transport
  (``data_lease``/``data_steal``/``data_cursor`` ops,
  :class:`~.ledger.RemoteLedger`).
- :class:`~.workers.DecodeWorkerFleet` — ``MXT_DATA_WORKERS`` decode
  threads per host feeding a bounded buffer (backpressure; its host
  bytes in ``mxt_data_buffer_bytes``), each state of a worker a span of
  the profiler's trace (``mxt.data.lease`` / ``decode`` / ``commit`` /
  ``put``).
- :class:`~.loader.StreamingDataLoader` — the ``for batch in loader``
  face, stamping per-host ``data_wait`` (the ``mxt.data.wait`` span),
  the device put (``mxt.data.h2d``) and the epoch's turn, and carrying a
  mid-epoch checkpoint cursor
  (``CheckpointManager.save(extra=loader.cursor())``).
"""
from .ledger import ChunkLedger, RemoteLedger, StaleLeaseError
from .loader import StreamBatch, StreamingDataLoader
from .manifest import Chunk, ShardManifest
from .workers import ArrayDecoder, DecodeWorkerFleet, ImageDecoder

__all__ = [
    "ShardManifest", "Chunk", "ChunkLedger", "RemoteLedger",
    "StaleLeaseError", "DecodeWorkerFleet", "ImageDecoder",
    "ArrayDecoder", "StreamingDataLoader", "StreamBatch",
]
