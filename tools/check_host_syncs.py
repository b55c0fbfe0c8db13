#!/usr/bin/env python
"""Static host-sync lint for the async dispatch hot path.

The async engine (mxnet_tpu/engine.py) only pays off while the fused-step
hot path performs NO device->host read outside the deferred-handle
protocol (ndarray/pending.py — PendingValue) and the engine's token
retirement. A single stray ``asnumpy()`` / ``np.asarray()`` / ``float()``
on a device value re-synchronizes every step and silently undoes the
pipelining — exactly the regression class this pass exists to catch.

Mechanism: scan the hot-path modules line by line (skipping comments and
docstrings) for sync-shaped constructs. Every INTENTIONAL sync point
carries a ``sync-ok: <reason>`` marker comment on its line; anything
unmarked fails the build. Runs standalone and from tier-1
(tests/test_engine_async.py::test_static_host_sync_pass).

Usage: python tools/check_host_syncs.py [repo_root]
"""
from __future__ import annotations

import os
import re
import sys

# constructs that force (or usually force) a device->host transfer
_ALL = [
    r"\.asnumpy\(",
    r"\.asscalar\(",
    r"\bnp\.asarray\(",
    r"\b_np\.asarray\(",
    r"\bnumpy\.asarray\(",
    r"\bfloat\(",
    r"\.item\(",
    r"block_until_ready",
    r"\bjax\.device_get\b",
]

# hot-path modules -> the patterns scanned there. metric.py hosts the
# legitimate numpy fallback path (host math on already-transferred
# arrays), so only the transfer itself is policed there; monitor.py's
# one sanctioned read is the batched tap materialization in toc().
# telemetry.py and the estimator event handlers run INSIDE the step/
# epoch loops — an accidental device read there would silently undo the
# async pipeline, so they are policed with the full pattern set.
_TRANSFER = [r"\.asnumpy\(", r"\.asscalar\(", r"\bnp\.asarray\(",
             r"block_until_ready"]

SCAN = {
    "mxnet_tpu/engine.py": _ALL,
    # diagnostics hooks ride INSIDE the hot paths (window pushes/retires,
    # decode ticks, RPC completions): the watchdog observes host
    # heartbeat counters and the HBM ledger observes shape metadata —
    # never device values. The ONE deliberate sync is the OOM handler's
    # window drain (the hot path is already dead there), sync-ok marked.
    "mxnet_tpu/diagnostics.py": _ALL,
    "mxnet_tpu/gluon/train_step.py": _ALL,
    "mxnet_tpu/gluon/trainer.py": _ALL,
    "mxnet_tpu/ndarray/pending.py": _ALL,
    "mxnet_tpu/telemetry.py": _ALL,
    # the fleet observability plane: the collector runs OFF the serving
    # hot path, but its span-stamping hooks live inside the router tick
    # and the scheduler's deferred retirements — everything here must
    # be host wall clocks and wire payloads; the sanctioned float()s
    # are config scalars and already-transferred wire values, each
    # sync-ok annotated.
    "mxnet_tpu/telemetry_fleet.py": _ALL,
    # the training-health plane: stat rows are computed ON DEVICE inside
    # the fused step and reach the host only through the InflightWindow's
    # deferred value channel — HealthMonitor.consume / the detectors run
    # at window retirement over rows that are already host data, and the
    # rules engine reads registry scalars. The annotated reads are those
    # retired rows and host rule params; an UNMARKED read here would
    # mean the Monitor heritage crept back in (a per-step gradient peek).
    "mxnet_tpu/health.py": _ALL,
    "mxnet_tpu/gluon/contrib/estimator.py": _ALL,
    "mxnet_tpu/monitor.py": _TRANSFER,
    "mxnet_tpu/metric.py": [r"\.asnumpy\(", r"\.asscalar\(",
                            r"block_until_ready"],
    # the tuning layer sits NEXT to the hot path: kernel-config lookups
    # run inside dispatch and read nothing from the device (nothing is
    # timed there: the cost model chooses)
    "mxnet_tpu/tuning/__init__.py": _ALL,
    "mxnet_tpu/tuning/table.py": _ALL,
    "mxnet_tpu/tuning/autotune.py": _ALL,
    "mxnet_tpu/tuning/warmup.py": _ALL,
    "mxnet_tpu/tuning/compile_cache.py": _ALL,
    # the GSPMD sharded-step layer: the step itself is ONE launch with
    # zero reads, so any sync here is control-plane by construction —
    # mesh setup, checkpoint spill/restore for the elastic reshard
    # transfer format, cross-process reduce re-entry, and rare cursor
    # reads. Each carries its sync-ok justification; an UNMARKED read
    # would mean the per-step path started syncing.
    "mxnet_tpu/parallel/mesh.py": _ALL,
    "mxnet_tpu/parallel/sharded.py": _ALL,
    "mxnet_tpu/parallel/reshard.py": _ALL,
    # the 4D composition: pipeline schedule + MoE routing run INSIDE the
    # one donated step program, and the router accounting accumulates in
    # device-resident aux params — the only sanctioned reads are the
    # windowed publish_moe_telemetry transfer (sync-ok marked) and
    # nothing else.
    "mxnet_tpu/parallel/unified.py": _ALL,
    # the serving decode loop IS a hot path with an SLO: scheduler ticks
    # and cache bookkeeping run between every decode dispatch, so one
    # stray read there re-synchronizes every token of every request.
    # Tokens/flags leave the device ONLY through the InflightWindow's
    # deferred protocol (one stacked read per K steps) and the
    # per-request prefill PendingValue. model.py's reference_decode is
    # the parity oracle and marks its per-step read sync-ok.
    # kvstore's sparse paths: _merge now reduces row_sparse lists over
    # the index union ON DEVICE, and the dist_embedding row push/pull
    # runs between every sparse step — the intended syncs left are the
    # network-serialization boundaries (a frame must be host bytes) and
    # host config scalars, each annotated.
    "mxnet_tpu/kvstore.py": _ALL,
    # the sharded embedding client/cache sit on the per-step sparse
    # path: row ids are host metadata by design (routing is control
    # plane), and row values leave the device only at the RPC
    # serialization boundary — any UNMARKED read means the cache
    # started round-tripping device rows per lookup.
    "mxnet_tpu/embedding/__init__.py": _ALL,
    "mxnet_tpu/embedding/hashing.py": _ALL,
    "mxnet_tpu/embedding/cache.py": _ALL,
    "mxnet_tpu/embedding/client.py": _ALL,
    "mxnet_tpu/embedding/store.py": _ALL,
    # the streaming data plane: decode WORKERS do host-side numpy by
    # design (that layer is the one place host memory is supposed to be
    # touched — JPEG decode + augment + batchify), so their intentional
    # host reads are sync-ok annotated at the worker boundary. The FEED
    # path (loader.py into _DevicePrefetcher) and the lease ledger
    # (host-integer bookkeeping + wire frames) must stay lint-clean: a
    # stray device read there re-serializes the consumer against every
    # batch.
    "mxnet_tpu/data_plane/__init__.py": _ALL,
    "mxnet_tpu/data_plane/manifest.py": _ALL,
    "mxnet_tpu/data_plane/ledger.py": _ALL,
    "mxnet_tpu/data_plane/workers.py": _ALL,
    "mxnet_tpu/data_plane/loader.py": _ALL,
    "mxnet_tpu/serving/__init__.py": _ALL,
    "mxnet_tpu/serving/engine.py": _ALL,
    "mxnet_tpu/serving/scheduler.py": _ALL,
    "mxnet_tpu/serving/kv_cache.py": _ALL,
    "mxnet_tpu/serving/model.py": _ALL,
    "mxnet_tpu/serving/metrics.py": _ALL,
    # shared-prefix reuse is an ADMISSION-time feature: the blake2b
    # chain hashes host token lists (annotated at the one asarray),
    # and index bookkeeping is pure host dict/tuple work — the decode
    # loop never consults it, so any unmarked device read here would
    # mean prefix lookups started syncing the hot path
    "mxnet_tpu/serving/prefix.py": _ALL,
    # the speculative round is TWO traced programs per k committed
    # tokens; the accepted-prefix commit is device-side by design, so
    # any unmarked read here would mean the host started peeking at
    # accept counts per round — exactly the sync class the staged
    # (B, k+1) row protocol exists to avoid
    "mxnet_tpu/serving/speculative.py": _ALL,
    # the fleet router sits ABOVE the decode hot path but runs between
    # every decode tick of every replica: routing decisions must be
    # host arithmetic on gauges and wall clocks, never a device read —
    # one stray sync here re-serializes the whole fleet's pipelines.
    # Control-plane scalars (config values, fault-rule params) are the
    # only sanctioned float()s, each sync-ok annotated.
    "mxnet_tpu/serving/fleet.py": _ALL,
    "mxnet_tpu/serving/router.py": _ALL,
    # the autoscaler's control loop and the QoS admission gate run
    # between decode ticks of the whole fleet: both must stay pure
    # host arithmetic over already-merged gauges/histograms — a device
    # read (or a blocking scrape) inside either would stall every
    # replica once per control period, turning the thing that absorbs
    # flash crowds into the thing that causes them
    "mxnet_tpu/serving/autoscaler.py": _ALL,
    "mxnet_tpu/serving/qos.py": _ALL,
}

_MARKER = "sync-ok"


def _strip_docstrings(lines):
    """Yield (lineno, line) for lines outside triple-quoted strings (a
    coarse tracker — good enough for these modules' style)."""
    in_doc = False
    for i, line in enumerate(lines, 1):
        quotes = line.count('"""') + line.count("'''")
        if in_doc:
            if quotes % 2 == 1:
                in_doc = False
            continue
        if quotes % 2 == 1:
            in_doc = True
            continue
        if quotes and quotes % 2 == 0:
            continue  # one-line docstring
        yield i, line


def check(root):
    """[(path, lineno, line)] of unmarked sync constructs."""
    bad = []
    for rel, patterns in sorted(SCAN.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            bad.append((rel, 0, "<hot-path module missing>"))
            continue
        regexes = [re.compile(p) for p in patterns]
        with open(path) as f:
            lines = f.read().splitlines()
        for lineno, line in _strip_docstrings(lines):
            code = line.split("#", 1)[0]
            if not code.strip():
                continue
            if _MARKER in line:
                continue
            for rx in regexes:
                if rx.search(code):
                    bad.append((rel, lineno, line.strip()))
                    break
    return bad


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    root = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    bad = check(root)
    if bad:
        print("check_host_syncs: %d unmarked host-sync point(s) in the "
              "async hot path:" % len(bad))
        for rel, lineno, line in bad:
            print("  %s:%d: %s" % (rel, lineno, line))
        print("route the read through the deferred protocol "
              "(ndarray/pending.py / engine.StepStream), or mark an "
              "intentional sync with `# sync-ok: <reason>`.")
        return 1
    print("check_host_syncs: hot path clean (%d modules)" % len(SCAN))
    return 0


if __name__ == "__main__":
    sys.exit(main())
