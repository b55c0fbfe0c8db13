"""Versioned on-disk tuning table — the per-shape kernel-config store.

The reference framework ships MXNET_CUDNN_AUTOTUNE_DEFAULT: the first
convolution at a new shape races every cuDNN algo and the winner is
memoized per shape for the life of the process. This module is that
memo made durable and explicit: every decision the cost model makes
(``autotune.py``; nothing is raced here) or a sweep's author writes by
hand as ``measured`` — flash-attention (block_q, block_k), the paged
kernel's head block, and the XLA-vs-Pallas backend choice — is keyed by

    (op, shape-bucket, dtype, causal, device_kind)

and stored in one JSON file (``MXT_TUNE_TABLE``), versioned so a stale
or corrupted table degrades to the heuristic cost model instead of
crashing or silently mis-tiling. The same file carries the **shape
signatures** recorded at kernel/step dispatch, which
``tuning.warmup()`` replays to AOT-compile a fresh process's hot path.

Shape bucketing bounds table growth: query/key sequence lengths round
up to the next multiple of 64 (exact below 64), row counts to the
next power of two. A config chosen for the bucket is tiling-legal for
every shape inside it because the kernels pad-and-mask to block
multiples — bucketing only costs (bounded, modeled) padding waste.

Lookups bump ``mxt_tune_cache_hits_total`` / ``_misses_total`` so a
serving replica's warm/cold tuning state is visible in ``mxt_top`` and
the bench rows.
"""
from __future__ import annotations

import json
import os
import threading

TABLE_VERSION = 1

_MAX_SIGNATURES = 64  # per entry point — warmup replay stays bounded


def _config():
    from .. import config

    return config


def _telemetry():
    from .. import telemetry

    return telemetry


def device_kind():
    """Tuning-key device identity: configs measured on one chip
    generation must not be served to another (or to CPU)."""
    import jax

    kind = jax.devices()[0].device_kind
    return str(kind).replace(" ", "_").replace("|", "_")


def bucket_seq(t):
    """Sequence-length bucket: exact below 64, else next multiple of 64
    (ceil(t/64) distinct buckets — bounded growth, bounded padding)."""
    t = int(t)
    if t <= 64:
        return t
    return -(-t // 64) * 64


def bucket_rows(m):
    """Row bucket: next power of two (batch x heads, decode slots and
    page-table widths can be anything; pow2 keeps the table tiny)."""
    m = int(m)
    p = 1
    while p < m:
        p <<= 1
    return p


def attn_key(q_shape, kv_len, dtype, causal, kind=None, kv_heads=None,
             masked=False):
    """``kv_heads`` (where they are fewer than the query's) and ``masked``
    (a selection mask) get fields of their own, so an entry tuned for a
    dense call is never taken for a grouped or a masked one; a dense call's
    key is what it always was."""
    b, h, tq, d = q_shape
    key = "flash|bh%d|q%d|k%d|d%d|%s|c%d" % (
        bucket_rows(b * h), bucket_seq(tq), bucket_seq(kv_len), d,
        str(dtype), 1 if causal else 0)
    if kv_heads not in (None, h):
        key += "|g%d" % (h // kv_heads)
    if masked:
        key += "|m1"
    return "%s|%s" % (key, kind or device_kind())


def paged_key(q_shape, page_size, max_pages, dtype, kind=None):
    """Decode-shape bucket for the ragged paged attention kernel: batch
    slots round to the next power of two, the page-table width (context
    capacity) likewise — a serving engine growing a sequence page by
    page must not churn new table entries every page."""
    b, h, d = q_shape
    return "paged|b%d|h%d|d%d|s%d|p%d|%s|%s" % (
        bucket_rows(b), int(h), int(d), int(page_size),
        bucket_rows(max_pages), str(dtype), kind or device_kind())


def quant_key(op, k, n, dtype, kind=None):
    """Quantized-vs-float kernel bucket for one decode matmul shape:
    (reduction k, output n) both round to the next power of two — the
    same bounded-growth discipline as paged_key, keyed per device kind
    because the int8 win is a memory-bandwidth property of the chip."""
    return "quant|%s|k%d|n%d|%s|%s" % (
        str(op), bucket_rows(k), bucket_rows(n), str(dtype),
        kind or device_kind())


class TuneTable:
    """One process's view of the tuning table: entries + signatures,
    loaded from ``path`` when it exists (corrupted/stale files are
    ignored with a note — the heuristic path keeps working), saved
    atomically (tmp + fsync + replace, the checkpoint idiom)."""

    def __init__(self, path=None):
        self.path = path
        self.load_error = None
        self._lock = threading.Lock()
        self._entries = {}
        self._signatures = {}
        self._dirty = False
        if path and os.path.exists(path):
            self._load(path)

    def _load(self, path):
        try:
            with open(path) as f:
                raw = json.load(f)
            if not isinstance(raw, dict):
                raise ValueError("tune table root is not an object")
            if raw.get("version") != TABLE_VERSION:
                raise ValueError("tune table version %r != %d"
                                 % (raw.get("version"), TABLE_VERSION))
            entries = raw.get("entries", {})
            sigs = raw.get("signatures", {})
            if not isinstance(entries, dict) or not isinstance(sigs, dict):
                raise ValueError("tune table sections malformed")
            self._entries = {str(k): dict(v) for k, v in entries.items()
                             if isinstance(v, dict)}
            self._signatures = {str(k): list(v)[:_MAX_SIGNATURES]
                                for k, v in sigs.items()
                                if isinstance(v, list)}
        except (OSError, ValueError, TypeError) as e:
            # a bad table must never take training down: note it, start
            # empty, and let the heuristic cost model answer everything
            self.load_error = "%s: %s" % (type(e).__name__, e)
            self._entries = {}
            self._signatures = {}
            _telemetry().counter(
                "mxt_tune_table_load_errors_total",
                "Tune-table files ignored as corrupted/stale.").inc()

    # -- decisions --------------------------------------------------------
    def lookup(self, key):
        """The stored config dict for ``key`` (None = miss). Every call
        lands in the tune-cache hit/miss counters."""
        with self._lock:
            ent = self._entries.get(key)
        _telemetry().record_tune_lookup(hit=ent is not None)
        return dict(ent) if ent is not None else None

    def peek(self, key):
        """lookup() without touching the hit/miss counters (tests,
        introspection)."""
        with self._lock:
            ent = self._entries.get(key)
        return dict(ent) if ent is not None else None

    def record(self, key, entry):
        """Store a decision. ``source`` ('measured'/'heuristic') rides
        the entry; a measured entry is never downgraded by a heuristic
        re-record for the same key."""
        with self._lock:
            old = self._entries.get(key)
            if old is not None and old.get("source") == "measured" \
                    and entry.get("source") != "measured":
                return dict(old)
            self._entries[key] = dict(entry)
            self._dirty = True
        return dict(entry)

    def entries(self):
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    # -- warmup signatures ------------------------------------------------
    def record_signature(self, entry_point, spec):
        """Remember one dispatched shape signature (dict, JSON-able) for
        ``entry_point`` — the AOT warm-start replay list. Deduplicated;
        bounded per entry point."""
        spec = dict(spec)
        with self._lock:
            sigs = self._signatures.setdefault(str(entry_point), [])
            if spec in sigs:
                return False
            if len(sigs) >= _MAX_SIGNATURES:
                return False
            sigs.append(spec)
            self._dirty = True
        return True

    def signatures(self, entry_point=None):
        with self._lock:
            if entry_point is not None:
                return [dict(s) for s in
                        self._signatures.get(str(entry_point), [])]
            return {k: [dict(s) for s in v]
                    for k, v in self._signatures.items()}

    # -- persistence ------------------------------------------------------
    @property
    def dirty(self):
        return self._dirty

    def save(self, path=None):
        """Atomically write the table. Returns the path written, or None
        when there is nowhere to write (no path configured)."""
        path = path or self.path
        if not path:
            return None
        with self._lock:
            payload = {"version": TABLE_VERSION,
                       "entries": dict(self._entries),
                       "signatures": dict(self._signatures)}
            self._dirty = False
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=0, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self.path = path
        return path


_table = None
_table_path = None
_table_lock = threading.Lock()


def table():
    """The process-default TuneTable, bound to the CURRENT
    ``MXT_TUNE_TABLE`` value — a path change (tests, sweeps) swaps in a
    fresh instance loaded from the new file."""
    global _table, _table_path
    path = _config().get("MXT_TUNE_TABLE")
    if _table is not None and path == _table_path:
        return _table
    with _table_lock:
        if _table is None or path != _table_path:
            if _table is not None and _table.dirty:
                try:
                    _table.save()
                except OSError:
                    pass  # old location gone: decisions were best-effort
            _table = TuneTable(path)
            _table_path = path
    return _table


def reset():
    """Drop the in-memory table (tests). The on-disk file is untouched;
    the next table() call reloads it."""
    global _table, _table_path
    with _table_lock:
        _table = None
        _table_path = None


def save():
    """Persist the default table if a path is configured."""
    return table().save()
