"""Runtime telemetry — typed metrics registry, step-phase spans,
distributed RPC tracing, and live export.

PRs 1-4 made the hot path *opaque by design*: one fused XLA launch per
step, a K-deep in-flight dispatch window, deferred host reads, and a
membership/KVStore layer that retries, fences, and renormalizes silently.
Understanding fused/compiled execution requires deliberate
instrumentation of launch behavior and phase timing ("Operator Fusion in
XLA: Analysis and Evaluation", PAPERS.md §fusion) — a handful of ad-hoc
scalar counters cannot answer "where did this step's time go" or "which
worker's RPC is slow". This module is the machine-readable layer under
``mx.profiler``:

1. **Typed metrics registry.** :class:`Counter` / :class:`Gauge` /
   :class:`Histogram` families with labels, created through one
   :class:`MetricsRegistry` (name-deduplicated, type-checked). Histograms
   use fixed log-scale buckets, are lock-guarded (observations arrive
   from the dispatch thread, deferred-read callbacks, and server
   connection threads), and are mergeable across instances. The old
   ``profiler._counters``/``_gauges`` dicts are now live views over this
   registry — ``profiler.counter_value``/``set_gauge`` keep working as
   shims.

2. **Step-phase spans.** The fused train paths record a per-step
   timeline — ``data_wait`` (DataLoader), ``dispatch`` (host work to
   launch the fused program), ``in_flight``/``retire`` (engine.StepStream
   token retirement) — as phase histograms plus optional JSONL span
   events. Retirement latency is measured from the timestamps the engine
   already keeps and lands inside the existing PendingValue
   materialization, so telemetry adds ZERO host syncs to the hot path
   (enforced statically by tools/check_host_syncs.py, which scans this
   module too).

3. **Distributed RPC tracing.** :func:`trace_scope` installs an ambient
   ``trace_id``; every async-server frame carries
   ``(trace_id, span_id, attempt)`` so a KVStore push/pull, membership
   heartbeat/register, or elastic rendezvous is correlatable end-to-end.
   Both sides record per-op latency/bytes/retry/fence metrics through
   :func:`record_rpc` and append to a bounded in-memory span log
   (:func:`rpc_spans`) plus the JSONL sink.

4. **Export.** ``MXT_TELEMETRY_JSONL=path`` activates a buffered
   JSONL event/metric sink (writer thread; ``flush()`` is called by
   ``nd.waitall()`` and the estimator at epoch end).
   :func:`render_prometheus` produces the text exposition format and
   ``MXT_TELEMETRY_PORT`` serves it from a stdlib HTTP endpoint
   (loopback-only — the async-server threat model applies to anything
   that listens). ``tools/mxt_top.py`` tails either and renders a live
   console.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import queue
import re
import threading
import time

from .base import MXNetError

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "counter", "gauge", "histogram", "render_prometheus",
    "registry_export",
    "emit_event", "flush", "jsonl_path",
    "add_event_tap", "remove_event_tap",
    "record_phase", "record_dispatch", "record_step_retired",
    "record_compile", "record_compile_cache", "record_tune_lookup",
    "record_flash_fwd", "flash_fwd_branches",
    "record_flash_bwd", "flash_bwd_branches",
    "record_gated_conv", "gated_conv_branches",
    "record_causal_conv", "causal_conv_branches",
    "record_ssd", "ssd_branches",
    "record_delta_rule", "delta_rule_branches",
    "record_embedding_grad", "embedding_grad_branches",
    "record_grouped_matmul", "grouped_matmul_branches",
    "record_row_movement", "row_movement_branches",
    "record_flash_heads", "flash_heads_per_step",
    "record_flash_window_blocks", "flash_window_blocks",
    "record_flash_layout", "flash_layouts",
    "record_moe_counts", "moe_counts",
    "record_selection_counts", "selection_counts",
    "trace_scope", "current_trace_id", "new_trace_id", "new_span_id",
    "record_rpc", "rpc_spans", "clear_rpc_spans",
    "record_trace_span", "trace_spans", "clear_trace_spans",
    "start_http_server", "http_port", "histogram_quantile",
    "sanitize_metric_name",
]


# --------------------------------------------------------------------------
# metric families
# --------------------------------------------------------------------------
_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def sanitize_metric_name(name):
    """Coerce an arbitrary string (e.g. a profiler counter name) into a
    valid Prometheus metric name."""
    name = _NAME_RE.sub("_", str(name))
    if not name or name[0].isdigit():
        name = "_" + name
    return name


def _fmt(v):
    """Numeric rendering: integral values print without a decimal point
    so counters read naturally ('value=3', not 'value=3.0')."""
    s = "%.10g" % v
    return s


class _ScalarChild:
    """One (labelset, value) cell of a Counter/Gauge family."""

    __slots__ = ("_lock", "_v")

    def __init__(self):
        self._lock = threading.Lock()
        self._v = 0.0

    def inc(self, n=1):
        with self._lock:
            self._v += n

    def dec(self, n=1):
        self.inc(-n)

    def set(self, v):
        with self._lock:
            self._v = v

    def reset(self):
        """Zero the cell; returns the previous value (the profiler's
        reset_*_count shims ride this)."""
        with self._lock:
            prev, self._v = self._v, 0.0
        return prev

    @property
    def value(self):
        return self._v

    def merge(self, other):
        self.inc(other.value)


class _HistChild:
    """One labelset's bucket state: counts per bucket (+Inf last), sum,
    total count. Lock-guarded — observations arrive from many threads."""

    __slots__ = ("_lock", "_bounds", "counts", "sum", "count")

    def __init__(self, bounds):
        self._lock = threading.Lock()
        self._bounds = bounds
        self.counts = [0] * (len(bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v):
        i = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def snapshot(self):
        with self._lock:
            return {"buckets": tuple(self._bounds),
                    "counts": list(self.counts),
                    "sum": self.sum, "count": self.count}

    def merge(self, other):
        """Fold another child (or snapshot dict) with IDENTICAL buckets
        into this one — the cross-instance aggregation primitive."""
        snap = other.snapshot() if hasattr(other, "snapshot") else other
        if tuple(snap["buckets"]) != tuple(self._bounds):
            raise MXNetError(
                "cannot merge histograms with different buckets")
        with self._lock:
            for i, c in enumerate(snap["counts"]):
                self.counts[i] += c
            self.sum += snap["sum"]
            self.count += snap["count"]

    def quantile(self, q):
        return histogram_quantile(q, self._bounds, list(self.counts))


def histogram_quantile(q, bounds, counts):
    """Approximate quantile from per-bucket counts (``counts`` has one
    extra +Inf cell). Returns the upper bound of the bucket the rank
    falls in (log-scale buckets make this a <=4x estimate)."""
    total = sum(counts)
    if total == 0:
        return 0.0
    rank = q * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= rank and c:
            if i < len(bounds):
                return bounds[i]
            return bounds[-1] if bounds else 0.0
    return bounds[-1] if bounds else 0.0


class _Family:
    """A named metric with a fixed label schema; children are
    deduplicated per label-values tuple."""

    kind = None

    def __init__(self, name, help="", labelnames=()):
        self.name = sanitize_metric_name(name)
        self.help = str(help)
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._children = {}
        self._default = None

    def _make_child(self):
        raise NotImplementedError

    def labels(self, *values, **kv):
        """The child for one label-values set (created on first use,
        the SAME object on every later call — label dedup)."""
        if kv:
            if values:
                raise MXNetError("pass labels positionally or by name, "
                                 "not both")
            try:
                values = tuple(str(kv.pop(k)) for k in self.labelnames)
            except KeyError as e:
                raise MXNetError("metric %s is missing label %s"
                                 % (self.name, e)) from e
            if kv:
                raise MXNetError("metric %s has no label(s) %s"
                                 % (self.name, sorted(kv)))
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise MXNetError(
                "metric %s takes labels %s, got %d value(s)"
                % (self.name, self.labelnames, len(values)))
        with self._lock:
            ch = self._children.get(values)
            if ch is None:
                ch = self._children[values] = self._make_child()
            return ch

    @property
    def default(self):
        """The no-labels child (only valid for an unlabeled family)."""
        ch = self._default
        if ch is None:
            ch = self._default = self.labels()
        return ch

    def children(self):
        with self._lock:
            return dict(self._children)


class Counter(_Family):
    """Monotonically increasing count (``reset()`` exists only for the
    profiler shims' reset semantics)."""

    kind = "counter"

    def _make_child(self):
        return _ScalarChild()

    def inc(self, n=1):
        self.default.inc(n)

    def reset(self):
        return self.default.reset()

    @property
    def value(self):
        return self.default.value


class Gauge(_Family):
    """Point-in-time value."""

    kind = "gauge"

    def _make_child(self):
        return _ScalarChild()

    def set(self, v):
        self.default.set(v)

    def inc(self, n=1):
        self.default.inc(n)

    def dec(self, n=1):
        self.default.dec(n)

    @property
    def value(self):
        return self.default.value


# log-scale bounds covering 1 microsecond .. ~18 minutes in x4 steps —
# wide enough for a host-side phase (~us), a fused step (~ms), a
# cross-host RPC (~100ms), and a checkpoint/epoch (~minutes)
DEFAULT_BUCKETS = tuple(1e-6 * 4.0 ** i for i in range(16))


class Histogram(_Family):
    """Fixed-bucket (log-scale by default) distribution."""

    kind = "histogram"

    def __init__(self, name, help="", labelnames=(),
                 buckets=DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames)
        bounds = tuple(sorted(set(buckets)))
        if not bounds:
            raise MXNetError("histogram %s needs at least one bucket "
                             "bound" % self.name)
        self.buckets = bounds

    def _make_child(self):
        return _HistChild(self.buckets)

    def observe(self, v):
        self.default.observe(v)

    def merge(self, other):
        """Fold another family's children into this one (same buckets,
        matching label schema)."""
        if getattr(other, "buckets", None) != self.buckets:
            raise MXNetError(
                "cannot merge histograms with different buckets")
        for values, child in other.children().items():
            self.labels(*values).merge(child)

    def snapshot(self):
        return self.default.snapshot()

    def quantile(self, q):
        return self.default.quantile(q)


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class MetricsRegistry:
    """Name-keyed collection of metric families. ``counter/gauge/
    histogram`` are get-or-create: the same name returns the SAME family
    (a kind or label-schema mismatch is a hard error, not a silent
    second metric)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _get_or_create(self, cls, name, help, labelnames, **kw):
        name = sanitize_metric_name(name)
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls:
                    raise MXNetError(
                        "telemetry metric %r is already registered as a "
                        "%s, not a %s" % (name, m.kind, cls.kind))
                if m.labelnames != tuple(labelnames):
                    raise MXNetError(
                        "telemetry metric %r is already registered with "
                        "labels %s" % (name, m.labelnames))
                if kw.get("buckets") is not None and \
                        tuple(sorted(set(kw["buckets"]))) != m.buckets:
                    raise MXNetError(
                        "telemetry histogram %r is already registered "
                        "with different buckets" % name)
                return m
            m = cls(name, help, labelnames, **{k: v for k, v in kw.items()
                                               if v is not None})
            self._metrics[name] = m
            return m

    def counter(self, name, help="", labelnames=()):
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()):
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(), buckets=None):
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def get(self, name, default=None):
        with self._lock:
            return self._metrics.get(sanitize_metric_name(name), default)

    def unregister(self, name):
        """Drop a family (the profiler's dumps(reset=True) shim)."""
        with self._lock:
            self._metrics.pop(sanitize_metric_name(name), None)

    def collect(self):
        """[(family, {labelvalues: child})] sorted by name — one
        consistent snapshot of the family LIST (children snapshot
        individually under their own locks)."""
        with self._lock:
            fams = sorted(self._metrics.values(), key=lambda m: m.name)
        return [(m, m.children()) for m in fams]

    def snapshot_values(self):
        """Compact {name: value | {'count','sum'}} dict (the JSONL
        metrics row)."""
        out = {}
        for fam, children in self.collect():
            for values, ch in sorted(children.items()):
                key = fam.name if not values else \
                    "%s{%s}" % (fam.name, ",".join(
                        "%s=%s" % kv for kv in zip(fam.labelnames, values)))
                if fam.kind == "histogram":
                    snap = ch.snapshot()
                    out[key] = {"count": snap["count"],
                                "sum": round(snap["sum"], 9)}
                else:
                    out[key] = ch.value
        return out

    def export(self):
        """Serializable full-registry snapshot — the ``tel_snapshot``
        wire payload the fleet collector (telemetry_fleet.py) scrapes:
        one dict per family (name/kind/help/labelnames, histogram
        buckets) with every child's current value or bucket snapshot.
        Pure host data; picklable and JSON-able."""
        fams = []
        for fam, children in self.collect():
            d = {"name": fam.name, "kind": fam.kind, "help": fam.help,
                 "labelnames": list(fam.labelnames)}
            if fam.kind == "histogram":
                d["buckets"] = list(fam.buckets)
            ch = []
            for values, child in sorted(children.items()):
                if fam.kind == "histogram":
                    snap = child.snapshot()
                    ch.append([list(values),
                               {"counts": list(snap["counts"]),
                                "sum": snap["sum"],
                                "count": snap["count"]}])
                else:
                    ch.append([list(values), child.value])
            d["children"] = ch
            fams.append(d)
        return {"ts": round(time.time(), 6), "families": fams}

    def render_prometheus(self):
        """Text exposition format (the /metrics payload)."""
        lines = []
        for fam, children in self.collect():
            if fam.help:
                lines.append("# HELP %s %s"
                             % (fam.name, fam.help.replace("\n", " ")))
            lines.append("# TYPE %s %s" % (fam.name, fam.kind))
            for values, ch in sorted(children.items()):
                base = _label_str(fam.labelnames, values)
                if fam.kind == "histogram":
                    snap = ch.snapshot()
                    cum = 0
                    for bound, c in zip(snap["buckets"], snap["counts"]):
                        cum += c
                        lines.append("%s_bucket%s %d" % (
                            fam.name,
                            _label_str(fam.labelnames + ("le",),
                                       values + (_fmt(bound),)), cum))
                    lines.append("%s_bucket%s %d" % (
                        fam.name,
                        _label_str(fam.labelnames + ("le",),
                                   values + ("+Inf",)), snap["count"]))
                    lines.append("%s_sum%s %s" % (fam.name, base,
                                                  _fmt(snap["sum"])))
                    lines.append("%s_count%s %d" % (fam.name, base,
                                                    snap["count"]))
                else:
                    lines.append("%s%s %s" % (fam.name, base,
                                              _fmt(ch.value)))
        return "\n".join(lines) + "\n"


def _label_str(names, values):
    if not names:
        return ""
    esc = [str(v).replace("\\", "\\\\").replace('"', '\\"')
           .replace("\n", "\\n") for v in values]
    return "{%s}" % ",".join('%s="%s"' % (n, v)
                             for n, v in zip(names, esc))


_REGISTRY = MetricsRegistry()


def registry():
    """The process-default registry (what render_prometheus and the
    profiler shims use)."""
    return _REGISTRY


def counter(name, help="", labelnames=()):
    return _REGISTRY.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()):
    return _REGISTRY.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(), buckets=None):
    return _REGISTRY.histogram(name, help, labelnames, buckets=buckets)


def render_prometheus():
    return _REGISTRY.render_prometheus()


def registry_export():
    """The process registry as a serializable snapshot (what the
    ``tel_snapshot`` async-server op answers with)."""
    return _REGISTRY.export()


# --------------------------------------------------------------------------
# JSONL event sink
# --------------------------------------------------------------------------
_STOP = object()


class JsonlSink:
    """Buffered JSONL writer: ``emit`` enqueues (never blocks the hot
    path — overflow drops and counts), a daemon thread writes, and
    ``flush`` round-trips a marker through the queue so everything
    enqueued before it is durably on disk."""

    def __init__(self, path):
        self.path = path
        self._q = queue.Queue(maxsize=100000)
        self.dropped = 0
        self._file = open(path, "a")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="mxt-telemetry-jsonl")
        self._thread.start()

    def emit(self, row):
        try:
            self._q.put_nowait(row)
        except queue.Full:
            self.dropped += 1

    def _loop(self):
        while True:
            item = self._q.get()
            if item is _STOP:
                self._file.flush()
                return
            if isinstance(item, threading.Event):
                self._file.flush()
                item.set()
                continue
            try:
                self._file.write(json.dumps(item) + "\n")
            except (TypeError, ValueError):
                self.dropped += 1  # non-serializable row: drop, keep going

    def flush(self, timeout=10.0):
        """Block until every row enqueued before this call is written
        and the file is flushed."""
        if not self._thread.is_alive():
            return
        ev = threading.Event()
        self._q.put(ev)
        ev.wait(timeout)

    def close(self):
        self._q.put(_STOP)
        self._thread.join(timeout=10.0)
        try:
            self._file.close()
        except OSError:
            pass


_sink_lock = threading.Lock()
_sink = None
_sink_path = None


def _active_sink():
    """The JSONL sink for the CURRENT ``MXT_TELEMETRY_JSONL`` value —
    re-reading the config each call keeps tests (monkeypatched env) and
    long-lived processes honest; path changes swap the sink."""
    global _sink, _sink_path
    from . import config

    path = config.get("MXT_TELEMETRY_JSONL")
    if path == _sink_path:
        return _sink
    with _sink_lock:
        if path != _sink_path:
            old, _sink, _sink_path = _sink, None, path
            if old is not None:
                old.close()
            if path:
                _sink = JsonlSink(path)
    return _sink


def jsonl_path():
    s = _active_sink()
    return s.path if s is not None else None


# event taps: callables fed every event row BEFORE the JSONL sink —
# the diagnostics flight recorder rides one, so every existing event
# source (spans, RPC spans, membership/reshard/checkpoint events) lands
# in the post-mortem ring without any source changing. Taps are host
# bookkeeping and must never raise into the emitter.
_event_taps = []


def add_event_tap(fn):
    if fn not in _event_taps:
        _event_taps.append(fn)


def remove_event_tap(fn):
    try:
        _event_taps.remove(fn)
    except ValueError:
        pass


def _events_active():
    """True when building an event row has a consumer (sink or tap)."""
    return _event_taps or _active_sink() is not None


def _dispatch_row(row):
    for fn in list(_event_taps):
        try:
            fn(row)
        except Exception:  # noqa: BLE001 — a broken tap must not stop events
            pass
    s = _active_sink()
    if s is not None:
        s.emit(row)


def emit_event(kind, **fields):
    """Queue one event row to the taps + JSONL sink (no-op when neither
    is active)."""
    if not _events_active():
        return
    row = {"ts": round(time.time(), 6), "kind": str(kind)}
    row.update(fields)
    _dispatch_row(row)


def flush(write_metrics=False):
    """Flush the JSONL sink (called by ``nd.waitall()`` and the
    estimator at epoch end). ``write_metrics=True`` also appends one
    compact metrics-snapshot row before flushing."""
    s = _active_sink()
    if s is None:
        return
    if write_metrics:
        s.emit({"ts": round(time.time(), 6), "kind": "metrics",
                "data": _REGISTRY.snapshot_values()})
    s.flush()


# --------------------------------------------------------------------------
# step-phase spans
# --------------------------------------------------------------------------
_phase_hist = None
_latency_hist = None
_depth_hist = None


def record_phase(phase, seconds, stream=None, step=None):
    """One step-phase observation: ``data_wait`` / ``dispatch`` /
    ``in_flight`` / ``retire``. Lands in the
    ``mxt_step_phase_seconds{phase=}`` histogram and (sink active) a
    JSONL span event. Host-side wall clock only — never a device read."""
    global _phase_hist
    h = _phase_hist
    if h is None:
        h = _phase_hist = histogram(
            "mxt_step_phase_seconds",
            "Per-step phase timing: data_wait -> dispatch -> in_flight "
            "-> retire.", ("phase",))
    h.labels(phase).observe(seconds)
    if _events_active():
        emit_event("span", name=str(phase), stream=stream, step=step,
                   seconds=round(seconds, 9))


def record_dispatch(stream, step, depth):
    """Dispatch-depth occupancy at the moment a fused step was pushed
    into the engine window."""
    global _depth_hist
    h = _depth_hist
    if h is None:
        h = _depth_hist = histogram(
            "mxt_dispatch_depth_occupancy",
            "In-flight fused steps at each dispatch (window occupancy).",
            buckets=tuple(range(1, 17)))
    h.observe(depth)
    if _events_active():
        emit_event("span", name="dispatch", stream=stream, step=step,
                   depth=depth)


def record_step_retired(stream, step, latency_s):
    """One fused step observed on host: dispatch->retire latency,
    measured inside the engine's EXISTING deferred read (zero new
    syncs). Exactly one of these per dispatched step."""
    global _latency_hist
    h = _latency_hist
    if h is None:
        h = _latency_hist = histogram(
            "mxt_step_latency_seconds",
            "Fused-step dispatch->retire latency (how long a step rode "
            "the in-flight window).", ("stream",))
    h.labels(stream).observe(latency_s)
    record_phase("in_flight", latency_s, stream=stream, step=step)
    if _events_active():
        emit_event("span", name="retire", stream=stream, step=step,
                   latency_s=round(latency_s, 9))


# --------------------------------------------------------------------------
# compile + tuning observability (fed by tuning/compile_cache.py's
# jax.monitoring listeners and tuning/table.py lookups)
# --------------------------------------------------------------------------
_compile_hist = None
_compile_total = None
_compile_cache_c = None
_tune_cache_c = None


def record_compile(phase, seconds):
    """One XLA compilation-pipeline phase observation
    (``trace``/``lower``/``compile``) — lands in
    ``mxt_compile_seconds{phase=}``; the ``compile`` phase also bumps
    ``mxt_compiles_total``. Cold-vs-warm cost in one histogram: a
    persistent-cache hit still reports here, as a ~ms deserialization
    instead of a full XLA run."""
    global _compile_hist, _compile_total
    if _compile_hist is None:
        _compile_hist = histogram(
            "mxt_compile_seconds",
            "JIT pipeline time per phase: trace (python->jaxpr), lower "
            "(jaxpr->StableHLO), compile (XLA backend, incl. persistent-"
            "cache deserialization on hits).", ("phase",))
        _compile_total = counter(
            "mxt_compiles_total",
            "Compiled-program builds dispatched to the XLA backend "
            "(persistent-cache hits included; see "
            "mxt_compile_cache_misses_total for true JIT compiles).")
    _compile_hist.labels(phase).observe(seconds)
    if phase == "compile":
        _compile_total.inc()
    # compile time is lost wall-clock: the diagnostics goodput ledger
    # (and the flight recorder) consume this via the event taps
    if _events_active():
        emit_event("compile", phase=str(phase),
                   seconds=round(seconds, 9))


def record_compile_cache(hit):
    """One persistent-compilation-cache outcome. A warm-started process
    shows hits only; a hot loop showing misses is paying JIT on the
    request path — the exact regression the warmup contract forbids."""
    global _compile_cache_c
    if _compile_cache_c is None:
        _compile_cache_c = counter(
            "mxt_compile_cache_total",
            "Persistent compilation cache lookups by outcome.",
            ("outcome",))
    _compile_cache_c.labels("hit" if hit else "miss").inc()


def record_tune_lookup(hit):
    """One tuning-table lookup outcome (mxt_tune_cache_hits_total /
    mxt_tune_cache_misses_total — a miss means the cost model chose for
    a new shape bucket)."""
    global _tune_cache_c
    if _tune_cache_c is None:
        _tune_cache_c = (
            counter("mxt_tune_cache_hits_total",
                    "Tuning-table lookups answered from the table."),
            counter("mxt_tune_cache_misses_total",
                    "Tuning-table lookups that fell through to "
                    "measurement or the heuristic cost model."))
    _tune_cache_c[0 if hit else 1].inc()


def _record_flash(direction, branch):
    counter("mxt_flash_%s_total" % direction,
            "Traced flash-attention %s passes by branch." % (
                "forward" if direction == "fwd" else "backward"),
            ("branch",)).labels(branch).inc()


def _branches(family):
    """{branch: count} of a counter family with the one label ``branch``."""
    fam = _REGISTRY.get(family)
    if fam is None:
        return {}
    return {values[0]: int(ch.value)
            for values, ch in sorted(fam.children().items())}


def _flash_branches(direction):
    return _branches("mxt_flash_%s_total" % direction)


def record_flash_fwd(branch):
    """One traced flash-attention forward, by the branch its dispatch took
    (``mxt_flash_fwd_total{branch=kernel|scan|reference}``; a call with a
    window counts under ``window_kernel`` / ``window_scan`` /
    ``window_reference``). Counted at
    trace time, as :func:`record_flash_bwd`: nothing enters the compiled
    step."""
    _record_flash("fwd", branch)


def flash_fwd_branches():
    """{branch: traces} of :func:`record_flash_fwd` so far."""
    return _flash_branches("fwd")


def record_flash_bwd(branch):
    """One traced flash-attention backward, by the branch its dispatch took
    (``mxt_flash_bwd_total{branch=kernel|chunked|materialised}``, and
    ``window_`` before each for a call with a window). Counted
    at trace time: once per compiled program that differentiates the op,
    not once a step."""
    _record_flash("bwd", branch)


def flash_bwd_branches():
    """{branch: traces} of :func:`record_flash_bwd` so far."""
    return _flash_branches("bwd")


def record_flash_window_blocks(kernel, visited, causal):
    """One traced window call of a flash kernel (``kernel``: ``fwd`` or
    ``bwd``): the (Q block, K/V block) tiles it visits and those the causal
    call of the same shapes and blocks visits
    (``mxt_flash_window_blocks_total{kernel, tiles=visited|causal}``), both
    from the shapes alone (``ops.attention.window_blocks``); a window call
    on an XLA branch, which bounds no loop, counts the chunks it walks on
    both sides. Counted at trace time, as the branches: nothing enters the
    compiled step."""
    fam = counter("mxt_flash_window_blocks_total",
                  "Tiles of traced window-attention kernels: visited, and "
                  "what the causal call visits.", ("kernel", "tiles"))
    fam.labels(kernel, "visited").inc(int(visited))
    fam.labels(kernel, "causal").inc(int(causal))


def flash_window_blocks():
    """{kernel: {"visited": tiles, "causal": tiles}} of
    :func:`record_flash_window_blocks` so far."""
    return _by_first_label("mxt_flash_window_blocks_total")


def record_flash_heads(kernel, heads):
    """One traced flash-attention kernel (``kernel``: ``fwd`` or ``bwd``) by
    the heads its grid step takes
    (``mxt_flash_heads_per_step{kernel,heads}``): how often several heads a
    step engage, and at how many. Counted at trace time, as the branches:
    nothing enters the compiled step."""
    counter("mxt_flash_heads_per_step",
            "Traced flash-attention kernels by heads a grid step.",
            ("kernel", "heads")).labels(kernel, str(int(heads))).inc()


def _by_first_label(family):
    """{first label: {second label: count}} of a counter family with two
    labels."""
    fam = _REGISTRY.get(family)
    out = {}
    if fam is not None:
        for (kernel, label), ch in sorted(fam.children().items()):
            out.setdefault(kernel, {})[label] = int(ch.value)
    return out


def flash_heads_per_step():
    """{kernel: {heads: traces}} of :func:`record_flash_heads` so far."""
    return _by_first_label("mxt_flash_heads_per_step")


def record_flash_layout(kernel, layout):
    """One traced flash-attention pass (``kernel``: ``fwd`` or ``bwd``) by
    the layout its operands lie in (``mxt_flash_layout_total{kernel,
    layout=in_place|heads_major}``): ``in_place`` where ``flash_attention_qkv``
    reads the fused projection as it lies, ``heads_major`` for every
    ``flash_attention`` call on (B, H, T, D) operands, a fallen-back
    ``flash_attention_qkv`` among them. Counted at trace time, as the
    branches: nothing enters the compiled step."""
    counter("mxt_flash_layout_total",
            "Traced flash-attention passes by operand layout.",
            ("kernel", "layout")).labels(kernel, layout).inc()


def flash_layouts():
    """{kernel: {layout: traces}} of :func:`record_flash_layout` so far."""
    return _by_first_label("mxt_flash_layout_total")


def record_gated_conv(branch):
    """One traced ``gated_short_conv`` forward, by the branch it took
    (``mxt_gated_conv_total{branch=xla|kernel}``). Counted at trace time, as
    the flash branches: nothing enters the compiled step."""
    counter("mxt_gated_conv_total", "Traced gated short convolutions by branch.",
            ("branch",)).labels(branch).inc()


def gated_conv_branches():
    """{branch: traces} of :func:`record_gated_conv` so far."""
    return _branches("mxt_gated_conv_total")


def record_causal_conv(branch):
    """One traced ``causal_conv_silu`` forward, by the branch it took
    (``mxt_causal_conv_total{branch=xla|kernel}``). Counted at trace time, as
    the flash branches: nothing enters the compiled step."""
    counter("mxt_causal_conv_total", "Traced causal filters under SiLU by branch.",
            ("branch",)).labels(branch).inc()


def causal_conv_branches():
    """{branch: traces} of :func:`record_causal_conv` so far."""
    return _branches("mxt_causal_conv_total")


def record_ssd(branch):
    """One traced ``ssd_scan`` forward, by the branch it took
    (``mxt_ssd_total{branch=xla|kernel}``). Counted at trace time, as the
    flash branches: nothing enters the compiled step."""
    counter("mxt_ssd_total", "Traced state-space scans by branch.",
            ("branch",)).labels(branch).inc()


def ssd_branches():
    """{branch: traces} of :func:`record_ssd` so far."""
    return _branches("mxt_ssd_total")


def record_delta_rule(branch):
    """One traced ``gated_delta_rule`` forward, by the branch it took
    (``mxt_delta_rule_total{branch=xla|kernel}``). Counted at trace time, as
    the flash branches: nothing enters the compiled step."""
    counter("mxt_delta_rule_total", "Traced gated delta rules by branch.",
            ("branch",)).labels(branch).inc()


def delta_rule_branches():
    """{branch: traces} of :func:`record_delta_rule` so far."""
    return _branches("mxt_delta_rule_total")


def record_embedding_grad(branch):
    """One traced ``Embedding`` by the form its weight's gradient takes
    (``mxt_embedding_grad_total{branch=xla|kernel}``): ``kernel`` a traced
    backward that is ``ops/embedding_grad.py``'s grouped product, ``xla`` a
    traced call that is ``jnp.take`` whole, its backward (if one is taken)
    JAX's scatter-add. Counted at trace time, as the flash branches: nothing
    enters the compiled step."""
    counter("mxt_embedding_grad_total", "Traced embedding gradients by branch.",
            ("branch",)).labels(branch).inc()


def embedding_grad_branches():
    """{branch: traces} of :func:`record_embedding_grad` so far."""
    return _branches("mxt_embedding_grad_total")


def record_grouped_matmul(product, branch):
    """One traced grouped matmul of the expert layer (``product``: ``fwd``,
    ``dx`` or ``dw``) by the branch it took
    (``mxt_grouped_matmul_total{product, branch=kernel|ragged_dot}``). Counted
    at trace time, as the flash branches: nothing enters the compiled step. A
    call that is ``jax.lax.ragged_dot`` whole counts its forward alone: its
    derivative is JAX's own."""
    counter("mxt_grouped_matmul_total", "Traced grouped matmuls by product and branch.",
            ("product", "branch")).labels(product, branch).inc()


def grouped_matmul_branches():
    """{product: {branch: traces}} of :func:`record_grouped_matmul` so far."""
    return _by_first_label("mxt_grouped_matmul_total")


def record_row_movement(movement, branch):
    """One traced movement of rows of the expert layer (``movement``: ``take``,
    rows gathered to their experts, or ``sum``, rows summed back to their
    tokens; each is the other's backward) by the branch it took
    (``mxt_row_movement_total{movement, branch=kernel|gather}``: the
    ``row_gather`` kernel, which moves the rows that exist, or XLA's gather of
    every row laid out). Counted at trace time, as the flash branches:
    nothing enters the compiled step."""
    counter("mxt_row_movement_total", "Traced row movements of the expert layer by branch.",
            ("movement", "branch")).labels(movement, branch).inc()


def row_movement_branches():
    """{movement: {branch: traces}} of :func:`record_row_movement` so far."""
    return _by_first_label("mxt_row_movement_total")


def record_moe_counts(expert_load, slots_lost, blocks_run, rows_moved=()):
    """The counts an expert-parallel model keeps on the device, as read once
    a window (``model_zoo.deepseek.publish_moe_counts``): token-slots each
    held expert of each expert layer got
    (``mxt_moe_expert_slots{layer,expert}``), slots held but not computed
    (``mxt_moe_slots_lost``, which must read 0) and blocks of rows run past
    each layer's first (``mxt_moe_blocks_run``: how often a routing overflowed
    the rows its layer is laid out for). Cumulative, so gauges. ``rows_moved``
    is each layer's last call's ``[rows moved, rows laid out]``
    (``mxt_moe_rows{layer,rows=moved|laid_out}``: what the two row movements
    moved in both passes of a step beside what XLA's gathers of every row laid
    out would have). A publication replaces the one before it whole: a model
    of fewer layers or experts leaves no child of an earlier one behind."""
    _REGISTRY.unregister("mxt_moe_expert_slots")
    _REGISTRY.unregister("mxt_moe_rows")
    if rows_moved:
        g = gauge("mxt_moe_rows", "Rows of the hidden width each expert layer's last "
                  "call moved, and the rows it laid out.", ("layer", "rows"))
        for layer, (moved, laid) in enumerate(rows_moved):
            g.labels(str(layer), "moved").set(float(moved))  # sync-ok: host value
            g.labels(str(layer), "laid_out").set(float(laid))  # sync-ok: host value
    g = gauge("mxt_moe_expert_slots",
              "Cumulative token-slots each held expert got (on-device "
              "accounting, read once a window).", ("layer", "expert"))
    for layer, row in enumerate(expert_load):
        for expert, v in enumerate(row):
            g.labels(str(layer), str(expert)).set(float(v))  # sync-ok: host value
    gauge("mxt_moe_slots_lost",
          "Cumulative token-slots held and not computed; 0 unless the "
          "expert layer is at fault.").set(float(slots_lost))  # sync-ok: host value
    gauge("mxt_moe_blocks_run",
          "Cumulative blocks of rows the expert layers ran past their "
          "first.").set(float(blocks_run))  # sync-ok: host value


def moe_counts():
    """What :func:`record_moe_counts` last published:
    ``{"expert_load": [[slots of each held expert] per layer],
    "slots_lost": n, "blocks_run": n}``, with ``"rows_moved": [[moved, laid
    out] per layer]`` where that was published, or {}."""
    fam = _REGISTRY.get("mxt_moe_expert_slots")
    lost = _REGISTRY.get("mxt_moe_slots_lost")
    ran = _REGISTRY.get("mxt_moe_blocks_run")
    if fam is None or lost is None or ran is None:
        return {}
    rows = {}
    for (layer, expert), ch in fam.children().items():
        rows.setdefault(int(layer), {})[int(expert)] = int(ch.value)
    out = {"expert_load": [[row[e] for e in sorted(row)]
                           for _, row in sorted(rows.items())],
           "slots_lost": int(lost.value), "blocks_run": int(ran.value)}
    moved = _by_first_label("mxt_moe_rows")
    if moved:
        out["rows_moved"] = [[moved[layer]["moved"], moved[layer]["laid_out"]]
                             for layer in sorted(moved, key=int)]
    return out


def record_selection_counts(selected_pairs, rows_searched):
    """The counts a model with a lightning indexer keeps on the device, as
    read once a window (``model_zoo.keye.publish_selection_counts``): (query,
    key) pairs each layer's indexer selected, which are the pairs the
    attention kernels consumed (``mxt_selected_pairs{layer}``), and query rows
    that had more candidates than ``topk`` and were searched
    (``mxt_rows_searched{layer}``). Cumulative, so gauges."""
    for name, text, values in (
            ("mxt_selected_pairs", "Cumulative (query, key) pairs the indexer "
             "selected for the attention kernels.", selected_pairs),
            ("mxt_rows_searched", "Cumulative query rows whose selection needed "
             "a search.", rows_searched)):
        _REGISTRY.unregister(name)  # a publication replaces the last one whole
        g = gauge(name, text + " (on-device accounting, read once a window)",
                  ("layer",))
        for layer, v in enumerate(values):
            g.labels(str(layer)).set(float(v))  # sync-ok: host value


def selection_counts():
    """What :func:`record_selection_counts` last published:
    ``{"selected_pairs": [per layer], "rows_searched": [per layer]}``, or {}."""
    out = {}
    for key in ("selected_pairs", "rows_searched"):
        fam = _REGISTRY.get("mxt_" + key)
        if fam is None:
            return {}
        rows = {int(layer): int(ch.value) for (layer,), ch in fam.children().items()}
        out[key] = [rows[i] for i in sorted(rows)]
    return out


# --------------------------------------------------------------------------
# distributed RPC tracing
# --------------------------------------------------------------------------
_trace = threading.local()


def new_trace_id():
    return os.urandom(8).hex()


def new_span_id():
    return os.urandom(4).hex()


def current_trace_id():
    return getattr(_trace, "tid", None)


class trace_scope:
    """Install an ambient trace id for the current thread; every
    AsyncClient frame sent inside the scope carries it. Nested scopes
    keep the outer id unless an explicit one is given — so one logical
    op (a multi-key push) is one trace."""

    def __init__(self, trace_id=None):
        self._explicit = trace_id

    def __enter__(self):
        self._prev = current_trace_id()
        tid = self._explicit or self._prev or new_trace_id()
        _trace.tid = tid
        return tid

    def __exit__(self, *exc):
        _trace.tid = self._prev
        return False


_RPC_SPAN_LOG = collections.deque(maxlen=1024)
_rpc_hist = None
_rpc_bytes = None
_rpc_total = None
_rpc_retries = None
_rpc_fenced = None


def record_rpc(side, op, seconds=None, nbytes=None, status="ok",
               trace=None, key=None):
    """One RPC observation from either endpoint. ``trace`` is the
    ``(trace_id, span_id, attempt)`` tuple riding the frame (or None for
    an untraced peer). Feeds the per-op latency/bytes/total/retry/fence
    metrics, the bounded in-memory span log, and the JSONL sink."""
    global _rpc_hist, _rpc_bytes, _rpc_total, _rpc_retries, _rpc_fenced
    if _rpc_hist is None:
        _rpc_hist = histogram(
            "mxt_kvstore_rpc_latency_seconds",
            "KVStore/membership RPC latency per op.", ("side", "op"))
        _rpc_bytes = histogram(
            "mxt_kvstore_rpc_bytes",
            "KVStore/membership RPC payload bytes per op.",
            ("side", "op"),
            buckets=tuple(4.0 ** i for i in range(2, 16)))
        _rpc_total = counter(
            "mxt_kvstore_rpc_total",
            "KVStore/membership RPCs by op and reply status.",
            ("side", "op", "status"))
        _rpc_retries = counter(
            "mxt_kvstore_rpc_retries_total",
            "RPC frames that were retry attempts (attempt > 0).",
            ("side", "op"))
        _rpc_fenced = counter(
            "mxt_kvstore_fenced_frames_total",
            "Frames refused by stale-worker fencing.", ("op",))
    op = str(op)
    side = str(side)
    status = str(status)
    if seconds is not None:
        _rpc_hist.labels(side, op).observe(seconds)
    if nbytes:
        _rpc_bytes.labels(side, op).observe(nbytes)
    _rpc_total.labels(side, op, status).inc()
    trace_id, span_id, attempt = (trace or (None, None, 0))
    if attempt:
        _rpc_retries.labels(side, op).inc()
    if status == "stale" and side == "server":
        _rpc_fenced.labels(op).inc()
    entry = {"ts": round(time.time(), 6), "side": side, "op": op,
             "key": key, "status": status, "trace_id": trace_id,
             "span_id": span_id, "attempt": attempt,
             "latency_s": None if seconds is None else round(seconds, 9),
             "bytes": nbytes}
    _RPC_SPAN_LOG.append(entry)
    if _events_active():
        _dispatch_row(dict(entry, kind="rpc_span"))


_emb_rpcs = None
_emb_bytes = None
_emb_pull_hist = None


def record_embedding_rpc(op, nbytes=0):
    """One sharded-embedding data RPC (embedding/client.py): per-op
    totals plus row-payload bytes split by direction — the numerator of
    the ``embedding_bytes_per_sec`` bench metric."""
    global _emb_rpcs, _emb_bytes
    if _emb_rpcs is None:
        _emb_rpcs = counter(
            "mxt_embedding_rpcs_total",
            "Sharded-embedding data RPCs by op (one per destination "
            "server per batched push/pull).", ("op",))
        _emb_bytes = counter(
            "mxt_embedding_bytes_total",
            "Embedding row bytes moved over the fleet transport.",
            ("dir",))
    _emb_rpcs.labels(str(op)).inc()
    if nbytes:
        _emb_bytes.labels("push" if op == "emb_push" else "pull").inc(
            int(nbytes))


def record_embedding_pull(seconds):
    """End-to-end latency of one ShardedEmbedding.pull (cache hits and
    server fetches included) — mxt_top's embedding p50/p99 source."""
    global _emb_pull_hist
    if _emb_pull_hist is None:
        _emb_pull_hist = histogram(
            "mxt_embedding_pull_seconds",
            "ShardedEmbedding.pull latency (device cache + fleet "
            "fetch).")
    _emb_pull_hist.observe(seconds)


def rpc_spans():
    """The bounded in-memory RPC span log (newest last) — what the
    trace-propagation test and mxt_top's JSONL mode read."""
    return list(_RPC_SPAN_LOG)


def clear_rpc_spans():
    _RPC_SPAN_LOG.clear()


# --------------------------------------------------------------------------
# request-lifecycle trace spans (the distributed tracing layer the
# fleet collector reassembles — telemetry_fleet.py)
# --------------------------------------------------------------------------
# Bounded like the RPC span log: old traces age out, appends never
# block. One row per closed span: the serving router/scheduler stamp
# queue/prefill/decode/commit spans against the request's trace_id from
# host wall clocks they already keep (spans CLOSE inside the existing
# deferred PendingValue retirement, so the layer adds zero device
# syncs — the mxt_step_latency_seconds discipline).
_TRACE_SPAN_LOG = collections.deque(maxlen=8192)


def record_trace_span(name, trace_id, t0, t1, clock_now=None,
                      track=None, **attrs):
    """Record one closed span of a distributed request trace.

    ``t0``/``t1`` are in the CALLER's clock (``time.monotonic`` or a
    test fake); ``clock_now`` is that clock's current reading, used to
    shift the span onto the wall-clock epoch so spans from different
    processes line up in one timeline. ``track`` names the timeline row
    ("router", "replica-0", ...). Returns the stored row (or None when
    ``trace_id`` is None — untraced requests cost nothing)."""
    if trace_id is None:
        return None
    off = 0.0 if clock_now is None else time.time() - clock_now
    row = {"kind": "trace_span", "name": str(name),
           "trace_id": str(trace_id), "span_id": new_span_id(),
           "track": None if track is None else str(track),
           "t0": round(float(t0) + off, 6),  # sync-ok: host wall-clock scalar
           "t1": round(float(t1) + off, 6)}  # sync-ok: host wall-clock scalar
    if attrs:
        row["attrs"] = {k: v for k, v in attrs.items() if v is not None}
    _TRACE_SPAN_LOG.append(row)
    if _events_active():
        _dispatch_row(dict(row))
    return row


def trace_spans(trace_id=None):
    """The bounded request-trace span log (oldest first), optionally
    filtered to one trace — the ``tel_spans`` wire payload."""
    rows = list(_TRACE_SPAN_LOG)
    if trace_id is None:
        return rows
    return [r for r in rows if r["trace_id"] == trace_id]


def clear_trace_spans():
    _TRACE_SPAN_LOG.clear()


# --------------------------------------------------------------------------
# HTTP exposition endpoint
# --------------------------------------------------------------------------
_http_server = None
_http_lock = threading.Lock()


def start_http_server(port=None):
    """Serve ``render_prometheus()`` on ``127.0.0.1:port`` from a daemon
    thread (port 0 picks a free one; see :func:`http_port`). Loopback
    only — the exposition is plain text but the listening posture
    follows async_server.py's threat model."""
    global _http_server
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            path, _, query = self.path.partition("?")
            if path.startswith("/debug/"):
                # diagnostics debug routes (stacks / memory /
                # flightrecorder / trace / timeline) ride the same
                # endpoint so one scrape target serves both metrics and
                # post-mortems
                try:
                    from . import diagnostics

                    status, ctype, body = diagnostics.handle_debug(
                        path, query)
                except Exception as e:  # noqa: BLE001 — a debug route
                    # must never take the exposition endpoint down
                    status, ctype = 500, "text/plain; charset=utf-8"
                    body = ("debug route error: %s" % e).encode("utf-8")
            elif path == "/fleet":
                # the fleet collector's merged view (member-labeled
                # samples from every scraped fleet member) — what
                # `mxt_top --fleet` tails
                try:
                    from . import telemetry_fleet

                    c = telemetry_fleet.default_collector()
                    if c is None:
                        status = 404
                        ctype = "text/plain; charset=utf-8"
                        body = (b"no fleet collector is running in this "
                                b"process (telemetry_fleet.FleetCollector"
                                b" + set_default_collector)")
                    else:
                        status = 200
                        ctype = ("text/plain; version=0.0.4; "
                                 "charset=utf-8")
                        body = c.render_prometheus().encode("utf-8")
                except Exception as e:  # noqa: BLE001 — see above
                    status, ctype = 500, "text/plain; charset=utf-8"
                    body = ("fleet route error: %s" % e).encode("utf-8")
            elif path == "/health":
                # the training-health plane's rule verdicts + anomaly
                # summary (200 ok / 503 degraded — the load-balancer
                # health-check contract)
                try:
                    from . import health

                    status, ctype, body = health.handle_health()
                    body = body.encode("utf-8")
                except Exception as e:  # noqa: BLE001 — see above
                    status, ctype = 500, "text/plain; charset=utf-8"
                    body = ("health route error: %s" % e).encode("utf-8")
            else:
                status = 200
                ctype = "text/plain; version=0.0.4; charset=utf-8"
                body = render_prometheus().encode("utf-8")
            try:
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except (BrokenPipeError, ConnectionResetError):
                pass  # client hung up mid-transfer (big trace bodies)

        def log_message(self, *args):
            pass  # metrics scrapes must not spam the training logs

    with _http_lock:
        if _http_server is not None:
            return _http_server
        if port is None:
            from . import config

            port = config.get("MXT_TELEMETRY_PORT")
        if port is None:
            raise MXNetError(
                "no telemetry port: pass one or set MXT_TELEMETRY_PORT")
        srv = ThreadingHTTPServer(("127.0.0.1", int(port)), _Handler)
        srv.daemon_threads = True
        threading.Thread(target=srv.serve_forever, daemon=True,
                         name="mxt-telemetry-http").start()
        _http_server = srv
    return srv


def http_port():
    """The bound exposition port, or None when no server is running."""
    return None if _http_server is None else \
        _http_server.server_address[1]


def _maybe_autostart():
    """Start the exposition endpoint when MXT_TELEMETRY_PORT is set
    (called once at package import)."""
    try:
        from . import config

        if config.get("MXT_TELEMETRY_PORT") is not None \
                and _http_server is None:
            start_http_server()
    except Exception:
        pass  # observability must never take the process down
