"""Profiler — ``mx.profiler`` API over ``jax.profiler`` (SURVEY §5
tracing/profiling: ref python/mxnet/profiler.py + src/profiler/profiler.cc;
the engine-level ProfileOperator records collapse into XLA's own op-level
trace, which the JAX profiler captures as Perfetto/TensorBoard data).

``set_config(filename=...)`` + ``set_state('run')`` starts a JAX trace; on
``set_state('stop')``/``dump()`` the trace lands under the configured
directory. ``dumps()`` then prints two tables, as the reference's prints time
per operator::

    mx.profiler.set_state('run')
    for _ in range(5): step(x, y).wait_to_read()
    mx.profiler.set_state('stop'); print(mx.profiler.dumps())

- host: user scopes (Task/Frame/Counter/Marker; each also a
  ``jax.profiler.TraceAnnotation`` in the trace), the program's own set-up
  phases (``setup.import``, ``.initialize``, ``.cast``, ``.place``,
  ``.infer_shapes``, ``.step_build``: exclusive of one another, so they add
  up) and the launch / host-sync / compile counters;
- device: time by phase (forward, backward, optimizer, grad_post, collective,
  other), by scope (``forward/resnetv10/stage1``), by Pallas kernel name and
  by HLO category, the offset between the device's clock and the host's, and
  the idle gaps named by the ``mxt.*`` span under them. It is read back from
  the trace by ``profiler_trace.aggregate`` (also ``profiler.aggregate``),
  from the names the fused steps, the blocks and the ops write with
  ``jax.named_scope`` while JAX traces them.

Env autostart: ``MXT_PROFILER_AUTOSTART=1`` (ref MXNET_PROFILER_AUTOSTART).
"""
from __future__ import annotations

import os
import threading
import time

from .base import MXNetError

__all__ = ["set_config", "set_state", "state", "start", "stop", "pause",
           "resume", "dump", "dumps", "Domain", "Task", "Frame", "Counter",
           "Marker", "record_launch", "launch_count", "reset_launch_count",
           "counter_value", "record_host_sync", "host_sync_count",
           "reset_host_sync_count", "set_gauge", "gauge_value",
           "compile_count", "compile_seconds", "aggregate", "setup_scope",
           "setup_seconds"]

_config = {
    "filename": "profile_output",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": True,
    "continuous_dump": False,
}
_state = "stop"
_paused = False
_trace_dir = None
_device_table = None  # dumps()' device half, read once per finished trace
# aggregate table: name -> [count, total_sec, min_sec, max_sec]
_agg = {}
# _LOCK guards _agg and the counter/gauge name maps below; the metric
# VALUES themselves live in the telemetry registry (telemetry.py), whose
# cells carry their own locks — counters/gauges are bumped both from the
# dispatch thread and from deferred-read callbacks (engine.StepStream
# retirement, DataLoader workers), so every mutation must be guarded
_LOCK = threading.RLock()

# raw profiler name -> sanitized telemetry metric name. The profiler's
# counter/gauge storage moved into the typed telemetry registry; these
# maps track which registry families the profiler owns so dumps() lists
# them and dumps(reset=True) unregisters exactly them.
_counter_names = {}
_gauge_names = {}

_MISSING = object()


def _telemetry():
    from . import telemetry

    return telemetry


class _MetricsView:
    """Live read-only mapping over the profiler-owned slice of the
    telemetry registry — back-compat for code that treated the old
    ``_counters``/``_gauges`` dicts as the source of truth (membership's
    and resilience's `name not in profiler._counters` recreation
    checks)."""

    def __init__(self, names):
        self._names = names

    def get(self, name, default=None):
        metric = self._names.get(name)
        if metric is None:
            return default
        fam = _telemetry().registry().get(metric)
        if fam is None:
            return default
        v = fam.value
        return int(v) if float(v).is_integer() else v

    def __contains__(self, name):
        return self.get(name, _MISSING) is not _MISSING

    def __getitem__(self, name):
        v = self.get(name, _MISSING)
        if v is _MISSING:
            raise KeyError(name)
        return v

    def __iter__(self):
        return iter(list(self._names))

    def __len__(self):
        return len(self._names)

    def clear(self):
        reg = _telemetry().registry()
        with _LOCK:
            for metric in self._names.values():
                reg.unregister(metric)
            self._names.clear()


_counters = _MetricsView(_counter_names)
_gauges = _MetricsView(_gauge_names)


def _counter_child(name):
    """The registry cell behind a profiler counter (created on demand)."""
    tel = _telemetry()
    with _LOCK:
        metric = _counter_names.get(name)
        if metric is None:
            metric = _counter_names[name] = tel.sanitize_metric_name(name)
    return tel.registry().counter(
        metric, "profiler counter %r" % name).default


def _gauge_child(name):
    tel = _telemetry()
    with _LOCK:
        metric = _gauge_names.get(name)
        if metric is None:
            metric = _gauge_names[name] = tel.sanitize_metric_name(name)
    return tel.registry().gauge(
        metric, "profiler gauge %r" % name).default


# hot-path cells cached so record_launch/record_host_sync stay one lock
# + one add (they run on every compiled dispatch / every deferred read)
_launch_cell = None
_sync_cell = None


def _launch():
    global _launch_cell
    c = _launch_cell
    if c is None:
        c = _launch_cell = _telemetry().counter(
            "mxt_xla_launches_total",
            "Compiled-program executions (XLA launches) dispatched by "
            "the framework.").default
    return c


def _syncs():
    global _sync_cell
    c = _sync_cell
    if c is None:
        c = _sync_cell = _telemetry().counter(
            "mxt_host_syncs_total",
            "Device->host synchronizations (blocking reads) performed "
            "by the framework.").default
    return c


def record_launch(n=1):
    """Count ``n`` compiled-program executions (XLA launches) dispatched.
    Called from apply_op / the fused-step jit dispatch sites; each launch
    is a host dispatch, so this counter is the cheapest fusion-health
    signal: a fused train step should show exactly 1 per step."""
    _launch().inc(n)


def launch_count():
    return int(_launch().value)


def reset_launch_count():
    return int(_launch().reset())


def record_host_sync(n=1):
    """Count ``n`` device->host synchronizations (blocking reads)."""
    _syncs().inc(n)


def host_sync_count():
    return int(_syncs().value)


def reset_host_sync_count():
    return int(_syncs().reset())


def compile_count():
    """XLA backend compiles this process has performed (incl. persistent-
    cache deserializations — tuning.compile_stats() splits hits/misses).
    Fed by the jax.monitoring listeners tuning/compile_cache.py installs
    at import; the cheapest cold-vs-warm signal next to launch_count."""
    from .tuning import compile_stats

    return int(compile_stats()["compiles"])


def compile_seconds():
    """Total XLA backend-compile wall time (seconds) this process."""
    from .tuning import compile_stats

    return compile_stats()["compile_seconds"]


def set_gauge(name, value):
    """Set a point-in-time gauge (e.g. engine's 'dispatch_depth' — the
    number of fused steps currently in flight). Gauges show in dumps()
    and in telemetry.render_prometheus()."""
    _gauge_child(name).set(value)


def gauge_value(name, default=0):
    return _gauges.get(name, default)


def counter_value(name, default=0):
    """Current value of a named profiler Counter (the dumps() table
    entries) — e.g. resilience's 'skipped_nonfinite_steps'."""
    return _counters.get(name, default)


def set_config(**kwargs):
    """Configure the profiler (ref: MXSetProcessProfilerConfig). Accepts the
    reference's kwargs; ``filename`` names the trace output directory."""
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise MXNetError("profiler.set_config: unknown options %s"
                         % sorted(unknown))
    if _state == "run":
        raise MXNetError("cannot reconfigure profiler while running")
    _config.update(kwargs)


def state():
    return _state


def set_state(new_state="stop"):
    """'run' starts a JAX trace; 'stop' ends it (ref:
    MXSetProcessProfilerState)."""
    global _state, _trace_dir, _device_table
    if new_state not in ("run", "stop"):
        raise MXNetError("profiler state must be 'run' or 'stop', got %r"
                         % (new_state,))
    if new_state == _state:
        return
    import jax

    if new_state == "run":
        base = _config["filename"]
        # the reference writes one chrome-trace JSON file; JAX writes a
        # Perfetto trace directory — use the filename sans extension as dir
        _trace_dir = base[:-5] if base.endswith(".json") else base
        os.makedirs(_trace_dir, exist_ok=True)
        jax.profiler.start_trace(_trace_dir)
        _state, _device_table = "run", None
    else:
        jax.profiler.stop_trace()
        _state = "stop"


def start():
    set_state("run")


def stop():
    set_state("stop")


def pause():
    """Suppress user-scope aggregation (the device trace itself cannot be
    paused mid-flight; ref MXProfilePause pauses op recording)."""
    global _paused
    _paused = True


def resume():
    global _paused
    _paused = False


def dump(finished=True):
    """Finish the trace and flush it to disk (ref: MXDumpProfile)."""
    if _state == "run" and finished:
        set_state("stop")
    return _trace_dir


def dumps(reset=False):
    """Aggregate-stats table of user scopes (ref: MXAggregateProfileStatsPrint
    — device-op aggregates live in the Perfetto trace; this table covers
    profiler.Task/Frame scopes and counters). Everything is snapshotted
    under the lock BEFORE formatting — writer threads (deferred-read
    callbacks, server connections) keep mutating while this renders."""
    with _LOCK:
        agg = {name: list(ent) for name, ent in _agg.items()}
    counters = {name: _counters.get(name) for name in _counters}
    gauges = {name: _gauges.get(name) for name in _gauges}
    lines = ["Profile Statistics:",
             "    %-24s %10s %14s %14s %14s"
             % ("Name", "Calls", "Total(ms)", "Min(ms)", "Max(ms)")]
    for name in sorted(agg):
        cnt, tot, mn, mx = agg[name]
        lines.append("    %-24s %10d %14.3f %14.3f %14.3f"
                     % (name, cnt, tot * 1e3, mn * 1e3, mx * 1e3))
    for name in sorted(counters):
        lines.append("    %-24s value=%s" % (name, counters[name]))
    for name in sorted(gauges):
        lines.append("    %-24s value=%s" % (name, gauges[name]))
    lines.append("    %-24s value=%d" % ("xla_launches", launch_count()))
    lines.append("    %-24s value=%d" % ("host_syncs", host_sync_count()))
    lines.append("    %-24s value=%d (%.3fs)"
                 % ("xla_compiles", compile_count(), compile_seconds()))
    if _state == "stop" and _trace_dir is not None:
        lines.append(_device_stats())
    if reset:
        with _LOCK:
            _agg.clear()
        _counters.clear()
        _gauges.clear()
        reset_launch_count()
        reset_host_sync_count()
    return "\n".join(lines)


def aggregate(trace=None, **kwargs):
    """Device seconds by phase, scope, kernel and HLO category, the clock
    offset and the named idle gaps of a finished trace (the last one taken
    with ``set_state`` where ``trace`` is None), as a dict:
    ``profiler_trace.aggregate``, which also documents ``kwargs``."""
    from . import profiler_trace

    if trace is None:
        trace = _trace_dir
    if trace is None:
        raise MXNetError("profiler.aggregate: no trace was taken")
    return profiler_trace.aggregate(trace, **kwargs)


def _device_stats():
    global _device_table
    if _device_table is None:
        from . import profiler_trace

        _device_table = profiler_trace.format_table(aggregate())
    return _device_table


def _record(name, dt):
    if _paused:
        return
    with _LOCK:
        ent = _agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
        ent[0] += 1
        ent[1] += dt
        ent[2] = min(ent[2], dt)
        ent[3] = max(ent[3], dt)


class Domain:
    """Grouping namespace for scopes (ref: profiler.Domain)."""

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return "Domain(%s)" % self.name


class _Scope:
    """Timed scope: host wall-clock into the aggregate table + a
    TraceAnnotation so device ops inside it are grouped in the trace."""

    def __init__(self, name, domain=None):
        self.name = name if domain is None else "%s::%s" % (domain.name,
                                                            name)
        self._t0 = None
        self._ann = None

    def start(self):
        import jax
        self._t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def stop(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0 is not None:
            self._done(time.perf_counter() - self._t0)
            self._t0 = None

    def _done(self, dt):
        _record(self.name, dt)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class Task(_Scope):
    pass


class _SetupStack(threading.local):
    """Per thread: [seconds, compiling seconds] of the phases entered inside
    each open set-up scope."""

    def __init__(self):
        super().__init__()
        self.open = []


_setup_stack = _SetupStack()


class _SetupScope(_Scope):
    """One of the program's own set-up phases. Its time is exclusive of the
    set-up phases entered inside it (a deferred initialisation inside the
    shape-inferring forward counts once, under ``setup.initialize``), so the
    phases add up to the seconds set-up took."""

    def start(self):
        _setup_stack.open.append([0.0, 0.0])
        self._c0 = _compiling_seconds()
        return super().start()

    def _done(self, dt):
        compiling = _compiling_seconds() - self._c0
        stack = _setup_stack.open
        inner = stack.pop()
        if stack:
            stack[-1][0] += dt
            stack[-1][1] += compiling
        _record(self.name, dt - inner[0])
        with _LOCK:
            _setup_compiling[self.name] = compiling - inner[1] \
                + _setup_compiling.get(self.name, 0.0)


# scope name -> seconds of it that JAX spent tracing, lowering, compiling
_setup_compiling = {}


def _compiling_seconds():
    from .tuning import compile_cache

    return compile_cache.total_seconds()


def setup_scope(phase):
    """``with profiler.setup_scope('place'):`` is the scope ``setup.place``."""
    return _SetupScope("setup." + phase)


def setup_seconds(compiling=False):
    """{phase: seconds so far} of the ``setup.*`` scopes (process totals);
    with ``compiling`` the part of each that JAX spent tracing, lowering and
    compiling (``tuning.compile_stats``' clock)."""
    with _LOCK:
        if compiling:
            return {name[len("setup."):]: s
                    for name, s in _setup_compiling.items()}
        return {name[len("setup."):]: ent[1] for name, ent in _agg.items()
                if name.startswith("setup.")}


class Frame(_Scope):
    pass


class Counter:
    """Named counter (ref: profiler.Counter). Backed by a telemetry
    registry cell, so creation and every mutation are lock-guarded and
    the value shows in telemetry.render_prometheus() too."""

    def __init__(self, domain, name, value=0):
        self.name = "%s::%s" % (domain.name, name) if domain else name
        self._cell = _counter_child(self.name)
        self._cell.set(value)

    def set_value(self, value):
        self._cell.set(value)

    def increment(self, delta=1):
        self._cell.inc(delta)

    def decrement(self, delta=1):
        self.increment(-delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    """Instant event (ref: profiler.Marker.mark)."""

    def __init__(self, domain, name):
        self.name = "%s::%s" % (domain.name, name) if domain else name

    def mark(self, scope="process"):
        _record("marker:%s" % self.name, 0.0)


if os.environ.get("MXT_PROFILER_AUTOSTART", "") == "1":
    set_config(profile_all=True)
    set_state("run")
