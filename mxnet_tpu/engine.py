"""Async dispatch engine — ThreadedEngine semantics over XLA
(ref: src/engine/threaded_engine.h + python/mxnet/engine.py).

The reference's ThreadedEngine lets the *host* run ahead of the device:
ops enqueue into a dependency queue, reads are the only sync points, and
``MXNET_ENGINE_BULK_SIZE`` bounds how much work is in flight. XLA's async
dispatch covers the device half of that, but until now every fused train
step still synchronized per step — the non-finite guard flag was read
back immediately, so each ~3.4 ms launch (PERF.md §1.2) paid a full
host↔device round-trip and the host could never pipeline.

This module is the missing host half:

- :class:`InflightWindow` is the per-call-site dependency queue: every
  dispatched fused program pushes a token; host-consumed scalars (the
  guard flag mask, a throttle read of the loss) ride tokens as deferred
  :class:`~mxnet_tpu.ndarray.pending.PendingValue` handles and are only
  materialized when the token *retires* — once the in-flight window is
  full, or at an explicit barrier. :class:`StepStream` is the training
  face of the same window (PR 4's name, kept as an alias); the serving
  decode stream (serving/engine.py) rides the SAME class with per-step
  *values* instead of guard flags: each decode step stages its sampled
  token ids, the window stacks a whole snapshot's worth into ONE device
  array, and a single deferred read delivers K steps of tokens to the
  scheduler — the decode hot loop never blocks on the device.
- the window depth K comes from ``MXT_MAX_INFLIGHT`` (default 2), and
  :func:`bulk`/:func:`set_bulk_size` are now the REAL knob instead of
  no-op shims: ``with engine.bulk(1):`` forces synchronous per-step
  reads, ``engine.bulk(8)`` lets 8 steps pipeline. The window also
  bounds backpressure: a retirement blocks until its step finished, so
  the un-synced dispatch queue (and the HBM working set behind the
  donated buffers) can never grow past ~2K steps.
- guard flags travel as a device-carried bitmask (one uint32 riding the
  fused program), so ONE host read retires up to K steps' worth of
  flags: host_syncs/step <= 1/K instead of 1.
- :func:`wait_all` drains every live stream — ``mx.nd.waitall()`` routes
  through it, making it the barrier tests and chaos_matrix.sh rely on,
  exactly like the reference's ``Engine::WaitForAll``.

Deferred-read callbacks retire on whichever thread triggers the read, so
everything here and the profiler counters it bumps are lock-guarded.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
import weakref

__all__ = ["bulk", "set_bulk_size", "max_inflight", "InflightWindow",
           "StepStream", "wait_all", "inflight_depth", "window_states"]

# flag bits a single snapshot read may cover: the mask is a uint32 riding
# the fused program, and with snapshots every K pushes plus one token
# still in the window, up to 2K bits can be pending at a read -> K <= 15
_MASK_BITS = 15

_lock = threading.RLock()
_streams = weakref.WeakSet()  # every live StepStream, for wait_all()
_BULK_SIZE = None  # set_bulk_size override; None -> MXT_MAX_INFLIGHT


def _config():
    from . import config

    return config


def max_inflight():
    """Effective dispatch-window depth K: the ``set_bulk_size`` override
    when one is active, else ``MXT_MAX_INFLIGHT``; clamped to [1, 15]."""
    size = _BULK_SIZE
    if size is None:
        size = _config().get("MXT_MAX_INFLIGHT")
    return max(1, min(int(size), _MASK_BITS))


def set_bulk_size(size):
    """Set the in-flight step window depth; returns the previous
    effective depth (ref: engine.py — set_bulk_size). Unlike the earlier
    shim this is load-bearing: fused steps defer their host reads until
    ``size`` steps are in flight."""
    global _BULK_SIZE
    with _lock:
        prev = max_inflight()
        _BULK_SIZE = int(size)
    return prev


@contextlib.contextmanager
def bulk(size):
    """with-scope analog of the reference's engine bulking
    (ref: engine.py — bulk): ``with engine.bulk(1):`` is the synchronous
    A/B baseline, larger sizes deepen the dispatch pipeline."""
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)


def inflight_depth():
    """Total dispatched-but-unobserved steps across all live streams
    (also published as the ``dispatch_depth`` profiler gauge)."""
    with _lock:
        return sum(s.pending for s in _streams)


def _update_depth_gauge():
    from . import profiler

    profiler.set_gauge("dispatch_depth", inflight_depth())


def _telemetry():
    from . import telemetry

    return telemetry


def _diag():
    from . import diagnostics

    return diagnostics


def window_states():
    """[{name, dispatched, consumed, pending, staged, held_bytes}] for
    every live stream — what the hang watchdog's stall report and the
    post-mortem dump snapshot (pure host bookkeeping)."""
    with _lock:
        streams = list(_streams)
    return [{"name": s.name, "dispatched": s._dispatched,
             "consumed": s._consumed, "pending": s.pending,
             "staged": len(s._staged), "held_bytes": s._held_bytes}
            for s in streams]


def _nbytes(v):
    """Host-side byte count of a device value (shape metadata only —
    reading ``.nbytes`` never transfers)."""
    return int(getattr(getattr(v, "data", v), "nbytes", 0) or 0)


class _Token:
    """One retirement point in a stream: a deferred host read covering
    every step dispatched since the previous token."""

    __slots__ = ("pv", "has_flags", "upto", "nvalues", "nbytes")

    def __init__(self, pv, has_flags, upto, nvalues=0, nbytes=0):
        self.pv = pv
        self.has_flags = has_flags
        self.upto = upto
        self.nvalues = nvalues
        self.nbytes = nbytes


class InflightWindow:
    """The dependency queue for ONE dispatch site (a CachedTrainStep, a
    guarded _FusedUpdate, the serving decode stream): ``push()`` records
    a dispatched launch, every K-th push becomes a snapshot token
    carrying a deferred read, and tokens retire oldest-first as the
    window slides.

    Two retirement payloads, one deferred read each:

    - ``on_flags`` (training) receives one ``finite: bool`` per retired
      step, in dispatch order, decoded from a device-carried guard
      bitmask — deferred bookkeeping (update counts, loss-scale,
      skipped-step counter) lives in that callback.
    - ``on_values`` (serving decode, training health) receives
      ``(step_no, host_row)`` per retired step, in dispatch order. Each
      push stages its per-step device value (the decode step's sampled
      token ids, or the training step's packed health stat row); at
      snapshot time the window stacks the staged values into ONE device
      array, so a single deferred transfer still retires a whole
      window's worth of steps — host_syncs/step stays <= 1/K no matter
      how much per-step data rides the window.

    A single push may defer flags or a value, not both (the snapshot
    carries exactly one deferred device source). The training-health
    plane exploits that: in guard mode the stat row's LAST column packs
    this step's non-finite bit, so the guard flag and the stats retire
    from the SAME stacked read (health.py / gluon/train_step.py) and
    syncs/step stays bit-equal with health on or off.
    """

    def __init__(self, name="step", on_flags=None, on_values=None):
        self.name = name
        self._on_flags = on_flags
        self._on_values = on_values
        self._dispatched = 0
        self._consumed = 0
        self._last_snap = 0
        self._window = []  # snapshot tokens not yet retired
        self._staged = []  # per-step device values since the last snapshot
        self._latest = None  # (sync_value, flags) of the newest push
        self._retire_lock = threading.RLock()
        # host wall-clock of each dispatch, consumed oldest-first at
        # retirement: the dispatch->retire latency histogram costs zero
        # extra device reads (it is measured INSIDE the deferred read
        # the engine already performs)
        self._dispatch_ts = collections.deque()
        # bytes the window itself retains (staged per-step values +
        # snapshot token sources) — the 'inflight_window' HBM pool
        self._held_bytes = 0
        with _lock:
            _streams.add(self)
        # the watchdog observes window retires: pending work with a
        # frozen retire counter == a wedged device or a dead pipeline
        _diag().register_source("engine_retire", pending_fn=inflight_depth)

    @property
    def pending(self):
        """Steps dispatched but not yet observed on host."""
        return self._dispatched - self._consumed

    @staticmethod
    def _stack(values):
        """One device array from a snapshot's staged per-step values —
        a pure device op (async dispatch), never a host transfer."""
        import jax.numpy as jnp

        raw = [getattr(v, "data", v) for v in values]
        return jnp.stack(raw)

    def push(self, sync_value, flags=None, value=None):
        """Record one dispatched fused step; returns its step number.

        ``sync_value``: any device output of the step (used for the
        throttle read when there are no flags). ``flags``: the step's
        output guard bitmask (newest bit = this step), read deferred.
        ``value``: a per-step device array staged for ``on_values``
        delivery (every push in a stream must then carry one, and the
        shapes must match so a snapshot can stack them).
        """
        from .ndarray.pending import PendingValue

        if flags is not None and value is not None:
            from .base import MXNetError

            raise MXNetError("InflightWindow.push: a step may defer "
                             "flags or a value, not both")
        retire = []
        with _lock:
            self._dispatched += 1
            self._dispatch_ts.append(time.perf_counter())
            depth = self._dispatched - self._consumed
            step_no = self._dispatched
            self._latest = (sync_value, flags)
            if value is not None:
                self._staged.append(value)
                self._held_bytes += _nbytes(value)
            k = max_inflight()
            if self._dispatched - self._last_snap >= k:
                if self._staged:
                    src = self._stack(self._staged)
                    # staged bytes were counted per push; the token
                    # inherits them so retirement releases the total
                    tok = _Token(PendingValue(src), False,
                                 self._dispatched, len(self._staged),
                                 nbytes=sum(_nbytes(v)
                                            for v in self._staged))
                    self._staged = []
                else:
                    src = flags if flags is not None else sync_value
                    tok = _Token(PendingValue(src), flags is not None,
                                 self._dispatched, nbytes=_nbytes(src))
                    self._held_bytes += tok.nbytes
                self._last_snap = self._dispatched
                self._window.append(tok)
                if k == 1:
                    retire.append(self._window.pop())
                else:
                    while len(self._window) > 1:
                        retire.append(self._window.pop(0))
        _telemetry().record_dispatch(self.name, step_no, depth)
        self._publish_held()
        if retire:
            with self._retire_lock:
                for tok in retire:
                    self._retire(tok)
        _update_depth_gauge()
        return step_no

    def _publish_held(self):
        """Export the window's retained bytes as the 'inflight_window'
        HBM-ledger pool (host arithmetic on shape metadata)."""
        _diag().hbm_set("inflight_window", self.name,
                        max(0, self._held_bytes))

    def _retire(self, tok):
        """Materialize one token's deferred read and catch host-side
        bookkeeping up to it. Serialized per stream by _retire_lock."""
        n = tok.upto - self._consumed
        if n <= 0:
            return
        import jax

        with jax.profiler.TraceAnnotation("mxt.window.retire", upto=tok.upto):
            value = tok.pv.get()  # blocks until the covered steps finished
        with _lock:
            self._held_bytes -= tok.nbytes
        # retires are the engine's watchdog heartbeat: a frozen counter
        # with a non-empty window means the device stopped answering
        diag = _diag()
        diag.progress("engine_retire")
        self._publish_held()
        # dispatch->retire latency per covered step, clocked off the
        # read that just happened (telemetry adds NO host sync here)
        now = time.perf_counter()
        tel = _telemetry()
        for i in range(n):
            ts = self._dispatch_ts.popleft() if self._dispatch_ts else now
            tel.record_step_retired(self.name, tok.upto - n + 1 + i,
                                    now - ts)
        if tok.has_flags and self._on_flags is not None:
            mask = int(value)
            for k in range(n - 1, -1, -1):  # oldest step first
                self._on_flags((mask >> k) & 1 == 0)
        if tok.nvalues and self._on_values is not None:
            first = tok.upto - tok.nvalues + 1
            for i in range(tok.nvalues):  # oldest step first
                self._on_values(first + i, value[i])
        self._consumed = tok.upto

    def flush(self):
        """Drain: retire every queued token, then synthesize one for any
        steps dispatched since the last snapshot, so ``pending`` is 0 and
        all deferred bookkeeping has landed."""
        from .ndarray.pending import PendingValue

        with self._retire_lock:
            with _lock:
                tokens, self._window = self._window, []
                staged, self._staged = self._staged, []
                latest = self._latest
                upto = self._dispatched
                self._last_snap = upto
            for tok in tokens:
                self._retire(tok)
            if self._consumed < upto and latest is not None:
                sync_value, flags = latest
                if staged:
                    # staged bytes entered the ledger at push time; the
                    # synthesized token carries them out at retirement
                    self._retire(_Token(PendingValue(self._stack(staged)),
                                        False, upto, len(staged),
                                        nbytes=sum(_nbytes(v)
                                                   for v in staged)))
                else:
                    src = flags if flags is not None else sync_value
                    self._retire(_Token(PendingValue(src),
                                        flags is not None, upto))
        _update_depth_gauge()


class StepStream(InflightWindow):
    """The training face of :class:`InflightWindow` (PR 4's name):
    CachedTrainStep / the guarded _FusedUpdate push fused train steps
    and retire guard-flag bitmasks through ``on_flags``."""


def wait_all():
    """Drain every live stream's in-flight window (the host half of
    ``Engine::WaitForAll``; ``mx.nd.waitall()`` calls this first). The
    barrier is also the durability point for the kernel-tuning table:
    decisions the cost model recorded since the last save hit disk here,
    so a process killed mid-epoch still leaves its tuning work behind
    for the next one (the same contract waitall gives the telemetry
    JSONL sink)."""
    with _lock:
        streams = list(_streams)
    for s in streams:
        s.flush()
    try:
        from . import tuning

        if tuning.table().dirty:
            tuning.save()
    except Exception:  # noqa: BLE001 — tuning persistence is best-effort
        pass
