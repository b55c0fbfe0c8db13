"""PendingValue — the deferred-handle half of the async engine.

The reference NDArray is a *future*: every op returns immediately and the
ThreadedEngine resolves the value later; only ``asnumpy()``/``asscalar()``
block (ref: include/mxnet/ndarray.h — engine variable + WaitToRead).
``jax.Array`` already gives device values that behavior, but host-side
*scalars the framework itself consumes* (the non-finite step flag, a
deferred loss, a metric sum) used to be read eagerly with
``np.asarray(...)`` — one blocking device-to-host read per step.

:class:`PendingValue` makes those reads explicit and lazy: it wraps a
device array and only transfers it to host on the first ``get()`` /
``float()`` / ``asnumpy()``. Callbacks registered with :meth:`on_ready`
run exactly once, at materialization — the engine's in-flight window
(engine.StepStream) retires tokens by materializing their PendingValues,
which is where deferred bookkeeping (optimizer update counts, the
loss-scale backoff, the skipped-step counter) catches up.

Every materialization records one ``host_syncs`` profiler tick, so
the benchmark can report ``host_syncs_per_step`` and
``tools/check_host_syncs.py`` can treat this module as the ONE sanctioned
sync funnel for deferred values.
"""
from __future__ import annotations

import threading

import numpy as np

__all__ = ["PendingValue"]


class PendingValue:
    """A device value whose host copy is produced lazily, once.

    ``dev`` may be a ``jax.Array`` or an :class:`NDArray` (unwrapped).
    Reading (``get``/``float``/``int``/``bool``/``asnumpy``) blocks until
    the producing computation finishes — the ``wait_to_read`` analog —
    and fires any :meth:`on_ready` callbacks with the host value.
    """

    __slots__ = ("_dev", "_host", "_callbacks", "_lock")

    def __init__(self, dev):
        data = getattr(dev, "data", None)
        self._dev = data if data is not None and hasattr(dev, "asnumpy") \
            else dev
        self._host = None
        self._callbacks = []
        self._lock = threading.Lock()

    @property
    def materialized(self):
        """True once the host copy exists (no blocking)."""
        return self._host is not None

    def ready(self):
        """Non-blocking: True if reading would not block (best-effort —
        falls back to ``materialized`` when the backend can't tell)."""
        if self._host is not None:
            return True
        probe = getattr(self._dev, "is_ready", None)
        try:
            return bool(probe()) if probe is not None else False
        except Exception:  # deleted/donated buffer: a read would raise too
            return False

    def on_ready(self, fn):
        """Run ``fn(host_value)`` at materialization (immediately if the
        value already materialized)."""
        with self._lock:
            if self._host is None:
                self._callbacks.append(fn)
                return
            host = self._host
        fn(host)

    def get(self):
        """The host value (numpy). First call blocks and fires callbacks."""
        with self._lock:
            if self._host is None:
                from .. import profiler

                profiler.record_host_sync()
                self._host = np.asarray(self._dev)  # sync-ok: the protocol's one read
                callbacks, self._callbacks = self._callbacks, []
            else:
                callbacks = []
            host = self._host
        for fn in callbacks:
            fn(host)
        return host

    def asnumpy(self):
        return self.get()

    def item(self):
        return self.get().reshape(-1)[0]

    def __float__(self):
        return float(self.item())  # sync-ok: conversion of the materialized host value

    def __int__(self):
        return int(self.item())  # sync-ok: conversion of the materialized host value

    def __bool__(self):
        return bool(self.item())  # sync-ok: conversion of the materialized host value

    def __repr__(self):
        state = "ready" if self._host is not None else "pending"
        return "PendingValue(%s)" % state
