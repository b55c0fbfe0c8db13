"""ctypes bridge to the native RecordIO engine (mxnet_tpu/src/recordio.cc).

The reference keeps its data plane in C++ (dmlc-core recordio +
src/io/iter_image_recordio_2.cc worker threads); this module is that
layer for the TPU build. The shared library is compiled on first use with
the system g++ (no pybind11 in this image — plain C ABI + ctypes) and
cached next to the source. Everything degrades gracefully: if no
compiler/toolchain is available, ``available()`` returns False and the
pure-Python paths in recordio.py / io/io.py keep working.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "status", "NativeRecordReader", "NativePrefetcher",
           "select_payload_by_starts"]

_HEADER_BYTES = 8  # [magic u32][cflag|len u32] precede every payload


def select_payload_by_starts(offsets, lengths, wanted_starts):
    """Map .idx sidecar offsets (record starts) onto a native scan's
    (payload offsets, lengths), preserving the sidecar's order/subset.
    Returns (offsets, lengths) or None when any start is unknown (stale
    sidecar — callers fall back to the Python reader, whose first read
    surfaces the clear invalid-magic error)."""
    by_start = {int(o) - _HEADER_BYTES: i for i, o in enumerate(offsets)}
    try:
        sel = [by_start[int(w)] for w in wanted_starts]
    except KeyError:
        return None
    return offsets[sel], lengths[sel]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "recordio.cc")
_SO = os.path.join(_HERE, "src", "libmxt_recordio.so")

_lib = None
_lib_lock = threading.Lock()
_build_err = None
_built_here = False  # this process compiled the .so (vs found it on disk)


def _build():
    # compile to a per-process temp name, then atomically rename: N
    # launcher-spawned processes may race to build the same cache path,
    # and a sibling must never CDLL a half-written .so
    tmp = "%s.%d.tmp" % (_SO, os.getpid())
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp]
    res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    if res.returncode != 0:
        raise RuntimeError("native build failed: %s" % res.stderr[-500:])
    os.replace(tmp, _SO)


def _load():
    global _lib, _build_err, _built_here
    if _lib is not None or _build_err is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_err is not None:
            return _lib
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                _build()
                _built_here = True
            lib = ctypes.CDLL(_SO)
        except Exception as e:  # noqa: BLE001 — no toolchain, bad cache, ...
            _build_err = e
            return None
        c = ctypes
        lib.mxt_rio_open.restype = c.c_void_p
        lib.mxt_rio_open.argtypes = [c.c_char_p]
        lib.mxt_rio_close.argtypes = [c.c_void_p]
        lib.mxt_rio_file_size.restype = c.c_int64
        lib.mxt_rio_file_size.argtypes = [c.c_void_p]
        lib.mxt_rio_scan.restype = c.c_int64
        lib.mxt_rio_scan.argtypes = [c.c_void_p, c.POINTER(c.c_int64),
                                     c.POINTER(c.c_int64), c.c_int64]
        lib.mxt_rio_read.restype = c.c_int64
        lib.mxt_rio_read.argtypes = [c.c_void_p, c.c_int64, c.c_int64,
                                     c.POINTER(c.c_uint8)]
        lib.mxt_rio_read_next.restype = c.c_int64
        lib.mxt_rio_read_next.argtypes = [c.c_void_p, c.POINTER(c.c_uint8),
                                          c.c_int64, c.POINTER(c.c_int64)]
        lib.mxt_rio_prefetch_start.restype = c.c_void_p
        lib.mxt_rio_prefetch_start.argtypes = [
            c.c_char_p, c.POINTER(c.c_int64), c.POINTER(c.c_int64),
            c.POINTER(c.c_int64), c.c_int64, c.c_int32, c.c_int32]
        lib.mxt_rio_prefetch_pop.restype = c.c_int64
        lib.mxt_rio_prefetch_pop.argtypes = [c.c_void_p,
                                             c.POINTER(c.c_uint8),
                                             c.c_int64,
                                             c.POINTER(c.c_int64)]
        lib.mxt_rio_prefetch_stop.argtypes = [c.c_void_p]
        _lib = lib
        return _lib


def available():
    """True when the native engine compiled + loaded on this machine."""
    return _load() is not None


def status():
    """Which of the three it was, in words: ``"built"`` (this process
    compiled the library from src/recordio.cc), ``"found"`` (a library
    already beside the source was loaded — it may have been copied in
    with the tree) or ``"absent: <why>"`` (the pure-Python readers run).
    available() alone cannot tell a fresh build from a stale copy."""
    if _load() is None:
        return "absent: %s" % (_build_err,)
    return "built" if _built_here else "found"


def _as_i64_ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


class NativeRecordReader:
    """Random/sequential access over one RecordIO shard, native-parsed."""

    def __init__(self, path):
        lib = _load()
        if lib is None:
            raise RuntimeError("native recordio unavailable: %r"
                               % (_build_err,))
        self._lib = lib
        self._h = lib.mxt_rio_open(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)
        self.path = path
        self._offsets = None
        self._lengths = None

    def close(self):
        if self._h:
            self._lib.mxt_rio_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def scan(self):
        """Index the shard by magic-walk; returns (offsets, lengths)."""
        if self._offsets is not None:
            return self._offsets, self._lengths
        # first pass with cap=0 counts records exactly — sizing the buffer
        # from file_size would allocate GBs for big shards and silently
        # truncate shards full of zero-length records
        empty = np.empty(0, np.int64)
        n = self._lib.mxt_rio_scan(self._h, _as_i64_ptr(empty),
                                   _as_i64_ptr(empty), 0)
        if n < 0:
            raise RuntimeError("corrupt RecordIO framing in %s" % self.path)
        offs = np.empty(n, np.int64)
        lens = np.empty(n, np.int64)
        n2 = self._lib.mxt_rio_scan(self._h, _as_i64_ptr(offs),
                                    _as_i64_ptr(lens), n)
        if n2 != n:
            raise RuntimeError("shard %s changed during scan" % self.path)
        self._offsets = offs
        self._lengths = lens
        return self._offsets, self._lengths

    def __len__(self):
        return len(self.scan()[0])

    def read(self, i):
        """Payload bytes of record i (by shard position)."""
        offs, lens = self.scan()
        return self.read_at(int(offs[i]), int(lens[i]))

    def read_at(self, offset, length):
        """Payload bytes at a known (offset, length) — no scan needed."""
        buf = np.empty(length, np.uint8)
        got = self._lib.mxt_rio_read(
            self._h, offset, length,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if got != length:
            raise IOError("short read in %s" % self.path)
        return buf.tobytes()

    def read_next(self):
        """Next record in file order, or None at EOF."""
        needed = ctypes.c_int64(0)
        cap = 1 << 16
        while True:
            buf = np.empty(cap, np.uint8)
            got = self._lib.mxt_rio_read_next(
                self._h, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                cap, ctypes.byref(needed))
            if got == 0:
                return None
            if got > 0:
                return buf[:got].tobytes()
            if needed.value > cap:  # retry with the exact size
                cap = int(needed.value)
                continue
            raise RuntimeError("corrupt RecordIO framing in %s" % self.path)


class NativePrefetcher:
    """Threaded read-ahead over a shard in a caller-given record order.

    Workers parse + copy records into a bounded ring off the GIL; ``pop``
    returns payloads strictly in the requested order. This is the
    reference's PrefetcherIter/worker-pool role for the raw-bytes stage.
    """

    def __init__(self, path, offsets, lengths, order, num_threads=4,
                 capacity=64):
        lib = _load()
        if lib is None:
            raise RuntimeError("native recordio unavailable: %r"
                               % (_build_err,))
        self._lib = lib
        self.path = path
        offsets = np.ascontiguousarray(offsets, np.int64)
        lengths = np.ascontiguousarray(lengths, np.int64)
        order = np.ascontiguousarray(order, np.int64)
        self._n = len(order)
        self._max_len = int(lengths[order].max()) if self._n else 0
        self._h = lib.mxt_rio_prefetch_start(
            path.encode(), _as_i64_ptr(offsets), _as_i64_ptr(lengths),
            _as_i64_ptr(order), self._n, int(num_threads), int(capacity))
        if not self._h:
            raise RuntimeError("prefetcher failed to start")

    def pop(self):
        """Next payload in order, or None when exhausted."""
        if self._h is None:
            return None
        needed = ctypes.c_int64(0)
        buf = np.empty(max(self._max_len, 1), np.uint8)
        got = self._lib.mxt_rio_prefetch_pop(
            self._h, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            buf.size, ctypes.byref(needed))
        if got == 0:
            return None
        if got == -2:
            raise IOError("prefetch worker IO failure on %s (shard "
                          "truncated or deleted mid-epoch?)" % self.path)
        if got < 0:
            raise RuntimeError("prefetch pop: buffer too small (%d < %d)"
                               % (buf.size, needed.value))
        return buf[:got].tobytes()

    def stop(self):
        if self._h is not None:
            self._lib.mxt_rio_prefetch_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def __iter__(self):
        while True:
            b = self.pop()
            if b is None:
                return
            yield b
