"""Device time by scope, read back from a profiler trace.

The fused steps name their phases (``forward``, ``optimizer``,
``grad_post``), every Gluon block and the hand-written ops name themselves
with ``jax.named_scope`` while JAX traces them, and each ``pallas_call`` has
a ``name=``. XLA carries that name stack on every operation of the compiled
program, and the profiler writes it into the trace's ``*.xplane.pb`` as the
``tf_op`` stat of the operation's *event metadata*, beside ``hlo_category``,
``flops`` and ``bytes_accessed``. ``jax.profiler.ProfileData`` (jax 0.9.0)
shows events and their own stats but not metadata stats, so this module reads
the file itself: a wire-format decoder for the seven xplane messages it needs
(XSpace, XPlane, XLine, XEvent, XStat, XEventMetadata, XStatMetadata), plain
Python, no TensorFlow and no ``google.protobuf``.

``aggregate(trace)`` is the one entry point; ``format_table`` renders what
``mx.profiler.dumps()`` prints after a trace.

How an operation's time is attributed (confirmed on a TPU v5e, PERF.md
section 3): the ``XLA Ops`` line of a device plane nests the body of a
``while`` or a conditional inside the loop's own event, so every operation
counts its *self* time (its duration less its children's) and the self times
of a plane sum to the plane's busy time exactly. Each falls in one phase:

- ``collective``: all-reduce and its kin, by the operation's own name;
- ``backward``: ``transpose(`` in the name stack (JAX's mark of a cotangent
  computation), a scope ending in ``_bwd`` (the bodies of ``custom_vjp``
  backward functions carry no ``transpose``), or ``rematted_computation``;
- ``optimizer`` / ``grad_post`` / ``forward``: the outermost such scope;
- ``other``: the rest (copies and parameter plumbing that no scope owns).
"""
from __future__ import annotations

import glob
import os
import re
import struct

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = "tpu_custom_call"  # the custom-call target of a Pallas kernel
PHASES = ("forward", "backward", "optimizer", "grad_post", "collective", "other")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
GAP_US = 50.0  # idle gaps longer than this are named
LAUNCH_EVENT = "DoEnqueueProgram"  # the host event that carries a launch's run_id
DISPATCH_SPAN = "mxt.step.dispatch"  # its threads are the ones whose spans name a gap


# -- wire format ----------------------------------------------------------
def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint or a fixed
    field (fixed ones as raw little-endian integers), a memoryview for a
    length-delimited one."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            value = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 5:
            value = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError("xplane: wire type %d at byte %d" % (wire, pos))
        yield key >> 3, value


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """One XStat as (name, value); a ``ref_value`` is resolved to the string
    it points at."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class _Plane:
    """One XPlane, decoded as far as its name, stat names and event metadata;
    lines are decoded when asked for."""

    def __init__(self, buf):
        self.name = ""
        self._lines, meta, self.stat_names = [], [], {}
        for f, v in _fields(buf):
            if f == 2:
                self.name = _text(v)
            elif f == 3:
                self._lines.append(v)
            elif f == 4:
                meta.append(v)
            elif f == 5:
                _, body = _map_entry(v)
                sid = sname = None
                for g, w in _fields(body):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _text(w)
                self.stat_names[sid] = sname
        self._meta_raw, self._meta = meta, None

    @property
    def metadata(self):
        """{metadata id: {'name', 'display_name', <stat name>: value}}"""
        if self._meta is None:
            self._meta = {}
            for entry in self._meta_raw:
                _, body = _map_entry(entry)
                m, mid = {"name": "", "display_name": ""}, None
                for f, v in _fields(body):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        m["name"] = _text(v)
                    elif f == 4:
                        m["display_name"] = _text(v)
                    elif f == 5:
                        k, val = _stat(v, self.stat_names)
                        m[k] = val
                self._meta[mid] = m
        return self._meta

    def lines(self, wanted=None, only=None, stats_for=()):
        """[(line name, [(metadata id, start_ps, end_ps, stats or None)])],
        times in picoseconds: the line's ``timestamp_ns`` plus the event's
        offset. ``wanted`` keeps lines by name and ``only`` events by
        metadata id (a host plane holds a million Python calls: they are
        passed over after one varint); an event's own stats are decoded only
        where its metadata id is in ``stats_for``."""
        out = []
        for raw in self._lines:
            name, base_ns, events = "", 0, []
            for f, v in _fields(raw):
                if f == 2:
                    name = _text(v)
                elif f == 3:
                    base_ns = _signed(v)
                elif f == 4:
                    events.append(v)
            if wanted is not None and name not in wanted:
                continue
            base, decoded = base_ns * 1000, []
            for ev in events:
                if only is not None and (ev[0] != 8 or _varint(ev, 1)[0] not in only):
                    continue  # 8: field 1 as a varint, the metadata id, comes first
                mid = off = dur = 0
                raw_stats = []
                for f, v in _fields(ev):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = v
                    elif f == 3:
                        dur = v
                    elif f == 4:
                        raw_stats.append(v)
                st = None
                if mid in stats_for:
                    st = dict(_stat(v, self.stat_names) for v in raw_stats)
                decoded.append((mid, base + off, base + off + dur, st))
            out.append((name, decoded))
        return out

    def ids_named(self, match):
        """Metadata ids whose event name satisfies ``match``."""
        return {mid for mid, m in self.metadata.items() if match(m["name"])}


def find_xplane(trace):
    """The newest ``*.xplane.pb`` under a trace directory, or the file itself."""
    if os.path.isfile(trace):
        return trace
    paths = glob.glob(os.path.join(trace, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def read_planes(path):
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_Plane(v) for f, v in _fields(buf) if f == 1]


# -- attribution ----------------------------------------------------------
# components of a name stack that are JAX's own and not a user scope
_SCOPE_SKIP = frozenset((
    "checkpoint", "rematted_computation", "remat", "remat2", "closed_call",
    "core_call", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "shard_map", "while", "body", "cond", "body_fun", "cond_fun"))
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
_CALLS = ("jit", "pjit")  # jit(f) names a function called, not a scope


def scopes_of(tf_op):
    """The name stack's user scopes, outermost first:
    ``jit(step)/transpose(jvp(forward))/stage1/batchnorm0/batchnorm_bwd/mul:``
    -> ``['forward', 'stage1', 'batchnorm0', 'batchnorm_bwd']``. The
    transformations JAX wraps a scope in (``jvp(..)``, ``transpose(..)``,
    ``vmap(..)``) are peeled, ``jit(f)`` and JAX's own components (``while``,
    ``body``, ``checkpoint``, ``branch_0_fun``) are dropped, and so is the
    last component, the primitive's own name."""
    parts, depth, start = [], 0, 0
    path = tf_op.split(";", 1)[0].rstrip(":")  # a fusion may list several: the first
    for i, ch in enumerate(path):  # split on '/' outside parentheses
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    out = []
    for part in parts:  # path[start:], the primitive, is left out
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = "" if m.group(1) in _CALLS else m.group(2)
        out.extend(p for p in part.split("/")
                   if p and p not in _SCOPE_SKIP and not p.startswith("branch_"))
    return out


def phase_of(op_name, tf_op):
    """The one phase an operation belongs to (module docstring)."""
    if op_name.lstrip("%").startswith(COLLECTIVES):
        return "collective"
    scopes = scopes_of(tf_op)
    if "transpose(" in tf_op or "rematted_computation" in tf_op \
            or any(s.endswith("_bwd") for s in scopes):
        return "backward"
    for s in scopes:
        if s in ("forward", "optimizer", "grad_post"):
            return s
    return "other"


def _self_times(events):
    """[(metadata id, self picoseconds)] of one line's events, where an event
    that lies inside another (a loop's body inside the loop) is taken out of
    the outer one's time. Also the busy picoseconds (the union) and the
    merged busy intervals."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack, merged = [], [], []  # stack of [mid, end, self_ps]
    for mid, s, e, _ in events:
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            e = min(e, stack[-1][1])  # a child never outlasts its parent
            stack[-1][2] -= e - s
        elif merged and s <= merged[-1][1]:  # back to back at the top level
            merged[-1][1] = e
        else:
            merged.append([s, e])
        stack.append([mid, e, e - s])
    while stack:
        top = stack.pop()
        out.append((top[0], top[2]))
    return out, sum(e - s for s, e in merged), merged


def _host_events(planes, prefixes):
    """One pass over the host planes: [(name, start_ps, end_ps, arguments,
    thread)] of the events of every thread whose name starts with one of
    ``prefixes`` (the arguments are what the ``TraceAnnotation`` was given; a
    thread is a line of a plane, by number), and {(device ordinal, run_id):
    picosecond at which the host enqueued that execution} from the runtime's
    own ``DoEnqueueProgram`` events."""
    spans, launches = [], {}
    for p, plane in enumerate(planes):
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        named = plane.ids_named(lambda n: n.startswith(prefixes))
        enqueue = plane.ids_named(lambda n: n == LAUNCH_EVENT)
        if not named and not enqueue:
            continue
        both = named | enqueue
        for t, (_, events) in enumerate(plane.lines(only=both, stats_for=both)):
            for mid, s, e, st in events:
                if mid in named:
                    spans.append((plane.metadata[mid]["name"], s, e, st, (p, t)))
                elif st.get("run_id") is not None:
                    key = (st.get("device_ordinal", 0), st["run_id"])
                    launches[key] = min(s, launches.get(key, s))
    return spans, launches


def _by_start(span):
    return span[1:3]


def host_spans(trace, prefixes=("mxt.", "bench.")):
    """[(name, start_ps, end_ps, arguments, thread)] of the program's spans in
    a trace, from every host thread, by start: what a check of one batch's spans, or a
    percentile of one span's lengths, is computed from. A trace taken on the
    CPU has them too, where ``aggregate`` finds no device and returns None."""
    path = find_xplane(trace)
    if path is None:
        return []
    return sorted(_host_events(read_planes(path), tuple(prefixes))[0], key=_by_start)


def span_totals(spans, lo=None, hi=None):
    """({name: seconds}, {name: calls}) of ``spans`` over all threads, each
    clipped to ``lo``..``hi`` picoseconds (a span outside counts nowhere; one
    of no length counts as a call where it lies inside)."""
    seconds, calls = {}, {}
    for name, s, e, _, _ in spans:
        if lo is not None:
            if e < lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
        seconds[name] = seconds.get(name, 0) + (e - s)
        calls[name] = calls.get(name, 0) + 1
    return {k: v / 1e12 for k, v in sorted(seconds.items())}, calls


def aggregate(trace, depth=2, window=None, top=20):
    """Device seconds by phase, scope, kernel and category from a profiler
    trace (a directory that ``jax.profiler.start_trace`` wrote into, or an
    ``.xplane.pb`` file). Seconds are averaged over the device planes.

    ``window`` names a host span (a ``TraceAnnotation``): operations are
    clipped to its extent on the uncorrected clocks, as the benchmark's
    reduction clips them to ``bench.trace_window``. ``depth`` is how many
    scopes deep ``scope_s`` goes, ``top`` how many operations ``ops`` lists
    (None: all of them).

    Returns None where the trace holds no device operations, else a dict:
    ``devices``, ``busy_s`` (union of the operations' intervals), ``window_s``,
    ``phase_s`` (every phase of ``PHASES``; they sum to ``busy_s``),
    ``scope_s`` ({'forward/net/stage1': s}), ``named_s`` (by any single scope
    name, e.g. ``batchnorm``, ``attention_bwd``: an operation counts under
    every scope of its stack), ``kind_s`` (by the innermost scope with its
    number taken off: ``conv2d``, ``batchnorm_bwd``, ``dense``), ``kernel_s`` /
    ``kernel_calls`` (by ``pallas_call`` name), ``category_s`` (by
    ``hlo_category``), ``ops`` (the operations that took most, each with its
    scope path, category, phase, result shape, seconds and calls), ``flops`` and
    ``bytes_accessed`` (as XLA's cost model wrote them, summed over the
    operations run), ``clock_offset_us``, ``launch_pairs``, ``idle_gaps``,
    ``idle_by_span_s``, ``host_span_s`` and ``host_span_calls`` (see
    ``_clock``), and ``spans`` (the ``mxt.*`` / ``bench.*`` spans themselves, as
    ``host_spans`` lists them: a trace of a host-fed loop is hundreds of
    megabytes, read once)."""
    path = find_xplane(trace)
    if path is None:
        return None
    planes = read_planes(path)
    lo = hi = None
    spans, launches = _host_events(
        planes, ("mxt.", "bench.") + ((window,) if window else ()))
    if window:
        ws = [(s, e) for n, s, e, _, _ in spans if n == window]
        if ws:
            lo, hi = min(s for s, _ in ws), max(e for _, e in ws)
    tables = {k: {} for k in ("phase", "scope", "named", "kind", "kernel", "calls",
                              "category", "ops")}
    flops = nbytes = busy = 0
    devices, extent, first = 0, [], None
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for _, events in plane.lines(wanted=(OPS_LINE,)):
            if lo is not None:
                events = [(m, max(s, lo), min(e, hi), st) for m, s, e, st in events
                          if min(e, hi) > max(s, lo)]
            if not events:
                continue
            devices += 1
            selfs, b, merged = _self_times(events)
            busy += b
            extent.append((merged[0][0], merged[-1][1]))
            if first is None:
                first = (plane, merged)
            totals = {}  # metadata id -> [self picoseconds, calls]
            for mid, ps in selfs:
                ent = totals.setdefault(mid, [0, 0])
                ent[0] += ps
                ent[1] += 1
            for mid, (ps, calls) in totals.items():
                m = plane.metadata.get(mid, {})
                op, keys = _describe(m, depth)
                for table, key in keys:
                    tables[table][key] = tables[table].get(key, 0) + ps
                if keys[-1][0] == "kernel":
                    kname = keys[-1][1]
                    tables["calls"][kname] = tables["calls"].get(kname, 0) + calls
                ent = tables["ops"].setdefault(op, [0, 0])
                ent[0] += ps
                ent[1] += calls
                flops += _number(m.get("flops")) * calls
                nbytes += _number(m.get("bytes_accessed")) * calls
    if not devices:
        return None
    if lo is None:
        lo, hi = min(s for s, _ in extent), max(e for _, e in extent)

    def sec(table):
        return {k: v / devices / 1e12
                for k, v in sorted(tables[table].items(), key=lambda kv: -kv[1])}

    ops = sorted(tables["ops"].items(), key=lambda kv: -kv[1][0])[:top]
    out = {"devices": devices, "busy_s": busy / devices / 1e12,
           "window_s": (hi - lo) / 1e12,
           "phase_s": {p: tables["phase"].get(p, 0) / devices / 1e12 for p in PHASES},
           "scope_s": sec("scope"), "named_s": sec("named"), "kind_s": sec("kind"),
           "kernel_s": sec("kernel"),
           "kernel_calls": {k: v / devices for k, v in tables["calls"].items()},
           "category_s": sec("category"),
           "ops": [{"name": k[0], "scope": k[1], "category": k[2], "phase": k[3],
                    "shape": k[4],
                    "seconds": v[0] / devices / 1e12, "calls": v[1] / devices}
                   for k, v in ops],
           "flops": flops / devices, "bytes_accessed": nbytes / devices}
    out.update(_clock(first, spans, launches, lo, hi))
    out["spans"] = sorted(spans, key=_by_start)
    return out


def _describe(meta, depth):
    """An operation's row in ``ops`` (name, scope path, category, phase, the
    result's shape with its layout) and the (table, key) pairs its time is
    added under; a kernel's pair is last."""
    tf_op = meta.get("tf_op") or ""
    own = (meta.get("display_name")
           or meta.get("name", "").partition(" = ")[0]).lstrip("%")
    scopes = scopes_of(tf_op)
    phase = phase_of(own, tf_op)
    category = meta.get("hlo_category") or "(none)"
    keys = [("phase", phase), ("scope", "/".join(scopes[:depth]) or "(none)"),
            ("kind", re.sub(r"\d+$", "", scopes[-1]) if scopes else "(none)"),
            ("category", category)]
    keys += [("named", n) for n in set(scopes)]
    kernel = kernel_name(meta)
    if kernel:
        keys.append(("kernel", kernel))
    return (own, "/".join(scopes), category, phase,
            meta.get("shape_with_layout") or ""), keys


def _number(value):
    """A metadata stat as a number: the profiler writes some as text."""
    try:
        return float(value or 0)
    except ValueError:
        return 0.0


def kernel_name(meta):
    """The ``pallas_call``'s ``name=`` where the operation is a Mosaic kernel
    (its text in the trace names the target ``tpu_custom_call``), else None.
    XLA names the instruction after the kernel (``flash_attention_fwd.3``);
    a kernel with no ``name=`` would be listed by its body's function name."""
    text = meta.get("name", "")
    if KERNEL_MARK not in text:
        return None
    own = (meta.get("display_name") or text.partition(" = ")[0]).lstrip("%")
    return re.sub(r"\.\d+$", "", own)


def _clock(first, spans, launches, lo, hi):
    """The offset between the device's clock and the host's, and the idle
    gaps named on the corrected clock.

    Each event of the ``XLA Modules`` line carries the ``run_id`` that the
    host's launch event of the same execution carries. A program cannot start
    on the device before the host has launched it, so the least (device start
    - host launch) over the trace's launches bounds the offset from above and
    is taken for it: ``clock_offset_us`` is what to subtract from a device
    time to put it on the host's clock. Gaps longer than ``GAP_US`` on the
    first device are named by the shortest ``mxt.*`` / ``bench.*`` span that
    covers their start, among the spans of the threads that dispatch steps
    (those with a ``mxt.step.dispatch`` span; every thread where the trace has
    none): a pool of decode threads is always inside some span, and what holds
    the device back is what the thread that launches was doing.
    ``idle_gaps`` lists the ten longest, and
    ``idle_by_span_s`` sums all of them by that name, those that no span
    covers under ``(no span)`` and the shorter ones, which the rule does not
    name, under ``(short)``. Its values add up to the window less the first
    device's busy time (``window_s - busy_s`` on one device).
    ``host_span_s`` / ``host_span_calls`` are the ``mxt.*`` spans of every
    thread over the same window on the host's clock."""
    out = {"clock_offset_us": None, "launch_pairs": 0, "idle_gaps": [],
           "idle_by_span_s": {}, "host_span_s": {}, "host_span_calls": {}}
    if first is None:
        return out
    plane, merged = first
    ordinal = int(plane.name.rsplit(":", 1)[-1]) if plane.name[-1].isdigit() else 0
    modules = plane.ids_named(lambda n: True)
    diffs = []
    for _, events in plane.lines(wanted=(MODULES_LINE,), stats_for=modules):
        for _, s, _, st in events:
            at = launches.get((ordinal, (st or {}).get("run_id")))
            if at is not None:
                diffs.append(s - at)
    offset = min(diffs) if diffs else 0
    if diffs:
        out["clock_offset_us"] = offset / 1e6
        out["launch_pairs"] = len(diffs)
    dispatchers = {sp[4] for sp in spans if sp[0] == DISPATCH_SPAN}
    naming = [sp for sp in spans if sp[4] in dispatchers] if dispatchers else spans
    gaps, by_span, edge = [], {}, lo
    for s, e in merged + [[hi, hi]]:
        if s - edge > GAP_US * 1e6:
            at = edge - offset
            cover = [(e2 - s2, n) for n, s2, e2, _, _ in naming if s2 <= at < e2]
            name = min(cover)[1] if cover else "(no span)"
            gaps.append((s - edge, name))
        else:
            name = "(short)"
        if s > edge:
            by_span[name] = by_span.get(name, 0) + s - edge
        edge = max(edge, e)
    gaps.sort(reverse=True)
    out["idle_gaps"] = [[n, d / 1e12] for d, n in gaps[:10]]
    out["idle_by_span_s"] = {n: d / 1e12 for n, d in
                             sorted(by_span.items(), key=lambda kv: -kv[1])}
    out["host_span_s"], out["host_span_calls"] = span_totals(
        [sp for sp in spans if sp[0].startswith("mxt.")], lo - offset, hi - offset)
    return out


def format_table(agg, top=12):
    """The device half of ``mx.profiler.dumps()``."""
    if not agg:
        return "Device Statistics: no device operations in the trace"
    busy = agg["busy_s"] or 1.0
    lines = ["Device Statistics (%d device%s, busy %.3f ms of %.3f ms):"
             % (agg["devices"], "" if agg["devices"] == 1 else "s",
                1e3 * agg["busy_s"], 1e3 * agg["window_s"])]

    def rows(title, table, limit):
        lines.append("  %-44s %12s %8s" % (title, "Time(ms)", "Share"))
        for k, v in list(table.items())[:limit]:
            lines.append("    %-42s %12.3f %7.2f%%" % (k[:42], 1e3 * v, 100 * v / busy))

    rows("Phase", agg["phase_s"], len(PHASES))
    rows("Scope", agg["scope_s"], top)
    rows("Innermost scope, by kind", agg["kind_s"], top)
    if agg["kernel_s"]:
        rows("Kernel", agg["kernel_s"], top)
    rows("HLO category", agg["category_s"], top)
    lines.append("  %-12s %8s %7s  %s" % ("Time(ms)", "Share", "Calls",
                                          "Operation [phase] scope"))
    for op in agg["ops"][:top]:
        lines.append("  %12.3f %7.2f%% %7.0f  %s [%s] %s"
                     % (1e3 * op["seconds"], 100 * op["seconds"] / busy, op["calls"],
                        op["name"], op["phase"], op["scope"] or op["category"]))
    if agg["clock_offset_us"] is not None:
        lines.append("  clock_offset_us %.3f (device - host, least of %d launches)"
                     % (agg["clock_offset_us"], agg["launch_pairs"]))
    for name, secs in agg["idle_gaps"]:
        lines.append("  idle gap %10.3f ms under %s" % (1e3 * secs, name))
    for name, secs in agg["idle_by_span_s"].items():
        lines.append("  idle in all %7.3f ms under %s" % (1e3 * secs, name))
    for name, secs in agg["host_span_s"].items():
        lines.append("  host span %9.3f ms in %6d of %s"
                     % (1e3 * secs, agg["host_span_calls"][name], name))
    return "\n".join(lines)
