"""Training-health plane — on-device per-layer gradient telemetry,
host-side anomaly detection, fleet skew watch, and a declarative rules
engine (the training twin of the serving observability stack).

The reference exposed per-tensor training statistics through
``mx.mon.Monitor`` (a stat_func tapped on every executor output) and
``MXNET_PROFILER``-driven dumps. Both assume an eager engine where every
tensor crosses the host per step. In the one-launch world that design is
exactly the regression class ``tools/check_host_syncs.py`` polices: a
per-step host read of a gradient norm would re-synchronize the async
dispatch window and undo the pipelining (PR 4/PR 7). This module
rebuilds the Monitor's job under the sync budget:

- :func:`stat_row` packs per-layer grad-norm / param-norm /
  update-ratio plus the step loss into ONE small float32 row INSIDE the
  donated step program (XLA fuses the reductions into the step — intra-
  program accumulation is nearly free, arXiv:2301.13062). The step
  builders (gluon/train_step.py, parallel/sharded.py) stage the row
  into their InflightWindow, so K steps of stats ride the SAME single
  deferred read the engine already performs: syncs/step is bit-equal
  with health on vs off (tests/test_health.py asserts it).
- :class:`HealthMonitor` consumes retired rows host-side (window
  retirement is the one sanctioned materialization point): loss-spike
  (z-score vs a host EMA/variance tracker), grad-explosion/vanish, and
  dead-layer detectors emit typed flight-recorder events,
  ``mxt_health_anomalies_total{kind,layer}``, an optional post-mortem,
  and — with ``MXT_HEALTH_GUARD_HOOK`` — feed the
  ``MXT_SKIP_NONFINITE`` guard's host bookkeeping (never the weights:
  detection is observability, the on-device skip stays the guard's own
  ``lax.cond``).
- per-host gauges (``mxt_health_host_step_ms``,
  ``mxt_health_grad_fingerprint``) publish into the process registry
  the PR 13 FleetCollector already scrapes; :func:`fleet_skew` turns
  the merged per-member view into straggler/divergence verdicts
  (``mxt_health_step_skew_ratio``, slowest-host gauge) the reshard
  controller and autoscaler can consume.
- :class:`HealthRule` / :class:`RuleEngine` evaluate declarative
  threshold / burn-rate / trend rules over the metrics registry
  (training AND serving SLOs); verdicts render as the telemetry
  endpoint's ``/health`` route and mxt_top's ``health`` section.

Host/device split: everything here is host arithmetic over rows the
engine already read, wall clocks, and registry values — the module is
scanned by tools/check_host_syncs.py with the full pattern set, and the
only annotated reads are window-retirement rows that are host data by
construction.
"""
from __future__ import annotations

import json
import math
import threading
import time

import numpy as _np

__all__ = [
    "enabled", "stat_layout", "stat_row", "HealthMonitor",
    "HealthRule", "RuleEngine", "default_engine", "add_rule",
    "evaluate_rules", "install_default_rules", "fleet_skew",
    "render_health", "handle_health", "reset",
]


def _config():
    from . import config

    return config


def _telemetry():
    from . import telemetry

    return telemetry


def _diag():
    from . import diagnostics

    return diagnostics


def enabled():
    """Whether the fused step builders compile the stat row into their
    program — read at build time, like MXT_SKIP_NONFINITE."""
    return bool(_config().get("MXT_HEALTH"))


# ---------------------------------------------------------------------------
# on-device stat packing (called INSIDE the donated step program)
# ---------------------------------------------------------------------------
def stat_layout(layer_names):
    """Column names of one packed stat row, in order: the step loss,
    then a grad-norm / param-norm / update-ratio block per trainable
    layer, then the guard bit (this step's non-finite flag, 0.0 when
    no guard is compiled in)."""
    cols = ["loss"]
    cols += ["grad_norm:%s" % n for n in layer_names]
    cols += ["param_norm:%s" % n for n in layer_names]
    cols += ["update_ratio:%s" % n for n in layer_names]
    cols.append("nonfinite")
    return cols


def stat_row(loss_vec, grads, old_vals, new_vals, mask=None):
    """Pack one step's health stats into a (3L+2,) float32 row — pure
    jnp, traced INSIDE the donated step program (never a host
    transfer): per-layer gradient L2 norm, post-update parameter L2
    norm, and update ratio ``||w_new - w_old|| / (||w_old|| + eps)``
    (a skipped guard step packs ratio 0 — new == old by construction).
    ``mask`` is the guard bitmask whose newest bit is THIS step; only
    that bit is packed (exact in float32, unlike the full shifted
    mask), so guard-mode callers retire flags and stats from the same
    stacked read."""
    import jax.numpy as jnp

    f32 = jnp.float32

    def _norm(a):
        return jnp.linalg.norm(jnp.ravel(a).astype(f32))

    eps = f32(1e-12)
    parts = [jnp.mean(jnp.asarray(loss_vec, f32)).reshape(1)]
    if grads:
        parts.append(jnp.stack([_norm(g) for g in grads]))
        parts.append(jnp.stack([_norm(w) for w in new_vals]))
        parts.append(jnp.stack(
            [_norm(w2 - w1) / (_norm(w1) + eps)
             for w1, w2 in zip(old_vals, new_vals)]))
    if mask is None:
        bit = jnp.zeros((1,), f32)
    else:
        bit = (mask & jnp.uint32(1)).astype(f32).reshape(1)
    parts.append(bit)
    return jnp.concatenate(parts)


def apply_grad_spike(grads, layer_names, scale):
    """Compile the seeded ``grad_spike`` chaos rule into the step
    program: multiply ONE layer's gradient by the traced ``scale``
    scalar (1.0 on every non-firing step — the host passes S only on
    the step the seeded dice selected). Returns the grads unchanged
    when no rule is armed. Called at trace time by the step builders;
    the rule params come from resilience.fault_point()."""
    from . import resilience

    rule = resilience.fault_point().rule("grad_spike")
    if not rule:
        return grads
    idx = int(rule.get("layer", 0))
    idx = max(0, min(idx, len(grads) - 1)) if grads else 0
    out = list(grads)
    if out:
        out[idx] = out[idx] * scale
    return tuple(out)


def grad_spike_scale(dispatch_no):
    """Host-side half of the ``grad_spike`` rule: the gradient scale to
    pass into this dispatch (1.0 = no perturbation). Consults the
    seeded FaultInjector once the dispatch count passes ``after=`` —
    deterministic under MXT_CHAOS_SEED, n-capped like every rule."""
    from . import resilience

    fp = resilience.fault_point()
    rule = fp.rule("grad_spike")
    if not rule:
        return 1.0
    after = int(rule.get("after", 0))
    if dispatch_no <= after:
        return 1.0
    if not fp.should("grad_spike"):
        return 1.0
    return float(rule.get("scale", 1e4))  # sync-ok: host rule param


# ---------------------------------------------------------------------------
# host-side anomaly detection (window retirement)
# ---------------------------------------------------------------------------
class HealthMonitor:
    """Consume retired stat rows and detect anomalies — pure host
    arithmetic on rows the engine's deferred read already materialized.

    One monitor per step builder (train_step / sharded); ``consume``
    runs inside the InflightWindow's ``on_values`` retirement callback,
    in dispatch order, possibly K steps after the launch. Detectors:

    - loss_spike: |loss - EMA| > z * stddev (after an 8-step warmup)
    - grad_explosion: a layer grad norm above MXT_HEALTH_EXPLODE or
      non-finite
    - dead_layer: MXT_HEALTH_DEAD_STEPS consecutive steps with a layer
      grad norm below MXT_HEALTH_VANISH

    Each anomaly emits a typed flight-recorder event
    (``health_anomaly``), bumps ``mxt_health_anomalies_total{kind,
    layer}``, optionally dumps ONE post-mortem per kind
    (MXT_HEALTH_POSTMORTEM), and — when MXT_HEALTH_GUARD_HOOK is on —
    routes grad explosions into the guard's host bookkeeping via
    ``guard_hook`` (numerics untouched: the hook is bookkeeping only).
    """

    _WARMUP = 8  # steps before the loss-spike z-score is trusted

    def __init__(self, layer_names, stream="fused_step", guard_hook=None):
        cfg = _config()
        self.layer_names = list(layer_names)
        self.columns = stat_layout(self.layer_names)
        self.stream = stream
        self._guard_hook = guard_hook
        self._spike_z = float(cfg.get("MXT_HEALTH_SPIKE_Z"))  # sync-ok: host config scalar
        self._explode = float(cfg.get("MXT_HEALTH_EXPLODE"))  # sync-ok: host config scalar
        self._vanish = float(cfg.get("MXT_HEALTH_VANISH"))  # sync-ok: host config scalar
        self._dead_steps = max(1, int(cfg.get("MXT_HEALTH_DEAD_STEPS")))
        self._decay = float(cfg.get("MXT_HEALTH_EMA_DECAY"))  # sync-ok: host config scalar
        self._hook_on = bool(cfg.get("MXT_HEALTH_GUARD_HOOK"))
        self._postmortem = bool(cfg.get("MXT_HEALTH_POSTMORTEM"))
        self._lock = threading.Lock()
        self._ema = None
        self._var = 0.0
        self._seen = 0
        self._vanish_run = [0] * len(self.layer_names)
        self._dumped_kinds = set()
        self._first_wall = None
        self.anomaly_count = 0
        tel = _telemetry()
        self._anom = tel.counter(
            "mxt_health_anomalies_total",
            "Training-health anomalies by detector kind and layer "
            "(health.py — evaluated host-side at window retirement).",
            ("kind", "layer"))
        self._g_ema = tel.gauge(
            "mxt_health_loss_ema",
            "Host-side EMA of the fused step loss (the loss-spike "
            "detector's baseline).")
        self._g_var = tel.gauge(
            "mxt_health_loss_var",
            "Host-side EMA variance of the fused step loss.")
        self._g_gnorm = tel.gauge(
            "mxt_health_grad_norm",
            "Per-layer gradient L2 norm from the last retired stat row "
            "(computed on device inside the fused step).", ("layer",))
        self._g_uratio = tel.gauge(
            "mxt_health_update_ratio",
            "Per-layer ||delta_w|| / ||w|| from the last retired stat "
            "row.", ("layer",))
        self._g_fp = tel.gauge(
            "mxt_health_grad_fingerprint",
            "Global gradient-norm fingerprint (L2 over all layers) — "
            "the fleet skew watch compares it across members to catch "
            "numeric divergence.")
        self._g_step = tel.gauge(
            "mxt_health_host_step_ms",
            "Mean wall-clock ms per retired training step on THIS host "
            "— the fleet skew watch's straggler signal.")

    # -- the retirement callback ------------------------------------------
    def consume(self, step_no, row):
        """Land ONE retired step's stat row into detection + gauges.
        ``row`` is host data (the engine's stacked deferred read
        already materialized it)."""
        row = _np.asarray(row, dtype=_np.float64)  # sync-ok: retired host row
        now = time.perf_counter()
        with self._lock:
            self._seen += 1
            if self._first_wall is None:
                self._first_wall = now
            elif self._seen > 1:
                span = now - self._first_wall
                self._g_step.set(1000.0 * span / (self._seen - 1))
            L = len(self.layer_names)
            loss = float(row[0])  # sync-ok: retired host row scalar
            gnorms = row[1:1 + L]
            uratios = row[1 + 2 * L:1 + 3 * L]
            self._check_loss(loss, step_no)
            fp = 0.0
            for i, name in enumerate(self.layer_names):
                g = float(gnorms[i])  # sync-ok: retired host row scalar
                fp += g * g if math.isfinite(g) else 0.0
                self._g_gnorm.labels(name).set(g)
                self._g_uratio.labels(name).set(
                    float(uratios[i]))  # sync-ok: retired host row scalar
                self._check_layer(name, i, g, step_no)
            self._g_fp.set(math.sqrt(fp))

    def _check_loss(self, loss, step_no):
        if self._ema is None:
            self._ema, self._var = loss, 0.0
            self._g_ema.set(loss)
            return
        sd = math.sqrt(max(self._var, 0.0))
        if not math.isfinite(loss):
            self._anomaly("loss_spike", "loss", step_no, loss)
        elif self._seen > self._WARMUP and sd > 0.0 and \
                abs(loss - self._ema) > self._spike_z * sd:
            self._anomaly("loss_spike", "loss", step_no, loss)
        if math.isfinite(loss):
            d = loss - self._ema
            a = 1.0 - self._decay
            self._ema += a * d
            self._var = self._decay * (self._var + a * d * d)
        self._g_ema.set(self._ema)
        self._g_var.set(self._var)

    def _check_layer(self, name, i, gnorm, step_no):
        if not math.isfinite(gnorm) or gnorm > self._explode:
            self._anomaly("grad_explosion", name, step_no, gnorm)
            if self._hook_on and self._guard_hook is not None:
                # the MXT_SKIP_NONFINITE host bookkeeping path —
                # skipped-step counter + AMP backoff, never the weights
                self._guard_hook()
            self._vanish_run[i] = 0
            return
        if gnorm < self._vanish:
            self._vanish_run[i] += 1
            if self._vanish_run[i] == self._dead_steps:
                self._anomaly("dead_layer", name, step_no, gnorm)
        else:
            self._vanish_run[i] = 0

    def _anomaly(self, kind, layer, step_no, value):
        self.anomaly_count += 1
        self._anom.labels(kind, layer).inc()
        _diag().record_event("health_anomaly", detector=kind,
                             layer=layer, stream=self.stream,
                             step=int(step_no),
                             value=float(value)  # sync-ok: host detector scalar
                             if math.isfinite(value) else repr(value))
        if self._postmortem and kind not in self._dumped_kinds:
            self._dumped_kinds.add(kind)
            try:
                _diag().dump_postmortem(
                    "health_anomaly", extra={
                        "kind": kind, "layer": layer,
                        "step": int(step_no), "stream": self.stream})
            except Exception:  # noqa: BLE001 — diagnostics must not fail a step
                pass


# ---------------------------------------------------------------------------
# declarative rules engine
# ---------------------------------------------------------------------------
def _metric_value(name, labels=None, quantile=None):
    """Current value of a registry metric (sum over children, or the
    one child matching ``labels``); histogram families read as the
    requested quantile. None when the family doesn't exist yet."""
    tel = _telemetry()
    fam = tel.registry().get(name)
    if fam is None:
        return None
    want = None
    if labels is not None:
        want = tuple(str(labels[k]) for k in fam.labelnames)
    if fam.kind == "histogram":
        total = None
        for values, child in fam.children().items():
            if want is not None and values != want:
                continue
            snap = child.snapshot()
            if snap["count"]:
                q = tel.histogram_quantile(
                    quantile if quantile is not None else 0.5,
                    list(snap["buckets"]), list(snap["counts"]))
                total = q if total is None else max(total, q)
        return total
    total, seen = 0.0, False
    for values, child in fam.children().items():
        if want is not None and values != want:
            continue
        total += float(child.value)  # sync-ok: host registry scalar
        seen = True
    return total if seen else None


class HealthRule:
    """One declarative SLO/health rule over the metrics registry.

    ``kind``:

    - ``threshold`` — breach when the metric's CURRENT value compares
      ``op`` against ``value`` (e.g. skew ratio > 1.5).
    - ``burn_rate`` — breach when the metric's per-second rate of
      change since the previous evaluation compares ``op`` against
      ``value`` (counters: anomaly burn, router-drop burn).
    - ``trend`` — breach when the metric's slope (units/second) over
      the last ``window`` seconds of evaluations compares ``op``
      against ``value`` (e.g. loss EMA rising).

    A rule names the BAD condition, alert-style: ``ok`` is False when
    the condition holds, True when it doesn't, None while the metric
    has no data (or a rate/trend has fewer than two points).
    """

    _OPS = {">": lambda a, b: a > b, "<": lambda a, b: a < b,
            ">=": lambda a, b: a >= b, "<=": lambda a, b: a <= b}

    def __init__(self, name, metric, kind="threshold", op=">", value=0.0,
                 labels=None, quantile=None, window=60.0,
                 description=""):
        if kind not in ("threshold", "burn_rate", "trend"):
            from .base import MXNetError

            raise MXNetError(
                "HealthRule kind must be threshold|burn_rate|trend, "
                "got %r" % (kind,))
        if op not in self._OPS:
            from .base import MXNetError

            raise MXNetError("HealthRule op must be one of %s, got %r"
                             % (sorted(self._OPS), op))
        self.name = name
        self.metric = metric
        self.kind = kind
        self.op = op
        self.value = float(value)  # sync-ok: host rule param
        self.labels = dict(labels) if labels else None
        self.quantile = quantile
        self.window = float(window)  # sync-ok: host rule param
        self.description = description
        self._history = []  # (ts, value) of past evaluations

    def evaluate(self, now=None):
        """One verdict dict: {rule, kind, metric, value, ok, detail}."""
        now = time.time() if now is None else now
        cur = _metric_value(self.metric, self.labels, self.quantile)
        verdict = {"rule": self.name, "kind": self.kind,
                   "metric": self.metric, "value": cur, "ok": None,
                   "detail": ""}
        if cur is None:
            verdict["detail"] = "no data"
            return verdict
        if self.kind == "threshold":
            breach = self._OPS[self.op](cur, self.value)
            verdict["ok"] = not breach
            verdict["detail"] = "%.6g %s %.6g" % (cur, self.op,
                                                  self.value)
            return verdict
        self._history.append((now, cur))
        cutoff = now - self.window
        self._history = [(t, v) for t, v in self._history
                         if t >= cutoff][-64:]
        if len(self._history) < 2:
            verdict["detail"] = "warming (1 sample)"
            return verdict
        if self.kind == "burn_rate":
            (t0, v0), (t1, v1) = self._history[-2], self._history[-1]
        else:  # trend: slope over the whole retained window
            (t0, v0), (t1, v1) = self._history[0], self._history[-1]
        dt = t1 - t0
        if dt <= 0:
            verdict["detail"] = "warming (zero interval)"
            return verdict
        rate = (v1 - v0) / dt
        breach = self._OPS[self.op](rate, self.value)
        verdict["value"] = rate
        verdict["ok"] = not breach
        verdict["detail"] = "%.6g/s %s %.6g" % (rate, self.op, self.value)
        return verdict


class RuleEngine:
    """Evaluate a set of :class:`HealthRule` over the process registry
    and publish verdicts as ``mxt_health_rule_ok{rule}`` gauges (1 ok,
    0 breached; rules with no data publish nothing)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rules = {}

    def add(self, rule):
        with self._lock:
            self._rules[rule.name] = rule
        return rule

    def remove(self, name):
        with self._lock:
            self._rules.pop(name, None)

    def rules(self):
        with self._lock:
            return [self._rules[n] for n in sorted(self._rules)]

    def evaluate(self, now=None):
        verdicts = [r.evaluate(now=now) for r in self.rules()]
        g = _telemetry().gauge(
            "mxt_health_rule_ok",
            "Health-rule verdicts (1 = ok, 0 = breached) from the "
            "declarative rules engine (health.py).", ("rule",))
        for v in verdicts:
            if v["ok"] is not None:
                g.labels(v["rule"]).set(1.0 if v["ok"] else 0.0)
        return verdicts


_default_engine = None
_defaults_installed = False
_lock = threading.Lock()


def default_engine():
    """The process-default rules engine (what /health evaluates),
    seeded with the standard training + serving rules on first use."""
    global _default_engine, _defaults_installed
    with _lock:
        if _default_engine is None:
            _default_engine = RuleEngine()
        if not _defaults_installed:
            _defaults_installed = True
            install_default_rules(_default_engine)
    return _default_engine


def add_rule(rule):
    return default_engine().add(rule)


def evaluate_rules(now=None):
    return default_engine().evaluate(now=now)


def install_default_rules(engine):
    """The standing rule set: training health (anomaly burn, loss
    trend, fleet skew, MoE router drops) plus whatever serving SLO
    rules the serving metrics module declares. Rules over metrics that
    don't exist yet evaluate as no-data — installing them is free."""
    cfg = _config()
    engine.add(HealthRule(
        "train_anomaly_burn", "mxt_health_anomalies_total",
        kind="burn_rate", op=">", value=0.0,
        description="any training-health anomaly actively firing"))
    engine.add(HealthRule(
        "loss_rising", "mxt_health_loss_ema", kind="trend", op=">",
        value=0.0, window=120.0,
        description="loss EMA trending up over the last 2 minutes"))
    engine.add(HealthRule(
        "step_skew", "mxt_health_step_skew_ratio", kind="threshold",
        op=">",
        value=float(cfg.get("MXT_HEALTH_SKEW_RATIO")),  # sync-ok: host config scalar
        description="slowest fleet member vs median step time"))
    engine.add(HealthRule(
        "moe_router_drop_burn", "mxt_moe_router_drops_total",
        kind="burn_rate", op=">", value=0.0,
        description="MoE router actively dropping tokens over expert "
                    "capacity"))
    try:
        from .serving import metrics as serving_metrics

        for rule in serving_metrics.health_rules():
            engine.add(rule)
    except Exception:  # noqa: BLE001 — serving stack optional here
        pass


# ---------------------------------------------------------------------------
# fleet skew watch (runs on the collector host over the merged view)
# ---------------------------------------------------------------------------
def fleet_skew(fleet_registry, skew_ratio=None, divergence=None):
    """Straggler/divergence verdicts over the FleetCollector's merged
    registry: per-member ``mxt_health_host_step_ms`` gives the step-
    time skew (slowest / median), per-member
    ``mxt_health_grad_fingerprint`` gives numeric divergence (data-
    parallel replicas should observe near-identical global grad
    norms). Publishes ``mxt_health_step_skew_ratio`` and the slowest-
    host gauges into the LOCAL registry so the autoscaler / reshard
    controller (and mxt_top) can consume them; returns the verdict
    dict. Pure host arithmetic over already-scraped wire values."""
    cfg = _config()
    if skew_ratio is None:
        skew_ratio = float(cfg.get("MXT_HEALTH_SKEW_RATIO"))  # sync-ok: host config scalar
    if divergence is None:
        divergence = float(cfg.get("MXT_HEALTH_DIVERGENCE"))  # sync-ok: host config scalar
    steps = fleet_registry.member_values("mxt_health_host_step_ms")
    prints = fleet_registry.member_values("mxt_health_grad_fingerprint")
    verdict = {"members": sorted(steps), "skew_ratio": None,
               "slowest": None, "stragglers": [], "divergent": [],
               "ok": True}
    tel = _telemetry()
    if steps:
        vals = sorted(steps.values())
        mid = vals[len(vals) // 2] if len(vals) % 2 else \
            0.5 * (vals[len(vals) // 2 - 1] + vals[len(vals) // 2])
        slowest = max(steps, key=steps.get)
        ratio = steps[slowest] / mid if mid > 0 else 1.0
        verdict["skew_ratio"] = ratio
        verdict["slowest"] = slowest
        verdict["stragglers"] = sorted(
            m for m, v in steps.items()
            if mid > 0 and v / mid > skew_ratio)
        tel.gauge(
            "mxt_health_step_skew_ratio",
            "Slowest fleet member's step time over the fleet median "
            "(health.fleet_skew; >MXT_HEALTH_SKEW_RATIO = straggler)."
        ).set(ratio)
        tel.gauge(
            "mxt_health_slowest_host_step_ms",
            "Step time of the slowest fleet member.", ("member",)
        ).labels(slowest).set(steps[slowest])
    if prints:
        vals = sorted(prints.values())
        mid = vals[len(vals) // 2] if len(vals) % 2 else \
            0.5 * (vals[len(vals) // 2 - 1] + vals[len(vals) // 2])
        scale = max(abs(mid), 1e-12)
        verdict["divergent"] = sorted(
            m for m, v in prints.items()
            if abs(v - mid) / scale > divergence)
    verdict["ok"] = not verdict["stragglers"] and \
        not verdict["divergent"]
    tel.gauge(
        "mxt_health_fleet_ok",
        "1 when the fleet skew watch sees no straggler and no "
        "divergent member, else 0.").set(1.0 if verdict["ok"] else 0.0)
    if not verdict["ok"]:
        _diag().record_event(
            "health_fleet_skew", stragglers=verdict["stragglers"],
            divergent=verdict["divergent"],
            skew_ratio=verdict["skew_ratio"])
    return verdict


# ---------------------------------------------------------------------------
# the /health payload
# ---------------------------------------------------------------------------
def _anomaly_counts():
    """[(kind, layer, count)] sorted by count desc, from the registry
    (empty when no monitor ever fired)."""
    fam = _telemetry().registry().get("mxt_health_anomalies_total")
    if fam is None:
        return []
    rows = [(values[0], values[1], float(ch.value))  # sync-ok: host registry scalar
            for values, ch in fam.children().items()]
    return sorted(rows, key=lambda r: -r[2])


def render_health(now=None):
    """The ``/health`` route payload: rule verdicts, anomaly counts,
    skew + loss gauges, and an overall status (``ok`` unless any rule
    is breached or any anomaly has fired)."""
    verdicts = evaluate_rules(now=now)
    anomalies = _anomaly_counts()
    breached = [v["rule"] for v in verdicts if v["ok"] is False]
    status = "ok" if not breached and not anomalies else "degraded"
    return {
        "status": status,
        "ts": round(time.time(), 6),
        "rules": verdicts,
        "breached": breached,
        "anomalies": [{"kind": k, "layer": l, "count": c}
                      for k, l, c in anomalies[:10]],
        "loss_ema": _metric_value("mxt_health_loss_ema"),
        "step_skew_ratio": _metric_value("mxt_health_step_skew_ratio"),
    }


def handle_health(now=None):
    """(status_code, content_type, body) for the telemetry endpoint's
    ``/health`` route — 200 when ok, 503 when degraded (the standard
    load-balancer health-check contract)."""
    payload = render_health(now=now)
    code = 200 if payload["status"] == "ok" else 503
    return code, "application/json", json.dumps(payload, indent=2)


def reset():
    """Drop the default engine + installed rules (test isolation)."""
    global _default_engine, _defaults_installed
    with _lock:
        _default_engine = None
        _defaults_installed = False
