#!/usr/bin/env python
"""mxt_top — a curses-free live console over the telemetry subsystem.

Tails either the Prometheus exposition endpoint
(``MXT_TELEMETRY_PORT`` → ``--url http://127.0.0.1:PORT``) or the JSONL
event sink (``MXT_TELEMETRY_JSONL`` → ``--jsonl path``) and renders the
async-training health signals once per interval:

    steps/s            retired fused steps (delta of step-latency count)
    host_syncs/step    device->host reads per step (<= 1/K when healthy)
    launches/step      compiled dispatches per step (1.0 = fully fused)
    dispatch depth     in-flight fused steps right now
    kv rpc p50/p99     server-side KVStore/membership RPC latency
    workers live/lost  membership view
    skipped steps      non-finite guard skips
    xla compiles       backend compiles + persistent-cache hit/miss
                       (a warm-started replica shows hits only)
    tune cache         kernel-autotuner table hit/miss

and, when a GSPMD sharded step is live (mesh gauges present):

    mesh               device count, per-axis extents (all four on a
                       dp×tp×pp×ep mesh), ZeRO stage
    per-dev bytes      param/optimizer bytes held by ONE device (the
                       memory the ZeRO-1/2/3 ladder shrinks ~dp×)
    reshards           in-place elastic mesh reshards so far
    moe load           per-expert kept-token counts + over-capacity
                       drops (windowed publish_moe_telemetry reads)

and, when the process serves (mxnet_tpu/serving/ metrics present):

    serving tok/s      generated tokens per second
    queue depth        requests waiting for a batch slot (+ active/evicted)
    request p50/p99    decode-phase request latency quantiles
    kv pages           paged KV-cache occupancy vs pool capacity

and, when a fleet router is live (serving/fleet.py + router.py):

    fleet replicas     routable / total, draining + dead counts
    disp/hedge/fail    dispatches, hedged duplicates, failovers (plus
                       fenced-zombie replies refused typed)
    routed p50/p99     fleet-level request latency (submit -> commit)

and, when the autoscaler / QoS layer is live (serving/autoscaler.py +
serving/qos.py):

    autoscale          target replicas + up/down/refused decision
                       counts + the most recent decision direction
    tenant <name>      per-tenant admitted / rejected (over-quota) /
                       preempted / inflight

and, with ``--fleet`` (the telemetry_fleet.py collector's merged page —
member-labeled samples from every scraped fleet member):

    fleet members      live/stale member count + stale names
    fleet tok/s        tokens/s summed across every member
    occupancy          active decode slots per replica
    emb hit ratio      per-embedding-server cache hit ratio
    goodput min/mean   worst / average goodput across workers
    scrape age         seconds since each member's last good scrape

and, when the diagnostics layer publishes (mxnet_tpu/diagnostics.py):

    hbm <pool>         per-subsystem device bytes (params / optimizer /
                       kv_cache / inflight_window / prefetch) + peak
                       watermark — the HBM ledger
    goodput            productive fraction of wall-clock, with the top
                       lost-time causes (compile/checkpoint/reshard/
                       stall/data_wait)
    watchdog stalls    hang-watchdog stall reports so far

Usage::

    python tools/mxt_top.py --url http://127.0.0.1:9109
    python tools/mxt_top.py --jsonl telemetry.jsonl
    python tools/mxt_top.py --url ... --once        # one frame, no clear

Plain ANSI output (\\x1b[H\\x1b[J between frames) — works in any terminal
and under ``watch``/``tee``; no curses, no dependencies.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import urllib.request

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+([0-9eE+.\-]+|NaN|\+Inf)$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text):
    """{(name, frozenset(label items)): value} from exposition text."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        lab = dict(_LABEL_RE.findall(labels)) if labels else {}
        try:
            v = float(value)
        except ValueError:
            v = float("inf") if value == "+Inf" else float("nan")
        out[(name, frozenset(lab.items()))] = v
    return out


def metric_sum(samples, name, **match):
    """Sum of every sample of ``name`` whose labels include ``match``."""
    total, seen = 0.0, False
    want = set(match.items())
    for (n, lab), v in samples.items():
        if n == name and want <= set(lab):
            total += v
            seen = True
    return total if seen else None


def histogram_quantiles(samples, name, qs, **match):
    """Quantiles from ``name_bucket`` samples (cumulative counts summed
    over every labelset matching ``match``)."""
    want = set(match.items())
    per_le = {}
    for (n, lab), v in samples.items():
        if n != name + "_bucket":
            continue
        lab = dict(lab)
        le = lab.pop("le", None)
        if le is None or not want <= set(lab.items()):
            continue
        bound = float("inf") if le == "+Inf" else float(le)
        per_le[bound] = per_le.get(bound, 0.0) + v
    if not per_le:
        return [None] * len(qs)
    bounds = sorted(per_le)
    cum = [per_le[b] for b in bounds]
    total = cum[-1]
    if total <= 0:
        return [None] * len(qs)
    out = []
    for q in qs:
        rank = q * total
        got = None
        for b, c in zip(bounds, cum):
            if c >= rank:
                got = b if b != float("inf") else bounds[-2] \
                    if len(bounds) > 1 else None
                break
        out.append(got)
    return out


def _fmt_s(v):
    if v is None:
        return "--"
    if v < 1e-3:
        return "%.0fus" % (v * 1e6)
    if v < 1.0:
        return "%.1fms" % (v * 1e3)
    return "%.2fs" % v


def _fmt(v, spec="%.2f"):
    return "--" if v is None else spec % v


def _fmt_b(v):
    """Human bytes for the per-device param/opt gauges."""
    if v is None:
        return "--"
    for unit in ("B", "KB", "MB", "GB"):
        if v < 1024 or unit == "GB":
            return ("%.0f%s" if unit == "B" else "%.1f%s") % (v, unit)
        v /= 1024.0


class EndpointSource:
    """Scrape --url (or MXT_TELEMETRY_PORT) once per frame."""

    def __init__(self, url):
        self.url = url if "://" in url else "http://" + url

    def sample(self):
        with urllib.request.urlopen(self.url, timeout=5) as r:
            return parse_prometheus(r.read().decode("utf-8"))


class JsonlSource:
    """Tail --jsonl and rebuild the same sample dict from span/rpc/
    metric rows (approximate: JSONL carries events, not the registry —
    the latest 'metrics' snapshot row supplies gauge/counter values)."""

    def __init__(self, path):
        self.path = path
        self._pos = 0
        self._steps = 0
        self._rpc_lat = []
        self._metrics = {}

    def sample(self):
        try:
            with open(self.path) as f:
                f.seek(self._pos)
                # readline(), not `for line in f`: tell() inside file
                # iteration raises OSError in text mode, which the
                # except below used to swallow — --jsonl mode silently
                # dropped every row
                while True:
                    line = f.readline()
                    if not line:
                        break
                    self._pos = f.tell()
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue
                    kind = row.get("kind")
                    if kind == "span" and row.get("name") == "retire":
                        self._steps += 1
                    elif kind == "rpc_span" and \
                            row.get("side") == "server" and \
                            row.get("latency_s") is not None:
                        self._rpc_lat.append(row["latency_s"])
                        del self._rpc_lat[:-4096]
                    elif kind == "metrics":
                        self._metrics = row.get("data", {})
        except OSError:
            pass
        samples = {("mxt_step_latency_seconds_count", frozenset()):
                   float(self._steps)}
        for key, v in self._metrics.items():
            name, _, labpart = key.partition("{")
            if isinstance(v, dict):
                continue
            # snapshot keys carry unquoted labels (name{axis=data}):
            # surface them as real labels so label-matched sections
            # (mesh axes) render in --jsonl mode too; `src` keeps every
            # labelset distinct
            lab = [("src", key)]
            if labpart:
                lab += re.findall(r'(\w+)=([^,}]+)', labpart)
            samples[(name, frozenset(lab))] = float(v)
        if self._rpc_lat:
            lat = sorted(self._rpc_lat)

            def pick(q):
                return lat[min(len(lat) - 1, int(q * len(lat)))]

            samples[("_jsonl_rpc_p50", frozenset())] = pick(0.50)
            samples[("_jsonl_rpc_p99", frozenset())] = pick(0.99)
        return samples


def render(samples, prev, dt):
    def rate(name, **match):
        cur = metric_sum(samples, name, **match)
        old = metric_sum(prev, name, **match) if prev else None
        if cur is None or old is None or dt <= 0:
            return None, cur
        return max(0.0, cur - old) / dt, cur

    steps_rate, steps_total = rate("mxt_step_latency_seconds_count")
    syncs_rate, _ = rate("mxt_host_syncs_total")
    launch_rate, _ = rate("mxt_xla_launches_total")
    per_step = lambda r: None if (r is None or not steps_rate) \
        else r / steps_rate
    depth = metric_sum(samples, "dispatch_depth")
    p50, p99 = histogram_quantiles(
        samples, "mxt_kvstore_rpc_latency_seconds", (0.50, 0.99),
        side="server")
    if p50 is None:
        p50 = metric_sum(samples, "_jsonl_rpc_p50")
        p99 = metric_sum(samples, "_jsonl_rpc_p99")
    live = metric_sum(samples, "mxt_membership_live_workers")
    lost = metric_sum(samples, "lost_workers")
    skipped = metric_sum(samples, "skipped_nonfinite_steps")
    compiles = metric_sum(samples, "mxt_compiles_total")
    compile_s = metric_sum(samples, "mxt_compile_seconds_sum",
                           phase="compile")
    cc_hits = metric_sum(samples, "mxt_compile_cache_total", outcome="hit")
    cc_miss = metric_sum(samples, "mxt_compile_cache_total",
                         outcome="miss")
    tune_hits = metric_sum(samples, "mxt_tune_cache_hits_total")
    tune_miss = metric_sum(samples, "mxt_tune_cache_misses_total")

    # mesh / GSPMD section (mxnet_tpu/parallel/): only rendered when a
    # ShardedTrainStep has published its mesh gauges — a single-device
    # trainer or a pure server shows no mesh noise
    mesh_dev = metric_sum(samples, "mxt_mesh_devices")
    zero_stage = metric_sum(samples, "mxt_zero_stage")
    mesh_pbytes = metric_sum(samples, "mxt_per_device_param_bytes")
    mesh_obytes = metric_sum(samples, "mxt_per_device_opt_bytes")
    reshards = metric_sum(samples, "mxt_reshard_events_total")
    mesh_axes = []
    for (n, lab), v in sorted(samples.items()):
        if n == "mxt_mesh_axis_size":
            d = dict(lab)
            if "axis" in d:
                mesh_axes.append("%s=%d" % (d["axis"], int(v)))
    # MoE router accounting (parallel/unified.py): only rendered when a
    # PipelineMoEBlock's windowed publish has landed — dense trainers
    # show no expert noise
    moe_load = []
    for (n, lab), v in sorted(samples.items()):
        if n == "mxt_moe_expert_load":
            d = dict(lab)
            if "expert" in d:
                moe_load.append("e%s=%d" % (d["expert"], int(v)))
    moe_drops = metric_sum(samples, "mxt_moe_router_drops_total")

    # diagnostics section (mxnet_tpu/diagnostics.py): only rendered
    # when the HBM ledger / goodput ledger have published — a process
    # without the diagnostics layer shows no memory/goodput noise
    hbm_pools = {}
    hbm_peaks = {}
    for (n, lab), v in sorted(samples.items()):
        d = dict(lab)
        if "pool" in d:
            if n == "mxt_hbm_bytes":
                hbm_pools[d["pool"]] = v
            elif n == "mxt_hbm_peak_bytes":
                hbm_peaks[d["pool"]] = v
    goodput = metric_sum(samples, "mxt_goodput_ratio")
    lost_causes = []
    for (n, lab), v in samples.items():
        if n == "mxt_lost_seconds_total":
            d = dict(lab)
            if "cause" in d and v > 0:
                lost_causes.append((v, d["cause"]))
    lost_causes.sort(reverse=True)
    stalls = metric_sum(samples, "mxt_watchdog_stalls_total")

    # embedding section (mxnet_tpu/embedding/): only rendered when a
    # sharded embedding client has published its cache gauges — a dense
    # trainer or a server-only process shows no embedding noise
    emb_resident = metric_sum(samples, "mxt_embedding_rows_resident")
    emb_hits = metric_sum(samples, "mxt_embedding_cache_hits_total")
    emb_miss = metric_sum(samples, "mxt_embedding_cache_misses_total")
    emb_evict = metric_sum(samples, "mxt_embedding_cache_evictions_total")
    emb_ratio = None
    if emb_hits is not None or emb_miss is not None:
        total = (emb_hits or 0) + (emb_miss or 0)
        emb_ratio = (emb_hits or 0) / total if total else None
    emb_p50, emb_p99 = histogram_quantiles(
        samples, "mxt_embedding_pull_seconds", (0.50, 0.99))
    emb_bytes_rate, _ = rate("mxt_embedding_bytes_total")

    # fleet section (serving/fleet.py + serving/router.py): only
    # rendered when a fleet router has published replica-state gauges
    flt_states = {}
    for (n, lab), v in samples.items():
        if n == "mxt_fleet_replicas":
            d = dict(lab)
            if "state" in d:
                flt_states[d["state"]] = v
    flt_disp = metric_sum(samples, "mxt_fleet_dispatch_total")
    flt_hedge = metric_sum(samples, "mxt_fleet_hedges_total")
    flt_fail = metric_sum(samples, "mxt_fleet_failovers_total")
    flt_stale = metric_sum(samples, "mxt_fleet_stale_replies_total")
    flt_p50, flt_p99 = histogram_quantiles(
        samples, "mxt_fleet_request_latency_seconds", (0.50, 0.99))

    # fleet-SCOPE section (telemetry_fleet.py collector page, reached
    # via --fleet): only rendered when member-labeled samples are
    # present — i.e. the source is a merged fleet page, not a single
    # process's endpoint. Per-member breakdowns: serving occupancy,
    # embedding hit ratio, goodput min/mean, scrape age + staleness.
    fleet_members = sorted({dict(lab).get("member")
                            for (n, lab), v in samples.items()
                            if "member" in dict(lab)} - {None})
    fleet_stale = sorted({dict(lab).get("member")
                          for (n, lab), v in samples.items()
                          if dict(lab).get("stale") == "true"} - {None})
    fleet_tok_rate = fleet_occ = fleet_emb = fleet_good = None
    fleet_ages = {}
    if fleet_members:
        fleet_tok_rate, _ = rate("mxt_serving_tokens_total")
        # per-replica occupancy (summed over members — each replica's
        # gauge is published by exactly one pool), falling back to the
        # per-member active-request gauge for non-serving members
        occ_by_rep = {}
        for (n, lab), v in samples.items():
            if n == "mxt_fleet_replica_occupancy":
                d = dict(lab)
                if "replica" in d:
                    occ_by_rep[d["replica"]] = \
                        occ_by_rep.get(d["replica"], 0.0) + v
        if occ_by_rep:
            fleet_occ = ["r%s=%d" % (r, int(v))
                         for r, v in sorted(occ_by_rep.items())]
        else:
            fleet_occ = []
            for m in fleet_members:
                occ = metric_sum(samples,
                                 "mxt_serving_active_requests",
                                 member=m)
                if occ is not None:
                    fleet_occ.append("%s=%d" % (m, int(occ)))
        fleet_emb = []
        for m in fleet_members:
            h = metric_sum(samples, "mxt_embedding_cache_hits_total",
                           member=m)
            ms_ = metric_sum(samples, "mxt_embedding_cache_misses_total",
                             member=m)
            if h is None and ms_ is None:
                continue
            tot = (h or 0) + (ms_ or 0)
            if tot:
                fleet_emb.append("%s=%.3f" % (m, (h or 0) / tot))
        goods = [metric_sum(samples, "mxt_goodput_ratio", member=m)
                 for m in fleet_members]
        goods = [g for g in goods if g is not None]
        if goods:
            fleet_good = (min(goods), sum(goods) / len(goods))
        for m in fleet_members:
            age = metric_sum(samples, "mxt_fleet_scrape_age_seconds",
                             member=m)
            if age is not None:
                fleet_ages[m] = age

    # data-plane section (mxnet_tpu/data_plane/): only rendered when a
    # streaming loader's decode fleet has published its host-labeled
    # gauges — a per-process-iterator trainer shows no data noise.
    # Per-host rec/s + data_wait share is the input-boundness
    # attribution: the host whose wait share grows is the one starving.
    data_hosts = sorted({dict(lab).get("host")
                         for (n, lab), v in samples.items()
                         if n == "mxt_data_records_per_second"} - {None})
    data_steals = metric_sum(samples, "mxt_data_steals_total")
    data_stale = metric_sum(samples, "mxt_data_stale_leases_total")
    data_bytes_rate, _ = rate("mxt_data_bytes_total")
    data_rps = {h: metric_sum(samples, "mxt_data_records_per_second",
                              host=h) for h in data_hosts}
    data_q = {h: metric_sum(samples, "mxt_data_queue_depth", host=h)
              for h in data_hosts}
    data_buf = {h: metric_sum(samples, "mxt_data_buffer_bytes", host=h)
                for h in data_hosts}
    data_wait = {h: rate("mxt_data_wait_seconds_total", host=h)[0]
                 for h in data_hosts}

    # serving section (mxnet_tpu/serving/): only rendered when the
    # process has served — a pure trainer shows no serving noise
    tok_rate, tok_total = rate("mxt_serving_tokens_total")
    srv_queue = metric_sum(samples, "mxt_serving_queue_depth")
    srv_active = metric_sum(samples, "mxt_serving_active_requests")
    srv_p50, srv_p99 = histogram_quantiles(
        samples, "mxt_serving_request_latency_seconds", (0.50, 0.99),
        phase="decode")
    pages_used = metric_sum(samples, "mxt_serving_kv_pages_in_use")
    pages_total = metric_sum(samples, "mxt_serving_kv_pages_total")
    evicted = metric_sum(samples, "mxt_serving_requests_total",
                         outcome="evicted")
    # speculative decode + quantized-page gauges (PR 12): rendered only
    # when the engine actually speculates / serves int8 pages
    spec_prop = metric_sum(samples,
                           "mxt_serving_spec_proposed_tokens_total")
    spec_acc = metric_sum(samples,
                          "mxt_serving_spec_accepted_tokens_total")
    quant_pages = metric_sum(samples,
                             "mxt_serving_kv_quant_pages_in_use")
    # shared-prefix reuse gauges (PR 16): rendered only when the engine
    # runs with prefix_cache=True (the counters exist only then)
    pfx_hits = metric_sum(samples, "mxt_serving_prefix_hits_total")
    pfx_miss = metric_sum(samples, "mxt_serving_prefix_misses_total")
    pfx_shared = metric_sum(samples, "mxt_serving_shared_pages")
    pfx_cow = metric_sum(samples, "mxt_serving_cow_copies_total")

    # autoscaler / QoS section (serving/autoscaler.py + qos.py): only
    # rendered when an autoscaler has stood up its target gauge or a
    # QoS policy has admitted per-tenant traffic — an unscaled,
    # single-tenant fleet shows no control-loop noise
    asc_target = metric_sum(samples, "mxt_autoscale_target_replicas")
    asc_events = {}
    asc_last = {}
    for (n, lab), v in samples.items():
        d = dict(lab)
        if "direction" not in d:
            continue
        if n == "mxt_autoscale_events_total":
            asc_events[d["direction"]] = \
                asc_events.get(d["direction"], 0.0) + v
        elif n == "mxt_autoscale_last_decision":
            # monotonic decision seq per direction: the max IS the
            # most recent decision
            asc_last[d["direction"]] = \
                max(asc_last.get(d["direction"], 0.0), v)
    asc_latest = max(asc_last, key=asc_last.get) if asc_last else None
    qos_tenants = sorted(
        {dict(lab).get("tenant") for (n, lab), v in samples.items()
         if n in ("mxt_tenant_admitted_total", "mxt_tenant_rejected_total",
                  "mxt_tenant_preempted_total",
                  "mxt_tenant_inflight_requests")
         and "tenant" in dict(lab)} - {None})

    # training-health section (mxnet_tpu/health.py): only rendered when
    # a HealthMonitor / rules engine has published — a process without
    # the health plane armed shows no training-health noise
    hl_ema = metric_sum(samples, "mxt_health_loss_ema")
    hl_skew = metric_sum(samples, "mxt_health_step_skew_ratio")
    hl_step_ms = metric_sum(samples, "mxt_health_host_step_ms")
    hl_anoms = []  # (count, kind, layer), top-3 by count
    hl_rules_ok, hl_rules_bad = [], []
    for (n, lab), v in sorted(samples.items()):
        d = dict(lab)
        if n == "mxt_health_anomalies_total" and "kind" in d:
            hl_anoms.append((v, d["kind"], d.get("layer", "?")))
        elif n == "mxt_health_rule_ok" and "rule" in d:
            (hl_rules_ok if v else hl_rules_bad).append(d["rule"])
    hl_anoms.sort(key=lambda r: (-r[0], r[1], r[2]))
    hl_present = (hl_ema is not None or hl_skew is not None
                  or hl_step_ms is not None or hl_anoms
                  or hl_rules_ok or hl_rules_bad)

    lines = [
        "mxt_top  %s" % time.strftime("%H:%M:%S"),
        "-" * 46,
        "  steps/s          %s   (total %s)"
        % (_fmt(steps_rate), _fmt(steps_total, "%.0f")),
        "  host_syncs/step  %s" % _fmt(per_step(syncs_rate), "%.3f"),
        "  launches/step    %s" % _fmt(per_step(launch_rate), "%.2f"),
        "  dispatch depth   %s" % _fmt(depth, "%.0f"),
        "  kv rpc p50/p99   %s / %s" % (_fmt_s(p50), _fmt_s(p99)),
        "  workers live     %s   lost %s"
        % (_fmt(live, "%.0f"), _fmt(lost, "%.0f")),
        "  skipped steps    %s" % _fmt(skipped, "%.0f"),
        "  xla compiles     %s   (%s)   cache %s/%s hit/miss"
        % (_fmt(compiles, "%.0f"), _fmt_s(compile_s),
           _fmt(cc_hits, "%.0f"), _fmt(cc_miss, "%.0f")),
        "  tune cache       %s/%s hit/miss"
        % (_fmt(tune_hits, "%.0f"), _fmt(tune_miss, "%.0f")),
    ]
    if mesh_dev is not None:
        lines += [
            "-" * 46,
            "  mesh             %s dev   %s   zero=%s"
            % (_fmt(mesh_dev, "%.0f"),
               " ".join(mesh_axes) if mesh_axes else "--",
               _fmt(zero_stage, "%.0f")),
            "  per-dev bytes    params %s   opt %s"
            % (_fmt_b(mesh_pbytes), _fmt_b(mesh_obytes)),
            "  reshards         %s" % _fmt(reshards, "%.0f"),
        ]
        if moe_load:
            lines.append(
                "  moe load         %s   drops=%s"
                % (" ".join(moe_load), _fmt(moe_drops, "%.0f")))
    if hbm_pools or goodput is not None:
        lines.append("-" * 46)
        for pool in sorted(hbm_pools):
            lines.append(
                "  hbm %-12s %s   (peak %s)"
                % (pool, _fmt_b(hbm_pools[pool]),
                   _fmt_b(hbm_peaks.get(pool))))
        if goodput is not None:
            top = ", ".join("%s %s" % (c, _fmt_s(v))
                            for v, c in lost_causes[:3]) or "none"
            lines.append("  goodput          %s   lost: %s"
                         % (_fmt(goodput, "%.3f"), top))
        if stalls:
            lines.append("  watchdog stalls  %s" % _fmt(stalls, "%.0f"))
    if emb_resident is not None or emb_ratio is not None:
        lines += [
            "-" * 46,
            "  emb rows res.    %s   hit ratio %s"
            % (_fmt(emb_resident, "%.0f"),
               _fmt(emb_ratio, "%.3f")),
            "  emb pull p50/p99 %s / %s   evicted %s"
            % (_fmt_s(emb_p50), _fmt_s(emb_p99),
               _fmt(emb_evict, "%.0f")),
            "  emb bytes/s      %s" % _fmt_b(emb_bytes_rate),
        ]
    if fleet_members:
        ages = ["%s %s" % (m, _fmt_s(fleet_ages[m]))
                for m in sorted(fleet_ages)]
        lines += [
            "-" * 46,
            "  fleet members    %d   stale: %s"
            % (len(fleet_members),
               ", ".join(fleet_stale) if fleet_stale else "none"),
            "  fleet tok/s      %s" % _fmt(fleet_tok_rate),
            "  occupancy        %s"
            % (" ".join(fleet_occ) if fleet_occ else "--"),
        ]
        if fleet_emb:
            lines.append("  emb hit ratio    %s" % " ".join(fleet_emb))
        if fleet_good is not None:
            lines.append("  goodput min/mean %.3f / %.3f" % fleet_good)
        if ages:
            lines.append("  scrape age       %s" % "  ".join(ages))
    if flt_states:
        lines += [
            "-" * 46,
            "  fleet replicas   %s routable / %s total   (drain %s "
            "dead %s)"
            % (_fmt(flt_states.get("routable", 0), "%.0f"),
               _fmt(sum(flt_states.values()), "%.0f"),
               _fmt(flt_states.get("draining", 0)
                    + flt_states.get("drained", 0), "%.0f"),
               _fmt(flt_states.get("dead", 0), "%.0f")),
            "  disp/hedge/fail  %s / %s / %s   stale refused %s"
            % (_fmt(flt_disp, "%.0f"), _fmt(flt_hedge, "%.0f"),
               _fmt(flt_fail, "%.0f"), _fmt(flt_stale, "%.0f")),
            "  routed p50/p99   %s / %s"
            % (_fmt_s(flt_p50), _fmt_s(flt_p99)),
        ]
    if data_hosts:
        lines += [
            "-" * 46,
            "  data rec/s       %s   bytes/s %s"
            % ("  ".join("h%s %s" % (h, _fmt(data_rps[h], "%.0f"))
                         for h in data_hosts),
               _fmt_b(data_bytes_rate)),
            "  data queue       %s   steals %s   stale refused %s"
            % ("  ".join("h%s %s (%s on the host)"
                         % (h, _fmt(data_q[h], "%.0f"), _fmt_b(data_buf[h]))
                         for h in data_hosts),
               _fmt(data_steals, "%.0f"), _fmt(data_stale, "%.0f")),
            "  data_wait share  %s"
            % "  ".join("h%s %s" % (h, _fmt(data_wait[h], "%.3f"))
                        for h in data_hosts),
        ]
    if tok_total is not None:
        lines += [
            "-" * 46,
            "  serving tok/s    %s   (total %s)"
            % (_fmt(tok_rate), _fmt(tok_total, "%.0f")),
            "  queue depth      %s   active %s   evicted %s"
            % (_fmt(srv_queue, "%.0f"), _fmt(srv_active, "%.0f"),
               _fmt(evicted, "%.0f")),
            "  request p50/p99  %s / %s (decode)"
            % (_fmt_s(srv_p50), _fmt_s(srv_p99)),
            "  kv pages         %s / %s in use"
            % (_fmt(pages_used, "%.0f"), _fmt(pages_total, "%.0f")),
        ]
        if spec_prop:
            lines.append(
                "  spec accept      %s   (%s / %s draft tokens)"
                % (_fmt((spec_acc or 0) / spec_prop, "%.3f"),
                   _fmt(spec_acc, "%.0f"), _fmt(spec_prop, "%.0f")))
        if quant_pages is not None:
            lines.append("  int8 kv pages    %s in use"
                         % _fmt(quant_pages, "%.0f"))
        if pfx_hits is not None or pfx_miss is not None:
            total = (pfx_hits or 0) + (pfx_miss or 0)
            ratio = (pfx_hits or 0) / total if total else 0.0
            lines.append(
                "  prefix           hit %s (%s/%s)   shared pages %s"
                "   cow %s"
                % (_fmt(ratio, "%.3f"), _fmt(pfx_hits, "%.0f"),
                   _fmt(total, "%.0f"), _fmt(pfx_shared, "%.0f"),
                   _fmt(pfx_cow, "%.0f")))
    if asc_target is not None or qos_tenants:
        lines.append("-" * 46)
        if asc_target is not None:
            lines.append(
                "  autoscale        target %s   up %s  down %s"
                "  refused %s"
                % (_fmt(asc_target, "%.0f"),
                   _fmt(asc_events.get("up", 0), "%.0f"),
                   _fmt(asc_events.get("down", 0), "%.0f"),
                   _fmt(asc_events.get("refused", 0), "%.0f")))
            if asc_latest is not None:
                lines.append("  last decision    %s (#%s)"
                             % (asc_latest,
                                _fmt(asc_last[asc_latest], "%.0f")))
        for t in qos_tenants:
            adm = metric_sum(samples, "mxt_tenant_admitted_total",
                             tenant=t)
            rej = metric_sum(samples, "mxt_tenant_rejected_total",
                             tenant=t)
            pre = metric_sum(samples, "mxt_tenant_preempted_total",
                             tenant=t)
            inflt = metric_sum(samples, "mxt_tenant_inflight_requests",
                               tenant=t)
            lines.append(
                "  tenant %-9s adm %s  rej %s  pre %s  inflight %s"
                % (t, _fmt(adm, "%.0f"), _fmt(rej, "%.0f"),
                   _fmt(pre, "%.0f"), _fmt(inflt, "%.0f")))
    if hl_present:
        lines += [
            "-" * 46,
            "  health loss ema  %s   step %s ms"
            % (_fmt(hl_ema, "%.5g"), _fmt(hl_step_ms, "%.1f")),
        ]
        if hl_skew is not None:
            lines.append("  step skew        %s" % _fmt(hl_skew, "%.2f"))
        if hl_anoms:
            lines.append(
                "  anomalies        %s"
                % "  ".join("%s:%s=%d" % (k, l, int(c))
                            for c, k, l in hl_anoms[:3]))
        if hl_rules_ok or hl_rules_bad:
            lines.append(
                "  rules            %d ok / %d breached%s"
                % (len(hl_rules_ok), len(hl_rules_bad),
                   ("   (" + ", ".join(sorted(hl_rules_bad)) + ")")
                   if hl_rules_bad else ""))
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", default=None,
                   help="Prometheus endpoint (default: "
                        "http://127.0.0.1:$MXT_TELEMETRY_PORT)")
    p.add_argument("--jsonl", default=None,
                   help="tail a telemetry JSONL file instead")
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--once", action="store_true",
                   help="render one frame and exit (no screen clear)")
    p.add_argument("--fleet", action="store_true",
                   help="scrape the fleet collector's merged page "
                        "(--url + /fleet): member-labeled samples from "
                        "every fleet member, with a fleet-scope "
                        "section (tokens/s, per-replica occupancy, "
                        "per-server embedding hit ratio, goodput "
                        "min/mean, scrape ages)")
    args = p.parse_args(argv)

    if args.jsonl:
        src = JsonlSource(args.jsonl)
    else:
        url = args.url
        if url is None:
            port = os.environ.get("MXT_TELEMETRY_PORT")
            if not port:
                p.error("give --url or --jsonl (or set "
                        "MXT_TELEMETRY_PORT)")
            url = "http://127.0.0.1:%s" % port
        if args.fleet:
            url = url.rstrip("/") + "/fleet"
        src = EndpointSource(url)

    prev, t_prev = None, None
    while True:
        try:
            samples = src.sample()
        except OSError as e:
            print("mxt_top: source unreachable: %s" % e, file=sys.stderr)
            if args.once:
                return 1
            time.sleep(args.interval)
            continue
        now = time.monotonic()
        frame = render(samples, prev, 0 if t_prev is None
                       else now - t_prev)
        if args.once:
            print(frame)
            return 0
        sys.stdout.write("\x1b[H\x1b[J" + frame + "\n")
        sys.stdout.flush()
        prev, t_prev = samples, now
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
