#!/usr/bin/env python
"""Perf-regression gate over the recorded bench trajectory.

``bench.py`` appends one JSONL row per run into ``bench_results.jsonl``
— until now a log, not a baseline. This tool turns the history into an
enforced gate: for each bench key (``config``, per platform) the
CANDIDATE row (the newest in the file, or every row of a ``--candidate``
file) is compared against the median of the PRIOR rows for the same
key, and a drop past the tolerance band exits non-zero — wire it after
a bench run and the perf trajectory becomes CI-enforced.

Metric selection per row, in priority order:

- ``step_time_ms``      — lower is better (a 1.5x slowdown regresses)
- ``images_or_tokens_per_sec_per_chip`` — higher is better

Verdicts are typed, one per candidate row:

- ``OK``                   — within ``--tolerance`` of the history median
- ``REGRESSION``           — worse than median by more than the band
- ``IMPROVED``             — better than median by more than the band
  (informational; never fails the gate)
- ``INSUFFICIENT_HISTORY`` — fewer than ``--min-history`` prior rows
  for this key (never fails: a brand-new bench has no trajectory yet)
- ``NO_METRIC``            — the row carries neither gated metric

Exit status: 1 iff any candidate row is a REGRESSION, else 0.

Comparisons never cross platforms or workload shapes: a ``cpu`` smoke
row is not judged against the ``tpu`` trajectory, and a batch-256 run
is not judged against batch-4 history (the key is config + platform +
chips + batch/seq/dtype). The band also self-calibrates: it widens to
the history's own relative median-absolute-deviation (times
``--mad-mult``), so a key whose trajectory is historically noisy
doesn't false-positive while a tight trajectory still gates at
``--tolerance``.

Usage:
  python tools/bench_regression.py                      # newest row per key
  python tools/bench_regression.py --candidate new.jsonl  # gate a fresh run
  python tools/bench_regression.py --history bench_results.jsonl --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# (metric, direction): +1 = higher is better, -1 = lower is better
_METRICS = (
    ("step_time_ms", -1),
    ("images_or_tokens_per_sec_per_chip", +1),
)


def _load(path):
    rows = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                sys.stderr.write("%s:%d: unparseable row skipped\n"
                                 % (path, lineno))
                continue
            if isinstance(row, dict) and row.get("config"):
                rows.append(row)
    return rows


def _key(row):
    return (str(row.get("config")), str(row.get("platform") or ""),
            str(row.get("chips") or ""), str(row.get("batch_size") or ""),
            str(row.get("seq_len") or ""), str(row.get("dtype") or ""))


def _metric(row):
    for name, direction in _METRICS:
        v = row.get(name)
        if isinstance(v, (int, float)) and v > 0:
            return name, direction, float(v)
    return None, 0, None


def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1]
                                             + vals[n // 2])


def judge(history, candidates, tolerance=0.25, min_history=3,
          mad_mult=3.0):
    """One verdict dict per candidate row, against the per-key median
    of ``history`` (candidate rows themselves are never in the band).
    The band is ``max(tolerance, mad_mult * relative MAD)`` of the
    prior rows, capped at 0.9 — a tight trajectory gates tightly, a
    historically noisy one gates loosely instead of crying wolf."""
    by_key = {}
    for row in history:
        by_key.setdefault(_key(row), []).append(row)
    verdicts = []
    for row in candidates:
        key = _key(row)
        name, direction, value = _metric(row)
        verdict = {"config": key[0], "platform": key[1], "metric": name,
                   "value": value, "median": None, "history": 0,
                   "ratio": None, "band": None, "verdict": "NO_METRIC",
                   "detail": ""}
        if name is None:
            verdict["detail"] = "row carries no gated metric"
            verdicts.append(verdict)
            continue
        prior = []
        for h in by_key.get(key, ()):
            if h is row:
                continue
            hv = h.get(name)
            if isinstance(hv, (int, float)) and hv > 0:
                prior.append(float(hv))
        verdict["history"] = len(prior)
        if len(prior) < min_history:
            verdict["verdict"] = "INSUFFICIENT_HISTORY"
            verdict["detail"] = ("%d prior row(s) for this key, need %d"
                                 % (len(prior), min_history))
            verdicts.append(verdict)
            continue
        med = _median(prior)
        rel_mad = _median([abs(v - med) / med for v in prior])
        band = min(0.9, max(tolerance, mad_mult * rel_mad))
        verdict["median"] = med
        verdict["band"] = band
        # normalize so ratio > 1 is always BETTER than the median
        ratio = (value / med) if direction > 0 else (med / value)
        verdict["ratio"] = ratio
        if ratio < 1.0 - band:
            verdict["verdict"] = "REGRESSION"
        elif ratio > 1.0 + band:
            verdict["verdict"] = "IMPROVED"
        else:
            verdict["verdict"] = "OK"
        verdict["detail"] = ("%s=%.6g vs median %.6g over %d rows "
                             "(%.2fx, band %.0f%%)"
                             % (name, value, med, len(prior), ratio,
                                100 * band))
        verdicts.append(verdict)
    return verdicts


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--history", default=None,
                   help="bench trajectory JSONL (default: "
                        "bench_results.jsonl next to the repo root)")
    p.add_argument("--candidate", default=None,
                   help="JSONL of fresh rows to gate; omitted, the "
                        "newest history row per bench key is the "
                        "candidate and the rest is its baseline")
    p.add_argument("--tolerance", type=float, default=0.25,
                   help="fractional band around the history median "
                        "(default 0.25: a 1.34x step-time slowdown or "
                        "a 25%% throughput drop regresses)")
    p.add_argument("--min-history", type=int, default=3,
                   help="prior rows required before the gate engages "
                        "(default 3)")
    p.add_argument("--mad-mult", type=float, default=3.0,
                   help="widen the band to this multiple of the "
                        "history's relative median-absolute-deviation "
                        "when that exceeds --tolerance (default 3.0)")
    p.add_argument("--json", action="store_true",
                   help="emit verdicts as JSON instead of a table")
    args = p.parse_args(argv)

    history_path = args.history
    if history_path is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        history_path = os.path.join(root, "bench_results.jsonl")
    if not os.path.exists(history_path):
        sys.stderr.write("bench_regression: no history at %s\n"
                         % history_path)
        return 0
    history = _load(history_path)

    if args.candidate:
        candidates = _load(args.candidate)
        baseline = history
    else:
        # newest row per key gates against everything before it
        newest = {}
        for row in history:
            newest[_key(row)] = row  # file order: last wins
        candidates = [newest[k] for k in sorted(newest)]
        baseline = history
    verdicts = judge(baseline, candidates, tolerance=args.tolerance,
                     min_history=args.min_history,
                     mad_mult=args.mad_mult)

    if args.json:
        print(json.dumps(verdicts, indent=2))
    else:
        for v in verdicts:
            print("%-22s %-10s %-20s %s"
                  % (v["verdict"], v["platform"] or "-", v["config"],
                     v["detail"]))
    regressions = [v for v in verdicts if v["verdict"] == "REGRESSION"]
    if regressions:
        sys.stderr.write(
            "bench_regression: %d regression(s) against the recorded "
            "trajectory\n" % len(regressions))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
