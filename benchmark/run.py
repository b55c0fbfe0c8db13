#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: finds the chips the cell asks for or fails; builds the cell's
configuration with weights and inputs made on the device from ``--seed``; warms
only the cell's own shapes; measures for ``--seconds``; checks what the timed
path produced against the plain reference outside the window; prints what it
likes on earlier lines and, last, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` when
traced), then ``compared``: every number compared beside its limit, which are
the last lines of standard error too. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

This file knows no model, cell or metric by name: everything is found through
``BENCHMARK.json`` and the files it names (see README.md beside this file).
``--rehearse`` runs the cell's tiny stand-in on the CPU to find wrong paths
and arguments; it prints every metric as null and exits 3, never 0.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from harness import loader  # noqa: E402


class Context:
    """What a runner is handed: the cell, its files, the devices, the clock."""

    def __init__(self, args, cell, config, traffic, devices):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.cell, self.config, self.traffic = cell, config, traffic
        self.devices = devices
        self._trace_dirs = []

    @staticmethod
    def since_start():
        return time.perf_counter() - T_START

    @staticmethod
    def say(**row):
        print(json.dumps(row, default=str), flush=True)

    def trace_dir(self):
        base = os.path.join(loader.BENCH_DIR, ".cache")
        os.makedirs(base, exist_ok=True)
        path = tempfile.mkdtemp(prefix="trace-", dir=base)
        self._trace_dirs.append(path)
        return path

    def cleanup(self):
        import shutil

        for path in self._trace_dirs:
            shutil.rmtree(path, ignore_errors=True)


def resolve(name, rehearse):
    """The cell's files: BENCHMARK.json entry -> workloads/, configs/, traffic/."""
    cell = loader.resolve_cell(name, rehearse)
    config = loader.load_json("configs", cell["config"])
    traffic = loader.load_json("traffic", cell["traffic"])
    return loader.bench_spec(), cell, config, traffic


def metrics_for(spec, cell_name, out, trace, rehearse=False):
    """The result line's metrics: units from BENCHMARK.json, values from the
    runner (end to end) or from each per-layer metric's own reader."""
    def listed(metric):
        return "workloads" not in metric or cell_name in metric["workloads"]

    metrics = {}
    if not trace:
        for m in spec["end_to_end"]:
            if listed(m) and m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
        return metrics
    units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    for name in out["facts"]["cell"]["layer_metrics"]:
        reader = loader.load_module("layer_metrics", name)
        try:
            value = reader.read(out["facts"])
        except loader.BenchError:
            if not rehearse:  # on the CPU a reader may lack what only a chip has
                raise
            value = float("nan")
        if value is not None:
            metrics[reader.NAME] = {"value": value, "unit": units.get(reader.NAME, reader.UNIT)}
    return metrics


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny stand-in on the CPU; never a result")
    args = ap.parse_args(argv)
    try:
        spec, cell, config, traffic = resolve(args.workload, args.rehearse)
        if args.seconds is None:
            args.seconds = spec["run_seconds"]
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            flag = "--xla_force_host_platform_device_count=%d" % cell["chips"]
            if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        from harness import device, program

        devices = device.find_devices(cell["chips"], args.rehearse)
        cache = program.setup(args.rehearse)
    except loader.BenchError as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 2
    ctx = Context(args, cell, config, traffic, devices)
    ctx.say(phase="start", workload=args.workload, seed=args.seed, seconds=args.seconds,
            trace=args.trace, rehearse=args.rehearse, device=device.describe(devices),
            compile_cache=cache)
    try:
        out = loader.load_module("runners", cell["runner"]).run(ctx)
        metrics = metrics_for(spec, args.workload, out, ctx.trace, args.rehearse)
    finally:
        ctx.cleanup()
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    if ctx.trace and out.get("trace"):
        dev["busy_s"], dev["window_s"] = out["trace"]["busy_s"], out["trace"]["window_s"]
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    if args.rehearse:
        # a CPU number never stands under a device metric's name
        for m in result["metrics"].values():
            m["value"] = None
    # every number compared beside its limit: the result's last key, and the
    # last lines of standard error (what a record keeps of a run not correct)
    result["compared"] = {
        row["compared"]: {"value": row["value"] if math.isfinite(row["value"])
                          else str(row["value"]), "limit": row["limit"]}
        for row in out["compared"]}
    print(json.dumps(result), flush=True)
    for name, row in result["compared"].items():
        print("compared %s %s limit %s" % (name, row["value"], row["limit"]), file=sys.stderr)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
