"""Find a configuration, cell, model, reference, runner, FLOP function or
per-layer metric by the name that a JSON file gives it.

A later PR adds files and appends entries to ``BENCHMARK.json``; it edits
no file that is there. So nothing here knows a name: ``<kind>/<name>.py`` or
``<kind>/<name>.json`` under ``benchmark/`` is all there is to find.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchError(Exception):
    """A fault of the benchmark's own files or of the machine it runs on."""


def _path(kind, name, ext):
    if not _NAME.match(name or ""):
        raise BenchError("bad %s name %r" % (kind, name))
    path = os.path.join(BENCH_DIR, kind, name + ext)
    if not os.path.isfile(path):
        raise BenchError("no %s named %r (looked for %s)"
                         % (kind, name, os.path.relpath(path, REPO_DIR)))
    return path


def load_json(kind, name):
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind, name):
    """Import ``benchmark/<kind>/<name>.py`` under a name of its own."""
    modname = "benchmark_%s_%s" % (kind, re.sub(r"[^A-Za-z0-9_]", "_", name))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, _path(kind, name, ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def bench_spec():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve_cell(name, rehearse=False):
    """One cell from its two sources, each key in one place: the entry in
    ``BENCHMARK.json`` (config, traffic, chips, why) and
    ``workloads/<name>.json`` (runner, limits, layer_metrics, rehearse).
    With ``rehearse`` the file's ``rehearse`` group stands in for the keys it
    names, and a cell that ``BENCHMARK.json`` does not list yet can be tried."""
    entry = next((w for w in bench_spec()["workloads"] if w["name"] == name), None)
    cell = load_json("workloads", name)
    if entry is None and not rehearse:
        raise BenchError("BENCHMARK.json lists no cell named %r" % name)
    twice = sorted(set(cell) & set(entry or ()))
    if twice:
        raise BenchError("workloads/%s.json restates %s, which BENCHMARK.json holds"
                         % (name, twice))
    cell = dict(entry or {"name": name}, **cell)
    if rehearse:
        cell.update(cell.get("rehearse", {}))
    return cell
