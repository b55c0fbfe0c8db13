"""The contract of the two programs that are run on the chip, as far as a
CPU-only sandbox can hold them to it: no accelerator is an error, never a
fallback; an unknown device kind has no assumed peak; a configuration that
would start children under a parent that holds the chip says so in words."""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, ROOT)
    try:
        import bench as mod
    finally:
        sys.path.pop(0)
    return mod


def test_on_tpu_is_false_on_the_cpu_backend():
    assert mx.context.on_tpu() is False
    assert not mx.runtime.Features().is_enabled("TPU")


def test_bench_without_a_chip_is_an_error(bench, monkeypatch):
    monkeypatch.delenv("BENCH_PLATFORM", raising=False)
    with pytest.raises(SystemExit) as e:
        bench._init_backend()
    assert "no accelerator" in str(e.value)
    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    assert bench._init_backend() == "cpu"


def test_bench_peak_is_looked_up_by_device_kind(bench):
    assert bench._PEAK_BF16_FLOPS["TPU v5 lite"] == 197e12
    assert bench._mfu(100.0, 1e9, "cpu") is None  # a CPU run has no MFU
    with pytest.raises(RuntimeError, match="no published peak"):
        bench._mfu(100.0, 1e9, "tpu")  # this sandbox's kind is "cpu"


@pytest.mark.parametrize("fn", ["bench_cold_warm", "bench_zero_stages",
                                "bench_parallel_4d"])
def test_bench_child_process_configs_do_not_run_under_a_chip(bench, fn):
    with pytest.raises(bench._NotRun, match="child processes"):
        getattr(bench, fn)("tpu", "float32")


def test_bench_cold_warm_leaves_the_environments_cache_alone(bench,
                                                             monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/cache")
    with pytest.raises(bench._NotRun, match="JAX_COMPILATION_CACHE_DIR"):
        bench.bench_cold_warm("cpu", "float32")


def test_chip_smoke_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_chip_smoke_last_line_is_the_contract(monkeypatch, capsys):
    """The shape of the last line, from a rehearsed sync phase: one JSON
    object with ok and the device as JAX reports it — and never ok:true
    off the chip."""
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--rehearse",
                                      "--phases", "sync"])
    monkeypatch.setattr(mx.tuning, "setup_compile_cache", lambda d: None)
    rc = chip_smoke.main()
    mx.config.set_default("MXT_TUNE_MODE", "auto")  # main pinned the cost model
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0 and last["ok"] is False and last["failed"] == []
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
