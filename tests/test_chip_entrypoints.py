"""The contract of ``chip_smoke.py``, as far as a CPU-only sandbox can hold
it: no accelerator is an error, never a fallback, and the last line never
says ``ok: true`` off the chip. (``benchmark/tests/`` holds
``benchmark/run.py`` to the same.)"""
import json
import os
import subprocess
import sys


import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_on_tpu_is_false_on_the_cpu_backend():
    assert mx.context.on_tpu() is False
    assert not mx.runtime.Features().is_enabled("TPU")


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
    mx.config.set_default("MXT_TUNE_MODE", "heuristic")  # what main set, the default
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0 and last["ok"] is False and last["failed"] == []
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
