"""Test config: force an 8-device CPU mesh so multi-device sharding paths
are exercised without TPU hardware (the driver separately dry-runs the
multi-chip path; see __graft_entry__.py). The suite is calibrated for the
CPU backend and held to it below; the hardware lane (MXT_TEST_TPU=1,
``-m tpu``) and chip_smoke.py are what run on the chip.
"""
import os

import pytest

_TPU_LANE = os.environ.get("MXT_TEST_TPU", "") == "1"

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

if not _TPU_LANE:
    jax.config.update("jax_platforms", "cpu")
    # numeric tests compare against numpy float32/64; don't let XLA downcast
    jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: hardware smoke test — run with `MXT_TEST_TPU=1 pytest -m tpu` "
        "on a machine with a real TPU (round-2 lesson: interpret-mode-only "
        "Pallas coverage let a hardware-invalid BlockSpec ship)")
    config.addinivalue_line(
        "markers",
        "nightly: slow/large-resource tier (ref: tests/nightly/) — run "
        "with MXT_TEST_NIGHTLY=1; skipped in the default suite")
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (kill-and-resume soaks) — excluded "
        "from the tier-1 gate, which runs -m 'not slow'")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (seeded MXT_FAULT, "
        "resilience.py) — fast enough to run in tier-1")


def pytest_collection_modifyitems(config, items):
    if _TPU_LANE:
        # the CPU-calibrated numeric suite must not run on the TPU backend
        # (tolerances assume highest matmul precision)
        skip = pytest.mark.skip(
            reason="CPU-lane test skipped under MXT_TEST_TPU=1")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip)
        return
    skip = pytest.mark.skip(
        reason="TPU lane disabled (set MXT_TEST_TPU=1 and run -m tpu)")
    skip_nightly = pytest.mark.skip(
        reason="nightly tier disabled (set MXT_TEST_NIGHTLY=1)")
    nightly_on = os.environ.get("MXT_TEST_NIGHTLY", "") == "1"
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)
        # NB: get_closest_marker, not `in item.keywords` — keywords
        # include ancestor node names, so the tests/nightly/ DIRECTORY
        # name would gate unmarked tests living there
        if item.get_closest_marker("nightly") is not None \
                and not nightly_on:
            item.add_marker(skip_nightly)


@pytest.fixture
def grouped_matmul_kernels(monkeypatch):
    """``engage()``: from then on ``ops/grouped_matmul.py`` dispatches as on a
    TPU, its two kernels in interpret mode (a test computes what
    ``ragged_dot`` gives first, then engages)."""
    from mxnet_tpu.ops import grouped_matmul as GM

    def engage():
        gmm, tgmm = GM._gmm_pallas, GM._tgmm_pallas
        monkeypatch.setattr(GM, "on_tpu", lambda: True)
        monkeypatch.setattr(GM, "_gmm_pallas", lambda *a, interpret=False, **kw:
                            gmm(*a, interpret=True, **kw))
        monkeypatch.setattr(GM, "_tgmm_pallas", lambda *a, interpret=False, **kw:
                            tgmm(*a, interpret=True, **kw))

    return engage


@pytest.fixture
def row_gather_kernel(monkeypatch):
    """``engage()``: from then on ``ops/row_gather.py`` answers as on a TPU,
    its kernels in interpret mode (a test computes what XLA's gathers give
    first, then engages)."""
    from mxnet_tpu.ops import row_gather as RG

    def engage():
        kernels = RG._sum_pallas
        monkeypatch.setattr(RG, "on_tpu", lambda: True)
        monkeypatch.setattr(RG, "_sum_pallas", lambda *a, interpret=False, **kw:
                            kernels(*a, interpret=True, **kw))

    return engage
