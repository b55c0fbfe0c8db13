"""CPU tests of the harness: ``python -m pytest benchmark/tests -q``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, so that a four-chip cell's stand-in runs in this process
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, REPO_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
