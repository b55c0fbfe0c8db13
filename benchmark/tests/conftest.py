"""CPU tests of the harness: ``python -m pytest benchmark/tests -q``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, so that a four-chip cell's stand-in runs in this process
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
# no fused multiply-add: XLA's CPU backend rounds a * b + c once where its
# instruction selection likes, and likes differently with a bfloat16 load in
# front, so two programs of the same arithmetic part in a last place
# (test_follower.py holds the follower to the old one's numbers bit for bit)
if "xla_cpu_max_isa" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_cpu_max_isa=AVX"
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, REPO_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
