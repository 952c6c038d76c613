import os
import sys

# tests must see exactly ONE device (the dry-run sets its own 512-device
# flag in its own process); fail fast if someone leaks XLA_FLAGS here.
assert "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""), "run tests without the dry-run's XLA_FLAGS"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root too, so tests can import the benchmark helpers
# (benchmarks.common's zipfian generators have their own unit tests)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
