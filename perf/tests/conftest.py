"""CPU rehearsals of the benchmark (``JAX_PLATFORMS=cpu python -m pytest
perf/tests -q``): four virtual devices for the fsdp path, the checkout's
root on ``sys.path``.  Nothing here measures anything."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
