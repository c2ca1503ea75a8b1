"""CPU rehearsals of the benchmark (``JAX_PLATFORMS=cpu python -m pytest
perf/tests -q``): four virtual devices for the fsdp path, the checkout's
root on ``sys.path``.  Nothing here measures anything."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def own_registry():
    """A metrics registry of this test's own for a rehearsed cell: the
    process's holds what other tests' engines observed (their chunks' rows,
    their steps), and theirs must not hold this one's.  The compile and
    collector listeners (``observe/compiles.py``) are taken off, so that the
    engine or trainer the cell builds installs them on THIS registry and
    ``xla.cache_misses`` is there from the start whichever test ran first;
    what the process had is put back."""
    from progen_tpu.observe import compiles, metrics

    process, was = metrics._REGISTRY, compiles.installed()
    compiles.uninstall()
    metrics._REGISTRY = metrics.MetricsRegistry()
    yield metrics._REGISTRY
    compiles.uninstall()
    metrics._REGISTRY = process
    if was:
        compiles.install()
