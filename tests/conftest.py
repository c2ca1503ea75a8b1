"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the standard JAX trick for exercising pjit/shard_map multi-device
semantics without hardware (SURVEY.md §4): the env vars must be set before
jax (or anything importing jax) is imported, which is why they live at the
top of conftest rather than in a fixture.
"""

import os

# Force CPU even when the launch env sets JAX_PLATFORMS to a real TPU
# backend — tests exercise multi-device semantics on virtual devices, and
# JAX honours the variable as long as it is set before the first import.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture
def observers(monkeypatch):
    """A metrics registry and a process tracer of this test's own, with the
    compile and collector listeners (``observe/compiles.py``) installed on
    them and removed after; what the process had is put back."""
    from progen_tpu.observe import compiles, metrics, trace

    registry = metrics.MetricsRegistry()
    tracer = trace.Tracer()
    monkeypatch.setattr(metrics, "_REGISTRY", registry)
    monkeypatch.setattr(trace, "_TRACER", tracer)
    was = compiles.installed()
    compiles.uninstall()
    compiles.install()
    yield registry, tracer
    compiles.uninstall()
    if was:
        compiles.install()
