"""Test harness: force an 8-device virtual CPU platform before JAX init.

Analog of the reference's shared Spark ``local[4]`` test context
(reference: photon-test/.../SparkTestUtils.scala:55-69,190) — all distributed
code paths (pjit sharding, psum collectives, mesh layouts) run for real
in-process over 8 host devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Tests run on the virtual multi-device CPU platform, whatever the
# environment's default.
jax.config.update("jax_platforms", "cpu")

# Tests validate kernel math against finite differences / scipy in float64;
# production code passes explicit float32 dtypes, which x64 mode preserves.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end sweeps excluded from the tier-1 run "
        "(-m 'not slow'), e.g. the sanitized decode-corpus replay")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _reset_default_mesh():
    """Driver runs install a process-default mesh (setup_default_mesh);
    keep that from leaking across tests."""
    yield
    from photon_ml_tpu.parallel.mesh import set_default_mesh

    set_default_mesh(None)


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    """Steer the kernel gate (ops/pallas_kernels.pallas_supported) the way
    one attached chip would: its shape, width and dtype rules still run.
    The program has no option for this; a test steers it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
