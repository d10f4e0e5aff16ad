"""Test harness: run the whole suite on a virtual 8-device CPU mesh.

The reference framework can only test multi-process behavior under
``mpirun -np N`` (BlueFog ``Makefile:28-51``); here XLA's host-platform device
multiplexing gives a real fake-cluster on one process, so every topology /
collective / optimizer test runs against 8 "ranks" with zero launchers.
"""

import os

# Must be set before jax initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# The suite runs on the virtual CPU mesh wherever it is started, a TPU host
# included: pin the platform in jax's config as well as in the environment.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(autouse=True)
def _reset_bluefog_state():
    """Each test gets a pristine module-level bluefog context, including the
    window store (a failing test must not leak windows into the next one)."""
    yield
    try:
        from bluefog_tpu import basics
        from bluefog_tpu.ops import window
        window._free_all_windows()
        basics._reset_for_tests()
    except (ImportError, AttributeError):
        pass


def pytest_generate_tests(metafunc):
    """A case of ``tests/twins.py`` runs for those of the twins its file
    names whose row has what the case needs."""
    names = getattr(metafunc.module, "TWINS", None)
    if names is not None and "twin" in metafunc.fixturenames:
        import twins
        need = getattr(metafunc.function, "needs", None)
        metafunc.parametrize("twin", [
            name for name in names
            if need is None or need in twins.TWINS[name]])


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process integration test")

