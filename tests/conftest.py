"""Test environment: force CPU with 8 virtual devices so distributed-mesh
tests run without TPU hardware (SURVEY.md environment notes; the analog of
the reference testing distributed paths with in-process LocalCluster,
test_dask.py:29)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """XLA's CPU compiler has been observed to segfault after compiling many
    hundreds of programs in one long process (jaxlib 0.9, during
    backend_compile_and_load); dropping the jit caches between test modules
    keeps the program count bounded. CI should still prefer per-file pytest
    processes (tests/run_suite.sh)."""
    yield
    jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.RandomState(42)


REFERENCE_EXAMPLES = "/root/reference/examples"
REFERENCE_DATA_REASON = ("reference example data unavailable "
                         f"({REFERENCE_EXAMPLES} is not in this image)")


def reference_data_available() -> bool:
    return os.path.isdir(REFERENCE_EXAMPLES)


def require_reference_data() -> None:
    """Skip (not error) when the reference's example files are absent —
    a missing /root/reference is an environment gap, and the ERROR noise
    it used to produce masked real regressions in the tier-1 dot line."""
    if not reference_data_available():
        pytest.skip(REFERENCE_DATA_REASON)


def _example_path(name):
    return os.path.join(REFERENCE_EXAMPLES, name)


@pytest.fixture(scope="session")
def binary_example():
    """The reference's binary_classification example data
    (examples/binary_classification/binary.{train,test}; label in col 0).
    Skips cleanly when the reference checkout is absent."""
    require_reference_data()
    train = np.loadtxt(_example_path("binary_classification/binary.train"))
    test = np.loadtxt(_example_path("binary_classification/binary.test"))
    return (train[:, 1:], train[:, 0], test[:, 1:], test[:, 0])
