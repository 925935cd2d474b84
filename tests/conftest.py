"""Test configuration.

Tests run on XLA:CPU with 8 virtual devices so sharding/mesh code paths
are exercised without TPU hardware (the driver's dryrun does the same).
Must run before the first `import jax` anywhere in the test process.
"""

import os

# Force CPU before the first jax import: unit tests must be fast,
# f32-exact, and see 8 virtual devices for sharding coverage, on a
# machine with or without a TPU.  The on-chip tier is tests_tpu/.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _isolated_data_dir(tmp_path_factory):
    """Point root.common.data_dir at a fresh temp dir for the whole
    session: real datasets materialized on this machine (MNIST IDX
    files under ~/.veles_tpu/data)
    must not leak into the suite — MnistLoader would silently switch
    from the tiny synthetic sets to 60k real samples and the suite's
    runtime would triple."""
    from veles_tpu.config import root
    root.common.data_dir = str(tmp_path_factory.mktemp("data"))
    yield


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test gets a clean config tree, PRNG registry, and
    telemetry registry (zeroed in place; no metrics dir armed)."""
    from veles_tpu import config, prng, telemetry
    saved = dict(config.root.__dict__)
    saved_mdir = os.environ.pop(telemetry.ENV_DIR, None)
    telemetry.reset()
    telemetry.set_enabled(True)
    prng._streams.clear()
    prng.seed_all(1234)
    yield
    config.root.__dict__.clear()
    config.root.__dict__.update(saved)
    prng._streams.clear()
    if saved_mdir is not None:
        os.environ[telemetry.ENV_DIR] = saved_mdir
    else:
        os.environ.pop(telemetry.ENV_DIR, None)
    telemetry.reset()
    telemetry.set_enabled(True)
