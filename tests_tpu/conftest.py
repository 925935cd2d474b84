"""The on-chip test tier.

Unlike tests/ (which pins XLA:CPU for speed and f32 exactness), this
directory runs on the TPU: ``python -m pytest tests_tpu/ -q`` on a
machine that has one (through the chip tool from a sandbox that does
not).  Every test carries the ``tpu`` marker.

No TPU is a FAILURE here, not a skip: the ``tpu_device`` fixture asks
for ``make_device("tpu")``, which raises when JAX has none — so a chip
held by another process cannot turn the tier green by skipping it.
The one exception is an operator who SAID this is not a chip run
(``JAX_PLATFORMS`` set without ``tpu``, e.g. a whole-repo ``pytest``
under tests/conftest.py's pin): those runs skip the tier, decided from
the environment alone.  This file must not touch a jax backend — the
pytest process becomes the chip's owner the moment it does, and
test_0_ga_parent.py has to start a chip-owning child before that.
"""

import os

import pytest


def _explicit_non_tpu_run() -> bool:
    platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    return bool(platforms) and "tpu" not in platforms.split(",")


def pytest_collection_modifyitems(config, items):
    skip = _explicit_non_tpu_run()
    for item in items:
        item.add_marker(pytest.mark.tpu)
        if skip:
            item.add_marker(pytest.mark.skip(
                reason=f"JAX_PLATFORMS="
                       f"{os.environ['JAX_PLATFORMS']} excludes the "
                       f"TPU (tests_tpu/ runs on the chip only)"))


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: runs on the TPU")


@pytest.fixture(autouse=True)
def _reset_global_state():
    from veles_tpu import config, prng
    saved = dict(config.root.__dict__)
    prng._streams.clear()
    prng.seed_all(1234)
    yield
    config.root.__dict__.clear()
    config.root.__dict__.update(saved)
    prng._streams.clear()


@pytest.fixture(scope="session")
def tpu_device():
    from veles_tpu.backends import make_device
    return make_device("tpu")   # a TPU or an exception
