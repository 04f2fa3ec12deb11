"""Shared fixtures.

We give the test process 8 CPU devices (NOT the dry-run's 512 — that flag is
set only inside launch/dryrun.py) so shard_map / PGAS tests exercise a real
2x4 mesh while smoke tests still run comfortably on CPU.
"""
# Must run before jax initializes its backend; conftest import is early
# enough as long as no test module imports jax at collection time before us.
import jax

jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

from repro.compat import make_auto_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh2x4():
    """A (y=2, x=4) tile grid — 8 tiles, one per CPU device."""
    return make_auto_mesh((2, 4), ("y", "x"))


@pytest.fixture(scope="session")
def mesh_dm():
    """A (data=2, model=4) mesh in the production axis naming."""
    return make_auto_mesh((2, 4), ("data", "model"))
