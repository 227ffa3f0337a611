# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see ONE CPU
# device (the 512-device override lives only in repro.launch.dryrun subprocesses).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# Tests never use JAX's persistent compilation cache — neither here nor in the
# entry-point subprocesses they start (which inherit this environment and
# would otherwise turn it on through repro.launch.cache).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def assert_finite(tree, msg=""):
    import jax.numpy as jnp
    for leaf in jax.tree.leaves(tree):
        assert jnp.isfinite(leaf).all(), f"non-finite values {msg}"
