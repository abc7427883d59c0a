"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 CPU device
(tests that need several virtual devices set them in a subprocess)."""
import jax
import numpy as np
import pytest

from repro.core import generate_cluster


@pytest.fixture(scope="session")
def cluster300():
    return generate_cluster(num_apps=300, seed=0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
