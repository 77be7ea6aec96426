"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see ONE device
(the 512-device override belongs exclusively to repro.launch.dryrun)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def tensors4x4():
    from repro.core.properties import controlled_tensors
    with jax.enable_x64(True):
        yield controlled_tensors(9, dtype=jnp.float64)


def make_contribs(n=4, shape=(8, 8), seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    return [jnp.asarray(r.standard_normal(shape), dtype) for _ in range(n)]
