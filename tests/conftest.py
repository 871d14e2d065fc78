"""Shared fixtures.  NOTE: no XLA device-count forcing here — smoke tests
must see the real single CPU device; distributed tests spawn subprocesses
with their own XLA_FLAGS (see test_distributed.py).
"""
import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
