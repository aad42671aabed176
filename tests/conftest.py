"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see 1 CPU device;
multi-device behaviour is tested via subprocesses (test_distributed.py)."""
import jax
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
