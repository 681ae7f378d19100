"""Shared fixtures.

NOTE: no XLA_FLAGS here — smoke tests must see the real single CPU device;
multi-device tests spawn subprocesses (test_distributed).
"""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
