"""Shared fixtures.  NOTE: tests run with the real single-CPU device count;
only multi-device tests spawn subprocesses with XLA_FLAGS, since jax fixes
a process's device count when it first initializes."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running multi-device/subprocess tests "
        "(deselect with -m 'not slow')",
    )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
