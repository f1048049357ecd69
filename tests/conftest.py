import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — tests must see the real (1-device)
# topology; only launch/dryrun*.py force placeholder devices (and the
# multishard test does so in a subprocess).


def pytest_configure(config):
    # registered in pyproject.toml as well; kept here so `pytest tests/...`
    # from any rootdir honours -m "not slow" without warnings
    config.addinivalue_line(
        "markers", "slow: long-running tests (multi-device subprocess)")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
