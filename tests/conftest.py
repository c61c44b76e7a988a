import numpy as np
import pytest


@pytest.fixture(scope="session")
def world():
    from repro.data.world import SemanticWorld

    return SemanticWorld(n_intents=200, dim=64, seed=0)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
