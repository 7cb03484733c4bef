import os
import sys

# Smoke tests and benches must see the REAL device count (1 CPU device) —
# only launch/dryrun.py forces 512 placeholder devices, in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    # bare container: run property tests via the deterministic fallback
    import _hypothesis_fallback

    sys.modules["hypothesis"] = _hypothesis_fallback

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def x64():
    """True float64 for the test (engines request float64 explicitly;
    without the flag JAX silently truncates to float32).  Modules whose
    every test needs it opt in with ``pytestmark =
    pytest.mark.usefixtures("x64")``."""
    with jax.enable_x64(True):
        yield
