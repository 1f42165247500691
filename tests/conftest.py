import numpy as np
import pytest

# the host Fraction oracle, shared with chip_smoke.py
from repro.core.metrics import fdp_oracle, frac_to_f32_rne  # noqa: F401


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def clean_sites():
    """Reset the process-global dispatch site registry around a test, so
    ``sites_seen()`` assertions never depend on which tests dispatched GEMMs
    earlier in the session (the registry is process-wide by design)."""
    from repro.core import dispatch
    dispatch.reset_sites_seen()
    yield dispatch.sites_seen
    dispatch.reset_sites_seen()
