import contextlib
import math
import os
import sys

import pytest

# Tests see exactly ONE CPU device (the dry-run sets its own 512-device flag
# in-process before importing jax — never here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


_ROUTE_THRESHOLD = {"sweep": 0.0, "fused": math.inf}


@contextlib.contextmanager
def _pinned_route(route):
    from repro.core.engine import IntervalVDCSimulator

    old = IntervalVDCSimulator.SWEEP_MIN_CHUNKS_PER_REQ
    if route is not None:
        IntervalVDCSimulator.SWEEP_MIN_CHUNKS_PER_REQ = _ROUTE_THRESHOLD[route]
    try:
        yield
    finally:
        IntervalVDCSimulator.SWEEP_MIN_CHUNKS_PER_REQ = old


@pytest.fixture(scope="session")
def interval_route():
    """Context-manager factory that pins the interval engine's static
    replay route by patching ``SWEEP_MIN_CHUNKS_PER_REQ``: ``"sweep"``
    (threshold 0) or ``"fused"`` (threshold inf); ``None`` keeps the
    engine's own choice."""
    return _pinned_route
