"""Test config. NOTE: no xla_force_host_platform_device_count here —
unit/smoke tests must see exactly 1 device. Multi-device behaviour is
tested via subprocesses (tests/test_distributed.py) and the dry-run."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _reset_obs_state():
    """Isolate the observability globals per test: the metrics registry
    (degradation / launch / cache counters are registry-scoped, ISSUE 9)
    and the active tracer must not bleed between tests."""
    from repro.obs import metrics as _m
    from repro.obs import trace as _t
    yield
    _t.set_tracer(None)
    _m.set_registry(None)        # back to the default registry ...
    _m.reset_metrics()           # ... and wipe it
