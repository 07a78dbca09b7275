"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see 1 CPU device; the
multi-device paths are exercised in subprocesses (test_multidevice.py) and
by the dry-run (launch/dryrun.py sets the flag itself).
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax      # noqa: E402
import pytest   # noqa: E402

jax.config.update("jax_enable_x64", False)


class StubReplica:
    """Minimal ReplicaRouter replica-protocol object for clock-free
    router tests (shared by test_scheduler_properties / test_router):
    a bare FIFO scheduler whose step admits and instantly completes one
    ticket."""

    def __init__(self, precision="fp32", **sched_kw):
        from repro.serving.scheduler import Scheduler
        self.scheduler = Scheduler("fifo", **sched_kw)
        self.telemetry = self.scheduler.telemetry
        self.precision = precision

    @property
    def inflight(self):
        return 0

    @property
    def has_work(self):
        return self.scheduler.depth > 0

    def step_once(self):
        for t in self.scheduler.admit(1):
            self.scheduler.complete(t)

    def submit(self, item, *, slo_ms=None, priority=None, **kw):
        return self.scheduler.submit(item, slo_ms=slo_ms,
                                     priority=priority or 0)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)
