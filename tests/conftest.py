"""Test harness configuration.

Tests run on a *virtual 8-device CPU mesh* so the multi-chip sharding paths
(parallel/mesh.py) execute without TPU hardware, mirroring how the reference
fakes a cluster with in-process threads + loopback sockets
(reference: tests/test_integration.py:51-115).

The env vars must be set before jax initializes its backends, hence the
module-level assignment in conftest (imported by pytest before any test
module).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests force the CPU through the config channel too (it outranks the
# variable); safe because no backend has initialized yet at conftest import.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

from distributed_faiss_tpu.utils import (compilecheck, racecheck, threadcheck,
                                         xfercheck)

# DFT_THREADCHECK=1: wrap Thread.start once, at collection time, so every
# thread started anywhere in the suite carries creation provenance
if threadcheck.enabled():
    threadcheck.install()

# DFT_RACECHECK=1: instrument the lockdep-factory-locked classes once, at
# collection time, so every instance the suite creates is witnessed from
# birth (utils/racecheck.py; implies lockdep's held-lockset tracking)
if racecheck.enabled():
    racecheck.install()

# DFT_COMPILECHECK=1: hook jax's lowering logger once, at collection time,
# so every XLA compilation anywhere in the suite lands in the per-entry
# tally (utils/compilecheck.py; the zero-new-compiles-after-warmup
# assertions read it via snapshot()/new_since())
if compilecheck.enabled():
    compilecheck.install()


@pytest.fixture(autouse=True)
def _thread_leak_witness():
    """DFT_THREADCHECK=1 runtime witness (utils/threadcheck.py): snapshot
    the live-thread set around each test; a NON-DAEMON thread created
    during the test that outlives it (past a bounded grace join) fails
    the test with its name and creation site. Threads owned by
    broader-scoped fixtures are in the `before` snapshot (higher-scope
    fixtures set up first) and are exempt, which scopes the witness to
    exactly what this test created. No-op when the knob is off."""
    if not threadcheck.enabled():
        yield
        return
    before = threadcheck.snapshot()
    yield
    threadcheck.check(before)


@pytest.fixture(autouse=True)
def _shared_state_race_witness():
    """DFT_RACECHECK=1 runtime witness (utils/racecheck.py): any
    shared-state race recorded during this test fails it — including
    races whose in-thread SharedStateRaceError a serving loop swallowed
    (batcher/connection threads catch broadly by design, so the raise
    alone cannot be the only failure path). Violations from earlier
    tests are drained up front so blame lands on the test that provoked
    the race. No-op when the knob is off."""
    if not racecheck.enabled():
        yield
        return
    racecheck.drain()
    yield
    racecheck.check()


@pytest.fixture(autouse=True)
def _implicit_transfer_witness():
    """DFT_XFERCHECK=1 runtime witness (utils/xfercheck.py): any implicit
    host<->device transfer recorded inside a guarded serving section
    during this test fails it — including violations whose in-thread
    ImplicitTransferError the scheduler's broad per-request error
    routing swallowed. Earlier tests' violations are drained up front so
    blame lands on the test that provoked the transfer. No-op when the
    knob is off."""
    if not xfercheck.enabled():
        yield
        return
    xfercheck.drain()
    yield
    xfercheck.check()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
