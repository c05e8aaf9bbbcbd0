"""Test harness configuration.

Forces JAX onto 8 virtual CPU devices so sharding/collective code paths run
without TPU hardware — the analog of the reference booting multiple nodes in
one JVM via InternalTestCluster (test/framework/.../test/InternalTestCluster.java:195).
Must run before jax is imported anywhere.
"""

import os
import sys

# tests pin the CPU backend, by the environment variable, before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {devices}"
    return devices


# ------------------------------------------------------- seeded randomization
#
# OpenSearchTestCase analog: every randomized test draws from a Random
# seeded by TEST_SEED (or a fresh seed), derived per test id so one run's
# tests are independent but fully reproducible. On failure the reproduce
# line is appended to the report:  TEST_SEED=<seed> python -m pytest <test>

import random as _random  # noqa: E402

_BASE_SEED = os.environ.get("TEST_SEED") or \
    f"{_random.SystemRandom().randrange(1 << 32):08X}"


@pytest.fixture()
def rnd(request):
    derived = f"{_BASE_SEED}:{request.node.nodeid}"
    r = _random.Random(derived)
    request.node._test_seed = _BASE_SEED
    return r


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    seed = getattr(item, "_test_seed", None)
    if rep.failed and seed is not None:
        rep.sections.append(
            ("randomized seed",
             f"reproduce with: TEST_SEED={seed} python -m pytest "
             f"{item.nodeid}"))


# ------------------------------------------------------- host-sync sanitizer
#
# ISSUE 8: the runtime counterpart of tools/lint's sync-lint. Enabled for
# the WHOLE tier-1 run: any jax.device_get executed from inside the
# opensearch_tpu package while no ledger-attributed region is active on
# the calling thread raises UnattributedSyncError — a new unattributed
# sync on the query path fails the suite the moment it runs, instead of
# surfacing as an unexplained gap in a later profile review. Calls from
# test/tool frames are exempt (the contract binds serving code).

@pytest.fixture(scope="session", autouse=True)
def _sync_sanitizer():
    from opensearch_tpu.common.sanitize import SANITIZER
    SANITIZER.install()
    SANITIZER.enabled = True
    yield
    SANITIZER.enabled = False
    SANITIZER.uninstall()
