"""Test harness setup.

Force JAX onto a virtual 8-device CPU mesh BEFORE jax is imported anywhere, so
sharding tests exercise real multi-device SPMD paths without TPU hardware
(the driver separately dry-runs the multi-chip path; see __graft_entry__.py).

TPU_TESTS=1 leaves the platform alone so the real chip stays visible — used
by the @pytest.mark.tpu on-hardware suite (tests/test_pallas_tpu.py):

    TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py -v

Run ONLY that module under TPU_TESTS: the rest of the suite expects the
8-device CPU mesh.
"""

import os

TPU_TESTS = os.environ.get("TPU_TESTS", "") == "1"

if not TPU_TESTS:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

# Boot-time bucket-ladder precompile (TPU_PRECOMPILE, default on in
# production) would add ~12 XLA compiles to EVERY Runner boot in the
# suite; tests that pin the precompile behavior opt back in explicitly
# (tests/test_hotpath.py).
os.environ.setdefault("TPU_PRECOMPILE", "false")

# The suite's CPU compiles (and the servers its tests spawn) have no
# business in the persistent compile cache that Runner/sidecar boots point
# at the checkout (utils/jaxsetup.py); tests of that helper read the
# configured path, not the files.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import faulthandler  # noqa: E402

import pytest  # noqa: E402

# Per-test deadlock guard (the pytest-timeout "thread" method, without the
# dependency — the container has no pytest_timeout): arm
# faulthandler.dump_traceback_later before each test and cancel it after.
# A shed/drain deadlock then surfaces as an all-thread stack dump plus a
# hard exit within PYTEST_PER_TEST_TIMEOUT seconds, instead of eating the
# whole 870s tier-1 budget silently. 0 disables.
PER_TEST_TIMEOUT = float(os.environ.get("PYTEST_PER_TEST_TIMEOUT", "300"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: runs the Pallas kernel COMPILED on a real TPU"
    )
    config.addinivalue_line(
        "markers", "slow: multi-second subprocess tests (bench artifact)"
    )
    config.addinivalue_line(
        "markers",
        "mp: multi-process frontend tests (shm rings / FRONTEND_PROCS; "
        "`make tests_mp`)",
    )
    config.addinivalue_line(
        "markers",
        "cluster: partitioned device-owner cluster tests (cluster/; "
        "`make tests_cluster`)",
    )
    config.addinivalue_line(
        "markers",
        "hotkeys: heavy-hitter sketch tests (ops/sketch.py; "
        "`make tests_hotkeys`)",
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if PER_TEST_TIMEOUT > 0:
        faulthandler.dump_traceback_later(PER_TEST_TIMEOUT, exit=True)
    try:
        yield
    finally:
        if PER_TEST_TIMEOUT > 0:
            faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def test_store():
    from api_ratelimit_tpu.stats import Store, TestSink

    sink = TestSink()
    store = Store(sink)
    return store, sink


@pytest.fixture
def fake_time():
    from api_ratelimit_tpu.utils import FakeTimeSource

    return FakeTimeSource(now=1234)
