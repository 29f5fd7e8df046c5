"""Unit tests for utils + models foundations."""

import pytest

from api_ratelimit_tpu.models import Unit, unit_to_divider, unit_from_string
from api_ratelimit_tpu.utils import (
    BasicSampler,
    BurstSampler,
    FakeTimeSource,
    RandomSampler,
    calculate_reset,
)


def test_unit_to_divider():
    assert unit_to_divider(Unit.SECOND) == 1
    assert unit_to_divider(Unit.MINUTE) == 60
    assert unit_to_divider(Unit.HOUR) == 3600
    assert unit_to_divider(Unit.DAY) == 86400
    with pytest.raises(ValueError):
        unit_to_divider(Unit.UNKNOWN)


def test_unit_from_string():
    assert unit_from_string("second") == Unit.SECOND
    assert unit_from_string("MINUTE") == Unit.MINUTE
    assert unit_from_string("Hour") == Unit.HOUR
    assert unit_from_string("day") == Unit.DAY
    assert unit_from_string("unknown") is None
    assert unit_from_string("fortnight") is None


def test_calculate_reset():
    # now=1234: second window resets in 1s, minute window in 60 - 34 = 26s.
    assert calculate_reset(Unit.SECOND, 1234) == 1
    assert calculate_reset(Unit.MINUTE, 1234) == 26
    assert calculate_reset(Unit.HOUR, 1234) == 3600 - 1234
    assert calculate_reset(Unit.DAY, 1234) == 86400 - 1234


def test_fake_time_source():
    ts = FakeTimeSource(100)
    assert ts.unix_now() == 100
    ts.sleep(5)
    assert ts.unix_now() == 105
    assert ts.sleeps == [5]


def test_basic_sampler():
    s = BasicSampler(3)
    results = [s.sample() for _ in range(9)]
    assert results == [True, False, False] * 3
    assert BasicSampler(1).sample() is True


def test_random_sampler_bounds():
    assert RandomSampler(0).sample() is False
    assert RandomSampler(1).sample() is True


def test_burst_sampler():
    s = BurstSampler(burst=3, period_seconds=100.0, next_sampler=None)
    assert [s.sample() for _ in range(5)] == [True, True, True, False, False]

    always = BasicSampler(1)
    s2 = BurstSampler(burst=1, period_seconds=100.0, next_sampler=always)
    assert [s2.sample() for _ in range(3)] == [True, True, True]


def test_assertx_location():
    from api_ratelimit_tpu.assertx import AssertionFailure, assert_

    assert_(True, "fine")
    with pytest.raises(AssertionFailure, match="test_utils.py"):
        assert_(False, "boom")


class TestJaxSetup:
    """utils/jaxsetup.py: where the compile cache lives, and which devices
    may serve."""

    def test_compile_cache_env_wins(self, monkeypatch):
        import jax

        from api_ratelimit_tpu.utils import jaxsetup

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        before = jax.config.jax_compilation_cache_dir
        assert jaxsetup.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched

    def test_compile_cache_defaults_to_checkout(self, monkeypatch):
        import os

        import jax

        from api_ratelimit_tpu.utils import jaxsetup

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = jaxsetup.enable_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert path == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_mesh_larger_than_visible_devices_raises(self):
        import jax

        from api_ratelimit_tpu.utils import jaxsetup

        n = len(jax.devices())
        assert jaxsetup.serving_mesh(0) is None
        assert jaxsetup.serving_mesh(n).devices.size == n
        with pytest.raises(RuntimeError, match=f"TPU_MESH_DEVICES={n + 1}"):
            jaxsetup.serving_mesh(n + 1)

    def test_host_devices_need_an_explicit_cpu_pin(self, monkeypatch):
        """No TPU and no JAX_PLATFORMS=cpu: the engine must not quietly
        serve from the host."""
        from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine
        from api_ratelimit_tpu.utils import jaxsetup

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="no TPU visible"):
            jaxsetup.serving_devices()
        with pytest.raises(RuntimeError, match="no TPU visible"):
            SlabDeviceEngine(time_source=FakeTimeSource(0), n_slots=1 << 10)
