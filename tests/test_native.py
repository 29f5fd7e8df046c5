"""Native host-codec parity tests: the C++ library (native/host_codec.cpp)
must produce bit-identical fingerprints and byte-identical cache keys to the
pure-Python implementations — slab slot identity may not depend on which
host path computed it. Mirrors the reference's exact-wire-command assertions
at the backend seam (test/redis/fixed_cache_impl_test.go:59-64)."""

from __future__ import annotations

import os
import random
import string

import numpy as np
import pytest

from api_ratelimit_tpu.ops import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native codec unavailable (no g++?)"
)


class TestPackScatterParity:
    """rl_pack_rows / rl_scatter_rows (the dispatch loop's gather/scatter
    stages) vs the numpy fallback: byte-identical operands and verdicts,
    including non-contiguous arena-slice sources."""

    def test_pack_rows_matches_numpy_copy_loop(self):
        rng = np.random.RandomState(7)
        # mix of contiguous blocks and column slices of a wider arena
        arena = rng.randint(0, 2**32, size=(6, 64), dtype=np.uint64).astype(
            np.uint32
        )
        blocks = [
            np.ascontiguousarray(
                rng.randint(0, 2**32, size=(6, 3), dtype=np.uint64).astype(
                    np.uint32
                )
            ),
            arena[:, 10:14],  # row stride 64, not 4
            arena[:, 30:31],
            np.ascontiguousarray(
                rng.randint(0, 2**32, size=(6, 5), dtype=np.uint64).astype(
                    np.uint32
                )
            ),
        ]
        total = sum(b.shape[1] for b in blocks)
        want = np.zeros((7, 16), dtype=np.uint32)
        off = 0
        for b in blocks:
            want[:6, off : off + b.shape[1]] = b
            off += b.shape[1]
        got = np.zeros((7, 16), dtype=np.uint32)
        native.pack_rows(blocks, got, total)
        assert got.tobytes() == want.tobytes()

    def test_pack_rows_bounds_checked(self):
        blocks = [np.zeros((6, 9), dtype=np.uint32)]
        dst = np.zeros((7, 8), dtype=np.uint32)
        with pytest.raises(ValueError, match="exceed"):
            native.pack_rows(blocks, dst, 9)

    def test_scatter_rows_matches_numpy_slices(self):
        rng = np.random.RandomState(8)
        src = rng.randint(0, 2**32, size=24, dtype=np.uint64).astype(np.uint32)
        counts = [3, 1, 12, 8]
        dsts = [np.zeros(c, dtype=np.uint32) for c in counts]
        native.scatter_rows(src, dsts, counts)
        off = 0
        for d, c in zip(dsts, counts):
            assert d.tolist() == src[off : off + c].tolist()
            off += c

    def test_scatter_rows_bounds_checked(self):
        src = np.zeros(4, dtype=np.uint32)
        with pytest.raises(ValueError, match="exceed"):
            native.scatter_rows(
                src, [np.zeros(5, dtype=np.uint32)], [5]
            )


def _rand_text(rng, n):
    alphabet = string.ascii_letters + string.digits + "_-./:é中"
    return "".join(rng.choice(alphabet) for _ in range(n))


class TestXxh64Parity:
    @pytest.mark.parametrize("n", [0, 1, 3, 4, 7, 8, 15, 31, 32, 33, 100, 4096])
    @pytest.mark.parametrize("seed", [0, 1, 60, 86400, 2**64 - 1])
    def test_matches_python_xxhash(self, n, seed):
        import xxhash

        data = os.urandom(n)
        assert native.xxh64(data, seed) == xxhash.xxh64(data, seed=seed).intdigest()


class TestFingerprintBatchParity:
    def test_matches_python_fingerprint64(self):
        from api_ratelimit_tpu.models.descriptors import Entry
        from api_ratelimit_tpu.ops.hashing import fingerprint64

        rng = random.Random(7)
        records = []
        seeds = []
        expected = []
        for _ in range(200):
            domain = _rand_text(rng, rng.randint(0, 20))
            entries = tuple(
                Entry(_rand_text(rng, rng.randint(0, 30)), _rand_text(rng, rng.randint(0, 30)))
                for _ in range(rng.randint(0, 4))
            )
            divider = rng.choice([1, 60, 3600, 86400])
            records.append(native.record_strings(domain, entries))
            seeds.append(divider)
            expected.append(fingerprint64(domain, entries, divider))
        got = native.fingerprint_batch(records, seeds)
        assert got.dtype == np.uint64
        assert [int(x) for x in got] == expected

    def test_empty_strings_and_aliasing(self):
        # length prefixes must prevent ("ab","") from aliasing ("a","b")
        from api_ratelimit_tpu.models.descriptors import Entry
        from api_ratelimit_tpu.ops.hashing import fingerprint64

        a = native.fingerprint_batch(
            [native.record_strings("d", (Entry("ab", ""),))], [60]
        )[0]
        b = native.fingerprint_batch(
            [native.record_strings("d", (Entry("a", "b"),))], [60]
        )[0]
        assert a != b
        assert int(a) == fingerprint64("d", (Entry("ab", ""),), 60)

    def test_fingerprint_many_dispatches_native(self):
        from api_ratelimit_tpu.models.descriptors import Entry
        from api_ratelimit_tpu.ops.hashing import fingerprint64, fingerprint_many

        records = [
            ("domain", (Entry("key1", f"val{i}"),)) for i in range(16)
        ]
        dividers = [60] * 16
        got = fingerprint_many(records, dividers)
        want = [fingerprint64(d, e, 60) for d, e in records]
        assert [int(x) for x in got] == want

    def test_fingerprint_many_small_batch_python_path(self):
        from api_ratelimit_tpu.models.descriptors import Entry
        from api_ratelimit_tpu.ops.hashing import fingerprint64, fingerprint_many

        records = [("d", (Entry("k", "v"),))]
        got = fingerprint_many(records, [1])
        assert int(got[0]) == fingerprint64("d", (Entry("k", "v"),), 1)


class TestComposeKeysParity:
    def test_matches_python_codec(self):
        from api_ratelimit_tpu.limiter.cache_key import generate_cache_key
        from api_ratelimit_tpu.models.config import RateLimit
        from api_ratelimit_tpu.models.descriptors import Descriptor, Entry
        from api_ratelimit_tpu.models.response import RateLimitValue
        from api_ratelimit_tpu.models.units import Unit, unit_to_divider

        rng = random.Random(13)
        records = []
        windows = []
        expected = []
        for _ in range(100):
            domain = _rand_text(rng, rng.randint(1, 15))
            entries = tuple(
                Entry(_rand_text(rng, rng.randint(1, 10)), _rand_text(rng, rng.randint(0, 10)))
                for _ in range(rng.randint(1, 3))
            )
            unit = rng.choice([Unit.SECOND, Unit.MINUTE, Unit.HOUR, Unit.DAY])
            limit = RateLimit(
                full_key="x",
                stats=None,
                limit=RateLimitValue(requests_per_unit=10, unit=unit),
            )
            now = rng.randint(0, 2**31 - 1)
            divider = unit_to_divider(unit)
            records.append(native.record_strings(domain, entries))
            windows.append((now // divider) * divider)
            expected.append(
                generate_cache_key(domain, Descriptor(entries=entries), limit, now).key
            )
        got = native.compose_keys_batch(records, windows)
        assert got == expected

    def test_window_zero(self):
        got = native.compose_keys_batch([["d", "k", "v"]], [0])
        assert got == ["d_k_v_0"]

    def test_window_negative_matches_python_str(self):
        # pre-epoch/skewed clocks must render like Python's str()
        got = native.compose_keys_batch(
            [["d", "k", "v"], ["d", "k", "v"]], [-60, -9223372036854775808]
        )
        assert got == ["d_k_v_-60", "d_k_v_-9223372036854775808"]

    def test_seed_count_mismatch_raises(self):
        with pytest.raises(ValueError):
            native.fingerprint_batch([["d"], ["e"]], [1])

    def test_generate_cache_keys_native_batch_parity(self, test_store):
        # >=8 checked descriptors routes through the native composer; keys
        # must match the per-descriptor Python codec exactly, with nil
        # limits interleaved as empty keys
        from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
        from api_ratelimit_tpu.limiter.cache_key import generate_cache_key
        from api_ratelimit_tpu.models.config import RateLimit, new_rate_limit_stats
        from api_ratelimit_tpu.models.descriptors import (
            Descriptor,
            RateLimitRequest,
        )
        from api_ratelimit_tpu.models.response import RateLimitValue
        from api_ratelimit_tpu.models.units import Unit
        from api_ratelimit_tpu.utils.timeutil import FakeTimeSource

        store, _ = test_store
        scope = store.scope("t")
        descriptors = []
        limits = []
        for i in range(12):
            descriptors.append(Descriptor.of(("key", f"v{i}"), ("sub", "x")))
            if i % 5 == 4:
                limits.append(None)  # unchecked descriptor
            else:
                limits.append(
                    RateLimit(
                        full_key=f"k{i}",
                        stats=new_rate_limit_stats(scope, f"k{i}"),
                        limit=RateLimitValue(
                            requests_per_unit=10,
                            unit=Unit.SECOND if i % 2 else Unit.HOUR,
                        ),
                    )
                )
        ts = FakeTimeSource(987_654_321)
        base = BaseRateLimiter(time_source=ts, jitter_rand=None)
        request = RateLimitRequest(
            domain="paritydom", descriptors=tuple(descriptors)
        )
        got = base.generate_cache_keys(request, limits, 1)
        want = [
            generate_cache_key("paritydom", d, lim, 987_654_321)
            for d, lim in zip(descriptors, limits)
        ]
        assert got == want

    def test_output_buffer_growth(self):
        # force the retry path with a huge value string
        big = "v" * 100_000
        got = native.compose_keys_batch([["d", "k", big]], [1234])
        assert got == [f"d_k_{big}_1234"]


class TestBuildKey:
    def test_so_is_named_by_source_hash(self):
        """A build is found by the sha256 of the source it came from, never
        by mtime: a stale .so copied beside edited sources is not loaded."""
        import hashlib

        if "RL_NATIVE_LIB" in os.environ:
            pytest.skip("RL_NATIVE_LIB pins a build by path")
        with open(native._SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        assert os.path.basename(native._SO_PATH) == (
            f"libratelimit_host-{digest}.so"
        )
        assert os.path.exists(native._SO_PATH)
