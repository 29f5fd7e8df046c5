"""One general traffic generator, driven by the numbers in a workload file
and its configuration's key universes. Everything is drawn from --seed;
the same seed gives the same inputs.

A fixed amount of work per run: an open-loop schedule has exactly
rate x seconds arrivals whatever the seed (the seed orders the gaps and
picks the keys), so runs on different seeds do the same work."""

from __future__ import annotations

import numpy as np

_FMIX_C1, _FMIX_C2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream (seeds up to 2**63)."""
    salt = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & (2**64 - 1), salt])


def fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finaliser: a bijection of uint32."""
    x = np.asarray(x, dtype=np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * _FMIX_C1
    x = x ^ (x >> np.uint32(13))
    x = x * _FMIX_C2
    return x ^ (x >> np.uint32(16))


class Zipf:
    """Bounded Zipf over ranks 0..n-1: P(rank k) ~ 1/(k+1)**s.

    YCSB's ZipfianGenerator draws the same law (its zipfian constant is s)
    over recordcount items; s may be below 1, where numpy's zipf cannot
    go. Sampling is an inverse-CDF lookup."""

    def __init__(self, n: int, s: float):
        weights = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
        cdf = np.cumsum(weights)
        self.cdf = cdf / cdf[-1]
        self.n = int(n)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        return np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          self.n - 1).astype(np.uint32)


def open_loop_offsets(rng: np.random.Generator, rate: float, seconds: float,
                      lead: float = 0.0) -> np.ndarray:
    """Poisson arrival times in [lead, lead + seconds): exactly
    round(rate * seconds) of them, exponential gaps scaled to the span."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0, n + 1)
    t = np.cumsum(gaps)
    return lead + t[:n] / t[n] * seconds


def ip_strings(ranks: np.ndarray) -> list[str]:
    """A client IP per popularity rank: rank * 2654435761 mod 2**24 is a
    bijection, so distinct ranks are distinct addresses in 10.0.0.0/8."""
    a = (ranks.astype(np.uint64) * np.uint64(2654435761)) & np.uint64(0xFFFFFF)
    return [f"10.{v >> 16}.{(v >> 8) & 255}.{v & 255}" for v in a.tolist()]


def user_strings(ranks: np.ndarray) -> list[str]:
    """A user id per popularity rank (fmix32 scatters ranks over ids)."""
    return [f"u{v:08x}" for v in fmix32(ranks).tolist()]


def record_fingerprints(ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(fp_lo, fp_hi) of YCSB record ids: fp_lo = fmix32(id + 1) is a
    bijection (and never 0 for ids < 2**32 - 1), so distinct records are
    distinct keys; fp_hi is a second independent mix."""
    ids = np.asarray(ids, dtype=np.uint32)
    return fmix32(ids + np.uint32(1)), fmix32(ids ^ np.uint32(0xA5A5A5A5))
