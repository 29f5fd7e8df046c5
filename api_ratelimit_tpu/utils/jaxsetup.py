"""JAX process setup shared by every entry point that owns a device.

Two decisions live here so the server (runner.py), the device owner
(cmd/sidecar_cmd.py), bench.py and chip_smoke.py cannot drift apart:

* where the persistent compile cache lives — JAX_COMPILATION_CACHE_DIR
  when the operator set it (JAX reads the variable itself; no other path is
  set in code), else the fixed `<checkout>/.jax_cache`. The path is part of
  the cache key, so it must never move between runs (no temp, pid or time
  component);
* which devices serve — a TPU unless JAX_PLATFORMS pins the CPU
  explicitly, and never fewer than asked for. A boot that finds no chip
  raises instead of quietly serving from the host.
"""

from __future__ import annotations

import logging
import os

_log = logging.getLogger(__name__)

CHECKOUT_DIR = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Call before the first compile: JAX settles the cache on first use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cpu_pinned() -> bool:
    """True when the operator pinned JAX to the host (JAX_PLATFORMS=cpu)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def serving_devices(n: int = 1) -> list:
    """The first `n` devices the engine serves from.

    Raises when fewer than `n` are visible, and when they are not TPUs
    unless JAX_PLATFORMS=cpu asked for the host explicitly."""
    import jax

    found = jax.devices()
    platform = found[0].platform
    _log.info(
        "jax devices: platform=%s kind=%s count=%d",
        platform,
        found[0].device_kind,
        len(found),
    )
    if platform != "tpu" and not cpu_pinned():
        raise RuntimeError(
            f"no TPU visible (jax found {len(found)} {platform} device(s)); "
            f"set JAX_PLATFORMS=cpu to serve from the host on purpose"
        )
    if len(found) < n:
        raise RuntimeError(
            f"TPU_MESH_DEVICES={n} but only {len(found)} {platform} "
            f"device(s) are visible"
        )
    return found[:n]


def serving_mesh(n: int):
    """The hash-sharded slab's mesh for TPU_MESH_DEVICES=n; None for n <= 1
    (the single-device engine)."""
    if n <= 1:
        return None
    from ..parallel.sharded_slab import make_mesh

    return make_mesh(serving_devices(n))
