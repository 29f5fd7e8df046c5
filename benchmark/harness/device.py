"""The chip: the look for it, the peaks table, memory and compile counts.

The benchmark never falls back to the host: with no TPU, or fewer chips
than the cell asks for, it exits non-zero before running anything."""

from __future__ import annotations

# Published peaks of one chip, keyed by jax's device_kind. Source: Google
# Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s). The slab step does integer compares and
# adds on a few dozen bytes per decision, far below any op/s peak, so its
# roofline is the HBM bandwidth alone.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to benchmark/harness/device.py with its source") from None


class NoChip(RuntimeError):
    pass


def require_tpu(chips: int):
    """The first `chips` TPU devices; raises NoChip otherwise."""
    import jax

    found = jax.devices()
    if found[0].platform != "tpu":
        raise NoChip(f"no TPU visible (jax found {len(found)} "
                     f"{found[0].platform} device(s))")
    if len(found) < chips:
        raise NoChip(f"the cell asks for {chips} chips, {len(found)} visible")
    return found[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of `devices` (None when the backend
    keeps no such statistic, as the CPU does not)."""
    peaks_seen = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_seen) if peaks_seen else None


class CompileCounter:
    """Counts programs lowered and compiled, from jax's monitoring events
    (a persistent-cache hit lowers but does not compile)."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1

    def mark(self) -> tuple[int, int]:
        return self.lowered, self.compiled
