"""Hardware/build provenance: the regime a measurement was taken in.

Every scaling claim in this repo is conditional on hardware (PERF.md has
carried "the box is ~2.2x slower than r06's" as prose since round 7, and
round 11/13 recorded multi-process arms that physically could not win on
one core). This module makes the regime a first-class, machine-checkable
fact in two places:

  * BENCH artifacts: ``build_provenance()`` returns a CRC'd block
    (host_cpus, cpu_model, JAX platform, device_count, git rev, knob
    set) that bench.py stamps into every emitted JSON line and
    tools/bench_report.py uses as the comparability gate — rows whose
    ``platform_marker()`` differ are never diffed against each other.

  * Live fleets: ``register_build_gauges()`` exports the same facts as
    ``ratelimit.build.*`` gauges on every frontend and sidecar
    ``/metrics``, next to ``ratelimit.native.available``, so a scraped
    fleet self-describes the regime it is being measured in.

Deliberately jax-free: the fleet master, the bench driver and the lint
tools must read/stamp provenance without importing the device stack.
The platform/device facts are passed IN by the component that owns a
device (bench.py after jax init, sidecar_cmd after engine build); a
frontend that owns no accelerator honestly reports platform "cpu" and
device_count 0.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import zlib

PROVENANCE_VERSION = 1

# numeric platform ids for the gauge export (gauges are floats); unknown
# platforms map to -1 so a new accelerator is visible, not invisible
PLATFORM_IDS = {"cpu": 0, "tpu": 1, "gpu": 2}

# the knob set stamped into the block: everything that changes what a
# BENCH number means without changing the code rev. BENCH_HOST_CPUS is
# itself a knob so a forced-cpus test run is visibly a forced run.
KNOB_NAMES = (
    "BENCH_PALLAS",
    "BENCH_ARM",
    "BENCH_TIERS",
    "BENCH_HOST_CPUS",
    "SLAB_WAYS",
    "SHM_RINGS",
    "LEASE_ENABLED",
    "HOTKEYS_ENABLED",
    "PARTITIONS",
    "FRONTEND_PROCS",
)

# fields a valid block must carry (bench_lint rejects anything less)
REQUIRED_FIELDS = (
    "version",
    "platform",
    "device_count",
    "host_cpus",
    "cpu_model",
    "git_rev",
    "knobs",
    "crc",
)


def host_cpus() -> int:
    """CPUs this process may actually run on (the affinity mask, not the
    box inventory — a container pinned to 1 of 64 cores is a 1-core box
    for scaling purposes). BENCH_HOST_CPUS overrides for tests driving
    the tier-arming matrix; the override is visible in the knob set."""
    forced = os.environ.get("BENCH_HOST_CPUS", "").strip()
    if forced:
        return max(1, int(forced))
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1


@functools.lru_cache(maxsize=1)
def cpu_model() -> str:
    """The /proc/cpuinfo model string — the only legacy-proof way to tell
    two "platform: cpu" boxes apart (the r06-vs-r07 bench-box swap)."""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return ""


@functools.lru_cache(maxsize=None)
def git_rev(repo_dir: str | None = None) -> str:
    """Short git rev of the working tree, "" when unavailable."""
    if repo_dir is None:
        repo_dir = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True,
            text=True,
            timeout=5,
            cwd=repo_dir,
        )
        return out.stdout.strip() if out.returncode == 0 else ""
    except (OSError, subprocess.SubprocessError):
        return ""


def rev_hash(rev: str) -> int:
    """Numeric stand-in for the rev string (gauges carry floats)."""
    return zlib.crc32(rev.encode("utf-8"))


def knob_set() -> dict:
    """The stamped knob environment: only knobs that are actually SET —
    an empty dict means "all defaults", which is itself information."""
    return {k: os.environ[k] for k in KNOB_NAMES if os.environ.get(k)}


def provenance_crc(block: dict) -> int:
    """CRC32 over the canonical JSON of everything except the crc field
    itself — a hand-edited or truncated block fails verification."""
    body = {k: v for k, v in block.items() if k != "crc"}
    return zlib.crc32(
        json.dumps(body, sort_keys=True, separators=(",", ":")).encode()
    )


def build_provenance(
    platform: str,
    device_count: int,
    knobs: dict | None = None,
    repo_dir: str | None = None,
) -> dict:
    """The CRC'd provenance block for one measurement run."""
    block = {
        "version": PROVENANCE_VERSION,
        "platform": str(platform),
        "device_count": int(device_count),
        "host_cpus": host_cpus(),
        "cpu_model": cpu_model(),
        "git_rev": git_rev(repo_dir),
        "python": "%d.%d" % sys.version_info[:2],
        "knobs": knobs if knobs is not None else knob_set(),
    }
    block["crc"] = provenance_crc(block)
    return block


def verify(block) -> bool:
    """True iff the block has every required field and its CRC matches."""
    if not isinstance(block, dict):
        return False
    if any(f not in block for f in REQUIRED_FIELDS):
        return False
    try:
        return int(block["crc"]) == provenance_crc(block)
    except (TypeError, ValueError):
        return False


def _model_slug(model: str) -> str:
    """Compact, stable token for the cpu model inside a marker."""
    slug = "".join(c if c.isalnum() else "-" for c in model.lower())
    while "--" in slug:
        slug = slug.replace("--", "-")
    return slug.strip("-")[:24] or "unknown-cpu"


def platform_marker(block: dict) -> str:
    """The comparability key bench_report gates on: two rounds are only
    diffed when their markers are EQUAL. Platform + device count + cpu
    count + cpu model — a different box, a lost core, or a chip window
    each produce a different marker."""
    return "{}/dev{}/cpus{}/{}".format(
        block.get("platform", "?"),
        block.get("device_count", "?"),
        block.get("host_cpus", "?"),
        _model_slug(str(block.get("cpu_model", ""))),
    )


def register_build_gauges(
    scope, platform: str = "cpu", device_count: int = 0
) -> None:
    """Export the regime as ``ratelimit.build.*`` gauges (host_cpus,
    device_count, platform_id, git_rev_hash) on whatever scope the
    caller serves /metrics from. Fleet note: stats/fleet.py merges these
    by MAX, not sum — every member reports the same box, and a summed
    host_cpus would invent cores."""
    build = scope.scope("build")
    build.gauge("host_cpus").set(host_cpus())
    build.gauge("device_count").set(int(device_count))
    build.gauge("platform_id").set(PLATFORM_IDS.get(platform, -1))
    build.gauge("git_rev_hash").set(rev_hash(git_rev()))
