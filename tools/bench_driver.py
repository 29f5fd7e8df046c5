"""Hardware-gated bench driver: probe → arm → staged run → harvest.

Probe the hardware, run only the stages that hardware can actually
witness, bound every stage with a subprocess timeout that kills the whole
descendant tree, and harvest evidence in one pass — the answer to the
regime question every BENCH round since r07 has tripped over: **what can
this box prove?** A 1-core
box running the FRONTEND_PROCS sweep produces numbers that look like a
scaling regression and are actually just the scheduler time-slicing one
core (BENCH_r11/r13 carry that caveat as prose). The fix is structural:

  * ``probe_hardware()`` detects host_cpus, JAX platform, and device
    count in a subprocess (a wedged device stack can't hang the driver);
  * ``arm_tiers()`` maps that onto the tier matrix — multi-process tiers
    (service_mp / cluster_scale / failover_blip / fleet_saturation /
    fed_divergence) arm
    only when ``host_cpus > 1``, device tiers (pallas slab, device
    sketch, multichip mesh) only when a chip window is open — and every
    un-armed tier is recorded **skipped-with-reason**, never as a
    misleading number;
  * ``cpu_affinity_plan()`` pins each spawned process to its own CPU
    slice when arming succeeds, so "procs=4" means four cores, not four
    names for one core;
  * the staged runner executes bench.py / the
    fleet-saturation tier under per-stage timeouts and harvests the last
    complete JSON line, validated by tools/bench_lint.py before it is
    allowed to become a BENCH_r*.json.

The ``--fleet`` mode is the distributed-load tier: it boots the real
FRONTEND_PROCS fleet (cmd/service_cmd.py — N frontend processes +
device owner + master aggregator), saturates it with tools/loadgen.py
(M driver processes, each its own GIL, merged client-side histograms),
and pairs the client view with the server-side fleet scrape
(``GET /metrics?fleet=1`` via stats/fleet.py). On a 1-core box it emits
the skipped-with-reason artifact instead — the acceptance shape.

The ``--fed-divergence`` mode is the global-quota-federation tier
(cluster/federation.py): two in-process cluster coordinators exchange
quota shares over real sockets under skewed closed-loop load, a mid-run
partition cuts the link both ways, and the artifact reports the measured
global overshoot against the share-ledger bound (overshoot ≤ reclaimed
unsettled tokens ≤ shares outstanding at the cut). On a 1-core box it
emits the skipped-with-reason artifact instead.

Usage:
    python -m tools.bench_driver [--out BENCH_rNN.json] [--budget S]
    python -m tools.bench_driver --fleet [--out FLEET_rNN.json]
    python -m tools.bench_driver --fed-divergence [--out FED_rNN.json]
    python -m tools.bench_driver --probe-only   # print hw + arming matrix
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from api_ratelimit_tpu.utils import provenance

# ---------------------------------------------------------------------------
# hardware probe


# Ask jax in a child process and only trust the LAST line — library
# banners mentioning "tpu" must not arm device tiers.
PROBE_SRC = (
    "import jax; d = jax.devices();"
    "print(d[0].platform, len(d))"
)


def probe_hardware(timeout_s: float = 90.0) -> dict:
    """Detect the regime: host_cpus (affinity mask), JAX platform, and
    device count. The device probe runs in a subprocess so a wedged
    device stack times out here instead of hanging the driver; BENCH_PLATFORM
    short-circuits it the same way it short-circuits bench.py's own
    resolve_platform (forced runs must not pay a probe)."""
    hw = {
        "host_cpus": provenance.host_cpus(),
        "platform": "cpu",
        "device_count": 1,
        "probe": "",
    }
    forced = os.environ.get("BENCH_PLATFORM", "").strip().lower()
    if forced:
        hw["platform"] = forced
        hw["probe"] = "forced by BENCH_PLATFORM"
        return hw
    try:
        out = subprocess.run(
            [sys.executable, "-c", PROBE_SRC],
            cwd=REPO,
            timeout=timeout_s,
            capture_output=True,
            text=True,
        )
        lines = [ln.strip() for ln in out.stdout.splitlines() if ln.strip()]
        parts = lines[-1].split() if lines else []
        if out.returncode == 0 and len(parts) == 2 and parts[1].isdigit():
            hw["platform"] = parts[0]
            hw["device_count"] = int(parts[1])
            hw["probe"] = "subprocess probe ok"
        else:
            hw["probe"] = f"probe rc={out.returncode}; defaulting to cpu"
    except (OSError, subprocess.SubprocessError) as e:
        hw["probe"] = f"probe failed ({type(e).__name__}); defaulting to cpu"
    return hw


# ---------------------------------------------------------------------------
# tier arming

# Requirements a tier must meet before its number means anything.
# min_host_cpus=2 marks the multi-PROCESS tiers: on one core the procs
# time-slice and the sweep measures the scheduler, not the architecture.
# platform="tpu" marks the tiers that only exist on a real chip (the
# interpret-mode Pallas fallback validates shapes, not throughput).
# Device tiers: sharded arms on EITHER devices>=2 (real mesh) OR
# host_cpus>=2 (virtual CPU mesh in a subprocess — shape validation
# needs a second core to not starve the tier sweep above it).
TIER_REQUIREMENTS: dict = {
    "service_mp": {"min_host_cpus": 2},
    "cluster_scale": {"min_host_cpus": 2},
    "failover_blip": {"min_host_cpus": 2},
    "fleet_saturation": {"min_host_cpus": 2},
    "fed_divergence": {"min_host_cpus": 2},
    "sharded": {"min_host_cpus": 2, "or_min_devices": 2},
    # the victim tier is host RAM + numpy on the dispatch path: the
    # overload differential is meaningful on any box, so the tier always
    # arms — it is in the matrix so the artifact records that it RAN
    # (bench_lint's claim-honesty rules key off configs.keyspace_overload)
    "keyspace_overload": {},
    # routed-batching / hot-tier A/B: the padding-waste and false_over
    # columns are exact on any box (host-side routing + differential
    # fuzz), so the tier always arms — the rate columns only mean
    # parallel throughput on tpu+>=2 devices, where the tier's multichip
    # sub-key records that it ran on real chips (it rides the same
    # hardware gate as multichip_mesh)
    "sharded_zipf": {},
    "pallas_slab": {"platform": "tpu"},
    "device_sketch": {"platform": "tpu"},
    "multichip_mesh": {"platform": "tpu", "min_devices": 2},
}


def arm_tiers(hw: dict, force: str | None = None) -> dict:
    """Map probed hardware onto the tier matrix. Returns an ordered
    ``{tier: {"armed": bool, "reason": str}}`` — the reason string is
    part of the artifact contract (skipped tiers carry it verbatim), so
    it always names the failed requirement with the observed value,
    e.g. ``"host_cpus=1 < 2 (multi-process tier needs real cores)"``.

    ``force`` (the BENCH_ARM env knob) is "all" or a CSV of tier names:
    forced tiers arm regardless of hardware, with the force recorded as
    the reason — a forced run is visibly a forced run."""
    forced = set()
    if force:
        forced = (
            set(TIER_REQUIREMENTS)
            if force.strip().lower() == "all"
            else {t.strip() for t in force.split(",") if t.strip()}
        )
    cpus = int(hw.get("host_cpus", 1))
    devs = int(hw.get("device_count", 1))
    platform = str(hw.get("platform", "cpu"))
    out: dict = {}
    for tier, req in TIER_REQUIREMENTS.items():
        if tier in forced:
            out[tier] = {"armed": True, "reason": "forced by BENCH_ARM"}
            continue
        reasons = []
        min_cpus = req.get("min_host_cpus")
        if min_cpus and cpus < min_cpus:
            reasons.append(
                f"host_cpus={cpus} < {min_cpus} "
                f"(multi-process tier needs real cores)"
            )
        want = req.get("platform")
        if want and platform != want:
            reasons.append(f"platform={platform} != {want} (no chip window)")
        min_devs = req.get("min_devices")
        if min_devs and devs < min_devs:
            reasons.append(f"device_count={devs} < {min_devs}")
        or_devs = req.get("or_min_devices")
        if reasons and or_devs and devs >= or_devs:
            reasons = []  # the device path satisfies the tier on its own
        if reasons:
            out[tier] = {"armed": False, "reason": "; ".join(reasons)}
        else:
            out[tier] = {
                "armed": True,
                "reason": (
                    f"host_cpus={cpus} devices={devs} platform={platform}"
                ),
            }
    return out


# ---------------------------------------------------------------------------
# CPU affinity

AFFINITY_ENV = "BENCH_CPU_AFFINITY"


def cpu_affinity_plan(host_cpus: int, procs: int) -> list | None:
    """Partition the CPU inventory round-robin across ``procs`` spawned
    processes: ``[[0, 2], [1, 3]]`` for 4 cpus / 2 procs. Returns None
    when the box cannot give each process at least part of a distinct
    core story (host_cpus < 2) — pinning everything to cpu 0 would just
    add syscalls to the time-slicing the skip-reason already names."""
    if host_cpus < 2 or procs < 1:
        return None
    try:
        inventory = sorted(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        inventory = list(range(host_cpus))
    inventory = inventory[:host_cpus] or list(range(host_cpus))
    plan: list = [[] for _ in range(procs)]
    for i, cpu in enumerate(inventory):
        plan[i % procs].append(cpu)
    # more procs than cpus: wrap so every proc gets a pin (2 procs on
    # cpu 0 is still better than 2 procs floating over both cores while
    # 2 are pinned)
    for i in range(len(inventory), procs):
        plan[i] = plan[i % len(inventory)][:]
    return plan


def affinity_env(cpus) -> str:
    """Render one process's CPU slice for the child-side env knob."""
    return ",".join(str(c) for c in cpus)


def apply_affinity_from_env(env_var: str = AFFINITY_ENV) -> bool:
    """Child-side: pin this process to the CPU set named in ``env_var``
    (comma-separated ids). Returns True when a pin was applied. Invalid
    or unsupported masks are ignored — affinity is an arming refinement,
    never a reason a measurement child dies."""
    spec = os.environ.get(env_var, "").strip()
    if not spec:
        return False
    try:
        cpus = {int(c) for c in spec.split(",") if c.strip()}
        if cpus:
            os.sched_setaffinity(0, cpus)
            return True
    except (AttributeError, ValueError, OSError):
        pass
    return False


# ---------------------------------------------------------------------------
# staged subprocess machinery


def log(msg: str, prefix: str = "bench_driver") -> None:
    print(f"[{prefix} {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def descendants(root: int) -> list:
    """All live PIDs whose parent chain reaches `root` (/proc walk).

    killpg alone is not enough here: intermediate wrapper processes can
    re-group children, so a timed-out stage's grandchildren (bench
    sidecar workers, fleet frontends, pytest children) may sit in a
    different process group while still holding the device runtime."""
    ppid: dict = {}
    for ent in os.listdir("/proc"):
        if not ent.isdigit():
            continue
        try:
            with open(f"/proc/{ent}/stat") as f:
                ppid[int(ent)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
    out, frontier = [], {root}
    while frontier:
        nxt = {p for p, pp in ppid.items() if pp in frontier and p not in out}
        out.extend(nxt)
        frontier = nxt
    return out


def kill_tree(pid: int) -> None:
    # Snapshot descendants BEFORE killing: the moment the direct child
    # dies, its children reparent to init and the PPID walk can no
    # longer find them.
    victims = descendants(pid)
    try:
        os.killpg(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    for p in victims + descendants(pid):
        try:
            os.kill(p, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def run_stage(
    name: str,
    argv: list,
    timeout_s: float,
    marker: str,
    env: dict | None = None,
    log_path: str | None = None,
    log_prefix: str = "bench_driver",
) -> str:
    """One bounded stage: rc + marker classified into
    "ok" | "fail" | "timeout" | "fallback" (rc==0 WITHOUT the marker —
    the tool silently downscaled onto a fallback path, which is a
    window/arming problem, not success). Output appends to ``log_path``
    and the marker search is scoped to the bytes THIS run appended, so a
    marker left by a previous run never satisfies this one."""
    log(f"stage {name}: start (timeout {timeout_s:.0f}s)", log_prefix)
    if log_path is None:
        log_path = f"/tmp/chip_{name}.log"
    if env is None:
        env = dict(os.environ)
    offset = os.path.getsize(log_path) if os.path.exists(log_path) else 0
    with open(log_path, "ab") as lf:
        lf.write(f"\n===== {time.ctime()} =====\n".encode())
        lf.flush()
        try:
            # New session so a timeout can kill grandchildren too — an
            # orphan holding the device runtime would wedge every later
            # probe in this driver.
            proc = subprocess.Popen(
                argv,
                cwd=REPO,
                stdout=lf,
                stderr=subprocess.STDOUT,
                env=env,
                start_new_session=True,
            )
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            kill_tree(proc.pid)
            proc.wait()
            log(
                f"stage {name}: TIMEOUT after {timeout_s:.0f}s (log {log_path})",
                log_prefix,
            )
            return "timeout"
    with open(log_path, "rb") as f:
        f.seek(offset)
        appended = f.read().decode(errors="replace")
    ok = rc == 0 and marker in appended
    log(
        f"stage {name}: rc={rc} marker_found={marker in appended} "
        f"(log {log_path})",
        log_prefix,
    )
    if ok:
        return "ok"
    return "fail" if rc != 0 else "fallback"


def harvest_json_line(log_path: str, offset: int = 0) -> dict | None:
    """The artifact contract bench.py has honored since round 3: the last
    COMPLETE (newline-terminated) JSON line on stdout is the artifact."""
    try:
        with open(log_path, "rb") as f:
            f.seek(offset)
            text = f.read().decode(errors="replace")
    except OSError:
        return None
    complete = text[: text.rfind("\n") + 1]
    lines = [ln for ln in complete.splitlines() if ln.startswith("{")]
    for line in reversed(lines):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


# ---------------------------------------------------------------------------
# fleet saturation tier (--fleet)


def _http_ok(url: str, timeout: float = 1.0) -> bool:
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:  # noqa: S310
            return resp.status == 200
    except Exception:  # noqa: BLE001 - readiness poll
        return False


_FLEET_CONFIG = """\
domain: bench
descriptors:
  - key: api_key
    rate_limit:
      unit: second
      requests_per_unit: 1000000
"""


def run_fleet_saturation(hw: dict, arming: dict, budget_s: float) -> dict:
    """The distributed-load tier: boot the real FRONTEND_PROCS fleet,
    saturate it with tools/loadgen.py driver processes, and pair the
    merged client histograms with the server-side fleet scrape deltas.
    Armed only when host_cpus > 1 — the caller records the skip."""
    from tools import loadgen

    procs = int(os.environ.get("BENCH_FLEET_PROCS", "0") or 0) or min(
        4, max(2, hw["host_cpus"] // 2)
    )
    drivers = int(os.environ.get("BENCH_FLEET_DRIVERS", "2"))
    duration = float(os.environ.get("BENCH_FLEET_SECONDS", "5"))
    port = int(os.environ.get("BENCH_FLEET_PORT", "18080"))
    debug_port = int(os.environ.get("BENCH_FLEET_DEBUG_PORT", "16070"))
    result: dict = {
        "frontend_procs": procs,
        "driver_procs": drivers,
        "duration_s": duration,
    }
    td = tempfile.mkdtemp(prefix="bench-fleet-")
    config_dir = os.path.join(td, "current", "ratelimit", "config")
    os.makedirs(config_dir)
    with open(os.path.join(config_dir, "bench.yaml"), "w") as f:
        f.write(_FLEET_CONFIG)
    env = dict(os.environ)
    env.update(
        {
            "FRONTEND_PROCS": str(procs),
            "RUNTIME_ROOT": os.path.join(td, "current"),
            "RUNTIME_SUBDIRECTORY": "ratelimit",
            "BACKEND_TYPE": "tpu",
            "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
            "PORT": str(port),
            "GRPC_PORT": str(port + 1),
            "DEBUG_PORT": str(debug_port),
            "USE_STATSD": "false",
            "SIDECAR_SOCKET": os.path.join(td, "owner.sock"),
            "LOG_LEVEL": "WARNING",
        }
    )
    env.pop("XLA_FLAGS", None)
    # pin each frontend worker + the owner to its own CPU slice: the
    # master passes the slice down via the env knob the Runner applies
    plan = cpu_affinity_plan(hw["host_cpus"], procs + 1)
    if plan is not None:
        env["BENCH_CPU_AFFINITY_PLAN"] = "|".join(
            affinity_env(cpus) for cpus in plan
        )
    master = subprocess.Popen(
        [sys.executable, "-m", "api_ratelimit_tpu.cmd.service_cmd"],
        env=env,
        cwd=REPO,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT,
        start_new_session=True,
    )
    try:
        deadline = time.monotonic() + min(budget_s * 0.5, 180.0)
        while not _http_ok(f"http://127.0.0.1:{port}/healthcheck"):
            if master.poll() is not None:
                raise RuntimeError(
                    f"fleet master exited rc={master.returncode} before ready"
                )
            if time.monotonic() > deadline:
                raise TimeoutError("fleet never became healthy")
            time.sleep(0.25)
        fleet_url = f"http://127.0.0.1:{debug_port}/metrics?fleet=1"
        report = loadgen.run_distributed(
            url=f"http://127.0.0.1:{port}/json",
            procs=drivers,
            threads=int(os.environ.get("BENCH_FLEET_THREADS", "4")),
            duration_s=duration,
            domain="bench",
            key="api_key",
            n_keys=int(os.environ.get("BENCH_FLEET_KEYS", "512")),
            fleet_metrics_url=fleet_url,
        )
        result.update(report)
    finally:
        kill_tree(master.pid)
        master.wait()
    return result


# ---------------------------------------------------------------------------
# federation divergence tier (--fed-divergence)


def run_fed_divergence(hw: dict, arming: dict, budget_s: float) -> dict:
    """The bounded-divergence tier (cluster/federation.py): two in-process
    cluster coordinators exchange shares over real TCP sockets under
    closed-loop Zipf-skewed load, a mid-run partition cuts the WAN both
    ways, and the measured global overshoot is checked against the
    share-ledger bound — overshoot ≤ reclaimed unsettled tokens ≤ the
    shares outstanding at the partition instant. Armed only when
    host_cpus > 1 (two live closed loops plus two settle pumps on one
    core measure the scheduler, not the algebra)."""
    import random
    import socket
    import threading

    from api_ratelimit_tpu.backends import sidecar as sc
    from api_ratelimit_tpu.cluster.federation import FederationCoordinator
    from api_ratelimit_tpu.utils.timeutil import RealTimeSource

    duration = min(
        float(os.environ.get("BENCH_FED_SECONDS", "6")), budget_s * 0.8
    )
    n_keys = int(os.environ.get("BENCH_FED_KEYS", "48"))
    limit = int(os.environ.get("BENCH_FED_LIMIT", "400"))

    # two listeners bound first (the membership map needs the ports),
    # coordinators second, accept loops last
    socks = {}
    for name in ("east", "west"):
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(32)
        srv.settimeout(0.2)
        socks[name] = srv
    peers = {
        name: f"tcp://127.0.0.1:{srv.getsockname()[1]}"
        for name, srv in socks.items()
    }
    coords = {
        name: FederationCoordinator(
            name,
            peers,
            time_source=RealTimeSource(),
            share_min=8,
            share_max=256,
            settle_interval_ms=50.0,
            max_lag_ms=250.0,
            share_ttl_ms=600.0,
        )
        for name in socks
    }
    partitioned = threading.Event()
    closing = threading.Event()

    def accept_loop(name: str) -> None:
        srv = socks[name]
        while not closing.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            if partitioned.is_set():
                conn.close()  # the WAN cut: peers get connection reset
                continue

            def serve(c=conn, coord=coords[name]) -> None:
                try:
                    need = sc._HDR.size
                    buf = b""
                    while len(buf) < need:
                        chunk = c.recv(need - len(buf))
                        if not chunk:
                            return
                        buf += chunk
                    coord.serve_exchange(c)
                except Exception:  # noqa: BLE001 - chaos by design
                    pass
                finally:
                    c.close()

            threading.Thread(target=serve, daemon=True).start()

    threads = [
        threading.Thread(target=accept_loop, args=(n,), daemon=True)
        for n in socks
    ]
    for t in threads:
        t.start()

    # Zipf-ish key popularity, skewed differently per region: east's hot
    # head is west's tail — the cross-borrow traffic that makes shares
    # flow both directions
    rng = random.Random(1234)
    now = int(time.time())
    window = (now // 3600) * 3600
    deadline = window + 3600
    keys = [((rng.getrandbits(63) << 1) | (i & 1), window) for i in range(n_keys)]
    weights = [1.0 / (i + 1) for i in range(n_keys)]
    east_keys = random.Random(7).choices(keys, weights=weights, k=4096)
    west_keys = random.Random(11).choices(
        keys, weights=list(reversed(weights)), k=4096
    )

    admitted: dict = {k: 0 for k in keys}
    denied = {"east": 0, "west": 0}
    lock = threading.Lock()
    t_end = time.monotonic() + duration
    t_cut = time.monotonic() + duration * 0.35
    t_heal = time.monotonic() + duration * 0.75
    bound_at_cut = {"tokens": -1}

    def drive(name: str, plan: list) -> None:
        coord = coords[name]
        i = 0
        next_pump = 0.0
        while time.monotonic() < t_end:
            fp, win = plan[i % len(plan)]
            i += 1
            ok = coord.consume(fp, win, limit, 1, deadline=deadline)
            with lock:
                if ok:
                    admitted[(fp, win)] += 1
                else:
                    denied[name] += 1
            t = time.monotonic()
            if t >= next_pump:
                next_pump = t + 0.05
                try:
                    coord.pump()
                except Exception:  # noqa: BLE001 - partition chaos
                    pass
            if i % 64 == 0:
                time.sleep(0.001)

    drivers = [
        threading.Thread(target=drive, args=("east", east_keys), daemon=True),
        threading.Thread(target=drive, args=("west", west_keys), daemon=True),
    ]
    for d in drivers:
        d.start()
    healed_at = None
    while time.monotonic() < t_end:
        t = time.monotonic()
        if not partitioned.is_set() and t >= t_cut and t < t_heal:
            bound_at_cut["tokens"] = sum(
                c.outstanding_tokens() for c in coords.values()
            )
            partitioned.set()
            log(
                f"fed tier: partition cut — outstanding "
                f"{bound_at_cut['tokens']} tokens"
            )
        if partitioned.is_set() and t >= t_heal:
            partitioned.clear()
            healed_at = t
            log("fed tier: partition healed")
        time.sleep(0.02)
    for d in drivers:
        d.join(timeout=10.0)
    # post-run settle passes so the healed ledgers reconverge
    for _ in range(6):
        for c in coords.values():
            try:
                c.pump()
            except Exception:  # noqa: BLE001
                pass
        time.sleep(0.06)
    closing.set()
    for srv in socks.values():
        srv.close()
    for c in coords.values():
        c.close()

    overshoot = sum(max(0, n - limit) for n in admitted.values())
    reclaimed = sum(c.reclaimed_tokens_total for c in coords.values())
    stale = sum(c.stale_epoch_rejected_total for c in coords.values())
    result = {
        "clusters": sorted(coords),
        "keys": n_keys,
        "per_key_limit": limit,
        "duration_s": duration,
        "admitted_total": sum(admitted.values()),
        "denied_total": dict(denied),
        "overshoot_tokens": overshoot,
        "reclaimed_tokens": reclaimed,
        "outstanding_at_partition": bound_at_cut["tokens"],
        # the ledger invariant (cluster/federation.py): every admitted
        # token beyond the limit traces to a reclaimed-but-still-spendable
        # share — idle TTL reclaims count too, so the bound is reclaimed
        # tokens, with the partition-instant outstanding as context
        "within_bound": overshoot <= reclaimed,
        "stale_epoch_rejected": stale,
        "healed": healed_at is not None,
        "settles": {
            n: c.settles_total for n, c in coords.items()
        },
        "grants": {n: c.grants_total for n, c in coords.items()},
        "degraded_during_run": {
            n: bool(c.degraded or c.exchange_errors_total)
            for n, c in coords.items()
        },
    }
    return result


# ---------------------------------------------------------------------------
# driver CLI


def _stamp(doc: dict, hw: dict, arming: dict) -> dict:
    doc["provenance"] = provenance.build_provenance(
        hw["platform"], hw["device_count"]
    )
    doc["tiers"] = arming
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", help="write the harvested artifact here")
    ap.add_argument(
        "--budget", type=float,
        default=float(os.environ.get("BENCH_BUDGET_S", "480")),
    )
    ap.add_argument(
        "--probe-only", action="store_true",
        help="print the hardware + arming matrix and exit",
    )
    ap.add_argument(
        "--fleet", action="store_true",
        help="run the fleet-saturation tier instead of bench.py",
    )
    ap.add_argument(
        "--fed-divergence", action="store_true",
        help="run the federation bounded-divergence tier instead of "
        "bench.py",
    )
    args = ap.parse_args(argv)

    hw = probe_hardware()
    arming = arm_tiers(hw, force=os.environ.get("BENCH_ARM"))
    log(f"hardware: {hw}")
    for tier, st in arming.items():
        log(f"tier {tier}: {'ARMED' if st['armed'] else 'skip'} — {st['reason']}")

    if args.probe_only:
        print(json.dumps({"hardware": hw, "tiers": arming}, indent=2))
        return 0

    if args.fed_divergence:
        doc: dict = {"metric": "fed_divergence", "hardware": hw}
        st = arming["fed_divergence"]
        if not st["armed"]:
            doc["fed_divergence"] = {"skipped": st["reason"]}
        else:
            try:
                doc["fed_divergence"] = run_fed_divergence(
                    hw, arming, args.budget
                )
            except Exception as e:  # noqa: BLE001 - artifact must land
                doc["fed_divergence"] = {"error": str(e)[-300:]}
        _stamp(doc, hw, arming)
        line = json.dumps(doc)
        print(line, flush=True)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0

    if args.fleet:
        doc: dict = {"metric": "fleet_saturation", "hardware": hw}
        st = arming["fleet_saturation"]
        if not st["armed"]:
            doc["fleet_saturation"] = {"skipped": st["reason"]}
        else:
            try:
                doc["fleet_saturation"] = run_fleet_saturation(
                    hw, arming, args.budget
                )
            except Exception as e:  # noqa: BLE001 - artifact must land
                doc["fleet_saturation"] = {"error": str(e)[-300:]}
        _stamp(doc, hw, arming)
        line = json.dumps(doc)
        print(line, flush=True)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0

    # staged bench.py run: the stage timeout must exceed
    # bench's own forced-emit horizon (budget + 120s watchdog + init
    # slack) or we SIGKILL the tree before the watchdog lands the line
    env = dict(os.environ)
    env.setdefault("BENCH_PLATFORM", hw["platform"])
    env.setdefault("BENCH_BUDGET_S", str(int(args.budget)))
    stage_log = os.path.join(
        tempfile.gettempdir(), "bench_driver_bench.log"
    )
    offset = os.path.getsize(stage_log) if os.path.exists(stage_log) else 0
    outcome = run_stage(
        "bench",
        [sys.executable, "bench.py"],
        args.budget + 300.0,
        '"configs"',
        env=env,
        log_path=stage_log,
    )
    doc = harvest_json_line(stage_log, offset)
    if doc is None:
        log(f"no artifact line harvested (outcome={outcome})")
        return 1
    if "provenance" not in doc:
        # belt-and-braces: bench.py stamps its own block; a legacy bench
        # on this path still leaves the driver's stamp
        _stamp(doc, hw, arming)
    from tools import bench_lint

    findings = bench_lint.lint_artifact(doc)
    for f_ in findings:
        log(f"bench_lint: {f_}")
    line = json.dumps(doc)
    print(line, flush=True)
    if args.out and not findings:
        with open(args.out, "w") as f:
            f.write(line + "\n")
        log(f"artifact written to {args.out}")
    elif args.out:
        log(f"artifact NOT written to {args.out}: {len(findings)} lint finding(s)")
        return 1
    return 0 if outcome in ("ok", "fallback") and not findings else 1


if __name__ == "__main__":
    sys.exit(main())
