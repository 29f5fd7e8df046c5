#!/usr/bin/env python3
"""Chip smoke: the served decision path on a TPU, checked against the
exact fixed-window oracle.

    python chip_smoke.py             # one chip: served + engine phases
    python chip_smoke.py --chips 4   # the hash-sharded mesh path only

One process holds the chip: the server runs on threads of this process and
is driven over localhost gRPC and HTTP. The script never falls back to the
host: with no TPU visible it exits non-zero before running anything, and
any failed phase ends it with a non-zero exit. The last line of stdout is
the only result, printed after every phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The other lines only inform (counts, compile seconds, device memory); no
line is a measured rate.

Phases (one chip):
  * served — boots the real Runner with default settings (BACKEND_TYPE=tpu,
    the 2^22-slot slab, boot precompile, Pallas, hot-key sketch) on a
    temporary rule directory, sends ~2,000 requests over ~200 keys through
    gRPC and /json from client threads that own disjoint keys, and compares
    every descriptor's verdict with the occurrence-rank oracle.
  * engine — 4M decisions in 65,536-wide launches over a 1M-key Zipf(1.1)
    universe (limit 4, one window) on the same 2^22-slot engine. ~18% of the
    slab fills, so no set overflows; the one loss left is the slab's
    documented fail-open one (two new keys of a batch that pick the same
    way of a set: the loser's count is not persisted, counted as a drop).
    So no verdict may be a false OVER_LIMIT, and the false OKs may not
    exceed `limit` per counted lossy event (see check_parity).

--chips N runs the engine phase's stream on TPU_MESH_DEVICES=N instead:
the routed arm and the compact shard_map arm with the hot-key tier off
(each held to the oracle as above, and to each other verdict for
verdict), then the routed arm with the tier on, which may over-admit by
the bound README.md states in "Sharded dispatch & hot-key tier".
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

# deployment sizes (module constants, not options: the driver runs the
# script with no arguments, and tests call the phase functions with their
# own sizes)
SERVED_REQUESTS = 2000
SERVED_KEYS = 200
SERVED_THREADS = 4
ENGINE_DECISIONS = 1 << 22
ENGINE_KEYS = 1 << 20
ENGINE_BATCH = 1 << 16
ENGINE_LIMIT = 4
ENGINE_DIVIDER = 3600
ZIPF_EXPONENT = 1.1
# drains of the mesh engine's host top-K (the stats-flush cadence in a
# server) every this many launches: often enough that Zipf head keys pass
# the promotion threshold mid-window
HOT_DRAIN_EVERY = 8

SERVED_RULES = {
    # domain -> (descriptor keys, requests per hour)
    "smoke_user": (("user",), 5),
    "smoke_route": (("tenant", "path"), 3),
    "smoke_ip": (("ip",), 10),
}
_CODE_OK, _CODE_OVER = 1, 2


def info(phase: str, **fields) -> None:
    """One informational line (never the result line)."""
    print(f"chip_smoke {phase}: {json.dumps(fields, sort_keys=True)}", flush=True)


def _rule_yaml(domain: str, keys: tuple, per_hour: int) -> str:
    lines = [f"domain: {domain}", "descriptors:"]
    indent = "  "
    for i, key in enumerate(keys):
        lines.append(f"{indent}- key: {key}")
        if i + 1 < len(keys):
            lines.append(f"{indent}  descriptors:")
            indent += "    "
    lines += [
        f"{indent}  rate_limit:",
        f"{indent}    unit: hour",
        f"{indent}    requests_per_unit: {per_hour}",
    ]
    return "\n".join(lines) + "\n"


def _served_keys(n_keys: int) -> list:
    """[(domain, ((key, value), ...), limit)] — n_keys distinct descriptors
    spread over the rule domains."""
    domains = sorted(SERVED_RULES)
    out = []
    for k in range(n_keys):
        domain = domains[k % len(domains)]
        keys, limit = SERVED_RULES[domain]
        entries = tuple((key, f"{key}-{k}") for key in keys)
        out.append((domain, entries, limit))
    return out


def _wait_out_hour_boundary(margin_s: float) -> None:
    """The oracle counts per hour window: start the phase with at least
    margin_s left in the current hour."""
    left = 3600 - time.time() % 3600
    if left < margin_s:
        time.sleep(left + 1.0)


def served_phase(device, env: dict, n_requests: int, n_keys: int,
                 n_threads: int, seed: int, window_margin_s: float = 120.0) -> dict:
    """Boot the Runner in-process as cmd/service_cmd.py does and check every
    served verdict against the occurrence-rank oracle. The traffic starts
    with at least window_margin_s left in the hour window."""
    import random
    import urllib.error
    import urllib.request

    import grpc

    from api_ratelimit_tpu.ops import native
    from api_ratelimit_tpu.pb import rls_grpc, rls_v3
    from api_ratelimit_tpu.runner import Runner
    from api_ratelimit_tpu.settings import new_settings

    keys = _served_keys(n_keys)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        config_dir = os.path.join(tmp, "current", "ratelimit", "config")
        os.makedirs(config_dir)
        for domain, (desc_keys, limit) in SERVED_RULES.items():
            with open(os.path.join(config_dir, f"{domain}.yaml"), "w") as f:
                f.write(_rule_yaml(domain, desc_keys, limit))
        settings = new_settings({
            **env,
            "RUNTIME_ROOT": os.path.join(tmp, "current"),
            "RUNTIME_SUBDIRECTORY": "ratelimit",
            "PORT": "0",
            "GRPC_PORT": "0",
            "DEBUG_PORT": "0",
            "USE_STATSD": "false",
        })
        t0 = time.perf_counter()
        runner = Runner(settings)
        try:
            runner.run_background()
            boot_s = time.perf_counter() - t0
            http = f"http://localhost:{runner.server.http_port}"
            with urllib.request.urlopen(f"{http}/healthcheck", timeout=30) as r:
                if r.status != 200:
                    raise RuntimeError(f"/healthcheck answered {r.status}")
            channel = grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            )
            stub = rls_grpc.RateLimitServiceV3Stub(channel)
            results: list = [None] * n_threads
            _wait_out_hour_boundary(window_margin_s)
            hour = int(time.time() // 3600)

            def client(t: int) -> None:
                # each thread owns keys k % n_threads == t, so per-key order
                # (what the oracle ranks) is this thread's send order
                rng = random.Random(seed * 1000 + t)
                mine = [k for k in range(n_keys) if k % n_threads == t]
                by_domain: dict = {}
                for k in mine:
                    by_domain.setdefault(keys[k][0], []).append(k)
                domains = sorted(by_domain)
                seen: dict = {}
                disagree = over = verdicts = 0
                for i in range(n_requests // n_threads):
                    domain = domains[rng.randrange(len(domains))]
                    pool = by_domain[domain]
                    picked = rng.sample(pool, min(len(pool), rng.randint(1, 3)))
                    want = []
                    for k in picked:
                        want.append(
                            _CODE_OVER if seen.get(k, 0) >= keys[k][2] else _CODE_OK
                        )
                        seen[k] = seen.get(k, 0) + 1
                    if i % 2 == 0:
                        req = rls_v3.RateLimitRequest(domain=domain)
                        for k in picked:
                            d = req.descriptors.add()
                            for key, value in keys[k][1]:
                                d.entries.add(key=key, value=value)
                        got = [s.code for s in stub.ShouldRateLimit(req, timeout=60).statuses]
                    else:
                        body = json.dumps({
                            "domain": domain,
                            "descriptors": [
                                {"entries": [{"key": a, "value": b} for a, b in keys[k][1]]}
                                for k in picked
                            ],
                        }).encode()
                        post = urllib.request.Request(
                            f"{http}/json", data=body,
                            headers={"Content-Type": "application/json"},
                        )
                        try:
                            with urllib.request.urlopen(post, timeout=60) as r:
                                doc = json.loads(r.read())
                        except urllib.error.HTTPError as e:
                            if e.code != 429:
                                raise
                            doc = json.loads(e.read())
                        names = {"OK": _CODE_OK, "OVER_LIMIT": _CODE_OVER}
                        got = [names.get(s.get("code"), 0) for s in doc["statuses"]]
                    if len(got) != len(want):
                        raise RuntimeError(f"{len(got)} statuses for {len(want)} descriptors")
                    verdicts += len(want)
                    disagree += sum(g != w for g, w in zip(got, want))
                    over += sum(g == _CODE_OVER for g in got)
                results[t] = (verdicts, disagree, over)

            threads = [
                threading.Thread(target=client, args=(t,), name=f"smoke-client-{t}")
                for t in range(n_threads)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=1800)
            channel.close()
            if any(th.is_alive() for th in threads) or None in results:
                raise RuntimeError("a client thread failed or hung (see above)")
            if int(time.time() // 3600) != hour:
                raise RuntimeError("the served phase crossed an hour window")
            engine = runner.limiter.engine
            use_pallas = engine._use_pallas
            precompiled = set(engine.precompiled)
            buckets = engine._buckets
        finally:
            runner.stop()
            if runner.limiter is not None:
                runner.limiter.close()
    verdicts = sum(r[0] for r in results)
    disagreements = sum(r[1] for r in results)
    over = sum(r[2] for r in results)
    out = {
        "requests": n_requests // n_threads * n_threads,
        "verdicts": verdicts,
        "disagreements": disagreements,
        "over_limit": over,
        "use_pallas": use_pallas,
        "precompiled": len(precompiled),
        "boot_s": boot_s,
    }
    info("served", **out)
    if disagreements:
        raise AssertionError(f"served: {disagreements} verdicts disagree with the oracle")
    if not over:
        raise AssertionError("served: no OVER_LIMIT verdict; the limits never bit")
    if use_pallas != (device.platform == "tpu"):
        raise AssertionError(f"served: engine use_pallas={use_pallas} on {device.platform}")
    want_shapes = {(b, dt) for b in buckets for dt in ("uint8", "uint16", "uint32")}
    if precompiled != want_shapes:
        raise AssertionError(f"served: precompile covered {sorted(precompiled)}")
    if not native.available():
        raise AssertionError("served: the native host codec fell back to Python")
    return out


def zipf_stream(n_decisions: int, n_keys: int, seed: int):
    """(ids, fp_lo, fp_hi): Zipf(1.1) key ids over an n_keys universe and
    their fingerprints — fp_lo is a bijection of the id (murmur3 fmix32 of
    id+1, never 0), so distinct ids are distinct keys."""
    import numpy as np

    def fmix32(x):
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x85EBCA6B)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(0xC2B2AE35)
        return x ^ (x >> np.uint32(16))

    rng = np.random.default_rng(seed)
    ids = ((rng.zipf(ZIPF_EXPONENT, n_decisions) - 1) % n_keys).astype(np.uint32)
    return ids, fmix32(ids + np.uint32(1)), fmix32(ids ^ np.uint32(0xA5A5A5A5))


def _drive(engine, fp_lo, fp_hi, batch: int, on_launch=None):
    """Post-increment counters for the whole stream, `batch` rows per
    launch (limit ENGINE_LIMIT, one ENGINE_DIVIDER window)."""
    import numpy as np

    n = fp_lo.shape[0]
    afters = np.empty(n, dtype=np.uint32)
    block = np.empty((6, batch), dtype=np.uint32)
    block[2], block[3], block[4], block[5] = 1, ENGINE_LIMIT, ENGINE_DIVIDER, 0
    for launch, start in enumerate(range(0, n, batch)):
        m = min(batch, n - start)
        block[0, :m], block[1, :m] = fp_lo[start:start + m], fp_hi[start:start + m]
        afters[start:start + m] = engine.submit_rows(block[:, :m].copy())
        if on_launch is not None:
            on_launch(launch)
    return afters


def _engine_cache(env: dict, now: int):
    """A TpuRateLimitCache built the way the server builds it
    (runner.create_limiter) on a clock pinned to `now` (one window)."""
    from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
    from api_ratelimit_tpu.runner import create_limiter
    from api_ratelimit_tpu.settings import new_settings
    from api_ratelimit_tpu.stats.sinks import NullSink
    from api_ratelimit_tpu.stats.store import Store
    from api_ratelimit_tpu.utils.timeutil import FakeTimeSource

    base = BaseRateLimiter(FakeTimeSource(now=now))
    return create_limiter(new_settings(env), base, Store(NullSink()))


def check_parity(phase: str, ids, afters, health: dict,
                 extra_allowance: int = 0) -> dict:
    """Verdicts (after > limit) against the occurrence-rank oracle, held
    to the slab's fail-open contract: no false OVER_LIMIT, and at most
    `limit` false OKs per device-counted lossy event (a lost or evicted
    counter restarts at 0, so its key admits at most `limit` again) plus
    extra_allowance (the hot tier's split-quota slack)."""
    import numpy as np

    from api_ratelimit_tpu.testing.oracle import occurrence_rank

    want_over = occurrence_rank(ids) + 1 > ENGINE_LIMIT
    got_over = afters > ENGINE_LIMIT
    lossy = int(health["drops"]) + int(health["evictions_live"])
    out = {
        "decisions": int(ids.shape[0]),
        "over_limit": int(got_over.sum()),
        "disagreements": int(np.sum(got_over != want_over)),
        "false_over": int(np.sum(got_over & ~want_over)),
        "false_ok": int(np.sum(~got_over & want_over)),
        "drops": int(health["drops"]),
        "evictions_live": int(health["evictions_live"]),
        "false_ok_bound": ENGINE_LIMIT * lossy + extra_allowance,
        "occupancy": health["occupancy"],
    }
    if out["false_over"]:
        raise AssertionError(f"{phase}: {out['false_over']} false OVER_LIMIT verdicts")
    if out["false_ok"] > out["false_ok_bound"]:
        raise AssertionError(
            f"{phase}: {out['false_ok']} false OKs exceed the "
            f"{out['false_ok_bound']} that counted lossy events explain"
        )
    return out


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def engine_phase(device, env: dict, n_decisions: int, n_keys: int,
                 batch: int, seed: int, now: int = 1_700_000_000) -> dict:
    """The deployment-size stream through one engine, every verdict checked
    against the occurrence-rank oracle (check_parity)."""
    import numpy as np

    ids, fp_lo, fp_hi = zipf_stream(n_decisions, n_keys, seed)
    t0 = time.perf_counter()
    cache = _engine_cache(env, now)
    try:
        build_s = time.perf_counter() - t0
        engine = cache.engine
        t0 = time.perf_counter()
        afters = _drive(engine, fp_lo, fp_hi, batch)
        drive_s = time.perf_counter() - t0
        health = engine.health_snapshot()
        use_pallas = engine._use_pallas
    finally:
        cache.close()
    out = {
        "distinct_keys": int(np.unique(ids).size),
        "use_pallas": use_pallas,
        "build_and_precompile_s": build_s,
        "drive_s_incl_compile": drive_s,
        "peak_bytes_in_use": _peak_bytes(device),
    }
    try:
        out.update(check_parity("engine", ids, afters, health))
    finally:
        info("engine", **out)
    if use_pallas != (device.platform == "tpu"):
        raise AssertionError(f"engine: use_pallas={use_pallas} on {device.platform}")
    return out


def _shard_placement(engine, n_chips: int, shard_bytes: int) -> list:
    """Device of each per-device table; raises unless there are n_chips
    distinct ones, each showing at least its shard in peak memory."""
    sharded = engine._engine
    if sharded._tables is not None:  # routed arm: one table per device
        arrays = sharded._tables
    else:  # compact arm: one P(axis, None) global array
        arrays = [s.data for s in sharded._state.addressable_shards]
    devices = [next(iter(a.devices())) for a in arrays]
    if len(set(devices)) != n_chips:
        raise AssertionError(f"mesh: tables on {devices}, want {n_chips} devices")
    for d in devices:
        peak = _peak_bytes(d)
        if peak is not None and peak < shard_bytes:
            raise AssertionError(f"mesh: {d} peak {peak} B < its {shard_bytes} B shard")
    return devices


def mesh_phase(devices, env: dict, n_decisions: int, n_keys: int,
               batch: int, seed: int, now: int = 1_700_000_000) -> dict:
    """The engine stream on TPU_MESH_DEVICES=len(devices): routed and compact
    arms against the oracle and each other, then the hot-key tier's bound."""
    import numpy as np

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH

    n_chips = len(devices)
    ids, fp_lo, fp_hi = zipf_stream(n_decisions, n_keys, seed)
    mesh_env = {**env, "TPU_MESH_DEVICES": str(n_chips)}
    slots = int(mesh_env.get("TPU_SLAB_SLOTS") or 1 << 22)
    shard_bytes = slots // n_chips * ROW_WIDTH * 4
    out: dict = {"chips": n_chips}
    afters_by_arm = {}
    for arm, routed in (("routed", "true"), ("compact", "false")):
        t0 = time.perf_counter()
        cache = _engine_cache(
            {**mesh_env, "SHARD_ROUTED_BATCHING": routed, "HOT_TIER_ENABLED": "false"},
            now,
        )
        try:
            placed = _shard_placement(cache.engine, n_chips, shard_bytes)
            afters = _drive(cache.engine, fp_lo, fp_hi, batch)
            health = cache.engine.health_snapshot()
        finally:
            cache.close()
        afters_by_arm[arm] = afters
        out[arm] = {
            "devices": [str(d) for d in placed],
            "peak_bytes_in_use": [_peak_bytes(d) for d in placed],
            "s_incl_compile": time.perf_counter() - t0,
        }
        try:
            out[arm].update(check_parity(f"mesh {arm}", ids, afters, health))
        finally:
            info(f"mesh-{arm}", **out[arm])
    # byte-identical arms (SHARD_ROUTED_BATCHING's rollback contract): the
    # same counters, hence the same verdicts, lossy events included
    out["arms_disagree"] = int(np.sum(
        (afters_by_arm["routed"] > ENGINE_LIMIT) != (afters_by_arm["compact"] > ENGINE_LIMIT)
    ))
    out["arms_counters_differ"] = int(np.sum(afters_by_arm["routed"] != afters_by_arm["compact"]))
    info("mesh-arms", disagree=out["arms_disagree"], counters_differ=out["arms_counters_differ"])
    if out["arms_disagree"] or out["arms_counters_differ"]:
        raise AssertionError("mesh: routed and compact arms differ")

    # hot tier on (the default): each promotion may over-admit its key by
    # (K-1)*ceil(limit/K) in the window it lands in (K = salt ways = all
    # shards), on top of the lossy-event allowance; never a false OVER_LIMIT
    t0 = time.perf_counter()
    cache = _engine_cache({**mesh_env, "SHARD_ROUTED_BATCHING": "true"}, now)
    try:
        engine = cache.engine

        def drain(launch: int) -> None:
            if (launch + 1) % HOT_DRAIN_EVERY == 0:
                engine.drain_hotkeys()

        afters = _drive(engine, fp_lo, fp_hi, batch, on_launch=drain)
        health = engine.health_snapshot()
        hot = engine.shard_routing_snapshot()["hot_tier"]
    finally:
        cache.close()
    slack = (hot["salt_ways"] - 1) * -(-ENGINE_LIMIT // hot["salt_ways"])
    out["hot_tier"] = {
        "promotions": hot["promotions"],
        "salt_ways": hot["salt_ways"],
        "slack_per_promotion": slack,
        "s_incl_compile": time.perf_counter() - t0,
    }
    try:
        out["hot_tier"].update(check_parity(
            "mesh hot tier", ids, afters, health,
            extra_allowance=hot["promotions"] * slack,
        ))
    finally:
        info("mesh-hot-tier", **out["hot_tier"])
    if not hot["promotions"]:
        raise AssertionError("mesh hot tier: no key was promoted")
    if hot["salt_ways"] != n_chips:
        raise AssertionError(f"mesh hot tier: {hot['salt_ways']} salt ways, want {n_chips}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: served + engine phases; N>1: the N-chip mesh path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the compile cache is settled before anything compiles
    from api_ratelimit_tpu.utils.jaxsetup import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU visible (jax found {len(devices)} "
              f"{dev.platform} device(s)); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 1
    info("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), compile_cache=cache_dir)
    env = dict(os.environ)
    t0 = time.perf_counter()
    if args.chips == 1:
        served_phase(dev, env, SERVED_REQUESTS, SERVED_KEYS, SERVED_THREADS, args.seed)
        engine_phase(dev, env, ENGINE_DECISIONS, ENGINE_KEYS, ENGINE_BATCH, args.seed)
    else:
        mesh_phase(devices[:args.chips], env, ENGINE_DECISIONS, ENGINE_KEYS,
                   ENGINE_BATCH, args.seed)
    info("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
