#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this machine holds.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result (one JSON object); the numbers the
comparison read, each beside its limit, are the last lines of stderr and
the last key of the result. With no TPU visible, or fewer chips than the
cell asks for, it exits non-zero and prints no result: there is no CPU
fallback. --control <name> puts a control (the reference with one stated
guarantee broken) in the program's place for the comparison; the driver's
runs never pass it.

    python3 benchmark/run.py --sweep <name> --points a,b,c --seconds <s> --out <file>

runs one boot of the cell's configuration at several offered rates (open
loop) or concurrencies (closed loop) and writes the curve to <file>; a
sweep is not a cell and prints no result line."""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def info(tag: str, **fields) -> None:
    """An informational stderr line (never the result)."""
    print(f"bench {tag}: {json.dumps(fields, sort_keys=True, default=str)}",
          file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--sweep", default=None, help="cell whose configuration to sweep")
    ap.add_argument("--points", default="", help="offered rates or concurrencies")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if not (args.workload or args.sweep):
        ap.error("--workload or --sweep is required")
    return args


def setup_jax():
    """The persistent compile cache at the checkout's fixed path, for the
    program too (it takes JAX_COMPILATION_CACHE_DIR when set), and every
    program cached however fast it compiled. The cache is unbounded: a
    size limit (JAX_COMPILATION_CACHE_MAX_SIZE, which a machine may set)
    turns on an eviction scan that fails every write once one entry lacks
    its access-time file, and every run then compiles."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def _result_line(rc, res, bench, spec, device_info):
    metrics = {}
    if rc.trace:
        ctx = dict(res.layer_ctx)
        ctx["trace"] = res.trace
        ctx["peaks"] = device_info["peaks"]
        for m in spec.per_layer_for(bench, rc.cell):
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.end_to_end_for(bench, rc.cell):
            metrics[m["name"]] = {"value": res.end_to_end[m["name"]], "unit": m["unit"]}
    dev = dict(device_info["device"])
    dev["memory_peak_bytes"] = res.memory_peak_bytes
    line = {
        "correct": all(v <= lim for v, lim in res.checks.values()),
        "attempted": res.attempted,
        "failed": res.failed,
        "metrics": metrics,
        "device": dev,
    }
    if rc.trace:
        dev["busy_s"] = res.trace.busy_s
        dev["window_s"] = res.trace.window_s
        line["breakdown"] = res.trace.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in res.checks.items()}
    return line


def main(argv=None, require_chip: bool = True) -> int:
    args = _parse(argv)
    sys.path.insert(0, CHECKOUT)
    sys.path.insert(0, BENCH_DIR)
    from harness import device, spec
    from harness.run_context import RunContext

    bench = spec.benchmark(CHECKOUT)
    name = args.workload or args.sweep
    cell = spec.cell_entry(bench, name)
    wl = spec.workload(name)
    cfg = spec.config(wl["config"])
    jax = setup_jax()
    if require_chip:
        try:
            devices = device.require_tpu(int(cell["chips"]))
        except device.NoChip as e:
            print(f"bench: {e}; nothing was run", file=sys.stderr)
            return 2
    else:
        devices = jax.devices()[: int(cell["chips"])]
    dinfo = {"device": device.describe(devices)}
    dinfo["peaks"] = device.peaks(dinfo["device"]["kind"]) if require_chip else None
    info("device", cache=CACHE_DIR, at_s=time.perf_counter() - T_PROCESS, **dinfo["device"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        rc = RunContext(
            cell=name, wl=wl, cfg=cfg, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), control=args.control, t_process=T_PROCESS,
            devices=devices, compiles=device.CompileCounter(), trace_dir=trace_dir,
            info=info,
        )
        entry = importlib.import_module(f"harness.entries.{wl['entry']}")
        if args.sweep:
            curve = entry.sweep(rc, [float(p) for p in args.points.split(",") if p])
            with open(args.out, "w") as f:
                for point in curve:
                    f.write(json.dumps(point) + "\n")
            info("sweep", out=args.out, points=len(curve))
            return 0
        res = entry.run(rc)
        line = _result_line(rc, res, bench, spec, dinfo)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
