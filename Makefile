# Build / test / run workflow for the TPU-native rate-limit framework.
# Mirrors the reference's Make targets (Makefile:76-125) mapped onto this
# stack: the "compile" step builds the native host codec (C++ -> .so) and
# generates protos; serving is `python -m api_ratelimit_tpu.cmd.service_cmd`.

PY ?= python

.PHONY: all compile native proto tests tests_unit tests_artifact \
        tests_chaos tests_cluster tests_hotkeys tests_integration \
        tests_mp tests_with_redis tests_tpu \
        bench bench_smoke bench_fleet bench_report bench_lint \
        chaos_campaign chaos_smoke \
        profile serve check_config clean docker_image docker_tests

all: compile

compile: native proto

# one build rule (ops/native.py): the .so is named by the source's hash,
# so a build from other sources is never loaded
native:
	$(PY) -c "from api_ratelimit_tpu.ops import native; assert native.available()"

# Proto messages are compiled with the protoc binary (grpcio-tools is not
# required); gRPC service glue is hand-written in api_ratelimit_tpu/pb/.
proto:
	./proto/gen.sh

# Unit + hermetic integration tests on a virtual 8-device CPU mesh
# (tests/conftest.py forces JAX_PLATFORMS=cpu; the reference's equivalent
# is `go test -race ./...`, Makefile:83-85). The native codec builds
# FIRST so the suite exercises the real pack/scatter/fingerprint path —
# tests/test_native.py then asserts availability, so a broken build fails
# the tier instead of silently riding the pure-Python fallback.
# Includes the slab differential-fuzz campaign (tests/test_slab_fuzz.py)
# at its small default example count; crank SLAB_FUZZ_EXAMPLES (e.g.
# `SLAB_FUZZ_EXAMPLES=2000 make tests_unit`) for the full idle-hardware
# campaign.
tests_unit: native
	$(PY) -m pytest tests/ -x -q -m "not slow"

# The multi-second bench-subprocess tests (artifact discipline): isolated
# from tests_unit so a wall-clock hiccup can't -x-fail the whole stage.
tests_artifact:
	$(PY) -m pytest tests/ -q -m slow

# Multi-process frontend tier (shm submit rings + the FRONTEND_PROCS
# fleet; backends/shm_ring.py, cmd/service_cmd.py): real frontend
# PROCESSES publishing into one device owner over shared memory,
# including the SIGKILL-mid-publish chaos story and the full
# service_cmd fleet boot. Slower than tests_unit (it boots worker
# interpreters), so it gets its own CI entry point.
tests_mp: native
	$(PY) -m pytest tests/ -v -m mp

# Failure-injection + failover chaos tier: the degradation ladder, the
# warm-standby replication suite, the SIGKILL-the-primary acceptance
# scenario (zero failed requests, bounded overshoot, split-brain fence),
# and the partitioned-cluster suite (kill-one-partition, live reshard)
# get their own CI entry point so the failover story can gate a release
# independently of the full unit tier.
tests_chaos:
	$(PY) -m pytest tests/test_chaos.py tests/test_replication.py \
	  tests/test_warm_restart.py tests/test_cluster.py -v -m "not slow"

# Partitioned device-owner cluster tier (cluster/; `cluster` marker):
# K-partition routing parity, the STATUS_STALE_MAP wire fence, live
# resharding K=2->4 under closed-loop load, per-partition standby
# promotion, and the PARTITIONS=1 byte-identical rollback arm.
tests_cluster:
	$(PY) -m pytest tests/test_cluster.py -v -m cluster

# Heavy-hitter sketch tier (ops/sketch.py; `hotkeys` marker): the
# kernel-vs-SketchOracle differential fuzz (space-saving error bound,
# bit-exact planes; crank HOTKEY_FUZZ_EXAMPLES for the idle-hardware
# campaign), drain/debug/journey plumbing, lease pre-seeding, and the
# HOTKEYS_ENABLED=false byte-identical rollback arm. Runs inside
# tests_unit too ("not slow" includes it) — this entry point exists for
# fast iteration on the sketch alone.
tests_hotkeys: native
	$(PY) -m pytest tests/ -q -m hotkeys

# Full suite; the in-process fake Redis/Memcache servers play the role the
# reference's local redis fleet plays (Makefile:91-125).
tests: tests_unit tests_artifact

# Integration tier against REAL redis-server processes (single, auth,
# sentinel, 3-node cluster, full runner) — the analog of the reference's
# local redis fleet (Makefile:91-125, Dockerfile.integration). Requires
# redis-server on PATH; the module skips itself otherwise.
tests_with_redis:
	$(PY) -m pytest tests/test_real_redis.py -v -rs

# On-hardware tier: the Pallas kernel differential suite COMPILED through
# Mosaic on a real TPU (interpret mode certifies semantics; this certifies
# the lowering). Run on a chip-attached host; skips cleanly elsewhere.
tests_tpu:
	TPU_TESTS=1 $(PY) -m pytest tests/test_pallas_tpu.py -v

# Decisions/sec + p99 benchmark; prints one JSON line. Run on TPU.
bench:
	$(PY) bench.py

# One-tier smoke run of the bench harness (~2 min on any box): the flat
# tier at a tiny request budget, every other tier recorded
# skipped-with-reason, provenance stamped and bench_lint-validated. The
# recipe the tier-1 bench_smoke test drives (tests/test_bench.py).
bench_smoke:
	BENCH_TIERS=flat_per_second BENCH_BUDGET_S=90 \
	  BENCH_SERVICE_REQUESTS=200 BENCH_PLATFORM=cpu $(PY) bench.py

# Hardware-gated fleet saturation run (tools/bench_driver.py): probe the
# box, arm what the hardware supports (multi-process tiers need real
# cores; Pallas tiers need a chip window), boot the FRONTEND_PROCS fleet
# with per-process CPU slices, drive it with the distributed closed-loop
# load generator (tools/loadgen.py) and pair client histograms with the
# server-side fleet scrape. Un-armed tiers land in the artifact as
# skipped-with-reason — a 1-core box still emits a valid artifact.
bench_fleet:
	$(PY) -m tools.bench_driver --fleet --out BENCH_fleet.json

# Provenance-gated perf trajectory across BENCH_r*.json rounds: deltas
# only within one hardware regime; cross-regime rows print an explicit
# refusal instead of a percentage (tools/bench_report.py).
bench_report:
	$(PY) -m tools.bench_report

# Artifact-discipline linter for bench JSON (tools/bench_lint.py), the
# bench sibling of metrics_lint: CRC-verified provenance, every skip has
# a reason, rate-claiming tiers carry non-empty stage evidence. Tier-1
# runs it over the checked-in rounds via tests/test_bench_lint.py.
bench_lint:
	$(PY) -m tools.bench_lint BENCH_r16.json

# Seeded chaos campaign (chaos/, tools/chaos_campaign.py): 10 seeds of
# the composed nemesis schedule (fault sites, role kills, clock skew,
# network partition, snapshot corruption) over the closed-loop workload,
# the admission-ledger bound checked per seed, the provenance-stamped
# CHAOS_rNN.json artifact written and immediately bench_lint-validated.
# Deterministic: same seed -> byte-identical timeline and verdict.
chaos_campaign:
	JAX_PLATFORMS=cpu $(PY) tools/chaos_campaign.py \
	  --seeds 10 --steps 120 --out CHAOS_r19.json
	$(PY) -m tools.bench_lint CHAOS_r19.json

# Two-seed chaos smoke (~2 s): a short composed sweep plus one replay
# that proves byte-identical determinism — the fast pre-commit arm of
# chaos_campaign. Exit 1 on any violation or replay mismatch.
chaos_smoke:
	JAX_PLATFORMS=cpu $(PY) tools/chaos_campaign.py --seeds 2 --steps 30
	JAX_PLATFORMS=cpu $(PY) tools/chaos_campaign.py \
	  --seed 1 --steps 30 --replay

# Host-path profile: cProfile over the flat_per_second request loop
# (tools/hotpath_profile.py).
profile:
	$(PY) -m tools.hotpath_profile

# Local dev server with the example config on the TPU backend.
serve:
	RUNTIME_ROOT=examples/ratelimit RUNTIME_SUBDIRECTORY= \
	  RUNTIME_WATCH_ROOT=false USE_STATSD=false LOG_LEVEL=INFO \
	  $(PY) -m api_ratelimit_tpu.cmd.service_cmd

# Offline config linter (config_check_cmd, src/config_check_cmd/main.go).
check_config:
	$(PY) -m api_ratelimit_tpu.cmd.config_check_cmd -config_dir examples/ratelimit/config

docker_image:
	docker build -t api-ratelimit-tpu:latest .

# Containerized integration tier: bakes redis-server so the real-redis
# tests run anywhere (the reference's `make docker_tests`, Makefile:122-125
# + Dockerfile.integration).
docker_tests:
	docker build -f Dockerfile.integration -t api-ratelimit-tpu-itest .
	docker run --rm api-ratelimit-tpu-itest

clean:
	rm -rf api_ratelimit_tpu/_native build dist
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
