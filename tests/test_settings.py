"""Settings layer: env parsing with the reference's variable names
(src/settings/settings.go:10-48)."""

import pytest

from api_ratelimit_tpu.settings import Settings, new_settings


class TestSettings:
    def test_defaults(self):
        s = new_settings({})
        assert s.port == 8080
        assert s.grpc_port == 8081
        assert s.debug_port == 6070
        assert s.use_statsd is True
        assert s.runtime_path == "/srv/runtime_data/current"
        assert s.near_limit_ratio == pytest.approx(0.8)
        assert s.expiration_jitter_max_seconds == 300
        assert s.local_cache_size_in_bytes == 0
        assert s.backend_type == "tpu"

    def test_reference_env_names(self):
        # a nomad-style env block (nomad/apigw-ratelimit/common.hcl)
        s = new_settings(
            {
                "GRPC_PORT": "9484",
                "PORT": "9486",
                "DEBUG_PORT": "9485",
                "USE_STATSD": "false",
                "RUNTIME_ROOT": "/data/runtime",
                "RUNTIME_SUBDIRECTORY": "ratelimit",
                "RUNTIME_WATCH_ROOT": "false",
                "LOG_LEVEL": "debug",
                "MAX_SLEEPING_ROUTINES": "64",
                "LOCAL_CACHE_SIZE_IN_BYTES": "1000000",
                "NEAR_LIMIT_RATIO": "0.9",
                "EXPIRATION_JITTER_MAX_SECONDS": "0",
            }
        )
        assert s.grpc_port == 9484
        assert s.use_statsd is False
        assert s.runtime_subdirectory == "ratelimit"
        assert s.runtime_watch_root is False
        assert s.max_sleeping_routines == 64
        assert s.local_cache_size_in_bytes == 1_000_000
        assert s.near_limit_ratio == pytest.approx(0.9)
        assert s.expiration_jitter_max_seconds == 0

    def test_go_duration_strings(self):
        s = new_settings(
            {
                "REDIS_PIPELINE_WINDOW": "75us",
                "TPU_BATCH_WINDOW": "500us",
            }
        )
        assert s.redis_pipeline_window == pytest.approx(75e-6)
        assert s.tpu_batch_window == pytest.approx(500e-6)
        assert new_settings({"TPU_BATCH_WINDOW": "2ms"}).tpu_batch_window == (
            pytest.approx(2e-3)
        )

    def test_bad_value_raises(self):
        with pytest.raises(ValueError, match="GRPC_PORT"):
            new_settings({"GRPC_PORT": "not-a-port"})
        with pytest.raises(ValueError, match="USE_STATSD"):
            new_settings({"USE_STATSD": "maybe"})

    def test_empty_string_keeps_default(self):
        s = new_settings({"STATSD_HOST": ""})
        assert s.statsd_host == "localhost"

    def test_tpu_knobs(self):
        s = new_settings(
            {
                "BACKEND_TYPE": "tpu",
                "TPU_SLAB_SLOTS": "8388608",
                "TPU_BATCH_LIMIT": "32768",
                "TPU_MESH_DEVICES": "4",
                "TPU_USE_PALLAS": "false",
            }
        )
        assert s.tpu_slab_slots == 1 << 23
        assert s.tpu_batch_limit == 32768
        assert s.tpu_mesh_devices == 4
        assert s.tpu_use_pallas is False

    def test_hotpath_knobs(self):
        s = new_settings(
            {
                "TPU_PRECOMPILE": "false",
                "TPU_BUCKETS": "16,256,4096",
            }
        )
        assert s.tpu_precompile is False
        assert s.tpu_buckets == "16,256,4096"
        assert s.buckets() == (16, 256, 4096)

    def test_hotpath_defaults(self):
        s = Settings()
        assert s.tpu_precompile is True
        assert s.tpu_batch_window == 0.0  # direct mode is the default
        assert s.buckets() is None  # engine default ladder

    def test_removed_path_knobs_are_not_read(self):
        """DISPATCH_LOOP and HOST_FAST_PATH are gone: a deployment that
        still sets them boots on the one path of its mode, whatever the
        value."""
        s = new_settings(
            {
                "DISPATCH_LOOP": "sideways",
                "HOST_FAST_PATH": "false",
                "TPU_BATCH_WINDOW": "200us",
            }
        )
        assert not hasattr(s, "dispatch_loop")
        assert not hasattr(s, "host_fast_path")
        assert s.tpu_batch_window == pytest.approx(0.0002)

    def test_journey_knobs(self):
        s = new_settings(
            {
                "JOURNEY_RECORDER_ENABLED": "false",
                "JOURNEY_SLOW_MS": "25.5",
                "JOURNEY_RETAIN": "512",
                "JOURNEY_RING": "32",
            }
        )
        assert s.journey_recorder_enabled is False
        assert s.journey_slow_ms == pytest.approx(25.5)
        assert s.journey_retain == 512
        assert s.journey_ring == 32
        assert s.journey_config() == (False, 25.5, 512, 32)

    def test_journey_defaults(self):
        s = new_settings({})
        # recorder on, live-p99 slow threshold, bounded buffers
        assert s.journey_config() == (True, 0.0, 256, 64)
        assert s.tpu_profile_dir == ""  # /debug/profile disabled

    def test_journey_junk_fails_boot(self):
        with pytest.raises(ValueError, match="JOURNEY_SLOW_MS"):
            new_settings({"JOURNEY_SLOW_MS": "-1"}).journey_config()
        with pytest.raises(ValueError, match="JOURNEY_RETAIN"):
            new_settings({"JOURNEY_RETAIN": "0"}).journey_config()
        with pytest.raises(ValueError, match="JOURNEY_RING"):
            new_settings({"JOURNEY_RING": "-4"}).journey_config()
        # non-numeric junk fails at parse time, like every other knob
        with pytest.raises(ValueError, match="JOURNEY_RETAIN"):
            new_settings({"JOURNEY_RETAIN": "many"})
        with pytest.raises(ValueError, match="JOURNEY_RECORDER_ENABLED"):
            new_settings({"JOURNEY_RECORDER_ENABLED": "maybe"})

    def test_tpu_profile_dir_knob(self):
        s = new_settings({"TPU_PROFILE_DIR": "/var/tmp/tpu-traces"})
        assert s.tpu_profile_dir == "/var/tmp/tpu-traces"

    def test_buckets_junk_fails_boot(self):
        for junk in ("abc", "128,xyz", "0", "-8,128", ","):
            with pytest.raises(ValueError, match="TPU_BUCKETS"):
                new_settings({"TPU_BUCKETS": junk}).buckets()

    def test_buckets_sorted(self):
        assert new_settings({"TPU_BUCKETS": "4096,16"}).buckets() == (16, 4096)

    def test_dataclass_is_plain(self):
        assert Settings().port == 8080

    def test_metrics_knobs(self):
        s = new_settings(
            {
                "DEBUG_METRICS_ENABLED": "false",
                "METRICS_LATENCY_BUCKETS_MS": "5, 0.5,1,100",
            }
        )
        assert s.debug_metrics_enabled is False
        assert s.latency_buckets() == (0.5, 1.0, 5.0, 100.0)  # sorted
        # default: endpoint on, store-default ladder
        assert Settings().debug_metrics_enabled is True
        assert Settings().latency_buckets() is None

    def test_metrics_buckets_junk_raises(self):
        with pytest.raises(ValueError):
            new_settings(
                {"METRICS_LATENCY_BUCKETS_MS": "1,abc"}
            ).latency_buckets()
        with pytest.raises(ValueError):
            new_settings(
                {"METRICS_LATENCY_BUCKETS_MS": "-1,5"}
            ).latency_buckets()


class TestResilienceSettings:
    """The PR-2 resilience knobs: sidecar retry/deadline/breaker, the
    FAILURE_MODE_DENY ladder, and FAULT_INJECT parsing — junk must fail
    boot like a typo'd bucket ladder."""

    def test_sidecar_resilience_env_names(self):
        s = new_settings(
            {
                "SIDECAR_CONNECT_TIMEOUT": "250ms",
                "SIDECAR_RPC_DEADLINE": "2s",
                "SIDECAR_RETRIES": "4",
                "SIDECAR_RETRY_BACKOFF": "5ms",
                "SIDECAR_RETRY_BACKOFF_MAX": "100ms",
                "SIDECAR_BREAKER_THRESHOLD": "3",
                "SIDECAR_BREAKER_RESET": "500ms",
            }
        )
        assert s.sidecar_connect_timeout == pytest.approx(0.25)
        assert s.sidecar_rpc_deadline == pytest.approx(2.0)
        assert s.sidecar_retries == 4
        assert s.sidecar_retry_backoff == pytest.approx(5e-3)
        assert s.sidecar_retry_backoff_max == pytest.approx(0.1)
        assert s.sidecar_breaker_threshold == 3
        assert s.sidecar_breaker_reset == pytest.approx(0.5)

    def test_resilience_defaults(self):
        s = new_settings({})
        assert s.failure_mode() is None  # legacy raise-through
        assert s.fault_rules() == []
        assert s.sidecar_retries == 2
        assert s.sidecar_breaker_threshold == 5

    def test_failure_mode_ladder_values(self):
        # upstream boolean parity: true = deny-all, false = fail-open
        assert new_settings({"FAILURE_MODE_DENY": "true"}).failure_mode() == "deny"
        assert new_settings({"FAILURE_MODE_DENY": "deny"}).failure_mode() == "deny"
        assert (
            new_settings({"FAILURE_MODE_DENY": "false"}).failure_mode()
            == "allow"
        )
        assert (
            new_settings({"FAILURE_MODE_DENY": "allow"}).failure_mode()
            == "allow"
        )
        assert (
            new_settings({"FAILURE_MODE_DENY": "degraded"}).failure_mode()
            == "degraded"
        )

    def test_failure_mode_junk_raises(self):
        with pytest.raises(ValueError, match="FAILURE_MODE_DENY"):
            new_settings({"FAILURE_MODE_DENY": "maybe"}).failure_mode()

    def test_fault_inject_spec_parses(self):
        s = new_settings(
            {
                "FAULT_INJECT": (
                    "sidecar.submit:error:0.2,sidecar.submit:delay_ms:500"
                ),
                "FAULT_INJECT_SEED": "7",
            }
        )
        rules = s.fault_rules()
        assert [(r.site, r.kind, r.value) for r in rules] == [
            ("sidecar.submit", "error", 0.2),
            ("sidecar.submit", "delay_ms", 500.0),
        ]
        assert s.fault_inject_seed == 7

    def test_fault_inject_junk_fails_boot(self):
        for spec in (
            "sidecar.submit:error",  # missing value
            "sidecar.submit:explode:0.5",  # unknown kind
            "sidecar.submit:error:1.5",  # probability out of range
            "sidecar.submit:error:zero",  # non-numeric value
            "BadSite:error:0.5",  # site convention
            "sidecar.submit:delay_ms:-1",  # negative delay
        ):
            with pytest.raises(ValueError, match="FAULT_INJECT"):
                new_settings({"FAULT_INJECT": spec}).fault_rules()

    def test_snapshot_knob_env_names(self):
        s = new_settings(
            {
                "SLAB_SNAPSHOT_DIR": "/var/lib/ratelimit/snapshots",
                "SLAB_SNAPSHOT_INTERVAL_MS": "2500",
                "SLAB_SNAPSHOT_STALE_AFTER_MS": "30000",
            }
        )
        assert s.slab_snapshot_dir == "/var/lib/ratelimit/snapshots"
        assert s.slab_snapshot_interval_ms == pytest.approx(2500.0)
        assert s.slab_snapshot_stale_after_ms == pytest.approx(30000.0)
        assert s.snapshot_config() == (
            "/var/lib/ratelimit/snapshots",
            2500.0,
            30000.0,
        )

    def test_snapshot_defaults_disabled(self):
        s = new_settings({})
        directory, interval_ms, stale_ms = s.snapshot_config()
        assert directory == ""  # empty dir = warm restart off
        assert interval_ms == pytest.approx(10_000.0)
        # staleness defaults to three intervals
        assert stale_ms == pytest.approx(30_000.0)

    def test_snapshot_junk_fails_boot(self):
        with pytest.raises(ValueError, match="SLAB_SNAPSHOT_INTERVAL_MS"):
            new_settings(
                {"SLAB_SNAPSHOT_INTERVAL_MS": "0"}
            ).snapshot_config()
        with pytest.raises(ValueError, match="SLAB_SNAPSHOT_INTERVAL_MS"):
            new_settings(
                {"SLAB_SNAPSHOT_INTERVAL_MS": "-5"}
            ).snapshot_config()
        with pytest.raises(ValueError, match="SLAB_SNAPSHOT_STALE_AFTER_MS"):
            new_settings(
                {"SLAB_SNAPSHOT_STALE_AFTER_MS": "-1"}
            ).snapshot_config()
        # staleness tighter than the write cadence would flap the probe
        with pytest.raises(ValueError, match="SLAB_SNAPSHOT_STALE_AFTER_MS"):
            new_settings(
                {
                    "SLAB_SNAPSHOT_INTERVAL_MS": "10000",
                    "SLAB_SNAPSHOT_STALE_AFTER_MS": "500",
                }
            ).snapshot_config()
        # non-numeric junk fails at parse time, like every other knob
        with pytest.raises(ValueError, match="SLAB_SNAPSHOT_INTERVAL_MS"):
            new_settings({"SLAB_SNAPSHOT_INTERVAL_MS": "soon"})

    def test_snapshot_fault_sites_parse_from_env(self):
        s = new_settings(
            {
                "FAULT_INJECT": (
                    "snapshot.write:torn_write:1.0,snapshot.load:corrupt:0.5"
                )
            }
        )
        rules = s.fault_rules()
        assert [(r.site, r.kind) for r in rules] == [
            ("snapshot.write", "torn_write"),
            ("snapshot.load", "corrupt"),
        ]


class TestLeaseSettings:
    def test_defaults_are_the_rollback_arm(self):
        s = Settings()
        assert s.lease_enabled is False  # byte-identical pre-lease pipeline
        assert s.lease_min == 8
        assert s.lease_max == 1024
        assert s.lease_ttl_fraction == pytest.approx(0.25)
        assert s.lease_near_limit_ratio == pytest.approx(0.9)
        assert s.lease_config() == (False, 8, 1024, 0.25, 0.9)

    def test_env_parsing(self):
        s = new_settings(
            {
                "LEASE_ENABLED": "true",
                "LEASE_MIN": "2",
                "LEASE_MAX": "256",
                "LEASE_TTL_FRACTION": "0.5",
                "LEASE_NEAR_LIMIT_RATIO": "0.8",
            }
        )
        assert s.lease_config() == (True, 2, 256, 0.5, 0.8)

    def test_junk_rejected(self):
        with pytest.raises(ValueError, match="LEASE_ENABLED"):
            new_settings({"LEASE_ENABLED": "sideways"})
        with pytest.raises(ValueError, match="LEASE_MIN"):
            new_settings({"LEASE_MIN": "four"})
        with pytest.raises(ValueError, match="LEASE_MIN"):
            new_settings({"LEASE_MIN": "0"}).lease_config()
        with pytest.raises(ValueError, match="LEASE_MAX"):
            new_settings({"LEASE_MIN": "64", "LEASE_MAX": "8"}).lease_config()
        with pytest.raises(ValueError, match="LEASE_TTL_FRACTION"):
            new_settings({"LEASE_TTL_FRACTION": "0"}).lease_config()
        with pytest.raises(ValueError, match="LEASE_TTL_FRACTION"):
            new_settings({"LEASE_TTL_FRACTION": "1.5"}).lease_config()
        with pytest.raises(ValueError, match="LEASE_NEAR_LIMIT_RATIO"):
            new_settings(
                {"LEASE_NEAR_LIMIT_RATIO": "-0.1"}
            ).lease_config()


class TestHotkeySettings:
    """HOTKEYS_* knobs (ops/sketch.py heavy-hitter telemetry), following
    the lease_config() junk-rejection pattern."""

    def test_defaults(self):
        s = Settings()
        assert s.hotkeys_enabled is True
        assert s.hotkey_k == 16
        assert s.hotkey_lanes == 128
        assert s.hotkey_config() == (True, 16, 128)

    def test_env_parsing(self):
        s = new_settings(
            {
                "HOTKEYS_ENABLED": "false",
                "HOTKEY_K": "8",
                "HOTKEY_LANES": "64",
            }
        )
        assert s.hotkey_config() == (False, 8, 64)

    def test_junk_rejected(self):
        with pytest.raises(ValueError, match="HOTKEYS_ENABLED"):
            new_settings({"HOTKEYS_ENABLED": "sideways"})
        with pytest.raises(ValueError, match="HOTKEY_K"):
            new_settings({"HOTKEY_K": "many"})
        with pytest.raises(ValueError, match="HOTKEY_K"):
            new_settings({"HOTKEY_K": "0"}).hotkey_config()
        with pytest.raises(ValueError, match="HOTKEY_LANES"):
            new_settings({"HOTKEY_LANES": "100"}).hotkey_config()
        with pytest.raises(ValueError, match="HOTKEY_LANES"):
            new_settings({"HOTKEY_LANES": "-128"}).hotkey_config()
        with pytest.raises(ValueError, match="HOTKEY_K"):
            new_settings(
                {"HOTKEY_K": "64", "HOTKEY_LANES": "32"}
            ).hotkey_config()


class TestVictimSettings:
    """VICTIM_* knobs (backends/victim.py host-RAM victim tier),
    following the lease_config() junk-rejection pattern: a typo'd bound
    must fail the boot, never silently become 'no tier' (live-eviction
    counter loss would come back without a trace)."""

    def test_defaults(self):
        s = Settings()
        assert s.victim_tier_enabled is False
        assert s.victim_max_rows == 1 << 20
        assert s.victim_watermark == 0.85
        assert s.victim_config() == (False, 1 << 20, 0.85)

    def test_env_parsing(self):
        s = new_settings(
            {
                "VICTIM_TIER_ENABLED": "true",
                "VICTIM_MAX_ROWS": "4096",
                "VICTIM_WATERMARK": "0.5",
            }
        )
        assert s.victim_config() == (True, 4096, 0.5)

    def test_junk_rejected(self):
        with pytest.raises(ValueError, match="VICTIM_TIER_ENABLED"):
            new_settings({"VICTIM_TIER_ENABLED": "sideways"})
        with pytest.raises(ValueError, match="VICTIM_MAX_ROWS"):
            new_settings({"VICTIM_MAX_ROWS": "many"})
        with pytest.raises(ValueError, match="VICTIM_MAX_ROWS"):
            new_settings({"VICTIM_MAX_ROWS": "0"}).victim_config()
        with pytest.raises(ValueError, match="VICTIM_MAX_ROWS"):
            new_settings({"VICTIM_MAX_ROWS": "-1"}).victim_config()
        with pytest.raises(ValueError, match="VICTIM_WATERMARK"):
            new_settings({"VICTIM_WATERMARK": "1.5"}).victim_config()
        with pytest.raises(ValueError, match="VICTIM_WATERMARK"):
            new_settings({"VICTIM_WATERMARK": "0"}).victim_config()


class TestReplicationSettings:
    """SIDECAR_ADDRS / REPL_* knobs (persist/replication.py), following
    the lease_config() junk-rejection pattern: a typo'd knob fails the
    boot, never silently becomes a different redundancy posture."""

    def test_defaults_disable_replication(self):
        s = new_settings({})
        assert s.repl_config() == ("", 100.0, 500.0)
        assert s.sidecar_addresses() == [s.sidecar_socket]
        assert s.repl_peer_address() is None

    def test_addrs_parse_and_order_preserved(self):
        s = new_settings(
            {"SIDECAR_ADDRS": " /a.sock , tcp://h:9000 ,tls://x:1 "}
        )
        assert s.sidecar_addresses() == [
            "/a.sock",
            "tcp://h:9000",
            "tls://x:1",
        ]

    def test_peer_is_first_entry_that_is_not_self(self):
        s = new_settings(
            {
                "SIDECAR_SOCKET": "/b.sock",
                "SIDECAR_ADDRS": "/a.sock,/b.sock",
            }
        )
        assert s.repl_peer_address() == "/a.sock"

    def test_roles_accepted(self):
        for role in ("primary", "standby", "auto"):
            s = new_settings(
                {
                    "REPL_ROLE": role,
                    "SIDECAR_SOCKET": "/me.sock",
                    "SIDECAR_ADDRS": "/me.sock,/peer.sock",
                }
            )
            assert s.repl_config()[0] == role

    def test_junk_role_fails_boot(self):
        s = new_settings({"REPL_ROLE": "leader"})
        with pytest.raises(ValueError, match="REPL_ROLE"):
            s.repl_config()

    def test_junk_interval_fails_boot(self):
        s = new_settings({"REPL_INTERVAL_MS": "0"})
        with pytest.raises(ValueError, match="REPL_INTERVAL_MS"):
            s.repl_config()
        with pytest.raises(ValueError, match="REPL_INTERVAL_MS"):
            new_settings({"REPL_INTERVAL_MS": "soon"})

    def test_max_lag_below_interval_fails_boot(self):
        s = new_settings(
            {"REPL_INTERVAL_MS": "100", "REPL_MAX_LAG_MS": "50"}
        )
        with pytest.raises(ValueError, match="REPL_MAX_LAG_MS"):
            s.repl_config()

    def test_max_lag_defaults_to_five_intervals(self):
        s = new_settings({"REPL_INTERVAL_MS": "40"})
        assert s.repl_config() == ("", 40.0, 200.0)

    def test_standby_without_peer_fails_boot(self):
        s = new_settings(
            {
                "REPL_ROLE": "standby",
                "SIDECAR_SOCKET": "/me.sock",
                "SIDECAR_ADDRS": "/me.sock",
            }
        )
        with pytest.raises(ValueError, match="peer"):
            s.repl_config()

    def test_malformed_addr_entry_fails_boot(self):
        s = new_settings({"SIDECAR_ADDRS": "tcp://nohost"})
        with pytest.raises(ValueError, match="SIDECAR_ADDRS"):
            s.sidecar_addresses()


class TestShmRingSettings:
    """SHM_RINGS / FRONTEND_PROCS knobs (backends/shm_ring.py +
    cmd/service_cmd.py): derivation rules for the control socket and the
    junk-fails-boot discipline every other knob follows."""

    def test_defaults(self):
        s = Settings()
        assert s.shm_rings is True
        assert s.frontend_procs == 1  # single-process legacy boot
        assert s.shm_ring_rows == 4096
        assert s.frontend_procs_count() == 1
        assert s.shm_ring_rows_count() == 4096

    def test_env_parsing(self):
        s = new_settings(
            {
                "SHM_RINGS": "false",
                "SHM_CONTROL_SOCK": "/tmp/ctl.sock",
                "SHM_RING_ROWS": "8192",
                "FRONTEND_PROCS": "4",
            }
        )
        assert s.shm_rings is False
        assert s.shm_control_sock == "/tmp/ctl.sock"
        assert s.shm_ring_rows_count() == 8192
        assert s.frontend_procs_count() == 4

    def test_control_path_derivation(self):
        s = Settings()
        s.sidecar_socket = "/run/rl/owner.sock"
        assert s.shm_control_path() == "/run/rl/owner.sock.shmctl"
        # explicit path wins
        s.shm_control_sock = "/tmp/x.sock"
        assert s.shm_control_path() == "/tmp/x.sock"
        # rollback arm derives nothing
        s.shm_rings = False
        assert s.shm_control_path() == ""
        # shared memory cannot cross hosts: tcp/tls sidecars disable shm
        s.shm_rings = True
        s.shm_control_sock = ""
        s.sidecar_socket = "tcp://owner:7070"
        assert s.shm_control_path() == ""
        s.sidecar_socket = "tls://owner:7070"
        assert s.shm_control_path() == ""

    def test_junk_rejected(self):
        with pytest.raises(ValueError, match="SHM_RINGS"):
            new_settings({"SHM_RINGS": "sideways"})
        with pytest.raises(ValueError, match="FRONTEND_PROCS"):
            new_settings({"FRONTEND_PROCS": "two"})
        with pytest.raises(ValueError, match="FRONTEND_PROCS"):
            new_settings({"FRONTEND_PROCS": "0"}).frontend_procs_count()
        with pytest.raises(ValueError, match="BACKEND_TYPE"):
            new_settings(
                {"FRONTEND_PROCS": "2", "BACKEND_TYPE": "memory"}
            ).frontend_procs_count()
        with pytest.raises(ValueError, match="SHM_RING_ROWS"):
            new_settings({"SHM_RING_ROWS": "8"}).shm_ring_rows_count()


class TestClusterSettings:
    """PARTITIONS / PARTITION_ADDRS / PARTITION_ROUTE_SETS /
    RESHARD_RATE_LIMIT_MB_S (cluster/)."""

    def test_defaults_are_the_rollback_arm(self):
        s = Settings()
        k, groups, route_sets, rate = s.cluster_config()
        assert k == 1
        assert groups == []
        assert route_sets == 256
        assert rate == 32.0

    def test_env_parsing(self):
        s = new_settings(
            {
                "PARTITIONS": "2",
                "PARTITION_ADDRS": (
                    "/run/p0a.sock,/run/p0b.sock;"
                    "tcp://h1:7070,tcp://h1:7071"
                ),
                "PARTITION_ROUTE_SETS": "512",
                "RESHARD_RATE_LIMIT_MB_S": "8.5",
            }
        )
        k, groups, route_sets, rate = s.cluster_config()
        assert k == 2
        assert groups == [
            ["/run/p0a.sock", "/run/p0b.sock"],
            ["tcp://h1:7070", "tcp://h1:7071"],
        ]
        assert route_sets == 512
        assert rate == 8.5
        # a sidecar discovers its own partition from the group listing
        # its socket; unlisted addresses discover nothing
        s.sidecar_socket = "/run/p0b.sock"
        assert s.cluster_partition_of(s.sidecar_socket) == 0
        assert s.cluster_partition_of("tcp://h1:7071") == 1
        assert s.cluster_partition_of("/run/elsewhere.sock") is None

    def test_junk_rejected(self):
        with pytest.raises(ValueError, match="PARTITIONS"):
            new_settings({"PARTITIONS": "two"})
        with pytest.raises(ValueError, match="PARTITIONS"):
            new_settings({"PARTITIONS": "0"}).cluster_config()
        with pytest.raises(ValueError, match="PARTITION_ROUTE_SETS"):
            new_settings({"PARTITION_ROUTE_SETS": "100"}).cluster_config()
        with pytest.raises(ValueError, match="RESHARD_RATE_LIMIT_MB_S"):
            new_settings({"RESHARD_RATE_LIMIT_MB_S": "0"}).cluster_config()
        # K>1 demands exactly K ';'-separated groups
        with pytest.raises(ValueError, match="groups"):
            new_settings(
                {"PARTITIONS": "2", "PARTITION_ADDRS": "/run/a.sock"}
            ).cluster_config()
        with pytest.raises(ValueError, match="PARTITION_ADDRS entry"):
            new_settings(
                {
                    "PARTITIONS": "2",
                    "PARTITION_ADDRS": "/run/a.sock;tcp://nope",
                }
            ).cluster_config()
        # more partitions than route sets cannot tile the space
        with pytest.raises(ValueError, match="cannot exceed"):
            new_settings(
                {
                    "PARTITIONS": "4",
                    "PARTITION_ROUTE_SETS": "2",
                    "PARTITION_ADDRS": "a;b;c;d",
                }
            ).cluster_config()


class TestFederationSettings:
    """FED_* knobs (cluster/federation.py global quota federation),
    following the lease_config() junk-rejection pattern: a typo'd
    membership must fail the boot, never silently become a different
    home assignment."""

    def test_defaults_are_the_rollback_arm(self):
        s = Settings()
        assert s.fed_enabled is False  # byte-identical pre-federation wire
        enabled, self_name, peers, mn, mx, interval, lag, ttl = (
            s.fed_config()
        )
        assert enabled is False
        assert self_name == "" and peers == {}
        assert (mn, mx) == (8, 1024)
        assert interval == pytest.approx(50.0)
        # 0 defaults resolve to multiples of the settle interval
        assert lag == pytest.approx(250.0)
        assert ttl == pytest.approx(500.0)

    def test_env_parsing(self):
        s = new_settings(
            {
                "FED_ENABLED": "true",
                "FED_SELF": "east",
                "FED_PEERS": " east=/run/e.sock , west=tcp://w:9000 ",
                "FED_SHARE_MIN": "2",
                "FED_SHARE_MAX": "64",
                "FED_SETTLE_INTERVAL_MS": "100",
                "FED_MAX_LAG_MS": "400",
                "FED_SHARE_TTL_MS": "1000",
            }
        )
        enabled, self_name, peers, mn, mx, interval, lag, ttl = (
            s.fed_config()
        )
        assert enabled is True
        assert self_name == "east"
        assert peers == {"east": "/run/e.sock", "west": "tcp://w:9000"}
        assert (mn, mx) == (2, 64)
        assert (interval, lag, ttl) == (100.0, 400.0, 1000.0)

    def test_junk_rejected(self):
        with pytest.raises(ValueError, match="FED_ENABLED"):
            new_settings({"FED_ENABLED": "sideways"})
        with pytest.raises(ValueError, match="FED_SHARE_MIN"):
            new_settings({"FED_SHARE_MIN": "four"})
        with pytest.raises(ValueError, match="FED_SHARE_MIN"):
            new_settings({"FED_SHARE_MIN": "0"}).fed_config()
        with pytest.raises(ValueError, match="FED_SHARE_MAX"):
            new_settings(
                {"FED_SHARE_MIN": "64", "FED_SHARE_MAX": "8"}
            ).fed_config()
        with pytest.raises(ValueError, match="FED_SETTLE_INTERVAL_MS"):
            new_settings({"FED_SETTLE_INTERVAL_MS": "0"}).fed_config()
        # a lag/ttl bound below the settle cadence would flap on every
        # pump — rejected, like REPL_MAX_LAG_MS below its interval
        with pytest.raises(ValueError, match="FED_MAX_LAG_MS"):
            new_settings(
                {"FED_SETTLE_INTERVAL_MS": "100", "FED_MAX_LAG_MS": "50"}
            ).fed_config()
        with pytest.raises(ValueError, match="FED_MAX_LAG_MS"):
            new_settings({"FED_MAX_LAG_MS": "-1"}).fed_config()
        with pytest.raises(ValueError, match="FED_SHARE_TTL_MS"):
            new_settings(
                {"FED_SETTLE_INTERVAL_MS": "100", "FED_SHARE_TTL_MS": "50"}
            ).fed_config()

    def test_enabled_membership_junk_rejected(self):
        with pytest.raises(ValueError, match="FED_SELF"):
            new_settings(
                {"FED_ENABLED": "true", "FED_PEERS": "a=/a,b=/b"}
            ).fed_config()
        with pytest.raises(ValueError, match="FED_PEERS"):
            new_settings(
                {"FED_ENABLED": "true", "FED_SELF": "a"}
            ).fed_config()
        with pytest.raises(ValueError, match="name=address"):
            new_settings(
                {
                    "FED_ENABLED": "true",
                    "FED_SELF": "a",
                    "FED_PEERS": "a=/a,b",
                }
            ).fed_config()
        with pytest.raises(ValueError, match="duplicate"):
            new_settings(
                {
                    "FED_ENABLED": "true",
                    "FED_SELF": "a",
                    "FED_PEERS": "a=/a,a=/b",
                }
            ).fed_config()
        with pytest.raises(ValueError, match="address"):
            new_settings(
                {
                    "FED_ENABLED": "true",
                    "FED_SELF": "a",
                    "FED_PEERS": "a=/a,b=tcp://nope",
                }
            ).fed_config()
        with pytest.raises(ValueError, match="at least two"):
            new_settings(
                {
                    "FED_ENABLED": "true",
                    "FED_SELF": "a",
                    "FED_PEERS": "a=/a",
                }
            ).fed_config()
        # self must be part of the membership it hashes over
        with pytest.raises(ValueError, match="FED_SELF"):
            new_settings(
                {
                    "FED_ENABLED": "true",
                    "FED_SELF": "c",
                    "FED_PEERS": "a=/a,b=/b",
                }
            ).fed_config()
