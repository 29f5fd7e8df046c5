"""Standalone measurement tools: they honor JAX_PLATFORMS=cpu, and the
report tools render their captures."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tool(mod, extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single CPU device, like an operator shell
    return subprocess.run(
        [sys.executable, "-m", mod, *extra],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "mod,extra",
    [
        ("tools.divtest", ("--batch", "4096", "--repeats", "2")),
    ],
)
def test_tool_runs_on_cpu_when_pinned(mod, extra):
    proc = _run_tool(mod, extra)
    assert proc.returncode == 0, proc.stderr[-500:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, proc.stdout[-300:]
    assert json.loads(lines[-1])["platform"] == "cpu"


class TestJourneyReport:
    """tools/journey_report.py smoke (tier-1, jax-free): it must render a
    /debug/journeys capture into the per-stage table and --json form."""

    def _sample_doc(self):
        base = 1_000_000_000
        journeys = []
        for i, (dur, flags) in enumerate(
            [(12.0, ["slow"]), (3.0, ["over_limit"]), (40.0, ["fault", "slow"])]
        ):
            journeys.append(
                {
                    "kind": "request",
                    "trace_id": f"{i + 1:032x}",
                    "flags": flags,
                    "duration_ms": dur,
                    "start_ns": base,
                    "stages": {
                        "publish": base + 100_000,
                        "take": base + 400_000,
                        "pack": base + 450_000,
                        "launch": base + 900_000,
                        "redeem": base + int(dur * 1e6),
                        "scatter": base + int(dur * 1e6) + 50_000,
                    },
                    "thread": f"worker-{i}",
                }
            )
        return {"enabled": True, "live_p99_ms": 38.5, "retained": journeys}

    def _write_doc(self, tmp_path):
        import json

        path = tmp_path / "journeys.json"
        path.write_text(json.dumps(self._sample_doc()))
        return str(path)

    def test_text_report(self, tmp_path):
        proc = _run_tool(
            "tools.journey_report", (self._write_doc(tmp_path), "--top", "2")
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        out = proc.stdout
        assert "[journeys] retained=3" in out
        for stage in ("publish", "take", "pack", "launch", "redeem", "scatter"):
            assert stage in out
        assert "top 2 slowest" in out
        assert "fault,slow" in out  # slowest journey's flags render

    def test_json_report(self, tmp_path):
        import json

        proc = _run_tool(
            "tools.journey_report", (self._write_doc(tmp_path), "--json")
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        report = json.loads(proc.stdout)
        assert report["journeys"] == 3
        assert report["stages"]["publish"]["count"] == 3
        # slowest first, with per-stage ms deltas
        assert report["slowest"][0]["duration_ms"] == 40.0
        assert report["slowest"][0]["stage_ms"]["take"] > 0

    def test_bad_input_exits_nonzero(self, tmp_path):
        bad = tmp_path / "nope.json"
        bad.write_text("{not json")
        proc = _run_tool("tools.journey_report", (str(bad),))
        assert proc.returncode == 1
        assert "cannot read" in proc.stderr


class TestHotpathProfile:
    """tools/hotpath_profile.py smoke (tier-1, not slow): it must run the
    flat_per_second loop under cProfile and emit a parseable table."""

    def test_runs_and_parses(self):
        proc = _run_tool(
            "tools.hotpath_profile", ("-n", "120", "--top", "6")
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        lines = proc.stdout.splitlines()
        summary = [ln for ln in lines if ln.startswith("[hotpath] rate=")]
        assert summary, proc.stdout[-300:]
        # summary parses: rate=<int>/s requests=<int>
        rate_field = summary[0].split()[1]
        assert rate_field.startswith("rate=") and rate_field.endswith("/s")
        assert int(rate_field[len("rate="):-len("/s")]) > 0
        header = [ln for ln in lines if "ncalls" in ln and "tottime" in ln]
        assert header, "pstats table header missing"
        # at least one profiled row mentions the service hot path
        assert any("should_rate_limit" in ln for ln in lines)

    def test_slab_split_baseline(self):
        proc = _run_tool("tools.hotpath_profile", ("--slab-split",))
        assert proc.returncode == 0, proc.stderr[-500:]
        lines = proc.stdout.splitlines()
        summary = [ln for ln in lines if ln.startswith("[slab_split] batch=")]
        assert summary, proc.stdout[-300:]
        assert int(summary[0].split("batch=")[1]) > 0
        for stage in ("gather_ns", "scan_ns", "scatter_ns"):
            rows = [ln for ln in lines if ln.strip().startswith(stage)]
            assert rows, (stage, proc.stdout[-300:])
            assert "p50=" in rows[0] and "p99=" in rows[0]

    def test_shard_split_stage_table(self):
        """--shard-split forces its own virtual mesh (the harness strips
        XLA_FLAGS, so the tool must set the device split itself before
        jax initializes) and prints the routed owner's stage table."""
        proc = _run_tool(
            "tools.hotpath_profile", ("--shard-split", "--shards", "2")
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        lines = proc.stdout.splitlines()
        summary = [ln for ln in lines if ln.startswith("[shard_split] shards=")]
        assert summary, proc.stdout[-300:]
        assert "shards=2" in summary[0] and "launches=" in summary[0]
        for stage in ("bucket_ns", "pad_ns", "launch_ns"):
            rows = [ln for ln in lines if ln.strip().startswith(stage)]
            assert rows, (stage, proc.stdout[-300:])
            assert "p50=" in rows[0] and "p99=" in rows[0]
        assert any(ln.strip().startswith("shard_rows") for ln in lines)
        assert any("padding_waste_pct=" in ln for ln in lines)

    def test_frontend_arm_reports_native_split(self):
        """--frontend: one worker's decode→match→compose→publish loop
        over shm rings to a local owner, with the [native_split] line
        naming which stages ran native."""
        proc = _run_tool(
            "tools.hotpath_profile", ("-n", "120", "--top", "8", "--frontend")
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        assert "path=frontend-shm" in proc.stdout
        lines = proc.stdout.splitlines()
        split = [ln for ln in lines if ln.startswith("[native_split]")]
        assert split, proc.stdout[-300:]
        # with the toolchain baked into this image the whole loop is
        # native end to end: codec + matcher + shm submit
        assert "codec=native" in split[0]
        assert "matcher=native" in split[0]
        assert "submit=shm" in split[0]
        header = [ln for ln in lines if "ncalls" in ln and "tottime" in ln]
        assert header, "pstats table header missing"
        assert any("shm_ring.py" in ln for ln in lines)
