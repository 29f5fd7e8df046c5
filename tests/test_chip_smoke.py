"""chip_smoke.py off the chip: it refuses the CPU, and its phases — given
a device and sizes by the caller — pass at tiny sizes on the CPU (the
script itself has no CPU mode). Plus the one-process-per-chip rule the
fleet master relies on: importing service_cmd initialises no backend."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert not any(
        line.startswith('{"ok"') for line in capsys.readouterr().out.splitlines()
    )


def _tiny_env(**extra):
    return {
        **os.environ,
        "TPU_SLAB_SLOTS": str(1 << 16),
        "TPU_PRECOMPILE": "false",
        "LOG_LEVEL": "ERROR",
        **extra,
    }


@pytest.mark.parametrize("phase", ["served", "engine", "mesh"])
def test_phase_passes_at_tiny_size(phase):
    import jax

    devices = jax.devices()
    if phase == "served":
        env = _tiny_env(
            TPU_SLAB_SLOTS=str(1 << 14), TPU_PRECOMPILE="true",
            TPU_BUCKETS="128,1024",
        )
        out = chip_smoke.served_phase(
            devices[0], env, n_requests=200, n_keys=30, n_threads=2, seed=0,
            window_margin_s=5.0,
        )
        assert out["disagreements"] == 0 and out["over_limit"] > 0
        assert out["precompiled"] == 6
    elif phase == "engine":
        out = chip_smoke.engine_phase(
            devices[0], _tiny_env(), n_decisions=1 << 15, n_keys=4096,
            batch=1 << 12, seed=0,
        )
        assert out["false_over"] == 0 and out["over_limit"] > 0
    else:
        out = chip_smoke.mesh_phase(
            devices[:4], _tiny_env(), n_decisions=1 << 17, n_keys=4096,
            batch=1 << 13, seed=0,
        )
        assert out["arms_disagree"] == 0 and out["hot_tier"]["promotions"] > 0


def test_service_cmd_import_initializes_no_backend():
    """The FRONTEND_PROCS master imports service_cmd and then spawns the
    device owner: it must not have claimed the chip first."""
    code = (
        "import sys, json\n"
        "import api_ratelimit_tpu.cmd.service_cmd\n"
        "from jax._src import xla_bridge\n"
        "print(json.dumps(sorted(xla_bridge._backends)))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    assert json.loads(proc.stdout.splitlines()[-1]) == []
