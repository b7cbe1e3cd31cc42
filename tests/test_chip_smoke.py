"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases, driven directly at a tiny size on the CPU, pass their own
checks (the rehearsal that precedes a chip run)."""
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout
from pathlib import Path

import jax

import chip_smoke
from repro.core import MachineConfig

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


def _run_on_cpu(script: Path, cwd: Path, **env):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full_env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd,
                          env=full_env, capture_output=True, text=True,
                          timeout=300)


def _assert_refused(proc):
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_exits_without_a_tpu():
    proc = _run_on_cpu(SCRIPT, REPO)
    _assert_refused(proc)
    assert "needs a TPU" in proc.stderr
    assert proc.stdout == ""           # refused before any work


def test_exits_alone_in_a_directory(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    _assert_refused(_run_on_cpu(lone, tmp_path))


def _tiny_machine():
    return MachineConfig(n_threads=4, radix_bits=6, va_pages=1 << 12,
                         dram_pages_per_node=768, nvmm_pages_per_node=3200)


def _cpu_device():
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def test_one_chip_phase_rehearsal_on_cpu():
    out = io.StringIO()
    with redirect_stdout(out):
        chip_smoke.one_chip(_cpu_device(), mc=_tiny_machine(),
                            footprint=1 << 12, run_steps=128,
                            ref_run_steps=64)
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    phases = [r["phase"] for r in lines]
    assert phases == ["first_burst", "repeat_burst", "warm_flush",
                      "blocked_vs_per_step", "broker_vs_oracle"]
    assert all(r["device"]["platform"] == "cpu" for r in lines)
    first = lines[0]
    assert first["queries"] == 18 and first["lanes_run"] == 18
    assert lines[1]["new_compiles"] == 0
    assert lines[3]["counters_placements_equal"]


def test_four_chip_phase_rehearsal_on_virtual_devices():
    """The lane-sharded phase on four virtual CPU devices, in a process of
    its own (the device count is fixed when JAX starts)."""
    code = (
        "import jax, chip_smoke, test_chip_smoke as t\n"
        "chip_smoke.four_chips(t._cpu_device(), mc=t._tiny_machine(),\n"
        "                      footprint=1 << 12, run_steps=128)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO), str(REPO / "src"), str(REPO / "tests")]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["phase"] == "four_chips_identity" and last["bitwise"]
    assert last["device"]["count"] == 4
