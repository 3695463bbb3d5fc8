"""``chip_smoke.py`` at tiny sizes on the CPU: its phases drive the same
entry points and checks as on the chip (statuses, float64 host references,
sharded vs BlockEmulationProvider), so a refactor that breaks the script
fails here, before any chip time is spent. The checks only a chip can make
(Pallas custom calls, per-device memory) are off (``on_chip=False``)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.serve.solver_service import ShapeClass  # noqa: E402

G = chip_smoke.Group
TINY = chip_smoke.Plan(
    classes=(ShapeClass(n=256, d=16, m_max=32),
             ShapeClass(n=1024, d=16, m_max=32, sketch="srht")),
    ridge=(G(3, (200, 256), (12, 16)), G(3, (700, 1024), (12, 16))),
    glm=G(2, (200, 256), (12, 16)),
    path=G(2, (200, 256), (12, 16)),
    path_points=3,
    sjlt=G(3, (200, 256), (12, 16)),
    lib_n=2048, lib_d=64, lib_m_max=64, lib_rate=0.95,
    sharded=G(4, (1024, 1024), (16, 16)),
    sharded_classes=(ShapeClass(n=1024, d=16, m_max=32, sketch="srht"),),
    on_chip=False,
)


@pytest.mark.parametrize("phase", ["a", "b"])
def test_one_chip_phases_at_tiny_size(phase):
    getattr(chip_smoke, f"phase_{phase}")(TINY)


def test_four_chip_phase_on_forced_cpu_devices():
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, sys.argv[1])
        import test_chip_smoke as t
        t.chip_smoke.phase_four(t.TINY)
        print("FOUR_OK")
    """)
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", code, str(ROOT / "tests")],
                       capture_output=True, text=True, env=env,
                       cwd=str(ROOT), timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_OK" in r.stdout


def test_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       cwd=str(ROOT), timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
