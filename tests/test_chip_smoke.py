"""``chip_smoke.py``'s phases, run tiny on CPU so the script cannot rot.

The script itself refuses to run anywhere but on a TPU; here each phase
function is called directly with small sizes.  The four-chip phases run
in a child process that sees four virtual CPU devices.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def farm_ref():
    return chip_smoke.farm_phase(64, 300)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and '"ok"' not in out


def test_farm_phase(farm_ref):
    assert farm_ref.n_finished == 300 and farm_ref.dropped == 0
    assert farm_ref.run_info.jit_compile_s >= 0
    assert farm_ref.wake_count.sum() > 0       # sleep/wake cycles happen


def test_kernels_phase(farm_ref):
    # interpreted on CPU, so there is no tpu_custom_call to find
    res = chip_smoke.kernels_phase(64, 300, farm_ref,
                                   require_custom_call=False)
    assert res.server_energy == farm_ref.server_energy


def test_replicas_phase():
    chip_smoke.replicas_phase(3, 16, 40)


def test_oracle_phase():
    chip_smoke.oracle_phase(40)


def test_four_chip_phases_on_virtual_devices():
    script = ("import chip_smoke as cs\n"
              "cs.sharded_farm_phase(64, 150, n_devices=4)\n"
              "cs.sharded_replicas_phase(4, 16, 40, n_devices=4)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "phase sharded_farm passed" in r.stdout
    assert "phase sharded_replicas passed" in r.stdout
