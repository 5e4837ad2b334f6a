"""The plain reference agrees with the program at a small size (CPU).

In float32 both compute the same mathematics, so the loss agrees to
float32 rounding and each leaf's gradient to a small share of its norm;
on four devices capacity is sized per device, as the reference does."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import agree
from chipbench.tests.cells import REPO

LOSS_TOL = 1e-5
GRAD_TOL = 1e-3


@pytest.mark.parametrize("mix", ["tiny.lsh", "tiny.nolsh"])
def test_one_device(mix):
    g = agree.gaps("tiny.1dev", mix)
    assert g["loss_gap"] < LOSS_TOL, g
    assert g["grad_gap"] < GRAD_TOL, g


@pytest.mark.parametrize("mix", ["tiny.lsh", "tiny.nolsh"])
def test_four_devices(mix):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), str(REPO / "src"), env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.agree",
                        "tiny.4dev", mix], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    g = json.loads(p.stdout.strip().splitlines()[-1])
    assert g["devices"] == 4
    assert g["loss_gap"] < LOSS_TOL, g
    assert g["grad_gap"] < GRAD_TOL, g


def test_calibration_reads_program_control_and_faults(tmp_path):
    """``calibrate.py``'s readings on one seed: the program within the
    configuration's limits, the planted half-batch fault outside them."""
    from chipbench import calibrate, check
    from chipbench.tests.cells import make_root
    root = make_root(tmp_path, [("t", "tiny.1dev", "tiny.lsh", 1)])
    lines = []
    (row,) = calibrate.readings("t", [2**33 + 7], 1, require_tpu=False,
                                root=root, emit=lines.append)
    limits = json.loads((root / "chipbench" / "configs" /
                         "tiny.1dev.json").read_text())["limits"]
    assert check.judge(row["program"], limits)
    assert not check.judge(row["half_batch"], limits)
    assert set(row["moment_gaps"]) == {"program", "control", "half_batch"}
    assert json.loads(lines[0])["seed"] == 2**33 + 7
