"""``correct`` comes out false when the timed path is broken underneath,
and when the control (the reference at float8) takes the program's place
(CPU, small size; the chip's readings are in PERF.md)."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import faults
from chipbench.tests.cells import REPO


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch", "control"])
def test_one_device_fault_is_not_correct(fault):
    res = faults.run(fault, "tiny.1dev", "tiny.lsh", 1)
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_four_devices_exchange_left_out_is_not_correct(fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), str(REPO / "src"), env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.faults",
                        fault, "tiny.4dev", "tiny.lsh", "4"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is correct, res["check"]
