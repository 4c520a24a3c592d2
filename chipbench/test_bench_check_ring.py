"""CPU test of the check that decides ``correct`` on the micro ring4 cell
(``micro.py``): one agent per device on four CPU devices, through
``build_train_step``.  A sound run is correct, each planted fault (a state
left unchanged, half of the batch left out, the exchange between chips
left out) is not, and the float8 control fails a limit.  Four devices need
a process of their own."""

import json
import os
import subprocess
import sys
from pathlib import Path

from chipbench import micro

ROOT = Path(__file__).resolve().parent.parent


def _fails(nums, limits):
    return any(nums[k] > limits[k] for k in limits)


def test_ring4_sound_faults_and_control(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, "-m", "chipbench.micro", "ring4"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    limits = micro.LIMITS["ring4"]
    assert not _fails(out["sound"][0], limits), out["sound"]
    for fault, nums in out["faults"].items():
        assert _fails(nums, limits), (fault, nums)
    assert set(out["faults"]) == {"frozen", "half_batch", "no_exchange"}
    assert _fails(out["control"], limits), out["control"]
