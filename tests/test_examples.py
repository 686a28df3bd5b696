"""The examples/ scripts (one per reference configuration) must run end-to-end in
their tiny smoke configuration — subprocess-executed exactly as a user
would, on the 8-device virtual mesh."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPTS = ["resnet_cifar10.py", "bert_pretrain_dp.py",
           "gpt_sharding_stage2.py", "ernie_mp_pp.py",
           "ppyoloe_detection.py", "long_context_sp.py"]


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_smoke(script):
    keep = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
            if p]
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join([REPO] + keep),
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                   " --xla_force_host_platform_device_count=8").strip())
    argv = [sys.executable, os.path.join(REPO, "examples", script)]
    if script != "resnet_cifar10.py":
        argv += ["--steps", "2"]
    out = subprocess.run(argv, capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss" in out.stdout, out.stdout[-500:]
