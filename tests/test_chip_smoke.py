"""chip_smoke.py's contract, as far as a sandbox without a chip can hold
it (ISSUE 21): without a chip the script fails fast and prints no result;
alone in a directory it fails; `--cpu-tiny` drives the very same phases
at gpt_tiny size and says it is not a chip run."""
import json
import os
import shutil
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run(argv, cwd=_ROOT, timeout=420, **env):
    return subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True,
        timeout=timeout, cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def _last_json(stdout, line=-1):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[line]) if lines else None
    except (ValueError, IndexError):
        return None


def test_no_chip_fails_fast_and_prints_no_result():
    t0 = time.monotonic()
    out = _run([_SMOKE], timeout=120)
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60
    assert _last_json(out.stdout) is None, out.stdout[-300:]
    assert "no TPU" in out.stderr and "--cpu-tiny" in out.stderr


def test_alone_in_a_directory_fails(tmp_path):
    """The script is not a stand-in for the program: copied out of the
    repo it cannot pass, with or without the dry-run flag."""
    shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    out = _run([str(tmp_path / "chip_smoke.py"), "--cpu-tiny"],
               cwd=str(tmp_path), timeout=120, PYTHONPATH="")
    assert out.returncode != 0
    assert _last_json(out.stdout) is None
    assert "paddle_tpu" in out.stderr          # the import that failed


def test_cpu_tiny_passes_every_phase():
    out = _run([_SMOKE, "--cpu-tiny"])
    assert out.returncode == 0, out.stderr[-3000:]
    assert "NOT A CHIP RUN" in out.stdout.splitlines()[0]
    # the result line holds exactly what the driver's check reads
    result = _last_json(out.stdout)
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    # (the count is conftest's 8 virtual devices, inherited via XLA_FLAGS)
    assert result["device"]["platform"] == result["device"]["kind"] == "cpu"
    assert isinstance(result["device"]["count"], int)
    # the report is the line before it
    rec = _last_json(out.stdout, -2)
    assert rec["cpu_tiny"] is True
    assert list(rec["phases"]) == ["device", "kernels", "train", "serve",
                                   "cache"]
    assert all(p["status"] == "ok" for p in rec["phases"].values())
    # the cache went where the environment said, nowhere else
    assert rec["phases"]["device"]["cache_dir"] == \
        os.environ["JAX_COMPILATION_CACHE_DIR"]
    serve = rec["phases"]["serve"]
    for impl in ("gather", "kernel"):
        assert serve[impl]["requests_done"] == 4
        assert serve[impl]["decode_traced"] + \
            serve[impl]["decode_loaded"] == 1
    assert rec["phases"]["train"]["loss_last"] < \
        rec["phases"]["train"]["loss_first"]
