"""Shared by the benchmark's tests: run a cell in this process at its tiny
sizes and hand back the result line and the record of what was printed."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(capsys, workload, trace=0, seed=5, seconds=1.0, hooks=None):
    """(result line as a dict, all of stdout, all of stderr)."""
    from benchmark import run
    capsys.readouterr()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace),
                   "--cpu-tiny"], hooks=hooks)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out, err


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
