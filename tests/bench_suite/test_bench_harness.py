"""The harness's own stamps against the scheduler's, and the rule that a
cell, a mix and a per-layer metric are added as files, editing none."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np

from bench_helpers import RESULT_KEYS, ROOT

from benchmark import run as bench_run


def test_ttft_stamps_agree_with_the_schedulers_within_a_step():
    _, cell, config, traffic = bench_run.load_cell("cgpt1p3b_serve_closed8",
                                                   tiny=True)
    ctx = bench_run.make_ctx(cell, config, traffic, seed=9, seconds=1.5,
                             tiny=True)
    record = bench_run.load_module("loops", "closed_loop").run(ctx)
    mine = np.asarray(record["samples"]["ttft_s"])
    theirs = np.asarray(record["samples"]["sched_ttft_s"])
    assert len(mine) == len(theirs) == record["attempted"] > 20
    # the scheduler stamps after prefill, the harness when step() returns:
    # never earlier, and later by no more than the longest step
    late = mine - theirs
    assert late.min() > -1e-4
    assert late.max() <= max(record["samples"]["step_s"]) + 1e-3
    assert record["failed"] == 0
    # every token of the window is counted once: the first tokens (one per
    # prefill in the window) and the decode tokens
    c = record["counters"]
    assert c["output_tokens"] > c["output_tokens_processed"] > 0
    assert c["gaps"] <= c["output_tokens_processed"]


def test_a_cell_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """In a temporary copy: a new traffic mix, a new cell over it and a new
    per-layer metric are one data file, one reader and three entries of
    BENCHMARK.json; no file of the benchmark is edited."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    before = {p: (tmp_path / "benchmark" / p).read_bytes()
              for p in ("run.py", "loops/closed_loop.py",
                        "traffic/closed8_mixed.json")}
    mix = bench_run.load_json(ROOT, "benchmark", "traffic",
                              "closed8_mixed.json")
    mix["tiny"]["clients"] = 1
    mix["tiny"]["output_len"] = {"dist": "uniform", "lo": 3, "hi": 3}
    (tmp_path / "benchmark/traffic/closed1_three.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/layer_metrics/requests_per_s.py").write_text(
        "def read(record, trace):\n"
        "    return record['attempted'] / record['window_s']\n")
    shutil.copy(tmp_path / "benchmark/limits/cgpt1p3b_serve_closed8.json",
                tmp_path / "benchmark/limits/new_cell.json")
    bench["workloads"].append(
        {"name": "new_cell", "config": "cerebras_gpt_1p3b",
         "traffic": "closed1_three", "chips": 1, "why": "a test's"})
    bench["per_layer"].append(
        {"name": "requests_per_s", "unit": "1/s", "better": "higher",
         "source": "host_clock", "layer": "scheduler",
         "moves": "serve_tokens_per_s", "workloads": ["new_cell"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and "cgpt1p3b_serve_closed8" in m["workloads"]:
            m["workloads"].append("new_cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark/run.py"), "--workload",
         "new_cell", "--seed", "4", "--seconds", "1", "--trace", "1",
         "--cpu-tiny"], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS and line["correct"] is True
    assert line["metrics"]["requests_per_s"]["unit"] == "1/s"
    assert line["metrics"]["requests_per_s"]["value"] > 0
    for p, content in before.items():
        assert (tmp_path / "benchmark" / p).read_bytes() == content


def test_alone_in_a_directory_the_benchmark_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program to
    measure, so no result."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m_train_b8",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--cpu-tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")


def test_benchmark_json_keeps_to_the_contract():
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells)
    for w in bench["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert any("mfu" in m["name"] and w["name"] in m["workloads"]
                   for m in bench["per_layer"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "limits", w["name"] + ".json"))
    for c in bench["configs"]:
        spec = bench_run.load_json(ROOT, c["file"])
        assert spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
