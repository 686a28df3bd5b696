"""Every cell runs at its tiny sizes on the CPU, its last line has exactly
the contract's keys, and a fault planted under the timed path makes `correct`
come out false."""
import pytest

from bench_helpers import DEVICE_KEYS, RESULT_KEYS, bench_json, run_cell

CELLS = [w["name"] for w in bench_json()["workloads"]]
TRAIN = [w["name"] for w in bench_json()["workloads"]
         if w["traffic"].startswith("pretrain")]
SERVE = [c for c in CELLS if c not in TRAIN]


def declared(kind, cell):
    return {m["name"] for m in bench_json()[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_tiny_and_prints_the_contract_line(capsys, cell):
    line, out, err = run_cell(capsys, cell, trace=0)
    assert "NOT A CHIP RUN" in out
    assert set(line) == RESULT_KEYS
    assert list(line)[-1] == "checks"          # compared numbers come last
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == declared("end_to_end", cell)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # each number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_only_declared_per_layer_metrics(capsys, cell):
    line, _, err = run_cell(capsys, cell, trace=1)
    assert line["correct"] is True, err
    names = set(line["metrics"])
    assert names <= declared("per_layer", cell)
    # no device, no peaks: shares of a peak and trace metrics stay silent
    assert not {n for n in names if "mfu" in n or "roofline" in n
                or "idle" in n}


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_makes_correct_false(capsys, cell, fault):
    from benchmark.harness import faults
    line, _, _ = run_cell(capsys, cell,
                          hooks={"wrap_step": getattr(faults, fault)})
    assert line["correct"] is False
    failed = [k for k, c in line["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed, line["checks"]


@pytest.mark.parametrize("cell", SERVE)
def test_altered_token_makes_correct_false(capsys, cell):
    from benchmark.harness import faults
    line, _, _ = run_cell(capsys, cell, seconds=2.0,
                          hooks={"wrap_engine": faults.alter_token})
    assert line["correct"] is False
    gap = line["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_unknown_workload_and_missing_chip_exit_nonzero(monkeypatch):
    from benchmark import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "no_such_cell"])
    assert e.value.code not in (0, None)
    with pytest.raises(SystemExit) as e:        # the CPU is not a chip
        run.main(["--workload", CELLS[0], "--seconds", "1"])
    assert e.value.code not in (0, None)
