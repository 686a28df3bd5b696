"""bench.py ladder semantics: the race phase measures the near-best configs
and reports the fastest; out-of-memory failures — and nothing else — fall
to the step-down tail; any other failure ends the run, in the race as in
the tail (never stepped over, never folded into `extra`)."""
import os

import pytest


@pytest.fixture
def bench_mocked(monkeypatch):
    import jax

    import bench

    monkeypatch.setenv("BENCH_SKIP_PREFLIGHT", "1")
    emitted = []
    monkeypatch.setattr(bench, "attached_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(bench, "emit",
                        lambda v, vb, extra=None: emitted.append((v, extra)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return bench, emitted


def test_race_reports_fastest_config(bench_mocked, monkeypatch):
    bench, emitted = bench_mocked
    calls = []

    def fake(B, S, remat, n_steps, on_tpu, scan_k, fused_ce=False):
        calls.append((B, remat, fused_ce))
        if B == 16:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        ms = {(True, "dots"): 400.0, (False, "dots"): 419.9,
              (False, "dots+attn"): 428.1}[(fused_ce, remat)]
        return {"value": round(419.9 / ms * 0.339, 4), "vs_baseline": 0.8,
                "extra": {"step_ms": ms}}

    monkeypatch.setattr(bench, "run_config", fake)
    bench.main()
    v, extra = emitted[0]
    assert extra["ladder_rung"] == "B=12,remat=dots,fused_ce"
    assert set(extra["race"]) == {"B=12,remat=dots,fused_ce",
                                  "B=12,remat=dots", "B=12,remat=dots+attn"}
    assert "B=16,remat=dots,fused_ce" in extra["race_oom"]
    assert calls == [(16, "dots", True), (12, "dots", True),
                     (12, "dots", False), (12, "dots+attn", False)]


def test_oom_race_falls_to_tail_first_success(bench_mocked, monkeypatch):
    bench, emitted = bench_mocked

    def fake(B, S, remat, n_steps, on_tpu, scan_k, fused_ce=False):
        if B >= 12:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return {"value": 0.30, "vs_baseline": 0.75, "extra": {"step_ms": 300.0}}

    monkeypatch.setattr(bench, "run_config", fake)
    bench.main()
    _, extra = emitted[0]
    assert extra["ladder_rung"] == "B=8,remat=dots,fused_ce"
    assert "race" not in extra


def test_non_oom_failure_raises(bench_mocked, monkeypatch):
    bench, emitted = bench_mocked

    def fake(B, S, remat, n_steps, on_tpu, scan_k, fused_ce=False):
        raise ValueError("some real bug")

    monkeypatch.setattr(bench, "run_config", fake)
    with pytest.raises(ValueError, match="real bug"):
        bench.main()
    assert not emitted


def test_race_non_oom_failure_ends_the_run(bench_mocked, monkeypatch):
    """A race rung that fails for any reason but device memory is a real
    failure even when another rung succeeded: it raises (bench.py then
    prints the failure record and exits non-zero)."""
    bench, emitted = bench_mocked

    def fake(B, S, remat, n_steps, on_tpu, scan_k, fused_ce=False):
        if remat == "dots+attn":
            raise AssertionError("impossible MFU 1.2: measurement is broken")
        return {"value": 0.33, "vs_baseline": 0.82, "extra": {"step_ms": 420.0}}

    monkeypatch.setattr(bench, "run_config", fake)
    with pytest.raises(AssertionError, match="impossible MFU"):
        bench.main()
    assert not emitted


def test_is_oom_is_only_what_the_runtime_says():
    import bench
    assert bench._is_oom(RuntimeError("RESOURCE_EXHAUSTED: Ran out of "
                                      "memory in memory space hbm"))
    assert bench._is_oom(RuntimeError("Exceeded hbm capacity by 3.96G"))
    # a bare substring or a compile-service failure is NOT out-of-memory
    assert not bench._is_oom(RuntimeError("BLOOM filter mismatch: OOM?"))
    assert not bench._is_oom(RuntimeError("INTERNAL: compile failed"))


def test_no_chip_no_default_train_rung(monkeypatch):
    """Without a TPU the default rung fails — it never runs a small
    config on the host under the MFU metric's name."""
    import bench
    monkeypatch.delenv("BENCH_B", raising=False)
    monkeypatch.delenv("BENCH_REMAT", raising=False)
    monkeypatch.setattr(bench, "run_config", lambda *a, **k: pytest.fail(
        "train rung ran without a chip"))
    with pytest.raises(RuntimeError, match="no TPU"):
        bench.main()
