"""CI guard for the perf-evidence pipeline: two tiny train steps under
`paddle_tpu.profiler.Profiler(timeline=...)` on CPU must leave a
schema-valid step-timeline JSONL + attribution report, and
tools/perf_report.py must render both — so the artifacts a dead TPU
grant leaves behind can never silently rot.

ISSUE 4 extends the same guard to the unified metrics registry: the run
also leaves a metrics-snapshot JSONL (paddle_tpu.metrics.v1) and a
Prometheus text dump, both schema-validated here, and
tools/metrics_report.py --compare (the counter-regression gate) is
exercised against them."""
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import metrics_report  # noqa: E402
import perf_report  # noqa: E402


@pytest.fixture(scope="module")
def profile_artifacts(tmp_path_factory):
    """Two tiny GPT train steps under the profiler, in process: the step
    timeline, the attribution report and the registry's two dumps, each
    through its producer's own writer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.observability import metrics
    from paddle_tpu.parallel import GPTSpmdConfig, MeshPlan, make_train_step
    from paddle_tpu.profiler import Profiler, RecordEvent, TracerEventType

    out_dir = str(tmp_path_factory.mktemp("profile"))
    cfg = GPTSpmdConfig(vocab_size=512, max_seq_len=64, hidden=64, layers=2,
                        heads=4, param_dtype="float32",
                        compute_dtype="float32")
    step_fn, init_fn, _ = make_train_step(cfg, MeshPlan(), learning_rate=2e-4)
    params, state = init_fn(jax.random.key(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 64)))
    labs = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 64)))
    lr = jnp.float32(2e-4)
    loss, params, state = step_fn(params, state, toks, labs, lr)   # compile
    paths = {"timeline": os.path.join(out_dir, "step_timeline.jsonl"),
             "attribution": os.path.join(out_dir, "attribution.md"),
             "metrics": os.path.join(out_dir, "metrics.jsonl"),
             "metrics_prom": os.path.join(out_dir, "metrics.prom")}
    prof = Profiler(timer_only=True, timeline=paths["timeline"])
    prof.start()
    for _ in range(2):
        with RecordEvent("train.dispatch", TracerEventType.Forward):
            loss, params, state = step_fn(params, state, toks, labs, lr)
            float(loss)
        prof.step(num_samples=2 * 64)
    prof.stop()
    with open(paths["attribution"], "w") as f:
        f.write(prof.analyze().render() + "\n")
    reg = metrics.registry()
    reg.write_snapshot(paths["metrics"])
    with open(paths["metrics_prom"], "w") as f:
        f.write(reg.dump_prometheus())
    return out_dir, paths


def test_profiler_leaves_timeline_and_attribution_artifacts(
        profile_artifacts):
    out_dir, paths = profile_artifacts
    for path in paths.values():
        assert os.path.getsize(path) > 0, path
        assert os.path.dirname(path) == out_dir


def test_timeline_jsonl_schema_valid(profile_artifacts):
    out_dir, paths = profile_artifacts
    records = perf_report.load_timeline(out_dir)   # raises on any violation
    assert len(records) == 2                       # one record per step
    for r in records:
        assert perf_report.validate_record(r) == []
        assert r["schema"] == perf_report.SCHEMA
        assert "Forward" in r["phases"]            # the dispatch span
        assert r["step_ms"] is None or r["step_ms"] > 0


def test_attribution_report_names_phases(profile_artifacts):
    out_dir, paths = profile_artifacts
    text = open(os.path.join(out_dir, "attribution.md")).read()
    assert "MFU attribution" in text
    assert "Forward" in text


def test_perf_report_renders_and_compares(profile_artifacts):
    out_dir, paths = profile_artifacts
    records = perf_report.load_timeline(out_dir)
    md = perf_report.render(records, title="smoke")
    assert "phase breakdown" in md and "avg step" in md
    cmp_md = perf_report.render_compare(records, records, "a", "b")
    assert "avg step ms" in cmp_md and "+0.0%" in cmp_md


def test_metrics_snapshot_artifact_schema_valid(profile_artifacts):
    """The unified registry's JSONL snapshot rides the artifact set and
    must stay schema-valid (paddle_tpu.metrics.v1)."""
    out_dir, paths = profile_artifacts
    snaps = metrics_report.load_snapshots(paths["metrics"])  # raises on rot
    assert all(metrics_report.validate_snapshot(s) == [] for s in snaps)
    names = {m["name"] for m in snaps[-1]["metrics"]}
    # the migrated producers register on import — a process that has
    # imported the package carries at least these families
    for expected in ("op_cache_hits", "op_cache_misses",
                     "live_device_bytes", "serving_tokens_total",
                     "dataloader_wait_seconds"):
        assert expected in names, f"{expected} missing from {names}"


def test_metrics_prometheus_dump_valid(profile_artifacts):
    out_dir, paths = profile_artifacts
    text = open(paths["metrics_prom"]).read()
    errs = metrics_report.validate_prometheus(text)
    assert errs == [], errs
    assert "# TYPE op_cache_hits gauge" in text


def test_metrics_report_compare_gates_regressions(profile_artifacts,
                                                  tmp_path):
    """The CI regression gate: --compare of a run against itself passes;
    a failure counter that grew past the threshold exits nonzero."""
    out_dir, paths = profile_artifacts
    mpath = paths["metrics"]
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    ok = subprocess.run(cli + ["--compare", mpath, mpath],
                        capture_output=True, text=True, timeout=60)
    assert ok.returncode == 0, ok.stdout + ok.stderr

    # inject a grown failure counter into a copy: the gate must trip
    snap = metrics_report.load_snapshots(mpath)[-1]

    def with_counter(value):
        doc = json.loads(json.dumps(snap))
        doc["metrics"].append({
            "name": "probe_timeouts_total", "type": "counter", "help": "",
            "labelnames": [], "samples": [{"labels": {}, "value": value}]})
        return doc

    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    with open(a, "w") as f:
        f.write(json.dumps(with_counter(1)) + "\n")
    with open(b, "w") as f:
        f.write(json.dumps(with_counter(10)) + "\n")
    bad = subprocess.run(cli + ["--compare", a, b],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "probe_timeouts_total" in bad.stdout
    assert "REGRESSIONS" in bad.stdout


def _snapshot_with(counters):
    """Minimal valid paddle_tpu.metrics.v1 snapshot with given counter
    name->value pairs."""
    return {"schema": metrics_report.SCHEMA, "ts": 1.0, "pid": 1,
            "metrics": [
                {"name": n, "type": "counter", "help": "", "labelnames": [],
                 "samples": [{"labels": {}, "value": v}]}
                for n, v in counters.items()]}


def test_metrics_compare_flags_shed_preempt_and_prefix_rate(tmp_path):
    """ISSUE 6 gate: shed/preempt counter growth and a prefix-cache
    hit-RATE drop are failure-class regressions, even when the absolute
    hit count grew with traffic."""
    a = _snapshot_with({"serving_shed_total": 1,
                        "serving_preempted_total": 2,
                        "serving_prefix_cache_hits_total": 80,
                        "serving_prefix_cache_misses_total": 20,
                        "serving_tokens_total": 1000})
    b = _snapshot_with({"serving_shed_total": 10,
                        "serving_preempted_total": 9,
                        "serving_prefix_cache_hits_total": 100,  # grew...
                        "serving_prefix_cache_misses_total": 100,  # rate 0.5
                        "serving_tokens_total": 1000})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, _, _, _, w in regs}
    assert why["serving_shed_total"] == "failure counter grew"
    assert why["serving_preempted_total"] == "failure counter grew"
    assert why["serving_prefix_cache_misses_total"] == "failure counter grew"
    assert why["serving_prefix_cache_hit_rate"] == "hit rate dropped"
    # identical runs stay clean, and the CLI exit code reflects the gate
    assert metrics_report.compare_counters(a, a) == []
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_prefix_cache_hit_rate" in bad.stdout
    # a pure traffic-growth run (rate intact) passes the rate rule
    c = _snapshot_with({"serving_prefix_cache_hits_total": 800,
                        "serving_prefix_cache_misses_total": 200,
                        "serving_tokens_total": 9000})
    assert not any(w == "hit rate dropped" for *_, w in
                   metrics_report.compare_counters(a, c))


def test_metrics_compare_flags_spec_acceptance_rate_drop(tmp_path):
    """ISSUE 7 gate: a spec-decode acceptance-RATE drop is failure-class
    even when the absolute accepted count grew with traffic — and a
    traffic-growth run with the rate intact passes."""
    a = _snapshot_with({"serving_spec_accepted_total": 75,
                        "serving_spec_proposed_total": 100,
                        "serving_tokens_total": 500})
    b = _snapshot_with({"serving_spec_accepted_total": 90,   # grew...
                        "serving_spec_proposed_total": 300,  # rate 0.30
                        "serving_tokens_total": 500})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_spec_acceptance_rate") == "hit rate dropped"
    # the CLI gate exits nonzero on the drop
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_spec_acceptance_rate" in bad.stdout
    # pure growth at the same rate: clean
    c = _snapshot_with({"serving_spec_accepted_total": 750,
                        "serving_spec_proposed_total": 1000,
                        "serving_tokens_total": 5000})
    assert not any(w == "hit rate dropped" for *_, w in
                   metrics_report.compare_counters(a, c))


def _snapshot_with_labeled(counters):
    """Snapshot whose counters carry per-sample labels:
    {name: [(labels_dict, value), ...]}."""
    return {"schema": metrics_report.SCHEMA, "ts": 1.0, "pid": 1,
            "metrics": [
                {"name": n, "type": "counter", "help": "",
                 "labelnames": sorted({k for lb, _ in samples
                                       for k in lb}),
                 "samples": [{"labels": lb, "value": v}
                             for lb, v in samples]}
                for n, samples in counters.items()]}


def test_metrics_compare_flags_spec_acceptance_rate_drop_pp_arm(tmp_path):
    """ISSUE 14 gate: the spec counters are labeled per ENGINE KIND, and
    the acceptance-rate rule pairs + gates each labelset separately — a
    spec×pp draft rotting on the pipeline ring is flagged even while
    the single-device engine's rate stays healthy (and must not drag
    the healthy series into the regression list)."""
    a = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 80),
                                        ({"engine": "spec_pp"}, 75)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 100),
                                        ({"engine": "spec_pp"}, 100)]})
    b = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 160),
                                        ({"engine": "spec_pp"}, 90)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 200),
                                        ({"engine": "spec_pp"}, 300)]})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_spec_acceptance_rate{engine=spec_pp}") == \
        "hit rate dropped"
    assert "serving_spec_acceptance_rate{engine=spec}" not in why
    # the CLI gate exits nonzero and names the labeled series
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_spec_acceptance_rate{engine=spec_pp}" in bad.stdout


def test_metrics_compare_spans_label_schema_boundary():
    """A baseline recorded BEFORE the spec counters grew the engine
    label must still gate: the labeled run's family aggregate pairs
    with the bare baseline rate, and the bare-vs-labeled key mismatch
    is read as a schema change — never as counters vanishing/appearing
    ('work counter shrank' false positives)."""
    old = _snapshot_with({"serving_spec_accepted_total": 75,
                          "serving_spec_proposed_total": 100})
    new_bad = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 90)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 300)]})
    regs = metrics_report.compare_counters(old, new_bad)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_spec_acceptance_rate") == "hit rate dropped"
    assert not any(w == "work counter shrank" for w in why.values())
    # same rate and volume across the boundary: clean both directions
    # (the bare row compares against the labeled side's family SUM, so
    # the volume rules keep gating across the schema change too)
    new_ok = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 75)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 100)]})
    assert metrics_report.compare_counters(old, new_ok) == []
    assert metrics_report.compare_counters(new_ok, old) == []
    # two LABELED runs with identical per-engine rates but a shifted
    # traffic mix: the per-labelset series gate, and the bare family
    # aggregate must NOT fire on the mix shift (Simpson's paradox)
    mix_a = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 90),
                                        ({"engine": "spec_pp"}, 30)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 100),
                                        ({"engine": "spec_pp"}, 100)]})
    mix_b = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 90),
                                        ({"engine": "spec_pp"}, 300)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 100),
                                        ({"engine": "spec_pp"}, 1000)]})
    assert not any(w == "hit rate dropped" for *_, w in
                   metrics_report.compare_counters(mix_a, mix_b))
    # a labeled MEMBER vanishing between two labeled runs is NOT a
    # schema change: an engine dropping out of the fleet must keep
    # tripping the counter rules
    gone = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 90)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 100)]})
    regs = metrics_report.compare_counters(mix_a, gone)
    assert any(k == "serving_spec_accepted_total{engine=spec_pp}"
               and w == "work counter shrank" for k, *_, w in regs)
    # volume rules bridge too: a 99% collapse in spec WORK across the
    # boundary gates even while the acceptance rate holds — the bare
    # row compares against the labeled side's family sum
    tiny_new = _snapshot_with_labeled({
        "serving_spec_accepted_total": [({"engine": "spec"}, 7)],
        "serving_spec_proposed_total": [({"engine": "spec"}, 10)]})
    regs = metrics_report.compare_counters(old, tiny_new)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_spec_accepted_total") == "work counter shrank"
    assert why.get("serving_spec_proposed_total") == "work counter shrank"


def test_metrics_compare_flags_quant_quality_regressions(tmp_path):
    """ISSUE 11 gate: a `serving_quant_greedy_match` drop (the quantized
    path disagreeing with its f32 oracle) and a `serving_quant_logit_kl`
    growth are failure-class — int8 serving that drifts from float is a
    correctness regression, however fast. Both directions exercised
    through compare_counters AND the CLI exit code."""
    a = _snapshot_with_gauges(gauges={"serving_quant_greedy_match": 1.0,
                                      "serving_quant_logit_kl": 0.001,
                                      "serving_load_tokens_per_s": 100.0})
    b = _snapshot_with_gauges(gauges={"serving_quant_greedy_match": 0.62,
                                      "serving_quant_logit_kl": 0.9,
                                      "serving_load_tokens_per_s": 100.0})
    regs = metrics_report.compare_counters(a, b, min_delta=0.001)
    why = {k: w for k, *_, w in regs}
    assert why["serving_quant_greedy_match"] == \
        "quantized greedy-match rate vs f32 oracle dropped"
    assert why["serving_quant_logit_kl"] == \
        "quantized logit KL vs f32 oracle grew"
    assert metrics_report.compare_counters(a, a, min_delta=0.001) == []
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb, "--min-delta", "0.001"],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_quant_greedy_match" in bad.stdout
    # an unchanged-quality run with MORE traffic stays clean
    c = _snapshot_with_gauges(gauges={"serving_quant_greedy_match": 1.0,
                                      "serving_quant_logit_kl": 0.001,
                                      "serving_load_tokens_per_s": 900.0})
    assert metrics_report.compare_counters(a, c, min_delta=0.001) == []


def test_metrics_compare_flags_numerics_anomalies(tmp_path):
    """ISSUE 19 gate: `numerics_anomaly_total` growth (a latched
    sentinel anomaly, per site×kind labelset) and a
    `numerics_site_finite_frac` gauge drop are failure-class — a run
    that went non-finite is broken however fast it was. Exercised
    through compare_counters AND the CLI exit code."""
    a = _snapshot_with_labeled({
        "numerics_anomaly_total": [({"site": "decode.logits",
                                     "kind": "nonfinite"}, 0)]})
    b = _snapshot_with_labeled({
        "numerics_anomaly_total": [({"site": "decode.logits",
                                     "kind": "nonfinite"}, 2)]})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("numerics_anomaly_total{kind=nonfinite,"
                   "site=decode.logits}") == "failure counter grew"
    assert metrics_report.compare_counters(a, a) == []
    # the finite-fraction gauge dropping fires the gauge-drop rule
    ga = _snapshot_with_gauges(
        gauges={"numerics_site_finite_frac": 1.0})
    gb = _snapshot_with_gauges(
        gauges={"numerics_site_finite_frac": 0.5})
    gregs = metrics_report.compare_counters(ga, gb)
    gwhy = {k: w for k, *_, w in gregs}
    assert any("finite fraction dropped" in w for w in gwhy.values()), gregs
    # the CLI gate exits nonzero and names the counter
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "numerics_anomaly_total" in bad.stdout


def test_metrics_compare_flags_gray_failure_plane(tmp_path):
    """ISSUE 20 gate: deadline-miss growth (router- or worker-side),
    suspect-reason migrations, and retry-budget exhaustion are
    failure-class, and the hedge primary-win RATE dropping fires even
    while both hedge counters grew with traffic. Drain-reason
    migrations are deliberate rolling-restart traffic and must pass.
    Exercised through compare_counters AND the CLI exit code."""
    a = _snapshot_with_labeled({
        "serving_deadline_missed_total": [({"where": "router"}, 1)],
        "serving_migrations_total": [({"reason": "suspect"}, 1),
                                     ({"reason": "drain"}, 2)],
        "serving_retry_budget_exhausted_total": [({"worker": "0"}, 0)],
        "serving_hedge_primary_total": [({"verb": "POLL"}, 90)],
        "serving_hedge_fired_total": [({"verb": "POLL"}, 10)]})
    b = _snapshot_with_labeled({
        "serving_deadline_missed_total": [({"where": "router"}, 10)],
        "serving_migrations_total": [({"reason": "suspect"}, 9),
                                     ({"reason": "drain"}, 40)],
        "serving_retry_budget_exhausted_total": [({"worker": "0"}, 6)],
        "serving_hedge_primary_total": [({"verb": "POLL"}, 100)],  # grew..
        "serving_hedge_fired_total": [({"verb": "POLL"}, 100)]})   # rate .5
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_deadline_missed_total{where=router}") \
        == "failure counter grew"
    assert why.get("serving_migrations_total{reason=suspect}") \
        == "failure counter grew"
    assert why.get("serving_retry_budget_exhausted_total{worker=0}") \
        == "failure counter grew"
    assert why.get("serving_hedge_primary_rate{verb=POLL}") \
        == "hit rate dropped"
    # drain-reason migrations grew 20x and must NOT gate: a rolling
    # restart migrating every stream is the feature working
    assert "serving_migrations_total{reason=drain}" not in why
    # identical runs stay clean
    assert metrics_report.compare_counters(a, a) == []
    # the CLI gate exits nonzero and names the new failure classes
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_deadline_missed_total" in bad.stdout
    assert "serving_migrations_total{reason=suspect}" in bad.stdout
    assert "serving_hedge_primary_rate" in bad.stdout


def _snapshot_with_gauges(counters=None, gauges=None):
    metrics = [
        {"name": n, "type": "counter", "help": "", "labelnames": [],
         "samples": [{"labels": {}, "value": v}]}
        for n, v in (counters or {}).items()]
    metrics += [
        {"name": n, "type": "gauge", "help": "", "labelnames": [],
         "samples": [{"labels": {}, "value": v}]}
        for n, v in (gauges or {}).items()]
    return {"schema": metrics_report.SCHEMA, "ts": 1.0, "pid": 1,
            "metrics": metrics}


def test_metrics_compare_flags_compile_cache_hit_rate_drop(tmp_path):
    """ISSUE 8 gate: a persistent compile-cache hit-RATE drop is a
    failure-class regression (restarts started compiling again) even
    when the absolute hit count grew with more executables."""
    a = _snapshot_with({"compile_cache_hits_total": 9,
                        "compile_cache_misses_total": 1,
                        "serving_tokens_total": 100})
    b = _snapshot_with({"compile_cache_hits_total": 10,   # grew...
                        "compile_cache_misses_total": 10,  # rate 0.9 -> 0.5
                        "serving_tokens_total": 100})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("compile_cache_hit_rate") == "hit rate dropped"
    # growth at the same rate passes the rate rule
    c = _snapshot_with({"compile_cache_hits_total": 90,
                        "compile_cache_misses_total": 10,
                        "serving_tokens_total": 1000})
    assert not any(w == "hit rate dropped" for *_, w in
                   metrics_report.compare_counters(a, c))
    # and the CLI gate exits nonzero on the drop
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "compile_cache_hit_rate" in bad.stdout


def test_metrics_compare_flags_cost_model_gap_growth(tmp_path):
    """ISSUE 8 satellite gate: the measured/predicted step-time gauge
    GROWING past the threshold is failure-class; shrinking (we got
    faster than the model expected) is not."""
    a = _snapshot_with_gauges(
        gauges={"train_cost_model_measured_vs_predicted": 2.0,
                "train_cost_model_predicted_step_ms": 10.0})
    b = _snapshot_with_gauges(
        gauges={"train_cost_model_measured_vs_predicted": 3.5,
                "train_cost_model_predicted_step_ms": 10.0})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("train_cost_model_measured_vs_predicted") == \
        "measured/predicted gap widened"
    # improvement or stability: clean
    assert metrics_report.compare_counters(a, a) == []
    assert metrics_report.compare_counters(b, a) == []
    # the CLI gate trips on the widened gap
    pa, pb = str(tmp_path / "ga.jsonl"), str(tmp_path / "gb.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "gap widened" in bad.stdout


def test_metrics_compare_flags_pp_bubble_growth(tmp_path):
    """ISSUE 13 gate: the pipeline-serving bubble fraction GROWING past
    the threshold is failure-class (stages started idling — schedule
    rot or microbatch imbalance); shrinking or stable stays clean."""
    a = _snapshot_with_gauges(gauges={"serving_pp_bubble_fraction": 0.20})
    b = _snapshot_with_gauges(gauges={"serving_pp_bubble_fraction": 0.45})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_pp_bubble_fraction") == \
        "pipeline-serving bubble fraction grew"
    assert metrics_report.compare_counters(a, a) == []
    assert metrics_report.compare_counters(b, a) == []
    pa, pb = str(tmp_path / "pa.jsonl"), str(tmp_path / "pb.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools", "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "bubble fraction grew" in bad.stdout


def test_validate_record_catches_rot():
    good = {"schema": perf_report.SCHEMA, "step": 0, "step_ms": 1.0,
            "phases": {"Forward": 1.0}, "ops": [], "num_samples": None,
            "mem_peak_bytes": None}
    assert perf_report.validate_record(good) == []
    assert perf_report.validate_record({}) != []
    bad = dict(good, phases={"Forward": -1.0})
    assert perf_report.validate_record(bad) != []
    bad = dict(good, ops=[{"name": "x"}])       # missing calls/total_ms
    assert perf_report.validate_record(bad) != []
    bad = dict(good, schema="other.v9")
    assert perf_report.validate_record(bad) != []


def _snapshot_with_hist(counters, hists):
    """Valid snapshot with counters plus histogram samples given as
    {name: {bucket_edge: cumulative_count}} (+Inf must be present)."""
    rec = _snapshot_with(counters)
    for name, buckets in hists.items():
        count = buckets["+Inf"]
        mean_edge = max((float(e) for e in buckets if e != "+Inf"),
                        default=1.0)
        rec["metrics"].append(
            {"name": name, "type": "histogram", "help": "",
             "labelnames": [],
             "samples": [{"labels": {}, "buckets": buckets,
                          "sum": mean_edge * count, "count": count}]})
    return rec


def test_metrics_compare_flags_failover_and_swap_drops(tmp_path):
    """ISSUE 10 gate: serving_failover_total growth (requests re-routed
    off dead hosts) and ANY serving_swap_dropped_requests_total growth
    (a hot-swap that dropped traffic — zero by construction) are
    failure-class regressions."""
    a = _snapshot_with({"serving_failover_total": 0,
                        "serving_swap_dropped_requests_total": 0,
                        "serving_tokens_total": 1000})
    b = _snapshot_with({"serving_failover_total": 4,
                        "serving_swap_dropped_requests_total": 2,
                        "serving_tokens_total": 1000})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why["serving_failover_total"] == "failure counter grew"
    assert why["serving_swap_dropped_requests_total"] == \
        "failure counter grew"
    assert metrics_report.compare_counters(a, a) == []
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_failover_total" in bad.stdout


def test_metrics_compare_flags_kv_handoff_p99_regression(tmp_path):
    """ISSUE 10 gate: the serving_kv_handoff_seconds approximate p99
    (from cumulative buckets) GROWING past the threshold is
    failure-class — a handoff-latency tail stalls decode admission even
    when every transfer succeeds. Same-tail traffic growth passes."""
    fast = {"0.005": 90, "0.01": 99, "0.05": 100, "+Inf": 100}
    slow = {"0.005": 10, "0.01": 30, "0.05": 99, "+Inf": 100}
    a = _snapshot_with_hist({"serving_tokens_total": 100},
                            {"serving_kv_handoff_seconds": fast})
    b = _snapshot_with_hist({"serving_tokens_total": 100},
                            {"serving_kv_handoff_seconds": slow})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_kv_handoff_seconds:p99") == \
        "KV handoff p99 grew", regs
    # same shape at 10x the traffic: the p99 is unchanged -> clean
    fast10 = {k: v * 10 for k, v in fast.items()}
    c = _snapshot_with_hist({"serving_tokens_total": 1000},
                            {"serving_kv_handoff_seconds": fast10})
    assert not any(w == "KV handoff p99 grew" for *_, w in
                   metrics_report.compare_counters(a, c))
    # an unrelated histogram's tail moving is NOT gated
    d = _snapshot_with_hist({"serving_tokens_total": 100},
                            {"serving_decode_step_seconds": slow})
    e = _snapshot_with_hist({"serving_tokens_total": 100},
                            {"serving_decode_step_seconds": fast})
    assert not any("p99" in k for k, *_ in
                   metrics_report.compare_counters(d, e))
    # and the CLI gate exits nonzero on the regression
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_kv_handoff_seconds:p99" in bad.stdout


def _snapshot_with_labeled_gauges(gauges):
    """Minimal valid metrics.v1 snapshot of labeled gauges:
    {name: [(labels, value), ...]}."""
    return {"schema": metrics_report.SCHEMA, "ts": 1.0, "pid": 1,
            "metrics": [
                {"name": n, "type": "gauge", "help": "",
                 "labelnames": sorted(samples[0][0]),
                 "samples": [{"labels": dict(lbl), "value": v}
                             for lbl, v in samples]}
                for n, samples in gauges.items()]}


def test_metrics_compare_gates_slo_burn_through_cli(tmp_path):
    """ISSUE 12 gate, through the CLI: `serving_slo_burn` crossing 1.0
    from a clean baseline and a `serving_slo_degraded` 0 -> 1 flip are
    failure-class — zero baselines, where every percentage rule must
    skip, are exactly where the watchdog gauges live in a healthy run.
    Burn GROWTH from a nonzero baseline trips the percentage rule."""
    burn = ("serving_slo_burn", ({"slo": "ttft", "window": "fast"},))
    healthy = _snapshot_with_labeled_gauges({
        "serving_slo_burn": [(burn[1][0], 0.0)],
        "serving_slo_degraded": [({}, 0.0)]})
    breached = _snapshot_with_labeled_gauges({
        "serving_slo_burn": [(burn[1][0], 25.0)],
        "serving_slo_degraded": [({}, 1.0)]})
    regs = metrics_report.compare_counters(healthy, breached)
    why = {k.split("{")[0]: w for k, *_, w in regs}
    assert "serving_slo_burn" in why and "serving_slo_degraded" in why
    assert metrics_report.compare_counters(healthy, healthy) == []
    # sub-1.0 burn from a clean baseline stays clean (budget not yet
    # consumed faster than allowed); degraded flips on ANY nonzero
    warm = _snapshot_with_labeled_gauges({
        "serving_slo_burn": [(burn[1][0], 0.5)],
        "serving_slo_degraded": [({}, 0.0)]})
    assert metrics_report.compare_counters(healthy, warm) == []
    # nonzero-baseline growth rides the percentage rule
    grown = _snapshot_with_labeled_gauges({
        "serving_slo_burn": [(burn[1][0], 2.0)],
        "serving_slo_degraded": [({}, 0.0)]})
    assert any(w == "SLO burn rate grew" for *_, w in
               metrics_report.compare_counters(warm, grown))
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, healthy), (pb, breached)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_slo_degraded" in bad.stdout
    assert "serving_slo_burn" in bad.stdout


def test_metrics_compare_tenant_membership_and_per_tenant_rules(tmp_path):
    """ISSUE 15 gate, through the CLI: per-tenant shed growth and a
    per-tenant SLO-burn flip fire on exactly the tenant that regressed;
    a tenant present in only one run is MEMBERSHIP-SKIPPED (the PR 12
    worker-intersection machinery generalized to the tenant dimension),
    and the `_all` (unscoped) SLO rows always participate."""
    a = _snapshot_with_labeled({
        "serving_shed_total": [({"tenant": "a"}, 2.0),
                               ({"tenant": "b"}, 2.0)],
        "serving_tokens_total": [({"tenant": "a"}, 1000.0),
                                 ({"tenant": "b"}, 1000.0)]})
    b = _snapshot_with_labeled({
        "serving_shed_total": [({"tenant": "a"}, 2.0),
                               ({"tenant": "b"}, 40.0),
                               ({"tenant": "c"}, 50.0)],
        "serving_tokens_total": [({"tenant": "a"}, 1000.0),
                                 ({"tenant": "b"}, 1000.0),
                                 ({"tenant": "c"}, 5.0)]})
    regs = metrics_report.compare_counters(a, b)
    keys = [k for k, *_ in regs]
    assert "serving_shed_total{tenant=b}" in keys          # the regressor
    assert not any("tenant=a" in k for k in keys)          # healthy tenant
    # tenant c exists only in B (onboarded between runs): its series
    # must not read as failure counters appearing from zero
    assert not any("tenant=c" in k for k in keys), keys
    # per-tenant burn flip from a clean baseline + the _all row's growth
    ga = _snapshot_with_labeled_gauges({"serving_slo_burn": [
        ({"slo": "ttft", "window": "fast", "tenant": "a"}, 0.0),
        ({"slo": "ttft", "window": "fast", "tenant": "b"}, 0.0),
        ({"slo": "ttft", "window": "fast", "tenant": "_all"}, 0.5)]})
    gb = _snapshot_with_labeled_gauges({"serving_slo_burn": [
        ({"slo": "ttft", "window": "fast", "tenant": "a"}, 0.2),
        ({"slo": "ttft", "window": "fast", "tenant": "b"}, 30.0),
        ({"slo": "ttft", "window": "fast", "tenant": "_all"}, 2.0)]})
    gregs = metrics_report.compare_counters(ga, gb, min_delta=0.01)
    gkeys = [k for k, *_ in gregs]
    assert any("tenant=b" in k for k in gkeys), gkeys      # b crossed 1.0
    assert any("tenant=_all" in k for k in gkeys), gkeys   # _all grew
    assert not any(",tenant=a," in k for k in gkeys), gkeys
    # the CLI exit code reflects the per-tenant gate
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_shed_total{tenant=b}" in bad.stdout


def test_metrics_compare_flags_rate_limit_and_ns_eviction_growth(tmp_path):
    """ISSUE 17 gate, through the CLI: serving_rate_limited_total{tenant}
    and serving_prefix_ns_evicted_total{namespace} growth are
    failure-class. Membership intersection covers BOTH label dimensions:
    a tenant (or namespace) present in only one run is skipped — churn in
    the tenant roster must not read as counters appearing from zero."""
    a = _snapshot_with_labeled({
        "serving_rate_limited_total": [({"tenant": "a"}, 1.0),
                                       ({"tenant": "b"}, 1.0)],
        "serving_prefix_ns_evicted_total": [({"namespace": "ns-a"}, 2.0)],
        "serving_tokens_total": [({}, 1000.0)]})
    b = _snapshot_with_labeled({
        "serving_rate_limited_total": [({"tenant": "a"}, 1.0),
                                       ({"tenant": "b"}, 40.0),
                                       ({"tenant": "c"}, 99.0)],
        "serving_prefix_ns_evicted_total": [({"namespace": "ns-a"}, 30.0),
                                            ({"namespace": "ns-new"}, 50.0)],
        "serving_tokens_total": [({}, 1000.0)]})
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_rate_limited_total{tenant=b}") == \
        "failure counter grew"
    assert why.get("serving_prefix_ns_evicted_total{namespace=ns-a}") == \
        "failure counter grew"
    keys = list(why)
    # the regressors fire on exactly the member that regressed...
    assert not any("tenant=a" in k for k in keys)
    # ...and roster churn (tenant c / ns-new exist only in B) is skipped
    assert not any("tenant=c" in k for k in keys), keys
    assert not any("ns-new" in k for k in keys), keys
    assert metrics_report.compare_counters(a, a) == []
    # the CLI exit code reflects the gate and names the labeled series
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_rate_limited_total{tenant=b}" in bad.stdout
    assert "serving_prefix_ns_evicted_total{namespace=ns-a}" in bad.stdout


def test_metrics_compare_flags_kv_tier_regressions(tmp_path):
    """ISSUE 18 gate, all three failure-class rules of the KV memory
    hierarchy: a per-tier hit-RATE drop (the generic hits/misses pair,
    per tier label — fires even when hit counts grew with traffic),
    serving_kv_restore_seconds p99 growth (promotion losing its race
    against recompute), and corrupt/drop counter growth (corrupt from a
    zero baseline — a single verify failure gates)."""
    fast = {"0.005": 95, "0.01": 99, "0.05": 100, "+Inf": 100}
    slow = {"0.005": 5, "0.01": 40, "0.05": 99, "+Inf": 100}

    def snap(hits, misses, drops, corrupt, buckets):
        rec = _snapshot_with_labeled(
            {"serving_kv_tier_hits_total": [({"tier": "host"}, hits)],
             "serving_kv_tier_misses_total": [({"tier": "host"}, misses)],
             "serving_kv_tier_drop_total": [({"tier": "host"}, drops)]})
        rec["metrics"].append(
            {"name": "serving_kv_tier_corrupt_total", "type": "counter",
             "help": "", "labelnames": [],
             "samples": [{"labels": {}, "value": corrupt}]})
        count = buckets["+Inf"]
        rec["metrics"].append(
            {"name": "serving_kv_restore_seconds", "type": "histogram",
             "help": "", "labelnames": [],
             "samples": [{"labels": {}, "buckets": buckets,
                          "sum": 0.01 * count, "count": count}]})
        return rec

    a = snap(hits=80, misses=20, drops=0, corrupt=0, buckets=fast)
    b = snap(hits=100, misses=100,       # hits grew, rate 0.8 -> 0.5
             drops=6, corrupt=2, buckets=slow)
    regs = metrics_report.compare_counters(a, b)
    why = {k: w for k, *_, w in regs}
    assert why.get("serving_kv_tier_hit_rate{tier=host}") \
        == "hit rate dropped", regs
    assert why.get("serving_kv_tier_corrupt_total") \
        == "failure counter grew", regs
    assert why.get("serving_kv_tier_drop_total{tier=host}") \
        == "failure counter grew", regs
    assert why.get("serving_kv_restore_seconds:p99") \
        == "KV tier restore p99 grew", regs
    # identical runs stay clean; traffic growth at the same rate and
    # tail fires neither the rate rule nor the p99 rule (the raw miss
    # counter growing 10x with traffic is the failure-counter rule's
    # business, same as every other hits/misses family)
    assert metrics_report.compare_counters(a, a) == []
    c = snap(hits=800, misses=200, drops=0, corrupt=0,
             buckets={k: v * 10 for k, v in fast.items()})
    assert not any(w in ("hit rate dropped", "KV tier restore p99 grew")
                   for *_, w in metrics_report.compare_counters(a, c))
    # and the CLI gate exits nonzero on the regressed run
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "serving_kv_tier_corrupt_total" in bad.stdout
