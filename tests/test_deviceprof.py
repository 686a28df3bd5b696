"""ISSUE 9: the device-profile closed loop, validated against REAL output.

The XPlane half of the profiler had never produced a validated artifact
(VERDICT weak #21: xplane_summary.py untested, zero captures in two
rounds). These tests run the ENTIRE pipeline on the CPU backend — a real
`jax.profiler.trace` capture of a real jitted step, the typed parser
over the real `.xplane.pb`, the deviceprof.v1 JSONL round-trip, and the
cost-model join — plus the orchestration: `bench.py --xplane` end to
end, the wedged-run postmortem carrying the armed-but-unfired capture,
and the serving scheduler's capture-N-decode-steps hook.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import _jax_compat
from paddle_tpu.cost_model import analytical
from paddle_tpu.observability import deviceprof, flight_recorder

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import perf_report  # noqa: E402


def _step_fn():
    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w).sum()
    return step


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One real CPU capture of a tiny jitted step, parsed+joined once for
    the whole module: (record, cost-model per-op dict, out_dir)."""
    out = str(tmp_path_factory.mktemp("xplane"))
    step = _step_fn()
    x = jnp.ones((64, 64))
    w = jnp.ones((64, 64))
    step(x, w).block_until_ready()          # compile OUTSIDE the window
    _, rec = deviceprof.capture(lambda: step(x, w), out, iters=3)
    rep = analytical.estimate(step, x, w, device="cpu")
    per_op = {name: 1e3 * rep.device.roofline_s(c.flops, c.bytes)
              for name, c in rep.by_op.items()}
    deviceprof.join_cost_model(rec, per_op, steps=3)
    return rec, per_op, out


# ------------------------------------------------------- capture + parse

def test_capture_parses_real_device_events(capture):
    """The parser finds real XLA op events in a CPU-backend capture: a
    matmul step must surface a `dot` op with nonzero device time."""
    rec, _, _ = capture
    assert rec["schema"] == deviceprof.SCHEMA
    assert rec["decoder"] in ("purepy", "native")
    assert rec["total_device_ms"] > 0
    assert rec["n_events"] > 0
    ops = {o["op"]: o for o in rec["ops"]}
    assert "dot" in ops, f"no dot op in {sorted(ops)}"
    assert ops["dot"]["device_ms"] > 0
    assert ops["dot"]["calls"] >= 3                 # one per traced iter
    assert ops["dot"]["prim"] == "dot_general"      # HLO -> framework op
    assert ops["dot"]["hlo_module"] and "jit" in ops["dot"]["hlo_module"]
    # fractions form a distribution over the chosen lanes
    assert abs(sum(o["frac"] for o in rec["ops"]) - 1.0) < 1e-3


def test_line_normalization_rejects_python_lane(capture):
    """The hardened pick rule: the python tracer lane (whose top event is
    the multi-second trace context itself) must never be the device
    lane — the old inline 'largest total' rule picked exactly that."""
    rec, _, out = capture
    assert "python" not in rec["line"].lower()
    assert rec["line_rule"] in ("hlo_stats", "xla_ops")
    # and the python lane IS the largest-total line of the plane, so the
    # legacy rule would have chosen it: prove the hazard is real
    planes, _ = deviceprof._load_planes(deviceprof.find_xplane(out))
    plane = next(p for p in planes
                 if any(ln.name == "python" for ln in p.lines))
    largest = max((ln for ln in plane.lines
                   if deviceprof._line_total_ns(ln) > 0),
                  key=deviceprof._line_total_ns)
    assert largest.name == "python"


def _fake(name, events=(), lines=None):
    class _Obj:
        pass
    o = _Obj()
    o.name = name
    if lines is not None:
        o.lines = lines
    else:
        o.events = list(events)
    return o


def _ev(name, dur_ns, offset_ns=0, stats=None):
    class _E:
        pass
    e = _E()
    e.name = name
    e.duration_ns = dur_ns
    e.offset_ns = offset_ns
    e.occurrences = 1
    e.stats = stats or {}
    return e


def test_pick_lines_rules_synthetic():
    """Rule order on synthetic planes: 'XLA Ops' wins exactly once (TPU
    hierarchy lanes are parallel views of the same nanoseconds); hlo-stat
    thread lanes are ALL kept (disjoint work); host-only traces fall back
    to largest-total and say so."""
    xla_ops = _fake("XLA Ops", [_ev("fusion.1", 100)])
    steps = _fake("Steps", [_ev("step 0", 1000)])
    fw = _fake("Framework Ops", [_ev("jit(step)", 1000)])
    tpu_plane = _fake("/device:TPU:0", lines=[steps, xla_ops, fw])
    picked = deviceprof.pick_lines(tpu_plane)
    assert [(ln.name, rule) for ln, rule in picked] == \
        [("XLA Ops", "xla_ops")]

    hlo = {"hlo_op": "dot.1", "hlo_module": "jit_step"}
    t1 = _fake("tf_XLA/1", [_ev("dot.1", 500, stats=hlo)])
    t2 = _fake("tf_XLA/2", [_ev("dot.2", 100, stats=hlo)])
    python = _fake("python", [_ev("$trace", 10_000_000)])
    cpu_plane = _fake("/host:CPU", lines=[python, t1, t2])
    picked = deviceprof.pick_lines(cpu_plane)
    assert [(ln.name, rule) for ln, rule in picked] == \
        [("tf_XLA/1", "hlo_stats"), ("tf_XLA/2", "hlo_stats")]

    host_only = _fake("/host:CPU", lines=[python])
    (line, rule), = deviceprof.pick_lines(host_only)
    assert rule == "largest_total"
    # ...and device_planes refuses a host-only CPU plane entirely
    assert deviceprof.device_planes([host_only]) == []


def test_self_time_unnests_containers():
    """`while`/`call` container events enclose their body ops on the SAME
    lane (measured: 1161/1501 events nested on a real capture) — the
    aggregation must count self time, not re-count the body."""
    hlo = {"hlo_op": "x"}
    events = [
        _ev("while.1", 1000, offset_ns=0, stats=hlo),
        _ev("dot.1", 600, offset_ns=100, stats=hlo),
        _ev("add.1", 200, offset_ns=700, stats=hlo),
        _ev("dot.2", 300, offset_ns=1200, stats=hlo),  # sibling after
    ]
    line = _fake("tf_XLA/1", events)
    ops, _, _ = deviceprof._aggregate(line, "hlo_stats")
    assert ops["dot"]["device_ns"] == 900          # 600 + 300, unchanged
    assert ops["add"]["device_ns"] == 200
    assert ops["while"]["device_ns"] == 200        # 1000 - 600 - 200
    total = sum(r["device_ns"] for r in ops.values())
    assert total == 1300                           # union, not 2100


def test_hlo_base_name_normalization():
    assert deviceprof.hlo_base_name("dot.4") == "dot"
    assert deviceprof.hlo_base_name("%loop_fusion.3") == "loop_fusion"
    assert deviceprof.hlo_base_name(
        "divide_subtract_fusion.5.clone") == "divide_subtract_fusion"
    assert deviceprof.hlo_base_name("reduce-window") == "reduce-window"
    assert deviceprof.hlo_to_prim("dot") == "dot_general"
    assert deviceprof.hlo_to_prim("loop_fusion") is None


# --------------------------------------------------- schema + round-trip

def test_jsonl_round_trip_through_schema(capture, tmp_path):
    rec, _, _ = capture
    assert deviceprof.validate_record(rec) == []
    path = str(tmp_path / "deviceprof.jsonl")
    deviceprof.write_record(rec, path)
    loaded = deviceprof.load_records(path)
    assert len(loaded) == 1
    assert loaded[0] == json.loads(json.dumps(rec))   # JSON-stable
    # the offline tool cross-validates with its OWN independent validator
    recs2 = perf_report.load_deviceprof(path)
    assert perf_report.validate_deviceprof_record(recs2[-1]) == []
    md = perf_report.render_deviceprof(recs2)
    assert "dot" in md and "device profile" in md


def test_schema_catches_rot(capture, tmp_path):
    rec, _, _ = capture
    bad = dict(rec, schema="other.v9")
    assert deviceprof.validate_record(bad) != []
    bad = dict(rec, ops=[])
    assert deviceprof.validate_record(bad) != []
    bad = dict(rec, ops=[{"op": "dot"}])          # missing calls/ms/frac
    assert deviceprof.validate_record(bad) != []
    with pytest.raises(ValueError):
        deviceprof.write_record(bad, str(tmp_path / "x.jsonl"))
    good_path = str(tmp_path / "ok.jsonl")
    deviceprof.write_record(rec, good_path)
    with open(good_path, "a") as f:
        f.write(json.dumps(dict(rec, total_device_ms=-1)) + "\n")
    with pytest.raises(ValueError):
        deviceprof.load_records(good_path)


# ------------------------------------------------------------- the join

def test_join_produces_nonzero_efficiency_and_reconciles(capture):
    """The closed loop's deliverable: at least one per-op row joins a
    measured device time to a cost-model prediction with a nonzero
    efficiency, and the device total reconciles against the host wall
    window (device <= wall)."""
    rec, per_op, _ = capture
    join = rec["join"]
    assert join["steps"] == 3
    assert join["device_ms_per_step"] > 0
    assert join["host_window_ms"] > 0
    assert join["device_wall_ratio"] is not None
    assert join["reconciles"], \
        f"device {join['device_ms_per_step']} > wall " \
        f"{join['wall_ms_per_step']} ms/step"
    dot = next(r for r in join["per_op"] if r["op"] == "dot")
    assert dot["predicted_ms"] == pytest.approx(per_op["dot_general"],
                                                rel=1e-3)
    assert dot["efficiency"] is not None and dot["efficiency"] > 0
    assert 0 < join["coverage"] <= 1.0


def test_join_gauges_exported(capture):
    from paddle_tpu.observability import metrics
    deviceprof.export_gauges(capture[0])
    flat = metrics.flatten_snapshot(metrics.registry().snapshot())
    assert flat["deviceprof_total_device_ms_per_step"] > 0
    assert 0 < flat["deviceprof_device_wall_ratio"] <= 1.0
    assert flat["deviceprof_min_op_efficiency"] > 0
    assert any(k.startswith("deviceprof_op_efficiency{op=dot")
               for k in flat), sorted(flat)


# --------------------------------------------------- compat seam satellite

def test_profile_data_is_the_installed_spelling():
    """No version shim: profile_data() IS jax.profiler.ProfileData's one
    installed constructor."""
    from jax.profiler import ProfileData
    assert _jax_compat.profile_data() == ProfileData.from_file


def test_parser_works_without_native_binding(capture):
    """Whatever the jax version, the purepy decoder must parse the real
    capture (it is the floor the pipeline stands on)."""
    _, _, out = capture
    from paddle_tpu.observability import xplane
    space = xplane.XSpace.from_file(deviceprof.find_xplane(out))
    assert any("hlo_op" in ev.stats
               for p in space.planes for ln in p.lines for ev in ln.events)


# ------------------------------------------------ xplane_summary thin CLI

def test_xplane_summary_cli_over_real_capture(capture, tmp_path):
    _, _, out = capture
    jsonl = str(tmp_path / "cli.jsonl")
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "xplane_summary.py"),
         out, "5", "--jsonl", jsonl],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "| dot |" in proc.stdout
    assert "device profile" in proc.stdout
    perf_report.load_deviceprof(jsonl)        # schema-valid artifact


def test_xplane_summary_cli_fails_loudly(tmp_path):
    """An empty/absent capture exits NONZERO with the reason — the
    silently-empty xplane_top_ops.md failure mode is closed."""
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "xplane_summary.py"),
         empty],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "FAILED" in proc.stderr
    assert "no .xplane.pb" in proc.stderr


# -------------------------------------------- bench --xplane orchestration

_BENCH_ENV = dict(
    JAX_PLATFORMS="cpu",
    BENCH_B="2", BENCH_S="64", BENCH_LAYERS="2", BENCH_HIDDEN="64",
    BENCH_HEADS="4", BENCH_VOCAB="512")


@pytest.fixture(scope="module")
def bench_xplane(tmp_path_factory):
    out_dir = str(tmp_path_factory.mktemp("bench_xplane"))
    env = dict(os.environ, **_BENCH_ENV)
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"),
         "--xplane", out_dir, "--steps", "2"],
        capture_output=True, text=True, timeout=480, cwd=_ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    return out_dir, rec


def test_bench_xplane_produces_validated_artifacts(bench_xplane):
    """Acceptance: `bench.py --xplane` on CPU produces a real .xplane.pb,
    a schema-valid deviceprof.v1 JSONL, and a join report whose device
    times reconcile (device <= wall) with predicted-vs-measured rows."""
    out_dir, rec = bench_xplane
    assert "error" not in rec, rec
    dp = rec["extra"]["deviceprof"]
    assert dp["state"] == "reported"
    assert os.path.exists(dp["xplane"])
    assert dp["xplane"].endswith(".xplane.pb")
    assert os.path.dirname(dp["jsonl"]) == out_dir
    records = deviceprof.load_records(dp["jsonl"])   # raises on rot
    join = records[-1]["join"]
    assert join["reconciles"], join
    assert dp["reconciles"]
    assert dp["total_device_ms"] > 0
    assert dp["device_wall_ratio"] <= 1.0
    # top-k ops carry predicted-vs-measured rows, joined to the SAME
    # cost-model block the bench emits
    assert rec["extra"]["cost_model"]["per_op"]
    effs = [r for r in dp["top_ops"] if r["efficiency"] is not None]
    assert effs, dp["top_ops"]
    dot = next(r for r in dp["top_ops"] if r["prim"] == "dot_general")
    assert dot["predicted_ms"] == pytest.approx(
        rec["extra"]["cost_model"]["per_op"]["dot_general"]["predicted_ms"],
        rel=1e-3)
    # the join report renders
    assert "### join" in open(dp["report"]).read()


def test_bench_xplane_gauges_ride_profile_artifacts(tmp_path):
    """--xplane + --profile in one run: the deviceprof_* gauges land in
    the metrics snapshot artifact, where --compare gates them."""
    import metrics_report
    out_dir = str(tmp_path / "both")
    env = dict(os.environ, **_BENCH_ENV)
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"),
         "--xplane", os.path.join(out_dir, "xplane"), "--profile",
         "--profile-dir", out_dir, "--steps", "2"],
        capture_output=True, text=True, timeout=480, cwd=_ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    snaps = metrics_report.load_snapshots(
        rec["extra"]["profile_artifacts"]["metrics"])
    names = {m["name"] for m in snaps[-1]["metrics"]}
    for g in ("deviceprof_total_device_ms_per_step",
              "deviceprof_device_wall_ratio",
              "deviceprof_op_efficiency"):
        assert g in names, f"{g} missing from {sorted(names)}"


def test_wedged_run_postmortem_records_armed_capture(tmp_path):
    """Acceptance: a run that wedges BEFORE the healthy window leaves the
    armed-but-unfired capture in its postmortem instead of losing it."""
    out_dir = str(tmp_path / "wedged_xplane")
    env = dict(os.environ, **_BENCH_ENV,
               BENCH_INJECT_WEDGE_S="2",
               PADDLE_TPU_POSTMORTEM_DIR=str(tmp_path / "postmortem"))
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"),
         "--xplane", out_dir],
        capture_output=True, text=True, timeout=240, cwd=_ROOT, env=env)
    assert proc.returncode != 0, "a hung rung must fail the process"
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "wedged" in rec["error"]
    pm_path = rec["extra"]["postmortem"]
    pm = json.load(open(pm_path))
    note = pm["annotations"]["deviceprof.bench"]
    assert note["state"] == "armed", note      # armed, never fired
    assert note["dir"] == os.path.abspath(out_dir)
    assert not os.path.exists(os.path.join(out_dir, "deviceprof.jsonl"))
