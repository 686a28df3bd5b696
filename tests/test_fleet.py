"""Fleet observability plane (ISSUE 12): federation, timelines, watchdog.

Acceptance, mapped:
  - metrics federation merges N per-process metrics.v1 snapshots into
    ONE schema-valid fleet snapshot: worker_id/role labels on every
    series, counters + histogram buckets aggregated bucket-wise into
    `_fleet` rows, gauges per-worker only, mismatched bucket edges drop
    the aggregate, and the merged snapshot renders through the SAME
    Prometheus renderer as a single process (test_merge_*);
  - per-request end-to-end timelines: PhaseTrail's contiguous segments
    sum EXACTLY to the e2e span, ttft_breakdown clips to the TTFT
    window, serve_report validates the reqtimeline.v1 contract and
    attributes the p99 tail (test_phase_trail_*, test_timeline_*);
  - the burn-rate watchdog: multi-window burn from cumulative samples,
    sustained-breach latching, one on_breach per episode, recovery
    (test_watchdog_*);
  - FleetPlane: OP_METRICS sweep -> merged jsonl/prom, dark members
    skipped not fatal, sustained breach -> flight-recorder annotation +
    fleet postmortem bundle with unreachable members RECORDED
    (test_plane_* — driven through a stub frontend, no engines);
  - the wire layer in-process: STAT is a thin projection of the same
    registry snapshot OP_METRICS ships, POLL carries worker_phases for
    terminal requests, OP_DUMP round-trips a postmortem
    (test_worker_verbs_*);
  - slow tier: a REAL forked 2-decode-worker fleet federates into one
    snapshot whose per-worker series reconcile with each worker's own
    registry (test_forked_federation_reconciles), and a SIGKILLed
    decode worker drives the failover hop into the victim's timeline as
    a named phase, the SLO burn gauge over threshold, and a fleet
    postmortem bundle holding the router's annotations plus both
    surviving workers' dumps (test_sigkill_chaos_*).
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.observability import fleet, flight_recorder, metrics
from paddle_tpu.observability import reqtimeline as rt
from paddle_tpu.serving import PagedEngineConfig, PagedGenerationEngine
from paddle_tpu.serving.distributed import DistFrontend, ServingWorker
from paddle_tpu.text.models import gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_ROOT, "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import metrics_report  # noqa: E402
import serve_report  # noqa: E402

VOCAB = 1024
WORKER_SEED = 2024


# ---------------------------------------------------------- synth helpers

def _snap(metrics_list, ts=1.0, pid=7):
    return {"schema": "paddle_tpu.metrics.v1", "ts": ts, "pid": pid,
            "metrics": metrics_list}


def _counter(name, value, labels=None):
    return {"name": name, "type": "counter", "help": "h",
            "labelnames": sorted(labels or {}),
            "samples": [{"labels": dict(labels or {}), "value": value}]}


def _gauge(name, value, labels=None):
    return {"name": name, "type": "gauge", "help": "h",
            "labelnames": sorted(labels or {}),
            "samples": [{"labels": dict(labels or {}), "value": value}]}


def _hist(name, buckets, total, count, labels=None):
    return {"name": name, "type": "histogram", "help": "h",
            "labelnames": sorted(labels or {}),
            "samples": [{"labels": dict(labels or {}),
                         "buckets": dict(buckets), "sum": total,
                         "count": count}]}


def _flat(snap, kinds=("counter", "gauge")):
    return metrics.flatten_snapshot(snap, kinds=kinds)


def _members(*snaps):
    return [{"worker_id": f"decode{i}", "role": "decode", "snapshot": s}
            for i, s in enumerate(snaps)]


# ------------------------------------------------------------- federation

def test_merge_labels_counters_and_gauges():
    a = _snap([_counter("serving_tokens_total", 10),
               _gauge("serving_queue_depth", 3)])
    b = _snap([_counter("serving_tokens_total", 5),
               _gauge("serving_queue_depth", 1)])
    merged = fleet.merge_snapshots(_members(a, b))
    assert metrics_report.validate_snapshot(merged) == []
    flat = _flat(merged)
    assert flat["serving_tokens_total{role=decode,worker_id=decode0}"] == 10
    assert flat["serving_tokens_total{role=decode,worker_id=decode1}"] == 5
    # counters aggregate into a _fleet row; gauges stay per-worker only
    assert flat["serving_tokens_total{role=_fleet,worker_id=_fleet}"] == 15
    assert "serving_queue_depth{role=_fleet,worker_id=_fleet}" not in flat
    assert flat["serving_queue_depth{role=decode,worker_id=decode0}"] == 3


def test_merge_histograms_bucketwise():
    a = _snap([_hist("serving_ttft_seconds",
                     {"0.1": 2, "1.0": 4, "+Inf": 5}, 3.0, 5)])
    b = _snap([_hist("serving_ttft_seconds",
                     {"0.1": 1, "1.0": 1, "+Inf": 3}, 5.0, 3)])
    merged = fleet.merge_snapshots(_members(a, b))
    assert metrics_report.validate_snapshot(merged) == []
    fam = [m for m in merged["metrics"]
           if m["name"] == "serving_ttft_seconds"][0]
    agg = [s for s in fam["samples"]
           if s["labels"]["worker_id"] == fleet.FLEET_LABEL]
    assert len(agg) == 1
    # bucket-wise: cumulative counts sum per edge, +Inf == count
    assert agg[0]["buckets"] == {"0.1": 3, "1.0": 5, "+Inf": 8}
    assert agg[0]["count"] == 8 and agg[0]["sum"] == 8.0
    per_worker = [s for s in fam["samples"]
                  if s["labels"]["worker_id"] != fleet.FLEET_LABEL]
    assert len(per_worker) == 2


def test_merge_mismatched_bucket_edges_drop_only_the_aggregate():
    a = _snap([_hist("h", {"0.1": 1, "+Inf": 2}, 1.0, 2)])
    b = _snap([_hist("h", {"0.5": 1, "+Inf": 1}, 0.5, 1)])
    merged = fleet.merge_snapshots(_members(a, b))
    fam = [m for m in merged["metrics"] if m["name"] == "h"][0]
    workers = {s["labels"]["worker_id"] for s in fam["samples"]}
    assert workers == {"decode0", "decode1"}   # no _fleet aggregate
    assert metrics_report.validate_snapshot(merged) == []


def test_merged_prometheus_renders_and_lints():
    merged = fleet.merge_snapshots(_members(
        _snap([_counter("serving_tokens_total", 10),
               _hist("serving_ttft_seconds",
                     {"0.1": 1, "+Inf": 2}, 1.0, 2)]),
        _snap([_counter("serving_tokens_total", 4)])))
    text = metrics.prometheus_from_snapshot(merged)
    assert metrics_report.validate_prometheus(text) == []
    assert 'worker_id="decode1"' in text
    assert 'worker_id="_fleet"' in text


# ---------------------------------------------------------- the watchdog

def _ttft_snap(slow_count, count):
    """A merged-shape snapshot whose TTFT histogram holds `count`
    observations, `slow_count` of them over the 1.0s threshold."""
    fast = count - slow_count
    return fleet.merge_snapshots(_members(_snap([_hist(
        "serving_ttft_seconds",
        {"1.0": fast, "+Inf": count}, float(count), count)])))


class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_watchdog_latency_burn_and_sustained_breach():
    clock = _FakeClock()
    fired = []
    wd = fleet.BurnRateWatchdog(
        slos=[fleet.SLO("ttft", hist="serving_ttft_seconds",
                        threshold_s=1.0, objective=0.99)],
        fast_window_s=10.0, slow_window_s=60.0, burn_threshold=1.0,
        sustain=2, clock=clock, on_breach=fired.append)
    wd.observe(_ttft_snap(0, 100))          # baseline: all fast
    assert wd.last_burn["ttft"]["fast"] == 0.0 and not wd.degraded
    clock.t += 5
    # 50 new observations, every one slow: bad fraction 1.0 / budget
    # 0.01 = burn 100 on both windows -> candidate #1
    wd.observe(_ttft_snap(50, 150))
    assert wd.last_burn["ttft"]["fast"] == pytest.approx(100.0)
    assert not wd.degraded and not fired    # sustain=2: not yet
    clock.t += 5
    wd.observe(_ttft_snap(60, 160))         # candidate #2 -> degraded
    assert wd.degraded and len(fired) == 1
    clock.t += 5
    wd.observe(_ttft_snap(70, 170))         # still burning: latched
    assert wd.degraded and len(fired) == 1  # one breach per episode
    # recovery: the slow window still sees the bad stretch, so jump past
    # it before the all-fast sample
    clock.t += 120
    wd.observe(_ttft_snap(70, 400))
    assert not wd.degraded
    g = _flat(metrics.registry().snapshot())
    assert "serving_slo_burn{slo=ttft,tenant=_all,window=fast}" in g
    assert g["serving_slo_degraded"] == 0.0


def test_watchdog_failure_ratio_slo():
    clock = _FakeClock()
    wd = fleet.BurnRateWatchdog(
        slos=[fleet.SLO("failures", kind="failure", objective=0.999,
                        bad=(r"^serving_failover_total",),
                        total=(r"^serving_requests_total\{.*"
                               r"status=admitted",))],
        fast_window_s=10.0, slow_window_s=60.0, burn_threshold=1.0,
        sustain=1, clock=clock)

    def snap(failovers, admitted):
        return fleet.merge_snapshots(_members(_snap([
            _counter("serving_failover_total", failovers),
            _counter("serving_requests_total", admitted,
                     {"status": "admitted"})])))

    wd.observe(snap(0, 10))
    assert not wd.degraded
    clock.t += 5
    wd.observe(snap(2, 14))                 # 2 bad / 4 total / 0.001
    assert wd.degraded
    assert wd.last_burn["failures"]["fast"] == pytest.approx(500.0)


# ----------------------------------------------------- trails & timelines

def test_phase_trail_sums_exactly_and_rel():
    tr = rt.PhaseTrail()
    tr.begin(rt.PH_QUEUE, 10.0)
    tr.close(10.5)                          # seal queue at prefill start
    tr.append(rt.PH_PREFILL, 10.5, 11.0)    # measured closed intervals,
    tr.append(rt.PH_KV_HANDOFF, 11.0, 11.25)  # router-style
    tr.begin(rt.PH_DECODE, 11.25)           # nothing open: plain open
    tr.close(12.0)
    rel = tr.rel(10.0)
    assert [s["phase"] for s in rel] == ["queue", "prefill",
                                         "kv_handoff", "decode"]
    assert sum(s["dur_s"] for s in rel) == pytest.approx(2.0, abs=1e-9)
    assert rel[0] == {"phase": "queue", "t0": 0.0, "dur_s": 0.5}
    # begin/close share boundary timestamps: contiguity is structural
    for a, b in zip(rel, rel[1:]):
        assert a["t0"] + a["dur_s"] == pytest.approx(b["t0"])


def test_ttft_breakdown_clips_to_first_token():
    rec = rt.build_record(
        "DONE", 0.0, 2.0, [
            {"phase": "queue", "t0": 0.0, "dur_s": 0.2},
            {"phase": "prefill", "t0": 0.2, "dur_s": 0.3},
            {"phase": "decode", "t0": 0.5, "dur_s": 1.5}],
        tokens=4, ttft_s=0.6)
    parts = rt.ttft_breakdown(rec)
    assert parts == {"queue": pytest.approx(0.2),
                     "prefill": pytest.approx(0.3),
                     "first_decode": pytest.approx(0.1)}
    assert rt.ttft_breakdown(rt.build_record(
        "TIMEOUT", 0.0, 1.0, [], ttft_s=None)) is None


def test_timeline_validation_catches_bad_records():
    good = rt.build_record(
        "DONE", 0.0, 1.0, [{"phase": "queue", "t0": 0.0, "dur_s": 0.4},
                           {"phase": "decode", "t0": 0.4, "dur_s": 0.6}],
        tokens=3, ttft_s=0.5, failovers=1)
    assert serve_report.validate_records([good]) == []
    drifted = json.loads(json.dumps(good))
    drifted["phases"][1]["dur_s"] = 0.2     # sums to 0.6 vs e2e 1.0
    assert any("sum" in e for e in serve_report.validate_records([drifted]))
    alien = json.loads(json.dumps(good))
    alien["phases"][0]["phase"] = "warp"
    assert any("unknown phase" in e
               for e in serve_report.validate_records([alien]))


def test_tail_attribution_names_the_dominant_phase():
    def rec(queue, decode):
        return rt.build_record(
            "DONE", 0.0, queue + decode,
            [{"phase": "queue", "t0": 0.0, "dur_s": queue},
             {"phase": "decode", "t0": queue, "dur_s": decode}],
            tokens=2, ttft_s=queue)
    tls = [rec(0.01, 0.1)] * 9 + [rec(5.0, 0.1)]
    tail = serve_report.tail_attribution(tls, q=0.99)
    assert tail["dominant"] == "queue"
    assert tail["share"]["queue"] > 0.9
    means = serve_report.timeline_phase_means(tls)
    assert set(means) == {"queue", "decode"}


# ------------------------------------------------- label-aware comparison

def test_compare_skips_members_absent_from_one_side():
    a = fleet.merge_snapshots(_members(
        _snap([_counter("serving_tokens_total", 100)]),
        _snap([_counter("serving_tokens_total", 100)])))
    b = fleet.merge_snapshots(_members(
        _snap([_counter("serving_tokens_total", 180)])))
    # decode1 died before run B: its work series must not read as
    # "shrank to zero"; the _fleet aggregate still compares
    regs = metrics_report.compare_counters(a, b)
    assert not [r for r in regs if "decode1" in r[0]], regs


def test_compare_flags_burn_growth_and_degraded_flip():
    a = fleet.merge_snapshots(_members(_snap([
        _gauge("serving_slo_degraded", 0.0),
        _gauge("serving_slo_burn", 0.0, {"slo": "ttft",
                                         "window": "fast"})])))
    b = fleet.merge_snapshots(_members(_snap([
        _gauge("serving_slo_degraded", 1.0),
        _gauge("serving_slo_burn", 40.0, {"slo": "ttft",
                                          "window": "fast"})])))
    regs = metrics_report.compare_counters(a, b)
    why = {r[0].split("{")[0]: r[4] for r in regs}
    assert "serving_slo_degraded" in why
    assert "serving_slo_burn" in why


# ------------------------------------------------- the plane (stub fleet)

class _StubClient:
    """Duck-typed ServingShardClient: canned OP_METRICS/OP_DUMP replies,
    with per-index failure injection (a dark host raises)."""

    def __init__(self, snaps, dark=()):
        self.endpoints = [f"stub:{i}" for i in range(len(snaps))]
        self.snaps = snaps
        self.dark = set(dark)
        self.dump_calls = []

    def metrics(self, i):
        if i in self.dark:
            raise ConnectionError("dark host")
        return {"role": "decode", "snapshot": self.snaps[i]}

    def dump(self, i, reason=""):
        self.dump_calls.append((i, reason))
        if i in self.dark:
            raise ConnectionError("dark host")
        return {"role": "decode", "path": f"/remote/{i}.json",
                "postmortem": {"schema": "paddle_tpu.postmortem.v1",
                               "reason": reason, "worker": i}}


class _StubFrontend:
    def __init__(self, client):
        self.decode = client
        self.prefill = None
        self.fleet_plane = None

    def live_decode_workers(self):
        return list(range(len(self.decode.endpoints)))


def test_plane_polls_merges_and_streams(tmp_path):
    snaps = [_snap([_counter("serving_tokens_total", 7)]),
             _snap([_counter("serving_tokens_total", 9)])]
    fe = _StubFrontend(_StubClient(snaps, dark={1}))
    plane = fleet.FleetPlane(
        fe, jsonl_path=str(tmp_path / "fleet.jsonl"),
        poll_interval_s=0.0)
    assert fe.fleet_plane is plane          # pump() hook attached
    merged = plane.poll_now()
    flat = _flat(merged)
    # the dark member is skipped, not fatal; the router's own registry
    # federates as member "router"
    assert flat["serving_tokens_total{role=decode,worker_id=decode0}"] == 7
    assert "serving_tokens_total{role=decode,worker_id=decode1}" not in flat
    assert any(k.endswith("worker_id=router}") for k in flat)
    recs = metrics_report.load_snapshots(str(tmp_path / "fleet.jsonl"))
    assert len(recs) == 1
    assert metrics_report.validate_prometheus(plane.prometheus()) == []


def test_plane_breach_annotates_and_bundles(tmp_path):
    """A sustained burn drives on_breach: flight-recorder annotation +
    a fleet postmortem bundle holding every reachable worker's dump and
    RECORDING the unreachable one."""
    failovers = {"n": 0}

    class _Client(_StubClient):
        def metrics(self, i):
            if i in self.dark:
                raise ConnectionError("dark host")
            return {"role": "decode", "snapshot": _snap([
                _counter("serving_failover_total", failovers["n"]),
                _counter("serving_requests_total",
                         10 + 2 * failovers["n"],
                         {"status": "admitted"})])}

    fe = _StubFrontend(_Client([None, None], dark={1}))
    clock = _FakeClock()
    wd = fleet.BurnRateWatchdog(
        slos=[fleet.SLO("failures", kind="failure", objective=0.999,
                        bad=(r"^serving_failover_total",),
                        total=(r"^serving_requests_total\{.*"
                               r"status=admitted",))],
        fast_window_s=10.0, slow_window_s=60.0, sustain=1, clock=clock)
    rec = flight_recorder.get()
    rec.annotations.pop("fleet.slo_breach", None)
    plane = fleet.FleetPlane(fe, watchdog=wd, clock=clock,
                             postmortem_dir=str(tmp_path / "pm"),
                             include_router=False)
    plane.poll_now()                        # baseline
    assert plane.last_bundle is None
    failovers["n"] = 4                      # the incident
    clock.t += 5
    plane.poll_now()
    assert wd.degraded
    bundle = plane.last_bundle
    assert bundle and os.path.isdir(bundle)
    doc = json.load(open(os.path.join(bundle, "bundle.json")))
    assert doc["schema"] == fleet.BUNDLE_SCHEMA
    assert doc["degraded"] is True
    assert "fleet.slo_breach" in doc["router_annotations"]
    by_id = {m["worker_id"]: m for m in doc["members"]}
    assert by_id["decode0"]["ok"] is True
    assert by_id["decode1"]["ok"] is False and by_id["decode1"]["error"]
    member = json.load(open(os.path.join(bundle, "decode0.json")))
    assert member["schema"] == "paddle_tpu.postmortem.v1"
    assert not os.path.exists(os.path.join(bundle, "decode1.json"))


# --------------------------------------------- the wire layer, in-process

@pytest.fixture(scope="module")
def fleet_worker():
    m = gpt_tiny()
    m.eval()
    engine = PagedGenerationEngine(m, PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    w = ServingWorker(m, engine, role="decode")
    fe = DistFrontend([w.endpoint])
    yield w, fe
    fe.stop_workers()
    fe.close()
    w.shutdown()


def test_worker_verbs_stat_projects_the_snapshot(fleet_worker):
    w, fe = fleet_worker
    prompt = np.random.RandomState(3).randint(0, VOCAB, 6).tolist()
    req = fe.submit(prompt, max_new=3)
    fe.run(timeout_s=60)
    assert req.status == "DONE"
    reply = fe.decode.metrics(0)
    assert reply["role"] == "decode"
    snap = reply["snapshot"]
    assert metrics_report.validate_snapshot(snap) == []
    flat = metrics.flatten_snapshot(snap)
    stat = fe.decode.stat(0)
    # STAT == a thin projection of the SAME registry snapshot: no
    # second bookkeeping to drift
    # tenant-labeled families (ISSUE 15): STAT sums the tenant slices
    assert stat["tokens_generated"] == sum(
        v for k, v in flat.items()
        if k.startswith("serving_tokens_total"))
    assert stat["handoff_bytes"] == flat.get(
        "serving_kv_handoff_bytes_total", 0)
    assert stat["requests"]["serving.completed"] == sum(
        v for k, v in flat.items()
        if k.startswith("serving_requests_total{status=completed"))
    # the terminal POLL carried the worker's own phase trail, joined
    # into the router record as worker_phases
    rec = fe.timeline_records()[-1]
    assert serve_report.validate_records([rec]) == []
    assert [s["phase"] for s in rec["worker_phases"]][0] == "queue"
    assert "decode" in {s["phase"] for s in rec["worker_phases"]}
    assert sum(s["dur_s"] for s in rec["phases"]) == pytest.approx(
        rec["e2e_s"], rel=0.05, abs=1e-3)


def test_worker_verbs_dump_roundtrip(fleet_worker, tmp_path):
    w, fe = fleet_worker
    rec = flight_recorder.get()
    old_dir = rec.dir
    rec.dir = str(tmp_path)
    try:
        reply = fe.decode.dump(0, "fleet test")
        assert reply["postmortem"]["schema"] == "paddle_tpu.postmortem.v1"
        assert reply["postmortem"]["reason"] == "fleet test"
        assert os.path.isfile(reply["path"])
    finally:
        rec.dir = old_dir


def test_readonly_verb_contract():
    """The federation sweep rides declared-read-only verbs: METRICS is
    registered readonly (implying idempotent/retry-safe), DUMP is
    idempotent but NOT readonly (it writes an artifact), and no
    mutating serving verb sneaks into READONLY_VERBS."""
    from paddle_tpu.distributed.ps import rpc
    from paddle_tpu.serving.distributed import worker as w
    assert w.OP_METRICS in rpc.READONLY_VERBS
    assert w.OP_METRICS in rpc._IDEMPOTENT_OPS
    assert w.OP_DUMP not in rpc.READONLY_VERBS
    assert w.OP_DUMP in rpc._IDEMPOTENT_OPS
    for op in (w.OP_SUBMIT, w.OP_KV_PUT, w.OP_SWAP, w.OP_PREFILL):
        assert op not in rpc.READONLY_VERBS


# ------------------------------------------------- forked fleets (slow)

def _scrubbed_env(extra=None):
    env = dict(os.environ)
    for k in list(env):
        if (k.startswith(("TPU_", "LIBTPU", "PJRT_"))
                or k in ("JAX_PLATFORM_NAME", "XLA_FLAGS",
                         "JAX_PLATFORMS", "PTN_FAULTS",
                         "PTN_TRACE_EXPORT_DIR")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _ROOT
    env.update(extra or {})
    return env


def _spawn_worker(role, index, ep_file, max_new, env_extra=None):
    return subprocess.Popen(
        [sys.executable, "-m",
         "paddle_tpu.serving.distributed.worker_main",
         "--role", role, "--engine", "paged", "--model", "gpt_tiny",
         "--seed", str(WORKER_SEED), "--index", str(index),
         "--engine-config", json.dumps(
             {"slots": 2, "max_len": 64, "block_size": 8}),
         "--serving-config", json.dumps(
             {"default_max_new_tokens": max_new}),
         "--step-interval", "0.03",
         "--endpoint-file", ep_file],
        env=_scrubbed_env(env_extra), cwd=_ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _await_endpoint(proc, ep_file, deadline_s=180):
    deadline = time.time() + deadline_s
    while not os.path.exists(ep_file):
        if proc.poll() is not None:
            _, err = proc.communicate()
            raise RuntimeError(f"worker died:\n{err[-4000:]}")
        if time.time() > deadline:
            proc.kill()
            raise TimeoutError("worker never published its endpoint")
        time.sleep(0.05)
    with open(ep_file) as f:
        return f.read().strip()


@pytest.mark.slow
def test_forked_federation_reconciles(tmp_path):
    """2 forked decode workers: ONE merged snapshot carries both under
    worker_id labels, and every per-worker-labeled series reconciles
    with that worker's own registry (the member snapshots the sweep
    fetched); histogram buckets sum bucket-wise into the aggregate."""
    procs, eps = [], []
    for i in range(2):
        ep_file = str(tmp_path / f"ep_{i}")
        procs.append(_spawn_worker("decode", i, ep_file, 4))
        eps.append((procs[-1], ep_file))
    try:
        endpoints = [_await_endpoint(p, f) for p, f in eps]
        fe = DistFrontend(endpoints,
                          timeline_path=str(tmp_path / "tl.jsonl"))
        plane = fleet.FleetPlane(
            fe, jsonl_path=str(tmp_path / "fleet.jsonl"),
            poll_interval_s=0.05)
        rng = np.random.RandomState(5)
        tenants = ("acme", "globex")
        reqs = [fe.submit(rng.randint(0, VOCAB, 6).tolist(), max_new=4,
                          tenant=tenants[i % 2])
                for i in range(6)]
        fe.run(timeout_s=120)
        assert all(r.status == "DONE" for r in reqs)
        merged = plane.poll_now()
        flat = _flat(merged)
        members = {m["worker_id"]: m for m in plane.last_members}
        assert {"decode0", "decode1", "router"} <= set(members)
        for wid in ("decode0", "decode1"):
            local = metrics.flatten_snapshot(members[wid]["snapshot"])
            merged_total = sum(
                v for k, v in flat.items()
                if k.startswith("serving_tokens_total{")
                and f"worker_id={wid}" in k)
            local_total = sum(v for k, v in local.items()
                              if k.startswith("serving_tokens_total"))
            assert merged_total == local_total > 0
        # the tenant labelset survives federation (ISSUE 15): each
        # tenant's series keeps worker_id x tenant labels AND gets its
        # own _fleet aggregate row per tenant labelset, summed over
        # every member carrying that tenant
        for t in tenants:
            agg_key = (f"serving_tokens_total{{role=_fleet,tenant={t},"
                       f"worker_id=_fleet}}")
            per_worker = sum(
                v for k, v in flat.items()
                if k.startswith("serving_tokens_total{")
                and f"tenant={t}" in k and "_fleet" not in k)
            assert flat[agg_key] == per_worker > 0

        # histogram buckets: per-(worker, tenant) samples sum
        # BUCKET-WISE into the _fleet row of each tenant labelset
        def _samples(snap, wid=None, tenant=None):
            for m in snap["metrics"]:
                if m["name"] != "serving_ttft_seconds":
                    continue
                return [s for s in m["samples"]
                        if (wid is None or (s.get("labels") or {})
                            .get("worker_id") == wid)
                        and (tenant is None or (s.get("labels") or {})
                             .get("tenant") == tenant)]
            return []
        assert sum(s["count"] for w in ("decode0", "decode1")
                   for s in _samples(members[w]["snapshot"])) == len(reqs)
        for t in tenants:
            aggs = _samples(merged, fleet.FLEET_LABEL, t)
            assert len(aggs) == 1, aggs
            parts = [s for m in plane.last_members
                     for s in _samples(m["snapshot"], tenant=t)]
            assert aggs[0]["count"] == sum(p["count"]
                                           for p in parts) > 0
            for edge, c in aggs[0]["buckets"].items():
                assert c == sum(p["buckets"][edge] for p in parts)
        # the artifacts: schema-valid fleet JSONL + ONE merged prom
        recs = metrics_report.load_snapshots(str(tmp_path / "fleet.jsonl"))
        assert recs
        assert metrics_report.validate_prometheus(
            plane.prometheus()) == []
        tl = [json.loads(x) for x in
              open(tmp_path / "tl.jsonl") if x.strip()]
        assert serve_report.validate_records(tl) == []
        tl_recs = [r for r in tl if r["kind"] == "timeline"]
        assert len(tl_recs) == len(reqs)
        # every timeline record names its tenant; the router's place
        # decisions (interleaved in the same stream) agree with it
        assert {r["tenant"] for r in tl_recs} == set(tenants)
        decs = [r for r in tl if r["kind"] == "decision"]
        assert decs and {d["tenant"] for d in decs} <= set(tenants)
        by_key = {r["key"]: r for r in tl_recs}
        for d in decs:
            if d["key"] in by_key:
                assert d["tenant"] == by_key[d["key"]]["tenant"]
        fe.stop_workers()
        fe.close()
    finally:
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)


@pytest.mark.slow
def test_sigkill_chaos_timeline_burn_and_bundle(tmp_path):
    """THE ISSUE 12 chaos acceptance: SIGKILL a decode worker
    mid-stream. The victim request's timeline carries the failover hop
    as a named phase and still sums to its end-to-end latency; the
    failure-SLO burn gauge crosses threshold; and the breach pulls a
    fleet postmortem bundle holding the router's annotations plus both
    SURVIVING workers' dumps, with the dead host recorded dark."""
    pm_dir = str(tmp_path / "pm")
    procs, eps = [], []
    for i, role in enumerate(("prefill", "decode", "decode")):
        ep_file = str(tmp_path / f"ep_{i}")
        procs.append(_spawn_worker(
            role, i, ep_file, 16,
            {"PADDLE_TPU_POSTMORTEM_DIR": str(tmp_path / f"wpm_{i}")}))
        eps.append((procs[-1], ep_file))
    try:
        endpoints = [_await_endpoint(p, f) for p, f in eps]
        fe = DistFrontend(endpoints[1:], [endpoints[0]],
                          timeline_path=str(tmp_path / "tl.jsonl"))
        clock = time.monotonic
        wd = fleet.BurnRateWatchdog(
            slos=[fleet.SLO(
                "failures", kind="failure", objective=0.999,
                bad=(r"^serving_failover_total",),
                total=(r"^serving_requests_total\{.*status=admitted",))],
            fast_window_s=60.0, slow_window_s=600.0, burn_threshold=1.0,
            sustain=2, clock=clock)
        plane = fleet.FleetPlane(fe, watchdog=wd, postmortem_dir=pm_dir,
                                 poll_interval_s=10_000.0)  # manual polls
        rec = flight_recorder.get()
        rec.annotations.pop("fleet.slo_breach", None)
        prompts = [np.random.RandomState(100 + i).randint(
            0, VOCAB, 6 + (i % 3)).tolist() for i in range(4)]
        reqs = [fe.submit(p, max_new=16, tenant=f"t{i % 2}")
                for i, p in enumerate(prompts)]
        plane.poll_now()                     # healthy baseline sample
        victims = [r for r in reqs if r.worker == 1]
        assert victims, "nothing placed on the worker we will kill"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            fe.pump()
            if all(len(r.tokens) >= 3 for r in victims):
                break
            time.sleep(0.01)
        os.kill(procs[2].pid, signal.SIGKILL)   # decode worker index 1
        procs[2].wait(timeout=30)
        fe.run(timeout_s=240)
        assert all(r.status == "DONE" for r in reqs)
        assert all(r.failovers >= 1 for r in victims)

        # two post-incident observations (sustain=2) -> degraded ->
        # bundle; the dead worker is skipped by the sweep, not fatal
        plane.poll_now()
        plane.poll_now()
        assert wd.degraded, wd.last_burn
        assert wd.last_burn["failures"]["fast"] > 1.0
        assert _flat(metrics.registry().snapshot())[
            "serving_slo_degraded"] == 1.0

        # the victim's timeline: failover is a NAMED phase, and the
        # trail still decomposes its end-to-end latency. The stream
        # interleaves timeline + decisions.v1 records; validation
        # REPLAYS every decision's inputs (ISSUE 15)
        tl_all = [json.loads(x) for x in open(tmp_path / "tl.jsonl")
                  if x.strip()]
        assert serve_report.validate_records(tl_all) == []
        tl = {r["key"]: r for r in tl_all if r["kind"] == "timeline"}
        decs = [r for r in tl_all if r["kind"] == "decision"]
        for v in victims:
            trec = tl[v.key]
            phases = [s["phase"] for s in trec["phases"]]
            assert "failover" in phases, phases
            assert trec["failovers"] == v.failovers
            assert sum(s["dur_s"] for s in trec["phases"]) == \
                pytest.approx(trec["e2e_s"], rel=0.05, abs=1e-3)
            # the hop re-placed and decoded again: decode appears on
            # both sides of the failover mark
            assert phases.index("failover") < len(phases) - 1
            # the decision log names the hop, with the SAME tenant and
            # trace id as the victim's timeline record (ISSUE 15): the
            # "why did this stream move hosts" record joins its latency
            # decomposition on (key, tenant, trace_id)
            hops = [d for d in decs if d["action"] == "failover"
                    and d["key"] == v.key]
            assert len(hops) == v.failovers > 0
            for d in hops:
                assert d["tenant"] == trec["tenant"] == v.tenant
                assert d.get("trace_id") == trec.get("trace_id")
                assert d["inputs"]["dead_worker"] == 1

        bundle = plane.last_bundle
        assert bundle and os.path.isdir(bundle)
        doc = json.load(open(os.path.join(bundle, "bundle.json")))
        assert doc["schema"] == fleet.BUNDLE_SCHEMA
        assert "fleet.slo_breach" in doc["router_annotations"]
        by_id = {m["worker_id"]: m for m in doc["members"]}
        # survivors dumped; the SIGKILLed host is RECORDED unreachable
        assert by_id["decode0"]["ok"] is True
        assert by_id["prefill0"]["ok"] is True
        assert by_id["decode1"]["ok"] is False
        for wid in ("decode0", "prefill0"):
            d = json.load(open(os.path.join(bundle, f"{wid}.json")))
            assert d["schema"] == "paddle_tpu.postmortem.v1"
        fe.stop_workers()
        fe.close()
    finally:
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)
