"""ISSUE 4 acceptance: the unified observability substrate.

Covers the tentpole + satellites end to end:
  - metrics registry semantics (labels, snapshot consistency, zero-cost
    disable, JSONL + Prometheus exposition round-trip),
  - thread-safe span emission (4 threads hammering RecordEvent, parent
    refs must stay intra-thread and uncorrupted),
  - the flight recorder (ring capture with the profiler CLOSED, watchdog
    dump, SIGTERM dump from a STANDALONE module load — no paddle_tpu,
    no jax),
  - the wedge path: a child that hangs inside an open span must leave
    a postmortem artifact (thread stacks + span ring + open spans +
    metrics snapshot) on disk and exit non-zero,
  - cross-process trace propagation: a real forked PS server process and
    the client export chrome traces that share ONE trace id and merge
    into a single causally-linked timeline (server spans parented under
    client span ids).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu import observability as obs
from paddle_tpu.observability import flight_recorder, metrics, tracecontext
from paddle_tpu.profiler import Profiler, RecordEvent, TracerEventType, \
    _tracer, export_chrome_tracing

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "graph_ps_worker.py")
FR_PATH = os.path.join(ROOT, "paddle_tpu", "observability",
                       "flight_recorder.py")
sys.path.insert(0, os.path.join(ROOT, "tools"))
import metrics_report  # noqa: E402


# ------------------------------------------------------------ registry unit

def test_registry_counter_gauge_histogram():
    reg = metrics.MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("status",))
    c.labels(status="ok").inc()
    c.labels(status="ok").inc(2)
    c.labels(status="err").inc()
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2)
    h = reg.histogram("lat_seconds", buckets=(0.01, 1.0))
    h.observe(0.001)
    h.observe(0.5)
    h.observe(99.0)
    flat = metrics.flatten_snapshot(reg.snapshot())
    assert flat["req_total{status=ok}"] == 3
    assert flat["req_total{status=err}"] == 1
    assert flat["depth"] == 5
    snap = reg.snapshot()
    hist = [m for m in snap["metrics"] if m["name"] == "lat_seconds"][0]
    s = hist["samples"][0]
    assert s["count"] == 3 and s["buckets"]["+Inf"] == 3
    assert s["buckets"]["0.01"] == 1 and s["buckets"]["1.0"] == 2
    # get-or-create: same family back, wrong kind/labels are loud
    assert reg.counter("req_total", labelnames=("status",)) is c
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("req_total")
    with pytest.raises(ValueError, match="labels"):
        reg.counter("req_total", labelnames=("other",))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad.name")
    with pytest.raises(ValueError, match="only go up"):
        c.labels(status="ok").inc(-1)


def test_registry_disabled_is_noop_and_reset():
    reg = metrics.MetricsRegistry()
    c = reg.counter("n_total")
    c.inc(5)
    reg.disable()
    c.inc(100)
    reg.gauge("g").set(9)
    reg.histogram("h").observe(1.0)
    reg.enable()
    flat = metrics.flatten_snapshot(reg.snapshot())
    assert flat["n_total"] == 5 and flat["g"] == 0
    reg.reset()
    assert metrics.flatten_snapshot(reg.snapshot())["n_total"] == 0


def test_registry_collectors_publish_at_snapshot_time():
    reg = metrics.MetricsRegistry()
    calls = []

    def collector(r):
        calls.append(1)
        r.gauge("pulled").set(len(calls))

    reg.register_collector(collector)
    assert metrics.flatten_snapshot(reg.snapshot())["pulled"] == 1
    assert metrics.flatten_snapshot(reg.snapshot())["pulled"] == 2

    def broken(r):
        raise RuntimeError("collector bug")

    reg.register_collector(broken)      # must never take the snapshot down
    assert "pulled" in metrics.flatten_snapshot(reg.snapshot())


def test_exposition_roundtrip_jsonl_and_prometheus(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.counter("jobs_total", "jobs", labelnames=("kind",)) \
        .labels(kind="a").inc(4)
    reg.histogram("wait_seconds").observe(0.02)
    path = str(tmp_path / "m.jsonl")
    reg.write_snapshot(path)
    reg.write_snapshot(path)
    recs = metrics_report.load_snapshots(path)   # raises on any violation
    assert len(recs) == 2
    assert all(metrics_report.validate_snapshot(r) == [] for r in recs)
    prom = reg.dump_prometheus()
    assert metrics_report.validate_prometheus(prom) == []
    assert 'jobs_total{kind="a"} 4' in prom
    assert "# TYPE wait_seconds histogram" in prom
    # rot guards
    assert metrics_report.validate_snapshot({}) != []
    bad = json.loads(json.dumps(recs[0]))
    bad["metrics"][0]["type"] = "weird"
    assert metrics_report.validate_snapshot(bad) != []


def test_default_registry_has_the_framework_producers():
    """The migration satellite: device op-cache, serving counters, PS
    fabric and DataLoader all registered on the ONE default registry."""
    import paddle_tpu.distributed.ps.rpc  # noqa: F401  (registers families)
    snap = obs.registry().snapshot()
    names = {m["name"] for m in snap["metrics"]}
    for expected in ("op_cache_hits", "op_cache_misses", "op_cache_size",
                     "serving_requests_total", "serving_tokens_total",
                     "serving_queue_depth", "serving_slot_occupancy",
                     "dataloader_wait_seconds", "ps_client_request_seconds",
                     "ps_server_request_seconds", "ps_errors_total",
                     "live_device_bytes"):
        assert expected in names, f"{expected} missing from the registry"


def test_device_op_cache_collector_matches_public_api():
    import paddle_tpu.device as device
    a = paddle_tpu.to_tensor(np.ones((2, 2), np.float32))
    _ = (a + a).numpy()
    stats = device.op_cache_stats()
    flat = metrics.flatten_snapshot(obs.registry().snapshot())
    assert flat["op_cache_hits"] == stats["hits"]
    assert flat["op_cache_misses"] == stats["misses"]
    assert flat["op_cache_size"] == stats["size"]


def test_dataloader_wait_histogram_observes():
    from paddle_tpu.io import DataLoader

    class DS:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.float32(i)

    def count():
        snap = obs.registry().snapshot()
        m = [x for x in snap["metrics"]
             if x["name"] == "dataloader_wait_seconds"][0]
        return m["samples"][0]["count"] if m["samples"] else 0

    before = count()
    for _ in DataLoader(DS(), batch_size=4):
        pass
    assert count() == before + 2        # one observation per batch


# ------------------------------------------------- serving counter migration

class _FakeEngine:
    """Minimal engine surface for Scheduler: N slots, instant tokens."""

    class config:
        eos_token_id = None
        max_len = 64

    def __init__(self, slots=2):
        self.slots = slots
        self.max_prompt_len = 32

    def prefill(self, slot, prompt):
        return 1

    def decode(self):
        return np.ones(self.slots, np.int32)

    def reset_slot(self, slot):
        pass


def test_serving_counters_hit_registry_and_legacy_dict():
    from paddle_tpu.serving import Scheduler

    before = metrics.flatten_snapshot(obs.registry().snapshot())
    sched = Scheduler(_FakeEngine(), max_queue=4, default_max_new_tokens=3)
    handles = [sched.submit([1, 2]) for _ in range(2)]
    sched.run_until_idle()
    after = metrics.flatten_snapshot(obs.registry().snapshot())

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    # the per-request families carry the tenant labelset (ISSUE 15);
    # unlabeled submits land under tenant=default
    assert delta(
        "serving_requests_total{status=admitted,tenant=default}") == 2
    assert delta(
        "serving_requests_total{status=completed,tenant=default}") == 2
    assert delta("serving_tokens_total{tenant=default}") == 6
    # the deprecated per-instance dict still answers
    assert sched.counts["serving.admitted"] == 2
    assert sched.counts["serving.tokens"] == 6
    assert all(h.done() for h in handles)
    # gauges reflect the last step
    assert after["serving_queue_depth"] == 0
    assert after["serving_slot_occupancy"] == 0


# --------------------------------------------------- thread-safe span emission

def test_record_event_4_threads_no_corrupt_parent_refs():
    """Satellite: serving worker threads hammer RecordEvent concurrently.
    Every span's parent must be a span of the SAME thread at depth-1 —
    interleaved/corrupt parent refs across threads would break the trace
    tree (and the chrome export's lane nesting)."""
    prof = Profiler(timer_only=True)
    n_iter, n_threads = 100, 4
    with prof:
        def hammer(k):
            for i in range(n_iter):
                with RecordEvent(f"t{k}.outer",
                                 TracerEventType.UserDefined):
                    with RecordEvent(f"t{k}.mid",
                                     TracerEventType.UserDefined):
                        with RecordEvent(f"t{k}.leaf",
                                         TracerEventType.UserDefined):
                            pass
        threads = [threading.Thread(target=hammer, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    spans = [e for e in prof._events if e["name"].startswith("t")]
    assert len(spans) == n_threads * n_iter * 3
    ids = [e["span_id"] for e in spans]
    assert len(set(ids)) == len(ids), "span ids collided"
    by_id = {e["span_id"]: e for e in spans}
    one_trace = {e["trace"] for e in spans}
    assert len(one_trace) == 1 and None not in one_trace
    for e in spans:
        tname, kind = e["name"].split(".", 1)
        if kind == "outer":
            continue
        parent = by_id.get(e["parent"])
        assert parent is not None, f"{e['name']}: dangling parent ref"
        assert parent["tid"] == e["tid"], \
            f"{e['name']}: parent crossed threads"
        assert parent["name"].startswith(tname + "."), \
            f"{e['name']}: parent {parent['name']} from another lane"
        assert parent["depth"] == e["depth"] - 1


# ------------------------------------------------------------ flight recorder

def test_flight_recorder_ring_captures_with_profiler_closed(tmp_path):
    fr = flight_recorder.FlightRecorder(capacity=8, dir=str(tmp_path))
    fr.enable()
    try:
        assert not _tracer.enabled      # no profiling window open
        for i in range(12):             # overflow the ring: bounded
            with RecordEvent(f"closed.span{i}",
                             TracerEventType.UserDefined):
                pass
        spans = fr.spans()
        assert len(spans) == 8          # ring keeps the LAST capacity spans
        assert spans[-1]["name"] == "closed.span11"
        # ring-only spans must NOT leak into profiler windows
        assert not any(e["name"].startswith("closed.span")
                       for e in _tracer.events)
        path = fr.dump("unit-test dump")
        doc = json.load(open(path))
        assert doc["schema"] == flight_recorder.POSTMORTEM_SCHEMA
        assert any(t["name"] == "MainThread" for t in doc["threads"])
        assert [s["name"] for s in doc["spans"]] == \
            [s["name"] for s in spans]
        assert doc["metrics"]["schema"] == metrics.SNAPSHOT_SCHEMA
    finally:
        fr.disable()


def test_flight_recorder_watchdog_fires_and_dumps(tmp_path):
    fr = flight_recorder.FlightRecorder(capacity=8, dir=str(tmp_path))
    fr.enable()
    fired = []
    try:
        token = fr.arm(0.2, "stuck operation", on_fire=fired.append)
        deadline = time.time() + 10
        while not fired and time.time() < deadline:
            time.sleep(0.02)
        assert fired, "watchdog never fired"
        doc = json.load(open(fired[0]))
        assert "stuck operation" in doc["reason"]
        assert doc["threads"]
        fr.disarm(token)
        # a disarmed deadline must NOT fire
        with fr.deadline(0.15, "fast op"):
            pass
        time.sleep(0.4)
        assert len(fired) == 1
    finally:
        fr.disable()


def test_flight_recorder_dump_retention_is_bounded(tmp_path):
    """ISSUE 7 hygiene: the postmortem directory can never grow without
    bound — each dump sweeps down to the newest keep_dumps artifacts
    (plus stale .tmp torn by a crash mid-write), and the dump that
    triggered the sweep always survives it."""
    fr = flight_recorder.FlightRecorder(capacity=4, dir=str(tmp_path),
                                        keep_dumps=3)
    last = None
    for i in range(7):
        last = fr.dump(f"retention test {i}")
        time.sleep(0.01)            # distinct mtimes for the sort
    dumps = [f for f in os.listdir(str(tmp_path)) if f.endswith(".json")]
    assert len(dumps) == 3
    assert os.path.basename(last) in dumps
    # a STALE torn .tmp from a crashed writer is swept on the next dump;
    # a fresh one (possibly another process's in-flight dump) survives
    stale = os.path.join(str(tmp_path), "postmortem_1_1.json.tmp")
    open(stale, "w").close()
    os.utime(stale, (time.time() - 120, time.time() - 120))
    fresh = os.path.join(str(tmp_path), "postmortem_2_2.json.tmp")
    open(fresh, "w").close()
    fr.dump("after torn tmp")
    names = os.listdir(str(tmp_path))
    assert not os.path.exists(stale)
    assert os.path.exists(fresh)
    assert len([n for n in names if n.endswith(".json")]) == 3
    # keep_dumps=0 disables the sweep entirely
    fr0 = flight_recorder.FlightRecorder(capacity=4,
                                         dir=str(tmp_path / "unbounded"),
                                         keep_dumps=0)
    for i in range(4):
        fr0.dump(f"u {i}")
        time.sleep(0.01)
    assert len(os.listdir(str(tmp_path / "unbounded"))) == 4


def test_flight_recorder_standalone_sigterm_dump(tmp_path):
    """The zero-evidence guarantee must hold even when paddle_tpu/jax
    never imported: load flight_recorder.py STANDALONE in a subprocess,
    hook SIGTERM, self-terminate — the artifact must exist and the
    process must still die by SIGTERM."""
    code = f"""
import importlib.util, os, signal, sys, time
spec = importlib.util.spec_from_file_location("fr", {FR_PATH!r})
fr = importlib.util.module_from_spec(spec)
spec.loader.exec_module(fr)
assert "paddle_tpu" not in sys.modules and "jax" not in sys.modules
rec = fr.FlightRecorder(dir={str(tmp_path)!r})
rec.enable(install_signal_handler=True)
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(30)   # unreachable: the chained default handler kills us
"""
    proc = subprocess.run([sys.executable, "-c", code], timeout=60,
                          capture_output=True, text=True)
    assert proc.returncode == -signal.SIGTERM, proc.stderr[-2000:]
    dumps = [p for p in os.listdir(tmp_path) if p.startswith("postmortem_")]
    assert len(dumps) == 1
    doc = json.load(open(tmp_path / dumps[0]))
    assert "SIGTERM" in doc["reason"]
    assert doc["threads"] and doc["metrics"] is None  # no registry loaded


# ------------------------------------------------- a child that wedges

_WEDGED_CHILD = """
import os, sys, time
import paddle_tpu
from paddle_tpu.observability import flight_recorder
from paddle_tpu.profiler import RecordEvent, TracerEventType
fr = flight_recorder.enable(dir=sys.argv[1])
with RecordEvent("child.setup", TracerEventType.UserDefined):
    pass
fr.arm(1.0, "injected wedge", on_fire=lambda path: os._exit(3))
with RecordEvent("child.wedged_probe", TracerEventType.UserDefined):
    time.sleep(3600)
"""


def test_wedged_child_leaves_postmortem_naming_its_open_spans(tmp_path):
    """What test_flight_recorder_watchdog_fires_and_dumps (in process)
    cannot show: a child that hangs inside an open span is ended by its
    own watchdog, exits non-zero, and the post-mortem it leaves on disk
    names the span it hung in, the span it had closed, the sleeping
    stack and the metrics registry."""
    pm_dir = str(tmp_path / "pm")
    child = tmp_path / "wedged_child.py"    # a file, so stacks show source
    child.write_text(_WEDGED_CHILD)
    proc = subprocess.run([sys.executable, str(child), pm_dir],
                          capture_output=True, text=True, timeout=240,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   PYTHONPATH=ROOT))
    assert proc.returncode == 3, proc.stderr[-2000:]
    dumps = os.listdir(pm_dir)
    assert len(dumps) == 1, dumps
    doc = json.load(open(os.path.join(pm_dir, dumps[0])))
    assert doc["schema"] == flight_recorder.POSTMORTEM_SCHEMA
    assert "injected wedge" in doc["reason"]
    stacks = "\n".join("\n".join(t["stack"]) for t in doc["threads"])
    assert "time.sleep" in stacks       # the wedge is visible
    assert any(s["name"] == "child.setup" for s in doc["spans"])
    assert any(s["name"] == "child.wedged_probe"
               for s in doc["open_spans"])
    assert doc["metrics"]["schema"] == metrics.SNAPSHOT_SCHEMA


# ------------------------------------------- cross-process trace propagation

def _scrubbed_env(extra=None):
    env = dict(os.environ)
    for k in list(env):
        if (k.startswith(("TPU_", "LIBTPU", "PJRT_"))
                or k in ("JAX_PLATFORM_NAME", "XLA_FLAGS",
                         "JAX_PLATFORMS")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    env.update(extra or {})
    return env


def test_two_process_ps_trace_merges_into_one_timeline(tmp_path):
    """ISSUE 4 acceptance: client + forked PS server each export their own
    chrome trace; the spans share ONE trace id, server spans parent under
    the remote client span ids, and merge_chrome_traces folds them into a
    single causally-linked view (flow arrows across pids)."""
    from paddle_tpu.distributed.ps import DistGraphClient

    trace_dir = str(tmp_path / "traces")
    ep_file = str(tmp_path / "ep_0")
    proc = subprocess.Popen(
        [sys.executable, WORKER, "0", "1", ep_file],
        env=_scrubbed_env({"PTN_TRACE_EXPORT_DIR": trace_dir}),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    client = None
    try:
        deadline = time.time() + 120
        while not os.path.exists(ep_file):
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise RuntimeError(f"worker died:\n{err[-4000:]}")
            if time.time() > deadline:
                raise TimeoutError("worker never published its endpoint")
            time.sleep(0.05)
        with open(ep_file) as f:
            endpoint = f.read().strip()
        client = DistGraphClient([endpoint])
        prof = Profiler(timer_only=True,
                        on_trace_ready=export_chrome_tracing(
                            trace_dir, worker_name="client"))
        with prof:
            client.sample_neighbors(np.arange(8), sample_size=2, seed=3)
            client.node_degree(np.arange(4))
    finally:
        if client is not None:
            client.stop_servers()
            client.close()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()

    deadline = time.time() + 60
    files = []
    while time.time() < deadline:
        names = os.listdir(trace_dir) if os.path.isdir(trace_dir) else []
        files = [os.path.join(trace_dir, n) for n in names
                 if n.endswith(".json")]
        if len([n for n in names if "client" in n]) and \
                len([n for n in names if "ps_shard0" in n]):
            break
        time.sleep(0.1)
    assert len(files) >= 2, f"missing trace exports: {files}"

    merged_path = str(tmp_path / "merged.json")
    merged = tracecontext.merge_chrome_traces(sorted(files), merged_path)
    assert os.path.exists(merged_path)
    events = merged["traceEvents"]
    client_spans = [e for e in events
                    if e.get("name", "").startswith("ps.client::")]
    server_spans = [e for e in events
                    if e.get("name", "").startswith("ps.server::")]
    assert client_spans and server_spans
    assert {e["pid"] for e in client_spans} != \
        {e["pid"] for e in server_spans}, "expected two distinct processes"

    # ONE shared trace id across both processes' RPC spans
    traces = {e["args"]["trace_id"]
              for e in client_spans + server_spans}
    assert len(traces) == 1, f"trace ids diverged: {traces}"

    # every server span parents under a REMOTE client span id
    client_ids = {e["args"]["span_id"] for e in client_spans}
    for e in server_spans:
        assert e["args"]["parent_span_id"] in client_ids
    # the merge added cross-process flow arrows
    flows = [e for e in events if e.get("cat") == "xproc"]
    assert any(e["ph"] == "s" for e in flows)
    assert any(e["ph"] == "f" for e in flows)
    # verbs line up: each client verb that hit the server has a server span
    server_verbs = {e["name"].split("::")[1] for e in server_spans}
    assert {"GSAMPLE", "GDEGREE"} <= server_verbs
