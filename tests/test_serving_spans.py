"""The serve step read from inside (ISSUE 26): one `serving::step` span tree
per `Scheduler.step()`, the always-on bounded span log that keeps it, the
`TraceAnnotation` every recorded span also is, the kernel names on the
Pallas calls and the attention scopes."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import profiler
from paddle_tpu.observability.flight_recorder import SpanLog
from paddle_tpu.serving import (GenerationEngine, PagedEngineConfig,
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)
from paddle_tpu.text.models import gpt_tiny

P = "serving::"
BLOCK = 8


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(0)
    tiny = gpt_tiny()
    tiny.eval()
    return tiny


def paged_scheduler(model, slots=2, clock=None):
    engine = PagedGenerationEngine(model, PagedEngineConfig(
        slots=slots, max_len=64, block_size=BLOCK, prefill_buckets=(16, 32)))
    kwargs = {"clock": clock} if clock else {}
    return Scheduler(engine, ServingConfig(max_queue=16), **kwargs), engine


def by_parent(spans):
    out = {}
    for s in spans:
        out.setdefault(s["parent"], []).append(s)
    return out


def logged(since=0):
    """The records appended since `since` (the log's `appended` then),
    as dicts: the log is a ring, so count back from its end."""
    log = profiler.span_log()
    new = log.appended - since
    return [dict(zip(SpanLog.FIELDS, r))
            for r in (log.spans()[-new:] if new else [])]


@pytest.fixture(scope="module")
def served(model):
    """Five requests through two slots, stepped one by one: the log's
    spans, and what the scheduler and pool said after each step."""
    assert not profiler._tracer.enabled and profiler._tracer.ring is None
    profiler.span_log().clear()
    sched, engine = paged_scheduler(model)
    rng = np.random.RandomState(0)
    handles = [sched.submit(rng.randint(1, 100, 5 + 3 * i).tolist(),
                            max_new_tokens=3 + i) for i in range(5)]
    seen = []
    more = True
    while more:
        more = sched.step()
        seen.append({"active_slots": sched.active_slots(),
                     "in_use": engine.block_pool.in_use,
                     "queue": len(sched._queue)})
    assert all(h.status == "DONE" for h in handles)
    spans = logged()
    return {"spans": spans, "seen": seen, "handles": handles,
            "steps": [s for s in spans if s["name"] == P + "step"],
            "children": by_parent(spans)}


def test_the_log_fills_with_no_profiler_and_no_flight_recorder(served):
    assert len(served["steps"]) == len(served["seen"]) > 5
    assert profiler.span_log().dropped == 0
    assert [s["attrs"]["step"] for s in served["steps"]] == \
        list(range(len(served["steps"])))


@pytest.mark.parametrize("child", ["retire", "refill", "grow",
                                   "bookkeeping", "step.counts"])
def test_every_step_holds_its_phase(served, child):
    for step in served["steps"]:
        names = [k["name"] for k in served["children"][step["span_id"]]]
        assert names.count(P + child) == 1


@pytest.mark.parametrize("child", ["decode.prepare", "decode_step",
                                   "decode.commit", "emit"])
def test_a_step_that_decoded_holds_decode_and_emit(served, child):
    decoded = 0
    for step in served["steps"]:
        kids = served["children"][step["span_id"]]
        names = [k["name"] for k in kids]
        if step["attrs"]["active_slots"]:
            assert names.count(P + child) == 1
            decoded += 1
            order = [n for n in names if n in (P + "grow", P + "decode_step",
                                               P + "emit",
                                               P + "bookkeeping")]
            assert order == [P + "grow", P + "decode_step", P + "emit",
                             P + "bookkeeping"]
        elif child == "emit":
            # nothing new to enqueue: the step fetches the call in flight
            # and hands its tokens out
            assert (P + "emit" in names) == (P + "decode.wait" in names)
        else:
            assert P + child not in names
    assert decoded > 5


def test_decode_step_holds_upload_dispatch_wait(served):
    decodes = [s for s in served["spans"] if s["name"] == P + "decode_step"]
    assert decodes
    for d in decodes:
        kids = served["children"][d["span_id"]]
        assert [k["name"] for k in kids] == [
            P + "decode.upload", P + "decode.dispatch", P + "decode.wait"]
        assert sum(k["dur"] for k in kids) <= d["dur"]
        assert 1 <= d["attrs"]["active"] <= d["attrs"]["slots"] == 2


# ------------------------------- the host's phases of a call (ISSUE 36)

def tiny_hybrid():
    """The benchmark's Nemotron configuration at its tiny sizes, built as
    its model tests build it: state-space, attention and expert blocks, and
    the counters that ride behind the tokens."""
    from test_nemotron_h_model import build, tiny_config
    config = tiny_config()
    return build(config), PagedEngineConfig(
        **config["program"]["paged_engine_config"])


@pytest.fixture(scope="module", params=["gpt_tiny", "tiny hybrid"])
def one_call_each(request, model):
    """One request of two tokens through a scheduler: one prefill, one
    decode. The log's spans, and the engine."""
    if request.param == "gpt_tiny":
        sched, engine = paged_scheduler(model)
    else:
        hybrid, config = tiny_hybrid()
        engine = PagedGenerationEngine(hybrid, config)
        sched = Scheduler(engine, ServingConfig(max_queue=16))
    mark = profiler.span_log().appended
    handle = sched.submit(list(range(1, 12)), max_new_tokens=2)
    while sched.step():
        pass
    assert handle.status == "DONE"
    spans = logged(mark)
    return {"spans": spans, "children": by_parent(spans), "engine": engine}


def in_turn(parent, kids):
    """`kids` lie inside `parent`, one after the other."""
    edges = [parent["ts"]]
    for k in kids:
        edges += [k["ts"], k["ts"] + k["dur"]]
    edges.append(parent["ts"] + parent["dur"])
    return edges == sorted(edges)


def test_prefill_holds_upload_dispatch_wait_and_keeps_its_attrs(
        one_call_each):
    spans, engine = one_call_each["spans"], one_call_each["engine"]
    prefill, = [s for s in spans if s["name"] == P + "prefill"]
    kids = one_call_each["children"][prefill["span_id"]]
    assert [k["name"] for k in kids] == [
        P + "prefill.upload", P + "prefill.dispatch", P + "prefill.wait"]
    assert in_turn(prefill, kids)
    # what the fetch brought is noted on `serving::prefill`, as before;
    # the upload says how many transfers it made, the wait how many
    # fetches: one each (ISSUE 37)
    assert [k["attrs"] for k in kids] == [
        {"request_id": prefill["attrs"]["request_id"], "transfers": 1},
        {"request_id": prefill["attrs"]["request_id"]},
        {"request_id": prefill["attrs"]["request_id"], "fetches": 1}]
    extra = set(engine._counter_names) | set(engine._layout.prefill_notes(
        prefill["attrs"]["bucket"], prefill["attrs"]["length"]))
    assert set(prefill["attrs"]) == extra | {
        "attend", "bucket", "kv_dtype", "length", "paged", "pool_donated",
        "prefix_hit_tokens", "request_id", "slot"}
    assert prefill["attrs"]["pool_donated"] == 1
    around = [s["name"] for s in
              one_call_each["children"][prefill["parent"]]]
    assert around == [P + "prefill.admit", P + "prefill",
                      P + "prefill.publish"]


def test_prepare_and_commit_stand_around_the_decode_step(one_call_each):
    spans = one_call_each["spans"]
    decode, = [s for s in spans if s["name"] == P + "decode_step"]
    prepare, = [s for s in spans if s["name"] == P + "decode.prepare"]
    commit, = [s for s in spans if s["name"] == P + "decode.commit"]
    step = next(s for s in spans if s["span_id"] == decode["parent"])
    assert step["name"] == P + "step"
    assert prepare["parent"] == commit["parent"] == step["span_id"]
    names = [k["name"] for k in one_call_each["children"][step["span_id"]]]
    at = names.index(P + "decode_step")
    assert names[at - 2:at + 3] == [
        P + "grow", P + "decode.prepare", P + "decode_step",
        P + "decode.commit", P + "emit"]
    # siblings, one after the other: decode_step keeps its own extent
    assert prepare["ts"] + prepare["dur"] <= decode["ts"]
    assert decode["ts"] + decode["dur"] <= commit["ts"]
    assert not prepare["attrs"] and not commit["attrs"]
    assert {"slots", "active", "paged", "kv_dtype", "attend", "ahead",
            "call"} == set(decode["attrs"])
    assert in_turn(decode, one_call_each["children"][decode["span_id"]])


def test_counts_closes_the_step_and_the_attrs_stay_on_the_step(
        one_call_each):
    steps = [s for s in one_call_each["spans"] if s["name"] == P + "step"]
    assert steps
    for step in steps:
        kids = one_call_each["children"][step["span_id"]]
        assert [k["name"] for k in kids[-2:]] == [
            P + "bookkeeping", P + "step.counts"]
        assert not kids[-1]["attrs"]
        assert {"step", "preempted", "queue_depth", "active_slots", "slots",
                "kv_blocks_in_use", "kv_blocks_total",
                "kv_tokens_held"} <= set(step["attrs"])
        assert in_turn(step, kids)


def test_the_log_alone_says_when_nothing_was_in_flight(one_call_each):
    """The benchmark's reader over the program's own spans: the four
    groups and the in-flight time are the window, to the nanosecond."""
    from benchmark.harness import host_gaps
    spans = one_call_each["spans"]
    steps = [s for s in spans if s["name"] == P + "step"]
    got = host_gaps.split(spans, steps[0]["ts"], steps[-1]["ts"] + 1)
    lo, hi = got["window_ns"]
    assert (lo, hi) == (steps[0]["ts"], steps[-1]["ts"] + steps[-1]["dur"])
    assert got["steps"] == len(steps)
    assert sum(got["starved_ns"].values()) + got["in_flight_ns"] == hi - lo
    assert all(ns >= 0 for ns in got["starved_ns"].values())
    calls = [s for s in spans
             if s["name"] in (P + "prefill", P + "decode_step")]
    kids = one_call_each["children"]
    assert got["in_flight_ns"] == sum(
        kids[c["span_id"]][2]["ts"] + kids[c["span_id"]][2]["dur"]
        - kids[c["span_id"]][1]["ts"] for c in calls)
    assert len(got["prefill_host_ns"]) == 1
    # every phase of the two calls that is not in flight found its group
    assert {"prefill.admit", "prefill.upload", "prefill.publish",
            "decode.prepare", "decode.upload",
            "decode.commit"} <= set(got["starved_by_span"])
    assert not {"prefill.wait", "prefill.dispatch",
                "decode.dispatch"} & set(got["starved_by_span"])
    # the one decode call is fetched a step later, by a step with nothing
    # new to enqueue: that wait stands under no `decode_step`, and the
    # reader, which pairs a dispatch with the wait beside it, counts it
    # starved (its numbers are upper bounds since PR 39)
    late, = [s for s in spans if s["name"] == P + "decode.wait"
             and s["attrs"]["fetches"]]
    assert kids[late["parent"]][0]["name"] != P + "decode.upload"
    assert got["starved_by_span"]["decode.wait"] >= late["dur"]


def test_an_engine_without_the_children_leaves_the_reader_silent(model):
    """The dense engine opens no `prefill.dispatch` / `.wait`: the reader
    says nothing rather than half of it."""
    from benchmark.harness import host_gaps
    sched = Scheduler(GenerationEngine(model, slots=2, max_len=48),
                      max_queue=4)
    mark = profiler.span_log().appended
    sched.submit([1, 2, 3], max_new_tokens=2)
    sched.run_until_idle()
    spans = logged(mark)
    assert P + "prefill" in {s["name"] for s in spans}
    assert P + "prefill.dispatch" not in {s["name"] for s in spans}
    starts = [s["ts"] for s in spans if s["name"] == P + "step"]
    assert host_gaps.split(spans, min(starts), max(starts) + 1) is None


def test_self_times_add_up_to_the_step(served):
    """A span's self time is its duration less what its children cover;
    over a step's whole tree that is the step's duration, within 2 %."""
    def self_sum(span):
        kids = served["children"].get(span["span_id"], [])
        own = span["dur"] - sum(k["dur"] for k in kids)
        assert own >= 0, span["name"]
        assert all(span["ts"] <= k["ts"] and k["ts"] + k["dur"]
                   <= span["ts"] + span["dur"] for k in kids)
        return own + sum(self_sum(k) for k in kids)
    for step in served["steps"]:
        assert self_sum(step) == pytest.approx(step["dur"], rel=0.02)


@pytest.mark.parametrize("attr,seen", [("active_slots", "active_slots"),
                                       ("kv_blocks_in_use", "in_use"),
                                       ("queue_depth", "queue")])
def test_step_attrs_are_the_counts_as_the_step_ends(served, attr, seen):
    assert [s["attrs"][attr] for s in served["steps"]] == \
        [row[seen] for row in served["seen"]]


def test_kv_tokens_fit_the_blocks_in_use(served):
    held = [s["attrs"]["kv_tokens_held"] for s in served["steps"]]
    assert max(held) > 2 * BLOCK
    for s in served["steps"]:
        a = s["attrs"]
        assert a["kv_tokens_held"] <= a["kv_blocks_in_use"] * BLOCK
        assert a["kv_blocks_total"] == 16 and a["slots"] == 2
        assert a["preempted"] == 0


def test_refill_and_emit_count_what_they_did(served):
    refills = [s["attrs"] for s in served["spans"]
               if s["name"] == P + "refill"]
    assert sum(r["admitted"] for r in refills) == 5
    assert sum(r["prefill_tokens"] for r in refills) == \
        sum(5 + 3 * i for i in range(5))
    emitted = sum(s["attrs"]["tokens"] for s in served["spans"]
                  if s["name"] == P + "emit")
    # the first token of each request comes from its prefill
    assert emitted == sum(len(h.tokens) for h in served["handles"]) - 5


def test_queue_and_prefill_share_the_request_and_meet(served):
    queues = {s["attrs"]["request_id"]: s for s in served["spans"]
              if s["name"] == P + "queue"}
    prefills = {s["attrs"]["request_id"]: s for s in served["spans"]
                if s["name"] == P + "prefill"}
    ids = {h.request_id for h in served["handles"]}
    assert set(queues) == set(prefills) == ids
    for rid in ids:
        q, p = queues[rid], prefills[rid]
        assert q["parent"] is None
        # the queue span ends at the trail's stamp, the prefill span opens
        # once the engine has matched and allocated: the same clock
        assert 0 <= p["ts"] - (q["ts"] + q["dur"]) < 50e6
        refill = next(s for s in served["spans"]
                      if s["span_id"] == p["parent"])
        assert refill["name"] == P + "refill"
        assert {"bucket", "length", "slot", "prefix_hit_tokens"} <= \
            set(p["attrs"])
    retired = {s["attrs"]["request_id"] for s in served["spans"]
               if s["name"] == P + "retire.slot"}
    assert retired == ids


@pytest.mark.parametrize("case", ["straight from the queue",
                                  "trail filled by a router first",
                                  "a made-up clock"])
def test_queue_span_is_the_trails_own_queue_segment(model, case):
    """One stamp serves the trail and the span: the span holds exactly
    the trail's queue segment, whatever else the trail holds before it.
    On a made-up clock the stamps are on another timeline than the
    log's, and no span is recorded."""
    import time
    now = [100.0]
    made_up = case == "a made-up clock"
    sched, _ = paged_scheduler(model,
                               clock=(lambda: now[0]) if made_up else None)
    mark = profiler.span_log().appended
    handle = sched.submit([1, 2, 3], max_new_tokens=2)
    trail = handle._req.trail
    if case == "trail filled by a router first":
        t = time.monotonic()
        trail.append("prefill", t - 3.0, t - 2.0)
        trail.append("kv_handoff", t - 2.0, t - 1.0)
    now[0] = 100.25
    sched.run_until_idle()
    queues = [s for s in logged(mark) if s["name"] == P + "queue"]
    (phase, t0, t1), = [s for s in trail.segments if s[0] == "queue"]
    if made_up:
        assert (queues, t0, t1) == ([], 100.0, 100.25)
        return
    q, = queues
    assert (q["ts"], q["dur"]) == (int(t0 * 1e9), int((t1 - t0) * 1e9))
    assert q["attrs"] == {"request_id": handle.request_id}
    # the system's clock: the wait ended inside this test, not at 100 s
    assert 0 <= time.perf_counter_ns() - (q["ts"] + q["dur"]) < 60e9


def test_preempted_is_counted_in_the_step_that_preempted(model):
    engine = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=BLOCK, num_blocks=5,
        prefill_buckets=(16, 32), enable_prefix_cache=False))
    sched = Scheduler(engine, ServingConfig(max_queue=8))
    mark = profiler.span_log().appended
    for _ in range(2):
        sched.submit(list(range(1, 15)), max_new_tokens=12)
    sched.run_until_idle()
    steps = [s for s in logged(mark) if s["name"] == P + "step"]
    assert sum(s["attrs"]["preempted"] for s in steps) == \
        sched.counts["serving.preempted"] > 0
    # queued twice, the preempted request still has ONE queue span
    queued = [s["attrs"]["request_id"] for s in logged(mark)
              if s["name"] == P + "queue"]
    assert len(queued) == len(set(queued)) == 2


def test_dense_engine_steps_carry_no_pool_counts(model):
    sched = Scheduler(GenerationEngine(model, slots=2, max_len=48),
                      max_queue=4)
    mark = profiler.span_log().appended
    sched.submit([1, 2, 3], max_new_tokens=3)
    sched.run_until_idle()
    spans = logged(mark)
    step = next(s for s in spans if s["name"] == P + "step")
    assert "kv_blocks_in_use" not in step["attrs"]
    assert step["attrs"]["slots"] == 2
    names = {s["name"] for s in spans}
    assert {P + "decode.upload", P + "decode.dispatch",
            P + "decode.wait"} <= names


# ------------------------------------------------------- the span primitive

class FakeAnnotation:
    """Stands where jax.profiler.TraceAnnotation is, with a session on."""
    seen = []

    def __init__(self, name, **kwargs):
        self.name, self.metadata = name, dict(kwargs)

    def __enter__(self):
        FakeAnnotation.seen.append(self)
        return self

    def __exit__(self, *exc):
        return False

    @staticmethod
    def is_enabled():
        return True

    def set_metadata(self, **kwargs):
        self.metadata.update(kwargs)


@pytest.mark.parametrize("name,annotated", [("serving::probe", True),
                                            ("other::probe", False)])
def test_a_recorded_span_is_a_trace_annotation_without_paddles_profiler(
        monkeypatch, name, annotated):
    assert not profiler._tracer.enabled
    FakeAnnotation.seen = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    attrs = {"n": 1, "what": "x", "skipped": [1, 2]}
    with profiler.RecordEvent(name, attrs=attrs):
        attrs["late"] = 7               # counts taken as the span ends
    assert [a.name for a in FakeAnnotation.seen] == \
        ([name] if annotated else [])
    if annotated:
        assert FakeAnnotation.seen[0].metadata == \
            {"n": 1, "what": "x", "late": 7}


def test_record_event_has_one_path_to_the_annotation():
    src = inspect.getsource(profiler.RecordEvent)
    assert src.count("jax.profiler.TraceAnnotation(") == 1
    assert "_tracer.enabled" not in src


def test_spans_reach_a_jax_profiler_session(tmp_path):
    """The real thing on the CPU: a `jax.profiler` session that paddle's
    Profiler did not open holds the span, with its attrs, on /host:CPU."""
    from jax.profiler import ProfileData
    import glob
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("serving::probe_in_session",
                                  attrs={"request_id": 41}):
            jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:CPU")
              for line in plane.lines for e in line.events
              if e.name.startswith("serving::probe_in_session")]
    assert len(events) == 1
    assert ("request_id", 41) in [(k, v) for k, v in events[0].stats] \
        or "request_id=41" in events[0].name


def test_span_attrs_reach_the_spans_opened_inside():
    mark = profiler.span_log().appended
    with profiler.span_attrs(request_id=9):
        with profiler.RecordEvent("serving::inner", attrs={"slot": 1}):
            pass
        with profiler.RecordEvent("serving::bare"):
            pass
    with profiler.RecordEvent("serving::after", attrs={"slot": 2}):
        pass
    got = {s["name"]: s["attrs"] for s in logged(mark)}
    assert got == {"serving::inner": {"slot": 1, "request_id": 9},
                   "serving::bare": {"request_id": 9},
                   "serving::after": {"slot": 2}}
    assert not profiler._tracer._inherit


@pytest.mark.parametrize("case", ["nothing dropped", "window still held",
                                  "window's start overwritten",
                                  "everything overwritten"])
def test_span_log_window_is_whole_or_none(case):
    log = SpanLog(capacity=4)
    spans = [("serving::x", 10 * i, 5, i, None, None) for i in range(8)]
    if case == "nothing dropped":
        for r in spans[:4]:
            log.append(r)
        assert log.dropped == 0
        assert [r["span_id"] for r in log.window(0, 1000)] == [0, 1, 2, 3]
        assert [r["span_id"] for r in log.window(10, 30)] == [1, 2]
        return
    for r in spans:
        log.append(r)
    assert (log.appended, log.dropped, log.capacity) == (8, 4, 4)
    if case == "window still held":     # the oldest kept closed at 45
        assert [r["span_id"] for r in log.window(45, 1000)] == [5, 6, 7]
    elif case == "window's start overwritten":
        assert log.window(44, 1000) is None
    else:
        assert log.window(0, 20) is None
    log.clear()
    assert (log.appended, log.dropped, log.spans()) == (0, 0, [])


def test_the_log_holds_the_benchmarks_window_at_three_times_the_rate():
    # 30 s window + 2 s traced + 3 s drain, 87 steps/s, 12 spans a step,
    # and a queue span for each of 480 requests
    assert profiler.span_log().capacity >= 35 * 87 * 12 + 480


def test_record_span_is_kept_closed_with_no_parent():
    mark = profiler.span_log().appended
    with profiler.RecordEvent("serving::outer"):
        profiler.record_span("serving::waited", 5_000, 2_500, {"k": 1})
        profiler.record_span("elsewhere::waited", 5_000, 2_500)
    got = logged(mark)
    assert [(s["name"], s["ts"], s["dur"], s["parent"], s["attrs"])
            for s in got[:1]] == [("serving::waited", 5000, 2500, None,
                                   {"k": 1})]
    assert [s["name"] for s in got] == ["serving::waited", "serving::outer"]


@pytest.mark.parametrize("case", ["note", "open_spans",
                                  "profiler starts inside",
                                  "recorder attaches inside"])
def test_a_log_only_span_is_the_logs_own_record(case):
    """With nothing but the log on, an open span is a list in the log's
    field order (no dict is built); everything that reads an open span
    reads either kind, and a store that comes on while one is open gets
    the spans opened after it, parented on the one that was open."""
    from paddle_tpu.observability import flight_recorder
    tracer = profiler._tracer
    assert not tracer.enabled and tracer.ring is None
    mark = profiler.span_log().appended
    outer = profiler.RecordEvent("serving::outer", attrs=None)
    outer.begin()
    assert type(outer._rec) is list and len(outer._rec) == len(SpanLog.FIELDS)
    outer_id = outer._rec[3]
    try:
        if case == "note":
            tracer.note("cache", "hit")
        elif case == "open_spans":
            open_now = flight_recorder.FlightRecorder().open_spans()
            assert [(s["name"], s["span_id"], s["dur"]) for s in open_now] \
                == [("serving::outer", outer_id, None)]
        elif case == "profiler starts inside":
            with profiler.Profiler(timer_only=True) as prof:
                with profiler.RecordEvent("serving::inner"):
                    pass
            # the window's own ProfileStep span stands between them
            inner, step = prof._events      # in the order they closed
            assert step["name"].startswith("ProfileStep")
            assert (step["parent"], step["depth"]) == (outer_id, 1)
            assert (inner["parent"], inner["depth"]) == (step["span_id"], 2)
        else:
            recorder = flight_recorder.FlightRecorder(capacity=8)
            recorder.enable()
            try:
                with profiler.RecordEvent("serving::inner"):
                    pass
            finally:
                recorder.disable()
            inner, = recorder.spans()
            assert inner["parent"] == outer_id
    finally:
        outer.end()
    got = {s["name"]: s for s in logged(mark)}
    assert got["serving::outer"]["span_id"] == outer_id
    assert got["serving::outer"]["dur"] > 0
    if case == "note":
        assert got["serving::outer"]["attrs"] == {"cache": "hit"}
    elif case == "recorder attaches inside":
        assert got["serving::inner"]["parent"] == outer_id
    assert not tracer._stacks


def test_span_log_counts_every_append_across_threads():
    """`dropped` is what keeps a reader from taking part of a window
    for all of it: appends from several threads at once are all
    counted."""
    import sys
    import threading
    log = SpanLog(capacity=64)
    per_thread, threads = 20_000, 4
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def feed(k):
            for i in range(per_thread):
                log.append(("serving::x", i, 1, k, None, None))
        workers = [threading.Thread(target=feed, args=(k,))
                   for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
    finally:
        sys.setswitchinterval(interval)
    assert log.appended == per_thread * threads
    assert log.dropped == per_thread * threads - 64
    assert log.window(0, 10**9) is None


# ----------------------------------------------------- what was taken out

def test_capture_decode_steps_is_gone():
    assert not hasattr(Scheduler, "capture_decode_steps")
    assert "_capture" not in inspect.getsource(Scheduler.step)
    assert "_capture" not in inspect.getsource(Scheduler._step)


# ------------------------------------------ kernel names, attention scopes

def pallas_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for value in eqn.params.values():
            for item in (value if isinstance(value, (list, tuple))
                         else [value]):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    pallas_eqns(inner, out)
    return out


def flash_jaxpr():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=True).astype(jnp.float32).sum()
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)


def paged_jaxpr():
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    pool = jnp.zeros((5, 8, 2, 64), jnp.float32)
    return jax.make_jaxpr(lambda q, t, p: paged_attention(
        q, pool, pool, t, p, interpret=True))(
        jnp.zeros((2, 1, 2, 64), jnp.float32),
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32))


@pytest.mark.parametrize("make,kernel", [
    (flash_jaxpr, "flash_fwd"), (flash_jaxpr, "flash_dq"),
    (flash_jaxpr, "flash_dkv"), (paged_jaxpr, "paged_attn")])
def test_each_pallas_call_carries_its_kernel_name(make, kernel):
    eqns = pallas_eqns(make().jaxpr, [])
    named = [e for e in eqns if e.params["name"] == kernel]
    assert len(named) == 1
    metadata = dict(named[0].params["metadata"])
    assert metadata["kernel"] == kernel
    # since PR 29 the flash kernels say beside their name what their plan
    # decided (ops/pallas/flash_attention.py FlashPlan.metadata)
    assert set(metadata) == {"kernel"} | ({
        "block_q", "block_k", "chunk", "chunks_total", "chunks_run",
        "chunks_masked"} if kernel.startswith("flash_") else set())
    assert all(e.params["metadata"] for e in eqns)


@pytest.mark.parametrize("impl", ["gather", "kernel"])
@pytest.mark.parametrize("scope", ["decode_attn", "prefill_attn"])
def test_attention_is_one_named_scope_whichever_arm(impl, scope):
    attend = blocks.attend if impl == "gather" else blocks.attend_kernel
    pool = jnp.zeros((5, 8, 2, 64), jnp.float32)
    args = (jnp.zeros((2, 1, 2, 64), jnp.float32), pool, pool,
            jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32))

    def stacks():          # a fresh function each time: no cached trace
        return {str(e.source_info.name_stack) for e in jax.make_jaxpr(
            lambda *a: attend(*a))(*args).jaxpr.eqns}
    with blocks.attention_scope(scope):
        assert all(scope in s for s in stacks())
    assert not any(scope in s for s in stacks())


def test_the_engines_executables_trace_inside_the_scopes(model):
    _, engine = paged_scheduler(model)
    seen = []
    real = blocks.attend.__wrapped__

    def spy(*args, **kwargs):
        seen.append(blocks._ATTEND_SCOPE)
        return real(*args, **kwargs)
    orig = blocks.attend
    try:
        blocks.attend = blocks._scoped(spy)
        engine.prefill(0, [1, 2, 3])
        engine.decode()
    finally:
        blocks.attend = orig
    layers = model.cfg.num_layers
    assert seen == ["prefill_attn"] * layers + ["decode_attn"] * layers
