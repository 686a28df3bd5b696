"""paddle.profiler equivalent.

Reference (SURVEY §5): python/paddle/profiler/profiler.py:340 `Profiler`
with scheduler windows, backed by C++ `platform/profiler/` — host_tracer.cc
collects RecordEvent spans (event_tracing.h:49), cuda_tracer.cc wraps CUPTI,
events merge into a tree (event_node.cc) exported as chrome-trace JSON
(chrometracing_logger.cc) plus python statistics tables
(profiler_statistic.py).

TPU-native mapping:
- host tracer  -> in-process span recorder (this file; RecordEvent spans
  with nesting tracked per thread), auto-fed by the framework: apply_op
  emits Operator spans, distributed/collective.py Communication spans,
  io.DataLoader Dataloader spans, hapi/optimizer/autograd the
  Forward/Backward/Optimization phase spans
- CUPTI tracer -> jax.profiler XPlane capture (start_trace/stop_trace),
  viewable in TensorBoard/XProf — device-side kernel timelines come from
  the XLA runtime, the role CUPTI plays for CUDA
- chrome-trace logger -> export_chrome_tracing handler over the host spans
- profiler_statistic  -> statistic.py summary views + the roofline
  attribution join against cost_model/analytical.py (Profiler.analyze)
"""
import contextlib
import json
import os
import threading
import time
from time import perf_counter_ns as _now_ns

import jax
import numpy as np

from ..observability.flight_recorder import SpanLog
from ..observability.tracecontext import (
    clear_trace as _clear_trace, current_trace_id as _current_trace_id,
    ensure_trace as _ensure_trace, new_span_id as _new_span_id,
    process_trace_id as _process_trace_id,
)

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "TracerEventType", "SortedKeys", "SummaryView",
           "make_scheduler", "export_chrome_tracing", "export_protobuf",
           "load_profiler_result", "span_log", "record_span", "span_attrs"]

STEP_TIMELINE_SCHEMA = "paddle_tpu.step_timeline.v1"


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    CUSTOM_DEVICE = "custom_device"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Reference: profiler.py make_scheduler — cycle through
    closed/ready/record windows."""
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        cycle = closed + ready + record
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


# ---------------------------------------------------------------- host tracer

def _live_bytes():
    """Live device bytes right now (the MemoryView sample). jax.live_arrays
    enumerates every jax.Array the process holds a reference to."""
    try:
        return int(sum(a.size * a.dtype.itemsize for a in jax.live_arrays()))
    except Exception:                                        # noqa: BLE001
        return None


class _HostTracer:
    """Span recorder (the host_tracer.cc role). Spans: dicts with name,
    thread id, start/end (ns), nesting depth, optional attrs (shapes,
    payload bytes, cache outcome), optional memory samples, and an
    in-memory `_ref` (fn + avals) for analyze-time roofline re-trace.

    The `enabled` attribute IS the hot-path guard: instrumentation sites
    check it before building any span metadata, so a CLOSED profiler costs
    one attribute load per op.

    Thread safety (serving scheduler workers hammer this from several
    threads at once): every thread owns its own nesting stack in
    `_stacks` (keyed by thread id — a plain dict entry each thread
    mutates alone, readable cross-thread by the flight recorder's
    postmortem dump), span/parent ids are assigned FROM that per-thread
    stack so a span's parent is always a span of the same thread, and
    the shared `events` list is only ever touched under `_lock`.

    Trace context: every span carries a fresh 8-byte `span_id`, its
    same-thread `parent` span id, and the current `trace` id
    (observability.tracecontext) — the fields the PS RPC fabric
    propagates cross-process and export_chrome_tracing emits.

    Flight recorder: when `ring` is attached (observability.
    flight_recorder), closed spans are ALSO pushed there — including
    spans recorded while the profiler is CLOSED, so a postmortem always
    has recent history.

    Span log: `log` (flight_recorder.SpanLog) is attached from the
    start and takes every closed span named `serving::*` as a tuple,
    profiler or not, recorder or not: the serve step is read from it
    (docs/observability.md, "What a serving operator gets"). It is the
    one store that is on by default, so only that prefix feeds it; the
    per-op spans of the eager path stay behind `enabled`. While the
    log is the ONLY store that is on (the serving default), an open
    span is the log's own record, a list in `SpanLog.FIELDS` order
    that `end` turns into the tuple: no dict, no thread id, depth or
    trace id that nothing would read (a third off the cost of a span,
    PERF.md §6, PR 26). `_span_id` / `_attrs` read either kind."""

    def __init__(self):
        self.enabled = False
        self.sample_memory = False
        self.with_flops = True
        self.events = []
        self.ring = None                 # FlightRecorder, when enabled
        self.log = SpanLog()             # serving::* spans, always on
        self._lock = threading.Lock()
        self._stacks = {}                # thread id -> open-span stack
        self._inherit = {}               # thread id -> span_attrs() scope
        self._ref_seen = set()

    def _stack(self):
        tid = threading.get_ident()
        st = self._stacks.get(tid)
        if st is None:
            st = self._stacks.setdefault(tid, [])
        return st

    def _open(self, attrs):
        """This thread's stack, and `attrs` with what a span_attrs()
        scope adds (in place: the caller may go on filling its dict)."""
        tid = threading.get_ident()
        st = self._stacks.get(tid)
        if st is None:
            st = self._stacks.setdefault(tid, [])
        inherited = self._inherit.get(tid)
        if inherited:
            attrs = {} if attrs is None else attrs
            for k, v in inherited.items():
                attrs.setdefault(k, v)
        return tid, st, attrs

    def begin(self, name, event_type, attrs=None, ref=None):
        if not self.enabled and self.ring is None:
            if not name.startswith(SpanLog.PREFIX):
                return None
            # the serving default, only the log is on: the open span
            # is the log's own record, a list until it closes
            tid = threading.get_ident()
            st = self._stacks.get(tid)
            if st is None or tid in self._inherit:
                _, st, attrs = self._open(attrs)
            rec = [name, _now_ns(), None, _new_span_id(),
                   _span_id(st[-1]) if st else None, attrs]
            st.append(rec)
            return rec
        tid, st, attrs = self._open(attrs)
        rec = {"name": name, "type": event_type,
               "tid": tid,
               "ts": _now_ns(), "dur": None,
               "depth": len(st),
               "span_id": _new_span_id(),
               "parent": _span_id(st[-1]) if st else None,
               "trace": _current_trace_id()}
        if not self.enabled:             # ring-only span: keep it out of
            rec["_fr_only"] = True       # the profiler's window events
        if attrs is not None:
            rec["attrs"] = attrs
        if ref is not None:
            rec["_ref"] = ref
        if self.sample_memory:
            rec["mem0"] = _live_bytes()
        st.append(rec)
        return rec

    def _pop(self, rec):
        tid = threading.get_ident()
        st = self._stacks.get(tid, ())
        if st and st[-1] is rec:
            st.pop()
        elif rec in st:                   # unbalanced nesting: drop through
            st.remove(rec)
        if not st:                        # evict: dead threads must not
            self._stacks.pop(tid, None)   # leak entries

    def end(self, rec):
        if rec is None:
            return
        if rec.__class__ is list:         # the log's own record
            rec[2] = _now_ns() - rec[1]
            st = self._stacks.get(threading.get_ident())
            if st and st[-1] is rec and len(st) > 1:
                st.pop()                  # the usual case: a child closes
            else:
                self._pop(rec)
            self.log.append(tuple(rec))
            return
        rec["dur"] = _now_ns() - rec["ts"]
        self._pop(rec)
        if self.sample_memory:
            rec["mem1"] = _live_bytes()
        self._closed(rec)

    def _closed(self, rec):
        """A closed span goes to every store that is on."""
        if rec["name"].startswith(SpanLog.PREFIX):
            self.log.append((rec["name"], rec["ts"], rec["dur"],
                             rec["span_id"], rec["parent"],
                             rec.get("attrs")))
        ring = self.ring
        if ring is not None:
            ring.record_span(rec)
        if rec.pop("_fr_only", False):
            return
        with self._lock:
            self.events.append(rec)

    def record(self, name, event_type, ts_ns, dur_ns, attrs=None):
        """A span that was timed elsewhere (a request's queue wait, from
        the stamps its PhaseTrail already holds): recorded closed, no
        parent, on no stack. It is in no jax.profiler trace: a TraceMe
        cannot be opened in the past."""
        if not self.enabled and self.ring is None:
            if name.startswith(SpanLog.PREFIX):
                self.log.append((name, int(ts_ns), int(dur_ns),
                                 _new_span_id(), None, attrs))
            return
        rec = {"name": name, "type": event_type,
               "tid": threading.get_ident(), "ts": int(ts_ns),
               "dur": int(dur_ns), "depth": 0,
               "span_id": _new_span_id(), "parent": None,
               "trace": _current_trace_id()}
        if not self.enabled:
            rec["_fr_only"] = True
        if attrs is not None:
            rec["attrs"] = attrs
        self._closed(rec)

    def cancel(self, rec):
        """Abandon an open span without recording it (e.g. the DataLoader
        span opened around a `next` that raised StopIteration)."""
        if rec is not None:
            self._pop(rec)

    def note(self, key, value):
        """Attach a key to the innermost open span on this thread (used by
        apply_op to mark the eager-cache outcome from inside the dispatch)."""
        st = self._stack()
        if not st:
            return
        rec = st[-1]
        if rec.__class__ is list:
            if rec[5] is None:
                rec[5] = {}
            rec[5][key] = value
        else:
            rec.setdefault("attrs", {})[key] = value

    def mark(self):
        with self._lock:
            return len(self.events)

    def since(self, idx):
        with self._lock:
            return list(self.events[idx:])

    def ref_once(self, key):
        """True the first time `key` is seen this window — callers attach
        the heavyweight analyze-ref only then (one per op bucket, not one
        per dispatch)."""
        with self._lock:
            if key in self._ref_seen:
                return False
            self._ref_seen.add(key)
            return True

    def drain(self):
        with self._lock:
            ev, self.events = self.events, []
            self._ref_seen.clear()
        return ev


def _span_id(rec):
    """The id of an open span of either kind (see _HostTracer)."""
    return rec[3] if rec.__class__ is list else rec["span_id"]


def _attrs(rec):
    return rec[5] if rec.__class__ is list else rec.get("attrs")


_tracer = _HostTracer()


class TracerEventType:
    Operator = "Operator"
    Dataloader = "Dataloader"
    ProfileStep = "ProfileStep"
    Forward = "Forward"
    Backward = "Backward"
    Optimization = "Optimization"
    Communication = "Communication"
    PythonOp = "PythonOp"
    UserDefined = "UserDefined"


def _trace_metadata(attrs):
    """The attrs a TraceMe can carry: plain numbers, strings, bools."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (bool, int, float, str))}


class RecordEvent:
    """User-code span (reference: platform/profiler/event_tracing.h:49;
    python surface profiler/utils.py RecordEvent).

    Every span that is recorded at all (profiler window, flight
    recorder, or the serve path's span log) is also a
    jax.profiler.TraceAnnotation, so ANY jax.profiler session — this
    package's Profiler, a benchmark's start_trace, an operator's
    start_server — holds it on /host:CPU on the clock of the device
    planes. With no session open it costs one flag check
    (`TraceAnnotation.is_enabled()`). The attrs are attached when the
    span closes (set_metadata), so counts taken at the end of the span
    are in the trace too."""

    __slots__ = ("name", "event_type", "attrs", "_rec", "_ann")

    def __init__(self, name, event_type=TracerEventType.PythonOp, attrs=None):
        self.name = name
        self.event_type = event_type
        self.attrs = attrs
        self._rec = None
        self._ann = None

    def __enter__(self):
        rec = self._rec = _tracer.begin(self.name, self.event_type,
                                        self.attrs)
        if rec is not None and jax.profiler.TraceAnnotation.is_enabled():
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            attrs = _attrs(self._rec)
            if attrs:
                self._ann.set_metadata(**_trace_metadata(attrs))
            self._ann.__exit__(None, None, None)
            self._ann = None
        _tracer.end(self._rec)
        self._rec = None
        return False

    begin = __enter__
    end = __exit__


def span_log():
    """The process's log of closed `serving::*` spans
    (flight_recorder.SpanLog): on by default, bounded, counts what it
    drops. `span_log().window(start_ns, end_ns)` on the
    time.perf_counter_ns clock reads a window, None on overflow."""
    return _tracer.log


def record_span(name, ts_ns, dur_ns, attrs=None,
                event_type=TracerEventType.UserDefined):
    """Record a span timed elsewhere (see _HostTracer.record)."""
    _tracer.record(name, event_type, ts_ns, dur_ns, attrs)


@contextlib.contextmanager
def span_attrs(**attrs):
    """Every span opened on this thread inside the scope also carries
    `attrs` — how the scheduler names the request an engine call works
    for without the engine's signature knowing about requests."""
    tid = threading.get_ident()
    outer = _tracer._inherit.get(tid)
    _tracer._inherit[tid] = {**outer, **attrs} if outer else attrs
    try:
        yield
    finally:
        if outer is None:
            _tracer._inherit.pop(tid, None)
        else:
            _tracer._inherit[tid] = outer


# ------------------------------------------------------------- trace handlers

def _json_safe_attrs(rec):
    attrs = rec.get("attrs")
    if not attrs:
        return None
    out = {}
    for k, v in attrs.items():
        try:
            json.dumps(v)
            out[k] = v
        except TypeError:
            out[k] = repr(v)
    return out


def export_chrome_tracing(dir_name, worker_name=None):
    """Returns an on_trace_ready handler writing chrome://tracing JSON
    (reference: chrometracing_logger.cc).

    Exports the LAST RECORD WINDOW only (an empty window exports as empty —
    never silently the cumulative history), and maps each (thread, nesting
    depth) to its own tid lane with thread_name metadata so nested spans
    render stacked instead of flattened.

    Every span's args carry its trace_id/span_id/parent_span_id, and the
    file's otherData carries clock_sync_ns (wall-clock epoch minus this
    process's perf_counter origin) — the two ingredients
    observability.merge_chrome_traces needs to fold the per-process
    exports of a distributed run into one causally-linked timeline."""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}"
                            ".paddle_trace.json")
        window = prof._window_events
        if window is None:          # profiler stopped without ever recording
            window = prof._events
        pid = os.getpid()
        lanes = {}                  # (tid, depth) -> lane id
        events = []
        for e in window:
            lane_key = (e["tid"], e["depth"])
            lane = lanes.setdefault(lane_key, len(lanes))
            ev = {"name": e["name"], "cat": e["type"], "ph": "X",
                  "pid": pid, "tid": lane,
                  "ts": e["ts"] / 1000.0, "dur": (e["dur"] or 0) / 1000.0}
            args = _json_safe_attrs(e) or {}
            if e.get("span_id"):
                args["span_id"] = e["span_id"]
            if e.get("parent"):
                args["parent_span_id"] = e["parent"]
            if e.get("trace"):
                args["trace_id"] = e["trace"]
            if args:
                ev["args"] = args
            events.append(ev)
        meta = []
        for (tid, depth), lane in sorted(lanes.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": lane,
                         "args": {"name": f"thread {tid} · depth {depth}"}})
            meta.append({"ph": "M", "name": "thread_sort_index", "pid": pid,
                         "tid": lane, "args": {"sort_index": lane}})
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events,
                       "displayTimeUnit": "ms",
                       "otherData": {
                           "clock_sync_ns":
                               time.time_ns() - time.perf_counter_ns(),
                           "pid": pid}}, f)
        prof._exported_path = path
    return handler


def export_protobuf(dir_name, worker_name=None):
    """The reference's protobuf dump; here an alias of chrome tracing (the
    XPlane protobufs are produced by jax.profiler's own capture)."""
    return export_chrome_tracing(dir_name, worker_name)


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------------------------- profiler

class Profiler:
    """Scheduler-windowed profiler (reference: profiler.py:340).

    targets defaults to host + device. timer_only=True skips the device
    XPlane capture (benchmark mode, reference semantics).
    profile_memory=True samples live device bytes at span boundaries
    (MemoryView). with_flops=True (default) lets apply_op attach the op
    callable + abstract shapes so analyze() can price each op with the
    analytical roofline. timeline=<path> appends one JSONL record per
    recorded step (phase durations, op digest, cache stats, memory peak)
    — the artifact tools/perf_report.py renders."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=True, timeline=None):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, record=hi - lo,
                                             repeat=1)
        else:
            self._scheduler = None  # always on
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._profile_memory = bool(profile_memory)
        self._with_flops = bool(with_flops)
        self._timeline_path = timeline
        self._log_dir = "./profiler_log"
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._device_active = False
        self._events = []
        self._step_times = []
        self._step_samples = []
        self._last_t = None
        self._step_rec = None
        self._exported_path = None
        self._window_events = None
        self._step_mark = 0
        self._cache_mark = None

    # ------------------------------------------------------------ lifecycle
    def _target_state(self):
        if self._scheduler is None:
            return ProfilerState.RECORD
        return self._scheduler(self._step)

    def _recording(self):
        return self._state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN)

    def _transition(self, new):
        recording = self._recording()
        want = new in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if want and not recording:
            _tracer.sample_memory = self._profile_memory
            _tracer.with_flops = self._with_flops
            _tracer.enabled = True
            if not self._timer_only:
                try:
                    jax.profiler.start_trace(self._log_dir)
                    self._device_active = True
                except Exception:
                    self._device_active = False
        if recording and not want:
            self._collect()
        self._state = new

    def _collect(self):
        _tracer.enabled = False
        _tracer.sample_memory = False
        window = _tracer.drain()
        self._events.extend(window)       # cumulative, for statistics()
        self._window_events = window      # this window only, for export
        if self._device_active:
            jax.profiler.stop_trace()
            self._device_active = False
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def start(self):
        # one trace id for everything this window records — and for every
        # PS RPC issued under it, in every process it reaches. If WE set
        # it, stop() clears it: post-window RPCs must not keep paying the
        # propagation bytes for span ids no export will contain, and the
        # next window gets a fresh trace (one trace id per causal unit).
        # ownership keys on the PROCESS default, not current_trace_id():
        # a thread-local trace_scope would mask the process slot and leave
        # the id ensure_trace() installs here uncleared forever
        self._owns_trace = _process_trace_id() is None
        _ensure_trace()
        self._last_t = time.perf_counter()
        self._transition(self._target_state())
        self._open_step_span()

    def stop(self):
        # timeline records are written per step() call only — stop() closes
        # a partial window that has no step duration to report
        self._close_step_span()
        if self._recording():
            self._collect()
        self._state = ProfilerState.CLOSED
        if getattr(self, "_owns_trace", False):
            _clear_trace()
            self._owns_trace = False

    def _open_step_span(self):
        self._step_mark = _tracer.mark()
        if self._timeline_path is not None and self._recording():
            from ..core.tensor import _CACHE_STATS
            self._cache_mark = dict(_CACHE_STATS)
        else:
            self._cache_mark = None
        self._step_rec = _tracer.begin(f"ProfileStep#{self._step}",
                                       TracerEventType.ProfileStep)

    def _close_step_span(self):
        _tracer.end(self._step_rec)
        self._step_rec = None

    def step(self, num_samples=None):
        now = time.perf_counter()
        dt = now - self._last_t if self._last_t is not None else None
        if dt is not None:
            self._step_times.append(dt)
            self._step_samples.append(num_samples)
        self._last_t = now
        self._close_step_span()
        self._write_timeline_record(dt, num_samples)
        self._step += 1
        self._transition(self._target_state())
        self._open_step_span()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ----------------------------------------------------------- timeline
    def _write_timeline_record(self, dt, num_samples):
        """One JSONL record for the step that just closed (only while the
        window was recording) — the durable perf evidence a dead TPU grant
        cannot take with it."""
        if self._timeline_path is None or not self._recording():
            return
        from . import statistic as _stat
        window = _tracer.since(self._step_mark)
        step_events = [e for e in window
                       if e["type"] != TracerEventType.ProfileStep]
        rec = {
            "schema": STEP_TIMELINE_SCHEMA,
            "step": self._step,
            "step_ms": None if dt is None else round(dt * 1e3, 4),
            "phases": _stat.phase_durations_ms(step_events),
            "ops": _stat.op_digest(step_events, top=8),
            "num_samples": num_samples,
        }
        if self._cache_mark is not None:
            from ..core.tensor import _CACHE_STATS
            rec["cache"] = {k: _CACHE_STATS[k] - self._cache_mark.get(k, 0)
                            for k in ("hits", "misses", "bypass")}
        mem = [m for e in step_events
               for m in (e.get("mem0"), e.get("mem1")) if m is not None]
        rec["mem_peak_bytes"] = max(mem) if mem else None
        os.makedirs(os.path.dirname(os.path.abspath(self._timeline_path)),
                    exist_ok=True)
        with open(self._timeline_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    # ------------------------------------------------------------ reporting
    def step_info(self, unit=None):
        """Last-10-steps digest. `unit` labels throughput: with
        step(num_samples=...) provided, ips = samples/s in that unit
        (reference: profiler.py step_info's `unit`); else steps/s."""
        if not self._step_times:
            return ""
        arr = np.asarray(self._step_times[-10:])
        pairs = [(t, s) for t, s in zip(self._step_times[-10:],
                                        self._step_samples[-10:])
                 if s is not None]
        if unit and pairs:
            ips = sum(s for _, s in pairs) / sum(t for t, _ in pairs)
            return (f"avg step {arr.mean() * 1000:.2f} ms, "
                    f"ips {ips:.2f} {unit}/s")
        # without num_samples the only honest rate is steps/s — a unit
        # label here would caption steps/s as e.g. images/s
        return (f"avg step {arr.mean() * 1000:.2f} ms, "
                f"ips {1.0 / arr.mean():.2f} steps/s")

    def statistics(self):
        """Aggregate spans by name (reference: profiler_statistic.py)."""
        by_name = {}
        for e in self._events:
            by_name.setdefault(e["name"], []).append(e["dur"] or 0)
        rows = []
        for name, durs in by_name.items():
            d = np.asarray(durs, dtype=np.float64) / 1e6  # ms
            rows.append({"name": name, "calls": len(durs),
                         "total_ms": float(d.sum()), "avg_ms": float(d.mean()),
                         "max_ms": float(d.max()), "min_ms": float(d.min())})
        rows.sort(key=lambda r: -r["total_ms"])
        return rows

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Print the summary tables (reference: profiler.py summary /
        profiler_statistic.py _build_table). `views`: a SummaryView value
        or list of them; default prints OverView + OperatorView (+
        DistributedView / MemoryView when comm spans / memory samples
        exist)."""
        from . import statistic as _stat
        text = _stat.build_summary(self._events, sorted_by=sorted_by,
                                   views=views, time_unit=time_unit)
        if text:
            print(text)
        elif not self._step_times:
            return
        if self._step_times:
            print(self.step_info())

    def analyze(self, device=None, top_k=3):
        """Join recorded host spans against the analytical roofline
        (cost_model/analytical.py): per-op achieved vs roofline time, the
        top-k MFU gap contributors, phase breakdown, and coverage of the
        recorded compute span time. Returns statistic.AnalyzeReport."""
        from . import statistic as _stat
        return _stat.analyze(self._events, step_times=self._step_times,
                             device=device, top_k=top_k)


class SortedKeys:
    """reference: profiler/profiler_statistic.py SortedKeys — summary sort
    orders. Host spans only (XLA owns the device timeline), so the GPU*
    keys alias their CPU counterparts."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """reference: profiler/profiler.py SummaryView — which summary tables
    to print."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
