"""Iteration-level (continuous) batching scheduler — the SLO tier.

Orca's [OSDI '22] observation: batching at REQUEST granularity strands
decode slots behind the longest member of the batch. Scheduling at
ITERATION granularity — one decode step at a time — lets a slot whose
sequence hit eos retire immediately and hand its lane to a queued
request while the other slots keep decoding. This module implements
that loop over a GenerationEngine:

  submit() -> bounded admission queue (QueueFullError past the cap,
              LoadShedError past the shed watermark for sheddable
              priority classes, deadline expiry while queued -> TIMEOUT)
  step()   -> retire finished slots (eos / max_new_tokens / deadline),
              refill free slots from the queue by (priority, arrival)
              (prefill = TTFT), grow paged slots' block tables —
              preempting victims under allocation pressure — then
              advance every occupied slot one token (decode)
  drain()  -> stop admitting, run until in-flight work finishes

SLO classes (ISSUE 6): every request carries a priority class
(interactive=0 < standard=1 < batch=2). The queue serves the best
(priority, arrival) first; admission load-sheds sheddable classes past a
queue watermark (or when the block pool runs dry) instead of letting
them rot to a deadline timeout; and when a paged engine cannot allocate
a block, the scheduler PREEMPTS a victim — the worst (priority, deadline
slack) occupant — frees its blocks back to the pool, and requeues it in
recompute style: the victim's prompt+generated-so-far become its restart
prompt, so its delivered token stream continues seamlessly (and, under
greedy decoding, bit-identically). `serving_preempted_total` and
`serving_shed_total` count the events.

Graceful degradation (ISSUE 5): a decode-step exception fails ONLY the
requests that were in flight on the affected slots — each gets terminal
status ERROR (its future unblocks, `handle.error` carries the cause) —
and the scheduler keeps running: the slots are quarantined, ONE probe
slot is released to the next refill, and a successful decode step lifts
the quarantine entirely (reprobe-then-reopen). Queued requests are
untouched. The scheduler can therefore never wedge on a poisoned
executable; it degrades to one-slot throughput until the engine proves
itself healthy again. `serving_decode_failures_total` counts the events
and failed requests land in `serving_requests_total{status="error"}`.

Observability: every step appends a JSONL record (queue depth, active
slots, tokens emitted) and every request completion appends a summary
(TTFT, decode rate, status, priority, preemption count, prefix-cache
hit) PLUS a `paddle_tpu.reqtimeline.v1` timeline record (ISSUE 12):
contiguous queue/prefill|adopt/decode phase segments whose durations sum
exactly to the request's end-to-end latency, re-entering `queue` on
every preemption; the same figures feed profiler spans and the metrics
registry, and `tools/serve_report.py` renders the file. The step loop is
synchronous by design — the engine's decode is one executable replay, so
a thread adds latency, not throughput.

Request attribution (ISSUE 15): every request carries `tenant`/`cohort`
labels — through the metric labelsets (`serving_requests_total{status,
tenant}` and friends), the timeline records, and the profiler span args
— and every load-bearing decision (admit/shed/preempt/place/quarantine/
swap) appends a `paddle_tpu.decisions.v1` audit record whose INPUTS
reproduce the outcome through the shared replay rules in
`observability/decisions.py` (the same code the live path calls). The
labels are observability-only: the engine never sees them, so labeled
and unlabeled traffic decode bit-identically.
"""
import collections
import itertools
import json
import threading
import time

import numpy as np

from ..observability import decisions as _dec
from ..observability import kvledger as _kvl
from ..observability import metrics as _metrics
from ..observability import reqtimeline as _rt
from ..observability import tracecontext as _tc
from ..profiler import (RecordEvent, TracerEventType, record_span,
                        span_attrs)
from .blocks import BlockAllocError
from .engine import _engine_kind, _span

__all__ = ["ServingConfig", "Scheduler", "Request", "RequestHandle",
           "QueueFullError", "LoadShedError", "RateLimitedError",
           "PRIORITIES"]

QUEUED = "QUEUED"
RUNNING = "RUNNING"
DONE = "DONE"
TIMEOUT = "TIMEOUT"
REJECTED = "REJECTED"
ERROR = "ERROR"
SHED = "SHED"

# SLO priority classes: LOWER is better. Admission shedding applies to
# classes >= ServingConfig.shed_priority; preemption victims are picked
# worst-class-first, most-deadline-slack-first within a class.
PRIORITIES = {"interactive": 0, "standard": 1, "batch": 2}

# DEPRECATED counter surface: the per-instance `Scheduler.counts` dict under
# the names below is kept for callers that already read it (`step()` among
# them), but the source of truth is now the unified metrics
# registry (paddle_tpu.observability.metrics) — the families registered
# here, exported via registry().snapshot()/dump_prometheus() and rendered
# by tools/metrics_report.py.
_COUNTERS = ("serving.admitted", "serving.completed", "serving.rejected",
             "serving.timeout", "serving.tokens", "serving.error",
             "serving.shed", "serving.preempted")

_M_REQUESTS = _metrics.counter(
    "serving_requests_total",
    "Serving requests by terminal/admission status and tenant "
    "(ISSUE 15: the tenant labelset rides every per-request family)",
    labelnames=("status", "tenant"))
_M_TOKENS = _metrics.counter(
    "serving_tokens_total", "Tokens emitted by the serving engine",
    labelnames=("tenant",))
_M_QUEUE_DEPTH = _metrics.gauge(
    "serving_queue_depth", "Admission-queue depth after the last step")
_M_OCCUPANCY = _metrics.gauge(
    "serving_slot_occupancy",
    "Fraction of decode slots occupied after the last step")
_M_TTFT = _metrics.histogram(
    "serving_ttft_seconds", "Time to first token per completed request",
    labelnames=("tenant",))
_M_DECODE_SECONDS = _metrics.histogram(
    "serving_decode_step_seconds", "Wall time of one engine decode step")
_M_REQ_DECODE = _metrics.histogram(
    "serving_request_decode_seconds",
    "Per-request decode wall time (first token -> terminal), the "
    "per-tenant decode-latency companion of the tenant-agnostic "
    "per-step histogram", labelnames=("tenant",))
_M_DECODE_FAILURES = _metrics.counter(
    "serving_decode_failures_total",
    "Engine decode/prefill calls that raised; each fails only the "
    "affected requests")
_M_SHED = _metrics.counter(
    "serving_shed_total",
    "Requests load-shed at admission (queue/pool watermark), by tenant "
    "— per-tenant growth is failure-class in tools/metrics_report.py",
    labelnames=("tenant",))
_M_PREEMPTED = _metrics.counter(
    "serving_preempted_total",
    "Preemptions under allocation pressure (victim requeued or "
    "errored), by the victim's tenant", labelnames=("tenant",))
_M_SPEC_PROPOSED = _metrics.counter(
    "serving_spec_proposed_total",
    "Draft tokens proposed to the speculative verifier (occupied "
    "slots), labeled by the engine kind that proposed them (spec | "
    "spec_pp) — the per-engine acceptance RATE is failure-class gated "
    "by tools/metrics_report.py --compare per labelset",
    labelnames=("engine",))
_M_SPEC_ACCEPTED = _metrics.counter(
    "serving_spec_accepted_total",
    "Draft tokens the speculative verifier accepted (occupied slots), "
    "labeled by engine kind like serving_spec_proposed_total",
    labelnames=("engine",))
_M_ADOPTED = _metrics.counter(
    "serving_kv_adopted_total",
    "Requests placed from a handed-off KV bundle instead of a local "
    "prefill (multi-host disaggregated serving)")
_M_SWAPS = _metrics.counter(
    "serving_weight_swaps_total",
    "Weight hot-swaps applied between decode steps, by outcome",
    labelnames=("status",))
_M_SWAP_DROPPED = _metrics.counter(
    "serving_swap_dropped_requests_total",
    "Requests failed by a decode step in a swap's probation window — "
    "zero by construction; any growth is a hot-swap that poisoned the "
    "engine (failure-class in tools/metrics_report.py)")
_M_MODEL_VERSION = _metrics.gauge(
    "serving_model_version",
    "Model version the engine is currently serving (flips on hot-swap)")
_M_RATE_LIMITED = _metrics.counter(
    "serving_rate_limited_total",
    "Requests denied at admission by their tenant's token bucket "
    "(ISSUE 17) — per-tenant growth is failure-class in "
    "tools/metrics_report.py", labelnames=("tenant",))
_M_ADAPTER_SWAPS = _metrics.counter(
    "serving_adapter_swaps_total",
    "Per-tenant LoRA adapter hot-swaps applied between decode steps, "
    "by outcome (a failed swap leaves the tenant's OLD adapter serving)",
    labelnames=("status",))


class QueueFullError(RuntimeError):
    """Admission queue at capacity — backpressure, caller should retry."""


class LoadShedError(QueueFullError):
    """Request shed at admission by the SLO watermark — the system chose
    to fail this (sheddable-class) request fast rather than queue it past
    its useful life. Terminal status SHED."""


class RateLimitedError(QueueFullError):
    """Request denied at admission by its tenant's token bucket (ISSUE
    17): the request's token cost (prompt + max_new) exceeds what the
    bucket holds right now. Terminal status SHED with the request
    record's `rate_limited` flag set; a QueueFullError subclass so
    existing backpressure handlers (retry / count-and-move-on) keep
    working unchanged."""


class ServingConfig:
    def __init__(self, max_queue=64, default_max_new_tokens=32,
                 default_timeout_s=None, metrics_path=None,
                 shed_watermark=None, shed_priority=2,
                 shed_pool_free=None):
        self.max_queue = int(max_queue)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.default_timeout_s = default_timeout_s
        self.metrics_path = metrics_path
        # load shedding: None disables. shed_watermark is a queue-depth
        # threshold; shed_pool_free a block-pool free-fraction floor.
        # Classes >= shed_priority are sheddable.
        self.shed_watermark = None if shed_watermark is None \
            else int(shed_watermark)
        self.shed_priority = int(shed_priority)
        self.shed_pool_free = None if shed_pool_free is None \
            else float(shed_pool_free)


class Request:
    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens, deadline, submitted_at,
                 priority=1, rng_seed=None, rng_gen=0, tenant=None,
                 cohort=None):
        self.id = next(Request._ids)
        self.prompt = list(prompt)        # ORIGINAL prompt, never mutated
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline          # absolute clock value or None
        self.submitted_at = submitted_at
        self.priority = int(priority)
        # request attribution (ISSUE 15): the tenant label carried into
        # every metric labelset, decision record, timeline record and
        # profiler span arg this request touches. `cohort` is the free-
        # form request-class companion (e.g. "interactive" traffic vs a
        # batch backfill inside one tenant). Observability-only by
        # construction: neither value reaches the engine.
        self.tenant = str(tenant) if tenant else _dec.DEFAULT_TENANT
        self.cohort = str(cohort) if cohort else None
        # multi-tenant serving (ISSUE 17): the adapter the tenant's
        # decode runs under (None = base weights), the prefix-cache
        # namespace its blocks live in (None = the shared unscoped
        # space), and whether admission denied it by token bucket
        self.adapter_id = None
        self.prefix_namespace = None
        self.rate_limited = False
        # per-request sampler RNG (ISSUE 13): generation index n samples
        # with fold_in(key(rng_seed), rng_gen + n) whatever slot/engine/
        # host runs it. rng_gen > 0 means tokens 0..rng_gen-1 were
        # already delivered elsewhere (a router failover restart) and
        # this request's prompt carries them.
        self.rng_seed = rng_seed          # filled by the scheduler
        self.rng_gen = int(rng_gen)
        self.status = QUEUED
        self.tokens = []                  # generated tokens, stream order
        self.error = None                 # cause string for status ERROR
        self.slot = None
        self.preempted = 0                # times evicted and requeued
        self.prefix_hit = False           # prefill reused cached blocks
        self.adopted = False              # placed from a handed-off bundle
        self._staged = None               # (ks, vs, plen, first_token)
        # fleet prefix restore (ISSUE 18): a wire-shipped PREFIX chain
        # (ks, vs, plen, namespace) registered into the local prefix
        # cache just before this request's own prefill runs
        self._staged_prefix = None
        self.kv_restored_tokens = 0       # tokens the restore registered
        self.tier_hit = False             # prefill restored tiered KV
        self.restore_s = 0.0              # seconds spent restoring it
        self.spec_proposed = 0            # draft tokens proposed for us
        self.spec_accepted = 0            # ... and accepted by verify
        self._exec_prompt = None          # recompute prompt after preempt
        self.first_token_at = None        # TTFT timestamp
        self.finished_at = None
        self._done = threading.Event()
        # end-to-end phase timeline (ISSUE 12): the queue segment opens
        # at submission, so segment durations sum EXACTLY to
        # finished_at - submitted_at by PhaseTrail's construction
        self.trail = _rt.PhaseTrail()
        self.trail.begin(_rt.PH_QUEUE, submitted_at)
        # trace id active at submission (None outside a trace window):
        # joins this request's timeline record to its profiler spans
        self.trace_id = _tc.current_trace_id()

    @property
    def exec_prompt(self):
        """What prefill actually runs: the original prompt, or — after a
        preemption — prompt + everything already generated, so the
        delivered stream continues where it left off."""
        return self._exec_prompt if self._exec_prompt is not None \
            else self.prompt

    def finished(self, eos_token_id):
        """THE completion predicate — the single definition shared by
        retire, prefill-time completion, and the multi-token window
        append loop, so the stop rule (max_new_tokens / eos) can never
        drift between the one-token and speculative paths."""
        return (len(self.tokens) >= self.max_new_tokens
                or (eos_token_id is not None and bool(self.tokens)
                    and self.tokens[-1] == eos_token_id))


class RequestHandle:
    """Caller-facing view of one request: a live token stream + terminal
    status. `tokens` is append-only in generation order, so a streaming
    client can poll it while the scheduler runs."""

    def __init__(self, req, clock):
        self._req = req
        self._clock = clock

    @property
    def request_id(self):
        return self._req.id

    @property
    def status(self):
        return self._req.status

    @property
    def tokens(self):
        return list(self._req.tokens)

    @property
    def error(self):
        """The decode failure that killed this request (status ERROR)."""
        return self._req.error

    @property
    def priority(self):
        return self._req.priority

    @property
    def tenant(self):
        """The request's attribution tenant label (ISSUE 15)."""
        return self._req.tenant

    @property
    def cohort(self):
        """The request-class label within its tenant (or None)."""
        return self._req.cohort

    @property
    def rate_limited(self):
        """Whether admission denied this request by token bucket
        (ISSUE 17; terminal status SHED with this flag set)."""
        return self._req.rate_limited

    @property
    def adapter_id(self):
        """The LoRA adapter this request decoded under (None = base)."""
        return self._req.adapter_id

    @property
    def prefix_namespace(self):
        """The prefix-cache namespace the request's blocks live in."""
        return self._req.prefix_namespace

    @property
    def preempted(self):
        """How many times the request was evicted and requeued."""
        return self._req.preempted

    @property
    def prefix_hit(self):
        """Whether prefill reused shared prefix-cache blocks."""
        return self._req.prefix_hit

    @property
    def adopted(self):
        """Whether the request was placed from a handed-off KV bundle
        (its prefill ran on another host) instead of a local prefill."""
        return self._req.adopted

    @property
    def spec_proposed(self):
        """Draft tokens proposed for this request (speculative engines)."""
        return self._req.spec_proposed

    @property
    def spec_accepted(self):
        """Draft tokens the verifier accepted for this request."""
        return self._req.spec_accepted

    @property
    def phases(self):
        """The request's closed phase segments so far, t0-relative to its
        submission (reqtimeline `rel()` shape) — what the POLL verb ships
        to the router as `worker_phases` for terminal fleet requests."""
        return self._req.trail.rel(self._req.submitted_at)

    def done(self):
        return self._req.status in (DONE, TIMEOUT, REJECTED, ERROR, SHED)

    def result(self, timeout=None):
        """Block until terminal; returns the token list. TIMEOUT and
        ERROR requests return their partial output (status/`error` tell
        the caller)."""
        if not self._req._done.wait(timeout):
            raise TimeoutError(f"request {self._req.id} still "
                               f"{self._req.status}")
        return self.tokens

    @property
    def ttft_s(self):
        r = self._req
        if r.first_token_at is None:
            return None
        return r.first_token_at - r.submitted_at


class Scheduler:
    def __init__(self, engine, config=None, clock=time.monotonic,
                 tenancy=None, **kwargs):
        self.engine = engine
        self.config = config or ServingConfig(**kwargs)
        # multi-tenant serving (ISSUE 17): `tenancy` is a
        # tenancy.TenancyConfig — per-tenant token buckets gate
        # admission AHEAD of the shed/preempt machinery, per-namespace
        # resident-block quotas arm the prefix cache's protected
        # eviction, and placement binds each slot to its tenant's
        # adapter + namespace. tenancy=None is the pre-tenancy
        # scheduler, bit for bit.
        self._tenancy = tenancy
        self._buckets = tenancy.buckets(clock) if tenancy is not None \
            else {}
        cache = getattr(engine, "prefix_cache", None)
        if tenancy is not None and cache is not None:
            cache.set_quotas(tenancy.quotas())
        # engine kind (ISSUE 14): labels the spec proposed/accepted
        # counters and the run record, so a fleet mixing spec and
        # spec_pp engines gates each acceptance rate separately.
        # Minimal stub engines (tests) without a real config class
        # degrade to "unknown" instead of failing construction.
        try:
            self._engine_kind = _engine_kind(engine.config)
        except Exception:                                # noqa: BLE001
            self._engine_kind = "unknown"
        self._clock = clock
        self._clock_is_span_clock = clock in (time.monotonic,
                                              time.perf_counter)
        self._queue = collections.deque()
        self._slots = [None] * engine.slots   # Request or None
        self._quarantined = set()             # slots held out after a failure
        self._decode_failures = 0
        self._draining = False
        self._steps = 0
        self._decode_tokens = 0
        self._decode_time_s = 0.0
        self._placed = 0                      # placements, and the prompt
        self._placed_tokens = 0               # tokens they brought
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._pending_swaps = collections.deque()   # armed hot-swaps
        self._pending_adapter_swaps = collections.deque()
        self.last_adapter_swap = None
        self._swap_probation = False          # first step after a swap
        self.last_swap = None                 # apply_pending_swap summary
        self.model_version = None
        self._completed = []
        # decisions.v1 records, newest-last; RING-bounded — the JSONL
        # stream keeps the full history, the in-memory view is for
        # tests/bench audits and must not grow with request count on a
        # long-lived worker
        self._decisions = collections.deque(maxlen=4096)
        self.counts = dict.fromkeys(_COUNTERS, 0)
        # KV attribution plane (ISSUE 16): when the engine attached a
        # ledger, reconcile it against the real pool at every step
        # boundary and stream its events into the serving JSONL. The
        # scheduler is ALSO the attribution source: every engine call
        # that can touch the pool runs under `_kv_attr`, so ledger
        # events carry request/tenant/origin with zero engine plumbing.
        ledger = getattr(engine, "kv_ledger", None)
        pool = getattr(engine, "block_pool", None)
        self._kv_reconciler = (
            _kvl.LedgerReconciler(ledger, pool,
                                  getattr(engine, "prefix_cache", None),
                                  tier_store=getattr(engine, "kv_tiers",
                                                     None))
            if ledger is not None and pool is not None else None)
        self._kv_events_written = 0
        self._metrics_f = (open(self.config.metrics_path, "a")
                           if self.config.metrics_path else None)
        self._write_run_record()

    def _kv_attr(self, req, origin):
        """Attribution scope for one engine call touching the block
        pool — a shared no-op context when no ledger is attached (the
        zero-cost contract)."""
        if self._kv_reconciler is None:
            return _kvl.NULL_CTX
        return _kvl.attribution(
            request_id=req.id if req is not None else None,
            tenant=req.tenant if req is not None else None,
            origin=origin)

    def _write_run_record(self):
        """One `run` header record per scheduler: the engine's KV/weight
        dtypes (ISSUE 11), so a serving JSONL is self-describing about
        what precision produced it. `quant_greedy_match` is filled by
        quality harnesses that append their own run record; absent
        fields default — historical artifacts stay gradeable."""
        if not self._metrics_f:
            return
        cfg = self.engine.config
        rec = {
            "kind": "run",
            "engine": self._engine_kind,
            "kv_dtype": getattr(cfg, "kv_dtype", "float32"),
            "weight_dtype": getattr(cfg, "weight_dtype", "float32")}
        # hybrid-parallel shape (ISSUE 13): lets serve_report label the
        # run and render the per-stage column for pp engines
        tp, pp = getattr(cfg, "tp", 1), getattr(cfg, "pp", 1)
        if tp != 1 or pp != 1:
            rec["tp"], rec["pp"] = int(tp), int(pp)
        # speculative shape (ISSUE 14): the spec AND spec_pp run records
        # carry the window knob next to their acceptance-rate fields
        gamma = getattr(cfg, "gamma", None)
        if gamma is not None:
            rec["gamma"] = int(gamma)
        self._metrics_f.write(json.dumps(rec) + "\n")
        self._metrics_f.flush()

    # -- the decision audit log (ISSUE 15) -----------------------------------
    def _decide(self, action, req, inputs, outcome, tenant=None):
        """Append one decisions.v1 record (in memory + the serving
        JSONL): the decision's inputs make it reproducible via the
        paddle_tpu.observability.decisions replay rules — the same code
        that just made it. `tenant` overrides the label for decisions
        with no Request context (adapter swaps)."""
        rec = _dec.build_record(
            action, inputs, outcome, "scheduler", self._clock(),
            request_id=getattr(req, "id", None),
            tenant=tenant if tenant is not None
            else getattr(req, "tenant", None),
            cohort=getattr(req, "cohort", None),
            trace_id=getattr(req, "trace_id", None))
        self._decisions.append(rec)
        if self._metrics_f:
            self._metrics_f.write(json.dumps(rec) + "\n")
            self._metrics_f.flush()
        return rec

    def decision_records(self):
        """Every decisions.v1 record emitted so far — what bench/tests
        audit without re-reading the JSONL."""
        return list(self._decisions)

    def _pool_free_fraction(self):
        """Allocatable fraction of the block pool (prefix-cache-held
        blocks count as free — they evict on demand), or None on
        engines without a pool. The shed rule's input, recorded on
        every shed decision."""
        pool = getattr(self.engine, "block_pool", None)
        if pool is None or pool.capacity <= 0:
            return None
        cache = getattr(self.engine, "prefix_cache", None)
        free = pool.available + (cache.evictable()
                                 if cache is not None else 0)
        return free / pool.capacity

    # -- admission -----------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, timeout_s=None,
               priority="standard", staged_kv=None, rng_seed=None,
               rng_gen=0, tenant=None, cohort=None, adapter_id=None,
               prefix_namespace=None, staged_prefix=None):
        """`staged_kv=(ks, vs, plen, first_token[, rng])` places the
        request from a handed-off KV bundle (another host already ran
        its prefill) instead of computing prefill locally — `prompt`
        must still be the full prompt: it is the recompute source for
        preemption and failover restarts, and the staged bundle is
        silently dropped (local prefill resumes ownership) whenever it
        cannot be adopted — wrong length, engine without a paged pool,
        or a bundle that fails adoption for any non-pressure reason.
        The optional 5th element is the bundle's (seed, gen) sampler
        state (a v3 bundle), which adoption arms verbatim.

        `rng_seed`/`rng_gen` pin the request's sampler stream (ISSUE
        13): token n samples with fold_in(key(rng_seed), rng_gen + n),
        so a restart carrying the same seed and the delivered count
        continues a sampled stream bit-identically. rng_seed=None
        derives a deterministic per-request default from the engine
        seed and the request id — in-process replays (and preemption
        restarts) are exact; cross-process oracles must pass the seed
        explicitly.

        `tenant`/`cohort` (ISSUE 15) label the request for attribution:
        metrics labelsets, the decision audit log, timeline records and
        profiler spans all carry them; the engine never sees either, so
        labeled and unlabeled traffic decode bit-identically.

        `adapter_id`/`prefix_namespace` (ISSUE 17) pin the LoRA adapter
        the request decodes under and the prefix-cache namespace its
        blocks live in — wire pass-throughs for the distributed worker;
        local callers usually leave both None and let the scheduler's
        TenancyConfig resolve them from the tenant label. With a
        tenancy config, admission ALSO runs the tenant's token bucket
        BEFORE the shed watermark: a request costing more tokens
        (prompt + max_new) than the bucket holds raises
        RateLimitedError, ticks serving_rate_limited_total{tenant}, and
        leaves a replayable rate_limit decision record.

        `staged_prefix=(ks, vs, plen, namespace)` (ISSUE 18) is a
        fleet-shipped PREFIX chain: at placement the scheduler first
        registers it into the local prefix cache (a named `kv_restore`
        timeline phase) so the request's own prefill then matches it
        like a warm local chain — the affinity-miss restore path. The
        request still owns its full prompt: a restore that fails for
        ANY reason (pressure, torn wire payload, chaos) degrades to
        plain recompute, and preemption drops the staged bundle exactly
        like staged_kv."""
        prompt = [int(t) for t in prompt]
        now = self._clock()
        max_new = self.config.default_max_new_tokens \
            if max_new_tokens is None else max_new_tokens
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        prio = PRIORITIES.get(priority, priority)
        if not isinstance(prio, int):
            raise ValueError(f"unknown priority {priority!r}; want one of "
                             f"{sorted(PRIORITIES)} or an int class")
        timeout = timeout_s if timeout_s is not None \
            else self.config.default_timeout_s
        req = Request(prompt, max_new,
                      now + timeout if timeout is not None else None, now,
                      priority=prio, rng_seed=rng_seed, rng_gen=rng_gen,
                      tenant=tenant, cohort=cohort)
        if req.rng_seed is None:
            req.rng_seed = (getattr(self.engine.config, "seed", 0)
                            * 1000003 + req.id * 7919 + 1) & 0x7FFFFFFF
        req.adapter_id = str(adapter_id) if adapter_id else None
        req.prefix_namespace = prefix_namespace if prefix_namespace \
            is not None else (self._tenancy.namespace_of(req.tenant)
                              if self._tenancy is not None else None)
        handle = RequestHandle(req, self._clock)
        if self._draining:
            self._finish(req, REJECTED, "serving.rejected")
            raise QueueFullError("scheduler is draining")
        if len(self._queue) >= self.config.max_queue:
            self._finish(req, REJECTED, "serving.rejected")
            raise QueueFullError(
                f"admission queue full ({self.config.max_queue})")
        if not prompt:
            self._finish(req, REJECTED, "serving.rejected")
            raise ValueError("empty prompt")
        if len(prompt) > self.engine.max_prompt_len or \
                len(prompt) + max_new > self.engine.config.max_len:
            # validate against what prefill can actually serve — a request
            # admitted past these limits would blow up inside step() and
            # strand itself with no terminal status
            self._finish(req, REJECTED, "serving.rejected")
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the engine limits (max prompt "
                f"{self.engine.max_prompt_len}, cache max_len "
                f"{self.engine.config.max_len})")
        # per-tenant token bucket (ISSUE 17) — AHEAD of the shed
        # watermark: a budget denial is the tenant's own contract, not
        # system pressure, so it must not depend on queue state. The
        # live verdict IS decisions.replay_rate_limit over the recorded
        # inputs — the validator re-runs the same rule on every artifact.
        bucket = self._buckets.get(req.tenant)
        if bucket is not None:
            cost = len(prompt) + max_new
            rl_inputs = {"tenant": req.tenant, "cost": cost,
                         "tokens_available": bucket.available(),
                         "rate_per_s": bucket.rate,
                         "burst": bucket.burst}
            rl_why = _dec.replay_rate_limit(rl_inputs)
            if rl_why:
                req.rate_limited = True
                _M_RATE_LIMITED.labels(tenant=req.tenant).inc()
                self._decide("rate_limit", req, rl_inputs,
                             {"reason": rl_why})
                self._finish(req, SHED, "serving.shed")
                raise RateLimitedError(
                    f"rate limited (tenant {req.tenant}): {rl_why}")
            bucket.take(cost)
        shed_inputs = self._shed_inputs(prio)
        shed_why = _dec.replay_shed(shed_inputs)
        if shed_why:
            _M_SHED.labels(tenant=req.tenant).inc()
            self._decide("shed", req, shed_inputs, {"reason": shed_why})
            self._finish(req, SHED, "serving.shed")
            raise LoadShedError(
                f"load shed (priority class {prio}): {shed_why}")
        if staged_kv is not None and hasattr(self.engine, "adopt_kv") \
                and int(staged_kv[2]) == len(prompt):
            req._staged = staged_kv
        if staged_prefix is not None \
                and hasattr(self.engine, "restore_prefix"):
            req._staged_prefix = staged_prefix
        self._queue.append(req)
        self._decide("admit", req,
                     dict(shed_inputs, max_queue=self.config.max_queue,
                          staged=req._staged is not None),
                     {"admitted": True, "queued_behind": len(self._queue)
                      - 1})
        self._count("serving.admitted", req)
        return handle

    def _shed_inputs(self, prio):
        """The admission load-shed rule's inputs (SLO admission control,
        ISSUE 6): sheddable classes are failed FAST past the watermark
        instead of queueing to a certain deadline death. The VERDICT is
        `decisions.replay_shed(inputs)` — the same rule every shed
        decision record replays under, so the audit log is reproducible
        by construction."""
        c = self.config
        # the pool scan (refcounts over every prefix-cache entry) is
        # paid only when the pool-free rule is armed — submit is the
        # admission hot path and the replay ignores the field otherwise
        return {"priority": prio, "shed_priority": c.shed_priority,
                "queue_depth": len(self._queue),
                "shed_watermark": c.shed_watermark,
                "pool_free_fraction": self._pool_free_fraction()
                if c.shed_pool_free is not None else None,
                "shed_pool_free": c.shed_pool_free}

    # -- the iteration loop --------------------------------------------------
    # -- zero-downtime weight hot-swap (ISSUE 10) ----------------------------
    def schedule_weight_swap(self, params, version=None):
        """Arm a weight hot-swap: `params` ({name: array}, e.g. a
        ckpt_commit-verified checkpoint's state dict) replaces the
        engine's serving weights at the TOP of the next step — strictly
        BETWEEN decode steps, so every emitted token is computed wholly
        under one weight set and no request is dropped or retraced.
        Returns a threading.Event set once the swap was applied (or
        rejected); the outcome lands in `self.last_swap` and the
        `serving_weight_swaps_total{status}` counter, and a successful
        swap flips the `serving_model_version` gauge to `version`.
        A failed swap (validation, or the `serving.weight_swap` chaos
        site) keeps the OLD weights serving — in-flight streams never
        see a half-applied weight set. Swaps armed back-to-back QUEUE
        and apply in arrival order in the same between-steps window —
        every caller's event fires, the last swap wins the steady
        state."""
        ev = threading.Event()
        self._pending_swaps.append({"params": params, "version": version,
                                    "event": ev})
        return ev

    def apply_pending_swap(self):
        """Apply every armed hot-swap now, in arrival order (called at
        the top of every step(); idle worker loops may also call it
        directly so a swap never waits for traffic). Returns True when
        at least one swap was processed."""
        applied = False
        while True:
            try:
                swap = self._pending_swaps.popleft()
            except IndexError:
                return applied
            applied = True
            with RecordEvent("serving::weight_swap",
                             TracerEventType.UserDefined,
                             {"version": swap["version"],
                              "inflight": self.active_slots()}):
                try:
                    n = self.engine.swap_params(swap["params"])
                except Exception as e:                   # noqa: BLE001
                    _M_SWAPS.labels(status="failed").inc()
                    self.last_swap = {
                        "ok": False, "version": swap["version"],
                        "error": f"{type(e).__name__}: {e}"}
                else:
                    _M_SWAPS.labels(status="ok").inc()
                    if swap["version"] is not None:
                        self.model_version = swap["version"]
                        _M_MODEL_VERSION.set(float(swap["version"]))
                    # probation: requests a decode failure kills in the
                    # very next step count as swap-dropped (must stay 0)
                    self._swap_probation = True
                    self.last_swap = {"ok": True,
                                      "version": swap["version"],
                                      "params": n,
                                      "inflight": self.active_slots()}
            self._decide("swap", None,
                         {"version": swap["version"],
                          "inflight": self.active_slots()},
                         dict(self.last_swap))
            # per-swap outcome rides the event: a queued swap's waiter
            # must not read a LATER swap's last_swap
            swap["event"].swap_result = dict(self.last_swap)
            swap["event"].set()

    # -- per-tenant adapter hot-swap (ISSUE 17) ------------------------------
    def schedule_adapter_swap(self, tenant, state):
        """Arm a per-tenant LoRA adapter hot-swap: `state` (a
        tenancy.AdapterState, e.g. AdapterRegistry.resolve's result)
        replaces `tenant`'s adapter at the TOP of the next step —
        strictly BETWEEN decode steps, the weight-swap window, so every
        emitted token is computed wholly under one adapter version.
        Same atomic-failure contract as schedule_weight_swap: a failed
        swap (bank validation, or the `serving.adapter_swap` chaos
        site) leaves the tenant's OLD adapter serving and every other
        tenant untouched — base weights are never involved. Returns a
        threading.Event set once applied or rejected; the outcome lands
        in `self.last_adapter_swap`, the event's `swap_result`, and
        `serving_adapter_swaps_total{status}`."""
        ev = threading.Event()
        self._pending_adapter_swaps.append(
            {"tenant": str(tenant), "state": state, "event": ev})
        return ev

    def apply_pending_adapter_swap(self):
        """Apply every armed adapter swap now, in arrival order (called
        at the top of every step()). Returns True when at least one
        swap was processed."""
        applied = False
        while True:
            try:
                swap = self._pending_adapter_swaps.popleft()
            except IndexError:
                return applied
            applied = True
            with RecordEvent("serving::adapter_swap",
                             TracerEventType.UserDefined,
                             {"tenant": swap["tenant"],
                              "inflight": self.active_slots()}):
                try:
                    idx = self.engine.swap_adapter(swap["tenant"],
                                                   swap["state"])
                except Exception as e:                   # noqa: BLE001
                    _M_ADAPTER_SWAPS.labels(status="failed").inc()
                    self.last_adapter_swap = {
                        "ok": False, "tenant": swap["tenant"],
                        "error": f"{type(e).__name__}: {e}"}
                else:
                    _M_ADAPTER_SWAPS.labels(status="ok").inc()
                    self.last_adapter_swap = {
                        "ok": True, "tenant": swap["tenant"],
                        "slot": idx,
                        "inflight": self.active_slots()}
            self._decide("swap", None,
                         {"kind": "adapter", "tenant": swap["tenant"],
                          "inflight": self.active_slots()},
                         dict(self.last_adapter_swap),
                         tenant=swap["tenant"])
            swap["event"].swap_result = dict(self.last_adapter_swap)
            swap["event"].set()

    def step(self):
        """One scheduling iteration. Returns True while work remains.

        One `serving::step` span covers it, with a child per phase
        (retire, refill -> prefill, grow, decode.prepare, decode_step,
        decode.commit, emit, bookkeeping, step.counts); the counts in its
        attrs are
        taken as the step ends, inside `step.counts`
        (docs/observability.md, "What a serving operator gets")."""
        attrs = {"step": self._steps}
        preempted = self.counts["serving.preempted"]
        with _span("serving::step", attrs):
            more = self._step()
            with _span("serving::step.counts"):
                attrs["preempted"] = \
                    self.counts["serving.preempted"] - preempted
                self._boundary_counts(attrs)
        return more

    def _boundary_counts(self, attrs):
        """What the step leaves behind it: queue, slots, and on a paged
        engine the pool's blocks against the tokens resident in them."""
        attrs["queue_depth"] = len(self._queue)
        attrs["active_slots"] = self.active_slots()
        attrs["slots"] = len(self._slots)
        pool = getattr(self.engine, "block_pool", None)
        if pool is None or not hasattr(self.engine, "slot_positions"):
            return
        attrs["kv_blocks_in_use"] = pool.in_use
        attrs["kv_blocks_total"] = pool.capacity
        pos = self.engine.slot_positions()
        attrs["kv_tokens_held"] = sum(
            int(pos[slot]) for slot, req in enumerate(self._slots)
            if req is not None)
        gauges = getattr(self.engine, "layout_gauges", None)
        if gauges is not None:
            # a model with its own cache layout: per-slot state and paged
            # rows in bytes, and the prefix lookups that were refused
            attrs.update(gauges() or {})

    def _step(self):
        self.apply_pending_swap()
        self.apply_pending_adapter_swap()
        now = self._clock()
        with _span("serving::retire"):
            self._expire_queued(now)
            self._retire(now)
        refill = {}
        with _span("serving::refill", refill):
            placed, tokens = self._placed, self._placed_tokens
            self._refill(now)
            refill["admitted"] = self._placed - placed
            refill["prefill_tokens"] = self._placed_tokens - tokens
        with _span("serving::grow"):
            self._grow_paged_slots(now)
        active = [r for r in self._slots if r is not None]
        if active:
            t0 = self._clock()
            # a speculative engine advances each slot by a whole verify
            # window per step; everything else stays a 1-wide window
            decode_many = getattr(self.engine, "decode_many", None)
            try:
                if decode_many is not None:
                    toks, counts = decode_many()
                else:
                    toks = np.asarray(self.engine.decode()).reshape(-1, 1)
                    counts = np.ones((toks.shape[0],), np.int32)
            except Exception as e:                       # noqa: BLE001
                self._on_decode_failure(e)
            else:
                dt = self._clock() - t0
                self._decode_time_s += dt
                _M_DECODE_SECONDS.observe(dt)
                emitted = {"tokens": 0}
                with _span("serving::emit", emitted):
                    before = self._decode_tokens
                    self._emit(toks, counts)
                    emitted["tokens"] = self._decode_tokens - before
                # a healthy step is the reprobe proof: reopen every
                # quarantined slot for the next refill (and a fresh
                # hot-swap leaves probation — it did not poison decode)
                self._quarantined.clear()
                self._swap_probation = False
        bookkeeping = {}
        with _span("serving::bookkeeping", bookkeeping):
            self._steps += 1
            _M_QUEUE_DEPTH.set(len(self._queue))
            _M_OCCUPANCY.set(self.active_slots() / max(self.engine.slots, 1))
            # the KV ledger watchdog (ISSUE 16): every step boundary,
            # replay-vs-reality — a leaked block is caught within ONE
            # step of the damage, and the step's lifecycle events land in
            # the JSONL ahead of the step record that closed them
            if self._kv_reconciler is not None:
                self._kv_reconciler.check()
                bookkeeping.update(self._kv_reconciler.last_check)
                self._write_kvledger_records()
            self._write_step_record(now, len(active))
        return bool(self._queue or any(s is not None for s in self._slots))

    def _emit(self, toks, counts):
        """Append each slot's emitted run to its request's stream."""
        proposed = toks.shape[1] - 1     # γ for spec, 0 otherwise
        eos = self.engine.config.eos_token_id
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            if proposed:
                accepted = int(counts[slot]) - 1
                req.spec_proposed += proposed
                req.spec_accepted += accepted
                self._spec_proposed += proposed
                self._spec_accepted += accepted
                _M_SPEC_PROPOSED.labels(
                    engine=self._engine_kind).inc(proposed)
                _M_SPEC_ACCEPTED.labels(
                    engine=self._engine_kind).inc(accepted)
            # append the slot's emitted run, truncating where the
            # one-token loop would have stopped (eos / max_new) —
            # the delivered stream stays bit-identical to it
            for j in range(int(counts[slot])):
                req.tokens.append(int(toks[slot, j]))
                self._decode_tokens += 1
                self._count("serving.tokens", req)
                if req.finished(eos):
                    break

    def active_slots(self):
        """Occupied decode slots right now (the concurrency figure the
        load harness tracks)."""
        return sum(1 for s in self._slots if s is not None)

    def drain(self, max_steps=100000):
        """Graceful drain: no new admissions, finish what's in flight."""
        self._draining = True
        for _ in range(max_steps):
            if not self.step():
                break
        self.close()

    def set_draining(self, draining=True):
        """Toggle admission-stop WITHOUT the blocking step loop `drain`
        runs: in-flight work keeps decoding on the normal step cadence,
        new `submit` calls raise QueueFullError("scheduler is
        draining"). The multi-host OP_DRAIN verb (ISSUE 20) flips this
        on a live worker so the router can hand its streams elsewhere
        and retire it with zero drops — and flips it back off when a
        rolling restart reinstates the worker."""
        self._draining = bool(draining)

    @property
    def draining(self):
        return self._draining

    def cancel(self, handle, status=None, counter=None):
        """Cancel one request wherever it currently is — queued
        (removed from the admission queue) or running (slot reset, KV
        blocks released) — and drive it terminal. Returns True when the
        request was live and is now terminal, False when it had already
        finished (cancel lost the race; the result stands).

        The router's migration path (ISSUE 20) cancels the ORIGINAL
        copy of a stream it has re-placed on a healthy worker, and the
        deadline-propagation path cancels work whose budget expired
        router-side (`status=TIMEOUT`). Defaults count the cancel as a
        shed."""
        req = getattr(handle, "_req", handle)
        if req.status not in (QUEUED, RUNNING):
            return False
        status = SHED if status is None else status
        if counter is None:
            counter = {TIMEOUT: "serving.timeout",
                       ERROR: "serving.error"}.get(status, "serving.shed")
        try:
            self._queue.remove(req)
        except ValueError:
            pass
        for slot, r in enumerate(self._slots):
            if r is req:
                try:
                    with self._kv_attr(req, "cancel"):
                        self.engine.reset_slot(slot)
                except Exception:                        # noqa: BLE001
                    pass          # a broken engine must not block cancel
                self._slots[slot] = None
                req.slot = None
        self._finish(req, status, counter)
        return True

    def run_until_idle(self, max_steps=100000):
        for _ in range(max_steps):
            if not self.step():
                return

    def close(self):
        if self._metrics_f:
            self._metrics_f.close()
            self._metrics_f = None

    def _fail_engine_request(self, slot, req, cause):
        """Terminal-ERROR one request after an engine failure: slot
        reset (broken engines must not block cleanup), future unblocked,
        error cause attached."""
        try:
            with self._kv_attr(req, "error"):
                self.engine.reset_slot(slot)
        except Exception:                                # noqa: BLE001
            pass
        self._slots[slot] = None
        req.error = cause
        self._finish(req, ERROR, "serving.error")

    def _quarantine_all_but_probe(self):
        """The reprobe protocol, shared by the decode and prefill
        failure paths: EVERY slot is quarantined (free ones too —
        otherwise a half-empty engine would refill a whole batch into
        the next failing step), exactly one probe slot rejoins
        immediately, and the next SUCCESSFUL decode step releases the
        rest."""
        self._quarantined = set(range(self.engine.slots))
        self._quarantined.discard(min(self._quarantined))

    def _on_decode_failure(self, exc):
        """Contain a decode-step exception: error out ONLY the in-flight
        requests, quarantine their slots, release one probe slot. The
        queue and the step loop are untouched — the scheduler degrades
        instead of wedging."""
        self._decode_failures += 1
        _M_DECODE_FAILURES.inc()
        if self._swap_probation:
            # the first decode step after a hot-swap failed: the swap
            # took these requests down — the gated tripwire counter
            _M_SWAP_DROPPED.inc(self.active_slots())
            self._swap_probation = False
        cause = f"{type(exc).__name__}: {exc}"
        failed = [{"slot": s, "request_id": r.id, "tenant": r.tenant}
                  for s, r in enumerate(self._slots) if r is not None]
        with RecordEvent("serving::decode_failure",
                         TracerEventType.UserDefined,
                         {"error": cause[:200],
                          "failures": self._decode_failures}):
            for slot, req in enumerate(self._slots):
                if req is not None:
                    self._fail_engine_request(slot, req, cause)
        self._quarantine_all_but_probe()
        self._decide("quarantine", None,
                     {"error": cause[:200], "failed": failed,
                      "engine_slots": self.engine.slots},
                     {"quarantined": sorted(self._quarantined),
                      "probe_slot": min(set(range(self.engine.slots))
                                        - self._quarantined, default=None),
                      "failed_requests": len(failed)})

    def _on_prefill_failure(self, slot, req, exc):
        """A prefill exception fails ONLY the request being placed — it
        gets a terminal ERROR (its future unblocks, never leaks) and the
        quarantine protocol engages exactly as for a decode failure, so
        a broken engine degrades to one errored request per step instead
        of escaping step() with a raw exception."""
        self._decode_failures += 1
        _M_DECODE_FAILURES.inc()
        cause = f"{type(exc).__name__}: {exc}"
        with RecordEvent("serving::prefill_failure",
                         TracerEventType.UserDefined,
                         {"slot": slot, "request": req.id,
                          "tenant": req.tenant,
                          "error": cause[:200]}):
            self._fail_engine_request(slot, req, cause)
        self._quarantine_all_but_probe()
        self._decide("quarantine", req,
                     {"error": cause[:200],
                      "failed": [{"slot": slot, "request_id": req.id,
                                  "tenant": req.tenant}],
                      "engine_slots": self.engine.slots},
                     {"quarantined": sorted(self._quarantined),
                      "probe_slot": min(set(range(self.engine.slots))
                                        - self._quarantined, default=None),
                      "failed_requests": 1})

    # -- SLO machinery: preemption ------------------------------------------
    def _victim_candidates(self, exclude=()):
        """The candidate table a preemption weighs: every occupied,
        non-excluded slot with its (priority, deadline slack, tenant) —
        in slot order, recorded verbatim on the decision record so the
        victim choice replays exactly."""
        now = self._clock()
        cands = []
        for slot, req in enumerate(self._slots):
            if req is None or slot in exclude:
                continue
            cands.append({
                "slot": slot, "request_id": req.id,
                "tenant": req.tenant, "priority": req.priority,
                "deadline_slack_s": (None if req.deadline is None
                                     else req.deadline - now)})
        return cands

    def _pick_victim(self, worse_than=None, exclude=()):
        """The preemption victim: worst priority class first, most
        deadline slack within a class (no deadline == infinite slack —
        batch work yields before anything on a clock). `worse_than`
        restricts to classes strictly below the given priority. The
        choice rule IS `decisions.replay_victim` over the candidate
        table, so every preempt decision record reproduces it. Returns
        (victim slot or None, candidates)."""
        cands = self._victim_candidates(exclude)
        best = _dec.replay_victim(cands, worse_than=worse_than)
        return (None if best is None else best["slot"]), cands

    def _preempt(self, slot, reason, worse_than=None, candidates=None):
        """Evict `slot`'s request, freeing its blocks back to the pool
        (engine.reset_slot drops every table reference), and requeue it
        recompute-style: prompt+generated-so-far becomes the restart
        prompt, keeping the delivered stream intact. A victim whose
        restart no longer fits the engine is failed loudly instead of
        silently truncated."""
        req = self._slots[slot]
        try:
            with self._kv_attr(req, "preempt"):
                self.engine.reset_slot(slot)
        except Exception:                                # noqa: BLE001
            pass
        self._slots[slot] = None
        req.slot = None
        req.preempted += 1
        self._count("serving.preempted", req)
        with RecordEvent("serving::preempt", TracerEventType.UserDefined,
                         {"slot": slot, "request": req.id,
                          "priority": req.priority,
                          "tenant": req.tenant,
                          "tokens": len(req.tokens),
                          "reason": reason}):
            pass
        remaining = req.max_new_tokens - len(req.tokens)
        resume = req.prompt + req.tokens
        fits = (len(resume) <= self.engine.max_prompt_len
                and len(resume) + remaining <= self.engine.config.max_len)
        disposition = "done" if remaining < 1 \
            else ("requeued" if fits else "error")
        # the audit record (ISSUE 15): the candidate table this victim
        # beat + the rule scope, so the choice replays from the record
        self._decide(
            "preempt", req,
            {"reason": reason, "worse_than": worse_than,
             "candidates": candidates
             if candidates is not None else [{
                 "slot": slot, "request_id": req.id,
                 "tenant": req.tenant, "priority": req.priority,
                 "deadline_slack_s": None}],
             "queue_depth": len(self._queue),
             # same armed-only cost rule as _shed_inputs: the replay
             # never reads this field, so the O(cache-entries) scan is
             # paid only when the pool-free shed rule is configured
             "pool_free_fraction": self._pool_free_fraction()
             if self.config.shed_pool_free is not None else None},
            {"victim_slot": slot, "victim_request_id": req.id,
             "victim_tenant": req.tenant, "disposition": disposition,
             "tokens_delivered": len(req.tokens)})
        if remaining < 1:                  # raced its own completion
            self._finish(req, DONE, "serving.completed")
            return
        if not fits:
            req.error = (f"preempted ({reason}) and the restart prompt "
                         f"({len(resume)} tokens) exceeds the engine "
                         f"limits")
            self._finish(req, ERROR, "serving.error")
            return
        req._exec_prompt = resume
        req._staged = None                 # evicted KV is gone: recompute
        req.status = QUEUED
        req.trail.begin(_rt.PH_QUEUE, self._clock())
        self._queue.append(req)            # keeps its original arrival
                                           # order within its class

    def _grow_paged_slots(self, now):
        """Paged engines allocate decode blocks lazily: before the step,
        every occupied slot must own the block its next token lands in.
        Allocation pressure is resolved by preemption over the occupants
        of the growing request's class AND WORSE — including the growing
        slot itself, so when everything better is running, the request
        with the worst (priority, deadline slack) yields. A
        strictly-better-class occupant is never evicted to feed a worse
        one; decode() below never sees BlockAllocError."""
        ensure = getattr(self.engine, "ensure_slot_capacity", None)
        if ensure is None:
            return
        for slot in range(len(self._slots)):
            req = self._slots[slot]
            if req is None:
                continue
            for _ in range(len(self._slots) + 1):
                if self._slots[slot] is None:
                    break                   # preempted itself below
                try:
                    with self._kv_attr(req, "decode_grow"):
                        ensure(slot)
                    break
                except BlockAllocError:
                    # worse_than=priority-1 keeps classes >= the growing
                    # request's own; the growing slot is a candidate too
                    victim, cands = self._pick_victim(
                        worse_than=req.priority - 1)
                    if victim is None:      # unreachable: slot qualifies
                        victim, cands = slot, None
                    self._preempt(victim, "allocation pressure",
                                  worse_than=req.priority - 1,
                                  candidates=cands)
                    if victim == slot:
                        break

    # -- phases ---------------------------------------------------------------
    def _expire_queued(self, now):
        kept = collections.deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                self._finish(req, TIMEOUT, "serving.timeout")
            else:
                kept.append(req)
        self._queue = kept

    def _retire(self, now):
        eos = self.engine.config.eos_token_id
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            finished = req.finished(eos)
            timed_out = req.deadline is not None and now > req.deadline
            if finished or timed_out:
                with RecordEvent("serving::retire.slot",
                                 TracerEventType.UserDefined,
                                 {"slot": slot, "request_id": req.id,
                                  "tenant": req.tenant,
                                  "tokens": len(req.tokens),
                                  "timeout": timed_out}):
                    with self._kv_attr(req, "retire"):
                        self.engine.reset_slot(slot)
                self._slots[slot] = None
                self._finish(req, TIMEOUT if timed_out else DONE,
                             "serving.timeout" if timed_out
                             else "serving.completed")

    def _pop_next(self, now):
        """Best queued request by (priority class, arrival order),
        finishing expired ones along the way."""
        while self._queue:
            best = min(self._queue, key=lambda r: (r.priority, r.id))
            self._queue.remove(best)
            if best.deadline is not None and now > best.deadline:
                self._finish(best, TIMEOUT, "serving.timeout")
                continue
            return best
        return None

    def _refill(self, now):
        for slot in range(len(self._slots)):
            if self._slots[slot] is not None or slot in self._quarantined:
                continue
            # a request that completes AT prefill (max_new_tokens=1, or an
            # instant eos) retires here, before decode could overrun it —
            # and frees the slot for the next queued request immediately
            while self._slots[slot] is None \
                    and slot not in self._quarantined:
                req = self._pop_next(now)
                if req is None:
                    return
                outcome = self._try_place(slot, req)
                if outcome == "stop":
                    return
                if outcome == "failed":
                    break

    def _place_once(self, slot, req):
        """One placement attempt: adopt the staged KV bundle when the
        request carries one (multi-host handoff), else local prefill.
        A bundle that fails adoption for any NON-pressure reason is
        dropped and the attempt falls back to local prefill in place —
        a rotted bundle degrades to recompute, never to a failed
        request. BlockAllocError always escapes (the caller preempts)."""
        self._bind_slot_tenancy(slot, req)
        staged = req._staged
        if staged is None:
            self._restore_staged_prefix(req)
            self._leave_queue(req, _rt.PH_PREFILL)
            return self._engine_prefill(slot, req)
        self._leave_queue(req, _rt.PH_ADOPT)
        try:
            # a v3 bundle's 5th element is the prefill host's post-first-
            # token (seed, gen). An rng-less (v1/v2) bundle still arms
            # the REQUEST's stream at gen+1 — the adopted first token's
            # provenance is the foreign prefill (so only greedy restarts
            # replay it exactly, the documented legacy contract), but
            # every subsequent sample rides this request's seed instead
            # of a throwaway engine default
            rng = staged[4] if len(staged) > 4 else \
                (req.rng_seed, req.rng_gen + 1)
            with self._kv_attr(req, "adopt"):
                first = self.engine.adopt_kv(slot, *staged[:4], rng=rng)
        except BlockAllocError:
            raise
        except Exception as e:                           # noqa: BLE001
            req._staged = None
            with RecordEvent("serving::adopt_fallback",
                             TracerEventType.UserDefined,
                             {"request": req.id,
                              "error": f"{type(e).__name__}: "
                                       f"{str(e)[:160]}"}):
                pass
            # the failed adoption stays visible as its own segment; the
            # recompute prefill opens a fresh one at the fallback moment
            req.trail.begin(_rt.PH_PREFILL, self._clock())
            return self._engine_prefill(slot, req)
        req._staged = None
        req.adopted = True
        _M_ADOPTED.inc()
        return first

    def _leave_queue(self, req, phase):
        """Open `phase` on the request's trail. The first time a request
        leaves the queue its wait becomes a `serving::queue` span, from
        the two stamps the trail already holds. Only a scheduler on the
        system's clock emits it (time.monotonic and the span clock are
        one clock on Linux): an injected clock's stamps are on another
        timeline than the log's."""
        req.trail.begin(phase, self._clock())
        if not self._clock_is_span_clock:
            return
        segments = req.trail.segments
        # the segment that just closed is the request's FIRST queue
        # wait: a router may have filled the trail before it (prefill,
        # kv_handoff, place), a preemption queues the request again
        if segments and segments[-1][0] == _rt.PH_QUEUE and sum(
                1 for s in segments if s[0] == _rt.PH_QUEUE) == 1:
            _, t0, t1 = segments[-1]
            record_span("serving::queue", t0 * 1e9, (t1 - t0) * 1e9,
                        {"request_id": req.id})

    def _restore_staged_prefix(self, req):
        """Register a fleet-shipped prefix chain (ISSUE 18) into the
        local prefix cache as its own named `kv_restore` timeline phase,
        one-shot: the bundle is consumed whatever happens, and a restore
        that fails for any reason simply restores 0 tokens — the prefill
        that follows recomputes, bit-identically. The restored chain is
        cache-owned (not slot-owned), so a BlockAllocError-preempted
        retry still matches it locally."""
        sp = req._staged_prefix
        if sp is None:
            return
        req._staged_prefix = None
        self._leave_queue(req, _rt.PH_KV_RESTORE)
        t0 = time.perf_counter()
        try:
            with self._kv_attr(req, "kv_restore"):
                req.kv_restored_tokens = int(self.engine.restore_prefix(
                    req.exec_prompt, sp[0], sp[1], sp[2],
                    namespace=sp[3]))
            if req.kv_restored_tokens > 0:
                req.tier_hit = True
                req.restore_s += time.perf_counter() - t0
        except Exception as e:                           # noqa: BLE001
            with RecordEvent("serving::kv_restore_fallback",
                             TracerEventType.UserDefined,
                             {"request": req.id,
                              "error": f"{type(e).__name__}: "
                                       f"{str(e)[:160]}"}):
                pass
            req.kv_restored_tokens = 0

    def _bind_slot_tenancy(self, slot, req):
        """Bind the slot to the request's adapter before placement
        (ISSUE 17): the tenant's bank row if one is loaded, else slot 0
        (base weights — also what non-tenant traffic always gets). A
        host int32 write per placement; engines without a bank skip it
        entirely."""
        bank = getattr(self.engine, "adapter_bank", None)
        if bank is None:
            return
        aid = req.adapter_id if req.adapter_id is not None else req.tenant
        idx = bank.slot_of(aid)
        self.engine.set_slot_adapter(slot, idx)
        if idx and req.adapter_id is None:
            req.adapter_id = aid

    def _engine_prefill(self, slot, req):
        """Prefill with the request's sampler state at THIS placement:
        its next token is generation index base + tokens-already-
        delivered (preempt restarts fold the delivered run into
        exec_prompt). Engines without per-slot RNG (minimal stubs) get
        the plain call — the capability probe mirrors the adopt_kv
        one. The request's prefix namespace rides into the engine's
        prefix-cache keying (only when set — stub engines never see the
        kwarg)."""
        kwargs = {}
        if req.prefix_namespace is not None:
            kwargs["namespace"] = req.prefix_namespace
        with self._kv_attr(req, "prefill"), span_attrs(request_id=req.id):
            if not hasattr(self.engine, "set_slot_rng"):
                return self.engine.prefill(slot, req.exec_prompt,
                                           **kwargs)
            return self.engine.prefill(
                slot, req.exec_prompt,
                rng=(req.rng_seed, req.rng_gen + len(req.tokens)),
                **kwargs)

    def _try_place(self, slot, req):
        """Prefill `req` into `slot`. Allocation pressure preempts a
        strictly-lower-priority victim and retries; with no victim the
        request is requeued untouched and refill stops for this step
        ("stop"). Other prefill exceptions engage the quarantine protocol
        ("failed"). Returns "placed" on success."""
        for _ in range(len(self._slots) + 1):
            try:
                first = self._place_once(slot, req)
            except BlockAllocError:
                victim, cands = self._pick_victim(
                    worse_than=req.priority, exclude=(slot,))
                if victim is None:
                    req.trail.begin(_rt.PH_QUEUE, self._clock())
                    self._queue.append(req)     # retry next step
                    return "stop"
                self._preempt(victim, "admission pressure",
                              worse_than=req.priority, candidates=cands)
                continue
            except Exception as e:               # noqa: BLE001
                self._on_prefill_failure(slot, req, e)
                return "failed"
            break
        else:
            req.trail.begin(_rt.PH_QUEUE, self._clock())
            self._queue.append(req)
            return "stop"
        req.slot = slot
        req.status = RUNNING
        self._placed += 1
        self._placed_tokens += len(req.exec_prompt)
        if req.first_token_at is None:
            req.first_token_at = self._clock()
        req.trail.begin(_rt.PH_DECODE, self._clock())
        stats = getattr(self.engine, "last_prefill_stats", None) or {}
        if stats.get("prefix_hit_tokens", 0) > 0:
            req.prefix_hit = True
        if stats.get("tier_promoted_blocks", 0) > 0:
            req.tier_hit = True
            req.restore_s += stats.get("tier_restore_s", 0.0)
        self._decide("place", req,
                     {"slot": slot, "queue_depth": len(self._queue),
                      "priority": req.priority,
                      "preempted": req.preempted,
                      "staged": req.adopted},
                     {"placed": True, "slot": slot,
                      "adopted": req.adopted,
                      "prefix_hit": req.prefix_hit})
        req.tokens.append(first)
        self._decode_tokens += 1
        self._count("serving.tokens", req)
        if req.finished(self.engine.config.eos_token_id):
            with self._kv_attr(req, "retire"):
                self.engine.reset_slot(slot)
            self._finish(req, DONE, "serving.completed")
        else:
            self._slots[slot] = req
        return "placed"

    def _finish(self, req, status, counter):
        req.status = status
        req.finished_at = self._clock()
        req.trail.close(req.finished_at)
        self._count(counter, req)
        if req.first_token_at is not None:
            _M_TTFT.labels(tenant=req.tenant).observe(
                req.first_token_at - req.submitted_at)
            _M_REQ_DECODE.labels(tenant=req.tenant).observe(
                req.finished_at - req.first_token_at)
        if status in (DONE, TIMEOUT, ERROR, SHED):
            self._completed.append(req)
            self._write_request_record(req)
            self._write_timeline_record(req)
        req._done.set()

    def _count(self, name, req=None):
        # registry first (the unified surface), then the deprecated
        # per-instance dict for existing readers (`step()` among them).
        # Every per-request family carries the request's tenant label
        # (ISSUE 15); counts with no request context label "default".
        tenant = getattr(req, "tenant", None) or _dec.DEFAULT_TENANT
        if name == "serving.tokens":
            _M_TOKENS.labels(tenant=tenant).inc()
        elif name == "serving.preempted":
            _M_PREEMPTED.labels(tenant=tenant).inc()
        else:
            _M_REQUESTS.labels(status=name.split(".", 1)[1],
                               tenant=tenant).inc()
        self.counts[name] += 1

    # -- metrics ---------------------------------------------------------------
    def metrics(self):
        occupied = self.active_slots()
        ttfts = [r.first_token_at - r.submitted_at for r in self._completed
                 if r.first_token_at is not None]
        out = {
            "steps": self._steps,
            "queue_depth": len(self._queue),
            "slot_occupancy": occupied / max(self.engine.slots, 1),
            "tokens_generated": self._decode_tokens,
            "decode_tokens_per_s": (
                self._decode_tokens / self._decode_time_s
                if self._decode_time_s > 0 else 0.0),
            "ttft_s_mean": sum(ttfts) / len(ttfts) if ttfts else None,
            "requests": dict(self.counts),
        }
        if self._spec_proposed:
            out["spec_proposed"] = self._spec_proposed
            out["spec_accepted"] = self._spec_accepted
            out["spec_acceptance_rate"] = (
                self._spec_accepted / self._spec_proposed)
        pool = getattr(self.engine, "block_pool", None)
        if pool is not None:
            out["blocks_in_use"] = pool.in_use
            out["blocks_total"] = pool.capacity
            pc = getattr(self.engine, "prefix_cache", None)
            out["prefix_cache_blocks"] = len(pc) if pc is not None else 0
        return out

    def _write_step_record(self, now, active):
        if not self._metrics_f:
            return
        rec = {"kind": "step", "step": self._steps, "t": now,
               "queue_depth": len(self._queue), "active_slots": active,
               "tokens_generated": self._decode_tokens}
        pp_stats = getattr(self.engine, "pp_stats", None)
        if pp_stats is not None:
            s = pp_stats()
            rec["pp_bubble_fraction"] = round(s["bubble_fraction"], 6)
            rec["pp_stage_busy"] = [round(b, 6) for b in s["stage_busy"]]
        self._metrics_f.write(json.dumps(rec) + "\n")
        self._metrics_f.flush()

    def _write_kvledger_records(self):
        """Stream the ledger events emitted since the last step into the
        serving JSONL as `kvledger` records — the on-disk half of the
        attribution plane: serve_report's residency table and the
        offline replay audit both reconstruct the pool from these."""
        if not self._metrics_f or self._kv_reconciler is None:
            return
        events = self._kv_reconciler.ledger.events
        if self._kv_events_written >= len(events):
            return
        for ev in events[self._kv_events_written:]:
            self._metrics_f.write(
                json.dumps({"kind": "kvledger", **ev}) + "\n")
        self._kv_events_written = len(events)
        self._metrics_f.flush()

    def _build_timeline(self, req):
        """One reqtimeline.v1 record for a terminal request — phase
        durations sum exactly to e2e_s by PhaseTrail's construction."""
        return _rt.build_record(
            req.status, req.submitted_at, req.finished_at,
            req.trail.rel(req.submitted_at), request_id=req.id,
            tokens=len(req.tokens),
            ttft_s=(req.first_token_at - req.submitted_at
                    if req.first_token_at is not None else None),
            priority=req.priority, preempted=req.preempted,
            adopted=req.adopted, trace_id=req.trace_id,
            tenant=req.tenant, cohort=req.cohort)

    def timeline_records(self):
        """reqtimeline.v1 records for every completed request so far —
        what tools/load_harness.py derives its per-phase TTFT breakdown
        gauges from without re-reading the JSONL."""
        return [self._build_timeline(r) for r in self._completed]

    def _write_timeline_record(self, req):
        if not self._metrics_f:
            return
        self._metrics_f.write(json.dumps(self._build_timeline(req)) + "\n")
        self._metrics_f.flush()

    def _write_request_record(self, req):
        if not self._metrics_f:
            return
        decode_s = (req.finished_at - req.first_token_at
                    if req.first_token_at else None)
        self._metrics_f.write(json.dumps({
            "kind": "request", "request_id": req.id, "status": req.status,
            "tenant": req.tenant,
            **({"cohort": req.cohort} if req.cohort else {}),
            **({"adapter_id": req.adapter_id} if req.adapter_id else {}),
            **({"prefix_namespace": str(req.prefix_namespace)}
               if req.prefix_namespace is not None else {}),
            **({"rate_limited": True} if req.rate_limited else {}),
            **({"tier_hit": True,
                "restore_ms": round(req.restore_s * 1e3, 3)}
               if req.tier_hit else {}),
            "prompt_len": len(req.prompt), "tokens": len(req.tokens),
            "priority": req.priority, "preempted": req.preempted,
            "prefix_hit": req.prefix_hit, "adopted": req.adopted,
            "spec_proposed": req.spec_proposed,
            "spec_accepted": req.spec_accepted,
            "ttft_s": (req.first_token_at - req.submitted_at
                       if req.first_token_at else None),
            "decode_s": decode_s}) + "\n")
        self._metrics_f.flush()
