#!/usr/bin/env python
"""Deterministic traffic-replay load harness for the serving engine.

Synthesizes a reproducible consumer-traffic trace — N synthetic users,
Poisson arrivals, a shared-prefix mixture (every user opens with one of
a small pool of "system prompts", the workload the prefix cache exists
for), and a priority-class mix — and replays it through a Scheduler over
either KV layout:

  dense  GenerationEngine        (one max_len reservation per slot)
  paged  PagedGenerationEngine   (block pool + prefix cache + preemption)
  spec   SpeculativeEngine       (paged + speculative multi-token decode:
                                  draft proposals, one verify forward per
                                  round, greedy-bit-identical output)

The replay reports p50/p99 TTFT, decode tokens/sec, peak concurrency,
shed/preempt/reject tallies, the prefix-cache hit rate, and (ISSUE 12)
a per-phase TTFT breakdown derived from the scheduler's reqtimeline
records (queue wait vs prefill vs handoff/adopt vs first decode step);
the same figures are exported through the unified metrics registry
(`serving_load_*` gauges — including
`serving_load_ttft_phase_seconds{phase=...}` — ride next to the
scheduler's own counters and histograms) and an optional registry
snapshot (paddle_tpu.metrics.v1 JSONL) is written for
`tools/metrics_report.py`.

Determinism: the TRACE is fully seeded (numpy RandomState). With
`virtual_step_s` set, time itself is virtual — the scheduler runs on a
monotonic counter the harness advances by a fixed amount per step, so
arrivals, shedding, preemption and peak concurrency are bit-reproducible
across hosts (the tier-1 paged-vs-dense win assertion runs this mode).
Without it, the wall clock drives arrivals.

Usage:
  python tools/load_harness.py --engine paged --users 8 --requests 32
  python tools/load_harness.py --engine both --metrics-out run/metrics.jsonl
"""
import argparse
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:          # script-mode: make paddle_tpu importable
    sys.path.insert(0, _ROOT)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import serve_report  # noqa: E402  (sibling tool: shared percentile calc)

__all__ = ["TrafficConfig", "VirtualClock", "synth_trace", "replay",
           "build_engine", "build_tenancy", "run_harness", "percentile"]


class TrafficConfig:
    """Knobs of the synthetic trace. `prefix_pool` shared system prompts
    of `prefix_len` tokens are dealt round-robin to `users`; each request
    appends a random suffix of suffix_min..suffix_max tokens.

    Multi-tenant mix (ISSUE 15): `tenants` maps tenant name ->
    arrival rate (rps); each tenant gets its own independent seeded
    Poisson stream and a share of `requests` proportional to its rate.
    `burst` = {"tenant", "t0", "dur_s", "mult"} multiplies ONE tenant's
    arrival rate inside a window — the isolation-gate scenario (tenant
    A bursts, tenant B's p99 must hold). With tenants=None the trace is
    the historical single-stream shape, byte-identical to before the
    labelset landed."""

    def __init__(self, users=8, requests=32, rate_rps=200.0, prefix_pool=2,
                 prefix_len=16, suffix_min=2, suffix_max=8,
                 max_new_tokens=4, priority_weights=(1, 2, 1),
                 timeout_s=None, seed=0, tenants=None, burst=None):
        self.users = int(users)
        self.requests = int(requests)
        self.rate_rps = float(rate_rps)
        self.prefix_pool = int(prefix_pool)
        self.prefix_len = int(prefix_len)
        self.suffix_min = int(suffix_min)
        self.suffix_max = int(suffix_max)
        self.max_new_tokens = int(max_new_tokens)
        self.priority_weights = tuple(priority_weights)
        self.timeout_s = timeout_s
        self.seed = int(seed)
        self.tenants = dict(tenants) if tenants else None
        self.burst = dict(burst) if burst else None


class VirtualClock:
    """Deterministic time: starts at 0, advances only when told."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def synth_trace(cfg, vocab):
    """The deterministic request trace: a list of dicts with arrival
    time `t` (seconds from start, Poisson via seeded exponential
    inter-arrivals), `prompt`, `priority`, `max_new`, `user` — plus
    `tenant` when cfg.tenants is set (one independent seeded stream per
    tenant, merged by arrival time; the burst window multiplies its
    tenant's rate in place)."""
    if cfg.tenants:
        return _synth_multi_tenant(cfg, vocab)
    rng = np.random.RandomState(cfg.seed)
    prefixes = [rng.randint(0, vocab, cfg.prefix_len).tolist()
                for _ in range(max(cfg.prefix_pool, 1))]
    w = np.asarray(cfg.priority_weights, np.float64)
    w = w / w.sum()
    items = []
    t = 0.0
    for i in range(cfg.requests):
        t += float(rng.exponential(1.0 / cfg.rate_rps))
        user = i % cfg.users
        prompt = list(prefixes[user % len(prefixes)])
        n_suffix = int(rng.randint(cfg.suffix_min, cfg.suffix_max + 1))
        prompt += rng.randint(0, vocab, n_suffix).tolist()
        items.append({
            "t": t, "user": user, "prompt": prompt,
            "priority": int(rng.choice(len(w), p=w)),
            "max_new": cfg.max_new_tokens,
        })
    return items


def _synth_multi_tenant(cfg, vocab):
    """One seeded Poisson stream per tenant (requests split pro-rata by
    rate), merged by arrival time. The burst knob multiplies the named
    tenant's instantaneous rate inside [t0, t0+dur_s) — the two-tenant
    isolation scenario of ROADMAP item 5."""
    w = np.asarray(cfg.priority_weights, np.float64)
    w = w / w.sum()
    burst = cfg.burst or {}
    total_rate = sum(cfg.tenants.values()) or 1.0
    items = []
    for idx, (tenant, rate) in enumerate(sorted(cfg.tenants.items())):
        rng = np.random.RandomState(cfg.seed + 7919 * (idx + 1))
        prefixes = [rng.randint(0, vocab, cfg.prefix_len).tolist()
                    for _ in range(max(cfg.prefix_pool, 1))]
        n = max(1, int(round(cfg.requests * rate / total_rate)))
        t = 0.0
        for i in range(n):
            r = float(rate)
            if burst.get("tenant") == tenant and \
                    burst["t0"] <= t < burst["t0"] + burst["dur_s"]:
                r *= float(burst["mult"])
            t += float(rng.exponential(1.0 / r))
            user = i % cfg.users
            prompt = list(prefixes[user % len(prefixes)])
            n_suffix = int(rng.randint(cfg.suffix_min,
                                       cfg.suffix_max + 1))
            prompt += rng.randint(0, vocab, n_suffix).tolist()
            items.append({
                "t": t, "user": user, "tenant": tenant,
                "prompt": prompt,
                "priority": int(rng.choice(len(w), p=w)),
                "max_new": cfg.max_new_tokens,
            })
    items.sort(key=lambda it: it["t"])
    return items


# one percentile convention across the serving tools: serve_report owns it
percentile = serve_report._pct


def replay(sched, trace, timeout_s=None, virtual_clock=None,
           virtual_step_s=0.01, max_steps=200000):
    """Drive `sched` through `trace`. Submissions happen when the
    scheduler's clock passes each item's arrival time; sheds/rejections
    are tallied, everything else runs to a terminal status. Returns the
    summary dict."""
    from paddle_tpu.serving import (PRIORITIES, LoadShedError,
                                    QueueFullError, RateLimitedError)

    cohort_of = {v: k for k, v in PRIORITIES.items()}
    wall0 = time.monotonic()
    now = (lambda: virtual_clock()) if virtual_clock is not None \
        else (lambda: time.monotonic() - wall0)
    handles = []
    shed = rejected = rate_limited = 0
    shed_by_tenant = {}
    rl_by_tenant = {}
    next_i = 0
    max_concurrent = 0
    steps = 0
    # per-tenant resident KV blocks (ISSUE 16), sampled at every step
    # boundary from the engine's kvledger shadow — the quota baseline:
    # peak says what a tenant cap must admit, mean what it typically
    # holds
    kv_ledger = getattr(sched.engine, "kv_ledger", None)
    kv_peak, kv_sum = {}, {}
    while True:
        while next_i < len(trace) and trace[next_i]["t"] <= now():
            it = trace[next_i]
            next_i += 1
            try:
                handles.append(sched.submit(
                    it["prompt"], max_new_tokens=it["max_new"],
                    timeout_s=timeout_s, priority=it["priority"],
                    tenant=it.get("tenant"),
                    cohort=cohort_of.get(it["priority"])))
            except LoadShedError:
                shed += 1
                t = it.get("tenant", "default")
                shed_by_tenant[t] = shed_by_tenant.get(t, 0) + 1
            except RateLimitedError:
                # ISSUE 17: the token bucket said no BEFORE the shed
                # watermark even looked — tallied apart from sheds so
                # the per-tenant readout separates "engine was full"
                # from "tenant exceeded its own budget"
                rate_limited += 1
                t = it.get("tenant", "default")
                rl_by_tenant[t] = rl_by_tenant.get(t, 0) + 1
            except QueueFullError:
                rejected += 1
        more = sched.step()
        steps += 1
        max_concurrent = max(max_concurrent, sched.active_slots())
        if kv_ledger is not None:
            for t, n in kv_ledger.shadow.tenant_resident_totals().items():
                if n > kv_peak.get(t, 0):
                    kv_peak[t] = n
                kv_sum[t] = kv_sum.get(t, 0) + n
        if virtual_clock is not None:
            virtual_clock.advance(virtual_step_s)
        if next_i >= len(trace) and not more:
            break
        if steps >= max_steps:
            raise RuntimeError(f"replay did not converge in {max_steps} "
                               f"steps")
    wall_s = time.monotonic() - wall0

    by_status = {}
    for h in handles:
        by_status[h.status] = by_status.get(h.status, 0) + 1
    ttfts = [h.ttft_s for h in handles if h.ttft_s is not None]
    m = sched.metrics()
    summary = {
        "requests": len(trace),
        "submitted": len(handles),
        "by_status": by_status,
        "shed": shed,
        "rejected": rejected,
        "rate_limited": rate_limited,
        "preempted": m["requests"].get("serving.preempted", 0),
        "prefix_hits": sum(1 for h in handles if h.prefix_hit),
        "max_concurrent": max_concurrent,
        "steps": steps,
        "wall_s": round(wall_s, 4),
        "tokens": m["tokens_generated"],
        "tokens_per_s": round(m["tokens_generated"] / wall_s, 2)
        if wall_s > 0 else None,
        "ttft_p50_s": percentile(ttfts, 0.50),
        "ttft_p99_s": percentile(ttfts, 0.99),
        "ttft_phase_s": _ttft_phase_breakdown(sched),
    }
    if kv_ledger is not None and kv_peak:
        summary["kv_blocks_peak"] = max(kv_peak.values())
    if any("tenant" in it for it in trace):
        summary["tenants"] = _tenant_summary(
            trace, handles, shed_by_tenant, sched,
            kv_peak=kv_peak if kv_ledger is not None else None,
            kv_mean={t: s / steps for t, s in kv_sum.items()}
            if kv_ledger is not None and steps else None,
            rl_by_tenant=rl_by_tenant)
    _export_registry(summary)
    return summary


def _tenant_summary(trace, handles, shed_by_tenant, sched,
                    kv_peak=None, kv_mean=None, rl_by_tenant=None):
    """Per-tenant replay figures (ISSUE 15): request/shed tallies,
    per-tenant p50/p99 TTFT, and per-tenant TTFT phase attribution
    (each tenant's own timeline records clipped to their TTFT windows)
    — the isolation-gate readout: did tenant A's burst move tenant B's
    tail? With a kvledger attached (ISSUE 16) each tenant also reports
    its peak/mean resident KV blocks over the replay — the residency
    figure next to p99 TTFT that ROADMAP item-2 quota caps calibrate
    against."""
    tenants = sorted({it.get("tenant", "default") for it in trace})
    by_tenant_handles = {}
    for h in handles:
        by_tenant_handles.setdefault(h.tenant, []).append(h)
    tl_by_tenant = {}
    for rec in sched.timeline_records():
        tl_by_tenant.setdefault(rec.get("tenant", "default"),
                                []).append(rec)
    # namespace residency/eviction (ISSUE 17): when the engine runs a
    # namespaced prefix cache, each tenant's quota view rides next to
    # its latency figures — tenant name IS the namespace under
    # TenancyConfig's default wiring
    pc = getattr(sched.engine, "prefix_cache", None)
    ns_resident = pc.namespace_residents() if pc is not None \
        and hasattr(pc, "namespace_residents") else {}
    ns_evicted = pc.namespace_evictions() if pc is not None \
        and hasattr(pc, "namespace_evictions") else {}
    out = {}
    for t in tenants:
        hs = by_tenant_handles.get(t, [])
        ttfts = [h.ttft_s for h in hs if h.ttft_s is not None]
        by_status = {}
        for h in hs:
            by_status[h.status] = by_status.get(h.status, 0) + 1
        out[t] = {
            "requests": sum(1 for it in trace
                            if it.get("tenant", "default") == t),
            "submitted": len(hs),
            "shed": shed_by_tenant.get(t, 0),
            "rate_limited": (rl_by_tenant or {}).get(t, 0),
            "by_status": by_status,
            "preempted": sum(h.preempted for h in hs),
            "ttft_p50_s": percentile(ttfts, 0.50),
            "ttft_p99_s": percentile(ttfts, 0.99),
            "ttft_phase_s": _phase_means(tl_by_tenant.get(t, [])),
        }
        if kv_peak is not None:
            out[t]["kv_blocks_peak"] = kv_peak.get(t, 0)
            out[t]["kv_blocks_mean"] = round(
                (kv_mean or {}).get(t, 0.0), 4)
        if ns_resident or ns_evicted:
            out[t]["ns_blocks_resident"] = int(ns_resident.get(t, 0))
            out[t]["ns_blocks_evicted"] = int(ns_evicted.get(t, 0))
    return out


def _phase_means(timeline_records):
    """Mean seconds each named phase contributed to TTFT over an
    iterable of reqtimeline.v1 records (ISSUE 12): each request's
    segments are clipped to its [0, ttft) window
    (reqtimeline.ttft_breakdown), then averaged over the requests that
    produced a first token. ONE implementation for the aggregate and
    the per-tenant (ISSUE 15) views, so the attribution math cannot
    drift between them."""
    from paddle_tpu.observability import reqtimeline as _rt
    totals, n = {}, 0
    for rec in timeline_records:
        parts = _rt.ttft_breakdown(rec)
        if parts is None:
            continue
        n += 1
        for phase, s in parts.items():
            totals[phase] = totals.get(phase, 0.0) + s
    return {p: round(t / n, 6) for p, t in sorted(totals.items())} \
        if n else {}


def _ttft_phase_breakdown(sched):
    """The replay-wide attribution (queue wait vs prefill vs
    handoff/adopt vs first decode step) — the summary carries WHY, not
    just the TTFT total."""
    return _phase_means(sched.timeline_records())


def _export_registry(summary):
    """Publish the replay headline figures as serving_load_* gauges in
    the unified registry (next to the scheduler's own histograms)."""
    from paddle_tpu.observability import metrics as _metrics
    g = {
        "serving_load_ttft_p50_seconds":
            ("Replay p50 time-to-first-token", summary["ttft_p50_s"]),
        "serving_load_ttft_p99_seconds":
            ("Replay p99 time-to-first-token", summary["ttft_p99_s"]),
        "serving_load_tokens_per_s":
            ("Replay decode throughput", summary["tokens_per_s"]),
        "serving_load_max_concurrent":
            ("Replay peak concurrent in-flight requests",
             summary["max_concurrent"]),
    }
    for name, (help_, value) in g.items():
        if value is not None:
            _metrics.gauge(name, help_).set(float(value))
    phase_g = _metrics.gauge(
        "serving_load_ttft_phase_seconds",
        "Mean seconds each timeline phase contributed to TTFT over the "
        "replay (per-request reqtimeline segments clipped to the TTFT "
        "window; 'first_decode' = placement -> first token)",
        labelnames=("phase",))
    for phase, value in (summary.get("ttft_phase_s") or {}).items():
        phase_g.labels(phase=phase).set(float(value))
    # per-tenant replay gauges (ISSUE 15): the tenant-labeled TTFT
    # percentiles + phase attribution the isolation gate compares
    tg50 = _metrics.gauge(
        "serving_load_tenant_ttft_p50_seconds",
        "Replay p50 TTFT per tenant", labelnames=("tenant",))
    tg99 = _metrics.gauge(
        "serving_load_tenant_ttft_p99_seconds",
        "Replay p99 TTFT per tenant — the figure the item-5 isolation "
        "gate compares across a neighbor's burst",
        labelnames=("tenant",))
    tgphase = _metrics.gauge(
        "serving_load_tenant_ttft_phase_seconds",
        "Mean seconds each timeline phase contributed to TTFT, per "
        "tenant", labelnames=("tenant", "phase"))
    # per-tenant resident KV blocks (ISSUE 16): the kvledger residency
    # sampled at replay step boundaries — peak next to p99 TTFT
    tgkvp = _metrics.gauge(
        "serving_load_tenant_kv_blocks_peak",
        "Peak resident KV blocks a tenant held at any replay step "
        "boundary (kvledger shadow sample)", labelnames=("tenant",))
    tgkvm = _metrics.gauge(
        "serving_load_tenant_kv_blocks_mean",
        "Mean resident KV blocks per tenant over all replay steps",
        labelnames=("tenant",))
    # multi-tenant isolation figures (ISSUE 17): rate-limit denials and
    # namespace-quota evictions per tenant — what the isolation gate and
    # metrics_report's failure-class scan read after a replay
    tgrl = _metrics.gauge(
        "serving_load_tenant_rate_limited",
        "Submissions the tenant's token bucket denied over the replay",
        labelnames=("tenant",))
    tgnse = _metrics.gauge(
        "serving_load_tenant_ns_evicted_blocks",
        "Prefix-cache blocks evicted FROM the tenant's namespace over "
        "the replay (quota-pressure reclaims included)",
        labelnames=("tenant",))
    for tenant, ts in (summary.get("tenants") or {}).items():
        if ts.get("ttft_p50_s") is not None:
            tg50.labels(tenant=tenant).set(float(ts["ttft_p50_s"]))
        if ts.get("ttft_p99_s") is not None:
            tg99.labels(tenant=tenant).set(float(ts["ttft_p99_s"]))
        for phase, value in (ts.get("ttft_phase_s") or {}).items():
            tgphase.labels(tenant=tenant, phase=phase).set(float(value))
        if ts.get("kv_blocks_peak") is not None:
            tgkvp.labels(tenant=tenant).set(float(ts["kv_blocks_peak"]))
            tgkvm.labels(tenant=tenant).set(
                float(ts.get("kv_blocks_mean") or 0.0))
        tgrl.labels(tenant=tenant).set(float(ts.get("rate_limited", 0)))
        if ts.get("ns_blocks_evicted") is not None:
            tgnse.labels(tenant=tenant).set(
                float(ts["ns_blocks_evicted"]))


def build_tenancy(tenants, adapters_arg=None, quotas_arg=None,
                  rates_arg=None):
    """A serving.tenancy.TenancyConfig from the CLI knob strings
    ('a:4,b:8' / 'a:8' / 'a:400/800'). Returns None when no knob names
    any tenant — the pre-tenancy scheduler shape. Namespace defaults to
    the tenant's own name for every tenant the config knows, so prompt
    blocks never cross tenants once tenancy is on."""
    from paddle_tpu.serving.tenancy import TenancyConfig, TenantSpec

    def _pairs(arg):
        if not arg:
            return {}
        return dict(part.split(":", 1) for part in arg.split(","))

    adapters = _pairs(adapters_arg)
    quotas = _pairs(quotas_arg)
    rates = _pairs(rates_arg)
    names = sorted(set(tenants or ()) | set(adapters) | set(quotas)
                   | set(rates))
    if not (adapters or quotas or rates):
        return None
    specs = {}
    for i, name in enumerate(names):
        rate = burst = None
        if name in rates:
            r = rates[name].split("/")
            rate = float(r[0])
            burst = float(r[1]) if len(r) > 1 else None
        specs[name] = TenantSpec(
            namespace=name,
            kv_block_quota=int(quotas[name]) if name in quotas else None,
            rate_tokens_per_s=rate, burst_tokens=burst,
            adapter_rank=int(adapters[name]) if name in adapters
            else None,
            adapter_seed=i + 1)
    return TenancyConfig(tenants=specs)


def _attach_tenant_adapters(model, engine, tenancy):
    """Load each adapter-carrying tenant's synthetic seeded LoRA into a
    bank on `engine` (ISSUE 17). Bank rank is the max declared tenant
    rank (lower-rank adapters zero-pad); tenants without a rank run base
    weights through slot 0 of the same ONE compiled trace. No-op when no
    tenant declares an adapter — the engine stays bit-identical to an
    adapter-free build."""
    from paddle_tpu.serving.tenancy import AdapterBank, init_adapter_state
    ranked = {t: s for t, s in tenancy.tenants.items()
              if s.adapter_rank is not None and s.adapter_rank > 0}
    if not ranked:
        return None
    rank = max(s.adapter_rank for s in ranked.values())
    bank = AdapterBank(model.cfg, n_adapters=max(tenancy.adapter_slots,
                                                 len(ranked) + 1),
                       rank=rank)
    for tenant, spec in sorted(ranked.items()):
        bank.load(tenant, init_adapter_state(
            model.cfg, spec.adapter_rank, seed=spec.adapter_seed,
            scale=spec.adapter_scale))
    engine.attach_adapters(bank)
    return bank


def build_engine(model, kind, slots, max_len, block_size=8, num_blocks=None,
                 prefix_cache=True, gamma=3, draft_layers=1,
                 attention_impl="gather", kv_dtype="float32",
                 weight_dtype="float32", tp=2, pp=2, prefill_chunk=None,
                 tier_kwargs=None):
    """A serving engine of any KV/decode layout over `model`. `quant`
    is paged with int8 KV pools AND int8 decode weights (ISSUE 11);
    `tp`/`pp` are the hybrid-parallel arms (ISSUE 13) over this
    process's local devices — `pp` takes both mesh knobs; `spec_pp`
    (ISSUE 14) runs speculative γ+1-token verify windows on the
    pipeline ring (gamma/draft_layers compose with pp/tp).
    `tier_kwargs` (ISSUE 18): extra PagedEngineConfig knobs for the
    host/disk KV tier hierarchy (enable_kv_tiers, host_tier_blocks,
    host_tier_dtype, disk_tier_dir, disk_tier_blocks, ...); applies to
    the single-process paged-family arms only."""
    from paddle_tpu.serving import (GenerationEngine, PagedGenerationEngine,
                                    SpeculativeEngine)
    tier_kwargs = dict(tier_kwargs or {})
    if kind == "quant":
        kind, kv_dtype, weight_dtype = "paged", "int8", "int8"
    if kind == "dense":
        return GenerationEngine(model, slots=slots, max_len=max_len)
    if kind == "paged":
        return PagedGenerationEngine(
            model, slots=slots, max_len=max_len, block_size=block_size,
            num_blocks=num_blocks, enable_prefix_cache=prefix_cache,
            attention_impl=attention_impl, kv_dtype=kv_dtype,
            weight_dtype=weight_dtype, **tier_kwargs)
    if kind == "spec":
        return SpeculativeEngine(
            model, slots=slots, max_len=max_len, block_size=block_size,
            num_blocks=num_blocks, enable_prefix_cache=prefix_cache,
            attention_impl=attention_impl, gamma=gamma,
            draft_layers=draft_layers, kv_dtype=kv_dtype,
            weight_dtype=weight_dtype, **tier_kwargs)
    if kind == "tp":
        from paddle_tpu.serving.distributed.tp import (
            TensorParallelEngineConfig, TensorParallelPagedEngine)
        return TensorParallelPagedEngine(model, TensorParallelEngineConfig(
            tp=tp, slots=slots, max_len=max_len, block_size=block_size,
            num_blocks=num_blocks, enable_prefix_cache=prefix_cache,
            attention_impl=attention_impl, kv_dtype=kv_dtype,
            weight_dtype=weight_dtype))
    if kind == "pp":
        from paddle_tpu.serving.distributed.pp import (
            PipelineParallelEngineConfig, PipelineParallelPagedEngine)
        return PipelineParallelPagedEngine(
            model, PipelineParallelEngineConfig(
                pp=pp, tp=tp, prefill_chunk=prefill_chunk, slots=slots,
                max_len=max_len, block_size=block_size,
                num_blocks=num_blocks, enable_prefix_cache=prefix_cache,
                attention_impl=attention_impl, kv_dtype=kv_dtype,
                weight_dtype=weight_dtype))
    if kind == "spec_pp":
        # the ISSUE 14 composition: --gamma/--draft-layers compose with
        # --pp/--tp — speculative verify windows on the pipeline ring
        from paddle_tpu.serving.distributed.pp import (
            PipelineParallelSpecConfig, PipelineParallelSpeculativeEngine)
        return PipelineParallelSpeculativeEngine(
            model, PipelineParallelSpecConfig(
                pp=pp, tp=tp, prefill_chunk=prefill_chunk, slots=slots,
                max_len=max_len, block_size=block_size,
                num_blocks=num_blocks, enable_prefix_cache=prefix_cache,
                attention_impl=attention_impl, gamma=gamma,
                draft_layers=draft_layers, kv_dtype=kv_dtype,
                weight_dtype=weight_dtype))
    raise ValueError(f"unknown engine kind {kind!r} "
                     f"(want dense|paged|spec|quant|tp|pp|spec_pp)")


def run_harness(model, kind, traffic, slots, max_len, block_size=8,
                num_blocks=None, prefix_cache=True, max_queue=256,
                shed_watermark=None, shed_pool_free=None,
                virtual_step_s=None,
                metrics_out=None, gamma=3, draft_layers=1,
                attention_impl="gather", kv_dtype="float32",
                weight_dtype="float32", tp=2, pp=2, prefill_chunk=None,
                engine_sink=None, serve_jsonl=None, decision_sink=None,
                tenancy=None, tier_kwargs=None):
    """Build engine+scheduler, replay `traffic`, return the summary
    (annotated with the engine's KV budget and compile counters).
    `engine_sink`: optional list the built (now-warmed) engine is
    appended to, so a caller can keep driving its compiled executables
    or audit its pool and ledger after the replay.
    `serve_jsonl` (ISSUE 15): write the scheduler's serving JSONL
    (step/request/timeline AND decisions.v1 records) to this path;
    `decision_sink`: optional list extended with the scheduler's
    decision records after the replay — what an audit asserts
    over. A multi-tenant traffic config additionally judges per-tenant
    SLO burn (fleet.per_tenant_slos) across the replay and reports it
    under summary["tenant_slo_burn"].
    `tenancy` (ISSUE 17): a serving.tenancy.TenancyConfig arms the
    scheduler's token buckets + prefix-namespace quotas, and every
    tenant whose spec carries an `adapter_rank` gets a synthetic
    seeded LoRA adapter loaded into the engine's bank before traffic —
    the one-command isolation-gate shape."""
    from paddle_tpu.observability import fleet as _fleet
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.serving import Scheduler

    engine = build_engine(model, kind, slots, max_len,
                          block_size=block_size, num_blocks=num_blocks,
                          prefix_cache=prefix_cache, gamma=gamma,
                          draft_layers=draft_layers,
                          attention_impl=attention_impl,
                          kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                          tp=tp, pp=pp, prefill_chunk=prefill_chunk,
                          tier_kwargs=tier_kwargs)
    if tenancy is not None:
        _attach_tenant_adapters(model, engine, tenancy)
    vclock = VirtualClock() if virtual_step_s is not None else None
    sched = Scheduler(engine, max_queue=max_queue,
                      shed_watermark=shed_watermark,
                      shed_pool_free=shed_pool_free,
                      metrics_path=serve_jsonl,
                      clock=(vclock if vclock is not None
                             else time.monotonic),
                      tenancy=tenancy)
    trace = synth_trace(traffic, model.cfg.vocab_size)
    wd = None
    if traffic.tenants:
        # per-tenant SLO burn across the replay window (ISSUE 15): one
        # baseline observation before traffic, one after — the burn
        # gauges land tenant-labeled in the shared registry, so the
        # metrics_out snapshot (and any fleet merge of it) carries
        # serving_slo_burn{slo,window,tenant}
        # prime the tenant label children FIRST: the baseline snapshot
        # must carry (0, 0) samples for fresh tenants, or the watchdog's
        # first-sight-is-baseline rule would swallow the whole replay
        _fleet.prime_tenant_series(sorted(traffic.tenants))
        wd = _fleet.BurnRateWatchdog(
            slos=_fleet.per_tenant_slos(sorted(traffic.tenants)),
            fast_window_s=60.0, slow_window_s=600.0, sustain=2,
            clock=(vclock if vclock is not None else time.monotonic))
        wd.observe(_metrics.registry().snapshot())
    summary = replay(sched, trace, timeout_s=traffic.timeout_s,
                     virtual_clock=vclock,
                     virtual_step_s=virtual_step_s or 0.01)
    if wd is not None:
        summary["tenant_slo_burn"] = wd.observe(
            _metrics.registry().snapshot())
    if decision_sink is not None:
        decision_sink.extend(sched.decision_records())
    if serve_jsonl:
        sched.close()
    summary["engine"] = kind
    summary["kv_memory_tokens"] = engine.kv_memory_tokens
    summary["slots"] = engine.slots
    summary["kv_dtype"] = getattr(engine.config, "kv_dtype", "float32")
    summary["weight_dtype"] = getattr(engine.config, "weight_dtype",
                                      "float32")
    # JSON-safe: the pp engine's per-(stage, chunk) counters key on
    # tuples — stringify inner keys so summaries serialize
    summary["trace_counts"] = {
        k: ({str(ik): iv for ik, iv in v.items()}
            if isinstance(v, dict) else v)
        for k, v in engine.trace_counts.items()}
    if kind in ("paged", "spec", "quant", "tp", "pp", "spec_pp"):
        summary["blocks_total"] = engine.block_pool.capacity
        pc = engine.prefix_cache
        summary["prefix_cache_blocks"] = len(pc) if pc is not None else 0
        # KV tier hierarchy readout (ISSUE 18): hit/miss/demote/promote
        # tallies + per-tier residency, straight off the store
        tiers = getattr(engine, "kv_tiers", None)
        if tiers is not None:
            summary["kv_tiers"] = tiers.stats()
    if kind in ("spec", "spec_pp"):
        m = sched.metrics()
        summary["spec_proposed"] = m.get("spec_proposed", 0)
        summary["spec_accepted"] = m.get("spec_accepted", 0)
        summary["spec_acceptance_rate"] = m.get("spec_acceptance_rate")
        summary["gamma"] = engine.config.gamma
    # measured per-device HBM (ISSUE 13): what equal-per-host-HBM
    # comparisons equalize on — never dtype-width arithmetic
    summary["hbm_max_device_bytes"] = \
        engine.hbm_accounting()["max_device_total"]
    if kind in ("tp", "pp", "spec_pp"):
        summary["tp"] = engine.config.tp
    if kind in ("pp", "spec_pp"):
        # acceptance rate and bubble fraction ride the SAME summary for
        # the composed arm (ISSUE 14): the two failure-class gauges of
        # the spec×pp win, reported together
        summary["pp"] = engine.config.pp
        summary["pp_stats"] = engine.pp_stats()
    if metrics_out:
        _metrics.registry().write_snapshot(metrics_out)
        summary["metrics_snapshot"] = metrics_out
    if engine_sink is not None:
        engine_sink.append(engine)
    return summary


def quant_quality(model, slots=3, max_len=64, block_size=8,
                  prompts=None, steps=24, seed=0, attention_impl="gather",
                  kv_dtype="int8", weight_dtype="int8",
                  serve_metrics_path=None, tie_eps=1e-3):
    """The ISSUE 11 quality gate: drive a quantized paged engine and the
    f32 paged ORACLE through the same teacher-forced token stream and
    measure how far int8 serving drifts from float serving.

    Teacher forcing makes the comparison per-step: after every decode
    the oracle's token is fed to BOTH engines, so one early argmax flip
    cannot cascade into incomparable streams — greedy_match is the
    fraction of (slot, step) decisions where the quantized engine's
    pick agrees with the oracle's, and logit_kl is the mean
    KL(oracle softmax || quant softmax) over the same decisions (the
    capture_logits decode tap).

    `tie_eps` makes the match GENUINE-disagreement only: a decision
    counts as matched when the oracle rates the quantized pick within
    `tie_eps` of its own best logit, OR (the mirror case) the quantized
    engine rates the oracle's pick within `tie_eps` of its own best —
    either way the "disagreement" is a sub-epsilon argmax tie on one
    side. Sub-epsilon gaps flip under float reproducibility noise alone
    (XLA CPU thread partitioning moves logits by ~1e-6; an
    untrained-model top-2 gap can be 1e-4), so they carry no signal
    about quantization — while real corruption (a wrong block scale,
    rotted codes) moves logits orders of magnitude more and still
    registers on BOTH sides, which the serving.kv_quant chaos test
    pins.

    Results are exported as `serving_quant_greedy_match` /
    `serving_quant_logit_kl` gauges (failure-class gated by
    `tools/metrics_report.py --compare`) and, when `serve_metrics_path`
    is given, appended as a `run` record to the serving JSONL."""
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.serving import PagedGenerationEngine

    rng = np.random.RandomState(seed)
    vocab = model.cfg.vocab_size
    if prompts is None:
        prompts = [rng.randint(0, vocab, int(rng.randint(
            block_size, 2 * block_size + 4))).tolist()
            for _ in range(slots)]
    prompts = list(prompts)[:slots]
    common = dict(slots=slots, max_len=max_len, block_size=block_size,
                  attention_impl=attention_impl, capture_logits=True,
                  seed=seed)
    oracle = PagedGenerationEngine(model, **common)
    quant = PagedGenerationEngine(model, kv_dtype=kv_dtype,
                                  weight_dtype=weight_dtype, **common)
    for s, p in enumerate(prompts):
        f = oracle.prefill(s, p)
        quant.prefill(s, p)
        quant.set_slot_token(s, f)           # teacher-force from step one
    n = len(prompts)
    matches, kls = [], []
    for _ in range(int(steps)):
        toks = oracle.decode()
        quant.decode()
        lo = oracle.last_logits[:n].astype(np.float64)
        lq = quant.last_logits[:n].astype(np.float64)
        ao, aq = np.argmax(lo, -1), np.argmax(lq, -1)
        rows = np.arange(n)
        matches.append((ao == aq)
                       | (lo[rows, aq] >= lo[rows, ao] - tie_eps)
                       | (lq[rows, ao] >= lq[rows, aq] - tie_eps))
        po = np.exp(lo - lo.max(-1, keepdims=True))
        po /= po.sum(-1, keepdims=True)
        zq = lq - lq.max(-1, keepdims=True)
        log_q = zq - np.log(np.exp(zq).sum(-1, keepdims=True))
        kls.append((po * (np.log(po + 1e-30) - log_q)).sum(-1))
        for s in range(n):
            quant.set_slot_token(s, int(toks[s]))
    greedy_match = float(np.mean(matches))
    logit_kl = float(np.mean(kls))
    _metrics.gauge(
        "serving_quant_greedy_match",
        "Teacher-forced greedy argmax agreement of the quantized serving "
        "path vs the f32 oracle (1.0 == every decision identical)"
    ).set(greedy_match)
    _metrics.gauge(
        "serving_quant_logit_kl",
        "Mean KL(f32 oracle || quantized) of the decode logits over the "
        "teacher-forced comparison stream").set(logit_kl)
    out = {"greedy_match": greedy_match, "logit_kl": logit_kl,
           "steps": int(steps), "slots": n,
           "kv_dtype": kv_dtype, "weight_dtype": weight_dtype}
    if serve_metrics_path:
        with open(serve_metrics_path, "a") as f:
            f.write(json.dumps({
                "kind": "run", "kv_dtype": kv_dtype,
                "weight_dtype": weight_dtype,
                "quant_greedy_match": greedy_match,
                "quant_logit_kl": logit_kl}) + "\n")
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--engine", default="both",
                   choices=("dense", "paged", "spec", "quant", "tp",
                            "pp", "spec_pp", "both", "all"),
                   help="'both' = dense+paged; 'all' adds the "
                        "spec-decode and quantized arms; tp/pp are the "
                        "hybrid-parallel engines over this process's "
                        "local devices (ISSUE 13); spec_pp composes "
                        "speculative verify windows onto the pipeline "
                        "ring (--gamma/--draft-layers with --pp/--tp, "
                        "ISSUE 14)")
    p.add_argument("--model", default="gpt_tiny")
    p.add_argument("--users", type=int, default=8)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate-rps", type=float, default=200.0)
    p.add_argument("--prefix-pool", type=int, default=2)
    p.add_argument("--prefix-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--slots", type=int, default=4,
                   help="dense slot count; paged gets --paged-slots")
    p.add_argument("--paged-slots", type=int, default=None,
                   help="paged slot count (default: sized to the same KV "
                        "budget as dense)")
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--block-size", type=int, default=8)
    p.add_argument("--gamma", type=int, default=3,
                   help="spec arm: draft tokens proposed per round")
    p.add_argument("--draft-layers", type=int, default=1,
                   help="spec arm: truncated-draft layer count")
    p.add_argument("--attention-impl", default="gather",
                   choices=("gather", "kernel"),
                   help="paged/spec attend: dense-view gather or the "
                        "Pallas in-kernel block-table walk")
    p.add_argument("--tp", type=int, default=2,
                   help="tensor degree of the tp/pp arms (per stage "
                        "for pp)")
    p.add_argument("--pp", type=int, default=2,
                   help="pipeline stage count of the pp arm")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="pp arm: tokens per pipelined prefill chunk "
                        "(default: one chunk per suffix bucket)")
    p.add_argument("--timeout-s", type=float, default=None)
    p.add_argument("--shed-watermark", type=int, default=None)
    p.add_argument("--tenants", default=None,
                   help="multi-tenant mix (ISSUE 15): 'a:400,b:100' = "
                        "tenant name:arrival rps per tenant; requests "
                        "split pro-rata, per-tenant p50/p99 TTFT + "
                        "phase attribution + SLO burn reported")
    p.add_argument("--burst", default=None,
                   help="burst knob: 'TENANT:T0:DUR:MULT' multiplies "
                        "TENANT's arrival rate by MULT inside "
                        "[T0, T0+DUR) seconds — the isolation-gate "
                        "scenario")
    p.add_argument("--tenant-adapters", default=None,
                   help="per-tenant LoRA rank (ISSUE 17): 'a:4,b:8' "
                        "loads a synthetic seeded rank-r adapter for "
                        "each named tenant; unlisted tenants decode "
                        "base weights through the same one compiled "
                        "trace")
    p.add_argument("--tenant-quotas", default=None,
                   help="per-tenant resident prefix-block quota: "
                        "'a:8,b:8' — namespace == tenant name; a hot "
                        "tenant over quota evicts its OWN leaves first")
    p.add_argument("--tenant-rates", default=None,
                   help="per-tenant token-bucket 'a:400/800,b:100' = "
                        "rate[/burst] tokens per second; denials land "
                        "as serving_rate_limited_total{tenant} and in "
                        "the per-tenant replay summary")
    p.add_argument("--serve-jsonl", default=None,
                   help="write the scheduler's serving JSONL here "
                        "(step/request/timeline + decisions.v1 audit "
                        "records; tools/serve_report.py renders it)")
    p.add_argument("--virtual-step-s", type=float, default=None,
                   help="run on a deterministic virtual clock (this many "
                        "virtual seconds per scheduler step)")
    p.add_argument("--metrics-out", default=None,
                   help="write a metrics-registry JSONL snapshot here")
    args = p.parse_args(argv)

    from paddle_tpu.text import models as _models
    model = getattr(_models, args.model)()
    model.eval()
    tenants = None
    if args.tenants:
        tenants = {name: float(rate) for name, rate in
                   (part.split(":") for part in args.tenants.split(","))}
    burst = None
    if args.burst:
        bt, t0, dur, mult = args.burst.split(":")
        burst = {"tenant": bt, "t0": float(t0), "dur_s": float(dur),
                 "mult": float(mult)}
    tenancy = build_tenancy(tenants, args.tenant_adapters,
                            args.tenant_quotas, args.tenant_rates)
    traffic = TrafficConfig(
        users=args.users, requests=args.requests, rate_rps=args.rate_rps,
        prefix_pool=args.prefix_pool, prefix_len=args.prefix_len,
        max_new_tokens=args.max_new, timeout_s=args.timeout_s,
        seed=args.seed, tenants=tenants, burst=burst)

    budget = args.slots * args.max_len           # dense KV budget, tokens
    num_blocks = budget // args.block_size       # same budget in blocks
    paged_slots = args.paged_slots or min(
        2 * args.slots, max(args.slots + 1, num_blocks - 1))
    kinds = {"both": ("dense", "paged"),
             "all": ("dense", "paged", "spec", "quant")}.get(
                 args.engine, (args.engine,))
    out = {}
    for kind in kinds:
        out[kind] = run_harness(
            model, kind, traffic,
            slots=args.slots if kind == "dense" else paged_slots,
            max_len=args.max_len, block_size=args.block_size,
            num_blocks=num_blocks, shed_watermark=args.shed_watermark,
            virtual_step_s=args.virtual_step_s,
            gamma=args.gamma, draft_layers=args.draft_layers,
            attention_impl=args.attention_impl,
            tp=args.tp, pp=args.pp, prefill_chunk=args.prefill_chunk,
            metrics_out=args.metrics_out
            if kind == kinds[-1] else None,
            serve_jsonl=args.serve_jsonl
            if kind == kinds[-1] else None,
            tenancy=tenancy)
    print(json.dumps(out, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
