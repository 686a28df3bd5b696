"""KV-memory attribution plane (ISSUE 16): block lifecycle ledger,
per-tenant HBM accounting, and the live leak/invariant watchdog.

The load-bearing properties:
  - a mixed two-tenant load-harness run (priority mix, burst-driven
    sheds/preemptions, prefix-cache hits) streams kvledger.v1 records
    into the serving JSONL, and replaying them after a JSON round trip
    reconstructs the real BlockPool's final free list and refcounts
    EXACTLY — the on-disk event log is the proof there is no leak;
  - the injected `serving.kv_ledger_leak` fault (pool skips one
    free-list return the ledger recorded) is caught by LedgerReconciler
    at the very step boundary it happened, latches
    `serving_kv_ledger_divergence_total{invariant=free_list}`, dumps a
    postmortem once, and gates `metrics_report --compare` as a
    failure-class regression from a clean baseline;
  - the ledger is OBSERVABILITY-ONLY: disabled vs enabled, every engine
    kind (dense/paged/spec/tp/pp) emits bit-identical token streams
    with identical trace counts;
  - PrefixCache.evictable() and eviction accounting stay consistent
    with the ledger's shadow model under COW chain sharing;
  - per-tenant residency lands everywhere it should: load_harness
    summaries + serving_load_tenant_kv_blocks_* gauges, fleet-merged
    serving_kv_blocks{tenant,kind} series, serve_report's residency and
    prefix-share tables.
"""
import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu.observability import faults, fleet, flight_recorder
from paddle_tpu.observability import kvledger
from paddle_tpu.observability import metrics
from paddle_tpu.serving import (BlockPool, PagedGenerationEngine,
                                PrefixCache, Scheduler)
from paddle_tpu.text.models import gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import load_harness  # noqa: E402
import metrics_report  # noqa: E402
import serve_report  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    m = gpt_tiny()
    m.eval()
    return m


def _divergence_total():
    snap = metrics.registry().snapshot()
    return sum(s["value"] for m in snap["metrics"]
               if m["name"] == "serving_kv_ledger_divergence_total"
               for s in m["samples"])


# ------------------------------------------------- the shadow model rules

def test_shadow_records_impossible_transitions():
    sh = kvledger.ShadowPool(4)
    sh.apply({"seq": 0, "event": "alloc", "blocks": [1], "tenant": "a"})
    sh.apply({"seq": 1, "event": "alloc", "blocks": [1], "tenant": "a"})
    sh.apply({"seq": 2, "event": "ref", "blocks": [2], "tenant": "a"})
    sh.apply({"seq": 3, "event": "unref", "blocks": [3], "tenant": "a"})
    sh.apply({"seq": 4, "event": "free", "blocks": [1], "tenant": "a"})
    assert len(sh.errors) == 4          # double alloc, ref/unref of
    assert "double alloc" in sh.errors[0]       # free, free with refs
    # the shadow keeps tracking a diverged pool instead of raising
    assert 1 not in sh.allocated


def test_holder_classification_and_drop_preference():
    """One block, three holders of three kinds; unrefs drop the right
    one: the evict-origin drops the cache's own, a request-id match
    drops that request's, tenant fallbacks come after."""
    led = kvledger.KVLedger(8)
    with kvledger.attribution(request_id=1, tenant="a", origin="prefill"):
        led.pool_alloc([3])                           # a/private
        with kvledger.origin_scope("prefix_cache.insert"):
            led.pool_ref(3)                           # a/cached
        led.cache_insert((3,))
    with kvledger.attribution(request_id=2, tenant="b", origin="prefill"):
        with kvledger.origin_scope("prefix_cache.match"):
            led.pool_ref(3)                           # b/shared
        led.cache_share((3,), tokens=4)
    tk = led.shadow.tenant_kind_blocks()
    assert tk == {("a", "private"): 1, ("a", "cached"): 1,
                  ("b", "shared"): 1}
    # request 2 retires: its shared holding drops, cache + private stay
    with kvledger.attribution(request_id=2, tenant="b", origin="retire"):
        led.pool_unref(3)
    assert led.shadow.tenant_kind_blocks() == \
        {("a", "private"): 1, ("a", "cached"): 1}
    # eviction drops the cache's own reference, not request 1's
    with kvledger.attribution(request_id=None, tenant=None,
                              origin="prefix_cache.evict"):
        led.cache_evict((3,))
        led.pool_unref(3)
    assert led.shadow.tenant_kind_blocks() == {("a", "private"): 1}
    assert led.shadow.cached == {}
    with kvledger.attribution(request_id=1, tenant="a", origin="retire"):
        led.pool_unref(3)
        led.pool_free(3)
    assert not led.shadow.errors
    assert led.shadow.tenant_resident_totals() == {}
    assert led.shadow.free_set() == {1, 2, 3, 4, 5, 6, 7}


def test_attribution_context_nests_and_restores():
    assert kvledger.current_attribution() is None
    with kvledger.attribution(request_id=7, tenant="t", origin="prefill"):
        with kvledger.origin_scope("prefix_cache.match"):
            cur = kvledger.current_attribution()
            assert cur == {"request_id": 7, "tenant": "t",
                           "origin": "prefix_cache.match"}
        assert kvledger.current_attribution()["origin"] == "prefill"
    assert kvledger.current_attribution() is None


# -------------------- evictable()/eviction accounting under COW sharing

def test_prefix_cache_evictable_and_eviction_accounting_under_cow():
    """Satellite: the cache's evictable() figure and its eviction
    bookkeeping agree with the ledger's shadow at every stage of a COW
    chain's life — insert, cross-request share, staggered retires,
    leaf-first eviction — with every reconciler invariant (including
    the evictable one) green throughout."""
    pool = BlockPool(num_blocks=8, block_size=4)
    ledger = kvledger.KVLedger(8, block_bytes=64)
    pool.attach_ledger(ledger)
    cache = PrefixCache(pool, 4)
    cache.attach_ledger(ledger)
    recon = kvledger.LedgerReconciler(ledger, pool, cache)
    prompt = list(range(12))
    with kvledger.attribution(request_id=1, tenant="a", origin="prefill"):
        row = pool.alloc(3)
        cache.insert(prompt, row, 8)          # 2 full blocks cached
    assert recon.check() == []
    assert cache.evictable() == 0             # request 1 still co-owns
    with kvledger.attribution(request_id=2, tenant="b", origin="prefill"):
        ids, n = cache.match(prompt)          # COW share of the chain
    assert ids == row[:2] and n == 8
    assert recon.check() == []
    tk = ledger.shadow.tenant_kind_blocks()
    assert tk[("a", "private")] == 3
    assert tk[("a", "cached")] == 2
    assert tk[("b", "shared")] == 2
    # nothing evictable while shared, and evict() must not free anything
    assert cache.evictable() == 0
    assert cache.evict(8) == 0 and len(cache) == 2
    assert recon.check() == []
    with kvledger.attribution(request_id=1, tenant="a", origin="retire"):
        for b in row:
            pool.unref(b)                     # row[2] frees, chain stays
    with kvledger.attribution(request_id=2, tenant="b", origin="retire"):
        for b in ids:
            pool.unref(b)
    assert recon.check() == []
    assert cache.evictable() == 2             # cache-only now
    assert ledger.shadow.tenant_kind_blocks() == {("a", "cached"): 2}
    # leaf-first eviction drains the chain and the pool reconstructs
    assert cache.evict(8) == 2 and len(cache) == 0
    assert recon.check() == []
    assert pool.available == pool.capacity
    assert ledger.shadow.free_set() == set(pool._free)
    assert not ledger.shadow.errors


# -------------------------- THE acceptance run: mixed load, exact replay

def test_mixed_burst_run_ledger_replay_reconstructs_the_pool(
        tiny, tmp_path):
    """Two-tenant burst through a small paged pool WITH the prefix
    cache: priority mix, sheds, preemptions, prefix hits. The full
    kvledger.v1 stream lands in the serving JSONL; parsed back, it
    replays into the pool's exact final free list + refcounts, the
    per-tenant residency reaches the harness summary, the
    serving_load_tenant/serving_kv gauges, the fleet merge, and
    serve_report's tables — with zero reconciler divergences."""
    div0 = _divergence_total()
    jsonl = str(tmp_path / "serve.jsonl")
    traffic = load_harness.TrafficConfig(
        users=6, requests=24, prefix_len=8, max_new_tokens=4, seed=3,
        tenants={"steady": 100.0, "spike": 100.0},
        burst={"tenant": "spike", "t0": 0.0, "dur_s": 0.2, "mult": 8.0})
    engines = []
    summary = load_harness.run_harness(
        tiny, "paged", traffic, slots=3, max_len=32, block_size=4,
        num_blocks=10, prefix_cache=True, max_queue=64,
        shed_watermark=3, virtual_step_s=0.01, serve_jsonl=jsonl,
        engine_sink=engines,
        metrics_out=str(tmp_path / "metrics.jsonl"))
    engine = engines[0]
    ledger = engine.kv_ledger
    assert ledger is not None and len(ledger.events) > 0
    # the mix actually exercised every lifecycle path
    assert summary["shed"] > 0
    assert summary["preempted"] > 0
    events_by_kind = {}
    recs = [json.loads(line) for line in open(jsonl) if line.strip()]
    kv_recs = [r for r in recs if r["kind"] == "kvledger"]
    for r in kv_recs:
        events_by_kind[r["event"]] = events_by_kind.get(r["event"], 0) + 1
    assert events_by_kind.get("share", 0) > 0          # prefix hits
    assert events_by_kind.get("cache_insert", 0) > 0
    # every event reached the JSONL, schema-valid
    assert len(kv_recs) == len(ledger.events)
    assert serve_report.validate_records(recs) == []
    # THE replay: the round-tripped stream reconstructs the real pool
    pool = engine.block_pool
    shadow = kvledger.replay_events(kv_recs, pool.num_blocks)
    assert not shadow.errors
    assert shadow.refs == [int(r) for r in pool._refs]
    assert shadow.free_set() == set(int(b) for b in pool._free)
    # zero leaks: everything still resident is a prefix-cache holding
    assert set(shadow.allocated) == set(shadow.cached)
    assert _divergence_total() == div0          # reconciler stayed green
    # per-tenant residency in the harness summary...
    ts = summary["tenants"]
    assert set(ts) == {"steady", "spike"}
    assert summary["kv_blocks_peak"] > 0
    assert any(t["kv_blocks_peak"] > 0 for t in ts.values())
    assert all("kv_blocks_mean" in t for t in ts.values())
    # ...in the harness gauges...
    flat = metrics.flatten_snapshot(metrics.registry().snapshot())
    assert any(k.startswith("serving_load_tenant_kv_blocks_peak{")
               for k in flat)
    assert any(k.startswith("serving_load_tenant_kv_blocks_mean{")
               for k in flat)
    # ...and relabeled per worker through the fleet merge
    merged = fleet.merge_snapshots(
        [{"worker_id": "w0", "role": "decode",
          "snapshot": metrics.registry().snapshot()}])
    mflat = metrics.flatten_snapshot(merged)
    kv_keys = [k for k in mflat if k.startswith("serving_kv_blocks{")
               and "worker_id=w0" in k and "tenant=" in k]
    assert kv_keys, sorted(k for k in mflat
                           if k.startswith("serving_kv"))[:10]
    # serve_report renders the residency + prefix-share tables
    digest = serve_report.summarize(recs)
    assert digest["kvledger_events"] == len(kv_recs)
    res = digest["kv_residency"]
    assert set(res["tenants"]) <= {"steady", "spike", "default"}
    text = serve_report.render(digest)
    assert "KV residency" in text
    assert "prefix-chain sharing" in text


# ------------------------------ the leak chaos test + the compare gate

def test_injected_leak_caught_within_one_step_and_gates_compare(
        tiny, tmp_path, capsys):
    """Chaos: `serving.kv_ledger_leak` (truncate) makes the pool skip
    one free-list return. The reconciler must latch the free_list
    divergence AT the step boundary of the very step the leak happened,
    name the leaked block, dump one postmortem — and the divergence
    counter must gate `metrics_report --compare` as failure-class from
    a clean zero baseline."""
    recorder = flight_recorder.enable(dir=str(tmp_path / "pm"))
    engine = PagedGenerationEngine(tiny, slots=2, max_len=32,
                                   block_size=4, num_blocks=12,
                                   enable_prefix_cache=False)
    sched = Scheduler(engine, max_queue=8)
    assert sched._kv_reconciler is not None
    baseline = str(tmp_path / "base.jsonl")
    after = str(tmp_path / "after.jsonl")
    metrics.registry().write_snapshot(baseline)
    rng = np.random.RandomState(5)
    spec = faults.arm("serving.kv_ledger_leak", "truncate", nth=1,
                      max_fires=1)
    try:
        hs = [sched.submit(rng.randint(0, 1000, 5).tolist(),
                           max_new_tokens=4) for _ in range(2)]
        while True:
            more = sched.step()
            if spec.fires:
                # caught at the SAME step boundary the damage happened
                assert sched._kv_reconciler.divergences, \
                    "leak not latched within one scheduler step"
                break
            if not more:
                break
        assert spec.fires == 1, "fault never fired (no block was freed)"
        msgs = sched._kv_reconciler.divergences
        assert any("free_list" in m and "leaked" in m for m in msgs), msgs
        sched.run_until_idle()
        assert all(h.status == "DONE" for h in hs)
        # one postmortem, latched once
        pm = sched._kv_reconciler.last_postmortem
        assert pm and os.path.exists(pm)
        metrics.registry().write_snapshot(after)
    finally:
        faults.disarm("serving.kv_ledger_leak")
        # detach from the host tracer: a recorder left attached fails
        # whichever test file the same worker runs next and expects none
        recorder.disable()
    # the CI gate: divergence growth from the primed-zero baseline is a
    # failure-class regression
    rc = metrics_report.main(["--compare", baseline, after])
    out = capsys.readouterr().out
    assert rc == 1
    assert "serving_kv_ledger_divergence_total" in out


def test_metrics_report_failure_class_matches_divergence_and_leak():
    assert metrics_report._FAIL_PAT.search(
        "serving_kv_ledger_divergence_total")
    assert metrics_report._FAIL_PAT.search("serving_kv_ledger_leak")


# ----------------------- the zero-cost contract across every engine kind

@pytest.mark.parametrize("kind", ["dense", "paged", "spec", "tp", "pp"])
def test_ledger_disabled_streams_bit_identical(tiny, kind):
    """Ledger enabled vs disabled: identical greedy token streams AND
    identical trace counts for every engine kind — observability must
    never touch device code or compile behavior."""
    import jax
    need = {"tp": 2, "pp": 2}.get(kind, 1)
    if len(jax.devices()) < need:
        pytest.skip(f"{kind} needs {need} devices")
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 1000, 5).tolist() for _ in range(2)]
    streams, traces, ledgers = [], [], []
    for on in (True, False):
        (kvledger.enable if on else kvledger.disable)()
        try:
            eng = load_harness.build_engine(
                tiny, kind, slots=2, max_len=32, block_size=4,
                num_blocks=12, prefix_cache=False, tp=2, pp=2,
                draft_layers=1)
        finally:
            kvledger.enable()
        sched = Scheduler(eng, max_queue=8)
        hs = [sched.submit(p, max_new_tokens=4) for p in prompts]
        sched.run_until_idle()
        assert all(h.status == "DONE" for h in hs)
        streams.append([h.tokens for h in hs])
        traces.append(json.dumps(
            {k: (sorted(v.items(), key=str) if isinstance(v, dict)
                 else v)
             for k, v in eng.trace_counts.items()}, default=str))
        ledgers.append(getattr(eng, "kv_ledger", None))
    assert streams[0] == streams[1]        # bit-identical output
    assert traces[0] == traces[1]          # zero trace/compile changes
    # enabled run attached a ledger exactly when there is a pool
    assert ledgers[1] is None
    if kind == "dense":
        assert ledgers[0] is None
    else:
        assert ledgers[0] is not None and len(ledgers[0].events) > 0


def test_block_bytes_priced_from_pool_dtype(tiny):
    """serving_kv_bytes prices a block from the engine's pool dtype:
    the f32/int8 figures must follow the equal-HBM block arithmetic."""
    f32 = PagedGenerationEngine(tiny, slots=2, max_len=32, block_size=4,
                                num_blocks=6, enable_prefix_cache=False)
    cfg = tiny.cfg
    h, d = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    assert f32._kv_block_bytes() == 2 * (4 * h * d * 4) * cfg.num_layers
    q = PagedGenerationEngine(tiny, slots=2, max_len=32, block_size=4,
                              num_blocks=6, enable_prefix_cache=False,
                              kv_dtype="int8")
    assert q._kv_block_bytes() == 2 * (4 * h * d + 4 * h) * cfg.num_layers
    assert f32.kv_ledger.block_bytes == f32._kv_block_bytes()


def test_fleet_priming_creates_kv_children_at_zero():
    fleet.prime_tenant_series(["primed_t"])
    flat = metrics.flatten_snapshot(metrics.registry().snapshot())
    for kind in ("private", "shared", "cached"):
        assert flat[
            f"serving_kv_blocks{{kind={kind},tenant=primed_t}}"] == 0
        assert flat[
            f"serving_kv_bytes{{kind={kind},tenant=primed_t}}"] == 0
