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


# ---------------- ISSUE 35: the aggregates kept as events apply, the checks
# ---------------- made whole at every boundary without a walk of the pool

_TENANTS = ("eq-a", "eq-b", "eq-c", None)
_ORIGINS = ("prefill", "grow", "retire", "prefix_cache.match",
            "prefix_cache.insert", "prefix_cache.evict",
            "prefix_cache.promote")


def _gauge(name, tenant, kind):
    return getattr(kvledger, name).labels(tenant=tenant, kind=kind).value


def _views_agree(shadow):
    """The kept aggregates against the per-block recount."""
    assert shadow.tenant_kind_blocks() == shadow.scan_tenant_kind_blocks()
    assert shadow.tenant_resident_totals() == \
        shadow.scan_tenant_resident_totals()
    assert shadow.cache_only == shadow.scan_cache_only()
    free = shadow.free_set()
    assert len(free) == shadow.num_blocks - 1 - len(shadow.allocated)
    assert free.isdisjoint(shadow.allocated)
    assert len(shadow.free_list) == len(free)
    assert set(shadow.free_list) == free
    assert shadow.tier_of == {k: t for k, (_o, t) in shadow.tiered.items()}


def _random_batch(led, rng, refs, n_events, big):
    """`n_events` ledger events under changing attribution. `refs` is
    the driver's own {block: refcount}: most transitions are the ones a
    pool could make, a few are not (a diverged stream must keep its
    aggregates equal to its own per-block state too)."""
    nb = led.num_blocks
    for _ in range(n_events):
        tenant = _TENANTS[rng.randint(len(_TENANTS))]
        rid = int(rng.randint(1, 40)) if rng.rand() < 0.8 else None
        held = list(refs)
        op = rng.choice(["alloc", "ref", "unref", "unref", "cache",
                         "evict", "share", "tier", "junk"],
                        p=[.2, .17, .2, .1, .1, .08, .03, .1, .02])
        with kvledger.attribution(request_id=rid, tenant=tenant,
                                  origin=_ORIGINS[rng.randint(3)]):
            if op == "alloc":
                free = [b for b in rng.randint(1, nb, 3 * (90 if big else 3))
                        if b not in refs]
                free = list(dict.fromkeys(int(b) for b in free))
                if free:
                    led.pool_alloc(free)
                    refs.update(dict.fromkeys(free, 1))
            elif op == "ref" and held:
                b = held[rng.randint(len(held))]
                with kvledger.origin_scope(_ORIGINS[rng.randint(7)]):
                    led.pool_ref(b)
                refs[b] += 1
            elif op == "unref" and held:
                for b in rng.choice(held, min(len(held),
                                              40 if big else 2), False):
                    b = int(b)
                    if rng.rand() < 0.3:
                        with kvledger.origin_scope("prefix_cache.evict"):
                            led.pool_unref(b)
                    else:
                        led.pool_unref(b)
                    refs[b] -= 1
                    if not refs[b]:
                        del refs[b]
                        led.pool_free(b)
            elif op == "cache" and held:
                b = held[rng.randint(len(held))]
                with kvledger.origin_scope("prefix_cache.insert"):
                    led.pool_ref(b)
                refs[b] += 1
                led.cache_insert((b,))
            elif op == "evict" and led.shadow.cached:
                cached = list(led.shadow.cached)
                led.cache_evict((cached[rng.randint(len(cached))],))
            elif op == "share" and held:
                led.cache_share(held[:2], tokens=8)
            elif op == "tier":
                key = f"chain{rng.randint(12)}"
                what = rng.randint(4)
                if what == 0:
                    led.tier_demote((), key, "host", tenant or "default")
                elif what == 1:
                    led.tier_demote((), key, "disk", "eq-b")
                elif what == 2:
                    led.tier_promote((1,), key, "host", "eq-a")
                else:
                    led.tier_drop(key, "disk", "eq-a", reason="capacity")
            elif op == "junk":
                # transitions no pool makes: the shadow records them in
                # `errors` and keeps tracking
                led._emit(["alloc", "ref", "unref", "free"][rng.randint(4)],
                          (int(rng.randint(0, nb + 2)),))


@pytest.mark.parametrize("num_blocks", [64, 12289])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kept_aggregates_equal_the_recount_and_the_replay(seed, num_blocks):
    """Random alloc / ref / unref / free / cache_insert / cache_evict /
    share / tier streams under changing attribution: after every batch
    the views kept as events apply equal the ones recounted from the
    per-block state, the gauges read the recount, and at the end a
    ShadowPool replayed from the serialized stream equals the live one,
    aggregates included."""
    rng = np.random.RandomState(seed)
    big = num_blocks > 1000
    led = kvledger.KVLedger(num_blocks, block_bytes=48)
    refs, seen = {}, set()
    for _ in range(12):
        _random_batch(led, rng, refs, 120 if big else 60, big)
        shadow = led.shadow
        _views_agree(shadow)
        led.export_gauges()
        counts = shadow.scan_tenant_kind_blocks()
        seen |= set(counts)
        for t, k in seen:
            assert _gauge("_G_BLOCKS", t, k) == counts.get((t, k), 0)
            assert _gauge("_G_BYTES", t, k) == 48 * counts.get((t, k), 0)
        assert not shadow.dirty
    assert big is False or len(led.shadow.allocated) > 1000
    assert led.shadow.errors                   # the junk was seen
    stream = json.loads(json.dumps(led.events))
    replayed = kvledger.replay_events(stream, num_blocks)
    live = led.shadow
    for field in ("refs", "allocated", "holders", "cached", "tiered",
                  "errors", "applied", "free_list", "cache_only",
                  "tier_of"):
        got = getattr(replayed, field)
        if field == "holders":      # tuples come back from JSON as such
            got = {b: [tuple(h) for h in hs] for b, hs in got.items()}
        assert got == getattr(live, field), field
    assert replayed.tenant_kind_blocks() == live.tenant_kind_blocks()
    assert replayed.tenant_resident_totals() == \
        live.tenant_resident_totals()
    assert replayed.free_set() == live.free_set()
    _views_agree(replayed)


def _attached_12k():
    """A 12 289-block pool, 95 % allocated by 128 requests of two
    tenants, each with its prompt's first eight blocks in an attached
    prefix cache, a tier store behind the cache holding a few evicted
    chains — and the reconciler over all of it, clean."""
    from paddle_tpu.serving.kv_tiers import TieredBlockStore
    nb, bs, per = 12289, 4, 91
    pool = BlockPool(nb, bs)
    ledger = kvledger.KVLedger(nb, block_bytes=64)
    pool.attach_ledger(ledger)
    cache = PrefixCache(pool, bs)
    cache.attach_ledger(ledger)
    store = TieredBlockStore(
        lambda blk: {"quant": False,
                     "arrays": {"k0": np.full((2,), blk, np.float32)}},
        lambda blk, arrays: None, host_blocks=64)
    store.attach_ledger(ledger)
    cache.attach_tier(store)
    rng = np.random.RandomState(35)
    rows = {}

    def admit(rid):
        prompt = rng.randint(0, 50000, 8 * bs + 1).tolist()
        with kvledger.attribution(request_id=rid, tenant=f"wb-{rid % 2}",
                                  origin="prefill"):
            rows[rid] = pool.alloc(per)
            cache.insert(prompt, rows[rid], 8 * bs)

    def retire(rid):
        with kvledger.attribution(request_id=rid, tenant=f"wb-{rid % 2}",
                                  origin="retire"):
            for b in rows.pop(rid):
                pool.unref(b)

    for rid in range(128):
        admit(rid)
    for rid in (3, 4):
        retire(rid)                  # their cached blocks: cache-only now
    assert cache.evict(6) == 6       # six chains' tails go to the host tier
    for rid in (200, 201):
        admit(rid)
    recon = kvledger.LedgerReconciler(ledger, pool, cache, tier_store=store)
    assert recon.check() == []
    assert pool.in_use > 0.94 * pool.capacity
    assert len(cache._entries) > 1000 and cache.evictable() == 10
    assert len(store.residency()) == 6
    return {"pool": pool, "ledger": ledger, "cache": cache, "store": store,
            "recon": recon, "rows": rows, "admit": admit, "retire": retire}


def _leak_one_block(w):
    spec = faults.arm("serving.kv_ledger_leak", "truncate", nth=1,
                      max_fires=1)
    try:
        with kvledger.attribution(request_id=7, tenant="wb-1",
                                  origin="retire"):
            w["pool"].unref(w["rows"][7].pop())
    finally:
        faults.disarm("serving.kv_ledger_leak")
    assert spec.fires == 1


def _ref_a_free_block(w):
    w["ledger"].pool_ref(w["pool"]._free[0])


def _poke_refcount(w):
    w["pool"]._refs[w["rows"][9][20]] += 1


def _drop_a_free_entry(w):
    w["pool"]._free.pop()


def _free_an_allocated_block(w):
    w["pool"]._free.append(w["rows"][9][20])


def _free_a_block_twice(w):
    w["pool"]._free.append(w["pool"]._free[0])


def _swap_in_a_block_out_of_range(w):
    w["pool"]._free[0] = w["pool"].num_blocks + 5


def _drop_a_cache_entry(w):
    leaf = next(iter(w["cache"]._leaves))
    del w["cache"]._entries[leaf]


def _repoint_a_cache_entry(w):
    key = next(iter(w["cache"]._entries))
    w["cache"]._entries[key] = w["rows"][9][20]


def _orphan_a_chain(w):
    key = next(k for k, p in w["cache"]._parent.items() if p is not None)
    w["cache"]._parent[key] = "no-such-entry"


def _cache_only_behind_the_ledger(w):
    # a cached block a request still co-owns, made to look cache-only
    w["pool"]._refs[w["rows"][9][0]] = 1


def _evictable_lies(w):
    real = w["cache"].evictable
    w["cache"].evictable = lambda: real() + 1


def _drop_a_tier_entry(w):
    w["store"].host.drop(next(iter(w["store"].residency())))


def _demote_behind_the_ledger(w):
    w["store"].host.put("unseen", {"ns": None, "parent": None,
                                   "quant": False, "arrays": {}})


@pytest.mark.parametrize("invariant,damage", [
    ("event_stream", _ref_a_free_block),
    ("refcounts", _poke_refcount),
    ("free_list", _leak_one_block),
    ("free_list", _drop_a_free_entry),
    ("free_list", _free_an_allocated_block),
    ("free_list", _free_a_block_twice),
    ("free_list", _swap_in_a_block_out_of_range),
    ("cached_set", _drop_a_cache_entry),
    ("cached_set", _repoint_a_cache_entry),
    ("orphan_chain", _orphan_a_chain),
    ("evictable", _cache_only_behind_the_ledger),
    ("evictable", _evictable_lies),
    ("tier_residency", _drop_a_tier_entry),
    ("tier_residency", _demote_behind_the_ledger),
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_each_invariant_fires_in_the_first_check_after_its_damage(
        invariant, damage):
    """Every entry of INVARIANTS, damage done through the fault site and
    behind the ledger's back (a poke at the pool's, the cache's or the
    tier store's own state, no event): the very next check() names it,
    and goes on naming it while it lasts."""
    w = _attached_12k()
    div0 = _divergence_total()
    damage(w)
    found = w["recon"].check()
    assert any(m.startswith(invariant + ":") for m in found), found
    assert _divergence_total() >= div0 + 1
    again = w["recon"].check()
    assert any(m.startswith(invariant + ":") for m in again), again


def test_every_invariant_has_a_detection_case():
    marks = test_each_invariant_fires_in_the_first_check_after_its_damage \
        .pytestmark
    cases = next(m for m in marks if m.name == "parametrize").args[1]
    assert {inv for inv, _ in cases} == set(kvledger.INVARIANTS)


@pytest.mark.parametrize("events", ["none", "a decode step", "a retirement"])
def test_a_clean_check_walks_what_the_step_changed_not_the_pool(
        events, monkeypatch):
    """The work bound, on the counter and never on a clock: a clean
    check() after k events visits at most k things one by one (the
    gauge series whose count moved), calls none of the per-block
    recounts, and says so in `last_check` — what the scheduler puts on
    its `serving::bookkeeping` span."""
    w = _attached_12k()
    shadow = w["ledger"].shadow
    for name in ("free_set", "scan_tenant_kind_blocks",
                 "scan_tenant_resident_totals", "scan_cache_only"):
        monkeypatch.setattr(
            shadow, name, lambda name=name: pytest.fail(
                f"a clean check() called {name}()"), raising=True)
    applied = shadow.applied
    if events == "a decode step":        # eight slots cross a block edge
        for rid in range(10, 18):
            with kvledger.attribution(request_id=rid,
                                      tenant=f"wb-{rid % 2}",
                                      origin="grow"):
                w["rows"][rid].extend(w["pool"].alloc(1))
    elif events == "a retirement":       # 182 blocks back, 91 out again
        w["retire"](20)
        w["retire"](21)
        w["admit"](300)
    k = shadow.applied - applied
    assert w["recon"].check() == []
    last = w["recon"].last_check
    assert last["ledger_events"] == k
    assert last["ledger_pool_blocks"] == 12289
    assert last["ledger_blocks_walked"] <= k
    assert last["ledger_blocks_walked"] < 0.05 * 12289
    # and a second check with nothing in between walks nothing at all
    assert w["recon"].check() == []
    assert w["recon"].last_check["ledger_blocks_walked"] == 0
    assert w["recon"].last_check["ledger_events"] == 0


def test_a_step_that_finds_a_divergence_may_walk_everything():
    w = _attached_12k()
    _drop_a_free_entry(w)
    assert w["recon"].check()
    assert w["recon"].last_check["ledger_blocks_walked"] >= 12289


def test_a_free_list_in_another_order_is_no_divergence():
    """The shadow keeps the free list in the pool's own order for the
    one-comparison short cut only: the invariant is the SET, as it
    always was."""
    w = _attached_12k()
    assert w["pool"]._free == w["ledger"].shadow.free_list
    w["pool"]._free.reverse()
    assert w["recon"].check() == []
    assert w["recon"].last_check["ledger_blocks_walked"] >= 12289
    with kvledger.attribution(request_id=5, tenant="wb-1", origin="grow"):
        w["rows"][5].extend(w["pool"].alloc(3))      # not the shadow's top
    w["retire"](5)
    assert w["recon"].check() == [] and not w["ledger"].shadow.errors
    assert set(w["pool"]._free) == w["ledger"].shadow.free_set()


def test_bookkeeping_span_carries_the_ledgers_counters(tiny):
    """`serving::bookkeeping` says what the reconcile cost on every
    step: events applied, things walked one by one, the pool's size."""
    from paddle_tpu import profiler
    from paddle_tpu.observability.flight_recorder import SpanLog
    engine = PagedGenerationEngine(tiny, slots=2, max_len=32,
                                   block_size=4, num_blocks=12,
                                   enable_prefix_cache=True)
    sched = Scheduler(engine, max_queue=8)
    before = profiler.span_log().appended
    rng = np.random.RandomState(9)
    for _ in range(3):
        sched.submit(rng.randint(0, 1000, 9).tolist(), max_new_tokens=4)
    sched.run_until_idle()
    spans = [dict(zip(SpanLog.FIELDS, r))
             for r in profiler.span_log().spans()][
                 before - profiler.span_log().appended:]
    kept = [s["attrs"] for s in spans
            if s["name"] == "serving::bookkeeping"]
    assert len(kept) == sched._steps > 3
    assert all(a["ledger_pool_blocks"] == 12 for a in kept)
    assert sum(a["ledger_events"] for a in kept) == \
        len(engine.kv_ledger.events)
    assert all(a["ledger_blocks_walked"] <= max(a["ledger_events"], 0)
               for a in kept)
    assert not sched._kv_reconciler.divergences


def test_kvledger_imports_and_replays_with_no_numpy_and_no_jax():
    """The offline half: `kvledger.py` loads beside a process that holds
    the chip, with the stdlib alone."""
    import subprocess
    obs = os.path.join(_ROOT, "paddle_tpu", "observability")
    code = f"""
import importlib, sys, types
pkg = types.ModuleType("obs"); pkg.__path__ = [{obs!r}]
sys.modules["obs"] = pkg
k = importlib.import_module("obs.kvledger")
assert not [m for m in sys.modules
            if m.split(".")[0] in ("numpy", "jax", "paddle_tpu")]
sh = k.replay_events([
    {{"seq": 0, "event": "alloc", "blocks": [1, 2], "tenant": "a"}},
    {{"seq": 1, "event": "unref", "blocks": [2], "tenant": "a"}},
    {{"seq": 2, "event": "free", "blocks": [2], "tenant": "a"}}], 4)
assert sh.tenant_kind_blocks() == {{("a", "private"): 1}} \\
    == sh.scan_tenant_kind_blocks()
assert sh.free_set() == {{2, 3}} and not sh.errors
"""
    proc = subprocess.run([sys.executable, "-c", code], timeout=60,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
