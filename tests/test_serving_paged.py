"""Paged-KV serving slice (ISSUE 6): block pool, block-table attention,
shared prefix cache, SLO scheduling, and the load-harness win.

The load-bearing properties:
  - block-table attend is TOKEN-EXACT vs the dense per-slot path across
    the bucket ladder, and the paged decode executable still compiles
    exactly once;
  - a shared system prompt is prefilled once: later requests reference
    its refcounted blocks (strictly fewer private blocks allocated) and
    still decode token-exactly;
  - preemption under allocation pressure — natural or injected via the
    `serving.block_alloc` fault site — never corrupts another request's
    stream, and (greedy) preempted requests resume bit-identically;
  - at a shared-prefix traffic mix and THE SAME KV memory budget, the
    paged+prefix-cache config sustains strictly more concurrent requests
    than the dense per-slot config, with p50/p99 TTFT and tokens/sec
    flowing through the metrics registry (schema-validated here).
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import faults
from paddle_tpu.serving import (
    BlockAllocError, BlockPool, GenerationEngine, LoadShedError,
    PagedGenerationEngine, PrefixCache, Scheduler,
)
from paddle_tpu.serving import blocks as blk
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.text.models import GPTForGeneration, gpt_tiny

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


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


def _prompt(seed, n, vocab=1000):
    return np.random.RandomState(seed).randint(0, vocab, n)


def _reference_tokens(model, prompt, max_new):
    gen = GPTForGeneration(model)
    ids = paddle.to_tensor(np.asarray(prompt)[None, :].astype("int64"))
    out, _ = gen.generate(ids, max_new_tokens=max_new)
    return list(out.numpy()[0])


# ------------------------------------------------------------- allocator
def test_block_pool_alloc_free_refcount():
    pool = BlockPool(num_blocks=6, block_size=8)
    assert pool.capacity == 5 and pool.available == 5    # block 0 reserved
    a = pool.alloc(3)
    assert blk.GARBAGE_BLOCK not in a
    assert pool.in_use == 3
    pool.ref(a[0])                       # shared: two owners now
    pool.unref(a[0])
    assert pool.in_use == 3              # still held by the first owner
    for b in a:
        pool.unref(b)
    assert pool.available == 5
    with pytest.raises(ValueError):
        pool.unref(a[0])                 # double free is loud


def test_block_pool_alloc_is_all_or_nothing():
    pool = BlockPool(num_blocks=4, block_size=8)
    pool.alloc(2)
    before = pool.available
    with pytest.raises(BlockAllocError):
        pool.alloc(2)                    # only 1 left
    assert pool.available == before      # nothing leaked


def test_block_alloc_fault_site_fires():
    pool = BlockPool(num_blocks=4, block_size=8)
    faults.arm("serving.block_alloc", "raise", exc=BlockAllocError,
               max_fires=1)
    with pytest.raises(BlockAllocError, match="fault-injection"):
        pool.alloc(1)
    assert pool.available == 3           # the injected failure leaked nothing
    assert len(pool.alloc(1)) == 1       # quiet after max_fires


# ----------------------------------------------------- attend regression
def test_attend_padded_garbage_never_nans():
    """ISSUE 6 satellite: masked attend must stay finite even when the
    padded/invisible region of the K/V buffers holds inf/NaN garbage
    (stale retired-request rows, scatter junk in the paged garbage
    block). The old jnp.finfo(min) fill let 0*NaN leak through the
    softmax tail."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    S, T, L, h, d = 2, 3, 16, 2, 4
    q = jnp.asarray(rng.randn(S, T, h, d).astype(np.float32))
    k_clean = rng.randn(S, L, h, d).astype(np.float32)
    v_clean = rng.randn(S, L, h, d).astype(np.float32)
    pos = jnp.asarray([0, 5], jnp.int32)   # slot 0: pos=0 (padded slot)
    want = np.asarray(kvc.attend(q, jnp.asarray(k_clean),
                                 jnp.asarray(v_clean), pos))
    assert np.isfinite(want).all()
    # poison everything INVISIBLE: positions > pos + T - 1
    k_bad, v_bad = k_clean.copy(), v_clean.copy()
    for s, p in enumerate([0, 5]):
        k_bad[s, p + T:] = np.nan
        v_bad[s, p + T:] = np.inf
    got = np.asarray(kvc.attend(q, jnp.asarray(k_bad), jnp.asarray(v_bad),
                                pos))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_attend_all_masked_row_emits_zeros():
    """The `where` on the output: a row with no visible key (pos < 0
    models a hole) emits exact zeros, not NaN or garbage."""
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 1, 2, 4).astype(np.float32))
    k = jnp.asarray(np.full((1, 8, 2, 4), np.nan, np.float32))
    v = jnp.asarray(np.full((1, 8, 2, 4), np.nan, np.float32))
    out = np.asarray(kvc.attend(q, k, v, jnp.asarray([-1], jnp.int32)))
    assert (out == 0.0).all()


# ------------------------------------------------------ token exactness
def test_paged_matches_dense_across_bucket_ladder(tiny):
    """Property (ISSUE 6 acceptance): the paged engine's prefill+decode
    trajectory is token-exact vs the dense engine AND the Layer-level
    oracle for prompt lengths crossing every block boundary of the
    ladder."""
    lengths = (1, 7, 8, 9, 15, 17, 31, 33)    # around block_size=8 edges
    for i in range(0, len(lengths), 2):
        pair = lengths[i:i + 2]
        prompts = [_prompt(10 + i + j, n) for j, n in enumerate(pair)]
        dense = GenerationEngine(tiny, slots=2, max_len=64)
        paged = PagedGenerationEngine(tiny, slots=2, max_len=64,
                                      block_size=8)
        rows_d = [[dense.prefill(s, p)] for s, p in enumerate(prompts)]
        rows_p = [[paged.prefill(s, p)] for s, p in enumerate(prompts)]
        for _ in range(4):
            sd, sp = dense.decode(), paged.decode()
            for s in range(2):
                rows_d[s].append(int(sd[s]))
                rows_p[s].append(int(sp[s]))
        for s, p in enumerate(prompts):
            want = _reference_tokens(tiny, p, 5)
            assert rows_d[s] == want, f"dense diverged at len {len(p)}"
            assert rows_p[s] == want, f"paged diverged at len {len(p)}"


def test_paged_decode_compiles_exactly_once(tiny):
    """16+ decode steps, a mid-flight slot refill and a prefix-cache-hit
    prefill add ZERO decode recompilations; prefill compiles once per
    SUFFIX bucket."""
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                prefill_buckets=(16,))
    eng.prefill(0, _prompt(0, 5))
    eng.prefill(1, _prompt(1, 12))
    eng.decode()
    assert eng.trace_counts["decode"] == 1
    for _ in range(16):
        eng.decode()
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["prefill"] == {16: 1}
    # refill with a different length in the same bucket + a prefix hit:
    # still the same two executables
    eng.reset_slot(0)
    eng.prefill(0, _prompt(2, 9))
    eng.reset_slot(0)
    eng.prefill(0, list(_prompt(2, 9)) + [3, 4])   # 8-token prefix cached
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 8
    for _ in range(4):
        eng.decode()
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["prefill"] == {16: 1}


# --------------------------------------------------------- prefix cache
def test_prefix_cache_shares_blocks_and_stays_exact(tiny):
    """Two requests with the same system prompt: the second's prefill
    reuses the cached blocks (fewer private allocations, hit recorded)
    and both decode token-exactly; resetting both keeps only the
    cache-held blocks resident."""
    rng = np.random.RandomState(3)
    prefix = rng.randint(0, 1000, 16).tolist()
    p1 = prefix + rng.randint(0, 1000, 5).tolist()
    p2 = prefix + rng.randint(0, 1000, 7).tolist()
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    f1 = eng.prefill(0, p1)
    alloc1 = eng.last_prefill_stats["blocks_allocated"]
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 0
    f2 = eng.prefill(1, p2)
    alloc2 = eng.last_prefill_stats["blocks_allocated"]
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 16
    assert alloc2 < alloc1                     # the shared blocks weren't paid
    rows = [[f1], [f2]]
    for _ in range(4):
        st = eng.decode()
        rows[0].append(int(st[0]))
        rows[1].append(int(st[1]))
    assert rows[0] == _reference_tokens(tiny, np.asarray(p1), 5)
    assert rows[1] == _reference_tokens(tiny, np.asarray(p2), 5)
    eng.reset_slot(0)
    eng.reset_slot(1)
    assert eng.block_pool.in_use == len(eng.prefix_cache)  # cache-held only
    assert eng.block_pool.in_use > 0


def test_prefix_cache_eviction_under_pressure():
    """LRU entries nobody references are evicted to serve an allocation;
    entries still referenced by a live table row survive."""
    pool = BlockPool(num_blocks=6, block_size=4)
    cache = PrefixCache(pool, block_size=4)
    row_a = pool.alloc(2)                  # request A's two full blocks
    cache.insert(list(range(8)), row_a, 8)
    assert pool.refcount(row_a[0]) == 2
    for b in row_a:                        # A retires; cache still holds them
        pool.unref(b)
    assert pool.in_use == 2 and pool.available == 3
    pool.alloc(3)                          # drain the free list
    with pytest.raises(BlockAllocError):
        pool.alloc(1)
    assert cache.evict(1) == 1             # LRU entry freed
    assert len(pool.alloc(1)) == 1
    # a referenced entry is NOT evictable
    ids, n = cache.match(list(range(8)) + [99])
    assert n == 4 and len(ids) == 1        # one block still cached + ref'd
    assert cache.evict(1) == 0


# ---------------------------------------------- preemption (chaos tier)
def test_preemption_under_natural_pressure_is_token_exact(tiny):
    """An oversubscribed pool forces preemption; every request still
    completes with its exact greedy stream (recompute-preemption is
    invisible in the output)."""
    rng = np.random.RandomState(7)
    eng = PagedGenerationEngine(tiny, slots=3, max_len=32, block_size=4,
                                num_blocks=8, enable_prefix_cache=False)
    sched = Scheduler(eng, max_queue=16)
    prompts = [rng.randint(0, 1000, 6) for _ in range(4)]
    hs = [sched.submit(p, max_new_tokens=6) for p in prompts]
    sched.run_until_idle()
    assert sched.counts["serving.preempted"] > 0
    for h, p in zip(hs, prompts):
        assert h.status == "DONE"
        assert h.tokens == _reference_tokens(tiny, p, 6)
    assert eng.block_pool.in_use == 0          # everything returned


def test_injected_alloc_pressure_never_corrupts_neighbors(tiny):
    """ISSUE 6 satellite chaos test: `serving.block_alloc` armed with
    BlockAllocError injects allocation failures the pool could actually
    serve — the scheduler must absorb them (requeue/preempt), every
    request must finish DONE with a token-exact stream, and no blocks
    may leak."""
    rng = np.random.RandomState(11)
    eng = PagedGenerationEngine(tiny, slots=2, max_len=32, block_size=4,
                                enable_prefix_cache=False)
    sched = Scheduler(eng, max_queue=16)
    faults.arm("serving.block_alloc", "raise", exc=BlockAllocError,
               nth=3, max_fires=4, seed=5)
    prompts = [rng.randint(0, 1000, 5) for _ in range(4)]
    hs = [sched.submit(p, max_new_tokens=5) for p in prompts]
    sched.run_until_idle()
    faults.disarm_all()
    for h, p in zip(hs, prompts):
        assert h.status == "DONE", (h.status, h.error)
        assert h.tokens == _reference_tokens(tiny, p, 5)
    assert eng.block_pool.in_use == 0
    # the decision audit log composes with chaos (ISSUE 15): every
    # injected-pressure preemption left a replay-valid decisions.v1
    # record naming its victim, and the tally matches the counter
    from paddle_tpu.observability import decisions as _dec
    recs = sched.decision_records()
    assert _dec.validate_records(recs) == [], _dec.validate_records(recs)
    preempts = [r for r in recs if r["action"] == "preempt"]
    assert len(preempts) == sched.counts["serving.preempted"]
    for r in preempts:
        assert r["outcome"]["victim_request_id"] in {h.request_id
                                                     for h in hs}


def test_growth_pressure_never_evicts_better_class(tiny):
    """SLO invariant: when a batch request needs a decode block and the
    only other occupant is interactive, the batch request yields ITSELF
    — a strictly-better class is never preempted to feed a worse one."""
    rng = np.random.RandomState(21)
    eng = PagedGenerationEngine(tiny, slots=2, max_len=32, block_size=4,
                                num_blocks=4, enable_prefix_cache=False)
    sched = Scheduler(eng, max_queue=8)
    hi = sched.submit(rng.randint(0, 1000, 4), max_new_tokens=8,
                      priority="interactive")
    lo = sched.submit(rng.randint(0, 1000, 4), max_new_tokens=8,
                      priority="batch")
    sched.run_until_idle()
    assert hi.status == "DONE" and lo.status == "DONE"
    assert hi.preempted == 0          # the interactive stream never moved
    assert lo.preempted > 0           # the batch request paid the pressure
    assert hi.tokens == _reference_tokens(
        tiny, np.random.RandomState(21).randint(0, 1000, 4), 8)


# ------------------------------------------------------- SLO scheduling
def test_priority_classes_order_the_queue(tiny):
    """An interactive request submitted LAST overtakes queued batch
    work."""
    eng = PagedGenerationEngine(tiny, slots=1, max_len=32, block_size=8)
    sched = Scheduler(eng, max_queue=16)
    a = sched.submit(_prompt(0, 4), max_new_tokens=2, priority="batch")
    b = sched.submit(_prompt(1, 4), max_new_tokens=2, priority="batch")
    c = sched.submit(_prompt(2, 4), max_new_tokens=2,
                     priority="interactive")
    sched.step()
    # refill happens at step time: the single slot goes to the best
    # (priority, arrival) — the interactive request, despite arriving last
    assert c.status in ("RUNNING", "DONE")
    assert a.status == "QUEUED" and b.status == "QUEUED"
    sched.run_until_idle()
    assert all(h.status == "DONE" for h in (a, b, c))
    assert c.ttft_s < b.ttft_s


def test_load_shedding_past_watermark(tiny):
    """Sheddable classes are failed FAST past the queue watermark with
    terminal SHED; interactive traffic is still admitted."""
    eng = PagedGenerationEngine(tiny, slots=1, max_len=32, block_size=8)
    sched = Scheduler(eng, max_queue=16, shed_watermark=2)
    hs = [sched.submit(_prompt(i, 4), max_new_tokens=2, priority="batch")
          for i in range(2)]
    with pytest.raises(LoadShedError, match="watermark"):
        sched.submit(_prompt(9, 4), max_new_tokens=2, priority="batch")
    ok = sched.submit(_prompt(3, 4), max_new_tokens=2,
                      priority="interactive")
    assert sched.counts["serving.shed"] == 1
    sched.run_until_idle()
    assert all(h.status == "DONE" for h in hs + [ok])


# ----------------------------------------- the load-harness win (tier-1)
def test_load_harness_paged_beats_dense_same_budget(tiny, tmp_path):
    """ISSUE 6 acceptance: at a shared-prefix traffic mix and THE SAME
    KV memory budget, paged+prefix-cache sustains strictly more
    concurrent requests than dense per-slot; p50/p99 TTFT and tokens/sec
    ride the metrics registry (snapshot schema-validated); the decode
    executable compiled exactly once in both configs."""
    traffic = load_harness.TrafficConfig(
        users=8, requests=16, rate_rps=500.0, prefix_pool=2, prefix_len=16,
        suffix_min=2, suffix_max=6, max_new_tokens=4, seed=0)
    budget_slots, max_len, bs = 3, 64, 8
    num_blocks = budget_slots * max_len // bs          # same token budget
    snap = str(tmp_path / "metrics.jsonl")
    dense = load_harness.run_harness(
        tiny, "dense", traffic, slots=budget_slots, max_len=max_len,
        virtual_step_s=0.05)
    paged = load_harness.run_harness(
        tiny, "paged", traffic, slots=8, max_len=max_len, block_size=bs,
        num_blocks=num_blocks, virtual_step_s=0.05, metrics_out=snap)

    # identical KV memory budget, strictly more sustained concurrency
    assert paged["kv_memory_tokens"] == dense["kv_memory_tokens"]
    assert paged["max_concurrent"] > dense["max_concurrent"]
    assert paged["by_status"] == {"DONE": 16}
    assert dense["by_status"] == {"DONE": 16}
    assert paged["prefix_hits"] > 0
    # compile-once holds under the full traffic mix
    assert paged["trace_counts"]["decode"] == 1
    assert dense["trace_counts"]["decode"] == 1
    # TTFT percentiles + throughput exist and are sane
    for s in (paged, dense):
        assert s["ttft_p50_s"] is not None and s["ttft_p50_s"] >= 0
        assert s["ttft_p99_s"] >= s["ttft_p50_s"]
        assert s["tokens_per_s"] > 0
    # the registry snapshot carries the harness gauges + pool/prefix
    # families, and validates against paddle_tpu.metrics.v1
    snaps = metrics_report.load_snapshots(snap)
    assert all(metrics_report.validate_snapshot(r) == [] for r in snaps)
    names = {m["name"] for m in snaps[-1]["metrics"]}
    for expected in ("serving_load_ttft_p50_seconds",
                     "serving_load_ttft_p99_seconds",
                     "serving_load_tokens_per_s",
                     "serving_block_pool_blocks_in_use",
                     "serving_prefix_cache_hits_total",
                     "serving_shed_total", "serving_preempted_total"):
        assert expected in names, f"{expected} missing"


def test_scheduler_jsonl_carries_slo_fields(tiny, tmp_path):
    """The serving metrics JSONL gains priority/preempted/prefix_hit per
    request and still validates against serve_report's schema."""
    metrics = str(tmp_path / "serve_metrics.jsonl")
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    sched = Scheduler(eng, max_queue=8, metrics_path=metrics)
    prefix = list(_prompt(0, 16))
    h1 = sched.submit(prefix + [1, 2], max_new_tokens=2,
                      priority="interactive")
    h2 = sched.submit(prefix + [3, 4, 5], max_new_tokens=2,
                      priority="batch")
    sched.drain()
    assert h1.status == "DONE" and h2.status == "DONE"
    assert h2.prefix_hit                      # shared the 2-block prefix
    records = serve_report.load(metrics)
    assert serve_report.validate_records(records) == []
    summary = serve_report.summarize(records)
    assert summary["prefix_hit_rate"] == 0.5
    assert summary["by_priority"] == {0: 1, 2: 1}
    assert "priority mix" in serve_report.render(summary)
