"""Multi-tenant serving subsystem (ISSUE 17): per-tenant LoRA adapters
gathered by slot inside the one decode executable, the adapter registry
on the ckpt_commit protocol, prefix-cache namespaces with quota-aware
eviction, and token-budget rate limiting ahead of shed/preempt.

The load-bearing properties:
  - a batch MIXING tenants (base rows + adapter rows) runs the ONE
    compiled decode executable — adapters change the program once,
    tenants never do — and the base rows stay bit-identical to an
    adapter-free engine, on the dense, paged, int8, speculative and
    pipeline-parallel engines alike;
  - an engine with NO bank attached passes zero extra executable args:
    adapter-off builds keep their pre-tenancy traces and token streams;
  - adapter loads/swaps are validate-ALL-then-write: a bad payload (or
    the `serving.adapter_swap` chaos site) leaves the tenant's OLD
    adapter and every other tenant serving untouched;
  - the registry rides the crash-safe checkpoint commit: a torn commit
    falls back to the newest verifying version, and when nothing
    verifies the tenant DEGRADES TO BASE WEIGHTS with a warning;
  - prefix-cache namespaces are disjoint key spaces (sharing across
    tenants is impossible, not merely forbidden) and quota-aware
    eviction drains the requester's OWN leaves before touching a
    within-quota foreign namespace;
  - per-tenant token buckets deny ahead of the shed watermark with a
    replayable decisions.v1 `rate_limit` record, and the request
    records carry adapter_id / prefix_namespace / rate_limited for
    tools/serve_report.py's tenancy table.
"""
import os
import sys
import warnings

import numpy as np
import pytest

from paddle_tpu.observability import decisions, faults, metrics
from paddle_tpu.serving import (
    BlockPool, GenerationEngine, PagedGenerationEngine, RateLimitedError,
    QueueFullError, Scheduler, SpeculativeEngine,
)
from paddle_tpu.serving.prefix_cache import PrefixCache, prefix_key
from paddle_tpu.serving.tenancy import (
    AdapterBank, AdapterRegistry, TenancyConfig, TenantSpec, TokenBucket,
    init_adapter_state, lora_delta,
)
from paddle_tpu.text.models import GPTConfig, GPTForGeneration, gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import load_harness  # noqa: E402
import serve_report  # noqa: E402

import paddle_tpu as paddle  # noqa: E402


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


def _small_cfg():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, max_position_embeddings=64,
                     intermediate_size=64)


def _counter(name):
    flat = metrics.flatten_snapshot(metrics.registry().snapshot(),
                                    kinds=("counter",))
    return flat.get(name, 0.0)


def _stream(engine, prompts, n_tokens):
    rows = [[engine.prefill(s, p)] for s, p in enumerate(prompts)]
    for _ in range(n_tokens - 1):
        if hasattr(engine, "ensure_decode_capacity"):
            engine.ensure_decode_capacity()
        step = engine.decode()
        for s in range(len(prompts)):
            rows[s].append(int(step[s]))
    return rows


def _mixed_bank(cfg, rank=4, seed=1):
    """A bank with one tenant ('acme') loaded at scale=1.0 — big enough
    that the delta visibly flips greedy argmaxes on the tiny model."""
    bank = AdapterBank(cfg, n_adapters=3, rank=rank)
    bank.load("acme", init_adapter_state(cfg, rank, seed=seed, scale=1.0))
    return bank


# ------------------------------------------------------- adapter math
def test_lora_delta_gathers_by_slot():
    """Row s of the batch takes slot ids[s]'s delta; a zero row (slot 0,
    the base model) contributes exactly zero."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    a1 = rng.normal(size=(8, 3)).astype(np.float32)
    b1 = rng.normal(size=(3, 5)).astype(np.float32)
    a = jnp.asarray(np.stack([np.zeros_like(a1), a1]))
    b = jnp.asarray(np.stack([np.zeros_like(b1), b1]))
    x = rng.normal(size=(2, 4, 8)).astype(np.float32)
    out = np.asarray(lora_delta(jnp.asarray(x), a, b,
                                jnp.asarray([0, 1], np.int32)))
    assert np.all(out[0] == 0.0)                     # base row: exact zero
    np.testing.assert_allclose(out[1], x[1] @ a1 @ b1, rtol=1e-5)


def test_adapter_bank_pads_lower_ranks_and_folds_alpha():
    """A rank-2 adapter in a rank-8 bank contributes exactly
    x @ A @ B * alpha/r — the zero padding adds nothing."""
    import jax.numpy as jnp
    cfg = _small_cfg()
    bank = AdapterBank(cfg, n_adapters=2, rank=8)
    st = init_adapter_state(cfg, 2, seed=3, scale=0.5, alpha=4.0)
    idx = bank.load("t", st)
    assert idx == 1 and bank.slot_of("t") == 1
    tree = bank.device_tree()
    a, b = tree["layers"][0]["qkv"]
    assert a.shape == (2, cfg.hidden_size, 8)
    x = np.random.default_rng(1).normal(
        size=(1, 1, cfg.hidden_size)).astype(np.float32)
    out = np.asarray(lora_delta(jnp.asarray(x), a, b,
                                jnp.asarray([1], np.int32)))
    ref = x @ st.tensors["layers.0.qkv.a"] \
        @ st.tensors["layers.0.qkv.b"] * (4.0 / 2.0)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)


def test_adapter_bank_load_is_validate_all_then_write():
    """A bad payload (wrong shape / missing key / over-rank) raises
    BEFORE any row is written: the loading tenant's previous adapter and
    every other tenant stay untouched, bit for bit."""
    cfg = _small_cfg()
    bank = AdapterBank(cfg, n_adapters=3, rank=4)
    bank.load("a", init_adapter_state(cfg, 4, seed=1))
    bank.load("b", init_adapter_state(cfg, 4, seed=2))
    before = {k: v.copy() for k, v in bank._a.items()}
    version = bank.version

    bad = init_adapter_state(cfg, 4, seed=3)
    bad.tensors["layers.0.qkv.a"] = np.zeros((7, 4), np.float32)
    with pytest.raises(ValueError, match="shapes"):
        bank.load("a", bad)
    missing = init_adapter_state(cfg, 4, seed=3)
    del missing.tensors["layers.1.fc2.b"]
    with pytest.raises(ValueError, match="missing"):
        bank.load("a", missing)
    with pytest.raises(ValueError, match="exceeds bank"):
        bank.load("a", init_adapter_state(cfg, 8, seed=3))
    # full bank: a THIRD tenant has nowhere to go, existing rows hold
    with pytest.raises(ValueError, match="full"):
        bank.load("c", init_adapter_state(cfg, 4, seed=3))

    assert bank.version == version
    for k, v in before.items():
        np.testing.assert_array_equal(bank._a[k], v)
    # drop frees the slot for reuse and zeroes the row
    idx = bank.drop("a")
    assert bank.slot_of("a") == 0
    assert np.all(bank._a[(0, "qkv")][idx] == 0.0)
    assert bank.load("c", init_adapter_state(cfg, 4, seed=3)) == idx


# ------------------------------------------- engine compose + compile-once
def test_dense_mixed_tenant_batch_one_trace(tiny):
    """One batch, two tenants (base + acme): ONE decode trace covers the
    mix, the base row is bit-identical to the layer-level oracle, and
    the adapter row diverges — per-tenant behavior with zero per-tenant
    executables."""
    prompts = [_prompt(0, 5), _prompt(1, 9)]
    eng = GenerationEngine(tiny, slots=2, max_len=64)
    bank = _mixed_bank(tiny.cfg)
    eng.attach_adapters(bank)
    firsts = [eng.prefill(s, p) for s, p in enumerate(prompts)]
    eng.set_slot_adapter(0, 0)
    eng.set_slot_adapter(1, bank.slot_of("acme"))
    rows = [[f] for f in firsts]
    for _ in range(7):
        step = eng.decode()
        for s in range(2):
            rows[s].append(int(step[s]))
    assert eng.trace_counts["decode"] == 1          # the mix is data
    assert rows[0] == _reference_tokens(tiny, prompts[0], 8)
    assert rows[1] != _reference_tokens(tiny, prompts[1], 8)
    # rebinding the adapter row back to base mid-flight is a host write,
    # not a recompile
    eng.set_slot_adapter(1, 0)
    eng.decode()
    assert eng.trace_counts["decode"] == 1


def test_adapter_off_engine_keeps_pretenancy_signature(tiny):
    """No bank attached -> NOTHING extra rides the executables (the
    rng-args convention): the stream is the oracle's and the adapter
    plumbing costs adapter-free builds nothing. An attached bank whose
    slots all point at base (ids == 0) adds an exact-zero delta — the
    tokens still match the oracle bit for bit."""
    p = _prompt(2, 7)
    off = GenerationEngine(tiny, slots=1, max_len=64)
    assert off._adapter_args() == ()
    assert _stream(off, [p], 6)[0] == _reference_tokens(tiny, p, 6)

    allbase = GenerationEngine(tiny, slots=1, max_len=64)
    allbase.attach_adapters(_mixed_bank(tiny.cfg))   # nobody bound to it
    assert len(allbase._adapter_args()) == 2
    assert _stream(allbase, [p], 6)[0] == _reference_tokens(tiny, p, 6)
    assert allbase.trace_counts["decode"] == 1


def test_paged_mixed_tenant_batch_one_trace(tiny):
    """Same contract on the paged engine: one decode trace over the
    block tables AND the adapter gather; base row token-exact."""
    prompts = [_prompt(3, 6), _prompt(4, 11)]
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    bank = _mixed_bank(tiny.cfg)
    eng.attach_adapters(bank)
    firsts = [eng.prefill(s, p) for s, p in enumerate(prompts)]
    eng.set_slot_adapter(1, bank.slot_of("acme"))
    rows = [[f] for f in firsts]
    for _ in range(7):
        step = eng.decode()
        for s in range(2):
            rows[s].append(int(step[s]))
    assert eng.trace_counts["decode"] == 1
    assert rows[0] == _reference_tokens(tiny, prompts[0], 8)
    assert rows[1] != _reference_tokens(tiny, prompts[1], 8)


def test_int8_adapter_composes_as_float_delta(tiny):
    """Adapters over the int8 weight path: the delta rides in float on
    top of the quantized base matmul. The base row of a mixed batch is
    bit-identical to an adapter-free int8 engine; the adapter row
    diverges from it. One decode trace either way."""
    prompts = [_prompt(5, 6), _prompt(6, 9)]
    base = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                 weight_dtype="int8")
    rows_base = _stream(base, prompts, 7)

    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                weight_dtype="int8")
    bank = _mixed_bank(tiny.cfg)
    eng.attach_adapters(bank)
    firsts = [eng.prefill(s, p) for s, p in enumerate(prompts)]
    eng.set_slot_adapter(1, bank.slot_of("acme"))
    rows = [[f] for f in firsts]
    for _ in range(6):
        step = eng.decode()
        for s in range(2):
            rows[s].append(int(step[s]))
    assert rows[0] == rows_base[0]
    assert rows[1] != rows_base[1]
    assert eng.trace_counts["decode"] == 1


def test_spec_adapter_stream_matches_one_token_loop(tiny):
    """Speculative decode under adapters: the verify window evaluates
    the delta over all gamma+1 positions, so the accepted stream stays
    bit-identical to the one-token adapter loop — and the spec compile
    discipline (one draft, one verify, no one-token path) holds."""
    prompts = [_prompt(7, 9), _prompt(8, 13)]
    plain = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    bank = _mixed_bank(tiny.cfg)
    plain.attach_adapters(bank)
    rows_p = [[plain.prefill(s, p)] for s, p in enumerate(prompts)]
    plain.set_slot_adapter(1, bank.slot_of("acme"))
    for _ in range(9):
        st = plain.decode()
        for s in range(2):
            rows_p[s].append(int(st[s]))

    spec = SpeculativeEngine(tiny, slots=2, max_len=64, block_size=8,
                             gamma=3, draft_layers=1)
    spec.attach_adapters(_mixed_bank(tiny.cfg))
    rows_s = [[spec.prefill(s, p)] for s, p in enumerate(prompts)]
    spec.set_slot_adapter(1, spec.adapter_bank.slot_of("acme"))
    while min(len(r) for r in rows_s) < 10:
        toks, n_emit = spec.decode_many()
        for s in range(2):
            for j in range(int(n_emit[s])):
                rows_s[s].append(int(toks[s, j]))
    assert [r[:10] for r in rows_s] == rows_p
    assert spec.trace_counts["spec_verify"] == 1
    assert spec.trace_counts["decode"] == 0


def test_pp_adapter_stream_matches_single_device(tiny):
    """Pipeline-parallel decode under adapters: each stage gathers its
    own layer slice's deltas, and the ring's stream equals the
    single-device paged adapter stream token for token."""
    from paddle_tpu.serving.distributed import (
        PipelineParallelEngineConfig, PipelineParallelPagedEngine)
    prompts = [_prompt(9, 7), _prompt(10, 10)]
    ref = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    ref.attach_adapters(_mixed_bank(tiny.cfg))
    rows_ref = [[ref.prefill(s, p)] for s, p in enumerate(prompts)]
    ref.set_slot_adapter(1, ref.adapter_bank.slot_of("acme"))
    for _ in range(6):
        ref.ensure_decode_capacity()
        st = ref.decode()
        for s in range(2):
            rows_ref[s].append(int(st[s]))

    pp = PipelineParallelPagedEngine(
        tiny, PipelineParallelEngineConfig(pp=2, slots=2, max_len=64,
                                           block_size=8))
    pp.attach_adapters(_mixed_bank(tiny.cfg))
    rows_pp = [[pp.prefill(s, p)] for s, p in enumerate(prompts)]
    pp.set_slot_adapter(1, pp.adapter_bank.slot_of("acme"))
    for _ in range(6):
        pp.ensure_decode_capacity()
        st = pp.decode()
        for s in range(2):
            rows_pp[s].append(int(st[s]))
    assert rows_pp == rows_ref


# ----------------------------------------------------------- registry
def test_registry_publish_resolve_roundtrip(tmp_path):
    cfg = _small_cfg()
    reg = AdapterRegistry(str(tmp_path))
    st = init_adapter_state(cfg, 2, seed=3, alpha=4.0)
    path = reg.publish("acme", st)
    assert os.path.isdir(path) and "adapter-000001" in path
    out = reg.resolve("acme")
    assert out.rank == 2 and out.alpha == 4.0
    for k, v in st.tensors.items():
        np.testing.assert_array_equal(out.tensors[k], v)
    # a second publish wins; an unknown tenant is base weights, silently
    st2 = init_adapter_state(cfg, 2, seed=9)
    reg.publish("acme", st2)
    np.testing.assert_array_equal(
        reg.resolve("acme").tensors["layers.0.qkv.a"],
        st2.tensors["layers.0.qkv.a"])
    assert reg.resolve("nobody") is None


def test_registry_torn_commit_degrades_to_base(tmp_path):
    """The crash-safety satellite: a torn newest commit falls back to
    the previous verifying version; with EVERY version torn the tenant
    degrades to base weights under a RuntimeWarning — never a crash,
    never a stale half-written delta."""
    import glob
    cfg = _small_cfg()
    reg = AdapterRegistry(str(tmp_path))
    st1 = init_adapter_state(cfg, 2, seed=1)
    reg.publish("acme", st1)
    p2 = reg.publish("acme", init_adapter_state(cfg, 2, seed=2))
    # tear v2 behind its manifest's back: truncate one tensor file
    npy = sorted(glob.glob(os.path.join(p2, "*.npy")))[0]
    with open(npy, "r+b") as f:
        f.truncate(os.path.getsize(npy) // 2)
    with pytest.warns(RuntimeWarning, match="falling back"):
        out = reg.resolve("acme")
    np.testing.assert_array_equal(out.tensors["layers.0.qkv.a"],
                                  st1.tensors["layers.0.qkv.a"])
    # tear every version: degradation to base, loudly
    for npy in glob.glob(os.path.join(tmp_path, "acme", "*", "*.npy")):
        with open(npy, "r+b") as f:
            f.truncate(0)
    with pytest.warns(RuntimeWarning, match="serving base weights"):
        assert reg.resolve("acme") is None


# ------------------------------------------------------- adapter swap
def test_scheduler_adapter_swap_between_steps(tiny):
    """schedule_adapter_swap applies at the top of the next step; the
    tenant's later requests decode under the new adapter (adapter_id on
    the handle) while base traffic stays oracle-exact."""
    eng = GenerationEngine(tiny, slots=2, max_len=64)
    eng.attach_adapters(AdapterBank(tiny.cfg, n_adapters=3, rank=4))
    sched = Scheduler(eng, max_queue=8)
    ev = sched.schedule_adapter_swap(
        "acme", init_adapter_state(tiny.cfg, 4, seed=1, scale=1.0))
    sched.step()
    assert ev.is_set() and ev.swap_result["ok"]
    assert sched.last_adapter_swap["slot"] == 1
    assert eng.adapter_bank.slot_of("acme") == 1

    pa, pb = _prompt(11, 5), _prompt(12, 8)
    ha = sched.submit(pa, max_new_tokens=5, tenant="acme")
    hb = sched.submit(pb, max_new_tokens=5)
    sched.run_until_idle()
    assert ha.adapter_id == "acme"
    assert hb.adapter_id is None
    assert ha.tokens != _reference_tokens(tiny, pa, 5)
    assert hb.tokens == _reference_tokens(tiny, pb, 5)
    assert eng.trace_counts["decode"] == 1


def test_adapter_swap_chaos_old_adapter_keeps_serving(tiny):
    """The `serving.adapter_swap` chaos site: a swap that fails mid-arm
    is ATOMIC — the tenant's old adapter keeps serving bit-identically,
    other tenants are untouched, and the failure lands in
    last_adapter_swap + serving_adapter_swaps_total{status=failed}."""
    eng = GenerationEngine(tiny, slots=2, max_len=64)
    eng.attach_adapters(AdapterBank(tiny.cfg, n_adapters=3, rank=4))
    sched = Scheduler(eng, max_queue=8)
    sched.schedule_adapter_swap(
        "acme", init_adapter_state(tiny.cfg, 4, seed=1, scale=1.0))
    sched.schedule_adapter_swap(
        "beta", init_adapter_state(tiny.cfg, 4, seed=2, scale=1.0))
    sched.step()
    pa, pb = _prompt(13, 6), _prompt(14, 7)

    def run(tenant, p):
        h = sched.submit(p, max_new_tokens=5, tenant=tenant)
        sched.run_until_idle()
        return list(h.tokens)

    before_a, before_b = run("acme", pa), run("beta", pb)
    failed0 = _counter("serving_adapter_swaps_total{status=failed}")

    faults.arm("serving.adapter_swap", "raise")
    ev = sched.schedule_adapter_swap(
        "acme", init_adapter_state(tiny.cfg, 4, seed=9, scale=1.0))
    sched.step()
    faults.disarm_all()
    assert ev.swap_result["ok"] is False
    assert "FaultInjected" in ev.swap_result["error"]
    assert sched.last_adapter_swap["ok"] is False
    assert _counter("serving_adapter_swaps_total{status=failed}") == \
        failed0 + 1
    # the old adapter (and the other tenant's) serve bit-identically
    assert run("acme", pa) == before_a
    assert run("beta", pb) == before_b
    # a bank-validation failure takes the same atomic path, no chaos
    bad = init_adapter_state(tiny.cfg, 4, seed=9, scale=1.0)
    del bad.tensors["layers.0.qkv.a"]
    ev2 = sched.schedule_adapter_swap("acme", bad)
    sched.step()
    assert ev2.swap_result["ok"] is False
    assert run("acme", pa) == before_a


# ------------------------------------------------- prefix namespaces
def test_prefix_key_namespace_salting():
    toks = list(range(16))
    assert prefix_key(toks) == prefix_key(toks, None)   # legacy keys
    assert prefix_key(toks, "a") != prefix_key(toks)
    assert prefix_key(toks, "a") != prefix_key(toks, "b")
    assert prefix_key(toks, "a") == prefix_key(toks, "a")


def _one_block_entry(cache, pool, seed, namespace):
    """Insert one single-block chain under `namespace`, cache-owned only
    (refcount 1) so it is eviction-eligible."""
    bs = cache.block_size
    prompt = list(_prompt(seed, bs + 1))
    row = pool.alloc(1)
    cache.insert(prompt, row, bs, namespace=namespace)
    pool.unref(row[0])
    return prompt


def test_namespace_disjoint_and_quota_eviction_order():
    """Cross-namespace sharing is impossible (disjoint key spaces); a
    hot tenant's pressure drains its OWN namespace's LRU leaves first
    and cannot touch a foreign namespace sitting within its quota —
    over-quota foreigners are drained only down to their quota."""
    pool = BlockPool(num_blocks=32, block_size=4)
    cache = PrefixCache(pool, 4)
    cache.set_quotas({"a": 2, "b": 2})

    shared = _one_block_entry(cache, pool, 20, "a")
    # same tokens, other namespace / unscoped: no hit — disjoint keys
    assert cache.match(shared, namespace="a")[1] == 4
    assert cache.match(shared, namespace="b") == ([], 0)
    assert cache.match(shared) == ([], 0)

    _one_block_entry(cache, pool, 21, "a")
    for seed in (22, 23, 24):                      # b runs over quota
        _one_block_entry(cache, pool, seed, "b")
    assert cache.resident("a") == 2 and cache.resident("b") == 3

    # b's pressure: own LRU leaves first — a untouched
    assert cache.evict(2, requester="b") == 2
    assert cache.resident("b") == 1 and cache.resident("a") == 2
    # b drained; a holds its quota: protected from b's further pressure
    assert cache.evict(4, requester="b") == 1      # only b's last entry
    assert cache.resident("a") == 2 and cache.resident("b") == 0
    # a goes OVER quota: foreign pressure may drain it — but only down
    # to its quota, re-checked per eviction
    _one_block_entry(cache, pool, 25, "a")
    assert cache.resident("a") == 3
    assert cache.evict(4, requester="b") == 1
    assert cache.resident("a") == 2
    ev = cache.namespace_evictions()
    assert ev.get("b") == 3 and ev.get("a") == 1
    assert cache.namespace_residents() == {"a": 2}


def test_engine_prefill_namespaces_isolate_tenants(tiny):
    """Through the paged engine: the same system prompt prefilled under
    two namespaces shares within a namespace (fewer private blocks) and
    never across — a tenant cannot warm another's cache."""
    pool_blocks, bs = 24, 8
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=bs,
                                num_blocks=pool_blocks)
    prefix = list(_prompt(30, 2 * bs))
    prompt = prefix + [1, 2, 3]
    eng.prefill(0, prompt, namespace="a")
    used_first = eng.block_pool.in_use
    # same namespace: the chain is referenced, not re-allocated
    eng.prefill(1, prompt, namespace="a")
    same_ns_new = eng.block_pool.in_use - used_first
    eng.reset_slot(1)
    # foreign namespace: full private re-allocation, no sharing
    eng.prefill(1, prompt, namespace="b")
    foreign_new = eng.block_pool.in_use - used_first
    assert same_ns_new < foreign_new
    assert eng.prefix_cache.resident("a") > 0


# ------------------------------------------------------ rate limiting
def test_token_bucket_is_deterministic_under_clock():
    t = [0.0]
    b = TokenBucket(rate=10.0, burst=20.0, clock=lambda: t[0])
    assert b.available() == 20.0
    b.take(15.0)
    assert b.available() == 5.0
    t[0] = 1.0                                   # +10 tokens
    assert b.available() == 15.0
    t[0] = 10.0                                  # clamped at burst
    assert b.available() == 20.0


def test_rate_limit_ahead_of_shed_with_replayable_decisions(tiny):
    """Per-tenant token buckets deny BEFORE queue/shed state matters:
    the denial is a RateLimitedError (a QueueFullError, so existing
    backpressure handling keeps working), ticks
    serving_rate_limited_total{tenant}, and leaves a decisions.v1
    `rate_limit` record whose recorded inputs replay to the same
    verdict. Refill re-admits; other tenants are never limited."""
    t = [0.0]
    eng = GenerationEngine(tiny, slots=1, max_len=64)
    tenancy = TenancyConfig(tenants={
        "acme": TenantSpec(rate_tokens_per_s=10.0, burst_tokens=20.0)})
    sched = Scheduler(eng, max_queue=8, clock=lambda: t[0],
                      tenancy=tenancy)
    p = _prompt(40, 8)                            # cost 8 + 2 = 10
    limited0 = _counter("serving_rate_limited_total{tenant=acme}")
    h1 = sched.submit(p, max_new_tokens=2, tenant="acme")
    h2 = sched.submit(_prompt(41, 8), max_new_tokens=2, tenant="acme")
    with pytest.raises(RateLimitedError, match="rate limited"):
        sched.submit(_prompt(42, 8), max_new_tokens=2, tenant="acme")
    assert _counter("serving_rate_limited_total{tenant=acme}") == \
        limited0 + 1
    # an untracked tenant rides free, whatever the bucket state
    h3 = sched.submit(_prompt(43, 8), max_new_tokens=2)
    # the denial is a QueueFullError subclass — legacy handlers catch it
    with pytest.raises(QueueFullError):
        sched.submit(_prompt(44, 8), max_new_tokens=2, tenant="acme")
    t[0] = 1.0                                    # refill 10 tokens
    h4 = sched.submit(_prompt(45, 8), max_new_tokens=2, tenant="acme")
    while any(not h.done() for h in (h1, h2, h3, h4)):
        sched.step()
        t[0] += 0.001
    recs = sched.decision_records()
    rl = [r for r in recs if r["action"] == "rate_limit"]
    assert len(rl) == 2
    assert rl[0]["inputs"]["tenant"] == "acme"
    assert rl[0]["inputs"]["cost"] == 10
    assert decisions.replay_rate_limit(rl[0]["inputs"]) is not None
    assert decisions.validate_records(recs) == []


# ------------------------------------------- isolation under a burst
def test_neighbour_burst_leaves_tenant_ttft_and_namespace_intact(tiny):
    """Two tenants share ONE paged engine: tenant A carries an adapter,
    a token bucket sized to its steady rate and a namespace quota;
    tenant B is the well-behaved neighbour. The same seeded trace is
    replayed on the virtual clock without and with A bursting 6x. Under
    the burst B's p99 TTFT stays within 2x its own baseline (floor 0.25
    virtual seconds), B's namespace loses no block to A's pressure, the
    limiter (not B) absorbs A's excess, and the mixed-tenant adapter
    batch still decodes through one executable."""
    block, num_blocks = 8, 16
    rate_a, rate_b = 400.0, 100.0
    mix = dict(users=8, requests=32, prefix_len=2 * block, suffix_max=8,
               max_new_tokens=4, seed=0,
               tenants={"tenant_a": rate_a, "tenant_b": rate_b})
    # A's bucket refills at its steady rate and holds ten requests of
    # slack: baseline traffic flows, the burst overdraws and is denied
    cost = mix["prefix_len"] + mix["suffix_max"] + mix["max_new_tokens"]
    quota = (num_blocks - 1) // 2
    tenancy = load_harness.build_tenancy(
        ("tenant_a", "tenant_b"), adapters_arg="tenant_a:4",
        quotas_arg=f"tenant_a:{quota},tenant_b:{quota}",
        rates_arg=f"tenant_a:{rate_a * cost:.0f}/{10 * cost:.0f}")
    arms = {}
    for arm, burst in (("baseline", None),
                       ("burst", {"tenant": "tenant_a", "t0": 0.0,
                                  "dur_s": 0.05, "mult": 6.0})):
        arms[arm] = load_harness.run_harness(
            tiny, "paged", load_harness.TrafficConfig(burst=burst, **mix),
            slots=4, max_len=64, block_size=block, num_blocks=num_blocks,
            virtual_step_s=0.01, tenancy=tenancy)
    base_b = arms["baseline"]["tenants"]["tenant_b"]
    burst_b = arms["burst"]["tenants"]["tenant_b"]
    burst_a = arms["burst"]["tenants"]["tenant_a"]
    assert burst_b["requests"] > 0 and burst_b["ttft_p99_s"] is not None
    gate_s = max(0.25, 2.0 * (base_b["ttft_p99_s"] or 0.0))
    assert burst_b["ttft_p99_s"] <= gate_s, (burst_b, base_b)
    assert burst_b.get("ns_blocks_evicted", 0) == 0, burst_b
    assert burst_a.get("rate_limited", 0) > 0, burst_a
    assert arms["burst"]["trace_counts"]["decode"] == 1


# ------------------------------------------------- serve_report plane
def test_serve_report_carries_tenancy_fields(tiny, tmp_path):
    """The request records gain adapter_id / prefix_namespace /
    rate_limited (all optional: pre-tenancy artifacts stay valid), and
    serve_report renders the per-tenant table off them."""
    metrics_path = str(tmp_path / "serve_metrics.jsonl")
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    eng.attach_adapters(_mixed_bank(tiny.cfg))
    t = [0.0]
    tenancy = TenancyConfig(tenants={
        "acme": TenantSpec(namespace="ns-acme", rate_tokens_per_s=1.0,
                           burst_tokens=12.0)})
    sched = Scheduler(eng, max_queue=8, clock=lambda: t[0],
                      tenancy=tenancy, metrics_path=metrics_path)
    h1 = sched.submit(_prompt(50, 8), max_new_tokens=2, tenant="acme")
    with pytest.raises(RateLimitedError):
        sched.submit(_prompt(51, 8), max_new_tokens=2, tenant="acme")
    h2 = sched.submit(_prompt(52, 6), max_new_tokens=2)
    while not (h1.done() and h2.done()):
        sched.step()
        t[0] += 0.001
    assert h1.prefix_namespace == "ns-acme"       # from the tenancy table
    records = serve_report.load(metrics_path)
    assert serve_report.validate_records(records) == []
    summary = serve_report.summarize(records)
    tt = summary["tenancy"]
    assert tt is not None
    acme = tt["acme"]
    assert acme["adapter_requests"] == 1
    assert acme["adapters"] == {"acme": 1}
    assert acme["rate_limited"] == 1
    assert acme["namespaces"] == ["ns-acme"]
    assert "multi-tenant serving" in serve_report.render(summary)
    # a pre-tenancy artifact (no new fields anywhere) has no table
    plain = [r for r in records
             if not any(k in r for k in ("adapter_id", "prefix_namespace",
                                         "rate_limited"))]
    assert serve_report.summarize(plain)["tenancy"] is None


def test_tenancy_config_defaults_to_pretenancy_behavior():
    """A TenancyConfig naming no limits is inert: no buckets, no quotas,
    namespace None — the pre-tenancy stack, exactly."""
    cfg = TenancyConfig(tenants={"x": TenantSpec()})
    assert cfg.buckets(lambda: 0.0) == {}
    assert cfg.quotas() == {}
    assert cfg.namespace_of("x") is None
    assert cfg.namespace_of("unknown") is None
    assert cfg.adapter_slots == 2
