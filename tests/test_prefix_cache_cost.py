"""What a prefill's prefix-cache bookkeeping costs on the host (ISSUE 33):
chain keys from one running sha1, byte for byte those of `prefix_key`; a
prompt hashed once a prefill; an eviction that visits leaves and not every
entry, with the victims and the order of the sweeps it replaced (kept below
as the reference); and the two spans that carry the counters."""
import random
from itertools import islice

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import profiler
from paddle_tpu.observability import kvledger as _kvl
from paddle_tpu.observability.flight_recorder import SpanLog
from paddle_tpu.serving import (PagedEngineConfig, PagedGenerationEngine,
                                Scheduler, ServingConfig)
from paddle_tpu.serving.blocks import BlockPool
from paddle_tpu.serving.prefix_cache import (DEFAULT_NAMESPACE, PrefixCache,
                                             chain_keys, prefix_key)
from paddle_tpu.text.models import gpt_tiny

P = "serving::"
BLOCK = 8


# ------------------------------------------------------------ (a) the keys

@pytest.mark.parametrize("namespace", [None, "a"])
@pytest.mark.parametrize("n", [1, 15, 16, 17, 736, 2048])
def test_chain_keys_are_prefix_keys_byte_for_byte(n, namespace):
    rng = np.random.RandomState(n)
    toks = rng.randint(0, 50000, n).tolist()
    # negative ids and ids past 32 bits, where the prompt is long enough
    toks[0] = -7
    toks[n // 2] = 2**31 + 5
    toks[-1] = -2**40 if n > 1 else toks[-1]
    want = [prefix_key(toks[:(k + 1) * 16], namespace)
            for k in range(n // 16)]
    assert list(chain_keys(toks, 16, namespace)) == want
    assert list(islice(chain_keys(np.asarray(toks), 16, namespace), 3)) \
        == want[:3]
    if namespace is None and n >= 16:
        # the pre-tenancy scheme: a bare sha1 over little-endian int64
        import hashlib
        assert want[0] == hashlib.sha1(
            np.asarray(toks[:16], "<i8").tobytes()).hexdigest()


def test_chain_keys_hash_only_what_is_asked_for():
    pool = BlockPool(num_blocks=8, block_size=16)
    cache = PrefixCache(pool, 16)
    chain = cache.chain(list(range(2048)))
    assert cache.hashed_tokens == 0            # nothing until a key is read
    assert chain.key(2) == prefix_key(list(range(48)))
    assert cache.hashed_tokens == 48
    chain.key(1)
    assert cache.hashed_tokens == 48           # kept, not hashed again


# ------------------------------------------------- (b) once a prefill

def _prefill_cost(cache, pool, prompt):
    """match + alloc + insert of one prompt the way `engine.prefill` does
    them; returns the token updates it cost."""
    before = cache.hashed_tokens
    chain = cache.chain(prompt)
    ids, n = cache.match(prompt, chain=chain)
    bs = cache.block_size
    row = ids + pool.alloc(-(-len(prompt) // bs) - len(ids))
    cache.insert(prompt, row, len(prompt) // bs * bs, chain=chain)
    for b in row:
        pool.unref(b)
    return cache.hashed_tokens - before


def test_a_2048_token_prompt_costs_2048_token_updates_not_131000():
    pool = BlockPool(num_blocks=300, block_size=16)
    cache = PrefixCache(pool, 16)
    prompt = np.random.RandomState(0).randint(0, 50000, 2048)
    assert _prefill_cost(cache, pool, prompt) == 2048      # cold: all inserted
    assert _prefill_cost(cache, pool, prompt) == 2048      # warm: all matched
    assert len(cache) == 128
    # without the shared chain each of the two hashes what it looks up
    before = cache.hashed_tokens
    cache.insert(prompt, [0] * 128, 2048)      # garbage row: nothing looked up
    assert cache.hashed_tokens == before


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(0)
    tiny = gpt_tiny()
    tiny.eval()
    return tiny


def paged_engine(model, **kw):
    cfg = dict(slots=2, max_len=64, block_size=BLOCK,
               prefill_buckets=(16, 32, 64))
    cfg.update(kw)
    return PagedGenerationEngine(model, PagedEngineConfig(**cfg))


@pytest.mark.parametrize("n", [5, 16, 41])
def test_engine_prefill_hashes_its_prompt_at_most_once(model, n):
    engine = paged_engine(model)
    cache = engine.prefix_cache
    prompt = np.random.RandomState(n).randint(1, 100, n)
    engine.prefill(0, prompt)
    assert cache.hashed_tokens == n // BLOCK * BLOCK <= n
    assert len(cache) == n // BLOCK
    # a second request with the same prompt: the hit path, once again
    before = cache.hashed_tokens
    engine.prefill(1, prompt)
    assert engine.last_prefill_stats["prefix_hit_tokens"] == \
        (n - 1) // BLOCK * BLOCK
    assert cache.hashed_tokens - before == n // BLOCK * BLOCK


# ------------------------------------------- (c) the eviction's oracle

class SweepingCache(PrefixCache):
    """The cache with the eviction pass as it was before ISSUE 33: every
    entry sorted by last use and walked, once more for each sweep that
    freed something. The body is verbatim but for its comments, the
    process-wide metric (left to the cache under test) and the test's own
    count of entries looked at. The reference for victims and order."""

    def _evict_pass(self, n_blocks, eligible):
        freed = 0
        progress = True
        while freed < n_blocks and progress:
            progress = False
            for key in sorted(self._lru, key=self._lru.get):
                self.swept = getattr(self, "swept", 0) + 1   # (test's count)
                if freed >= n_blocks:
                    break
                ns = self._ns.get(key)
                if not eligible(ns):
                    continue
                blk = self._entries.get(key)
                if blk is None or self.pool.refcount(blk) != 1 \
                        or self._children.get(key, 0) > 0:
                    continue
                if self._tier is not None:
                    self._tier.demote(key, ns, self._parent.get(key), blk)
                if self._ledger is not None:
                    self._ledger.cache_evict((blk,))
                    with _kvl.origin_scope("prefix_cache.evict"):
                        self.pool.unref(blk)
                else:
                    self.pool.unref(blk)
                parent = self._parent.pop(key, None)
                if parent is not None and parent in self._children:
                    self._children[parent] -= 1
                    if self._children[parent] <= 0:
                        del self._children[parent]
                self._children.pop(key, None)
                del self._entries[key]
                del self._lru[key]
                self._ns.pop(key, None)
                self._resident[ns] = self._resident.get(ns, 1) - 1
                label = ns if ns is not None else DEFAULT_NAMESPACE
                self._ns_evicted[label] = self._ns_evicted.get(label, 0) + 1
                freed += 1
                progress = True     # a freed leaf may expose its parent
        return freed


class FakePool:
    """Refcounts and a free list, and the order in which blocks came back."""

    def __init__(self, num_blocks):
        self._free = list(range(num_blocks - 1, 0, -1))
        self._refs = {}
        self.freed = []

    @property
    def available(self):
        return len(self._free)

    def refcount(self, b):
        return self._refs.get(b, 0)

    def refcounts(self, blocks):
        return np.array([self.refcount(b) for b in blocks], np.int32)

    def alloc(self, n):
        assert n <= len(self._free)
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def ref(self, b):
        assert self._refs[b] >= 1
        self._refs[b] += 1

    def unref(self, b):
        self._refs[b] -= 1
        if self._refs[b] == 0:
            del self._refs[b]
            self._free.append(b)
            self.freed.append(b)


class Twin:
    """One cache and its pool, driven by the same script as its twin."""
    BS = 4

    def __init__(self, cls, quotas):
        self.pool = FakePool(96)
        self.cache = cls(self.pool, self.BS)
        self.cache.set_quotas(quotas)
        self.held = []                 # rows of requests still running

    def request(self, prompt, ns):
        """A prefill: match, allocate the rest (evicting under pressure),
        insert; the request keeps its row."""
        bs = self.BS
        ids, _ = self.cache.match(prompt, namespace=ns)
        need = -(-len(prompt) // bs) - len(ids)
        short = need - self.pool.available
        if short > 0 and self.cache.evict(short, requester=ns) < short:
            for b in ids:
                self.pool.unref(b)
            return False
        row = ids + self.pool.alloc(need)
        self.cache.insert(prompt, row, len(prompt) // bs * bs, namespace=ns)
        self.held.append(row)
        return True

    def release(self, i):
        for b in self.held.pop(i):
            self.pool.unref(b)

    def state(self):
        c = self.cache
        return (c._entries, c._parent, c._children, c._ns, c._resident,
                c.namespace_evictions(), c.evictable(), len(c),
                self.pool.freed, self.pool._free)


@pytest.mark.parametrize("quotas", [{}, {"a": 6, "b": 3}],
                         ids=["no quotas", "quotas"])
def test_eviction_takes_the_victims_of_the_sweeps_in_their_order(quotas):
    """100 seeded scripts of prefill / release / match / evict over three
    namespaces, each run on the cache and on its sweeping reference: the
    same blocks come back in the same order, and every book agrees after
    every operation."""
    evictions = 0
    for seed in range(100):
        rng = random.Random(seed)
        new, ref = Twin(PrefixCache, quotas), Twin(SweepingCache, quotas)
        prompts = []
        for _ in range(120):
            op = rng.random()
            if op < 0.45:
                ns = rng.choice([None, "a", "b"])
                base = rng.choice(prompts)[:rng.randrange(0, 20)] \
                    if prompts and rng.random() < 0.5 else []
                prompt = base + [rng.randrange(1000)
                                 for _ in range(rng.randrange(1, 26))]
                prompts.append(prompt)
                assert new.request(prompt, ns) == ref.request(prompt, ns)
            elif op < 0.75 and new.held:
                i = rng.randrange(len(new.held))
                new.release(i), ref.release(i)
            elif op < 0.85 and prompts:
                # a lookup alone: touches the chain, holds it for a while
                prompt, ns = rng.choice(prompts), rng.choice([None, "a", "b"])
                got = [t.cache.match(prompt, namespace=ns) for t in (new, ref)]
                assert got[0] == got[1]
                new.held.append(got[0][0]), ref.held.append(got[1][0])
            else:
                n, who = rng.randrange(1, 12), rng.choice([None, "a", "b"])
                freed = new.cache.evict(n, requester=who)
                assert freed == ref.cache.evict(n, requester=who)
                evictions += freed
            assert new.state() == ref.state()
            c = new.cache
            assert c._leaves == {k for k in c._entries
                                 if not c._children.get(k)}
            assert c._lru == ref.cache._lru
    assert evictions > 1500


# ------------------------------------------------ (d) what a sweep visits

@pytest.mark.parametrize("cls,most", [(PrefixCache, 4 * 46),
                                      (SweepingCache, None)])
def test_evicting_46_of_ten_chains_visits_leaves_not_every_entry(cls, most):
    pool = FakePool(10 * 46 + 1)
    cache = cls(pool, 16)
    rng = np.random.RandomState(0)
    rows = []
    for _ in range(10):
        prompt = rng.randint(0, 50000, 46 * 16)
        row = pool.alloc(46)
        cache.insert(prompt, row, 46 * 16)
        rows.append(row)
    for row in rows:                   # the requests end: cache-only blocks
        for b in row:
            pool.unref(b)
    assert cache.evictable() == 460 and pool.freed == []
    assert cache.evict(46) == 46
    # leaf-first by last use: the chains' tails, oldest chain first, then
    # the blocks before them: four rounds of ten and six of the fifth
    want = [rows[c][45 - r] for r in range(5) for c in range(10)][:46]
    assert pool.freed == want
    assert len(cache) == 414 and cache.evictable() == 414
    if most is None:
        assert cache.swept > 2000      # five sweeps over ~450 entries
    else:
        assert 46 <= cache.evict_visited <= most


# --------------------------------------------------------- (e) the spans

def logged():
    return [dict(zip(SpanLog.FIELDS, r))
            for r in profiler.span_log().spans()]


@pytest.fixture(scope="module")
def served(model):
    """Six requests through two slots and a pool that holds four prompts,
    so that later admissions evict."""
    assert not profiler._tracer.enabled and profiler._tracer.ring is None
    profiler.span_log().clear()
    engine = paged_engine(model, num_blocks=17)
    sched = Scheduler(engine, ServingConfig(max_queue=16))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 100, 26 + i).tolist() for i in range(6)]
    handles = [sched.submit(p, max_new_tokens=3) for p in prompts]
    while sched.step():
        pass
    assert all(h.status == "DONE" for h in handles)
    return {"spans": logged(), "prompts": prompts, "engine": engine}


def test_admit_and_publish_stand_around_every_prefill(served):
    spans = served["spans"]
    prefills = [s for s in spans if s["name"] == P + "prefill"]
    assert len(prefills) == 6
    for p in prefills:
        kids = sorted((s for s in spans if s["parent"] == p["parent"]
                       and s["attrs"].get("request_id")
                       == p["attrs"]["request_id"]),
                      key=lambda s: s["ts"])
        assert [s["name"] for s in kids] == \
            [P + "prefill.admit", P + "prefill", P + "prefill.publish"]
        admit, _, publish = kids
        # siblings, one after the other: prefill keeps its own extent
        assert admit["ts"] + admit["dur"] <= p["ts"]
        assert p["ts"] + p["dur"] <= publish["ts"]
        n = p["attrs"]["length"]
        hashed = admit["attrs"]["prefix_hashed_tokens"] + \
            publish["attrs"]["prefix_hashed_tokens"]
        assert n // BLOCK * BLOCK <= hashed <= n
        assert set(publish["attrs"]) == {"prefix_hashed_tokens",
                                         "request_id"}
        assert set(admit["attrs"]) == {"prefix_hashed_tokens",
                                       "prefix_evict_visited", "request_id"}
    visited = [s["attrs"]["prefix_evict_visited"] for s in spans
               if s["name"] == P + "prefill.admit"]
    assert visited[0] == 0 and max(visited) > 0       # the pool filled up
    cache = served["engine"].prefix_cache
    assert sum(visited) <= cache.evict_visited        # decode growth evicts too


def test_prefill_span_is_what_it_was(served):
    spans = served["spans"]
    for p in (s for s in spans if s["name"] == P + "prefill"):
        assert set(p["attrs"]) == {
            "attend", "bucket", "kv_dtype", "length", "paged",
            "pool_donated", "prefix_hit_tokens", "request_id", "slot"}
        # ISSUE 36: the host's three phases inside it, and nothing else
        assert [s["name"] for s in spans if s["parent"] == p["span_id"]] \
            == [P + "prefill.upload", P + "prefill.dispatch",
                P + "prefill.wait"]
        parent = next(s for s in spans if s["span_id"] == p["parent"])
        assert parent["name"] == P + "refill"


# ------------------------------------- the reconciler on a closed loop

def test_200_steps_of_a_closed_loop_leave_the_reconciler_green(model):
    """Two clients, each sending its next request when the last one ended,
    over a pool that evicts all the time: the ledger's shadow agrees with
    the cache (cached set, chain parents, evictable()) at every step."""
    engine = paged_engine(model, num_blocks=17)
    sched = Scheduler(engine, ServingConfig(max_queue=16))
    recon = sched._kv_reconciler
    assert recon is not None and engine.prefix_cache._ledger is not None
    rng = np.random.RandomState(1)
    shared = rng.randint(1, 100, 2 * BLOCK).tolist()

    def submit():
        own = rng.randint(1, 100, rng.randint(3, 30)).tolist()
        prompt = shared + own if rng.rand() < 0.4 else own
        return sched.submit(prompt, max_new_tokens=int(rng.randint(1, 5)))

    running = [submit(), submit()]
    for _ in range(200):
        sched.step()
        running = [h if h.status not in ("DONE", "FAILED") else submit()
                   for h in running]
    assert recon.divergences == []
    cache = engine.prefix_cache
    assert cache.evict_visited > 0 and cache.namespace_evictions()
    assert cache._leaves == {k for k in cache._entries
                             if not cache._children.get(k)}
