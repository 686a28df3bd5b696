"""Shared prefix cache: hash-addressed KV blocks, refcounted, COW-safe.

Millions of users means millions of requests opening with the same system
prompt; prefilling it per request burns both compute (the prefill
executable re-runs the same tokens) and memory (the pool stores the same
K/V N times). This cache makes full blocks of prompt K/V content-
addressable, vLLM-style: block k of a prompt is keyed by the hash of the
ENTIRE token prefix `prompt[0 : (k+1)*block_size]` — chaining the key
over everything before the block, so two prompts share block k iff they
agree on every token up to its end.

Sharing protocol (the copy-on-write invariant):

  - `match(prompt)` walks the chain and returns the longest run of cached
    blocks, taking one pool reference per block ON BEHALF of the caller —
    the request's table row now co-owns them. Matching is capped at
    `len(prompt) - 1` tokens so at least one suffix token always runs
    through the model (the logits that produce the first generated token).
  - shared blocks are never written: sharing is full-block-granular, so a
    request's writable region starts exactly at the first private block —
    the "copy" in copy-on-write is avoided by alignment rather than
    performed.
  - `insert(prompt, table_row, upto_tokens)` registers the request's own
    fully-written blocks after its prefill, taking one cache-owned
    reference each, so the blocks outlive the request.
  - `evict(n)` drops least-recently-used entries whose blocks have no
    other owner (refcount == 1, the cache's own), returning blocks to
    the pool — called by the engine when an allocation comes up short,
    before the scheduler resorts to preemption.

What the bookkeeping costs (ISSUE 33): a prompt's chain keys come from
one running sha1 (`chain_keys`), lazily, and one `chain()` serves the
`match` and the `insert` of a prefill, so a prefill hashes its prompt
once; an eviction works from the set of leaf entries, so it costs the
leaves resident and the blocks it frees. `hashed_tokens` and
`evict_visited` count both.

Hit/miss counters (per prefill lookup) and the resident-block gauge feed
the unified metrics registry; `tools/metrics_report.py --compare` treats
a prefix-hit-rate drop as a failure-class regression.

KV tiers (ISSUE 18): `attach_tier` plugs a
`serving.kv_tiers.TieredBlockStore` under the cache. Eviction then
DEMOTES instead of freeing — the entry's KV is captured into the host
tier (cascading to disk under host pressure) before the block returns
to the pool — and `match` PROMOTES: when the HBM walk breaks on a key a
colder tier holds, the block is re-allocated, its KV written back
eagerly (device_put prefetch — host/transfer work only, never a new
traced program), and the entry re-registered cache-owned, so the match
continues through it. Promotion respects a `reserve` headroom hint so
restoring a cold chain can never starve the suffix prefill's own
allocation. Because demoted entries leave `_entries`/`_resident`,
tenant quotas meter the HBM tier only — an over-quota namespace SPILLS
instead of dropping (ISSUE 18's quota contract).

Multi-tenant namespaces (ISSUE 17): a request's prefix NAMESPACE salts
every chain key, so two tenants in different namespaces can never share
a block even for identical prompts — sharing stops at the trust
boundary, by construction of the key. Eviction is quota-aware:
`evict(n, requester=...)` drains the requester's OWN namespace's LRU
leaves first, and a foreign namespace whose resident count sits within
its quota (`set_quota`) is PROTECTED — a hot tenant's allocation
pressure can never evict a paying tenant's system prompt. Requests with
no namespace (and caches with no quotas) behave exactly as before.
"""
import hashlib
import heapq
import time

import numpy as np

from ..observability import kvledger as _kvl
from ..observability import metrics as _metrics
from .blocks import GARBAGE_BLOCK, BlockAllocError

__all__ = ["PrefixCache", "prefix_key", "chain_keys", "DEFAULT_NAMESPACE"]

_M_HITS = _metrics.counter(
    "serving_prefix_cache_hits_total",
    "Prefill lookups that reused at least one cached prefix block")
_M_MISSES = _metrics.counter(
    "serving_prefix_cache_misses_total",
    "Prefill lookups that reused no cached prefix block")
_M_BLOCKS = _metrics.gauge(
    "serving_prefix_cache_blocks", "KV blocks resident in the prefix cache")
_M_EVICTED = _metrics.counter(
    "serving_prefix_cache_evicted_total",
    "Prefix blocks evicted back to the pool under allocation pressure")
_M_NS_EVICTED = _metrics.counter(
    "serving_prefix_ns_evicted_total",
    "Prefix blocks evicted per namespace under allocation pressure",
    labelnames=("namespace",))

# the namespace label value of un-namespaced entries — one vocabulary
# with decisions.DEFAULT_TENANT so single-tenant artifacts grade the same
DEFAULT_NAMESPACE = "default"


def prefix_key(tokens, namespace=None):
    """Stable content hash of a token prefix (the chain key). A non-None
    `namespace` salts the hash FIRST, so namespaced chains live in
    disjoint key spaces — cross-namespace sharing is impossible, not
    merely forbidden. namespace=None keys are byte-identical to the
    pre-tenancy scheme."""
    h = hashlib.sha1()
    if namespace is not None:
        h.update(str(namespace).encode("utf-8") + b"\x00")
    for t in tokens:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.hexdigest()


def chain_keys(tokens, block_size, namespace=None):
    """The chain keys of `tokens`' full blocks, in order: key k is byte
    for byte `prefix_key(tokens[:(k + 1) * block_size], namespace)`,
    from ONE running sha1 that is fed a block at a time and copied for
    each digest, so a chain costs its tokens once and not once a block.
    A generator: a caller that stops at its first miss has hashed only
    what it looked up."""
    step = 8 * int(block_size)
    buf = memoryview(np.asarray(tokens, dtype="<i8").tobytes())
    h = hashlib.sha1()
    if namespace is not None:
        h.update(str(namespace).encode("utf-8") + b"\x00")
    for k in range(len(buf) // step):
        h.update(buf[k * step:(k + 1) * step])
        yield h.copy().hexdigest()


class _Chain:
    """One prompt's chain keys, hashed lazily and once: `key(k)` runs
    `chain_keys` as far as block k and keeps what it yielded, so `match`
    stops hashing at its first miss and `insert` goes on from there."""
    __slots__ = ("_cache", "_gen", "_keys")

    def __init__(self, cache, tokens, namespace):
        self._cache = cache
        self._gen = chain_keys(tokens, cache.block_size, namespace)
        self._keys = []

    def key(self, k):
        keys = self._keys
        while len(keys) <= k:
            keys.append(next(self._gen))
            self._cache.hashed_tokens += self._cache.block_size
        return keys[k]


class PrefixCache:
    def __init__(self, pool, block_size, bypass=False):
        self.pool = pool
        self.block_size = int(block_size)
        # bypass: the engine prefills its model from position 0 (a model
        # with its own cache layout: its prefill cannot continue from
        # resident rows, and per-slot state is in no block), so reusing a
        # prefix's blocks would be wrong. Every lookup then reports no hit
        # (and is counted in `bypassed`), nothing is registered, and the
        # pool's blocks go back when a request ends.
        self.bypass = bool(bypass)
        self.bypassed = 0
        self._entries = {}        # key -> block id
        self._lru = {}            # key -> last-use sequence number
        self._parent = {}         # key -> chain-parent key (None at k=0)
        self._children = {}       # key -> cached direct children count
        self._leaves = set()      # entries with no cached child: the
        #                           only ones an eviction can take
        self._seq = 0
        # what the bookkeeping cost, since construction: token updates
        # fed to sha1, and entries an eviction looked at (the engine
        # notes a prefill's share of each on its spans)
        self.hashed_tokens = 0
        self.evict_visited = 0
        # per-namespace bookkeeping (ISSUE 17): entry ownership, resident
        # counts, and quotas (resident <= quota protects a namespace from
        # FOREIGN eviction pressure)
        self._ns = {}             # key -> namespace (None = unscoped)
        self._resident = {}       # namespace -> resident entry count
        self._quotas = {}         # namespace -> quota (blocks)
        self._ns_evicted = {}     # namespace -> evicted count (report tap)
        # KV attribution ledger (observability.kvledger): the cache
        # emits the SEMANTIC layer — share/cache_insert/cache_evict —
        # and refines the origin of its own pool refs so the shadow
        # model classifies holders as shared/cached, not private
        self._ledger = None
        # cold-tier store (ISSUE 18, serving.kv_tiers): None keeps the
        # pre-tier behavior bit for bit — evictions free, misses miss
        self._tier = None
        # last match's promotion figures, the engine's prefill-stats tap
        # (the scheduler attributes them to the request as tier_hit /
        # restore_ms in its serving JSONL)
        self.last_tier_stats = {"promoted_blocks": 0, "restore_s": 0.0}

    def attach_ledger(self, ledger):
        self._ledger = ledger

    def attach_tier(self, store):
        self._tier = store

    # -- namespace quotas (ISSUE 17) -----------------------------------------
    def set_quota(self, namespace, blocks):
        """Cap + protect `namespace`: while its resident entries stay
        <= `blocks`, no OTHER namespace's pressure can evict them (its
        own requests still can). None removes the quota."""
        if blocks is None:
            self._quotas.pop(namespace, None)
        else:
            self._quotas[namespace] = int(blocks)

    def set_quotas(self, quotas):
        for ns, blocks in dict(quotas or {}).items():
            self.set_quota(ns, blocks)

    def resident(self, namespace):
        """Resident prefix entries owned by `namespace`."""
        return self._resident.get(namespace, 0)

    def namespace_residents(self):
        """{namespace-label: resident entries} (None -> "default")."""
        return {(ns if ns is not None else DEFAULT_NAMESPACE): n
                for ns, n in self._resident.items() if n}

    def namespace_evictions(self):
        """{namespace-label: blocks evicted} since construction."""
        return dict(self._ns_evicted)

    def _protected(self, namespace):
        """True while `namespace` holds a quota AND sits within it —
        foreign pressure must not touch it."""
        quota = self._quotas.get(namespace)
        return quota is not None and \
            self._resident.get(namespace, 0) <= quota

    def __len__(self):
        return len(self._entries)

    def evictable(self):
        """Blocks reclaimable on demand (refcount == 1: only the cache
        holds them). Capacity probes — e.g. the scheduler's
        shed_pool_free watermark — must treat these as free, else a warm
        cache reads as a full pool and sheds traffic an eviction would
        trivially serve."""
        refs = self.pool.refcounts(self._entries.values())
        return int(np.count_nonzero(refs == 1))

    def _touch(self, key):
        self._seq += 1
        self._lru[key] = self._seq

    def chain(self, prompt, namespace=None):
        """`prompt`'s chain keys under `namespace`, for the `chain=` of
        `match` and `insert`: a prefill that hands both the same one
        hashes its prompt once."""
        return _Chain(self, prompt, namespace)

    # -- lookup --------------------------------------------------------------
    def match(self, prompt, record=True, namespace=None, reserve=0,
              chain=None):
        """Longest cached block chain covering a strict prefix of
        `prompt`. Returns (block_ids, n_tokens) with one pool reference
        taken per returned block (owned by the caller's table row).
        n_tokens is always a multiple of block_size and <= len(prompt)-1.

        With a tier store attached (ISSUE 18), a break in the HBM walk
        probes the colder tiers and PROMOTES resident continuation
        blocks back into freshly allocated HBM, so a cold chain still
        matches. `reserve` is the caller's total block need for this
        prompt (`blocks_for_tokens(plen)`): promotion of block k only
        proceeds while `pool.available > reserve - k - 1`, i.e. it can
        never eat the headroom the suffix prefill is about to allocate
        — a promote that would force the caller into BlockAllocError is
        skipped, leaving the entry tiered for a calmer moment.

        record=False skips the hit/miss counters — callers whose
        placement can fail-and-retry (BlockAllocError -> preempt ->
        re-prefill) count via `record_lookup` once the placement
        actually sticks, so pressure retries cannot inflate the
        CI-gated hit rate."""
        if self.bypass:
            self.bypassed += 1
            self.last_tier_stats = {"promoted_blocks": 0, "restore_s": 0.0}
            if record:
                self.record_lookup(False)
            return [], 0
        bs = self.block_size
        usable = (len(prompt) - 1) // bs      # full blocks, 1 token spared
        if chain is None:
            chain = self.chain(prompt, namespace)
        ids = []
        prev_key = None
        for k in range(usable):
            key = chain.key(k)
            blk = self._entries.get(key)
            if blk is None:
                break
            ids.append(blk)
            self._touch(key)
            prev_key = key
        self.last_tier_stats = {"promoted_blocks": 0, "restore_s": 0.0}
        if self._tier is not None and len(ids) < usable:
            # eviction is leaf-first, so the tiered part of a chain is
            # always a contiguous SUFFIX of the HBM walk — promote the
            # whole run in one batched device write
            t0 = time.perf_counter()
            promoted = self._promote_run(chain, len(ids), usable,
                                         namespace, prev_key, reserve)
            for key, blk in promoted:
                ids.append(blk)
                self._touch(key)
            if promoted:
                self.last_tier_stats = {
                    "promoted_blocks": len(promoted),
                    "restore_s": time.perf_counter() - t0}
        if ids and self._ledger is not None:
            with _kvl.origin_scope("prefix_cache.match"):
                for b in ids:
                    self.pool.ref(b)
            self._ledger.cache_share(ids, len(ids) * bs)
        else:
            for b in ids:
                self.pool.ref(b)
        if record:
            self.record_lookup(bool(ids))
        return ids, len(ids) * bs

    def record_lookup(self, hit):
        """Count one prefill lookup toward the hit-rate metrics."""
        (_M_HITS if hit else _M_MISSES).inc()

    def probe(self, prompt, namespace=None):
        """Longest servable prefix in TOKENS, side-effect-free: no pool
        refs, no LRU touches, no promotion, no hit/miss counters (what
        it hashed is in `hashed_tokens`, as everyone's) — counts HBM
        entries AND tiered continuations. The `OP_PREFIX_LOOKUP` fabric
        verb answers from this (readonly verbs must not mutate)."""
        if self.bypass:
            return 0
        bs = self.block_size
        usable = (len(prompt) - 1) // bs
        chain = self.chain(prompt, namespace)
        n = 0
        for k in range(usable):
            key = chain.key(k)
            if key in self._entries or \
                    (self._tier is not None and key in self._tier):
                n += 1
            else:
                break
        return n * bs

    def _promote_run(self, chain, k0, usable, namespace, parent,
                     reserve):
        """Promote the contiguous tiered continuation of the prompt's
        `chain` (blocks k0..usable) back into HBM in ONE batched device
        write. The sequential headroom rule is precomputed: promoting
        block k is allowed only while the pool's availability, net of
        the run's earlier promotes, stays >= max(reserve - k, 1) — a
        promote that would force the caller's suffix prefill into
        BlockAllocError is skipped, leaving the tail tiered for a
        calmer moment. Each allocation's refcount-1 becomes the cache's
        own reference (the normal insert path's ref), mirrored to the
        ledger as a cache_insert so the shadow model's cached set and
        evictable() stay exact. Returns [(key, block_id)] in chain
        order."""
        store = self._tier
        keys = []
        for k in range(k0, usable):
            key = chain.key(k)
            if key not in store:
                break
            keys.append(key)
        avail = self.pool.available
        m = 0
        for j in range(len(keys)):
            if avail - j < max(int(reserve) - (k0 + j), 1):
                break
            m += 1
        if not m:
            return []

        def alloc_run(n):
            try:
                if self._ledger is not None:
                    with _kvl.origin_scope("prefix_cache.promote"):
                        return list(self.pool.alloc(n))
                return list(self.pool.alloc(n))
            except BlockAllocError:
                return None

        out = []
        for key, blk in store.promote_run(keys[:m], alloc_run):
            self.register_block(key, blk, namespace, parent)
            parent = key
            out.append((key, blk))
        return out

    def register_block(self, key, blk, namespace, parent):
        """Register an ALREADY-ALLOCATED block (refcount 1, owned by
        nobody else) as a cache entry — the promotion/fleet-restore
        twin of `insert`, which instead refs blocks a request's table
        row owns. The allocation's own reference becomes the cache's."""
        if self._ledger is not None:
            self._ledger.cache_insert((int(blk),))
        self._add_entry(key, int(blk), namespace, parent)
        _M_BLOCKS.set(len(self._entries))

    def _add_entry(self, key, blk, namespace, parent):
        """The books of one new entry: a leaf, its parent no longer."""
        self._entries[key] = blk
        self._ns[key] = namespace
        self._resident[namespace] = self._resident.get(namespace, 0) + 1
        self._parent[key] = parent
        if parent is not None:
            self._children[parent] = self._children.get(parent, 0) + 1
            self._leaves.discard(parent)
        self._leaves.add(key)
        self._touch(key)

    # -- registration --------------------------------------------------------
    def insert(self, prompt, table_row, upto_tokens, namespace=None,
               chain=None):
        """Register the fully-written blocks of `prompt` (logical blocks
        whose every position < upto_tokens) from the request's table row.
        Already-cached chains keep their existing block (the duplicate
        stays request-private); newly cached blocks gain one cache-owned
        reference."""
        if self.bypass:
            return
        if chain is None:
            chain = self.chain(prompt, namespace)
        prev_key = None
        for k in range(int(upto_tokens) // self.block_size):
            blk = int(table_row[k])
            if blk == GARBAGE_BLOCK:
                continue
            key = chain.key(k)
            if key in self._entries:
                self._touch(key)
                prev_key = key
                continue
            if self._ledger is not None:
                with _kvl.origin_scope("prefix_cache.insert"):
                    self.pool.ref(blk)
                self._ledger.cache_insert((blk,))
            else:
                self.pool.ref(blk)
            self._add_entry(key, blk, namespace, prev_key)
            prev_key = key
        _M_BLOCKS.set(len(self._entries))

    # -- eviction ------------------------------------------------------------
    def evict(self, n_blocks, requester=None):
        """Free up to n_blocks LRU entries nobody else references
        (refcount == 1: only the cache's own). Eviction is LEAF-first —
        an entry with a cached child is skipped, because `match` walks
        chains from block 0 and an evicted head would orphan its tail
        (still resident, never matchable again).

        Quota-aware order (ISSUE 17): pass 1 drains the REQUESTER's own
        namespace; pass 2 reaches into foreign namespaces, but skips any
        that holds a quota and sits within it — the protection is
        re-checked per eviction, so an over-quota namespace is drained
        only down to its quota. With no requester and no quotas, every
        entry is eligible — the pre-tenancy behavior, bit for bit.
        Returns how many blocks went back to the pool."""
        if n_blocks <= 0:
            return 0
        freed = self._evict_pass(n_blocks, lambda ns: ns == requester)
        if freed < n_blocks:
            freed += self._evict_pass(
                n_blocks - freed,
                lambda ns: ns != requester and not self._protected(ns))
        if freed:
            _M_EVICTED.inc(freed)
            _M_BLOCKS.set(len(self._entries))
        return freed

    def _evict_pass(self, n_blocks, eligible):
        """One LRU leaf-first pass over entries whose namespace passes
        `eligible` (re-evaluated per eviction — resident counts move).

        The victims and their order are those of sweeping ALL entries
        by last use, freeing the leaves met, and starting over while a
        sweep freed something: a leaf freed in sweep s exposes its
        parent to the same sweep where the parent was used after it,
        to sweep s + 1 where before. Only leaves can go, so the heap
        holds (sweep, last use, key) of the leaves alone, and a pass
        costs the leaves it starts from and what it frees, not sweeps x
        entries. A candidate turned down stays turned down for the
        pass: no other block's refcount moves under an eviction, and a
        namespace's protection only sets in as it drains."""
        heap = [(0, self._lru[key], key) for key in self._leaves]
        heapq.heapify(heap)
        self.evict_visited += len(heap)
        freed = 0
        while heap and freed < n_blocks:
            sweep, seq, key = heapq.heappop(heap)
            ns = self._ns.get(key)
            if not eligible(ns):
                continue
            blk = self._entries[key]
            if self.pool.refcount(blk) != 1:
                continue
            if self._tier is not None:
                # demote-instead-of-free (ISSUE 18): capture the
                # block's KV into the cold tiers while it is still
                # allocated; the eviction below then releases the
                # HBM copy exactly as before. A torn spill simply
                # skips the capture — lost, never corrupt.
                self._tier.demote(key, ns, self._parent.get(key), blk)
            if self._ledger is not None:
                # cache_evict BEFORE the unref so a replay never
                # sees the cache holding a freed block
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
                    if parent in self._entries:
                        # the freed leaf exposed its parent
                        self._leaves.add(parent)
                        pseq = self._lru[parent]
                        heapq.heappush(
                            heap, (sweep + (pseq < seq), pseq, parent))
                        self.evict_visited += 1
            self._leaves.discard(key)
            del self._entries[key]
            del self._lru[key]
            self._ns.pop(key, None)
            self._resident[ns] = self._resident.get(ns, 1) - 1
            label = ns if ns is not None else DEFAULT_NAMESPACE
            self._ns_evicted[label] = self._ns_evicted.get(label, 0) + 1
            _M_NS_EVICTED.labels(namespace=label).inc()
            freed += 1
        return freed
