"""KV-memory attribution plane: block lifecycle ledger + live watchdog.

The block pool (serving/blocks.py) exposes occupancy gauges, but nobody
can answer "which tenant owns this HBM" or "did that preemption leak a
block" except by test-time assertion. This module is the measurement
substrate underneath per-tenant quota enforcement and KV tier-spill
policy (ROADMAP items 2 and 5): a typed `paddle_tpu.kvledger.v1` event
log of every block lifecycle transition, per-tenant resident accounting
exported as live gauges, and a continuous invariant checker that
replays the event stream into a shadow pool model and reconciles it
against the real allocator at scheduler-step boundaries — the live
analogue of the chaos tests' "zero block leaks" assertion, in the
decisions.v1/replay idiom of PR 15.

Event vocabulary (each event carries block ids, request id, tenant,
and origin site, captured from the attribution context at emit time):

  alloc         BlockPool.alloc handed out fresh blocks (refcount 1)
  ref           one reference taken on an allocated block
  unref         one reference dropped
  free          the last reference dropped — the block returned to the
                free list (emitted in addition to its `unref`)
  share         a prefix-cache match put cached blocks into a request's
                table row (the `ref`s ride alongside; `tokens` counts
                the prefill work the reuse avoided)
  cache_insert  the prefix cache took its own reference on a block
                (the block now outlives the inserting request)
  cache_evict   the prefix cache dropped an entry under pressure
  tier_demote   an evicted chain entry left HBM for a colder tier
                (ISSUE 18): carries `key` (the prefix-chain entry key),
                `tier` ("host"|"disk") and `owner` (the chain's
                namespace tenant). The HBM side still emits its own
                unref/free — tier events track the COLD copy's
                residency, so the reconciler can prove zero blocks
                leaked ACROSS tiers, not just inside the pool
  tier_promote  a tiered entry was restored into HBM (the pool-side
                alloc/ref/cache_insert events ride alongside)
  tier_drop     a tiered entry was discarded (capacity pressure,
                corruption at restore, or explicit invalidation) —
                the chain is gone everywhere; a later match misses

Attribution: BlockPool and PrefixCache know nothing about requests or
tenants. The scheduler wraps every engine call that can touch the pool
in `attribution(request_id=..., tenant=..., origin=...)`; the emit path
reads the innermost context, so events are labeled with zero plumbing
through engine signatures (the PR 15 labels-never-reach-the-engine
contract, inverted: the labels ride a context, not the call chain).
PrefixCache refines `origin` with `origin_scope("prefix_cache.*")` so
the shadow model can classify each holder:

  private   the request alloc'd the block itself (COW-writable)
  shared    the request co-owns a cached chain via `match`
  cached    the prefix cache's own reference

Per-tenant residency is exported as `serving_kv_blocks{tenant,kind}`
plus `serving_kv_bytes{tenant,kind}` priced from the pool dtype by the
engine — plain gauges, so PR 12's fleet federation relabels them
per-worker and the router sees fleet-wide per-tenant HBM with no
fleet.py merge changes.

`LedgerReconciler.check()` runs at scheduler-step boundaries and
compares the shadow model against the real pool + prefix cache:
refcount conservation, free-list agreement, cached-set agreement, no
orphaned prefix-chain tails, evictable()-vs-ledger agreement, and
event-stream self-consistency. Any divergence latches
`serving_kv_ledger_divergence_total{invariant}`, a flight-recorder
annotation, and (once) a postmortem bundle.

What a check costs (ISSUE 35): the step's events, not the pool. The
shadow keeps its aggregates as events apply (distinct blocks per
(tenant, kind) and per tenant, the free list in the pool's own order,
the cache-only count, the tier map, the (tenant, kind) keys whose
count moved), and every invariant is still a WHOLE-pool comparison at
every step boundary, made by the interpreter's own primitives (a list
against a list, a set against a dict's keys): no Python-level
iteration per block, cached entry or holder while nothing diverged.
The per-block walks remain as the describer of a divergence once one
is found and as the from-scratch oracle (`free_set`,
`scan_tenant_kind_blocks`, `scan_tenant_resident_totals`,
`scan_cache_only`) the tests hold the kept aggregates to.
`LedgerReconciler.last_check` says what the last check visited one by
one (`ledger_blocks_walked`).

Zero-cost when disabled: the pool/cache hot paths pay one `is None`
check; `disable()` (or PTN_KV_LEDGER=0) keeps engines from attaching a
ledger at construction, and the streams are bit-identical either way —
the ledger only ever observes.
"""
import contextlib
import os
import threading

from . import flight_recorder as _fr
from . import metrics as _metrics

__all__ = ["SCHEMA", "EVENTS", "KINDS", "INVARIANTS", "KVLedger",
           "ShadowPool", "LedgerReconciler", "attribution",
           "origin_scope", "current_attribution", "replay_events",
           "enabled", "enable", "disable"]

SCHEMA = "paddle_tpu.kvledger.v1"
EVENTS = ("alloc", "ref", "unref", "free", "share", "cache_insert",
          "cache_evict", "tier_demote", "tier_promote", "tier_drop")
KINDS = ("private", "shared", "cached", "host", "disk")
INVARIANTS = ("event_stream", "refcounts", "free_list", "cached_set",
              "orphan_chain", "evictable", "tier_residency")
DEFAULT_TENANT = "default"

_G_BLOCKS = _metrics.gauge(
    "serving_kv_blocks",
    "Resident KV blocks attributed per tenant and ownership kind "
    "(private|shared|cached), from the kvledger shadow model",
    labelnames=("tenant", "kind"))
_G_BYTES = _metrics.gauge(
    "serving_kv_bytes",
    "Resident KV bytes per tenant and ownership kind, priced from the "
    "engine's pool dtype (block_bytes x serving_kv_blocks)",
    labelnames=("tenant", "kind"))
_C_DIVERGENCE = _metrics.counter(
    "serving_kv_ledger_divergence_total",
    "Ledger-vs-pool invariant violations caught by LedgerReconciler "
    "(failure-class: any growth means a leak, a double free, or a "
    "corrupted prefix chain)",
    labelnames=("invariant",))

_enabled = os.environ.get("PTN_KV_LEDGER", "1").lower() \
    not in ("0", "off", "false")


def enabled():
    """Whether engines attach a ledger at construction. Checked once,
    when `_alloc_host_state` runs — flipping it later affects only
    engines built afterwards."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


# ------------------------------------------------- attribution context

_ctx = threading.local()

#: shared reusable null context for callers on the disabled path
NULL_CTX = contextlib.nullcontext()


def current_attribution():
    """The innermost attribution frame ({'request_id','tenant','origin'})
    or None outside any scope."""
    return getattr(_ctx, "cur", None)


@contextlib.contextmanager
def attribution(request_id=None, tenant=None, origin=None):
    """Label every ledger event emitted inside the scope. The scheduler
    wraps engine calls (prefill/adopt/reset/grow) in this; nesting
    replaces the frame, restoring the outer one on exit."""
    prev = getattr(_ctx, "cur", None)
    _ctx.cur = {"request_id": request_id, "tenant": tenant,
                "origin": origin}
    try:
        yield
    finally:
        _ctx.cur = prev


@contextlib.contextmanager
def origin_scope(origin):
    """Refine only the `origin` of the current frame (PrefixCache wraps
    its own pool calls so `ref`s classify as shared/cached, not
    private), preserving request/tenant attribution."""
    prev = getattr(_ctx, "cur", None)
    base = prev or {"request_id": None, "tenant": None}
    _ctx.cur = {"request_id": base.get("request_id"),
                "tenant": base.get("tenant"), "origin": origin}
    try:
        yield
    finally:
        _ctx.cur = prev


# ---------------------------------------------------- the shadow model

def _holder_kind(origin):
    """Ownership kind of a reference, from the origin that took it."""
    if origin == "prefix_cache.match":
        return "shared"
    if origin == "prefix_cache.insert":
        return "cached"
    return "private"


class ShadowPool:
    """Event-stream replica of a BlockPool: refcounts, the allocated
    set, per-block holder attribution, and the cached-block ownership
    map — everything the reconciler compares against the real allocator
    and everything the residency gauges aggregate. Impossible
    transitions (ref of a free block, unref below zero, double alloc)
    are recorded in `errors` instead of raising: the shadow must keep
    tracking a diverged pool so the reconciler can describe the damage.

    The aggregates the step boundary reads are kept as events apply
    (ISSUE 35), so reading them costs nothing per block: the distinct
    blocks per (tenant, kind) and per tenant, `free_list`, `cache_only`,
    `tier_of`, and `dirty`. The `scan_*` methods recount each from the
    per-block state; the two always agree (tests/test_kvledger.py).

    Stdlib-only on purpose (plain-list refcounts): the package contract
    is that every observability submodule imports before/without the
    accelerator stack, so offline tools can replay a ledger stream
    beside the process that holds the chip."""

    _MAX_ERRORS = 32

    def __init__(self, num_blocks):
        self.num_blocks = int(num_blocks)
        self.refs = [0] * self.num_blocks
        self.allocated = set()       # block ids with a live allocation
        self.holders = {}            # block -> [(tenant, kind, req_id)]
        self.cached = {}             # block -> inserting tenant
        self.tiered = {}             # chain key -> (owner tenant, tier)
        self.errors = []             # event-stream self-inconsistencies
        self.applied = 0
        # the free list as the pool keeps it, a stack (alloc pops, the
        # last unref pushes): a clean pool's list equals it element for
        # element. A pool that orders its list otherwise loses only
        # that short cut; the set of it is `free_set()` either way
        self.free_list = list(range(self.num_blocks - 1, 0, -1))
        self.cache_only = 0          # cached blocks with refcount 1
        self.tier_of = {}            # chain key -> tier
        self.dirty = set()           # (tenant, kind) whose count moved
        #                              since `export_gauges` last took it
        self._kind_blocks = {}       # (tenant, kind) -> distinct blocks
        self._tenant_blocks = {}     # tenant -> distinct resident blocks

    def _err(self, msg):
        if len(self.errors) < self._MAX_ERRORS:
            self.errors.append(msg)

    # -- the kept aggregates ------------------------------------------------
    def _count(self, tenant, kind, d):
        """Move (tenant, kind)'s distinct-block count by d; a count of
        zero is no entry, as the from-scratch view has none."""
        tk = (tenant, kind)
        n = self._kind_blocks.get(tk, 0) + d
        if n:
            self._kind_blocks[tk] = n
        else:
            self._kind_blocks.pop(tk, None)
        self.dirty.add(tk)

    def _count_tenant(self, tenant, d):
        n = self._tenant_blocks.get(tenant, 0) + d
        if n:
            self._tenant_blocks[tenant] = n
        else:
            self._tenant_blocks.pop(tenant, None)

    def _holder_moved(self, hs, holder, d):
        """`holder` joined (d=+1, not yet in `hs`) or left (d=-1,
        already out of `hs`) a block's holders: the block counts once
        for a (tenant, kind) pair and once for a tenant however many of
        its holders carry them, so only the first to come and the last
        to go move a count."""
        tenant, kind = holder[0], holder[1]
        same_tenant = False
        for h in hs:
            if h[0] == tenant:
                if h[1] == kind:
                    return
                same_tenant = True
        self._count(tenant, kind, d)
        if not same_tenant:
            self._count_tenant(tenant, d)

    def _forget_holders(self, b):
        """Block `b` lost all its holders at once (freed, or allocated
        over): each distinct pair and tenant among them counts one
        block fewer."""
        hs = self.holders.pop(b, None)
        if not hs:
            return
        for tenant, kind in {(h[0], h[1]) for h in hs}:
            self._count(tenant, kind, -1)
        for tenant in {h[0] for h in hs}:
            self._count_tenant(tenant, -1)

    def _drop_holder(self, b, tenant, rid, origin):
        hs = self.holders.get(b)
        if not hs:
            return
        preds = (
            lambda h: rid is not None and h[2] == rid
            and h[1] != "cached",
            lambda h: h[0] == tenant and h[1] == "shared",
            lambda h: h[0] == tenant and h[1] == "private",
            lambda h: True,
        )
        if origin == "prefix_cache.evict":
            # the cache's own reference, whoever inserted it
            preds = (lambda h: h[1] == "cached",) + preds
        for pred in preds:
            for i, h in enumerate(hs):
                if pred(h):
                    self._holder_moved(hs, hs.pop(i), -1)
                    return

    def _tier_moved(self, key, entry):
        """Chain `key`'s cold copy is now `entry` ((owner, tier), or
        None when it left the tiers): one entry == one block-sized
        record under `serving_kv_blocks{tenant,kind=host|disk}`."""
        old = self.tiered.pop(key, None)
        self.tier_of.pop(key, None)
        if old is not None and old[1] in ("host", "disk"):
            self._count(old[0] or DEFAULT_TENANT, old[1], -1)
        if entry is not None:
            self.tiered[key] = entry
            self.tier_of[key] = entry[1]
            if entry[1] in ("host", "disk"):
                self._count(entry[0] or DEFAULT_TENANT, entry[1], 1)

    def apply(self, ev):
        kind = ev["event"]
        tenant = ev.get("tenant") or DEFAULT_TENANT
        rid = ev.get("request_id")
        origin = ev.get("origin")
        if kind in ("tier_demote", "tier_promote", "tier_drop"):
            # tier events are keyed by prefix-chain entry, not block id:
            # the HBM side's alloc/unref/free events cover the pool, so
            # a tier event only moves the COLD copy's residency record
            key = ev.get("key")
            if key is None:
                self._err(f"seq {ev.get('seq')}: {kind} without a key")
            elif kind == "tier_demote":
                self._tier_moved(key, (ev.get("owner") or tenant,
                                       ev.get("tier")))
            else:
                if key not in self.tiered:
                    self._err(f"seq {ev.get('seq')}: {kind} of "
                              f"untiered key {key}")
                self._tier_moved(key, None)
            self.applied += 1
            return
        refs, cached = self.refs, self.cached
        for b in ev.get("blocks", ()):
            b = int(b)
            if not 0 < b < self.num_blocks:
                self._err(f"seq {ev.get('seq')}: block {b} out of "
                          f"range for pool of {self.num_blocks}")
                continue
            cache_only = b in cached and refs[b] == 1
            if kind == "alloc":
                if b in self.allocated:
                    self._err(f"seq {ev.get('seq')}: double alloc of "
                              f"block {b}")
                elif self.free_list[-1] == b:
                    self.free_list.pop()
                else:
                    self.free_list.remove(b)
                self._forget_holders(b)     # a diverged stream's leftovers
                self.allocated.add(b)
                refs[b] = 1
                holder = (tenant, "private", rid)
                self.holders[b] = [holder]
                self._holder_moved((), holder, 1)
            elif kind == "ref":
                if b not in self.allocated or refs[b] < 1:
                    self._err(f"seq {ev.get('seq')}: ref of free "
                              f"block {b}")
                refs[b] += 1
                holder = (tenant, _holder_kind(origin), rid)
                hs = self.holders.setdefault(b, [])
                self._holder_moved(hs, holder, 1)
                hs.append(holder)
            elif kind == "unref":
                if refs[b] < 1:
                    self._err(f"seq {ev.get('seq')}: unref of free "
                              f"block {b}")
                else:
                    refs[b] -= 1
                self._drop_holder(b, tenant, rid, origin)
            elif kind == "free":
                if refs[b] != 0:
                    self._err(f"seq {ev.get('seq')}: free of block {b} "
                              f"with {int(refs[b])} refs")
                if b in self.allocated:
                    self.allocated.discard(b)
                    self.free_list.append(b)
                self._forget_holders(b)
            elif kind == "cache_insert":
                cached[b] = tenant
            elif kind == "cache_evict":
                cached.pop(b, None)
            # share: attribution metadata only — its refs ride alongside
            self.cache_only += (b in cached and refs[b] == 1) - cache_only
        self.applied += 1

    # -- aggregation views --------------------------------------------------
    def tenant_kind_blocks(self):
        """{(tenant, kind): distinct resident blocks} — a block counts
        once per (tenant, kind) pair holding it, so two same-tenant
        sharers of one block read as one shared block; a chain entry on
        a cold tier counts once under its owner and kind host|disk
        (ISSUE 18). Kept as events apply: a copy, at no cost a block."""
        return dict(self._kind_blocks)

    def tenant_resident_totals(self):
        """{tenant: distinct resident blocks of any kind} — the load
        harness's per-step residency sample. Kept as events apply."""
        return dict(self._tenant_blocks)

    # -- the same, recounted from the per-block state -----------------------
    # What the views above must equal at every moment, and what an
    # offline audit may trust without trusting the bookkeeping.
    def free_set(self):
        """Block ids the shadow believes sit on the free list."""
        return {b for b in range(1, self.num_blocks)
                if b not in self.allocated}

    def scan_tenant_kind_blocks(self):
        out = {}
        for b, hs in self.holders.items():
            for tk in {(h[0], h[1]) for h in hs}:
                out[tk] = out.get(tk, 0) + 1
        for owner, tier in self.tiered.values():
            if tier in ("host", "disk"):
                tk = (owner or DEFAULT_TENANT, tier)
                out[tk] = out.get(tk, 0) + 1
        return out

    def scan_tenant_resident_totals(self):
        out = {}
        for b, hs in self.holders.items():
            for t in {h[0] for h in hs}:
                out[t] = out.get(t, 0) + 1
        return out

    def scan_cache_only(self):
        """Cached blocks whose only reference is the cache's: what
        `PrefixCache.evictable()` must read."""
        return sum(1 for b in self.cached if self.refs[b] == 1)


def replay_events(events, num_blocks):
    """Replay a serialized kvledger.v1 stream (e.g. parsed back from a
    serving JSONL) into a fresh ShadowPool — the offline half of the
    reconciler, and what an end-of-run audit reconstructs the pool
    from (tests/test_kvledger.py)."""
    shadow = ShadowPool(num_blocks)
    for ev in events:
        shadow.apply(ev)
    return shadow


# ------------------------------------------------------------ the ledger

class KVLedger:
    """Append-only kvledger.v1 event log + live shadow model for ONE
    BlockPool. Engines construct and attach it in `_alloc_host_state`
    (the mesh-oblivious host half shared by paged/spec/tp/pp), so every
    engine kind is covered by the same two instrumentation points.

    The event list is unbounded by design: the reconciler's acceptance
    contract is an exact replay of the FULL stream (a bounded ring
    could not prove a leak absent). Long-lived workers that only need
    the live invariants can `compact()` at a reconciled boundary."""

    def __init__(self, num_blocks, block_bytes=0):
        self.num_blocks = int(num_blocks)
        self.block_bytes = int(block_bytes)
        self.events = []
        self.shadow = ShadowPool(self.num_blocks)
        self._seq = 0

    def __len__(self):
        return len(self.events)

    def _emit(self, event, block_ids, **extra):
        ctx = current_attribution() or {}
        ev = {"schema": SCHEMA, "seq": self._seq, "event": event,
              "blocks": [int(b) for b in block_ids],
              "request_id": ctx.get("request_id"),
              "tenant": ctx.get("tenant") or DEFAULT_TENANT,
              "origin": ctx.get("origin")}
        if extra:
            ev.update(extra)
        self._seq += 1
        self.events.append(ev)
        self.shadow.apply(ev)
        return ev

    # BlockPool hooks (ground truth: every refcount transition)
    def pool_alloc(self, block_ids):
        self._emit("alloc", block_ids)

    def pool_ref(self, block_id):
        self._emit("ref", (block_id,))

    def pool_unref(self, block_id):
        self._emit("unref", (block_id,))

    def pool_free(self, block_id):
        self._emit("free", (block_id,))

    # PrefixCache hooks (semantic layer: who shares whose chains)
    def cache_share(self, block_ids, tokens):
        self._emit("share", block_ids, tokens=int(tokens))

    def cache_insert(self, block_ids):
        self._emit("cache_insert", block_ids)

    def cache_evict(self, block_ids):
        self._emit("cache_evict", block_ids)

    # TieredBlockStore hooks (ISSUE 18: residency across cold tiers)
    def tier_demote(self, block_ids, key, tier, owner, sat=None):
        # `sat` (ISSUE 19): int8 requant code-saturation fraction of the
        # demoted block — None when the host tier stores float32
        ev = {"key": str(key), "tier": str(tier), "owner": str(owner)}
        if sat is not None:
            ev["sat"] = round(float(sat), 6)
        self._emit("tier_demote", block_ids, **ev)

    def tier_promote(self, block_ids, key, tier, owner):
        self._emit("tier_promote", block_ids, key=str(key),
                   tier=str(tier), owner=str(owner))

    def tier_drop(self, key, tier, owner, reason=None):
        ev = {"key": str(key), "tier": str(tier), "owner": str(owner)}
        if reason is not None:
            ev["reason"] = str(reason)
        self._emit("tier_drop", (), **ev)

    def compact(self):
        """Drop the serialized history (the live shadow keeps its
        state). Only safe at a reconciled boundary; replay from the
        remaining stream is no longer an alloc-from-empty replay."""
        self.events = []

    def export_gauges(self):
        """Publish serving_kv_blocks/bytes{tenant,kind} from the shadow:
        only the (tenant, kind) series whose count moved since the last
        export are set, a series that went non-resident to zero, so a
        stale child can never read as live HBM. Returns how many series
        it set."""
        if not _metrics.registry().enabled:
            return 0        # a set() would be dropped: the series stay owed
        shadow = self.shadow
        dirty, shadow.dirty = shadow.dirty, set()
        counts = shadow._kind_blocks
        for t, k in dirty:
            n = counts.get((t, k), 0)
            _G_BLOCKS.labels(tenant=t, kind=k).set(n)
            _G_BYTES.labels(tenant=t, kind=k).set(n * self.block_bytes)
        return len(dirty)


# -------------------------------------------------------- the reconciler

class LedgerReconciler:
    """Continuous invariant checker: at every scheduler-step boundary,
    compare the ledger's shadow model against the REAL free list,
    refcounts, and prefix-cache structure. Every invariant is a
    whole-pool comparison every time, so damage done behind the
    ledger's back (a refcount or a free-list entry changed with no
    event) is caught within one step too; a clean pool passes each in
    a constant number of the interpreter's own list/set/dict
    operations, and only a comparison that fails walks the pool block
    by block, to describe what it found. Any divergence is latched
    (counter + flight-recorder annotation + one postmortem bundle) and
    keeps being counted each step it persists — a leak does not heal by
    being old."""

    def __init__(self, ledger, pool, cache=None, tier_store=None):
        self.ledger = ledger
        self.pool = pool
        self.cache = cache
        self.tier_store = tier_store
        self.divergences = []        # latched messages, newest-last
        self._dumped = False
        self.last_postmortem = None
        self._applied = ledger.shadow.applied
        # what the last check() cost (the scheduler notes it on its
        # `serving::bookkeeping` span): events applied since the check
        # before, blocks / cached entries / holders / tier entries /
        # gauge series visited one by one in Python, the pool's blocks
        self.last_check = {"ledger_events": 0, "ledger_blocks_walked": 0,
                           "ledger_pool_blocks": ledger.num_blocks}
        # prime every invariant's series at zero so a later increment is
        # a DELTA from a clean baseline, not a first sight that
        # metrics_report --compare could mistake for schema churn
        for inv in INVARIANTS:
            _C_DIVERGENCE.labels(invariant=inv).inc(0)

    def _diffs(self):
        """([(invariant, message)], walked) — one entry per violated
        invariant, and how many blocks, cached entries and tier entries
        the describing of them visited one by one (0 on a clean pool)."""
        out, walked = [], 0
        shadow = self.ledger.shadow
        pool = self.pool
        if shadow.errors:
            out.append(("event_stream",
                        f"{len(shadow.errors)} impossible transitions "
                        f"in the event stream; first: "
                        f"{shadow.errors[0]}"))
        real_refs = pool._refs.tolist()
        if shadow.refs != real_refs:
            walked += shadow.num_blocks
            bad = [b for b in range(shadow.num_blocks)
                   if shadow.refs[b] != real_refs[b]][:8]
            out.append(("refcounts", "refcount mismatch at blocks " +
                        ", ".join(f"{b} (ledger {shadow.refs[b]} vs "
                                  f"pool {real_refs[b]})" for b in bad)))
        if pool._free != shadow.free_list:
            walked += shadow.num_blocks + len(pool._free)
            real_free = set(int(b) for b in pool._free)
            shadow_free = shadow.free_set()
            leaked = sorted(shadow_free - real_free)
            phantom = sorted(real_free - shadow_free)
            parts = []
            if leaked:
                parts.append(f"blocks {leaked[:8]} freed in the ledger "
                             f"but missing from the pool free list "
                             f"(leaked)")
            if phantom:
                parts.append(f"blocks {phantom[:8]} on the free list "
                             f"the ledger still sees allocated "
                             f"(double free)")
            if len(real_free) != len(pool._free):
                parts.append(f"{len(pool._free) - len(real_free)} "
                             f"entries on the free list twice "
                             f"(double free)")
            if parts:           # the same set in another order is none
                out.append(("free_list", "; ".join(parts)))
        cache = self.cache
        if cache is not None:
            if set(cache._entries.values()) != shadow.cached.keys():
                walked += len(cache._entries) + len(shadow.cached)
                real_cached = set(int(b) for b in cache._entries.values())
                led_cached = set(shadow.cached)
                if real_cached != led_cached:
                    out.append(
                        ("cached_set",
                         f"cache holds blocks "
                         f"{sorted(real_cached - led_cached)[:8]} the "
                         f"ledger missed; ledger holds "
                         f"{sorted(led_cached - real_cached)[:8]} the "
                         f"cache dropped"))
            parents = set(cache._parent.values())
            parents.discard(None)
            if not cache._entries.keys() >= parents:
                walked += len(cache._parent)
                orphans = [k for k, parent in cache._parent.items()
                           if parent is not None
                           and parent not in cache._entries]
                out.append(("orphan_chain",
                            f"{len(orphans)} cached entries whose chain "
                            f"parent was evicted (unmatchable tails)"))
            want = shadow.cache_only
            got = cache.evictable()
            if want != got:
                walked += len(shadow.cached)
                want = shadow.scan_cache_only()
                if want != got:
                    out.append(("evictable",
                                f"cache.evictable()={got} but the ledger "
                                f"counts {want} cache-only blocks"))
        store = self.tier_store
        if store is not None:
            # ISSUE 18: the shadow's {key: tier} map must equal the live
            # tier store's residency — a demote the ledger missed (or a
            # dropped entry it still counts) is a cross-tier leak
            residency = store.residency()
            if residency != shadow.tier_of:
                walked += len(residency) + len(shadow.tiered)
                real_tiers = {str(k): str(t)
                              for k, t in residency.items()}
                led_tiers = {str(k): str(t)
                             for k, (_own, t) in shadow.tiered.items()}
                if real_tiers != led_tiers:
                    ghost = sorted(set(led_tiers) - set(real_tiers))
                    unseen = sorted(set(real_tiers) - set(led_tiers))
                    moved = sorted(
                        k for k in set(led_tiers) & set(real_tiers)
                        if led_tiers[k] != real_tiers[k])
                    out.append(
                        ("tier_residency",
                         f"{len(ghost)} ledger-only tier entries "
                         f"(dropped without tier_drop), {len(unseen)} "
                         f"store-only (demoted without tier_demote), "
                         f"{len(moved)} on the wrong tier"))
        return out, walked

    def check(self):
        """Run every invariant; returns the (possibly empty) list of
        divergence messages found THIS call. Also refreshes the
        per-tenant residency gauges — the reconciler is the step-boundary
        hook, so the gauges track live occupancy at step granularity."""
        diffs, walked = self._diffs()
        shadow = self.ledger.shadow
        self.last_check = {
            "ledger_events": shadow.applied - self._applied,
            "ledger_blocks_walked":
                walked + self.ledger.export_gauges(),
            "ledger_pool_blocks": shadow.num_blocks}
        self._applied = shadow.applied
        if not diffs:
            return []
        msgs = [f"{inv}: {msg}" for inv, msg in diffs]
        for inv, _ in diffs:
            _C_DIVERGENCE.labels(invariant=inv).inc()
        self.divergences.extend(msgs)
        _fr.annotate("serving.kv_ledger_divergence",
                     {"invariants": [inv for inv, _ in diffs],
                      "first": msgs[0][:200],
                      "events": len(self.ledger.events)})
        if not self._dumped:
            self._dumped = True
            try:
                self.last_postmortem = _fr.dump_postmortem(
                    "kv ledger divergence: " + msgs[0][:160])
            except Exception:                            # noqa: BLE001
                self.last_postmortem = None
        return msgs
