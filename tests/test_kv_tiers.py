"""KV memory hierarchy (ISSUE 18): host/disk block tiers + fleet-global
prefix cache.

Acceptance, mapped:
  - tiering disabled: kv_tiers is None, no tier_restore trace key, and
    the tiered engine's streams are bit-identical to the untiered
    oracle's (test_tiered_restore_f32_bit_exact_and_compile_once);
  - tiering enabled: a promoted chain restores bit-exactly and BOTH
    decode and the tier-restore scatter compile exactly once (same);
  - int8 host tier stays within the PR 11 quality bounds
    (test_tiered_restore_int8_within_quality_bounds);
  - torn spill/restore chaos degrades to recompute bit-identically and
    latches serving_kv_tier_corrupt_total
    (test_chaos_spill_and_restore_degrade_to_recompute);
  - disk tier survives SIGKILL-mid-spill: torn tail truncated on
    recovery, sha-verified restores, compaction keeps live records
    (test_disk_tier_torn_tail_recovery_and_compaction);
  - quota-spill ordering under the PR 17 two-pass eviction
    (test_quota_spill_ordering_two_pass);
  - the ledger's tier_residency invariant catches out-of-band drops
    (test_ledger_tier_residency_divergence);
  - a multi-stream replay under pool pressure demotes and promotes
    chains by itself, compile-once, with the ledger clean
    (test_replay_under_pressure_cycles_chains_through_host_tier);
  - affinity placement is deterministic and auditable
    (test_affinity_rule_units_and_record_validation);
  - two-host fleet: worker B serves a prompt whose prefix is resident
    only on worker A — affinity finds the owner, load slack overrides,
    the chain ships over the wire, the stream is bit-identical to
    local recompute, and the restore is a named reqtimeline phase
    (test_fleet_wire_restore_cross_host).
"""
import os
import sys

import numpy as np
import pytest

from paddle_tpu.observability import decisions, faults, kvledger, metrics
from paddle_tpu.serving import (PagedEngineConfig, PagedGenerationEngine,
                                Scheduler, ServingConfig)
from paddle_tpu.serving.distributed import DistFrontend, ServingWorker
from paddle_tpu.serving.kv_tiers import DiskTier, HostTier
from paddle_tpu.text.models import gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import load_harness  # noqa: E402
import serve_report  # noqa: E402

VOCAB = 1024
ENGINE_KW = dict(slots=2, max_len=64, block_size=8)


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


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, VOCAB, n).tolist()


def _engine(model, **over):
    kw = dict(ENGINE_KW)
    kw.update(over)
    return PagedGenerationEngine(model, PagedEngineConfig(**kw))


def _tier_engine(model, **over):
    kw = dict(enable_kv_tiers=True, host_tier_blocks=16)
    kw.update(over)
    return _engine(model, **kw)


def _clone(model):
    m = gpt_tiny()
    m.eval()
    m.set_state_dict(model.state_dict())
    return m


def _worker_pair(model):
    m = _clone(model)
    return m, _engine(m)


def _run(sched, prompt, max_new=4, **kw):
    h = sched.submit(prompt, max_new_tokens=max_new, **kw)
    sched.run_until_idle()
    assert h.status == "DONE", (h.status, h.error)
    return h.tokens


def _counter(name, **labels):
    flat = metrics.flatten_snapshot(metrics.registry().snapshot(),
                                    kinds=("counter",))
    key = name
    if labels:
        key += "{" + ",".join(f"{k}={labels[k]}"
                              for k in sorted(labels)) + "}"
    return flat.get(key, 0.0)


def _rec(seed, heads=2, block=8, dim=16):
    """One fake tier record: the shape the engine reader produces —
    f32 (heads, block, dim) per pool array."""
    r = np.random.RandomState(seed)
    return {"ns": None, "parent": None,
            "arrays": {f"k{i}": r.randn(heads, block, dim)
                       .astype(np.float32) for i in range(2)}}


# ---------------------------------------------------------------- disk tier

def test_disk_tier_torn_tail_recovery_and_compaction(tmp_path):
    """SIGKILL-mid-spill semantics: a torn append is never indexed, a
    fresh open truncates the torn tail and keeps every intact record,
    sha mismatch degrades to a verified-corrupt miss, and compaction
    rewrites only live bytes."""
    d = str(tmp_path / "kvt")
    t = DiskTier(d, capacity_blocks=8, compact_threshold=0.5)
    recs = {f"key{i}": _rec(i) for i in range(3)}
    for k, r in recs.items():
        assert t.put(k, r)
    assert len(t) == 3

    # torn write (the spill's SIGKILL window): not indexed, and the
    # half-frame on disk must not poison later appends or reopen
    assert not t.put("torn", _rec(9), torn=True)
    assert "torn" not in t
    assert t.put("key3", _rec(3))          # appends fine over the tear

    # crash + restart: a fresh DiskTier over the same log recovers all
    # four intact records; a REAL torn tail is truncated away
    assert not t.put("torn2", _rec(10), torn=True)
    t2 = DiskTier(d, capacity_blocks=8, compact_threshold=0.5)
    assert sorted(t2.keys()) == ["key0", "key1", "key2", "key3"]
    assert t2.recovered_torn_bytes > 0
    for k, r in recs.items():
        got, corrupt = t2.get(k)
        assert not corrupt
        for name, arr in r["arrays"].items():
            np.testing.assert_array_equal(got["arrays"][name], arr)

    # bit-rot: flip the last payload byte on disk -> sha mismatch is a
    # VERIFIED corrupt miss, never silently-wrong KV
    with open(t2.path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    got, corrupt = t2.get("key3")
    assert got is None and corrupt

    # capacity + compaction: drops accumulate dead bytes until the
    # threshold rewrite, which keeps every live record restorable
    t3 = DiskTier(str(tmp_path / "kvt2"), capacity_blocks=2,
                  compact_threshold=0.9)
    for i in range(5):
        assert t3.put(f"c{i}", _rec(i))
        evicted = t3.enforce_capacity()
        for key, header in evicted:
            assert isinstance(header, dict)
    assert len(t3) == 2
    size_before = os.path.getsize(t3.path)
    t3.compact()
    assert os.path.getsize(t3.path) < size_before
    assert t3.dead_fraction() == 0.0
    live = sorted(t3.keys())
    assert live == ["c3", "c4"]
    for k in live:
        got, corrupt = t3.get(k)
        assert got is not None and not corrupt


# ---------------------------------------------------------------- host tier

def test_host_tier_int8_roundtrip_and_lru():
    """int8 mode requantizes f32 arrays through the canonical
    per-head-scale codes: error bounded by half a quant step; capacity
    overflow surfaces the LRU entries for the disk cascade."""
    t = HostTier(capacity_blocks=2, dtype="int8")
    rec = _rec(0)
    t.put("a", rec)
    got = t.get("a")
    for name, arr in rec["arrays"].items():
        q = got["arrays"][name]
        assert q.dtype == np.float32
        step = np.abs(arr).max(axis=(1, 2), keepdims=True) / 127.0
        assert np.all(np.abs(q - arr) <= step * 0.51 + 1e-7)

    # f32 mode is lossless
    tf = HostTier(capacity_blocks=4, dtype="float32")
    tf.put("a", rec)
    for name, arr in rec["arrays"].items():
        np.testing.assert_array_equal(tf.get("a")["arrays"][name], arr)

    # LRU overflow: oldest out first, newest two stay resident
    t.put("b", _rec(1))
    t.put("c", _rec(2))
    spilled = [k for k, _raw in t.overflow()]
    assert spilled == ["a"]
    assert sorted(t.keys()) == ["b", "c"]


class _LedgerSpy:
    """Captures the store's ledger events so tier-drop attribution is
    directly assertable without spinning up a full KVLedger."""

    def __init__(self):
        self.events = []

    def tier_demote(self, block_ids, key, tier, owner, sat=None):
        self.events.append(("demote", key, tier, owner))

    def tier_promote(self, block_ids, key, tier, owner):
        self.events.append(("promote", key, tier, owner))

    def tier_drop(self, key, tier, owner, reason=None):
        self.events.append(("drop", key, tier, owner, reason))


def test_disk_corrupt_drop_attributes_namespace(tmp_path):
    """A corrupt disk restore attributes its tier_drop to the chain's
    NAMESPACE owner (read from the index header before the drop), not
    the default tenant — per-tenant attribution survives the entry
    being gone by the time the event is emitted."""
    from paddle_tpu.serving.kv_tiers import TieredBlockStore
    led = _LedgerSpy()
    store = TieredBlockStore(
        lambda blk: {"quant": False, "arrays": _rec(int(blk))["arrays"]},
        lambda blk, arrays: None,
        host_blocks=0,                     # everything cascades to disk
        disk_dir=str(tmp_path / "kvt"))
    store.attach_ledger(led)
    assert store.demote("key0", "tenant-a", None, 0)
    assert ("demote", "key0", "disk", "tenant-a") in led.events
    assert store.residency() == {"key0": "disk"}

    faults.arm("serving.kv_restore", mode="truncate", nth=1)
    assert store.promote("key0", lambda: 1) is None
    faults.disarm_all()
    drops = [e for e in led.events if e[0] == "drop"]
    assert drops == [("drop", "key0", "disk", "tenant-a",
                      "corrupt restore")]
    assert store.residency() == {}


# ------------------------------------------------------- engine restore path

def test_tiered_restore_f32_bit_exact_and_compile_once(tiny):
    """Evict -> demote -> resubmit: the promoted chain's stream is
    bit-identical to both the warm run and the untiered oracle; the
    batched restore scatter and decode each compile EXACTLY once; the
    ledger reconciler stays clean through the full tier lifecycle."""
    prompt = _prompt(40, 26)               # 3 full cached blocks + tail
    oracle_eng = _engine(tiny)
    oracle = _run(Scheduler(oracle_eng,
                            ServingConfig(default_max_new_tokens=4)),
                  prompt)
    # tiering disabled: no store, no restore trace key — the oracle IS
    # the disabled arm
    assert oracle_eng.kv_tiers is None
    assert "tier_restore" not in oracle_eng.trace_counts

    eng = _tier_engine(tiny)
    assert eng.kv_ledger is not None
    sched = Scheduler(eng, ServingConfig(default_max_new_tokens=4))
    t1 = _run(sched, prompt)
    assert t1 == oracle                    # cold tier changes nothing

    hits0 = _counter("serving_kv_tier_hits_total", tier="host")
    freed = eng.prefix_cache.evict(999)
    assert freed == 3
    assert eng.kv_tiers.residency() == {
        k: "host" for k in eng.kv_tiers.residency()}
    assert len(eng.kv_tiers.residency()) == 3

    t2 = _run(sched, prompt)
    assert t2 == t1, "promoted-chain stream diverged from warm run"
    assert eng.trace_counts["tier_restore"] == 1
    assert eng.trace_counts["decode"] == 1
    assert _counter("serving_kv_tier_hits_total", tier="host") \
        == hits0 + 3
    assert eng.kv_tiers.residency() == {}  # promoted back out
    # the prefill-stats tap the scheduler's tier_hit/restore_ms
    # request fields ride on
    assert eng.last_prefill_stats["tier_promoted_blocks"] == 3
    assert eng.last_prefill_stats["tier_restore_s"] > 0

    rec = kvledger.LedgerReconciler(eng.kv_ledger, eng.block_pool,
                                    cache=eng.prefix_cache,
                                    tier_store=eng.kv_tiers)
    assert rec.check() == []


def test_tiered_restore_int8_within_quality_bounds(tiny):
    """int8 host tier: the restored-chain stream agrees with the warm
    f32 run within the PR 11 quantization bounds (>= 0.9 greedy token
    agreement over the decode window)."""
    prompt = _prompt(41, 26)
    eng = _tier_engine(tiny, host_tier_dtype="int8")
    sched = Scheduler(eng, ServingConfig(default_max_new_tokens=10))
    t1 = _run(sched, prompt, max_new=10)

    demote0 = _counter("serving_kv_tier_demote_total", tier="host")
    assert eng.prefix_cache.evict(999) >= 3
    assert _counter("serving_kv_tier_demote_total", tier="host") \
        >= demote0 + 3
    t2 = _run(sched, prompt, max_new=10)
    agree = sum(a == b for a, b in zip(t1, t2)) / len(t1)
    assert agree >= 0.9, f"int8 tier agreement {agree} (t1={t1} t2={t2})"
    assert eng.trace_counts["tier_restore"] == 1


def test_int8_host_tier_disk_cascade_promotes(tiny, tmp_path):
    """int8 host tier + disk cascade COMBINED (the review repro): the
    host tier requantizes records, overflow spills the raw /q8 + /s8
    code pairs to disk, and the disk restore must decode them back to
    pool-native names before the engine writers index arrays['k0'] —
    a promoted mixed-tier chain streams within the int8 bounds instead
    of dying on KeyError."""
    prompt = _prompt(48, 26)               # 3 full cached blocks + tail
    eng = _tier_engine(tiny, host_tier_dtype="int8", host_tier_blocks=1,
                       disk_tier_dir=str(tmp_path / "kvt"))
    sched = Scheduler(eng, ServingConfig(default_max_new_tokens=10))
    t1 = _run(sched, prompt, max_new=10)

    assert eng.prefix_cache.evict(999) == 3
    res = eng.kv_tiers.residency()
    assert sorted(res.values()) == ["disk", "disk", "host"], \
        "host capacity 1 should cascade the two colder blocks to disk"
    # the export path reads the same records: a peeked disk entry must
    # already be pool-native (no host-requantized /q8 or /s8 names)
    dkey = next(k for k, tier in res.items() if tier == "disk")
    rec = eng.kv_tiers.peek(dkey)
    assert rec is not None
    assert all(not n.endswith(("/q8", "/s8")) for n in rec["arrays"]), \
        sorted(rec["arrays"])

    promote0 = _counter("serving_kv_tier_promote_total", tier="disk")
    t2 = _run(sched, prompt, max_new=10)
    agree = sum(a == b for a, b in zip(t1, t2)) / len(t1)
    assert agree >= 0.9, f"disk-promoted int8 agreement {agree}"
    assert _counter("serving_kv_tier_promote_total", tier="disk") \
        == promote0 + 2
    assert eng.kv_tiers.residency() == {}


def test_chaos_spill_and_restore_degrade_to_recompute(tiny, tmp_path):
    """Both fault sites, truncate mode: a torn spill loses the entry
    (never stores it), a torn restore drops + latches corrupt — and in
    BOTH arms the resubmitted stream recomputes bit-identical to the
    no-fault run. Corrupt KV is never served."""
    prompt = _prompt(42, 26)
    eng = _tier_engine(tiny, disk_tier_dir=str(tmp_path / "kvt"))
    sched = Scheduler(eng, ServingConfig(default_max_new_tokens=4))
    t1 = _run(sched, prompt)

    # arm A: every spill tears mid-write -> nothing gains residency
    drop0 = _counter("serving_kv_tier_drop_total", tier="host")
    faults.arm("serving.kv_spill", mode="truncate", nth=1)
    assert eng.prefix_cache.evict(999) == 3
    faults.disarm_all()
    assert eng.kv_tiers.residency() == {}
    assert _counter("serving_kv_tier_drop_total", tier="host") \
        == drop0 + 3
    assert _run(sched, prompt) == t1, "torn-spill recompute diverged"

    # arm B: clean demote, then every restore read tears -> the first
    # fetch drops its entry, latches corrupt, and the request recomputes
    assert eng.prefix_cache.evict(999) == 3
    assert len(eng.kv_tiers.residency()) == 3
    corrupt0 = _counter("serving_kv_tier_corrupt_total")
    faults.arm("serving.kv_restore", mode="truncate", nth=1)
    assert _run(sched, prompt) == t1, "torn-restore recompute diverged"
    faults.disarm_all()
    assert _counter("serving_kv_tier_corrupt_total") == corrupt0 + 1
    assert len(eng.kv_tiers.residency()) == 2   # chain head dropped
    assert eng.trace_counts.get("tier_restore", 0) == 0  # never restored

    rec = kvledger.LedgerReconciler(eng.kv_ledger, eng.block_pool,
                                    cache=eng.prefix_cache,
                                    tier_store=eng.kv_tiers)
    assert rec.check() == []


def test_quota_spill_ordering_two_pass(tiny):
    """PR 17 two-pass eviction drives demotion order: the requester's
    own namespace spills to the host tier first, and a quota-protected
    foreign namespace keeps its chain HBM-resident."""
    pA, pB = _prompt(43, 26), _prompt(44, 26)   # 3 full blocks + tail
    eng = _tier_engine(tiny)
    eng.prefill(0, pA, namespace="a")
    eng.reset_slot(0)
    eng.prefill(0, pB, namespace="b")
    eng.reset_slot(0)
    eng.prefix_cache.set_quota("a", 3)

    assert eng.prefix_cache.evict(2, requester="b") == 2
    spilled = [eng.kv_tiers.host.raw(k)["ns"]
               for k in eng.kv_tiers.residency()]
    assert spilled == ["b", "b"], "requester's namespace not drained first"

    # pass 2 would reach foreign namespaces — but "a" sits at its
    # quota, so only b's last block moves and the sweep comes up short
    assert eng.prefix_cache.evict(10, requester="b") == 1
    spilled = [eng.kv_tiers.host.raw(k)["ns"]
               for k in eng.kv_tiers.residency()]
    assert sorted(spilled) == ["b", "b", "b"]
    assert eng.prefix_cache.resident("a") == 3

    # the protected chain is still a pure HBM hit
    eng.prefill(0, pA, namespace="a")
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 24
    assert eng.last_prefill_stats["tier_promoted_blocks"] == 0
    eng.reset_slot(0)


def test_ledger_tier_residency_divergence(tiny):
    """An out-of-band drop (host entry vanishes without a tier_drop
    event) is caught by the reconciler's tier_residency invariant and
    latches the divergence counter."""
    eng = _tier_engine(tiny)
    assert eng.kv_ledger is not None
    eng.prefill(0, _prompt(45, 24))
    eng.reset_slot(0)
    assert eng.prefix_cache.evict(999) == 3

    rec = kvledger.LedgerReconciler(eng.kv_ledger, eng.block_pool,
                                    cache=eng.prefix_cache,
                                    tier_store=eng.kv_tiers)
    assert rec.check() == []

    key = next(iter(eng.kv_tiers.residency()))
    eng.kv_tiers.host.drop(key)           # no event — a leak
    div0 = _counter("serving_kv_ledger_divergence_total",
                    invariant="tier_residency")
    found = rec.check()
    assert any(msg.startswith("tier_residency:") for msg in found), found
    assert _counter("serving_kv_ledger_divergence_total",
                    invariant="tier_residency") > div0


# ------------------------------------------- the hierarchy under load

def _tier_counters():
    flat = metrics.flatten_snapshot(metrics.registry().snapshot(),
                                    kinds=("counter",))
    return {k: v for k, v in flat.items()
            if k.startswith(("serving_kv_tier_",
                             "serving_kv_ledger_divergence_total"))}


def test_replay_under_pressure_cycles_chains_through_host_tier(tiny,
                                                              tmp_path):
    """A multi-stream replay whose prefix working set is wider than the
    pool can keep resident, on the virtual clock: eviction under that
    pressure DEMOTES chains and returning prompts PROMOTE them back,
    with no hand-made eviction. Then the whole working set is demoted
    and the same mixture replayed through a fresh scheduler (the cold-
    return wave). Over both, decode and the restore scatter each trace
    once (one fixed-shape program serves every run length), every
    request completes, and the ledger's tier_residency invariant stays
    clean."""
    block, num_blocks = 8, 16
    traffic = load_harness.TrafficConfig(
        users=8, requests=48, rate_rps=4000.0, prefix_pool=4,
        prefix_len=2 * block, suffix_min=1, suffix_max=2,
        max_new_tokens=2, seed=0)
    before = _tier_counters()
    engines = []
    summary = load_harness.run_harness(
        tiny, "paged", traffic, slots=8, max_len=64, block_size=block,
        num_blocks=num_blocks, virtual_step_s=0.01, engine_sink=engines,
        tier_kwargs=dict(enable_kv_tiers=True,
                         host_tier_blocks=4 * num_blocks,
                         disk_tier_dir=str(tmp_path / "disk"),
                         disk_tier_blocks=8 * num_blocks))
    eng = engines[0]
    in_replay = _tier_counters()

    def grew(now, name):
        return now.get(name, 0) - before.get(name, 0)

    assert summary["by_status"] == {"DONE": traffic.requests}, summary
    assert grew(in_replay, "serving_kv_tier_demote_total{tier=host}") > 0
    assert grew(in_replay, "serving_kv_tier_promote_total{tier=host}") > 0
    eng.prefix_cache.evict(num_blocks)
    vclock = load_harness.VirtualClock()
    load_harness.replay(Scheduler(eng, clock=vclock),
                        load_harness.synth_trace(traffic, VOCAB),
                        virtual_clock=vclock)
    after = _tier_counters()
    assert grew(after, "serving_kv_tier_promote_total{tier=host}") > \
        grew(in_replay, "serving_kv_tier_promote_total{tier=host}")
    assert not [k for k in after
                if "divergence" in k and grew(after, k)], after
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["tier_restore"] == 1
    assert kvledger.LedgerReconciler(
        eng.kv_ledger, eng.block_pool, eng.prefix_cache,
        tier_store=eng.kv_tiers).check() == []


# ------------------------------------------------------- fleet prefix cache

def test_affinity_rule_units_and_record_validation():
    """The placement rule is pure and deterministic: longest match
    wins ahead of least-loaded, min_match filters sub-block matches,
    load slack falls back to least-loaded, lowest index breaks ties —
    and a recorded affinity decision replays (or fails validation when
    its outcome lies)."""
    rule = decisions.replay_affinity_place
    # longest match beats least-loaded
    assert rule({"loads": {0: 2, 1: 0}, "matches": {0: 24, 1: 8},
                 "min_match": 8, "load_slack": 2}) == 0
    # sub-min_match matches never bind -> least-loaded
    assert rule({"loads": {0: 1, 1: 0}, "matches": {0: 4, 1: 0},
                 "min_match": 8, "load_slack": 9}) == 1
    # owner too busy -> slack fallback to least-loaded
    assert rule({"loads": {0: 3, 1: 0}, "matches": {0: 24, 1: 0},
                 "min_match": 8, "load_slack": 1}) == 1
    # match ties -> lowest worker index
    assert rule({"loads": {0: 0, 1: 0}, "matches": {0: 16, 1: 16},
                 "min_match": 8, "load_slack": 0}) == 0

    inputs = {"loads": {"0": 1, "1": 0}, "matches": {"0": 24, "1": 0},
              "min_match": 8, "load_slack": 0}
    good = decisions.build_record("place", inputs,
                                  {"worker": "1", "restored_from": "0"},
                                  "router", 1.0, tenant="t")
    assert decisions.validate_records([good]) == []
    bad = dict(good, outcome={"worker": "0"})
    errs = decisions.validate_records([bad])
    assert errs and "affinity" in errs[0]


def test_wire_restore_chaos_latches_corrupt(tiny):
    """restore_prefix's bundle-level chaos (raise AND truncate on
    serving.kv_restore) registers nothing AND latches
    serving_kv_tier_corrupt_total — a torn FLEET restore is as visible
    to the failure-class gate as a torn tier restore."""
    eng = _engine(tiny)
    prompt = _prompt(49, 26)
    c0 = _counter("serving_kv_tier_corrupt_total")

    faults.arm("serving.kv_restore", mode="truncate", nth=1)
    assert eng.restore_prefix(prompt, [], [], 24) == 0
    faults.disarm_all()
    assert _counter("serving_kv_tier_corrupt_total") == c0 + 1

    faults.arm("serving.kv_restore", mode="raise", nth=1)
    assert eng.restore_prefix(prompt, [], [], 24) == 0
    faults.disarm_all()
    assert _counter("serving_kv_tier_corrupt_total") == c0 + 2


def test_fleet_wire_restore_cross_host(tiny, tmp_path):
    """Two decode workers. r1 warms worker 0's prefix cache; a filler
    keeps worker 0 busy; r2 (same prompt) probes the fleet, finds the
    chain on 0, but zero load slack places it on worker 1 — so the
    router wire-restores 0's chain onto 1. The stream is bit-identical
    to a local recompute, the restore is a named timeline phase, and
    every decision record replays."""
    prompt = _prompt(46, 26)
    filler = _prompt(47, 26)
    max_new = 4
    oracle = _run(Scheduler(_engine(tiny),
                            ServingConfig(default_max_new_tokens=max_new)),
                  prompt, max_new=max_new)

    tl = str(tmp_path / "timeline.jsonl")
    bytes0 = _counter("serving_kv_handoff_bytes_total")
    workers = [ServingWorker(*_worker_pair(tiny), role="decode",
                             serving_config=ServingConfig(
                                 default_max_new_tokens=max_new),
                             step_interval_s=0.02)
               for _ in range(2)]
    fe = DistFrontend([w.endpoint for w in workers],
                      timeline_path=tl, prefix_affinity=True,
                      affinity_min_match=ENGINE_KW["block_size"],
                      affinity_load_slack=0)
    try:
        r1 = fe.submit(prompt, max_new=max_new)
        assert r1.worker == 0              # no match anywhere -> tie -> 0
        fe.run(timeout_s=60)
        assert r1.status == "DONE" and r1.tokens == oracle

        rf = fe.submit(filler, max_new=30)  # keeps worker 0 loaded
        assert rf.worker == 0
        r2 = fe.submit(prompt, max_new=max_new)
        fe.run(timeout_s=60)
        assert r2.status == "DONE", (r2.status, r2.error)
        assert r2.worker == 1, "slack fallback did not move the request"
        assert r2.tokens == oracle, "wire-restored stream diverged"
        assert _counter("serving_kv_handoff_bytes_total") > bytes0

        recs = fe.decision_records()
        assert decisions.validate_records(recs) == []
        place = [r for r in recs if r["action"] == "place"
                 and r["key"] == r2.key][0]
        assert str(place["outcome"].get("restored_from")) == "0"
        assert place["inputs"]["matches"], "affinity probe recorded nothing"
    finally:
        fe.close()
        for w in workers:
            w.shutdown()

    # the restore is a first-class reqtimeline phase, and the whole
    # stream (timelines + decisions) passes the serve_report validator
    records = serve_report.load(tl)
    assert serve_report.validate_records(records) == []
    r2_tl = [r for r in records if r.get("kind") == "timeline"
             and r.get("key") == r2.key][0]
    phases = {p["phase"] for p in r2_tl["phases"]}
    assert "kv_restore" in phases
