"""TieredBlockStore: the host/disk tier orchestrator one engine owns.

Sits behind the PrefixCache: eviction calls `demote` (instead of just
freeing), `match` calls `promote` when the HBM walk breaks on a key a
colder tier still holds. Device I/O goes through two engine-provided
callbacks — `read_block(blk) -> {name: np.ndarray}` (eager per-layer
gathers) and `write_block(blk, arrays)` (eager `.at[].set` updates with
`jax.device_put` prefetch issued first) — so promote/demote are host +
transfer work ONLY: no new traced programs, and the decode executable's
compile-once contract survives tiering by construction.

Chaos sites: `serving.kv_spill` fires per tier write (truncate tears
the spill — the entry is lost, a later match misses and recomputes),
`serving.kv_restore` fires per restore attempt on a resident key
(truncate feeds the sha256 verify a torn payload; raise models a failed
read). Either way the degradation is miss-and-recompute, never wrong
KV, and `serving_kv_tier_corrupt_total` latches verify failures as a
failure-class signal.

Ledger contract: `tier_demote` when an entry gains cold residency (or
moves host->disk), `tier_promote` when it returns to HBM, `tier_drop`
when it is discarded — the reconciler's `tier_residency` invariant
compares the shadow's {key: tier} map against `residency()` every
scheduler step.
"""
import time

from ...observability import faults as _faults
from ...observability import metrics as _metrics
from .disk import DiskTier
from .host import HostTier

__all__ = ["TieredBlockStore"]

_C_HITS = _metrics.counter(
    "serving_kv_tier_hits_total",
    "Tier lookups that found a restorable entry, per tier",
    labelnames=("tier",))
_C_MISSES = _metrics.counter(
    "serving_kv_tier_misses_total",
    "Tier lookups that found nothing (or found corruption), per tier",
    labelnames=("tier",))
_C_DEMOTE = _metrics.counter(
    "serving_kv_tier_demote_total",
    "Blocks demoted into a tier (HBM->host, host->disk)",
    labelnames=("tier",))
_C_PROMOTE = _metrics.counter(
    "serving_kv_tier_promote_total",
    "Blocks promoted back into HBM, per source tier",
    labelnames=("tier",))
_C_DROP = _metrics.counter(
    "serving_kv_tier_drop_total",
    "Tiered blocks discarded (capacity, torn spill, corrupt restore)",
    labelnames=("tier",))
_C_CORRUPT = _metrics.counter(
    "serving_kv_tier_corrupt_total",
    "Tier restores that failed verification (torn payload, sha256 "
    "mismatch) — failure-class: the chain degraded to recompute")
_G_BLOCKS = _metrics.gauge(
    "serving_kv_tier_blocks", "Blocks resident per cold tier",
    labelnames=("tier",))
_H_RESTORE = _metrics.histogram(
    "serving_kv_restore_seconds",
    "Wall seconds per block promoted from a cold tier back into HBM "
    "(fetch + verify + device write)")

_OWNER_DEFAULT = "default"


def corrupt_counter():
    """The failure-class corrupt-restore counter, shared with the
    engine's fleet wire-restore path: `restore_prefix` latches it on a
    chaos raise/truncate so a torn CROSS-HOST restore is as visible to
    the metrics_report failure-class gate as a torn tier restore."""
    return _C_CORRUPT


class TieredBlockStore:
    def __init__(self, read_block, write_block, host_blocks=64,
                 host_dtype="float32", disk_dir=None, disk_blocks=256,
                 disk_compact_threshold=0.5, write_blocks=None):
        self._read = read_block
        self._write = write_block
        self._write_many = write_blocks
        self.host = HostTier(host_blocks, host_dtype)
        self.disk = None
        if disk_dir is not None:
            self.disk = DiskTier(disk_dir, disk_blocks,
                                 disk_compact_threshold)
        self._ledger = None
        self._export()

    def attach_ledger(self, ledger):
        self._ledger = ledger
        # a recovered disk log predates this process's event stream:
        # re-emit its residency so the shadow model starts consistent
        if self.disk is not None:
            for key in self.disk.keys():
                header = self.disk.header(key) or {}
                ledger.tier_demote((), key, "disk",
                                   self._owner(header.get("ns")))

    @staticmethod
    def _owner(ns):
        return ns if ns is not None else _OWNER_DEFAULT

    def _export(self):
        _G_BLOCKS.labels(tier="host").set(len(self.host))
        _G_BLOCKS.labels(tier="disk").set(
            len(self.disk) if self.disk is not None else 0)

    # -- residency -----------------------------------------------------------
    def __contains__(self, key):
        return key in self.host or \
            (self.disk is not None and key in self.disk)

    def residency(self):
        """{key: "host"|"disk"} — what the ledger reconciler's
        tier_residency invariant compares the shadow model against."""
        out = dict.fromkeys(
            self.disk.keys() if self.disk is not None else (), "disk")
        out.update(dict.fromkeys(self.host.keys(), "host"))
        return out

    # -- demote (PrefixCache eviction hook) ----------------------------------
    def demote(self, key, namespace, parent, blk):
        """Capture block `blk`'s KV (via the engine reader — the block
        is still allocated when the eviction hook runs) into the host
        tier; True when the chain entry gained cold residency. Host
        overflow cascades the coldest entries to disk (or drops them).
        """
        owner = self._owner(namespace)
        rec = {"ns": namespace, "parent": parent}
        rec.update(self._read(blk))
        spec = _faults.fire("serving.kv_spill")
        if spec is not None and spec.mode == "truncate":
            # torn host spill: the entry is never stored — the chain is
            # lost (a later match misses and recomputes), never corrupt
            _C_DROP.labels(tier="host").inc()
            self._export()
            return False
        self.host.put(key, rec)
        _C_DEMOTE.labels(tier="host").inc()
        if self._ledger is not None:
            self._ledger.tier_demote((int(blk),), key, "host", owner,
                                     sat=self.host.last_put_saturation)
        self._spill_overflow()
        self._export()
        return True

    def _spill_overflow(self):
        """Move the host tier's beyond-capacity LRU entries to disk
        (raw — a host-requantized record ships its codes as-is), or
        drop them when no disk tier is configured / the spill tears."""
        for key, raw in self.host.overflow():
            owner = self._owner(raw.get("ns"))
            if self.disk is None:
                _C_DROP.labels(tier="host").inc()
                if self._ledger is not None:
                    self._ledger.tier_drop(key, "host", owner,
                                           reason="capacity")
                continue
            spec = _faults.fire("serving.kv_spill")
            torn = spec is not None and spec.mode == "truncate"
            if self.disk.put(key, raw, torn=torn):
                _C_DEMOTE.labels(tier="disk").inc()
                if self._ledger is not None:
                    self._ledger.tier_demote((), key, "disk", owner)
                for dkey, header in self.disk.enforce_capacity():
                    _C_DROP.labels(tier="disk").inc()
                    if self._ledger is not None:
                        self._ledger.tier_drop(
                            dkey, "disk", self._owner(header.get("ns")),
                            reason="capacity")
            else:
                _C_DROP.labels(tier="host").inc()
                if self._ledger is not None:
                    self._ledger.tier_drop(key, "host", owner,
                                           reason="torn spill")

    # -- restore -------------------------------------------------------------
    def _fetch(self, key):
        """(record, tier) for a resident key after firing the restore
        chaos site and verifying content; (None, None) on miss, torn
        read, raise-mode failure, or sha mismatch — every failure
        already counted/latched here."""
        in_host = key in self.host
        in_disk = self.disk is not None and key in self.disk
        if not in_host and not in_disk:
            return None, None
        tier = "host" if in_host else "disk"
        try:
            spec = _faults.fire("serving.kv_restore")
        except Exception:                                    # noqa: BLE001
            # failed restore I/O: a miss, not an error — recompute
            _C_MISSES.labels(tier=tier).inc()
            return None, None
        torn = spec is not None and spec.mode == "truncate"
        if in_host:
            if torn:
                # torn host read: drop + latch corruption, degrade to
                # miss — the HBM recompute path owns the request now
                raw = self.host.raw(key)
                owner = self._owner((raw or {}).get("ns"))
                self.host.drop(key)
                _C_CORRUPT.inc()
                _C_DROP.labels(tier="host").inc()
                _C_MISSES.labels(tier="host").inc()
                if self._ledger is not None:
                    self._ledger.tier_drop(key, "host", owner,
                                           reason="torn restore")
                self._export()
                return None, None
            rec = self.host.get(key)
            _C_HITS.labels(tier="host").inc()
            return rec, "host"
        # owner from the index header BEFORE disk.get — a corrupt
        # restore drops the entry, taking the namespace with it
        header = self.disk.header(key) or {}
        rec, corrupt = self.disk.get(key, torn=torn)
        if rec is None:
            _C_MISSES.labels(tier="disk").inc()
            if corrupt or torn:
                _C_CORRUPT.inc()
                _C_DROP.labels(tier="disk").inc()
                if self._ledger is not None:
                    self._ledger.tier_drop(
                        key, "disk", self._owner(header.get("ns")),
                        reason="corrupt restore")
                self._export()
            return None, None
        _C_HITS.labels(tier="disk").inc()
        # disk records spilled by an int8 host tier still carry their
        # requantized /q8 + /s8 code pairs (the cascade serialized the
        # raw host record) — reconstitute pool-native arrays before the
        # engine writers index arrays["k0"]. A no-op for f32 records.
        return HostTier._decode(rec), "disk"

    def peek(self, key):
        """Verified record without promotion (the fleet export path
        reads a chain's tiered continuation to ship it to a peer — the
        entry stays resident here)."""
        rec, _tier = self._fetch(key)
        return rec

    def promote(self, key, alloc):
        """Full promotion of one block: fetch + verify, `alloc()` an
        HBM block (returns a block id, or None under pressure — the
        caller's reserve-headroom rule), eager device write, finalize
        residency + ledger. Returns (blk, record) or None; on None
        nothing moved (a verified-corrupt entry was dropped by _fetch).
        """
        t0 = time.perf_counter()
        rec, tier = self._fetch(key)
        if rec is None:
            return None
        blk = alloc()
        if blk is None:
            return None                 # entry stays tiered; no churn
        self._write(int(blk), rec["arrays"])
        if tier == "host":
            self.host.drop(key)
        else:
            self.disk.drop(key)
        _C_PROMOTE.labels(tier=tier).inc()
        _H_RESTORE.observe(time.perf_counter() - t0)
        if self._ledger is not None:
            self._ledger.tier_promote((int(blk),), key, tier,
                                      self._owner(rec.get("ns")))
        self._export()
        return int(blk), rec

    def promote_run(self, keys, alloc_run):
        """Batched promotion of a contiguous chain run: fetch + verify
        every record first (stopping at the first miss/corruption —
        each failure already counted by `_fetch`), allocate that many
        HBM blocks in ONE call (`alloc_run(n) -> [block_id] or None`),
        and hand the whole run to the engine's batched writer — one
        transfer + one scatter per pool array instead of one per
        (block, layer) — before finalizing residency + ledger per
        entry. Returns [(key, block_id)] in chain order ([] when
        nothing restorable or the allocation was refused; unwritten
        entries stay tiered)."""
        t0 = time.perf_counter()
        runs = []
        for key in keys:
            rec, tier = self._fetch(key)
            if rec is None:
                break
            runs.append((key, rec, tier))
        if not runs:
            return []
        blks = alloc_run(len(runs))
        if blks is None:
            return []
        blks = [int(b) for b in blks]
        if self._write_many is not None:
            self._write_many(blks, [rec["arrays"] for _, rec, _ in runs])
        else:
            for blk, (_, rec, _) in zip(blks, runs):
                self._write(blk, rec["arrays"])
        dt = (time.perf_counter() - t0) / len(runs)
        out = []
        for blk, (key, rec, tier) in zip(blks, runs):
            if tier == "host":
                self.host.drop(key)
            else:
                self.disk.drop(key)
            _C_PROMOTE.labels(tier=tier).inc()
            _H_RESTORE.observe(dt)
            if self._ledger is not None:
                self._ledger.tier_promote((blk,), key, tier,
                                          self._owner(rec.get("ns")))
            out.append((key, blk))
        self._export()
        return out

    # -- invalidation --------------------------------------------------------
    def discard(self, key, reason="invalidated"):
        """Drop `key` from whichever tier holds it (namespace flush,
        explicit invalidation)."""
        dropped = False
        for tier, store in (("host", self.host), ("disk", self.disk)):
            if store is None or key not in store:
                continue
            raw = store.raw(key) if tier == "host" else None
            owner = self._owner((raw or {}).get("ns"))
            store.drop(key)
            _C_DROP.labels(tier=tier).inc()
            if self._ledger is not None:
                self._ledger.tier_drop(key, tier, owner, reason=reason)
            dropped = True
        self._export()
        return dropped

    # -- report taps ---------------------------------------------------------
    def stats(self):
        sat = self.host.saturation_stats()
        return {
            "host_blocks": len(self.host),
            "disk_blocks": len(self.disk) if self.disk is not None else 0,
            "disk_dead_fraction": round(self.disk.dead_fraction(), 4)
            if self.disk is not None else 0.0,
            "host_requant_saturation": {
                "samples": sat["samples"],
                "mean": round(sat["mean"], 4),
                "max": round(sat["max"], 4),
            },
        }
