"""Kernel autotune (reference: python/paddle/incubate/autotune.py +
phi/kernels/autotune/cache.h — the runtime kernel-pick cache).

On TPU, XLA already autotunes its own fusions, so the one knob the
framework genuinely owns is Pallas kernel tiling. `autotune_flash_blocks`
measures the flash-attention (block_q, block_k) candidates for a concrete
shape ON THE DEVICE, caches the winner keyed by (backend, H, S, D, causal)
— in memory, in an optional env-path disk cache, and via the shipped
`ops/pallas/flash_blocks_tuned.json` table, the phi AlgorithmsCache role —
and `ops.flash_attention` consults the cache on every call.

The reference's dataloader/layout tuning knobs remain config-only (XLA owns
layout on TPU; the DataLoader sizes its worker pool explicitly).
"""
import json
import os
import time

_config = {"kernel": {"enable": True, "tuning_range": [1, 10]},
           "dataloader": {"enable": False},
           "layout": {"enable": False}}

# Two kernels share the table. Flash keys are UNTAGGED (the original
# format): (backend, H, S, D, causal) -> (block_q, block_k). Paged-
# attention keys lead with a kernel tag: ("paged", backend, H,
# padded_len, D, block_size) -> (q_tile, head_tile) caps. Batch size is
# NOT part of either key: tiling is set by the geometry, so a winner
# tuned at one B serves every batch size (and per-B retuning would be
# dead weight). The tag check runs BEFORE the legacy-6-tuple collapse,
# so old flash caches keep parsing and old frameworks reading a new file
# simply never look tagged keys up.
# _block_cache holds entries tuned IN THIS PROCESS (these get persisted to
# the env-path file); _disk_cache holds entries loaded from the shipped file
# and the env-path file (read-only — never written back, so a framework
# upgrade that improves flash_blocks_tuned.json is never shadowed by a stale
# frozen copy in the user cache).
_KERNEL_TAGS = ("paged",)
_block_cache = {}
_disk_cache = {}
_disk_loaded = False
# geometries whose in-memory entry is a static FALLBACK, not a measured
# winner: excluded from every disk write so they can never shadow shipped
# tuned entries in a future process (ADVICE r4)
_fallback_keys = set()
_CACHE_ENV = "PADDLE_TPU_AUTOTUNE_CACHE"


def set_config(config=None):
    if config:
        for k, v in config.items():
            if isinstance(v, dict) and isinstance(_config.get(k), dict):
                _config[k].update(v)       # per-section merge (reference
            else:                          # set_config semantics)
                _config[k] = v


def get_config():
    return dict(_config)


def kernel_tuning_enabled():
    return bool(_config.get("kernel", {}).get("enable"))


def _cache_path():
    return os.environ.get(_CACHE_ENV, "")


# Tuned blocks shipped with the framework (the phi role of the bundled
# cuDNN-heuristics tables): winners of an on-chip block sweep (PERF.md §6,
# PR 29) get committed here so every process —
# including ones with no PADDLE_TPU_AUTOTUNE_CACHE env — starts from
# chip-measured tilings. The env-path cache (per-user/runtime) overrides.
_SHIPPED_PATH = os.path.join(os.path.dirname(__file__), os.pardir, "ops",
                             "pallas", "flash_blocks_tuned.json")


def _read_cache_file(path):
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                out = {}
                for k, v in json.load(f).items():
                    key = tuple(json.loads(k))
                    if not (key and key[0] in _KERNEL_TAGS):
                        # untagged == flash
                        if len(key) == 6:  # legacy (backend,B,H,S,D,causal)
                            key = key[:1] + key[2:]
                    out[key] = tuple(v)
                return out
        except (OSError, ValueError):
            return {}
    return {}


def _load_disk_cache():
    merged = _read_cache_file(_SHIPPED_PATH)
    merged.update(_read_cache_file(_cache_path()))
    return merged


def _save_disk_cache():
    path = _cache_path()
    if path:
        try:
            # load-then-merge the env-path file only (never clobber entries
            # written by other processes sharing it; never freeze shipped
            # entries into the user cache, where they would shadow future
            # shipped updates)
            merged = _read_cache_file(path)
            merged.update({k: v for k, v in _block_cache.items()
                           if k not in _fallback_keys})
            with open(path, "w") as f:
                json.dump({json.dumps(list(k)): list(v)
                           for k, v in merged.items()}, f)
        except OSError:
            pass


def lookup_flash_blocks(B, H, S, D, causal):
    """Cached (block_q, block_k) for this geometry, or None (B is accepted
    for call-site convenience but is not part of the key). Honors the
    kernel.enable knob. Disk caches (shipped file + env path) are read once
    per process (keeping file IO off the eager dispatch path); entries tuned
    by other processes after that point become visible on the next process
    start. In-process tuned entries win over disk entries."""
    import jax
    global _disk_loaded
    if not kernel_tuning_enabled():
        return None
    key = (jax.default_backend(), H, S, D, bool(causal))
    hit = _block_cache.get(key)
    if hit is not None:
        return hit
    if not _disk_loaded:
        _disk_cache.update(_load_disk_cache())
        _disk_loaded = True
    return _disk_cache.get(key)


def lookup_paged_blocks(H, padded_len, D, block_size):
    """Tuned (q_tile, head_tile) CAPS for the paged-attention kernel's
    geometry, or None. Same caches and enable knob as the flash lookup.

    The fall-back-don't-raise contract (PR 6, extended here): a stale or
    hand-poisoned shipped entry that is not a pair of positive ints is
    treated as absent — the kernel then tiles with its own defaults —
    because an exception from a table lookup inside a traced forward is
    the worst possible place to learn the table rotted. Values are caps,
    not exact tiles: the kernel clamps each to the largest divisor of
    the live extent, so an entry tuned for one prefill bucket serves
    every bucket (and the T=1 decode shape) without retuning."""
    import jax
    global _disk_loaded
    if not kernel_tuning_enabled():
        return None
    key = ("paged", jax.default_backend(), int(H), int(padded_len), int(D),
           int(block_size))
    entry = _block_cache.get(key)
    if entry is None:
        if not _disk_loaded:
            _disk_cache.update(_load_disk_cache())
            _disk_loaded = True
        entry = _disk_cache.get(key)
    if entry is None:
        return None
    try:
        qt, ht = int(entry[0]), int(entry[1])
    except (TypeError, ValueError, IndexError):
        return None                 # rotted entry: fall back, don't raise
    if qt < 1 or ht < 1:
        return None
    return (qt, ht)


def record_flash_blocks(H, S, D, causal, blocks, persist=True):
    """Record an externally-measured (block_q, block_k) winner for a
    geometry (an on-chip sweep) and persist it to the env-path
    cache if configured. persist=False keeps the entry in-memory only —
    used for static FALLBACK results, which must never shadow shipped
    tuned entries at the next load (ADVICE r4)."""
    import jax
    key = (jax.default_backend(), H, S, D, bool(causal))
    _block_cache[key] = tuple(blocks)
    if persist:
        _fallback_keys.discard(key)
        _save_disk_cache()
    else:
        _fallback_keys.add(key)


def commit_shipped_table(entries, backend="tpu", path=None, kernel="flash"):
    """Commit measured winners into the SHIPPED table
    (`ops/pallas/flash_blocks_tuned.json`) — the path on-chip sweep
    results take into the tree, using the exact
    cache serialization the lookups read back.

    kernel="flash": entries {(H, S, D, causal): (block_q, block_k)}.
    kernel="paged": entries {(H, padded_len, D, block_size):
    (q_tile, head_tile)} — the paged-attention kernel's tile caps,
    served back by `lookup_paged_blocks`. Existing shipped entries for
    other geometries/kernels are preserved (load-then-merge). The
    in-process disk cache is invalidated so the committing process sees
    its own commit."""
    global _disk_loaded
    if kernel not in ("flash",) + _KERNEL_TAGS:
        raise ValueError(f"unknown kernel {kernel!r}; want 'flash' or one "
                         f"of {_KERNEL_TAGS}")
    path = path or _SHIPPED_PATH
    merged = _read_cache_file(path)
    for key, blocks in entries.items():
        if kernel == "paged":
            H, L, D, bs = key
            qt, ht = int(blocks[0]), int(blocks[1])
            if qt < 1 or ht < 1:
                raise ValueError(f"paged tile caps {blocks} must be "
                                 f"positive ints")
            if int(L) % int(bs):
                raise ValueError(f"padded_len {L} is not a multiple of "
                                 f"block_size {bs}")
            merged[("paged", backend, int(H), int(L), int(D), int(bs))] = \
                (qt, ht)
            continue
        H, S, D, causal = key
        bq, bk = int(blocks[0]), int(blocks[1])
        if bq <= 0 or bk <= 0 or bq % 8 or bk % 8:
            raise ValueError(f"blocks {blocks} must be positive multiples "
                             f"of 8 (TPU sublane alignment)")
        if S % bq or S % bk:
            raise ValueError(f"blocks {blocks} do not tile S={S}")
        if max(bq, bk) % min(bq, bk):
            # the kernels walk a group of max(bq, bk) rows at a time;
            # committing a pair that cannot would ship an entry the
            # runtime guard ignores — reject it here instead
            raise ValueError(f"one block must divide the other, got "
                             f"{blocks}")
        merged[(backend, int(H), int(S), int(D), bool(causal))] = (bq, bk)
    with open(path, "w") as f:
        json.dump({json.dumps(list(k)): list(v)
                   for k, v in sorted(merged.items())}, f, indent=1)
    _disk_cache.clear()
    _disk_loaded = False
    return path


def autotune_flash_blocks(B, H, S, D, causal=True, dtype="bfloat16",
                          candidates=(128, 256, 512), n_iters=3):
    """Measure every (block_q, block_k) pair of the candidates on the live
    backend and cache the fastest; the two need not be equal, causal or
    not (`ops.pallas.flash_attention.FlashPlan`). Returns (block_q,
    block_k). Candidates that don't divide S or fail to compile are
    skipped; measurement uses a host fetch of a result element as the sync
    (it cannot complete before the work)."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.flash_attention import flash_attention

    hit = lookup_flash_blocks(B, H, S, D, causal)
    if hit is not None:
        return hit
    if not kernel_tuning_enabled():
        from ..ops.pallas.flash_attention import default_block
        b = default_block(S)
        return (b, b)

    q = (jax.random.normal(jax.random.key(0), (B, H, S, D)) * 0.1) \
        .astype(dtype)
    interpret = jax.default_backend() != "tpu"
    best, best_dt = None, float("inf")
    fits = [b for b in candidates if b <= S and S % b == 0]
    for bq, bk in [(a, b) for a in fits for b in fits]:
        try:
            f = jax.jit(lambda q, bq=bq, bk=bk: flash_attention(
                q, q, q, causal=causal, block_q=bq, block_k=bk,
                interpret=interpret))
            float(jnp.ravel(f(q))[0].astype(jnp.float32))    # compile+warm
            t0 = time.perf_counter()
            for _ in range(n_iters):
                float(jnp.ravel(f(q))[0].astype(jnp.float32))
            dt = time.perf_counter() - t0
        except Exception:                                    # noqa: BLE001
            continue
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    fallback = best is None
    if fallback:
        from ..ops.pallas.flash_attention import default_block
        b = default_block(S)           # always divides S (never poisons cache)
        best = (b, b)
    # fallbacks stay in-memory only: a persisted fallback would override the
    # shipped tuned table for this geometry on every future load (ADVICE r4)
    record_flash_blocks(H, S, D, causal, best, persist=not fallback)
    return best
