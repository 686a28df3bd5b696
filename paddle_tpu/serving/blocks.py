"""Paged KV memory: fixed-size block pool + block-table attention.

The PR 3 cache (`kv_cache.py`) preallocates one dense
`[slots, max_len, heads, head_dim]` buffer per layer — one implicit
max_len-sized block per slot. At scale that layout fragments: every slot
reserves its worst case, so concurrency is bounded by
`budget // max_len` even when the live requests are short, and two
requests sharing a system prompt store its K/V twice. This module is the
real PagedAttention shape [SOSP '23]: K/V live in a pool of fixed-size
blocks (`[num_blocks, block_size, heads, head_dim]` per layer), each
slot owns a small int32 *block table* mapping logical block index ->
physical block id, and attention gathers the slot's blocks back into a
contiguous view before running the exact same masked math as the dense
path — token-exact by construction, and the avals (pool, tables, pos)
never change shape, so the decode step still compiles exactly once.

Two halves:

  - device (pure jnp, used inside the jitted executables): `alloc_pools`,
    `write` (scatter new tokens into their blocks), `gather`, `attend`
    (gather + `kv_cache.attend`).
  - host (the allocator): `BlockPool` — free list + per-block refcounts,
    so the prefix cache can share blocks copy-on-write across requests
    (a shared block is never written; sharing is full-block-granular).
    `serving.block_alloc` is a fault-injection site, and pool occupancy
    is exported through the metrics registry.

Block id 0 is RESERVED as the garbage block: unallocated table entries
point at it, so stray writes from right-padded prefill tails land there
harmlessly and the gather for masked positions reads it invisibly.
"""
import collections
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import faults as _faults
from ..observability import metrics as _metrics
from . import kv_cache as kvc

__all__ = ["BlockAllocError", "BlockPool", "PagedLayerKV",
           "QuantPagedLayerKV", "PagedDecodeCache", "LatentSpec",
           "StateSpec", "NoCache", "WindowSpec", "LatentLayer",
           "StateLayer", "SlotStateStore", "alloc_layers", "gather_rows",
           "window_of", "window_write", "alloc_pools",
           "alloc_quant_pools", "write", "quant_write", "gather",
           "gather_quant", "dequant", "attend", "attend_quant",
           "attend_kernel", "attend_kernel_quant", "attention_impl",
           "attention_scope", "kernel_attends",
           "current_attention_impl", "blocks_for_tokens", "GARBAGE_BLOCK",
           "QMAX"]

GARBAGE_BLOCK = 0

# int8 symmetric quantization range: codes in [-127, 127], scale = the
# per-block per-head abs-max, dequant = code * scale / QMAX — the same
# math as quantization.fake_quant at bits=8 (qmax = 2^(8-1) - 1), which
# is the reference the quality tests compare against.
QMAX = 127.0

_M_POOL_TOTAL = _metrics.gauge(
    "serving_block_pool_blocks_total",
    "Allocatable KV blocks in the live engine's pool (garbage block "
    "excluded)")
_M_POOL_IN_USE = _metrics.gauge(
    "serving_block_pool_blocks_in_use",
    "KV blocks currently referenced (request tables + prefix cache)")


class BlockAllocError(RuntimeError):
    """Block pool exhausted — allocation pressure, the scheduler's cue to
    evict prefix-cache entries or preempt a victim request."""


# One layer's paged K/V: [num_blocks, block_size, heads, head_dim] pools.
PagedLayerKV = collections.namedtuple("PagedLayerKV", ["k", "v"])

# One layer's QUANTIZED paged K/V: int8 pools of the same shape plus the
# per-block per-head scale arrays ([num_blocks, heads] float32) that ride
# NEXT TO them — a physical block's token K/V dequantizes as
# `code * scale[block, head] / QMAX`. Scales are part of block identity:
# sharing a block (prefix cache, COW) shares its scale row, and freeing
# it retires both together (the scale row is simply overwritten by the
# next writer, like the codes).
QuantPagedLayerKV = collections.namedtuple(
    "QuantPagedLayerKV", ["k", "v", "k_scale", "v_scale"])

# Whole-model paged cache: `layers` tuple of PagedLayerKV, `tables` int32
# [slots, max_blocks_per_slot] physical block ids (0 == garbage), `pos`
# int32 [slots] tokens written per slot — same role as DecodeCache.pos.
# `valid` (optional, int32 [S] or None) is how many of a write's T tokens
# are REAL per slot: prefill feeds bucket-PADDED ids, and a quantized
# pool must not let the padding tokens' K/V inflate the tail block's
# abs-max scale (the float path never cared — padding is position-masked
# out of attention either way). None means all T tokens are real (decode,
# verify, the float path). `slot` (int32 scalar or None) is the slot a
# prefill fills: a layer whose cache is per slot and not paged (StateLayer)
# writes that row; None means every slot advances (decode).
PagedDecodeCache = collections.namedtuple(
    "PagedDecodeCache", ["layers", "tables", "pos", "valid", "slot"],
    defaults=(None, None))

# What a layer of a model declares it caches (`model.cache_layout()`, one
# spec a layer), and the arrays the engine allocates for each. The kinds
# live side by side in one pool tuple, under one block table and one
# allocator, and are donated together:
#   LatentSpec(width)   one row of `width` values a token, paged like K/V:
#                       LatentLayer(rows [num_blocks, block_size, width])
#   StateSpec(state, tail)  a fixed-size state a SLOT, whatever its length
#                       (a linear-attention layer's matrix and the last
#                       inputs of its short convolution): StateLayer(
#                       state [slots, *state] float32, tail [slots, *tail])
#   NoCache()           nothing: a layer that is a feed-forward alone. It
#                       is its own (empty) layer in the pool tuple, so the
#                       pool keeps one entry a layer
#   WindowSpec(width, window, chunk)  rows of `width` values of two
#                       lifetimes under the one table (below): a LatentLayer
# Softmax attention with few key/value heads needs no third kind: a token's
# keys and values of all its key/value heads are one LatentSpec row.
# A model without `cache_layout` (GPT) caches K and V per layer, as above.
LatentSpec = collections.namedtuple("LatentSpec", ["width"])
StateSpec = collections.namedtuple("StateSpec", ["state", "tail"])
NoCache = collections.namedtuple("NoCache", [])
LatentLayer = collections.namedtuple("LatentLayer", ["rows"])
StateLayer = collections.namedtuple("StateLayer", ["state", "tail"])


class WindowSpec(collections.namedtuple("WindowSpec",
                                        ["width", "window", "chunk"])):
    """A layer that keeps a token's row only while the token's window of
    `window` positions is open, and one summary row per `chunk` positions
    for as long as the request lives (windows are aligned blocks: position
    p lies in window p // window). Rows of both kinds are `width` wide and
    live in one LatentLayer; the layout, not the engine, says how many
    blocks a slot of n tokens needs, where a position writes and what a
    query sees:

      table entries [0, window / block_size): a RING of token blocks.
        Position p writes entry (p % window) // block_size, row
        p % block_size; the entries are allocated as the first window grows
        and written again by every later window. Visibility is by
        position, so nothing is cleared.
      the entries behind them: summaries. Chunk c writes entry
        window / block_size + c // block_size, row c % block_size.

    So the dense view of a slot (`gather_rows`) is `window` ring rows, then
    one row a chunk, and a query at p sees ring rows [0, p % window] and the
    summaries of every chunk of every CLOSED window."""
    __slots__ = ()

    def ring_blocks(self, block_size):
        return self.window // block_size

    def table_blocks(self, max_len, block_size):
        """Table entries a slot of up to `max_len` positions may use."""
        chunks = -(-int(max_len) // self.chunk)
        return self.ring_blocks(block_size) + -(-chunks // block_size)

    def blocks_for(self, n_tokens, block_size):
        """Blocks a slot of `n_tokens` tokens holds: the ring as far as
        the first window has grown, and a summary row a chunk begun."""
        n = int(n_tokens)
        chunks = -(-n // self.chunk)
        return min(-(-n // block_size), self.ring_blocks(block_size)) \
            + -(-chunks // block_size)

    def entries(self, first, last, block_size):
        """Table entries that tokens at positions first..last write: their
        ring blocks and their chunks' summary blocks."""
        first, last, ring = int(first), int(last), \
            self.ring_blocks(block_size)
        a, b = (p % self.window // block_size for p in (first, last))
        if last - first >= self.window - 1:
            held = range(ring)                       # a whole window's
        elif first // self.window == last // self.window:
            held = range(a, b + 1)
        else:                                   # across a window's edge
            held = sorted(set(range(a, ring)) | set(range(b + 1)))
        return [*held, *range(ring + first // self.chunk // block_size,
                              ring + last // self.chunk // block_size + 1)]

    def visible_rows(self, pos):
        """(ring rows, summary rows) a query at position `pos` (an int or
        an array of them) scores, its own row among the first."""
        pos = np.asarray(pos)
        return pos % self.window + 1, \
            pos // self.window * (self.window // self.chunk)

    def prefill_pairs(self, n_tokens):
        """(query, visible row) pairs of a prompt of `n_tokens` tokens from
        position 0: `sum(visible_rows(p))` over it, in closed form."""
        n, w = int(n_tokens), self.window
        full, rest = divmod(n, w)
        ring = full * w * (w + 1) // 2 + rest * (rest + 1) // 2
        return ring + (w // self.chunk) * (w * full * (full - 1) // 2
                                           + rest * full)


def window_of(layout, block_size=None):
    """The WindowSpec of a model's `cache_layout()`, or None where no layer
    declares one (or there is no layout at all). One table a slot serves
    every paged layer, so every paged layer must then declare the SAME
    geometry: window layers beside one-row-a-token layers, or windows of two
    sizes, would need a table each (ROADMAP B4's other half)."""
    paged = {s for s in layout or () if isinstance(s, (LatentSpec,
                                                       WindowSpec))}
    spec = next((s for s in paged if isinstance(s, WindowSpec)), None)
    if spec is None:
        return None
    if len({(type(s), *s[1:]) for s in paged}) != 1:
        raise ValueError(
            f"one block table a slot: every paged layer must declare the "
            f"same window geometry, got {sorted(map(repr, paged))}")
    if spec.window % spec.chunk or (block_size and (
            spec.window % block_size or spec.chunk > spec.window)):
        raise ValueError(
            f"{spec!r}: the window must be whole chunks and whole blocks "
            f"of {block_size}")
    return spec


def blocks_for_tokens(n_tokens, block_size):
    """Logical blocks needed to hold n_tokens."""
    return -(-int(n_tokens) // int(block_size))


def alloc_pools(num_layers, num_blocks, block_size, num_heads, head_dim,
                dtype=jnp.float32):
    """Zeroed K/V pools for a whole model: one PagedLayerKV per layer."""
    shape = (num_blocks, block_size, num_heads, head_dim)
    return tuple(PagedLayerKV(jnp.zeros(shape, dtype),
                              jnp.zeros(shape, dtype))
                 for _ in range(num_layers))


def alloc_layers(layout, num_blocks, block_size, slots, dtype):
    """Zeroed cache arrays for a model that declares its layers' caches:
    one LatentLayer, StateLayer or (for a layer that caches nothing)
    NoCache per spec of `layout`. Latent rows and convolution tails take
    `dtype`; the recurrent state is float32."""
    out = []
    for spec in layout:
        if isinstance(spec, (LatentSpec, WindowSpec)):
            out.append(LatentLayer(jnp.zeros(
                (num_blocks, block_size, spec.width), dtype)))
        elif isinstance(spec, StateSpec):
            out.append(StateLayer(
                jnp.zeros((slots,) + tuple(spec.state), jnp.float32),
                jnp.zeros((slots,) + tuple(spec.tail), dtype)))
        elif isinstance(spec, NoCache):
            out.append(spec)
        else:
            raise TypeError(f"unknown cache spec {spec!r}")
    return tuple(out)


def layout_bytes(layout, block_size, dtype):
    """(bytes one pool block pins over the latent layers, bytes one slot's
    state pins over the state layers)."""
    item = np.dtype(dtype).itemsize
    block = sum(block_size * s.width * item for s in layout
                if isinstance(s, (LatentSpec, WindowSpec)))
    slot = sum(4 * int(np.prod(s.state)) + item * int(np.prod(s.tail))
               for s in layout if isinstance(s, StateSpec))
    return block, slot


def alloc_quant_pools(num_layers, num_blocks, block_size, num_heads,
                      head_dim):
    """Zeroed INT8 K/V pools + per-block per-head scale arrays: one
    QuantPagedLayerKV per layer. At equal token capacity the pool bytes
    are dtype-bytes/1 of the float pools, with a `4 * heads` bytes/block
    scale overhead (~1/(block_size*head_dim) relative — negligible)."""
    shape = (num_blocks, block_size, num_heads, head_dim)
    sshape = (num_blocks, num_heads)
    return tuple(QuantPagedLayerKV(jnp.zeros(shape, jnp.int8),
                                   jnp.zeros(shape, jnp.int8),
                                   jnp.zeros(sshape, jnp.float32),
                                   jnp.zeros(sshape, jnp.float32))
                 for _ in range(num_layers))


def write(pool, new, tables, pos):
    """Scatter `new` [S, T, h, d] token K/V into `pool`
    [N, block_size, h, d] at logical positions `pos + 0..T-1` of each
    slot, routed through `tables` [S, max_blocks]. Positions past the
    table (right-padded prefill tails) and unallocated logical blocks
    land in the garbage block. Shapes are static — same trace for every
    call."""
    T = new.shape[1]
    bs = pool.shape[1]
    nb = tables.shape[1]
    positions = pos.astype(jnp.int32)[:, None] \
        + jnp.arange(T, dtype=jnp.int32)[None, :]          # [S, T]
    lb = positions // bs
    off = positions % bs
    phys = jnp.take_along_axis(tables.astype(jnp.int32),
                               jnp.minimum(lb, nb - 1), axis=1)
    phys = jnp.where(lb < nb, phys, GARBAGE_BLOCK)
    return pool.at[phys, off].set(new.astype(pool.dtype))


def window_write(pool, new, tables, entry, row, live):
    """Scatter `new` [S, T, width] into the latent `pool` [N, block_size,
    width] at table entry `entry` [S, T], row `row` [S, T] of each slot's
    `tables` [S, max_blocks]: the write of a layer whose layout, not the
    position alone, says where a row goes (`WindowSpec`). Rows that are not
    `live` [S, T] land in the garbage block."""
    nb = tables.shape[1]
    entry = entry.astype(jnp.int32)
    phys = jnp.take_along_axis(tables.astype(jnp.int32),
                               jnp.minimum(entry, nb - 1), axis=1)
    phys = jnp.where(live & (entry < nb), phys, GARBAGE_BLOCK)
    return pool.at[phys, row.astype(jnp.int32)].set(new.astype(pool.dtype))


def dequant(codes, scale):
    """Dequantize int8 block codes [..., block_size, heads, head_dim]
    against per-block per-head scales [..., heads]:
    `code * (scale / QMAX)`. The multiplication ORDER is part of the
    contract — the Pallas kernel computes the identical expression, so
    the kernel and gather paths see bit-identical dequantized values."""
    return dequant_codes(codes, scale[..., None, :, None])


def dequant_codes(codes, scale_b):
    """THE canonical dequant expression over a broadcast-ready scale:
    `code * (scale / QMAX)` — multiplication ORDER included, the Pallas
    kernel computes the identical expression in VMEM. Every dequant in
    the package (per-head KV pools here, per-channel decode weights in
    `engine._dequant_params`) must route through this one helper so a
    precision tweak can never diverge the paths."""
    return codes.astype(jnp.float32) * (scale_b / QMAX)


def quantize_codes(x, scale_b):
    """THE canonical quantize expression over a broadcast-ready POSITIVE
    scale: fake-quant round/clip to int8 codes. The inverse partner of
    `dequant_codes`; shared by the KV write path and the decode-weight
    quantizer for the same single-expression reason."""
    q = jnp.clip(jnp.round(x / scale_b * QMAX), -QMAX, QMAX)
    return q.astype(jnp.int8)


def _quantize(x, scale):
    """x [..., bs, h, d] f32 -> int8 codes against per-head scales
    [..., h] (abs-max symmetric; zero-scale blocks quantize to 0)."""
    return quantize_codes(x, jnp.maximum(scale, 1e-30)[..., None, :, None])


def quant_write(pool, scale, new, tables, pos, valid=None):
    """The quantizing `write`: scatter `new` [S, T, h, d] float token
    K/V into the INT8 `pool` [N, bs, h, d] + `scale` [N, h], routed
    through `tables` exactly like `write`. Returns (pool', scale').

    Scale maintenance is per touched block: the write gathers every
    physical block the S slots' new tokens land in, dequantizes the
    already-resident positions (positions < pos — later positions hold
    junk that must not poison the scale), overlays the new tokens,
    recomputes the per-head abs-max over all valid positions
    (< pos + valid; `valid` [S] defaults to T), and requantizes the
    whole block. `valid < T` is the bucket-PADDED prefill: the padding
    tokens' K/V must neither ride the abs-max scale (a one-time
    inflated rounding the later re-zeroing could never undo) nor leave
    nonzero codes. Fully-written earlier blocks are never touched, so
    their codes and scales are immutable — which is what makes
    prefix-cache sharing of quantized blocks safe. Shapes are static:
    the same trace serves every call."""
    S, T = new.shape[0], new.shape[1]
    bs = pool.shape[1]
    nb = tables.shape[1]
    pos = pos.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    # tight static bound on blocks one slot's T-token write can touch:
    # positions pos..pos+T-1 span at most (pos%bs + T - 1)//bs + 1
    # blocks, maximized at pos%bs == bs-1 — for the T=1 decode hot path
    # this is exactly ONE block per slot, not two
    nblk = (T + bs - 2) // bs + 1
    base = pos // bs                                             # [S]
    tlb = base[:, None] + jnp.arange(nblk, dtype=jnp.int32)[None, :]
    phys = jnp.take_along_axis(tables, jnp.minimum(tlb, nb - 1), axis=1)
    phys = jnp.where(tlb < nb, phys, GARBAGE_BLOCK)              # [S, nblk]
    blk_q = pool[phys]                             # [S, nblk, bs, h, d]
    blk_s = scale[phys]                            # [S, nblk, h]
    f = dequant(blk_q, blk_s)
    gpos = tlb[:, :, None] * bs \
        + jnp.arange(bs, dtype=jnp.int32)[None, None, :]   # [S, nblk, bs]
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    bidx = positions // bs - base[:, None]                   # [S, T]
    off = positions % bs
    sidx = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[:, None], (S, T))
    f = f.at[sidx, bidx, off].set(new.astype(jnp.float32))
    n_real = jnp.full((S,), T, jnp.int32) if valid is None \
        else jnp.minimum(valid.astype(jnp.int32), T)
    # one mask zeroes everything non-real: dequantized junk past the
    # resident frontier (positions in [pos, pos+n_real) were ALL just
    # overlaid by the .set above, so nothing real is lost) and the
    # overlaid padding tail of a bucket-padded prefill — neither may
    # ride the abs-max scale below nor leave nonzero codes
    keep = gpos < pos[:, None, None] + n_real[:, None, None]
    f = jnp.where(keep[..., None, None], f, 0.0)
    s_new = jnp.max(jnp.abs(f), axis=(2, 4))                 # [S, nblk, h]
    q_new = _quantize(f, s_new)
    # duplicate phys entries (several slots' overflow -> the garbage
    # block) scatter in unspecified order — garbage only, same as write
    return pool.at[phys].set(q_new), scale.at[phys].set(s_new)


def gather(pool, tables):
    """Rebuild each slot's contiguous [S, max_blocks*block_size, h, d]
    K/V view from the pool via its block table (one XLA gather)."""
    S, nb = tables.shape
    g = pool[tables.astype(jnp.int32)]        # [S, nb, bs, h, d]
    return g.reshape(S, nb * pool.shape[1], pool.shape[2], pool.shape[3])


def gather_rows(pool, tables):
    """`gather` for a latent pool [N, block_size, width]: each slot's
    contiguous [S, max_blocks*block_size, width] rows."""
    S, nb = tables.shape
    g = pool[tables.astype(jnp.int32)]        # [S, nb, bs, w]
    return g.reshape(S, nb * pool.shape[1], pool.shape[2])


def gather_quant(pool, scales, tables):
    """Quantized `gather`: rebuild each slot's contiguous dense f32 view
    from an int8 pool + its scale array — the dequantizing reference the
    in-kernel dequant path is tested against."""
    S, nb = tables.shape
    t = tables.astype(jnp.int32)
    f = dequant(pool[t], scales[t])           # [S, nb, bs, h, d] f32
    return f.reshape(S, nb * pool.shape[1], pool.shape[2], pool.shape[3])


# The name the attention of an executable goes by in a trace: the engine's
# decode and prefill functions trace inside `attention_scope("decode_attn")`
# / `("prefill_attn")`, and whichever of the four attend arms implements it
# opens that jax.named_scope. So the gather arm and the kernel arm are the
# SAME named work to a trace reader (XProf's name stack today). Trace-time
# only; outside an engine executable there is no scope.
_ATTEND_SCOPE = None


@contextlib.contextmanager
def attention_scope(name):
    global _ATTEND_SCOPE
    prev, _ATTEND_SCOPE = _ATTEND_SCOPE, name
    try:
        yield
    finally:
        _ATTEND_SCOPE = prev


def _scoped(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if _ATTEND_SCOPE is None:
            return fn(*args, **kwargs)
        with jax.named_scope(_ATTEND_SCOPE):
            return fn(*args, **kwargs)
    return wrapper


@_scoped
def attend(q, k_pool, v_pool, tables, pos, scale=None):
    """Block-table attention: gather the slot's blocks into the dense
    layout, then run the exact dense masked attention (`kv_cache.attend`)
    — token-exact vs the per-slot dense path because the gathered view
    reproduces it elementwise and masked positions contribute exact
    zeros."""
    return kvc.attend(q, gather(k_pool, tables), gather(v_pool, tables),
                      pos, scale)


@_scoped
def attend_quant(q, k_pool, v_pool, k_scale, v_scale, tables, pos,
                 scale=None):
    """Quantized block-table attention, gather reference: dequantize the
    gathered blocks (per-block per-head scales) into the dense f32 view,
    then the exact same masked math as `attend`. The oracle the int8
    kernel path is asserted against on CPU."""
    return kvc.attend(q, gather_quant(k_pool, k_scale, tables),
                      gather_quant(v_pool, v_scale, tables), pos, scale)


@_scoped
def attend_kernel(q, k_pool, v_pool, tables, pos, scale=None):
    """Block-table attention via the Pallas paged-attention kernels: the
    block table is walked IN-kernel, so the dense per-slot view is never
    materialized — same masking semantics as `attend`, online-softmax
    numerics (float-equal, not bit-equal). One query a slot (the decode
    step) goes to the decode kernel, which loops over the blocks a slot
    holds and fetches no other; T > 1 (prefill, speculative verify) goes
    to the grid-per-block kernel (tile caps served through
    `incubate.autotune.lookup_paged_blocks`). Both run in interpret mode
    off-TPU, so CPU tier-1 can assert exactness against the gather
    path."""
    from ..ops.pallas.paged_attention import (
        paged_attention, paged_decode_attention)
    if _decode_kernel_takes(q, k_pool):
        return paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                      scale=scale)
    return paged_attention(q, k_pool, v_pool, tables, pos, scale=scale)


@_scoped
def attend_kernel_quant(q, k_pool, v_pool, k_scale, v_scale, tables, pos,
                        scale=None):
    """Quantized block-table attention, in-kernel dequant: the scale
    rows ride the same scalar-prefetch/block-DMA machinery as the block
    table walk, and each streamed int8 block dequantizes in VMEM with
    the exact `dequant` expression — the dense f32 view is never
    materialized, so the decode HBM read bill is the int8 bytes plus a
    ~1/(block_size*head_dim) scale overhead."""
    from ..ops.pallas.paged_attention import paged_attention
    return paged_attention(q, k_pool, v_pool, tables, pos, scale=scale,
                           k_scale=k_scale, v_scale=v_scale, qmax=QMAX)


# Which attend implementation GPTAttention traces for paged caches:
# "gather" (the bit-exact dense-view oracle), "kernel" (the in-kernel
# block-table walk at every T) or "decode_kernel" (the kernel where there
# is one query a slot, the gather for prefill and verify windows: what an
# engine resolves its unset `attention_impl` to on a TPU; not a value a
# configuration can spell). A module-level flag read at TRACE time: the
# engines wrap every executable call in `attention_impl(...)` so each
# engine's executables bake in its resolved impl, and the arms are distinct
# function objects so the eager op-cache can never replay the wrong one.
_ATTEND_IMPLS = ("gather", "kernel", "decode_kernel")
_ATTEND_IMPL = "gather"


def current_attention_impl():
    return _ATTEND_IMPL


def _decode_kernel_takes(q, k_pool):
    from ..ops.pallas.paged_attention import decode_kernel_takes
    return q.shape[1] == 1 and k_pool.dtype != jnp.int8 \
        and decode_kernel_takes(q.shape[2], q.shape[3], k_pool.dtype)


def kernel_attends(q, k_pool):
    """Trace time: whether the paged attention of `q` [S, T, h, d] over
    `k_pool` takes the kernel arm under the scoped implementation.
    "decode_kernel" means the decode kernel or the gather, never the
    grid-per-block kernel: a decode shape it cannot be compiled for (a
    head size under a lane's width) gathers."""
    return _ATTEND_IMPL == "kernel" or (
        _ATTEND_IMPL == "decode_kernel" and _decode_kernel_takes(q, k_pool))


@contextlib.contextmanager
def attention_impl(impl):
    """Scope the paged-attend implementation for code traced inside."""
    global _ATTEND_IMPL
    if impl not in _ATTEND_IMPLS:
        raise ValueError(f"unknown paged attention impl {impl!r} "
                         f"(want one of {_ATTEND_IMPLS})")
    prev = _ATTEND_IMPL
    _ATTEND_IMPL = impl
    try:
        yield
    finally:
        _ATTEND_IMPL = prev


class SlotStateStore:
    """Host-side account of the per-slot state rows (StateLayer): which
    slots hold a request's state, and the bytes that pins. The rows need no
    allocator (slot `s` owns row `s` of every state layer) and no device
    work on release: a prefill starts from a zero state and overwrites the
    row, so what a finished request left there is never read."""

    def __init__(self, slots, bytes_per_slot):
        self.bytes_per_slot = int(bytes_per_slot)
        self._held = np.zeros((int(slots),), bool)

    def acquire(self, slot):
        self._held[int(slot)] = True

    def release(self, slot):
        self._held[int(slot)] = False

    @property
    def in_use(self):
        return int(self._held.sum())

    @property
    def bytes_in_use(self):
        return self.in_use * self.bytes_per_slot


class BlockPool:
    """Host-side allocator over physical block ids 1..num_blocks-1
    (id 0 is the reserved garbage block). Refcounted: a block is returned
    to the free list when its last reference drops — the prefix cache
    holds one reference per cached block, each request's table row holds
    one per entry, which is what makes copy-on-write sharing safe (shared
    blocks are simply never written; writers always own fresh blocks)."""

    def __init__(self, num_blocks, block_size):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (one is reserved "
                             "as the garbage block)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free = list(range(self.num_blocks - 1, GARBAGE_BLOCK, -1))
        self._refs = np.zeros((self.num_blocks,), np.int32)
        # KV attribution ledger (observability.kvledger): attached by
        # the engine when the ledger is enabled; every refcount
        # transition below mirrors into it. One `is None` check per
        # operation is the entire disabled-path cost.
        self._ledger = None
        self._export()

    def attach_ledger(self, ledger):
        self._ledger = ledger

    # -- accounting ---------------------------------------------------------
    @property
    def capacity(self):
        """Allocatable blocks (garbage block excluded)."""
        return self.num_blocks - 1

    @property
    def available(self):
        return len(self._free)

    @property
    def in_use(self):
        return self.capacity - len(self._free)

    def refcount(self, block_id):
        return int(self._refs[block_id])

    def refcounts(self, block_ids):
        """The refcounts of many blocks in one indexing (an int32 array,
        in the order given): what a count over every cached block costs
        as one operation and not a `refcount()` call a block."""
        return self._refs[np.fromiter(block_ids, np.int64)]

    def _export(self):
        _M_POOL_TOTAL.set(self.capacity)
        _M_POOL_IN_USE.set(self.in_use)

    # -- alloc / ref / unref ------------------------------------------------
    def alloc(self, n=1):
        """Allocate n blocks (each with refcount 1). Raises
        BlockAllocError when the pool cannot serve all n — all-or-nothing,
        so a half-allocated request never strands blocks."""
        _faults.fire("serving.block_alloc")
        if n > len(self._free):
            raise BlockAllocError(
                f"block pool exhausted: want {n}, have {len(self._free)} "
                f"free of {self.capacity}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        if self._ledger is not None:
            self._ledger.pool_alloc(out)
        self._export()
        return out

    def ref(self, block_id):
        """Take one more reference on an allocated block (prefix-cache
        sharing)."""
        if block_id == GARBAGE_BLOCK or self._refs[block_id] < 1:
            raise ValueError(f"ref of unallocated block {block_id}")
        self._refs[block_id] += 1
        if self._ledger is not None:
            self._ledger.pool_ref(block_id)

    def unref(self, block_id):
        """Drop one reference; the block returns to the free list at
        zero.

        `serving.kv_ledger_leak` is a fault-injection site: in truncate
        mode the free-list return of a last-reference drop is SKIPPED —
        the pool leaks the block while the ledger records the free it
        should have produced. The damage is exactly what
        LedgerReconciler's free-list invariant exists to catch, within
        one scheduler step."""
        if block_id == GARBAGE_BLOCK:
            return
        if self._refs[block_id] < 1:
            raise ValueError(f"unref of free block {block_id}")
        self._refs[block_id] -= 1
        if self._ledger is not None:
            self._ledger.pool_unref(block_id)
        if self._refs[block_id] == 0:
            if self._ledger is not None:
                self._ledger.pool_free(block_id)
            spec = _faults.fire("serving.kv_ledger_leak")
            if spec is None or spec.mode != "truncate":
                self._free.append(int(block_id))
        self._export()
