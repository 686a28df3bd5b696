"""Paged attention as a Pallas TPU kernel: block tables consumed IN-kernel.

The PR 6 paged path (`serving/blocks.py`) is token-exact but pays for it
in HBM traffic: `attend` first *gathers* every slot's physical blocks
into a dense `[slots, max_len, heads, head_dim]` view (a full write +
re-read of the padded KV), then runs the dense masked softmax over it.
At decode shapes that is tolerable; at long-prompt shapes the gather IS
the memory bill — O(slots x max_len) written and read again per layer
per step, regardless of how many tokens are live.

Two kernels remove the dense view. One query a slot over float pools
(the decode step) takes `paged_decode_attention`, at the end of this file:
one call walks every slot's table with the loop over blocks INSIDE the
kernel. Everything else (T > 1, int8 pools) takes `paged_attention`, the
grid-per-block kernel described next. The per-slot block tables
ride into the kernel as *scalar-prefetch* operands
(`pltpu.PrefetchScalarGridSpec`), so the BlockSpec index map of the K/V
pool can walk the table: grid step (slot, head-tile, q-tile, kv-block)
DMAs exactly ONE physical pool block — `tables[slot, kv_block]` — into
VMEM and folds it into an online-softmax accumulator (the flash
recurrence, `flash_attention.py`). K/V stream through VMEM once; nothing
is materialized per-slot in HBM.

Layout seen by the kernel (what Mosaic tiles, checked on a v5e): heads
and head_dim are FOLDED into one lane axis — q `[S, T, H*D]`, pools
`[N, block_size, H*D]` — so a head is a static lane slice
`[hh*D, (hh+1)*D)` of a 2-D tile, never a slice of a middle (sublane)
axis. (On the TPU that view of a pool is NOT a free reshape: the tiled
layout puts (H, D) on (sublanes, lanes), so XLA writes a relaid-out copy
of both pools before every call; PR 31 saw it as two `reshape` kernels in
the compile for a described v5e. The decode kernel reads the pool as
stored.) Per-head softmax state lives in
scratch with the head as the LEADING axis for the same reason.

Masking is identical to `kv_cache.attend` (the exactness oracle the
tier-1 tests assert against, in interpret mode):

  * key position j is visible to query i iff j <= pos[slot] + i;
  * masked scores are filled with the same finite -1e30 (never -inf:
    fully-masked rows must exp to zero, not NaN);
  * probabilities off-mask are exact zeros, and V rows no query of this
    tile can ever see are zeroed before the PV product — the garbage
    block (physical block 0) legitimately holds inf/NaN scatter junk
    and 0*inf == NaN would leak through an unguarded matmul;
  * rows with no visible key emit exact zeros.

Blocks whose first key position is past the tile's last visible query
position are predicated off with `pl.when`, and the index map clamps
their block index to the tile's last live block — a repeated index is
not re-fetched, so for a slot at position p only ceil((p+T)/block_size)
of the table's entries cost DMA or MXU work.

Tiling knobs (`q_tile`, `head_tile`) are CAPS served through the
`incubate.autotune` shipped-table machinery (`lookup_paged_blocks`,
keyed on (heads, padded_len, head_dim, block_size)): the effective tile
is the largest divisor of the live extent under the cap THAT THE TPU
CAN TILE (q rows a multiple of 8 or all of T; head lanes a multiple of
128 or all of H*D), so a stale shipped entry can never raise
mid-forward — it degrades to another legal tile.
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "paged_decode_attention", "DecodePlan",
           "decode_kernel_takes",
           "DEFAULT_Q_TILE", "DEFAULT_HEAD_TILE"]

# VMEM-minded caps: at D=128 a (128, 8*128) f32 query tile, its output
# tile (both double-buffered), the accumulator and the two softmax-stat
# scratches come to ~4 MB, leaving the scoped budget to the streamed
# K/V blocks. Shipped tuned entries
# (ops/pallas/flash_blocks_tuned.json, kernel="paged") override.
DEFAULT_Q_TILE = 128
DEFAULT_HEAD_TILE = 8
_LANE = 128           # TPU lane width for the softmax-stat scratch
_SUBLANE = 8          # TPU sublane count (second-minor tile extent)
_MASK_VALUE = -1e30   # same finite fill as kv_cache.attend / flash


def _interpreted(interpret):
    """The kernels compile for the TPU and are interpreted anywhere else,
    unless the caller says."""
    return jax.default_backend() != "tpu" if interpret is None \
        else bool(interpret)


def _largest_divisor_leq(n, cap, legal=None):
    """Largest divisor of n that is <= cap and passes `legal` (>=1
    always when `legal` is None; None when no divisor is legal)."""
    cap = max(1, min(int(cap), int(n)))
    for d in range(cap, 0, -1):
        if n % d == 0 and (legal is None or legal(d)):
            return d
    return None


def _tiles(T, H, D, q_cap, head_cap, aligned):
    """(tq, hq): the largest divisors of T / H under the caps. With
    `aligned` (compiled for the TPU, not interpreted) a tile must also
    satisfy Mosaic's block rule — second-minor extent a multiple of 8 or
    the whole axis, minor extent a multiple of 128 or the whole axis —
    and an extent with no such divisor is taken whole."""
    if not aligned:
        return (_largest_divisor_leq(T, q_cap),
                _largest_divisor_leq(H, head_cap))
    tq = _largest_divisor_leq(T, q_cap, lambda d: d % _SUBLANE == 0)
    hq = _largest_divisor_leq(H, head_cap, lambda d: (d * D) % _LANE == 0)
    return tq or T, hq or H


def _kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest, bs, tq, hq, D,
            nb, scale, quant, qmax):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)          # kv block — innermost: the online scan

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p0 = pos_ref[s]
    # highest query position of this tile; keys past it are invisible to
    # every row, so the whole block's MXU work is predicated off
    q_hi = p0 + (qi + 1) * tq - 1
    run = (j * bs) <= q_hi

    @pl.when(run)
    def _body():
        rows = jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 0) + qi * tq
        cols = jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1) + j * bs
        visible = cols <= p0 + rows
        # V rows no query of this tile ever sees may hold inf/NaN scatter
        # junk (the garbage block): a zero probability is not enough
        # against 0*inf == NaN, zero the rows themselves
        ever = (jax.lax.broadcasted_iota(jnp.int32, (bs, D), 0)
                + j * bs) <= q_hi
        for hh in range(hq):
            lanes = slice(hh * D, (hh + 1) * D)
            qh = q_ref[0, :, lanes]           # (tq, D)
            kh = k_ref[0, :, lanes]           # (bs, D) of ONE pool block
            vh = v_ref[0, :, lanes]
            if quant:
                # in-VMEM dequant of the streamed int8 block: the exact
                # expression serving.blocks.dequant computes, so the
                # kernel and the gather oracle see identical f32 values
                kh = kh.astype(jnp.float32) * (ks_ref[0, 0, j, hh] / qmax)
                vh = vh.astype(jnp.float32) * (vs_ref[0, 0, j, hh] / qmax)
            vh = jnp.where(ever, vh, 0.0)
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(visible, sc, _MASK_VALUE)
            m_prev = m_ref[hh, :, :1]                         # (tq, 1)
            l_prev = l_ref[hh, :, :1]
            m_cur = jnp.max(sc, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            # fully-masked rows: m_new == _MASK_VALUE makes p == 1
            p = jnp.where(sc <= _MASK_VALUE * 0.5, 0.0, p)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[hh] = acc_ref[hh] * alpha + pv
            m_ref[hh] = jnp.broadcast_to(m_new, (tq, _LANE))
            l_ref[hh] = jnp.broadcast_to(l_new, (tq, _LANE))

    @pl.when(j == nb - 1)
    def _finalize():
        for hh in range(hq):
            l = l_ref[hh, :, :1]                              # (tq, 1)
            l_safe = jnp.where(l == 0.0, 1.0, l)              # all-masked: 0
            o_ref[0, :, hh * D:(hh + 1) * D] = \
                (acc_ref[hh] / l_safe).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, tables, pos, scale=None,
                    q_tile=None, head_tile=None, interpret=None,
                    k_scale=None, v_scale=None, qmax=127.0):
    """Block-table attention without the dense gather.

    q: [S, T, H, D] query tokens sitting at positions pos..pos+T-1 of
    their slot; k_pool/v_pool: [N, block_size, H, D] physical pools;
    tables: [S, max_blocks] int32 physical block ids (0 == garbage);
    pos: [S] int32 tokens already resident per slot. Returns
    [S, T, H, D] — numerically the online-softmax evaluation of exactly
    the same masked attention `blocks.attend` (gather + dense) computes.

    With `k_scale`/`v_scale` ([N, H] float32, the quantized pools'
    per-block per-head scales) the pools are int8 and dequantize
    IN-kernel. The scale rows are gathered through the block table
    outside the kernel (a [S, max_blocks, H] float gather — bytes, not
    the pool) and each (slot, head-tile) strip rides in as one small
    VMEM block, so the dense f32 view is never materialized and the HBM
    read bill is the int8 bytes.

    q_tile/head_tile are caps (tuned via the shipped autotune table);
    see `_tiles` for how the effective tile is chosen. On non-TPU
    backends the kernel runs in Pallas interpret mode.
    """
    S, T, H, D = q.shape
    N, bs = k_pool.shape[0], k_pool.shape[1]
    nb = tables.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("quantized paged attention needs BOTH k_scale "
                         "and v_scale (or neither)")
    quant = k_scale is not None
    if quant and (k_pool.dtype != jnp.int8 or v_pool.dtype != jnp.int8):
        raise ValueError(f"scales given but pool dtypes are "
                         f"{k_pool.dtype}/{v_pool.dtype}, want int8")
    if not quant and (k_pool.dtype == jnp.int8
                      or v_pool.dtype == jnp.int8):
        # mirror of the guard above: attention over raw int8 codes is
        # finite, plausible, and silently wrong — the corruption class
        # the quality gate exists to catch must not have a front door
        raise ValueError("int8 pools need k_scale AND v_scale")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    interpret = _interpreted(interpret)
    if q_tile is None or head_tile is None:
        from ...incubate import autotune as _autotune
        tuned = _autotune.lookup_paged_blocks(H, nb * bs, D, bs)
        if tuned is not None:
            q_tile = tuned[0] if q_tile is None else q_tile
            head_tile = tuned[1] if head_tile is None else head_tile
    tq, hq = _tiles(T, H, D, q_tile or DEFAULT_Q_TILE,
                    head_tile or DEFAULT_HEAD_TILE, aligned=not interpret)
    return _paged_call(q, k_pool, v_pool, tables.astype(jnp.int32),
                       pos.astype(jnp.int32), k_scale, v_scale, tq=tq,
                       hq=hq, scale=float(scale), qmax=float(qmax),
                       interpret=interpret)


# jitted so the 24+ identical per-layer calls of one model trace and lower
# the kernel ONCE (one shared function in the module, not one copy a layer)
@functools.partial(jax.jit, static_argnames=("tq", "hq", "scale", "qmax",
                                             "interpret"))
def _paged_call(q, k_pool, v_pool, tables, pos, k_scale, v_scale, *, tq, hq,
                scale, qmax, interpret):
    S, T, H, D = q.shape
    N, bs = k_pool.shape[0], k_pool.shape[1]
    nb = tables.shape[1]
    quant = k_scale is not None
    nh, nq = H // hq, T // tq

    def q_index(s, h, qi, j, tables_ref, pos_ref):
        return (s, qi, h)

    def kv_index(s, h, qi, j, tables_ref, pos_ref):
        # THE block-table walk: this grid step's K/V block is whatever
        # physical block the slot's table maps logical block j to —
        # clamped to the tile's last live block, so the predicated-off
        # tail repeats one index and is never fetched
        last = jnp.clip((pos_ref[s] + (qi + 1) * tq - 1) // bs, 0, nb - 1)
        return (tables_ref[s, jnp.minimum(j, last)], 0, h)

    def scale_index(s, h, qi, j, tables_ref, pos_ref):
        return (s, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, tq, hq * D), q_index),
        pl.BlockSpec((1, bs, hq * D), kv_index),
        pl.BlockSpec((1, bs, hq * D), kv_index),
    ]
    operands = [q.reshape(S, T, H * D), k_pool.reshape(N, bs, H * D),
                v_pool.reshape(N, bs, H * D)]
    if quant:
        def strips(sc):
            # [N, H] -> this call's [S, nh, nb, hq] table-ordered strips
            g = sc.astype(jnp.float32)[tables]            # [S, nb, H]
            return g.reshape(S, nb, nh, hq).transpose(0, 2, 1, 3)
        in_specs += [pl.BlockSpec((1, 1, nb, hq), scale_index,
                                  memory_space=pltpu.SMEM)] * 2
        operands += [strips(k_scale), strips(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # tables, pos
        grid=(S, nh, nq, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tq, hq * D), q_index),
        scratch_shapes=[
            pltpu.VMEM((hq, tq, D), jnp.float32),      # acc
            pltpu.VMEM((hq, tq, _LANE), jnp.float32),  # running max
            pltpu.VMEM((hq, tq, _LANE), jnp.float32),  # running sum
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, tq=tq, hq=hq, D=D, nb=nb,
                               scale=scale, quant=quant, qmax=qmax)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, H * D), q.dtype),
        interpret=interpret,
        name="paged_attn", metadata={"kernel": "paged_attn"},
    )(tables, pos, *operands)
    return out.reshape(S, T, H, D)


# ----------------------------------------------------------------- decode
# T == 1 over float pools: one query row a slot, so the grid above (one
# 16-token block a grid step) is all grid steps and no work. Here the loop
# over a slot's blocks is INSIDE the kernel: the pools stay in HBM as the
# engine stores them ([N, block_size, H, D]: a block over all heads is one
# contiguous run, so one DMA), every live block is fetched into a
# double-buffered chunk of VMEM, and the chunk before it is folded into the
# online softmax meanwhile. Blocks past a slot's position are never fetched
# and cost nothing.

_DECODE_VMEM_BUDGET = 8 << 20     # both pools' double-buffered chunks
# keys a chunk: 2 MB of K and V at H*D = 2048 float32. On a v5e 32, 64 and
# 128 keys a chunk read the same (PERF.md, PR 31): the fold's time follows
# the keys, not the chunks, and a chunk's DMAs hide behind the fold before
_DECODE_CHUNK_TOKENS = 128


class DecodePlan(collections.namedtuple("DecodePlan", ["chunk_blocks"])):
    """How the decode kernel walks a block table: `chunk_blocks` pool
    blocks a chunk, from the shapes and a VMEM budget (no tuned table).
    A chunk aims at 128 keys and is cut to what the budget holds of K
    and V, double-buffered, and to the table."""

    @classmethod
    def for_shapes(cls, block_size, lanes, itemsize, max_blocks):
        per_block = 2 * 2 * block_size * lanes * itemsize
        c = min(max(1, _DECODE_CHUNK_TOKENS // block_size),
                max(1, _DECODE_VMEM_BUDGET // per_block), max_blocks)
        return cls(int(c))


def _decode_kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, acc_ref, m_ref, l_ref, sems,
                   *, S, H, D, W, bs, nb, C, scale):
    T = C * bs                                  # keys a chunk

    def live_blocks(s):
        # ceil((pos+1)/bs), held to the table; never 0, so every slot has
        # a chunk and the prefetch chain below needs no search (a slot
        # with no visible key masks all of its one block)
        return jnp.clip((pos_ref[s] + bs) // bs, 1, nb)

    def copies(s, c, buf, fn):
        """start or wait for the DMAs of chunk c of slot s: one a live
        block and pool."""
        n = live_blocks(s) - c * C
        for i in range(C):
            @pl.when(i < n)
            def _():
                b = tables_ref[s, c * C + i]
                for j, (hbm, dst) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))):
                    fn(pltpu.make_async_copy(
                        hbm.at[b], dst.at[buf, pl.ds(i * bs, bs)],
                        sems.at[buf, j]))

    # A pool block is [block_size, H, D] with the heads on sublanes, as
    # the pool stores it: no head is a contiguous tile, so q.K^T is not a
    # matmul over a head's keys. It is a multiply on the VPU (a vreg is 8
    # heads of one key) and a sum over D, which the MXU does against an
    # all-ones matrix and hands back on every lane: the scores arrive
    # lane-broadcast, the shape P.V's multiply wants.
    ones = jnp.ones((D, W), jnp.float32)

    def fold(s, c, buf, last):
        """One chunk into the slot's online softmax. Only a slot's last
        chunk holds keys past its position (and rows no DMA wrote)."""
        q = q_ref[s].astype(jnp.float32) * scale          # [H, D]
        k = kbuf[buf].astype(jnp.float32)                 # [T, H, D]
        sc = jnp.dot((k * q[None]).reshape(T * H, D), ones,
                     preferred_element_type=jnp.float32).reshape(T, H, W)
        if last:
            n_vis = pos_ref[s] + 1 - c * T
            vis = jax.lax.broadcasted_iota(jnp.int32, (T, 1, 1), 0) < n_vis
            sc = jnp.where(vis, sc, _MASK_VALUE)
        m_prev = m_ref[...]                               # [H, W]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[None])
        v = vbuf[buf].astype(jnp.float32)                 # [T, H, D]
        if last:
            # exact zeros off the mask (a slot with no visible key has
            # m_new == the fill, and exp(0) == 1 there), and V rows past
            # the position zeroed: they may hold inf/NaN (the garbage
            # block, VMEM no DMA wrote), and 0 * inf is NaN
            p = jnp.where(vis, p, 0.0)
            v = jnp.where(vis, v, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :D] \
            + jnp.sum(p[:, :, :D] * v, axis=0)

    def slot_body(s, g):
        n_chunks = pl.cdiv(live_blocks(s), C)
        m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk_body(c, g):
            buf = g % 2
            is_last = c + 1 == n_chunks
            # the chunk after this one, of this slot or the next, goes
            # out before this one is waited for
            nxt_s = jnp.where(is_last, s + 1, s)
            nxt_c = jnp.where(is_last, 0, c + 1)

            @pl.when(nxt_s < S)
            def _():
                copies(nxt_s, nxt_c, 1 - buf, lambda cp: cp.start())

            copies(s, c, buf, lambda cp: cp.wait())

            @pl.when(jnp.logical_not(is_last))
            def _():
                fold(s, c, buf, last=False)

            @pl.when(is_last)
            def _():
                fold(s, c, buf, last=True)
            return g + 1

        g = jax.lax.fori_loop(0, n_chunks, chunk_body, g)
        l = l_ref[:, :D]
        l_safe = jnp.where(l == 0.0, 1.0, l)              # all-masked: 0
        o_ref[s] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        return g

    copies(0, 0, 0, lambda cp: cp.start())
    jax.lax.fori_loop(0, S, slot_body, 0)


def decode_kernel_takes(heads, head_dim, pool_dtype, interpret=None):
    """Whether the decode kernel can be compiled for these shapes. A
    pool block is DMA'd as the pool stores it, [block_size, H, D] with
    (H, D) on (sublanes, lanes), so compiled for the TPU D has to fill
    whole lanes and H whole sublane tiles of the pool's dtype.
    (Interpreted, anything goes.)"""
    sublanes = _SUBLANE * 4 // jnp.dtype(pool_dtype).itemsize
    return _interpreted(interpret) or (head_dim % _LANE == 0
                                       and heads % sublanes == 0)


def paged_decode_attention(q, k_pool, v_pool, tables, pos, scale=None,
                           chunk_blocks=None, interpret=None):
    """`paged_attention` for the decode step: q [S, 1, H, D] over float
    pools, one kernel call that walks each slot's block table and reads
    only the ceil((pos+1)/block_size) blocks the slot holds. Masking is
    `kv_cache.attend`'s (see the module docstring). Pools are read as
    stored and widened to float32 in VMEM; q.k products, the softmax and
    P.V are float32 on the VPU, and only the sum of a head's products
    over D goes through the MXU, at the compiler's default precision
    (what the gather arm's matmuls get). `chunk_blocks` overrides the
    plan's blocks a chunk (tests)."""
    S, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"the decode kernel takes one query a slot, got "
                         f"T={T}")
    if k_pool.dtype == jnp.int8 or v_pool.dtype == jnp.int8:
        raise ValueError("the decode kernel reads float pools; int8 pools "
                         "go through paged_attention")
    bs, nb = k_pool.shape[1], tables.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if chunk_blocks is None:
        chunk_blocks = DecodePlan.for_shapes(
            bs, H * D, k_pool.dtype.itemsize, nb).chunk_blocks
    return _decode_call(q, k_pool, v_pool, tables.astype(jnp.int32),
                        pos.astype(jnp.int32), scale=float(scale),
                        C=max(1, min(int(chunk_blocks), nb)),
                        interpret=_interpreted(interpret))


@functools.partial(jax.jit, static_argnames=("scale", "C", "interpret"))
def _decode_call(q, k_pool, v_pool, tables, pos, *, scale, C, interpret):
    S, _, H, D = q.shape
    bs, nb = k_pool.shape[1], tables.shape[1]
    T, W = C * bs, max(D, _LANE)
    kernel = functools.partial(_decode_kernel, S=S, H=H, D=D, W=W, bs=bs,
                               nb=nb, C=C, scale=scale)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        in_specs=[smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, T, H, D), k_pool.dtype),    # K chunks
            pltpu.VMEM((2, T, H, D), v_pool.dtype),    # V chunks
            pltpu.VMEM((H, D), jnp.float32),           # acc
            pltpu.VMEM((H, W), jnp.float32),           # running max
            pltpu.VMEM((H, W), jnp.float32),           # running sum
            pltpu.SemaphoreType.DMA((2, 2)),           # [buffer, K|V]
        ],
        interpret=interpret,
        name="paged_attn_decode", metadata={"kernel": "paged_attn_decode"},
    )(tables, pos, q.reshape(S, H, D), k_pool, v_pool)
    return out.reshape(S, 1, H, D)
