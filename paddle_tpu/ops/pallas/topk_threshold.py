"""The k-th largest score of each row of a causal block, as a Pallas TPU
kernel: the threshold of DeepSeek Sparse Attention's learned selection in
prefill (`text/models/hybrid_ops.py` `dsa_prefill`).

A block of B queries holds a [B, L] float32 row of index scores each, of
which only the first `width` columns can be read: query row r of the block
sees the columns `c <= width - B + r` (the key blocks before its own whole,
its own key block up to the diagonal). The kernel finds, for every row, the
largest order key `kth` (the float's bits mapped so that integer order is
float order, -inf lowest) with at least k of the row's visible keys at or
above it: the same bisection over the 32 bits, one bit a pass, that XLA
runs over the whole row (`hybrid_ops.dsa_select_mask`), so the answer is
the same integer and not an approximation of it. Beside it, the number of
visible keys above `kth` and the number at it: a row with more at `kth`
than the k - above places left has ties for its caller to break.

Each grid step takes a tile of rows. Its visible columns are copied from
HBM into VMEM once, in chunks of B columns (the last chunk is the
diagonal), while the previous tile is counted; the keys are made in place
and every pass of the bisection reads them from VMEM: a compare and an add
a vreg, one sum across the lanes a row at the end of a pass. A row with
fewer than k visible keys keeps `kth` 0 (every key is at or above it).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _interpreted

__all__ = ["row_threshold"]

# VMEM the double-buffered key tile may take: rows a tile are the most of
# 64, 32, 16 or 8 that fit
_TILE_BUDGET = 24 << 20
_INT_MIN = -(1 << 31)
_LANES = 128
# chunks a trip of a counting loop. Compiled for a described v5e, a tile
# of 64 rows counted four chunks a trip fills the four vector ALUs (a
# compare, a select and an add a vreg: 0.80 bundles a vreg), as 32 rows
# eight chunks a trip do, with half the passes' fixed cost a row (the sum
# across the lanes, the loop's way in and out); 128 rows spill
_UNROLL = 4


def _row_tile(rows, width, interpret):
    """Rows a grid step: the most of 64, 32, 16, 8 that divides `rows`
    and whose two tiles of `width` float32 columns fit the budget; any
    divisor up to 64 when interpreted."""
    if interpret:
        return max(d for d in range(1, min(rows, 64) + 1) if rows % d == 0)
    for r in (64, 32, 16, 8):
        if rows % r == 0 and 2 * r * width * 4 <= _TILE_BUDGET:
            return r
    raise ValueError(f"no row tile for a [{rows}, {width}] block")


def row_threshold(scores, width, k, interpret=None):
    """(kth uint32 [B], above int32 [B], at int32 [B]) of `scores`
    [B, L] float32 whose row r sees the columns up to `width - B + r`
    (`width` an int32 scalar, a multiple of B, B <= width <= L; L a
    multiple of B): the k-th largest visible order key of each row
    (`hybrid_ops._order_key`; 0 where a row sees fewer than k), and the
    row's visible keys above it and at it. A score is never NaN."""
    B, L = scores.shape
    interpret = _interpreted(interpret)
    kth, above, at = _call(jnp.reshape(width, (1,)).astype(jnp.int32),
                           scores.astype(jnp.float32), k=int(k),
                           R=_row_tile(B, L, interpret), interpret=interpret)
    return (jax.lax.bitcast_convert_type(kth[:, 0], jnp.uint32),
            above[:, 0], at[:, 0])


@functools.partial(jax.jit, static_argnames=("k", "R", "interpret"))
def _call(width, scores, *, k, R, interpret):
    B, L = scores.shape
    kernel = functools.partial(_kernel, R=R, B=B, k=k, tiles=B // R)
    row = pl.BlockSpec((R, 1), lambda t, width: (t, 0))
    vmem = 2 * L * R * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // R,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row, row, row],
            scratch_shapes=[
                pltpu.VMEM((2, L // B, R, B), jnp.float32),  # key tiles
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="dsa_topk_threshold", metadata={"kernel": "dsa_topk_threshold"},
    )(width, scores)


def _kernel(width_ref, scores_hbm, kth_ref, above_ref, at_ref, buf, sems, *,
            R, B, k, tiles):
    t = pl.program_id(0)
    n = width_ref[0] // B                 # chunks of B columns a row
    slot = t % 2

    def copies(tile, into, fn):
        def one(c, carry):
            fn(pltpu.make_async_copy(
                scores_hbm.at[pl.ds(tile * R, R), pl.ds(c * B, B)],
                buf.at[into, c], sems.at[into]))
            return carry
        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(t == 0)
    def _():
        copies(0, 0, lambda cp: cp.start())

    # the next tile's columns come in while this one is counted
    @pl.when(t + 1 < tiles)
    def _():
        copies(t + 1, 1 - slot, lambda cp: cp.start())

    copies(t, slot, lambda cp: cp.wait())

    def key(c):
        return jax.lax.bitcast_convert_type(buf[slot, c], jnp.int32)

    def to_key(c, carry):
        # the float's bits -> an int32 in the floats' order (-0 just under
        # +0), kept in the float buffer as bits
        x = key(c)
        buf[slot, c] = jax.lax.bitcast_convert_type(
            x ^ ((x >> 31) & 0x7FFFFFFF), jnp.float32)
        return carry

    jax.lax.fori_loop(0, n, to_key, 0)
    # the last chunk is the diagonal: row r sees its columns up to r
    seen = jax.lax.broadcasted_iota(jnp.int32, (R, B), 1) \
        <= t * R + jax.lax.broadcasted_iota(jnp.int32, (R, B), 0)

    lanes = min(B, _LANES)

    def fold(hits, acc):
        # a chunk's lane tiles counted into one [R, lanes] accumulator
        for j in range(0, B, lanes):
            acc = jnp.where(hits[:, j:j + lanes], acc + 1, acc)
        return acc

    def count(hit):
        """[R, 1]: the visible keys x of each row with hit(x)."""
        def one(c, acc):
            return fold(hit(key(c)), acc)

        def body(c, acc):
            for u in range(_UNROLL):
                acc = one(c * _UNROLL + u, acc)
            return acc

        m = (n - 1) // _UNROLL
        acc = jax.lax.fori_loop(0, m, body,
                                jnp.zeros((R, lanes), jnp.int32))
        acc = jax.lax.fori_loop(m * _UNROLL, n - 1, one, acc)
        acc = fold(hit(key(n - 1)) & seen, acc)
        # a lane's count is at most the row's chunks times its lane tiles:
        # exact in float32, which the sum across the lanes takes
        return jnp.sum(acc.astype(jnp.float32), axis=1,
                       keepdims=True).astype(jnp.int32)

    # the sign first, then the 31 bits below it, each kept where at least
    # k keys lie at or above the candidate
    kth = jnp.where(count(lambda x: x >= 0) >= k, 0, _INT_MIN)

    def bit(i, kth):
        cand = kth | jax.lax.shift_left(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda x: x >= cand) >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 31, bit, kth)
    above = count(lambda x: x > kth)
    # as the unsigned order key: the sign bit flipped
    kth_ref[...] = kth ^ _INT_MIN
    above_ref[...] = above
    at_ref[...] = count(lambda x: x >= kth) - above
