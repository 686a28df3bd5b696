"""Flash attention (blockwise online-softmax) as Pallas TPU kernels.

TPU-native replacement for the reference's fused MHA CUDA ops
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h): instead
of a monolithic CUDA kernel per (fwd, bwd), three Pallas kernels walk the
attention matrix in (block_q, block_k) score tiles so the full S×S score
matrix never materialises in HBM:

  * `_fwd_kernel`   — online-softmax forward, saves per-row logsumexp
  * `_dq_kernel`    — dQ accumulation (grid over q-blocks, loop over keys)
  * `_dkv_kernel`   — dK/dV accumulation (grid over k-blocks, loop over
                      queries)

The loop over the other operand runs INSIDE the kernel (PR 29): a grid step
is one block of queries (for `_dkv`: of keys); the other operand of that
(batch, head) stays resident in VMEM — all of it when it fits
`_RESIDENT_BYTES`, else one "major" block per step of a third grid axis —
and the body walks it in chunks. What that buys under `causal` (see
`FlashPlan`): the trip count is the causal limit, so no chunk above the
diagonal is issued; only the chunks the diagonal crosses build a mask; and
`block_q` need not equal `block_k`. The running statistics live in VMEM
scratch, updated in place by each chunk: carried as loop values they were
spilled and refilled whole at every loop boundary (192 vector registers'
worth at block_q 512). Measurements, the bundle counts behind that choice
and the block sweep: PERF.md §6, PR 29.

Feature coverage (VERDICT r1 item 8, matching the reference fused path):
  * additive attention mask, broadcastable over batch and/or heads
    (reference fused_attention attn_mask semantics: added to scaled scores)
  * attention-probability dropout with a counter-based in-kernel RNG
    (murmur3-finalizer hash of absolute (row, col) coordinates), so the
    backward kernels regenerate the identical keep mask from the seed with
    no S×S mask tensor ever materialised
  * GQA/MQA: fewer KV heads than Q heads; the kv block index maps derive
    the shared head, dK/dV are reduced over the query-head group outside

Layout: (B, H, S, D) for q, (B, Hk, S, D) for k/v. All softmax statistics
are kept in float32 regardless of input dtype, lane-broadcast to 128.
"""
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The largest block a shape with no tuned row gets. On v5e at S=1024, D=64
# the sweep's optimum is the largest tile it tried, (512, 512): a chunk's
# fixed work (statistics, lane reductions, the MXU's fill and drain) is
# paid per chunk, not per score (PERF.md §6, PR 29).
DEFAULT_BLOCK = 512
_LANE = 128           # TPU lane width; statistics carry a broadcast lane dim
_NEG_INF = -1e30
# VMEM the resident operands of one grid step may take, double buffering
# included (the scoped default is 16 MB; blocks, scratch and spills need
# the rest). K and V of one head at S=1024, D=64 are 0.5 MB of it.
_RESIDENT_BYTES = 8 * 2 ** 20


def _dropout_keep(seed, b, row_ids, col_ids, rate):
    """Deterministic keep mask from absolute coordinates: murmur3-style
    integer finalizer, identical in forward and backward kernels."""
    u = jnp.uint32
    x = (row_ids.astype(u) * u(0x9E3779B9)
         + col_ids.astype(u) * u(0x85EBCA6B))
    x = x ^ (seed.astype(u) + b.astype(u) * u(0xC2B2AE35))
    x = x ^ (x >> u(16))
    x = x * u(0x85EBCA6B)
    x = x ^ (x >> u(13))
    x = x * u(0xC2B2AE35)
    x = x ^ (x >> u(16))
    threshold = u(min(int(rate * 4294967296.0), 4294967295))
    return x >= threshold          # keep with prob 1 - rate


# ---------------------------------------------------------------------------
# the plan: what a call's blocks mean and how much of the square it runs
# ---------------------------------------------------------------------------

class FlashPlan(NamedTuple):
    """How one call walks the S×S scores.

    Every kernel computes (block_q, block_k) score tiles. Forward and dq
    take a grid step per `block_q` queries and loop over the keys in chunks
    of `block_k`; dkv takes a grid step per `block_k` keys and loops over
    the queries in chunks of `block_q`. The loop advances a `group` =
    max(block_q, block_k) rows of the looped operand at a time (its chunks
    unrolled), so that under `causal` exactly one group holds the diagonal:
    the groups on the visible side of it run with no mask, that one builds
    it, the others are never issued. `major_k` / `major_q` are the rows of
    keys / queries resident per grid step (S when they fit VMEM).
    `chunks_*` count tiles of one (batch, head): in the square, issued, and
    issued with a mask."""
    block_q: int
    block_k: int
    group: int
    major_k: int
    major_q: int
    chunks_total: int
    chunks_run: int
    chunks_masked: int

    def metadata(self, kernel, heads):
        """The `pallas_call` metadata of one kernel: its name (what the
        benchmark's readers match) and what the plan decided, counted over
        the whole call. `chunk` is the rows a loop step takes of the
        resident operand."""
        return {"kernel": kernel, "block_q": self.block_q,
                "block_k": self.block_k,
                "chunk": self.block_q if kernel == "flash_dkv"
                else self.block_k,
                "chunks_total": heads * self.chunks_total,
                "chunks_run": heads * self.chunks_run,
                "chunks_masked": heads * self.chunks_masked}


def _major(S, group, row_bytes):
    """Rows of the looped operand held per grid step: the largest multiple
    of `group` that divides S and fits `_RESIDENT_BYTES` double-buffered."""
    n = S // group
    for parts in range(1, n + 1):
        if n % parts == 0 and \
                2 * (S // parts) * row_bytes <= _RESIDENT_BYTES:
            return S // parts
    return group


def flash_plan(S, D, block_q, block_k, causal, itemsize=2, mask_itemsize=0):
    """The `FlashPlan` of a call; raises where the blocks do not tile S."""
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} must be a multiple of block sizes "
                         f"({block_q}, {block_k})")
    group = max(block_q, block_k)
    if group % block_q or group % block_k:
        raise ValueError(f"one of block sizes ({block_q}, {block_k}) must "
                         f"divide the other")
    nq, nk = S // block_q, S // block_k
    if causal:
        # query block i runs the key groups up to the one holding its rows
        per_group = group // block_k
        run = sum((i * block_q // group + 1) * per_group for i in range(nq))
        masked = nq * per_group
    else:
        run, masked = nq * nk, 0
    return FlashPlan(
        block_q, block_k, group,
        _major(S, group, 2 * D * itemsize + block_q * mask_itemsize),
        _major(S, group, 2 * D * itemsize + 2 * _LANE * 4
               + block_k * mask_itemsize),
        nq * nk, run, masked)


def default_block(S):
    """The block a shape with no tuned row gets, for queries and keys
    alike: the largest power of two up to DEFAULT_BLOCK that divides S —
    S=1024 gets 512, S=768 gets 256, S=640 gets 128. When no power-of-two
    candidate divides S: the whole sequence if it fits one block (S=192),
    else the largest 8-aligned divisor of S under the cap (S=4000 -> 400,
    keeping the score tile inside VMEM)."""
    b = DEFAULT_BLOCK
    while b > 128 and S % b:
        b //= 2
    if S % b == 0:
        return min(b, S)
    if S <= DEFAULT_BLOCK:
        return S
    for d in range(DEFAULT_BLOCK, 7, -8):
        if S % d == 0:
            return d
    # S > 512 with no 8-aligned divisor: a whole-sequence block would be
    # both unaligned and VMEM-hostile — fail with the actionable message
    raise ValueError(
        f"S={S} has no viable flash block (no 8-aligned divisor <= "
        f"{DEFAULT_BLOCK}); pass block_q/block_k explicitly or pad S "
        f"to a multiple of 128")


# ---------------------------------------------------------------------------
# pieces shared by the three kernels
# ---------------------------------------------------------------------------

def _fold_scale(sm_scale):
    """True where scaling an operand once instead of every score tile is
    exact: a power of two (1/8 at D=64) only moves the exponent. 1/sqrt(128)
    is not one, and a scaled bf16 query would round: scores are scaled."""
    return math.frexp(sm_scale)[0] == 0.5


def _lanes(x, n):
    """A lane-broadcast (rows, 128) statistic as (rows, n)."""
    if n == _LANE:
        return x
    if n % _LANE == 0:
        return jnp.tile(x, (1, n // _LANE))
    if n < _LANE:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _stat(x):
    """(rows, 1) -> lane-broadcast (rows, 128)."""
    return jnp.broadcast_to(x, (x.shape[0], _LANE))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _chunk(start, size):
    return pl.ds(pl.multiple_of(start, size), size)


def _split(refs, n_in, has_mask, rate):
    """(inputs, mask_ref, seed_ref, outputs and scratch) of a kernel."""
    refs = list(refs)
    ins, rest = refs[:n_in], refs[n_in:]
    mask_ref = rest.pop(0) if has_mask else None
    seed_ref = rest.pop(0) if rate > 0 else None
    return ins, mask_ref, seed_ref, rest


def _walk(chunk, *, group, step, major, S, mj, start, causal, below):
    """Run the chunks of the resident block: `major` rows of an operand of
    S rows, major step `mj`, walked `step` rows a chunk and `group` rows a
    loop iteration. `start` is the first row (column) of this grid step's
    block: the group that holds it holds the diagonal. `below`: the groups
    before that one are the wholly visible ones (keys, for forward and
    dq), else those after it (queries, for dkv); the groups on the other
    side are never issued. `chunk(c, on_diag)` takes the chunk's index in
    the resident block."""
    per_group, groups = group // step, major // group

    def run_group(on_diag):
        def run(t, carry=0):
            for d in range(per_group):
                chunk(t * per_group + d, on_diag)
            return carry
        return run

    def loop(lo, hi):
        jax.lax.fori_loop(lo, hi, run_group(False), 0)

    if not causal:
        return loop(0, groups)
    # the diagonal's group, counted from the first resident one
    local = start // group - (0 if major == S else mj * groups)
    if below:
        loop(0, jnp.clip(local, 0, groups))
    if major == S:                      # one block holds the whole operand
        run_group(True)(local)
    else:
        @pl.when((local >= 0) & (local < groups))
        def _diagonal():
            run_group(True)(local)
    if not below:
        loop(jnp.clip(local + 1, 0, groups), groups)


def _scores(q, k, mask_tile, visible_from, sm_scale):
    """One (block_q, block_k) tile of masked, scaled scores in float32.
    `visible_from` is None off the diagonal; on it, the pair at tile offset
    (a, b) is visible where a - b >= visible_from (first column minus first
    row of the tile)."""
    s = _dot(q, k, (1, 1))
    if not _fold_scale(sm_scale):
        s = s * sm_scale
    if mask_tile is not None:
        s = jnp.maximum(s + mask_tile.astype(jnp.float32), _NEG_INF)
    if visible_from is not None:
        diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(diff >= visible_from, s, _NEG_INF)
    return s


def _kill(s, p, has_mask):
    # an additive mask can blank a whole row: its max is then _NEG_INF and
    # exp(s - max) is 1, not 0. Causality alone always leaves the diagonal.
    return jnp.where(s <= _NEG_INF * 0.5, 0.0, p) if has_mask else p


def _keep(seed_ref, b, row0, col0, shape, rate):
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + col0
    return _dropout_keep(seed_ref[0], b, row, col, rate)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, plan, S, causal, sm_scale, rate, has_mask):
    (q_ref, k_ref, v_ref), mask_ref, seed_ref, rest = _split(
        refs, 3, has_mask, rate)
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    bq, bk = plan.block_q, plan.block_k
    D = q_ref.shape[-1]
    b, i, mj = (pl.program_id(a) for a in range(3))

    q = q_ref[0]
    if _fold_scale(sm_scale):
        q = q * sm_scale
    row0 = i * bq

    @pl.when(mj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def chunk(c, on_diag):
        keys = _chunk(c * bk, bk)
        v = v_ref[0, keys, :]
        col0 = mj * plan.major_k + c * bk
        s = _scores(q, k_ref[0, keys, :],
                    mask_ref[0, :, keys] if has_mask else None,
                    col0 - row0 if on_diag else None, sm_scale)
        m = m_sc[:]
        m_new = jnp.maximum(m, _stat(jnp.max(s, axis=-1, keepdims=True)))
        alpha = jnp.exp(m - m_new)
        m_sc[:] = m_new
        p = _kill(s, jnp.exp(s - _lanes(m_new, bk)), has_mask)
        l_sc[:] = l_sc[:] * alpha + _stat(jnp.sum(p, axis=-1, keepdims=True))
        if rate > 0:
            keep = _keep(seed_ref, b, row0, col0, p.shape, rate)
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        acc_sc[:] = acc_sc[:] * _lanes(alpha, D) \
            + _dot(p.astype(v.dtype), v, (1, 0))

    _walk(chunk, group=plan.group, step=bk, major=plan.major_k, S=S, mj=mj,
          start=row0, causal=causal, below=True)

    @pl.when(mj == pl.num_programs(2) - 1)
    def _finalize():
        # guard fully-masked rows so they emit 0, not NaN
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / _lanes(l_safe, D)).astype(o_ref.dtype)
        # lse is stored with a broadcast 128-lane trailing dim: TPU block
        # shapes need the last two dims (8,128)-aligned, so a flat (BH, S)
        # layout with (1, block_q) blocks is not lowerable
        lse_ref[0] = m_sc[:] + jnp.log(l_safe)


def _head_maps(H, Hk, Bm, Hm):
    """(kv head, mask head) of flattened q index b (= batch*H + h)."""
    g = H // Hk

    def kv(b):
        return (b // H) * Hk + (b % H) // g

    def mask(b):
        return (b // H if Bm > 1 else 0) * Hm + ((b % H) if Hm > 1 else 0)
    return kv, mask


def _resident(causal, block, major, after):
    """Index of the major block a grid step (x = grid block, m = major
    step) needs: under `causal` the steps past the diagonal (before it, for
    dkv: `after`) ask for the block they already hold, so nothing is
    fetched for them."""
    if not causal:
        return lambda x, m: m
    if after:
        return lambda x, m: jnp.maximum(m, x * block // major)
    return lambda x, m: jnp.minimum(m, x * block // major)


def _call(kernel, name, plan, heads, **kw):
    return pl.pallas_call(
        kernel, name=name, metadata=plan.metadata(name, heads),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        **kw)


def _mha_forward(q, k, v, mask, seed, causal, sm_scale, plan, interpret,
                 H, Hk, mask_dims):
    BH, S, D = q.shape
    bq, major = plan.block_q, plan.major_k
    rate = 0.0 if seed is None else seed[1]
    has_mask = mask is not None
    kv_head, mask_head = _head_maps(H, Hk, *mask_dims)
    res = _resident(causal, bq, major, after=False)

    def q_rows(width):
        return pl.BlockSpec((1, bq, width), lambda b, i, m: (b, i, 0))
    kv_rows = pl.BlockSpec((1, major, D),
                           lambda b, i, m: (kv_head(b), res(i, m), 0))
    in_specs = [q_rows(D), kv_rows, kv_rows]
    operands = [q, k, v]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, bq, major), lambda b, i, m: (mask_head(b), i, res(i, m))))
        operands.append(mask)
    if rate > 0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed[0])

    return _call(
        functools.partial(_fwd_kernel, plan=plan, S=S, causal=causal,
                          sm_scale=sm_scale, rate=rate, has_mask=has_mask),
        "flash_fwd", plan, BH,
        grid=(BH, S // bq, S // major),
        in_specs=in_specs,
        out_specs=[q_rows(D), q_rows(_LANE)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, _LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANE), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANE), jnp.float32),   # running sum
            pltpu.VMEM((bq, D), jnp.float32),       # acc
        ],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(*refs, plan, S, causal, sm_scale, rate, has_mask):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), mask_ref, seed_ref, \
        rest = _split(refs, 6, has_mask, rate)
    dq_ref, acc_sc = rest
    bq, bk = plan.block_q, plan.block_k
    b, i, mj = (pl.program_id(a) for a in range(3))
    fold = _fold_scale(sm_scale)

    q = q_ref[0]
    if fold:
        q = q * sm_scale
    do = do_ref[0]
    lse = _lanes(lse_ref[0], bk)
    delta = _lanes(delta_ref[0], bk)
    row0 = i * bq

    @pl.when(mj == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def chunk(c, on_diag):
        keys = _chunk(c * bk, bk)
        k = k_ref[0, keys, :]
        col0 = mj * plan.major_k + c * bk
        s = _scores(q, k, mask_ref[0, :, keys] if has_mask else None,
                    col0 - row0 if on_diag else None, sm_scale)
        p = _kill(s, jnp.exp(s - lse), has_mask)
        dp = _dot(do, v_ref[0, keys, :], (1, 1))
        if rate > 0:
            keep = _keep(seed_ref, b, row0, col0, p.shape, rate)
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        ds = p * (dp - delta)
        if not fold:
            ds = ds * sm_scale
        acc_sc[:] += _dot(ds.astype(k.dtype), k, (1, 0))

    _walk(chunk, group=plan.group, step=bk, major=plan.major_k, S=S, mj=mj,
          start=row0, causal=causal, below=True)

    @pl.when(mj == pl.num_programs(2) - 1)
    def _finalize():
        acc = acc_sc[:]
        dq_ref[0] = (acc * sm_scale if fold else acc).astype(dq_ref.dtype)


def _dkv_kernel(*refs, plan, S, causal, sm_scale, rate, has_mask):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), mask_ref, seed_ref, \
        rest = _split(refs, 6, has_mask, rate)
    dk_ref, dv_ref, dk_sc, dv_sc = rest
    bq, bk = plan.block_q, plan.block_k
    b, j, mj = (pl.program_id(a) for a in range(3))
    fold = _fold_scale(sm_scale)

    k = k_ref[0]
    if fold:                # the block of this grid step takes the scale
        k = k * sm_scale
    v = v_ref[0]
    col0 = j * bk

    @pl.when(mj == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def chunk(c, on_diag):
        rows = _chunk(c * bq, bq)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        row0 = mj * plan.major_q + c * bq
        s = _scores(q, k, mask_ref[0, rows, :] if has_mask else None,
                    col0 - row0 if on_diag else None, sm_scale)
        p = _kill(s, jnp.exp(s - _lanes(lse_ref[0, rows, :], bk)), has_mask)
        dp = _dot(do, v, (1, 1))
        if rate > 0:
            keep = _keep(seed_ref, b, row0, col0, p.shape, rate)
            p_drop = jnp.where(keep, p / (1.0 - rate), 0.0)
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        else:
            p_drop = p
        # dV += P_drop^T @ dO, dK += dS^T @ Q (contract over the q rows)
        dv_sc[:] += _dot(p_drop.astype(do.dtype), do, (0, 0))
        ds = p * (dp - _lanes(delta_ref[0, rows, :], bk))
        if not fold:
            ds = ds * sm_scale
        dk_sc[:] += _dot(ds.astype(q.dtype), q, (0, 0))

    _walk(chunk, group=plan.group, step=bq, major=plan.major_q, S=S, mj=mj,
          start=col0, causal=causal, below=False)

    @pl.when(mj == pl.num_programs(2) - 1)
    def _finalize():
        dk = dk_sc[:]
        dk_ref[0] = (dk * sm_scale if fold else dk).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _mha_backward(q, k, v, o, lse, do, mask, seed, causal, sm_scale, plan,
                  interpret, H, Hk, mask_dims):
    BH, S, D = q.shape
    bq, bk = plan.block_q, plan.block_k
    rate = 0.0 if seed is None else seed[1]
    has_mask = mask is not None
    kv_head, mask_head = _head_maps(H, Hk, *mask_dims)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (BH, S, _LANE))

    operands = [q, k, v, do, lse, delta]
    if has_mask:
        operands.append(mask)
    if rate > 0:
        operands.append(seed[0])
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] if rate > 0 else []
    static = dict(plan=plan, S=S, causal=causal, sm_scale=sm_scale,
                  rate=rate, has_mask=has_mask)

    # dq: a grid step per query block, the keys resident
    major = plan.major_k
    res = _resident(causal, bq, major, after=False)

    def q_rows(width):
        return pl.BlockSpec((1, bq, width), lambda b, i, m: (b, i, 0))
    kv_rows = pl.BlockSpec((1, major, D),
                           lambda b, i, m: (kv_head(b), res(i, m), 0))
    mask_spec = [pl.BlockSpec(
        (1, bq, major), lambda b, i, m: (mask_head(b), i, res(i, m)))] \
        if has_mask else []
    dq = _call(
        functools.partial(_dq_kernel, **static), "flash_dq", plan, BH,
        grid=(BH, S // bq, S // major),
        in_specs=[q_rows(D), kv_rows, kv_rows, q_rows(D), q_rows(_LANE),
                  q_rows(_LANE)] + mask_spec + smem,
        out_specs=q_rows(D),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(*operands)

    # dk, dv: a grid step per key block, the queries resident
    major = plan.major_q
    res_q = _resident(causal, bk, major, after=True)

    def k_rows(head):
        return pl.BlockSpec((1, bk, D), lambda b, j, m: (head(b), j, 0))

    def resident_q(width):
        return pl.BlockSpec((1, major, width),
                            lambda b, j, m: (b, res_q(j, m), 0))
    mask_spec = [pl.BlockSpec(
        (1, major, bk), lambda b, j, m: (mask_head(b), res_q(j, m), j))] \
        if has_mask else []
    # dk/dv are per Q-head; GQA reduces over the head group outside
    dk, dv = _call(
        functools.partial(_dkv_kernel, **static), "flash_dkv", plan, BH,
        grid=(BH, S // bk, S // major),
        in_specs=[resident_q(D), k_rows(kv_head), k_rows(kv_head),
                  resident_q(D), resident_q(_LANE), resident_q(_LANE)]
        + mask_spec + smem,
        out_specs=[k_rows(lambda b: b), k_rows(lambda b: b)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public custom-vjp entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, mask, seed_arr, rate, causal, sm_scale, plan, interpret):
    return _flash_fwd(q, k, v, mask, seed_arr, rate, causal, sm_scale,
                      plan, interpret)[0]


def _flash_fwd(q, k, v, mask, seed_arr, rate, causal, sm_scale, plan,
               interpret):
    B, H, S, D = q.shape
    Hk = k.shape[1]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * Hk, S, D)
    vf = v.reshape(B * Hk, S, D)
    mf, mask_dims = _flatten_mask(mask, B, H)
    seed = None if rate == 0.0 else (seed_arr, rate)
    o, lse = _mha_forward(qf, kf, vf, mf, seed, causal, sm_scale, plan,
                          interpret, H, Hk, mask_dims)
    return o.reshape(B, H, S, D), (qf, kf, vf, mf, seed_arr, o, lse,
                                   (B, H, Hk, S, D), mask_dims)


def _flash_bwd(rate, causal, sm_scale, plan, interpret, res, g):
    qf, kf, vf, mf, seed_arr, o, lse, (B, H, Hk, S, D), mask_dims = res
    seed = None if rate == 0.0 else (seed_arr, rate)
    do = g.reshape(B * H, S, D)
    dq, dk, dv = _mha_backward(qf, kf, vf, o, lse, do, mf, seed, causal,
                               sm_scale, plan, interpret, H, Hk, mask_dims)
    dq = dq.reshape(B, H, S, D)
    if Hk != H:
        g_sz = H // Hk
        dk = dk.reshape(B, Hk, g_sz, S, D).sum(axis=2)
        dv = dv.reshape(B, Hk, g_sz, S, D).sum(axis=2)
    else:
        dk = dk.reshape(B, H, S, D)
        dv = dv.reshape(B, H, S, D)
    return (dq, dk, dv, None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flatten_mask(mask, B, H):
    if mask is None:
        return None, (1, 1)
    while mask.ndim < 4:
        mask = mask[None]
    Bm = mask.shape[0]
    Hm = mask.shape[1]
    if Bm not in (1, B) or Hm not in (1, H):
        raise ValueError(f"mask shape {mask.shape} does not broadcast to "
                         f"(B={B}, H={H}, S, S)")
    return mask.reshape(Bm * Hm, *mask.shape[2:]), (Bm, Hm)


def flash_attention(q, k, v, mask=None, causal=False, sm_scale=None,
                    dropout_rate=0.0, dropout_seed=None,
                    block_q=None, block_k=None,
                    interpret=None):
    """Flash attention over (B, H, S, D) q and (B, Hk, S, D) k/v.

    mask: additive, broadcastable from (B|1, H|1, S, S). dropout_rate with
    dropout_seed (int32 scalar/array) drops attention probabilities with the
    keep mask derived from absolute coordinates (regenerated in backward).
    Hk may divide H (GQA/MQA). (block_q, block_k) is the score tile of all
    three kernels (`FlashPlan`): each must divide S and one the other; they
    need not be equal, `causal` or not. On non-TPU backends the kernels run
    in Pallas interpret mode.
    """
    B, H, S, D = q.shape
    Hk = k.shape[1]
    if H % Hk:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hk}")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    block_q = default_block(S) if block_q is None else min(block_q, S)
    block_k = default_block(S) if block_k is None else min(block_k, S)
    plan = flash_plan(S, D, block_q, block_k, causal,
                      itemsize=q.dtype.itemsize,
                      mask_itemsize=0 if mask is None else
                      mask.dtype.itemsize)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rate = float(dropout_rate)
    if rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    seed_arr = (jnp.asarray(dropout_seed, jnp.int32).reshape(1)
                if rate > 0.0 else jnp.zeros((1,), jnp.int32))
    return _flash(q, k, v, mask, seed_arr, rate, causal, float(sm_scale),
                  plan, interpret)
