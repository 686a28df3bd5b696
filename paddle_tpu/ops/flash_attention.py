"""Flash attention for TPU.

Replaces the reference's fused attention CUDA ops
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h) with a
Pallas TPU kernel (blockwise online-softmax) supporting additive masks,
probability dropout and GQA, falling back to a pure-XLA implementation on
CPU or when shapes don't tile.

Layout contract: (B, S, H, D) in / out ("BSHD", paddle's MHA layout).
"""
import functools

import jax
import jax.numpy as jnp


def _ref_attention_bhsd(q, k, v, causal, scale, mask=None, dropout_rate=0.0,
                        dropout_seed=None):
    if k.shape[1] != q.shape[1]:               # GQA: expand kv heads
        g = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        while mask.ndim < 4:
            mask = mask[None]
        s = s + mask.astype(jnp.float32)
    if causal:
        S_q, S_k = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((S_q, S_k), dtype=bool), k=S_k - S_q)
        s = jnp.where(cm, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p).astype(q.dtype)
    if dropout_rate > 0.0:
        # same counter-based keep mask as the Pallas kernel, so both paths
        # are bit-identical given the seed
        from .pallas.flash_attention import _dropout_keep
        B, H, Sq, Sk = p.shape
        row = jnp.arange(Sq, dtype=jnp.int32)[:, None]
        col = jnp.arange(Sk, dtype=jnp.int32)[None, :]
        b_idx = jnp.arange(B * H, dtype=jnp.int32).reshape(B, H, 1, 1)
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape(())
        keep = _dropout_keep(seed, b_idx, row[None, None], col[None, None],
                             dropout_rate)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _use_pallas(q, k):
    """q/k here are always (B, H, S, D) — both callers transpose first."""
    import os
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_FLASH") == "1":
        # operator/profiling escape hatch: forces the pure-XLA attention
        return False
    if jax.default_backend() != "tpu":
        return False
    B, H, S, D = q.shape
    # the Pallas kernel assumes one S for q and k/v; cross-length attention
    # (e.g. sequence-parallel q over gathered full-length k/v) falls back
    return S == k.shape[2] and S % 128 == 0 and D in (64, 128, 256)


def flash_blocks(B, H, S, D, causal):
    """(block_q, block_k) the Pallas kernels will run with for this
    geometry, or (None, None) for the kernel's own default: the autotune
    cache's row (incubate.autotune — the phi AlgorithmsCache role) when
    it tiles the call.

    What the pair means (`ops.pallas.flash_attention.FlashPlan`): the
    (block_q, block_k) score tile of all three kernels. Forward and dq
    take a grid step per block_q queries and loop over the keys, resident
    in VMEM, in chunks of block_k; dkv takes a grid step per block_k keys
    and loops over the queries in chunks of block_q. Under `causal` the
    two need not be equal since PR 29, but one must divide the other.

    A row that does not tile the call — it fails to divide S, or neither
    block divides the other — is reported and ignored; it must not raise
    mid-forward, and it must not be taken without a word either."""
    from ..incubate.autotune import lookup_flash_blocks
    hit = lookup_flash_blocks(B, H, S, D, causal)
    if not hit:
        return None, None
    from .pallas.flash_attention import flash_plan
    bq, bk = int(hit[0]), int(hit[1])
    try:
        flash_plan(S, D, bq, bk, causal)
    except (ValueError, ZeroDivisionError):
        import warnings
        warnings.warn(
            f"flash autotune row {(bq, bk)} for (H={H}, S={S}, D={D}, "
            f"causal={causal}) does not tile the call; using the kernel "
            f"default blocks")
        return None, None
    return bq, bk


def _pallas_flash_bhsd(q, k, v, causal, scale, mask=None, dropout_rate=0.0,
                       dropout_seed=None):
    from .pallas.flash_attention import flash_attention

    bq, bk = flash_blocks(*q.shape, causal)
    return flash_attention(q, k, v, mask=mask, causal=causal, sm_scale=scale,
                           dropout_rate=dropout_rate,
                           dropout_seed=dropout_seed,
                           block_q=bq, block_k=bk)


def flash_attention_bshd(q, k, v, causal=False, scale=None, mask=None,
                         dropout_rate=0.0, dropout_seed=None):
    """q: (B, S, H, D); k/v: (B, S, Hk, D). Returns (B, S, H, D)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, scale=scale,
                               mask=mask, dropout_rate=dropout_rate,
                               dropout_seed=dropout_seed)
    return jnp.swapaxes(out, 1, 2)


def flash_attention_bhsd(q, k, v, causal=False, scale=None, mask=None,
                         dropout_rate=0.0, dropout_seed=None):
    """q: (B, H, S, D); k/v: (B, Hk, S, D) (GPT-internal layout)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if _use_pallas(q, k):
        return _pallas_flash_bhsd(q, k, v, causal, scale, mask,
                                  dropout_rate, dropout_seed)
    return _ref_attention_bhsd(q, k, v, causal, scale, mask,
                               dropout_rate, dropout_seed)
