"""Pallas flash-attention kernels vs. plain-XLA reference (interpret mode).

Mirrors the reference's fused-attention op tests
(python/paddle/fluid/tests/unittests/test_fused_attention_op.py): forward
parity and analytic-gradient parity against an unfused implementation.
Since PR 29 the key loop runs inside the kernels: the cases below cover
unequal (block_q, block_k) under `causal`, additive masks that blank a row,
GQA, dropout, and sequences split over several resident "major" blocks.
"""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.flash_attention import _ref_attention_bhsd
from paddle_tpu.ops.pallas.flash_attention import (default_block,
                                                   flash_attention,
                                                   flash_plan)

# the package re-exports the function under the module's own name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
BLANK_ROW = 5          # the query row the additive mask blanks entirely


def make_qkv(B=2, H=2, S=256, D=64, seed=0, dtype=jnp.float32, Hk=None):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hk or H, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hk or H, S, D), dtype)
    return q, k, v


def shipped_row(H, S, D, causal):
    with open(os.path.join(os.path.dirname(fa.__file__),
                           "flash_blocks_tuned.json")) as f:
        return tuple(json.load(f)[json.dumps(["tpu", H, S, D, causal])])


# (id, dict(S, D, causal, blocks, and what the call adds))
CASES = [
    ("s256_d64_causal_128x128", dict(S=256, D=64, causal=True,
                                     blocks=(128, 128))),
    ("s256_d64_full_128x128", dict(S=256, D=64, causal=False,
                                   blocks=(128, 128))),
    ("s256_d128_causal_128x64", dict(S=256, D=128, causal=True,
                                     blocks=(128, 64))),
    ("s384_d64_causal_128x128", dict(S=384, D=64, causal=True,
                                     blocks=(128, 128))),
    ("s384_d128_causal_384x128", dict(S=384, D=128, causal=True,
                                      blocks=(384, 128))),
    ("s384_d64_causal_default", dict(S=384, D=64, causal=True,
                                     blocks=(None, None))),
    ("s512_d64_causal_256x128", dict(S=512, D=64, causal=True,
                                     blocks=(256, 128))),
    ("s512_d64_causal_128x256", dict(S=512, D=64, causal=True,
                                     blocks=(128, 256))),
    ("s512_d64_full_128x256", dict(S=512, D=64, causal=False,
                                   blocks=(128, 256))),
    ("s256_d64_causal_mask_blank_row", dict(S=256, D=64, causal=True,
                                            blocks=(128, 64), mask=True)),
    ("s256_d128_full_mask_blank_row", dict(S=256, D=128, causal=False,
                                           blocks=(64, 128), mask=True)),
    ("s256_d64_causal_gqa4", dict(S=256, D=64, causal=True,
                                  blocks=(128, 64), H=4, Hk=1)),
    ("s384_d128_causal_gqa4_dropout", dict(S=384, D=128, causal=True,
                                           blocks=(128, 128), H=4, Hk=1,
                                           rate=0.1)),
    ("s256_d64_causal_dropout", dict(S=256, D=64, causal=True,
                                     blocks=(64, 128), rate=0.1)),
    # the resident operand split over several major blocks (a VMEM budget
    # that S=512 does not fit): the third grid axis and its scratch
    ("s512_d64_causal_majors", dict(S=512, D=64, causal=True,
                                    blocks=(128, 128), resident=100_000)),
    ("s512_d64_causal_majors_256x128_mask",
     dict(S=512, D=64, causal=True, blocks=(256, 128), mask=True,
          resident=300_000)),
    ("s512_d64_full_majors_128x256", dict(S=512, D=64, causal=False,
                                          blocks=(128, 256),
                                          resident=100_000)),
    # the benchmark cell's own (S, D) with the row the table ships for it
    ("s1024_d64_causal_shipped_row", dict(S=1024, D=64, causal=True,
                                          blocks="shipped", B=1, H=1)),
]


@pytest.mark.parametrize("case", [c[1] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_forward_and_grads_match_reference(case, monkeypatch):
    S, D, causal = case["S"], case["D"], case["causal"]
    B, H = case.get("B", 1), case.get("H", 2)
    Hk, rate = case.get("Hk", H), case.get("rate", 0.0)
    blocks = case["blocks"]
    if blocks == "shipped":
        blocks = shipped_row(16, S, D, causal)
    if "resident" in case:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", case["resident"])
        plan = flash_plan(S, D, *blocks, causal, itemsize=4,
                          mask_itemsize=4 if case.get("mask") else 0)
        assert plan.major_k < S or plan.major_q < S
    q, k, v = make_qkv(B, H, S, D, seed=S + D, Hk=Hk)
    w = jax.random.normal(jax.random.key(7), q.shape)
    scale = 1.0 / D ** 0.5
    mask = ref_mask = None
    if case.get("mask"):
        mask = jax.random.normal(jax.random.key(9), (B, 1, S, S))
        mask = mask.at[:, :, :, 7].set(-jnp.inf)        # a key nobody sees
        # the reference's softmax has no gradient through a row of -inf:
        # it keeps the row, and the row's weight is zero on both sides
        ref_mask = mask
        mask = mask.at[:, :, BLANK_ROW, :].set(-jnp.inf)
        w = w.at[:, :, BLANK_ROW, :].set(0.0)
    seed = 3 if rate else None

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               block_q=blocks[0], block_k=blocks[1],
                               dropout_rate=rate, dropout_seed=seed,
                               interpret=True)

    def ref(q, k, v):
        return _ref_attention_bhsd(q, k, v, causal, scale, ref_mask, rate,
                                   seed)

    out, want = flash(q, k, v), ref(q, k, v)
    if mask is not None:
        assert not np.asarray(out[:, :, BLANK_ROW]).any()   # 0, not NaN
        want = want.at[:, :, BLANK_ROW].set(0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)
    g_flash = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) * w), (0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


# (S, block_q, block_k) -> (chunks_total, chunks_run, chunks_masked) a head
PLANS = [
    ((1024, 512, 512), (4, 3, 2)),       # the shipped row: 0.75 / 0.667
    ((1024, 256, 128), (32, 20, 8)),     # 0.625 of the square
    ((1024, 128, 128), (64, 36, 8)),     # 0.5625: the triangle + diagonal
    ((1024, 128, 512), (16, 12, 8)),
    ((512, 256, 128), (8, 6, 4)),
]


@pytest.mark.parametrize("shape,want", PLANS,
                         ids=["x".join(map(str, p[0])) for p in PLANS])
def test_causal_plan_issues_the_triangle_and_masks_only_the_diagonal(
        shape, want):
    S, bq, bk = shape
    plan = flash_plan(S, 64, bq, bk, True)
    assert (plan.chunks_total, plan.chunks_run, plan.chunks_masked) == want
    # brute force over the tiles: a tile runs iff its key group reaches no
    # further than the group holding the query block's rows; it is masked
    # iff it lies in that group; every tile with a visible pair runs
    G = max(bq, bk)
    run = masked = 0
    for i in range(S // bq):
        for j in range(S // bk):
            visible = j * bk <= i * bq + bq - 1
            needs_mask = visible and (j + 1) * bk - 1 > i * bq
            issued = j * bk // G <= i * bq // G
            assert issued or not visible
            run += issued
            on_diag = issued and j * bk // G == i * bq // G
            assert on_diag or not (issued and needs_mask)
            masked += on_diag
    assert (run, masked) == want[1:]
    # a call without `causal` runs the square and masks nothing
    full = flash_plan(S, 64, bq, bk, False)
    assert (full.chunks_run, full.chunks_masked) == (want[0], 0)


def test_the_plan_is_in_each_kernels_metadata():
    from test_serving_spans import pallas_eqns
    q = jnp.zeros((2, 2, 512, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=256,
                               block_k=128,
                               interpret=True).astype(jnp.float32).sum()
    eqns = pallas_eqns(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q)
                       .jaxpr, [])
    meta = {e.params["name"]: dict(e.params["metadata"]) for e in eqns}
    assert set(meta) == {"flash_fwd", "flash_dq", "flash_dkv"}
    for name, m in meta.items():
        assert m["kernel"] == name
        assert (m["block_q"], m["block_k"]) == (256, 128)
        assert m["chunk"] == (256 if name == "flash_dkv" else 128)
        # 4 heads x (8 tiles, 6 run, 4 masked)
        assert (m["chunks_total"], m["chunks_run"],
                m["chunks_masked"]) == (32, 24, 16)


def test_blocks_that_do_not_tile_are_refused():
    q, k, v = make_qkv(B=1, H=1, S=384)
    with pytest.raises(ValueError, match="multiple of block sizes"):
        flash_attention(q, k, v, block_q=256, block_k=128, interpret=True)
    with pytest.raises(ValueError, match="divide the other"):
        flash_attention(q, k, v, block_q=192, block_k=128, interpret=True)


def test_default_block_sizes_for_non_512_multiples():
    # DEFAULT_BLOCK=512 must degrade to a divisor of S (r3 review finding:
    # S=640/768 are multiples of 128 but not 512)
    assert default_block(1024) == 512
    assert default_block(768) == 256
    assert default_block(640) == 128
    assert default_block(64) == 64
    assert default_block(192) == 192
    assert default_block(4000) == 400
    q, k, v = make_qkv(B=1, H=2, S=640, D=64, seed=5)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref_attention_bhsd(q, k, v, True, 1.0 / (q.shape[-1] ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("D", [64, 128])
def test_bf16_forward(D):
    # D=64 folds the softmax scale (1/8) into the query block, exactly;
    # D=128's 1/sqrt(128) is no power of two and scales the scores
    q, k, v = make_qkv(S=128, D=D, dtype=jnp.bfloat16, seed=3)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref_attention_bhsd(q, k, v, True, 1.0 / (q.shape[-1] ** 0.5))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)
