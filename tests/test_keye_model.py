"""Keye-VL-2.0's language model through `HybridDecoder` (grouped-query
attention behind DeepSeek Sparse Attention's learned selection of positions,
with rotary and q/k norm; softmax-routed experts with no shared expert)
against the benchmark's plain reference, at tiny sizes (topk 8, blocks of 4,
2 key/value heads) on seeded weights: the served path at contexts past topk,
the layer at contexts within it against dense causal attention, prefill's
and decode's selections against each other, the expert shares against the
uncut layer, and the key pool written in whole rows (the programs of every
serve configuration are pinned in `test_serving_programs.py`)."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_serving_programs import lowered_texts               # noqa: E402

from benchmark.harness import reference_keye as ref            # noqa: E402
from benchmark.harness import weights_keye as wk               # noqa: E402
from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu.serving import (PagedEngineConfig,             # noqa: E402
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)
from paddle_tpu.text.models import hybrid_ops as ops           # noqa: E402
from paddle_tpu.text.models.hybrid import (HybridConfig,       # noqa: E402
                                           HybridDecoder)

SEED = 2147483783          # past 2**31: seeds need more than 32 bits
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
NAME = "keye_vl2_a3b_ep8_share"


def full_config(name=NAME):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def tiny_config(**over):
    config = tiny_of(full_config())
    config.update(over)
    return config


def model_config(config, **over):
    kw = dict(config["program"]["model_config"])
    kw.update(param_dtype="float32", init_weights=False)
    kw.update(over)
    return HybridConfig(**kw)


def build(config, seed=SEED, **over):
    model = HybridDecoder(model_config(config, **over))
    model.eval()
    model.load_arrays(wk.named(config, seed, "float32"))
    return model


def reference_logits(config, ids, seed=SEED):
    return np.asarray(ref.logits(
        config, lambda: wk.make_globals(config, seed, "float32"),
        lambda i: wk.make_layer(config, seed, i, "float32"),
        jnp.asarray(ids, jnp.int32)))


def normed_input(config, t, seed=3):
    x = jax.random.normal(jax.random.key(seed), (t, config["hidden_size"]))
    return ops.rms_norm(x, jnp.ones((config["hidden_size"],)), 1e-6)


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max(), scale)


@pytest.fixture
def float32_matmuls(monkeypatch):
    """The program's matmuls widened to float32: nothing but rounding then
    separates it from the reference."""
    monkeypatch.setattr(ops, "mm", lambda spec, a, b: jnp.einsum(
        spec, a.astype(jnp.float32), b.astype(jnp.float32),
        precision=ops.HIGHEST))
    with jax.default_matmul_precision("highest"):
        yield


def mixer_inputs(config, cfg, t, seed=SEED):
    """The layer-0 mixer's inputs over a normed x [t, H], in float32."""
    w = wk.make_layer(config, seed, 0, "float32")
    x = normed_input(config, t)
    at = jnp.arange(t)
    q, row = ops.gqa_project(x, w, cfg, at, jnp.float32)
    q_i, wts, key = ops.dsa_index_project(x, w, cfg, at, jnp.float32)
    return w, x, q, row, q_i, wts, key


# ------------------------------------------------------------ the mixer

@pytest.mark.parametrize("t", [8, 5])
def test_within_topk_the_layer_is_dense_causal_attention(float32_matmuls, t):
    """Every context within topk: every earlier position is selected, so
    the layer is the dense causal softmax with rotary and q/k norm."""
    config = tiny_config()
    cfg = model_config(config)
    w, x, q, row, q_i, wts, key = mixer_inputs(config, cfg, t)
    dense = ops.gqa_prefill(q, row, w, cfg)
    close(ops.dsa_prefill(q, row, q_i, wts, key, w, cfg)[0], dense, 1e-5)
    flat = ref.flat(config)
    close(ref.dsa_mixer(x, w, flat, "float32"), dense, 1e-5)
    # and the decode of the last token over every row
    picked = ops.dsa_select_decode(q_i[-1:], wts[-1:], key[None],
                                   jnp.array([t - 1]), cfg)
    close(ops.dsa_decode(q[-1:], row[picked], picked, jnp.array([t - 1]),
                         w, cfg), dense[-1:], 1e-5)


@pytest.mark.parametrize("t", [30, 17])
def test_past_topk_prefill_and_decode_are_the_reference(float32_matmuls, t):
    config = tiny_config()
    cfg = model_config(config)
    w, x, q, row, q_i, wts, key = mixer_inputs(config, cfg, t)
    want = ref.dsa_mixer(x, w, ref.flat(config), "float32")
    close(ops.dsa_prefill(q, row, q_i, wts, key, w, cfg)[0], want, 1e-5)
    pos = jnp.arange(t)
    picked = ops.dsa_select_decode(
        q_i, wts, jnp.broadcast_to(key[None], (t,) + key.shape), pos, cfg)
    assert picked.shape == (t, 8)
    close(ops.dsa_decode(q, row[picked], picked, pos, w, cfg), want, 1e-5)
    # the selection bites: dense attention is another answer
    dense = np.asarray(ops.gqa_prefill(q, row, w, cfg))
    assert np.abs(dense - np.asarray(want)).max() > 1e-2 * \
        np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("gain", [4.0, 8.0])
def test_large_qk_norm_gains_keep_prefill_finite_and_the_reference(
        float32_matmuls, gain):
    """Trained q/k-norm gains of 4-8 make a query's scores lie hundreds
    below |q| max |k|: a shift by that bound would flush every term to 0
    and divide 0 by 0. Prefill shifts by the selected scores' own maximum,
    as decode's softmax does, so both stay the reference."""
    config = tiny_config()
    cfg = model_config(config)
    w = dict(wk.make_layer(config, SEED, 0, "float32"))
    w["q_norm"], w["k_norm"] = w["q_norm"] * gain, w["k_norm"] * gain
    t = 30
    x = normed_input(config, t)
    at = jnp.arange(t)
    q, row = ops.gqa_project(x, w, cfg, at, jnp.float32)
    q_i, wts, key = ops.dsa_index_project(x, w, cfg, at, jnp.float32)
    got, _ = ops.dsa_prefill(q, row, q_i, wts, key, w, cfg)
    assert np.isfinite(np.asarray(got)).all()
    want = ref.dsa_mixer(x, w, ref.flat(config), "float32")
    close(got, want, 1e-5)
    picked = ops.dsa_select_decode(
        q_i, wts, jnp.broadcast_to(key[None], (t,) + key.shape), at, cfg)
    close(ops.dsa_decode(q, row[picked], picked, at, w, cfg), got, 1e-5)
    # some query's every score lies further under that bound than a float32
    # normal reaches below 1: exp(-88) is a denormal, which a TPU flushes
    d, kv = cfg.head_dim, cfg.num_kv_heads
    k = row[:, :kv * d].reshape(t, kv, d)
    qg = q.reshape(t, kv, -1, d)
    scores = jnp.einsum("tkgd,lkd->tkgl", qg, k) * d ** -0.5
    top = jnp.where(jnp.tril(jnp.ones((t, t), bool))[:, None, None],
                    scores, -jnp.inf).max(-1)
    bound = jnp.linalg.norm(qg, axis=-1) \
        * jnp.linalg.norm(k, axis=-1).max() * d ** -0.5
    assert float((bound - top).max()) > 88.0


def prefill_sets(scores, k):
    """{query: the positions its prefill block selects}: the scores of each
    block of rows as the prefill forms them, causality applied."""
    t = scores.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    chosen = ops.dsa_select_mask(jnp.where(causal, scores, -jnp.inf), k) \
        & causal
    return [set(np.flatnonzero(np.asarray(r)).tolist()) for r in chosen]


def decode_sets(q_i, wts, key, cfg):
    t = key.shape[0]
    pos = jnp.arange(t)
    picked = np.asarray(ops.dsa_select_decode(
        q_i, wts, jnp.broadcast_to(key[None], (t,) + key.shape), pos, cfg))
    return [set(p for p in row.tolist() if p <= i)
            for i, row in enumerate(picked)]


@pytest.mark.parametrize("ties", ["none", "all", "some"])
def test_prefill_and_decode_select_the_same_positions(ties):
    """At float32, ties included: all keys alike (every score ties, the
    lower positions win), or every other key a copy of its neighbour."""
    config = tiny_config()
    cfg = model_config(config)
    t = 29
    _, _, _, _, q_i, wts, key = mixer_inputs(config, cfg, t)
    if ties == "all":
        key = jnp.broadcast_to(key[:1], key.shape)
    elif ties == "some":
        key = jnp.repeat(key[::2], 2, axis=0)[:t]
    scores = ops.dsa_index_scores(q_i, wts, key)
    got, want = prefill_sets(scores, 8), decode_sets(q_i, wts, key, cfg)
    assert got == want
    assert [len(s) for s in got] == [min(8, i + 1) for i in range(t)]
    if ties == "all":
        assert got[-1] == set(range(8))
    if ties == "some":
        sizes = [len(set(np.asarray(scores)[i, :i + 1].tolist()))
                 for i in range(t)]
        assert max(sizes) < t


def test_the_threshold_is_exact_over_the_bits():
    """dsa_select_mask keeps exactly k of a row, the k largest, ties to the
    lower index, whatever the signs and magnitudes."""
    rng = np.random.default_rng(5)
    rows = np.concatenate([
        rng.normal(size=(4, 300)) * 10.0 ** rng.integers(-30, 30, (4, 1)),
        np.round(rng.normal(size=(4, 300)), 1),          # many ties
        np.full((1, 300), -np.inf)], 0).astype(np.float32)
    rows[0, :5] = [np.inf, -0.0, 0.0, 1e-45, -1e-45]
    got = np.asarray(ops.dsa_select_mask(jnp.asarray(rows), 37))
    for r, g in zip(rows, got):
        order = sorted(range(len(r)), key=lambda i: (-r[i], i))[:37]
        assert g.sum() == 37 and set(np.flatnonzero(g)) == set(order)


# ------------------------------------------------ the threshold kernel

def block_scores(case, b, width, seed=7):
    """[b, width + b] float32 index scores of one query block whose row r
    sees the columns up to width - b + r. What no row sees is filled with
    values above every visible one, so a kernel that read them would count
    them."""
    rng = np.random.default_rng(seed)
    s = rng.normal(size=(b, width + b))
    if case == "ties":
        s = np.round(s, 1)
    elif case == "signs":
        s = s * 10.0 ** rng.integers(-45, 38, s.shape)
        s[:, :6] = [np.inf, -np.inf, -0.0, 0.0, 1e-45, -1e-45]
        s[:, 6:12] = 1e-40                               # denormal ties
    elif case == "ninf_tail":
        s[:, width - b - 3:] = -np.inf
    elif case == "few_finite":
        s[:, 4:] = -np.inf
    s = s.astype(np.float32)
    seen = np.arange(width + b)[None] <= width - b + np.arange(b)[:, None]
    return np.where(seen, s, np.float32(3e38)), seen


def xla_bisection(scores, seen, k):
    """(kth, above, at) by the XLA bisection, over the visible keys."""
    key = np.asarray(jnp.where(seen, ops._order_key(jnp.asarray(scores)),
                               jnp.uint32(0)))
    kth = np.asarray(ops._kth_key(jnp.asarray(key), k))
    return kth, (seen & (key > kth[:, None])).sum(1), \
        (seen & (key == kth[:, None])).sum(1)


@pytest.mark.parametrize("b,width,k", [(12, 12, 5), (96, 288, 37),
                                       (72, 360, 100)],
                         ids=["diagonal", "tiles", "wide_k"])
@pytest.mark.parametrize("case", ["normal", "ties", "signs", "ninf_tail",
                                  "few_finite"])
def test_kernel_threshold_is_the_xla_bisection(case, b, width, k):
    """The kernel's k-th largest visible order key of each row, and the
    keys above and at it, are the XLA bisection's to the bit: ties, +-0,
    +-inf, denormals, -inf tails, rows with fewer than k finite keys, the
    diagonal key block alone, widths no multiple of the row tile or of the
    lanes, and two row tiles."""
    from paddle_tpu.ops.pallas.topk_threshold import row_threshold
    scores, seen = block_scores(case, b, width)
    got = [np.asarray(a) for a in row_threshold(
        jnp.asarray(scores), jnp.int32(width), k, interpret=True)]
    want = xla_bisection(scores, seen, k)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    if case == "few_finite":
        # -inf's order key where a row sees k or more, else 0
        assert (got[0] == np.where(seen.sum(1) < k, 0, 0x7FFFFF)).all()


@pytest.mark.parametrize("ties", ["none", "all", "some"])
@pytest.mark.parametrize("i", [0, 1, 3, 6])
def test_block_selection_is_dsa_select_mask(ties, i):
    """What a prefill query block keeps by its threshold (`dsa_kept` of
    `dsa_block_threshold`: the kernel's threshold, the tie path) is what
    `dsa_select_mask` keeps over its causal row, position for
    position: a block within topk, the first past it, and wider ones; the
    tie path engages where a row holds more at its k-th than places left,
    and only there."""
    blk, k, t = 4, 8, 28
    rng = np.random.default_rng(11 + i)
    scores = rng.normal(size=(blk, t)).astype(np.float32)
    if ties == "all":
        scores[:] = 0.5
    elif ties == "some":
        scores = np.round(scores, 0)
    lo = i * blk
    scores[:, lo + blk:] = 3e38        # past its own key block: never read
    scores = jnp.asarray(scores)
    kth, last, tie = ops.dsa_block_threshold(scores, lo, k)
    pos = jnp.arange(t)
    causal = pos[None, :] <= lo + jnp.arange(blk)[:, None]
    got = ops.dsa_kept(scores, pos[None, :], kth, last) & causal
    want = ops.dsa_select_mask(jnp.where(causal, scores, -jnp.inf), k) \
        & causal
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got).sum(1)
            == np.minimum(k, lo + 1 + np.arange(blk))).all()
    # no tie within topk, every block past it when all scores are equal,
    # none among distinct ones
    assert not bool(tie) or lo + blk > k
    if ties == "all":
        assert bool(tie) == (lo + blk > k)
    if ties == "none":
        assert not bool(tie)


def parent_prefill(q, row, q_i, wts, key, w, cfg):
    """The prefill as it was before the kernel: every query's selection by
    `dsa_select_mask` over its whole causal row, then attention over the
    positions selected."""
    t = q.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = ops.dsa_index_scores(q_i, wts, key)
    chosen = ops.dsa_select_mask(jnp.where(causal, scores, -jnp.inf),
                                 cfg.index_topk) & causal
    return ops._gqa_attend(q[None], row[None], chosen[None], w, cfg)[0]


@pytest.mark.parametrize("ties", ["none", "all", "some"])
def test_prefill_over_many_blocks_is_the_parents(float32_matmuls, ties):
    """`dsa_prefill` over a prompt of ten query blocks, eight of them past
    topk, gives what the prefill gave before the kernel found its
    thresholds, ties included; it counts the blocks that broke ties."""
    config = tiny_config()
    cfg = model_config(config)
    t, blk, k = 40, cfg.index_block, cfg.index_topk
    w, x, q, row, q_i, wts, key = mixer_inputs(config, cfg, t)
    if ties == "all":
        key = jnp.broadcast_to(key[:1], key.shape)
    elif ties == "some":
        key = jnp.repeat(key[::2], 2, axis=0)[:t]
    got, tied = ops.dsa_prefill(q, row, q_i, wts, key, w, cfg)
    close(got, parent_prefill(q, row, q_i, wts, key, w, cfg), 1e-5)
    # a row ties where more of its scores equal its k-th largest than
    # the places left above it (the tiny indexer's relu leaves exact 0s)
    scores = np.asarray(ops.dsa_index_scores(q_i, wts, key))
    tied_rows = []
    for r in range(t):
        top = np.sort(scores[r, :r + 1])[::-1]
        kth = top[min(k, r + 1) - 1]
        tied_rows.append(r + 1 > k and (top == kth).sum()
                         > k - (top > kth).sum())
    assert int(tied) == sum(any(tied_rows[i:i + blk])
                            for i in range(0, t, blk))
    if ties == "all":
        assert int(tied) == t // blk - k // blk    # every block past topk


# ---------------------------------------------------------- expert shares

def test_softmax_router_is_the_reference(float32_matmuls):
    config = tiny_config()
    cfg = model_config(config)
    w = wk.make_layer(config, SEED, 1, "float32")
    assert "router_bias" not in w and "ws_up" not in w
    x = normed_input(config, 40)
    got, counters = ops.moe_share(x, w, cfg, jnp.ones((40,), bool))
    close(got, ref.moe_ffn(x, w, ref.flat(config), "float32"), 1e-5)
    assert int(counters[0]) == 40 * 4


def test_eight_shares_add_up_to_the_uncut_expert_layer(float32_matmuls):
    """The 8 chips' routed parts (2 of 16 experts each) are the uncut
    layer: there is no shared expert to count once."""
    config = tiny_config(num_experts=2, num_local_experts=2)
    x = normed_input(config, 48)
    whole = dict(config, num_experts=16, num_local_experts=16,
                 experts_held_first=0)
    uncut = ref.moe_ffn(x, wk.make_layer(whole, SEED, 0, "float32"),
                        ref.flat(whole), "float32")
    total, picks = 0.0, 0
    for chip in range(8):
        share = dict(config, experts_held_first=2 * chip)
        w = wk.make_layer(share, SEED, 0, "float32")
        cfg = model_config(share, num_experts=2, experts_first=2 * chip)
        got, counters = ops.moe_share(x, w, cfg, jnp.ones((48,), bool))
        total = total + got
        picks += int(counters[1])
    close(total, uncut, 1e-5)
    assert picks == 48 * 4               # every pick fell on some chip


def test_a_long_prefill_runs_its_experts_a_block_of_tokens_at_a_time(
        float32_matmuls, monkeypatch):
    config = tiny_config()
    cfg = model_config(config)
    w = wk.make_layer(config, SEED, 0, "float32")
    x = normed_input(config, 40)
    whole, _ = ops.moe_share(x, w, cfg, jnp.ones((40,), bool))
    monkeypatch.setattr(ops, "MOE_HIDDEN_BYTES", 4 * 4 * 32 * 16)
    assert ops.moe_token_block(4, 40, 32) == 16
    blocked, _ = ops.moe_share(x, w, cfg, jnp.ones((40,), bool))
    close(blocked, whole, 1e-6)


# ------------------------------------------------------ what it declares

def test_layers_are_what_the_configuration_declares():
    config = tiny_config()
    model = build(config)
    assert model.kinds == [("gqa", "moe")] * 2
    assert model.cache_layout() == (blocks.SelectSpec(2 * 2 * 16, 8, 8),) * 2
    shapes = model.parameter_shapes()
    for i in range(2):
        mine = {n.split(".", 2)[2]: s for n, s in shapes.items()
                if n.startswith(f"layers.{i}.")}
        assert mine == wk.layer_shapes(config)
    assert {"layers.0.q_norm", "layers.0.k_norm", "layers.0.k_idx_norm",
            "layers.0.k_idx_bias", "layers.1.router"} \
        <= model.float32_parameters()
    big = HybridDecoder(HybridConfig(**full_config()["program"][
        "model_config"])).cache_layout()
    assert big == (blocks.SelectSpec(1024, 64, 2048),) * 8


@pytest.mark.parametrize("bad", [
    {"index_topk": 0}, {"index_head_dim": 7}, {"router_score": "relu"},
    {"mixers": ["kda", "mla"], "index_topk": 8}])
def test_configuration_that_cannot_be_built_raises(bad):
    with pytest.raises(ValueError):
        model_config(tiny_config(), **bad)


def test_the_two_rows_of_a_token_share_one_table():
    """The K/V row and the indexer key of a token land under the same table
    entry; the keys of a block sit side by side in one row."""
    tables = jnp.array([[3, 1], [2, 0]], jnp.int32)
    pos = jnp.array([5, 2])
    pool = jnp.zeros((4, 4, 6))
    keys = jnp.zeros((4, 4 * 3))
    row = jnp.arange(12.0).reshape(2, 1, 6)
    key = -jnp.arange(6.0).reshape(2, 1, 3) - 1
    pool = blocks.write(pool, row, tables, pos)
    keys = blocks.write_keys(keys, key, tables, pos)
    assert np.asarray(pool[1, 1]).tolist() == list(range(6))
    assert np.asarray(keys[1, 3:6]).tolist() == [-1.0, -2.0, -3.0]
    assert np.asarray(keys[2, 6:9]).tolist() == [-4.0, -5.0, -6.0]
    view = blocks.gather_keys(keys, tables, 3)
    assert view.shape == (2, 8, 3)
    assert np.asarray(view[0, 5]).tolist() == [-1.0, -2.0, -3.0]
    got = blocks.rows_at(pool, tables, jnp.array([[5, 0], [2, 9]]))
    assert np.asarray(got[0, 0]).tolist() == list(range(6))
    assert np.asarray(got[1, 0]).tolist() == list(range(6, 12))
    assert blocks.layout_bytes((blocks.SelectSpec(6, 3, 2),), 4,
                               np.float32) == (4 * 9 * 4, 0)
    assert blocks.SelectSpec(6, 3, 2).selected_pairs(5) == 1 + 2 * 4
    assert blocks.SelectSpec(6, 3, 8).selected_pairs(5) == 15


def write_keys_per_key(pool, new, tables, pos):
    """The oracle: the key write as one e-wide window a key, scattered at
    its row's lanes of its block's row (`new` [S, T, e] at positions
    `pos + 0..T-1`; past the table, the garbage block)."""
    s, t, e = new.shape
    bs, nb = pool.shape[1] // e, tables.shape[1]
    positions = pos.astype(jnp.int32)[:, None] + jnp.arange(t)[None, :]
    lb = positions // bs
    phys = jnp.where(lb < nb, jnp.take_along_axis(
        tables, jnp.minimum(lb, nb - 1), axis=1), blocks.GARBAGE_BLOCK)
    at = jnp.stack([phys, positions % bs * e], -1).reshape(s * t, 2)
    return jax.lax.scatter(
        pool, at, new.reshape(s * t, e).astype(pool.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 1)),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def key_pool(n, bs, e, seed=0):
    """A pool of `n` blocks holding distinct values, so a row written where
    it should not be shows."""
    return jax.random.normal(jax.random.key(seed), (n, bs * e))


def outside_garbage(pool):
    """What the garbage block holds is any writer's: the rest must match."""
    return np.asarray(pool)[blocks.GARBAGE_BLOCK + 1:]


@pytest.mark.parametrize("row,t", [
    ([5, 2, 6], 12),             # every block in the table, out of order
    ([3, 6, 1, 0], 12),          # an unallocated entry behind the keys
    ([4, 1], 16),                # blocks past the table: the garbage block
    ([2, 5, 3], 8)])             # fewer keys than the table holds
def test_a_prefill_writes_its_keys_as_the_per_key_write(row, t):
    """A prefill's keys from position 0, a block row at a time, are the
    per-key write bit for bit wherever a slot's table reaches; the real
    keys are fewer than the bucket (length 9), the padding lands as the
    per-key write puts it."""
    bs, e = 4, 3
    tables = jnp.array([row], jnp.int32)
    pool = key_pool(7, bs, e)
    new = jax.random.normal(jax.random.key(1), (1, t, e))
    new = jnp.where(jnp.arange(t)[None, :, None] < 9, new, 100.0)
    got = blocks.write_keys(pool, new, tables)
    want = write_keys_per_key(pool, new, tables, jnp.zeros((1,), jnp.int32))
    np.testing.assert_array_equal(outside_garbage(got),
                                  outside_garbage(want))
    assert not np.array_equal(outside_garbage(got),
                              outside_garbage(pool))


def test_a_prefill_of_part_of_a_block_pads_past_its_keys():
    """A bucket that is not whole blocks: every key where the per-key write
    puts it, and the rest of the last row zeros, past every real key."""
    bs, e, t = 4, 3, 10
    tables = jnp.array([[5, 2, 6]], jnp.int32)
    pool = key_pool(7, bs, e)
    new = jax.random.normal(jax.random.key(1), (1, t, e))
    got = np.asarray(blocks.write_keys(pool, new, tables))
    want = np.asarray(write_keys_per_key(pool, new, tables,
                                         jnp.zeros((1,), jnp.int32)))
    np.testing.assert_array_equal(got[6, :2 * e], want[6, :2 * e])
    assert not got[6, 2 * e:].any()
    np.testing.assert_array_equal(np.delete(got, [0, 6], 0),
                                  np.delete(want, [0, 6], 0))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_decode_writes_one_key_a_slot_as_the_per_key_write(dtype):
    """Four slots, one key each: the block row read, the key laid over its
    lanes, the row written back, bit for bit the per-key write; the dead
    slot (all of its table the garbage block) and a position past the
    table write the garbage block alone."""
    bs, e = 4, 3
    tables = jnp.array([[3, 1, 0], [2, 6, 0], [0, 0, 0], [5, 4, 7]],
                       jnp.int32)
    pos = jnp.array([5, 3, 0, 12], jnp.int32)
    pool = key_pool(8, bs, e).astype(dtype)
    new = jax.random.normal(jax.random.key(1), (4, 1, e))
    got = blocks.write_keys(pool, new, tables, pos)
    want = write_keys_per_key(pool, new, tables, pos)
    assert got.dtype == dtype
    np.testing.assert_array_equal(outside_garbage(got),
                                  outside_garbage(want))
    changed = np.flatnonzero((np.asarray(got, np.float32)
                              != np.asarray(pool, np.float32)).any(1))
    assert set(changed) - {blocks.GARBAGE_BLOCK} == {1, 2}
    with pytest.raises(ValueError):
        blocks.write_keys(pool, jnp.concatenate([new, new], 1), tables, pos)


# ------------------------------------------------------------ served path

def served_logits(model, prompt, new_tokens, **engine_kw):
    """Logits of the served path at every generated position, and the
    tokens: prefill, then decode through the cache in slot 1 while slot 0
    serves another request."""
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=4, capture_logits=True,
        **engine_kw))
    eng.prefill(0, [7, 8, 9, 10, 11, 12, 13, 14, 15, 16])
    tokens = [eng.prefill(1, prompt)]
    rows = []
    for _ in range(new_tokens):
        out = eng.decode()
        rows.append(eng.last_logits[1])
        tokens.append(int(out[1]))
    return np.stack(rows), tokens


def test_served_path_is_the_reference_past_topk(float32_matmuls):
    """Scheduler-free engine path (prefill, then decode through both rows of
    the cache) against the reference's full forward, at contexts from 21
    past topk 8 to 33."""
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(1).integers(0, 500, 21).tolist()
    got, tokens = served_logits(model, prompt, 12, kv_dtype="float32")
    full = reference_logits(config, prompt + tokens[:-1])
    close(got, full[len(prompt):len(prompt) + 12], 1e-4)
    first = full[len(prompt) - 1]
    assert first.argmax() == tokens[0]


def test_bfloat16_engine_serves_and_stays_near_the_reference():
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(2).integers(0, 500, 17).tolist()
    got, tokens = served_logits(model, prompt, 6, weight_dtype="bfloat16",
                                kv_dtype="bfloat16")
    want = reference_logits(config, prompt + tokens[:-1])[17:23]
    close(got, want, 0.15)


def test_a_long_prompt_hands_back_its_last_row_of_logits(monkeypatch):
    """Past PREFILL_LOGIT_BYTES the prefill computes the head at the last
    real token alone; the first token is the same."""
    from paddle_tpu.text.models import hybrid
    model = build(tiny_config())
    prompt = list(range(3, 26))
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=4))
    want = eng.prefill(0, prompt)
    monkeypatch.setattr(hybrid, "PREFILL_LOGIT_BYTES", 4 * 512)
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=4))
    assert eng.prefill(0, prompt) == want


def test_scheduler_serves_and_the_spans_count_the_selection():
    from paddle_tpu import profiler
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=4))
    assert eng.state_store is None
    assert eng.kv_block_bytes == 2 * 4 * (64 + 8) * 4
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    log = profiler.span_log()
    before = log.appended
    handles = [sched.submit(list(range(3, 3 + n)), 4) for n in (17, 5)]
    while sched.step():
        pass
    assert all(len(h.tokens) == 4 for h in handles)
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    prefills = [s["attrs"] for s in spans if s["name"] == "serving::prefill"]
    # two layers; 17 tokens: 153 causal pairs, of which 8 a query past 8
    assert prefills[0]["dsa_index_pairs"] == 2 * 153
    assert prefills[0]["dsa_selected_pairs"] == 2 * (36 + 9 * 8)
    assert prefills[1]["dsa_selected_pairs"] == 2 * 15
    steps = [s["attrs"] for s in spans
             if s["name"] == "serving::decode_step"]
    first = steps[0]
    assert first["dsa_rows_indexed"] == 2 * (18 + 6)
    assert first["dsa_rows_selected"] == 2 * (8 + 6)
    assert "dsa_kv_rows_read" not in first
    # the program's own count of the rows it read rides in with the call's
    # counters, beside the host's count of those it selected: 2 layers x
    # 2 slots x topk 8 rows a decode; a prefill's 8 blocks of 4 queries
    # read 1 + ... + 8 key blocks of 4 a layer
    waits = [s["attrs"] for s in spans if s["name"] == "serving::decode.wait"
             and "call" in s["attrs"]]
    assert waits[0]["dsa_rows_selected"] == first["dsa_rows_selected"]
    assert all(a["dsa_kv_rows_read"] == 2 * 2 * 8 for a in waits)
    assert prefills[0]["bucket"] == 32
    assert prefills[0]["dsa_kv_rows_read"] == 2 * 4 * (8 * 9 // 2)
    # and the key block rows it wrote whole: 2 layers x bucket / block
    # size on a prefill, 2 layers x 2 slots on a decode
    assert all(a["dsa_key_blocks_written"] == 2 * a["bucket"] // 4
               for a in prefills)
    assert all(a["dsa_key_blocks_written"] == 2 * 2 for a in waits)


def test_the_spans_count_the_blocks_the_kernel_thresholded():
    """A prefill's span counts the query blocks whose threshold the kernel
    found (every block wider than topk, in both layers) and those of them
    that broke ties; a decode, which selects by `lax.top_k`, counts 0."""
    from paddle_tpu import profiler
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=4))
    log = profiler.span_log()
    before = log.appended
    eng.prefill(0, list(range(3, 20)))
    eng.prefill(1, list(range(40, 70)))
    for _ in range(2):
        eng.decode()
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    prefills = [s["attrs"] for s in spans if s["name"] == "serving::prefill"]
    assert [a["bucket"] for a in prefills] == [32, 32]
    for a in prefills:
        # 8 query blocks of 4 a layer, the first 2 within topk 8
        assert a["dsa_select_kernel_blocks"] == 2 * (8 - 2)
        assert 0 <= a["dsa_select_tie_blocks"] <= 2 * (8 - 2)
    waits = [s["attrs"] for s in spans if s["name"] == "serving::decode.wait"
             and "call" in s["attrs"]]
    assert waits and all(a["dsa_select_kernel_blocks"] == 0
                         and a["dsa_select_tie_blocks"] == 0 for a in waits)


SCATTER = re.compile(
    r'"stablehlo\.scatter"\(.*?update_window_dims = \[([\d, ]*)\], '
    r'inserted_window_dims = \[([\d, ]*)\].*?\}\) : '
    r'\((tensor<[^>]*>), (tensor<[^>]*>), (tensor<[^>]*>)\)', re.S)


def test_every_key_write_is_whole_block_rows():
    """In the tiny engine's decode and prefill programs, every scatter into
    a selecting layer's key pool [N, block_size * e] writes whole rows: a
    window of the row's width, never one key's e lanes (which the TPU
    compiler builds as a loop of one trip a key)."""
    texts, eng = lowered_texts(NAME)
    keys = {l.keys.shape for l in eng._pool}
    assert len(keys) == 1
    n, width = keys.pop()
    pool = f"tensor<{n}x{width}x"
    for name, text in texts.items():
        into = [m for m in SCATTER.finditer(text)
                if m.group(3).startswith(pool)]
        assert len(into) == 2, name               # one a selecting layer
        for m in into:
            update, inserted, updates = m.group(1), m.group(2), m.group(5)
            assert (update, inserted) == ("1", "0"), (name, m.group(0))
            assert updates.split("x")[1] == str(width), (name, updates)
