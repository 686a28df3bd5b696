"""Pallas paged-attention kernel (ISSUE 7 tentpole a): the in-kernel
block-table walk must be exact against the gather path in interpret
mode, hold the PR 6 NaN regressions without the dense view, serve its
tile caps through the shipped autotune table with the
fall-back-don't-raise contract, and drive the paged engine token-exactly
behind the `attention_impl="kernel"` flag with compile counts intact.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.incubate import autotune
from paddle_tpu.ops.pallas.paged_attention import (
    DecodePlan, _largest_divisor_leq, decode_kernel_takes, paged_attention,
    paged_decode_attention)
from paddle_tpu.serving import GenerationEngine, PagedGenerationEngine
from paddle_tpu.serving import blocks as blk
from paddle_tpu.text.models import gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))


@pytest.fixture(scope="module")
def tiny():
    m = gpt_tiny()
    m.eval()
    return m


def _paged_state(seed, S, bs, nb, N, H=4, D=8, poison_garbage=False):
    """A valid paged KV state: every slot's table is filled with real
    blocks front-to-garbage-back, so any pos within the allocated run is
    backed (the engine invariant: blocks are allocated+written before
    they become visible)."""
    rng = np.random.RandomState(seed)
    kp = rng.randn(N, bs, H, D).astype(np.float32)
    vp = rng.randn(N, bs, H, D).astype(np.float32)
    if poison_garbage:
        kp[blk.GARBAGE_BLOCK] = np.nan
        vp[blk.GARBAGE_BLOCK] = np.inf
    # distinct physical blocks 1..N-1 dealt to slots round-robin
    perm = rng.permutation(np.arange(1, N))
    tables = np.zeros((S, nb), np.int32)
    flat = iter(perm)
    for s in range(S):
        for j in range(nb):
            tables[s, j] = next(flat)
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables)


def _assert_matches_gather(q, kp, vp, tables, pos, **kw):
    want = np.asarray(blk.attend(q, kp, vp, tables, pos))
    got = np.asarray(paged_attention(q, kp, vp, tables, pos, **kw))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- kernel exactness
def test_kernel_matches_gather_across_block_boundaries():
    """Decode shape (T=1) at positions crossing every boundary of the
    block ladder — including pos exactly at a block edge and one short
    of it."""
    bs, nb = 4, 6
    S = 7
    kp, vp, tables = _paged_state(0, S, bs, nb, N=S * nb + 1)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(S, 1, 4, 8).astype(np.float32))
    # 0, edge-1, edge, edge+1, mid, last-1, last
    pos = jnp.asarray([0, 3, 4, 5, 13, 22, 23], jnp.int32)
    _assert_matches_gather(q, kp, vp, tables, pos)


def test_kernel_matches_gather_prefill_shapes():
    """Multi-token windows (prefill buckets / spec verify windows) with
    ragged per-slot occupancy."""
    bs, nb = 4, 8
    S = 3
    kp, vp, tables = _paged_state(2, S, bs, nb, N=S * nb + 1)
    rng = np.random.RandomState(3)
    for T in (2, 8, 16):
        q = jnp.asarray(rng.randn(S, T, 4, 8).astype(np.float32))
        pos = jnp.asarray([0, 5, nb * bs - T], jnp.int32)   # ragged
        _assert_matches_gather(q, kp, vp, tables, pos)


def test_kernel_poisoned_garbage_block_stays_finite():
    """The PR 6 NaN regression, in-kernel: the garbage block holds
    inf/NaN scatter junk; masked probabilities and the never-visible V
    rows must keep every output finite AND equal to the gather path."""
    bs, nb = 4, 4
    S = 2
    kp, vp, tables = _paged_state(4, S, bs, nb, N=S * nb + 1,
                                  poison_garbage=True)
    # tail table entries point at the (poisoned) garbage block — the
    # unallocated-logical-block layout prefill actually produces
    tables = np.asarray(tables).copy()
    tables[0, 2:] = blk.GARBAGE_BLOCK
    tables[1, 1:] = blk.GARBAGE_BLOCK
    tables = jnp.asarray(tables)
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(S, 2, 4, 8).astype(np.float32))
    pos = jnp.asarray([6, 2], jnp.int32)     # writes stay inside owned blocks
    _assert_matches_gather(q, kp, vp, tables, pos)


def test_kernel_all_masked_rows_emit_zeros():
    """A slot with no visible key (pos<0 models a hole) emits exact
    zeros even over a fully-poisoned pool — the l==0 guard."""
    bs, nb = 4, 2
    kp, vp, tables = _paged_state(6, 1, bs, nb, N=3, poison_garbage=True)
    kp = jnp.asarray(np.full(kp.shape, np.nan, np.float32))
    vp = jnp.asarray(np.full(vp.shape, np.nan, np.float32))
    q = jnp.asarray(np.random.RandomState(7).randn(1, 1, 4, 8)
                    .astype(np.float32))
    out = np.asarray(paged_attention(q, kp, vp, tables,
                                     jnp.asarray([-1], jnp.int32)))
    assert (out == 0.0).all()


def test_kernel_tiling_caps_do_not_change_results():
    """Every (q_tile, head_tile) cap combination — divisor or not — is
    clamped to a valid tile and yields the same output."""
    bs, nb = 4, 4
    S = 2
    kp, vp, tables = _paged_state(8, S, bs, nb, N=S * nb + 1)
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(S, 6, 4, 8).astype(np.float32))
    pos = jnp.asarray([1, 9], jnp.int32)
    want = np.asarray(paged_attention(q, kp, vp, tables, pos,
                                      q_tile=6, head_tile=4))
    for qt, ht in ((1, 1), (2, 2), (3, 4), (4, 3), (100, 100)):
        got = np.asarray(paged_attention(q, kp, vp, tables, pos,
                                         q_tile=qt, head_tile=ht))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_largest_divisor_clamp():
    assert _largest_divisor_leq(12, 4) == 4
    assert _largest_divisor_leq(12, 5) == 4
    assert _largest_divisor_leq(7, 4) == 1
    assert _largest_divisor_leq(1, 128) == 1
    assert _largest_divisor_leq(192, 128) == 96


# --------------------------------------------------- autotune integration
def test_shipped_table_serves_paged_entries(tmp_path, monkeypatch):
    """commit_shipped_table(kernel='paged') round-trips through
    lookup_paged_blocks; stale/poisoned entries FALL BACK to None
    instead of raising (the PR 6 contract, extended to this kernel);
    flash entries in the same file are untouched."""
    import jax
    path = str(tmp_path / "tuned.json")
    autotune.commit_shipped_table({(4, 64, 8, 4): (128, 2)},
                                  backend=jax.default_backend(),
                                  kernel="paged", path=path)
    autotune.commit_shipped_table({(4, 64, 8, True): (32, 32)},
                                  backend=jax.default_backend(),
                                  kernel="flash", path=path)
    monkeypatch.setattr(autotune, "_SHIPPED_PATH", path)
    monkeypatch.setattr(autotune, "_disk_loaded", False)
    monkeypatch.setattr(autotune, "_disk_cache", {})
    monkeypatch.setattr(autotune, "_block_cache", {})
    assert autotune.lookup_paged_blocks(4, 64, 8, 4) == (128, 2)
    assert autotune.lookup_flash_blocks(1, 4, 64, 8, True) == (32, 32)
    assert autotune.lookup_paged_blocks(4, 128, 8, 4) is None  # other geom
    # hand-rot the paged entry: lookup falls back, never raises
    raw = json.load(open(path))
    for k in list(raw):
        if json.loads(k)[0] == "paged":
            raw[k] = [0, -3]
    json.dump(raw, open(path, "w"))
    monkeypatch.setattr(autotune, "_disk_loaded", False)
    monkeypatch.setattr(autotune, "_disk_cache", {})
    assert autotune.lookup_paged_blocks(4, 64, 8, 4) is None


def test_commit_rejects_nonsense_paged_entries(tmp_path):
    with pytest.raises(ValueError, match="positive"):
        autotune.commit_shipped_table({(4, 64, 8, 4): (0, 2)},
                                      kernel="paged",
                                      path=str(tmp_path / "t.json"))
    with pytest.raises(ValueError, match="multiple"):
        autotune.commit_shipped_table({(4, 63, 8, 4): (8, 2)},
                                      kernel="paged",
                                      path=str(tmp_path / "t.json"))


def test_shipped_file_carries_both_kernels():
    """The tree's shipped table serves the flash entries it always had
    AND the new paged tile caps."""
    cache = autotune._read_cache_file(autotune._SHIPPED_PATH)
    assert any(k[0] == "paged" for k in cache)
    assert any(k[0] != "paged" for k in cache)    # untagged flash entries
    assert cache[("tpu", 12, 1024, 64, True)] == (512, 512)
    assert cache[("paged", "tpu", 12, 1024, 64, 16)] == (128, 4)


# ------------------------------------------------- engine behind the flag
def test_kernel_engine_token_exact_vs_dense(tiny):
    """The acceptance bar: attention_impl='kernel' reproduces the dense
    engine's exact greedy token streams across block-boundary prompt
    lengths, and still compiles once per executable."""
    lengths = (1, 7, 8, 9, 17, 31)
    prompts = [np.random.RandomState(20 + i).randint(0, 1000, n)
               for i, n in enumerate(lengths)]
    for i in range(0, len(lengths), 2):
        pair = prompts[i:i + 2]
        dense = GenerationEngine(tiny, slots=2, max_len=64)
        kern = PagedGenerationEngine(tiny, slots=2, max_len=64,
                                     block_size=8,
                                     attention_impl="kernel")
        rows_d = [[dense.prefill(s, p)] for s, p in enumerate(pair)]
        rows_k = [[kern.prefill(s, p)] for s, p in enumerate(pair)]
        for _ in range(5):
            sd, sk = dense.decode(), kern.decode()
            for s in range(2):
                rows_d[s].append(int(sd[s]))
                rows_k[s].append(int(sk[s]))
        assert rows_k == rows_d, \
            f"kernel diverged at lengths {[len(p) for p in pair]}"
        assert kern.trace_counts["decode"] == 1


def test_kernel_engine_ragged_occupancy_and_refill(tiny):
    """Mid-flight retire + refill at a different length (ragged slot
    occupancy) stays exact under the kernel impl — the scenario where a
    stale dense view would betray a gather bug."""
    kern = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                 attention_impl="kernel")
    ref = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    for eng in (kern, ref):
        eng.prefill(0, _p(0, 9))
        eng.prefill(1, _p(1, 21))
        for _ in range(3):
            eng.decode()
        eng.reset_slot(0)
        eng.prefill(0, _p(2, 5))
    rows_k, rows_r = [[], []], [[], []]
    for _ in range(4):
        sk, sr = kern.decode(), ref.decode()
        for s in range(2):
            rows_k[s].append(int(sk[s]))
            rows_r[s].append(int(sr[s]))
    assert rows_k == rows_r
    assert kern.trace_counts["decode"] == 1


def _p(seed, n):
    return np.random.RandomState(seed).randint(0, 1000, n)


def test_config_rejects_unknown_impl(tiny):
    with pytest.raises(ValueError, match="attention_impl"):
        PagedGenerationEngine(tiny, slots=1, max_len=32,
                              attention_impl="fused")


# ------------------------------------------------ the decode kernel (T = 1)
def _decode_case(seed, S, bs, nb, H, D, dtype=np.float32, poison=False):
    kp, vp, tables = _paged_state(seed, S, bs, nb, N=S * nb + 1, H=H, D=D,
                                  poison_garbage=poison)
    q = jnp.asarray(np.random.RandomState(seed + 1).randn(S, 1, H, D)
                    .astype(np.float32))
    return q, kp.astype(dtype), vp.astype(dtype), tables


def _assert_decode_matches_gather(q, kp, vp, tables, pos, **kw):
    pos = jnp.asarray(pos, jnp.int32)
    want = np.asarray(blk.attend(q, kp, vp, tables, pos))
    got = np.asarray(paged_decode_attention(q, kp, vp, tables, pos, **kw))
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    return got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("D", [64, 128])
def test_decode_kernel_matches_gather_at_ragged_positions(D, bs, dtype):
    """One kernel for head sizes 64 and 128, block sizes 8 and 16, float32
    and bfloat16 pools: per-slot positions all over the table."""
    S, nb = 5, 6
    q, kp, vp, tables = _decode_case(40 + D + bs, S, bs, nb, H=4, D=D,
                                     dtype=dtype)
    pos = [0, bs + 3, 2 * bs - 1, 4 * bs, nb * bs - 1]
    _assert_decode_matches_gather(q, kp, vp, tables, pos, chunk_blocks=2)


_BS, _NB, _CB = 8, 6, 2          # 48 positions a slot, 16-key chunks


@pytest.mark.parametrize("p", [0, _BS - 1, _BS, _CB * _BS - 1, _CB * _BS,
                               _NB * _BS - 1],
                         ids=["first", "block_end", "block_start",
                              "chunk_end", "chunk_start", "max_len-1"])
def test_decode_kernel_at_every_boundary(p):
    q, kp, vp, tables = _decode_case(50, 3, _BS, _NB, H=4, D=32)
    _assert_decode_matches_gather(q, kp, vp, tables, [p, p, p],
                                  chunk_blocks=_CB)


@pytest.mark.parametrize("layout", ["shuffled", "shared", "repeated"])
def test_decode_kernel_walks_any_table(layout):
    """The table is the only map: physical blocks in any order, one block
    under two slots (a shared prefix), one block twice in a slot."""
    S, bs, nb = 3, 8, 5
    q, kp, vp, tables = _decode_case(60, S, bs, nb, H=4, D=32)
    t = np.asarray(tables).copy()
    if layout == "shuffled":
        t = t[:, ::-1].copy()
    elif layout == "shared":
        t[1, :2] = t[0, :2]
        t[2, :3] = t[0, :3]
    else:
        t[0, 1:4] = t[0, 0]
        t[2, :] = t[2, 2]
    _assert_decode_matches_gather(q, kp, vp, jnp.asarray(t), [37, 20, 39],
                                  chunk_blocks=2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_decode_kernel_never_sees_the_garbage_block(dtype):
    """inf/NaN in the garbage block, which every table entry past a
    slot's blocks names, and in the rows of a live block past the
    position: invisible, as they are to the gather arm."""
    S, bs, nb = 3, 8, 6
    q, kp, vp, tables = _decode_case(70, S, bs, nb, H=4, D=32, dtype=dtype,
                                     poison=True)
    pos = np.asarray([4, 19, 40])
    t = np.asarray(tables).copy()
    kp, vp = np.asarray(kp.astype(jnp.float32)).copy(), \
        np.asarray(vp.astype(jnp.float32)).copy()
    for s in range(S):
        live = (pos[s] + bs) // bs
        t[s, live:] = blk.GARBAGE_BLOCK
        # the slot's last block past its position: stale junk
        kp[t[s, live - 1], pos[s] % bs + 1:] = np.nan
        vp[t[s, live - 1], pos[s] % bs + 1:] = np.inf
    _assert_decode_matches_gather(
        q, jnp.asarray(kp).astype(dtype), jnp.asarray(vp).astype(dtype),
        jnp.asarray(t), pos, chunk_blocks=2)


def test_decode_kernel_inactive_slot_emits_finite_values():
    """A slot no request holds: position 0, every table entry the
    (poisoned) garbage block. What it emits is thrown away, and must not
    be NaN on its way through the layers."""
    S, bs, nb = 2, 8, 4
    q, kp, vp, tables = _decode_case(80, S, bs, nb, H=4, D=32, poison=True)
    t = np.asarray(tables).copy()
    t[1] = blk.GARBAGE_BLOCK
    kp = kp.at[blk.GARBAGE_BLOCK, 0].set(0.5)    # position 0 was "written"
    vp = vp.at[blk.GARBAGE_BLOCK, 0].set(0.25)
    got = _assert_decode_matches_gather(q, kp, vp, jnp.asarray(t), [11, 0])
    np.testing.assert_allclose(got[1], 0.25, rtol=1e-6)


def test_decode_kernel_hole_emits_zeros_over_a_poisoned_pool():
    q, kp, vp, tables = _decode_case(90, 1, 4, 2, H=4, D=8)
    kp, vp = jnp.full_like(kp, jnp.nan), jnp.full_like(vp, jnp.nan)
    out = np.asarray(paged_decode_attention(
        q, kp, vp, tables, jnp.asarray([-1], jnp.int32)))
    assert (out == 0.0).all()


@pytest.mark.parametrize("chunk", [1, 2, 3, 6, None],
                         ids=["1", "2", "3", "all_live", "planned"])
def test_decode_kernel_chunk_size_does_not_change_results(chunk):
    S, bs, nb = 4, 8, 6
    q, kp, vp, tables = _decode_case(100, S, bs, nb, H=4, D=32)
    pos = [5, 16, 30, 47]
    want = paged_decode_attention(q, kp, vp, tables,
                                  jnp.asarray(pos, jnp.int32),
                                  chunk_blocks=nb)
    got = _assert_decode_matches_gather(q, kp, vp, tables, pos,
                                        chunk_blocks=chunk)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,want", [
    # (block_size, H*D, itemsize, table length) -> blocks a chunk
    ((16, 2048, 4, 64), 8),       # the serve cell: 128 keys, 4 MB of VMEM
    ((16, 2048, 2, 64), 8),       # bfloat16 pools: the same 128 keys
    ((8, 768, 4, 128), 16),       # small blocks: more of them
    ((16, 16384, 4, 64), 2),      # wide layers: the VMEM budget cuts it
    ((16, 2048, 4, 3), 3),        # never more than the table holds
    ((256, 65536, 4, 8), 1),      # and never less than one block
])
def test_decode_plan_comes_from_the_shapes(shape, want):
    assert DecodePlan.for_shapes(*shape).chunk_blocks == want


@pytest.mark.parametrize("H,D,dtype,want", [
    (16, 128, jnp.float32, True), (16, 128, jnp.bfloat16, True),
    (32, 256, jnp.float32, True), (16, 64, jnp.float32, False),
    (12, 128, jnp.float32, False), (8, 128, jnp.bfloat16, False)])
def test_decode_kernel_takes_whole_tiles_when_compiled(H, D, dtype, want):
    assert decode_kernel_takes(H, D, dtype, interpret=False) is want
    assert decode_kernel_takes(H, D, dtype, interpret=True)


def test_decode_kernel_refuses_what_it_is_not_for():
    q, kp, vp, tables = _decode_case(110, 2, 8, 2, H=4, D=8)
    pos = jnp.asarray([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="one query"):
        paged_decode_attention(jnp.concatenate([q, q], axis=1), kp, vp,
                               tables, pos)
    with pytest.raises(ValueError, match="int8"):
        paged_decode_attention(q, kp.astype(jnp.int8), vp.astype(jnp.int8),
                               tables, pos)


def test_attend_kernel_sends_one_query_a_slot_to_the_decode_kernel(
        monkeypatch):
    """One path a shape: T = 1 over float pools reaches the decode kernel
    and nothing else, T > 1 the grid-per-block kernel."""
    import importlib
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    calls = []
    for name in ("paged_decode_attention", "paged_attention"):
        real = getattr(pa, name)
        monkeypatch.setattr(
            pa, name, lambda *a, _n=name, _r=real, **k:
            (calls.append(_n), _r(*a, **k))[1])
    q, kp, vp, tables = _decode_case(120, 2, 8, 3, H=4, D=8)
    pos = jnp.asarray([3, 9], jnp.int32)
    blk.attend_kernel(q, kp, vp, tables, pos)
    blk.attend_kernel(jnp.concatenate([q, q], axis=1), kp, vp, tables, pos)
    assert calls == ["paged_decode_attention", "paged_attention"]


def test_decode_kernel_impl_gathers_prefill_and_verify_windows():
    q, kp, vp, tables = _decode_case(130, 2, 8, 3, H=4, D=8)
    wide = jnp.concatenate([q, q], axis=1)
    assert not blk.kernel_attends(q, kp)            # outside any scope
    with blk.attention_impl("decode_kernel"):
        assert blk.kernel_attends(q, kp)
        assert not blk.kernel_attends(wide, kp)
        assert not blk.kernel_attends(q, kp.astype(jnp.int8))
    with blk.attention_impl("kernel"):
        assert blk.kernel_attends(q, kp) and blk.kernel_attends(wide, kp)
    with blk.attention_impl("gather"):
        assert not blk.kernel_attends(q, kp)


# ------------------------------------------------ what the default resolves to
def _as_if_on(monkeypatch, platform):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: platform)


def test_default_impl_is_gather_off_the_tpu(tiny):
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8)
    assert eng.config.attention_impl is None
    assert eng.attention_impl == "gather"
    assert eng._compile_signature()["config"]["attention_impl"] == "gather"


@pytest.mark.parametrize("spelled", ["gather", "kernel"])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_explicit_impl_passes_through(tiny, monkeypatch, platform, spelled):
    _as_if_on(monkeypatch, platform)
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                attention_impl=spelled)
    assert eng.attention_impl == spelled
    assert eng._compile_signature()["config"]["attention_impl"] == spelled


@pytest.mark.parametrize("kwargs,want", [
    ({}, "decode_kernel"), ({"kv_dtype": "bfloat16"}, "decode_kernel"),
    ({"kv_dtype": "int8"}, "gather")],
    ids=["float32", "bfloat16", "int8"])
def test_default_impl_on_a_tpu_follows_the_pools(tiny, monkeypatch, kwargs,
                                                 want):
    _as_if_on(monkeypatch, "tpu")
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                **kwargs)
    assert eng.attention_impl == want
    assert eng._compile_signature()["config"]["attention_impl"] == want
    # the configuration still says "unset", and round-trips so
    assert eng.config.as_dict()["attention_impl"] is None


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_default_impl_is_gather_for_a_model_with_its_own_layout(
        monkeypatch, platform):
    from test_hybrid_model import build, tiny_config
    _as_if_on(monkeypatch, platform)
    eng = PagedGenerationEngine(build(tiny_config()), slots=2, max_len=64,
                                block_size=8)
    assert eng.attention_impl == "gather"


def test_default_impl_is_gather_over_a_mesh(tiny, monkeypatch):
    from paddle_tpu.serving.distributed.tp import (
        TensorParallelEngineConfig, TensorParallelPagedEngine)
    _as_if_on(monkeypatch, "tpu")
    eng = TensorParallelPagedEngine(tiny, TensorParallelEngineConfig(
        tp=2, slots=2, max_len=64, block_size=8))
    assert eng.attention_impl == "gather"


def _serve_ragged(eng):
    """Prefill two slots, decode, retire one mid-flight and refill it at
    another length, decode on: every token and every executable."""
    out = [eng.prefill(0, _p(0, 9)), eng.prefill(1, _p(1, 21))]
    for _ in range(3):
        out += [int(t) for t in eng.decode()]
    eng.reset_slot(0)
    out.append(eng.prefill(0, _p(2, 5)))
    for _ in range(4):
        out += [int(t) for t in eng.decode()]
    return out


def test_decode_kernel_engine_token_exact_over_a_ragged_refill(
        tiny, monkeypatch):
    """What a TPU's default engine traces (decode through the kernel,
    prefill through the gather), served here interpreted: token-exact
    against the gather engine and against the explicit kernel engine."""
    kw = dict(slots=2, max_len=64, block_size=16)
    with monkeypatch.context() as m:
        _as_if_on(m, "tpu")
        auto = PagedGenerationEngine(tiny, **kw)
    assert auto.attention_impl == "decode_kernel"
    want = _serve_ragged(PagedGenerationEngine(tiny, **kw))
    assert _serve_ragged(auto) == want
    assert _serve_ragged(PagedGenerationEngine(
        tiny, attention_impl="kernel", **kw)) == want
    assert auto.trace_counts["decode"] == 1


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_decode_wait_span_counts_the_blocks_the_kernel_read(tiny, impl):
    from paddle_tpu import profiler
    bs, nb = 8, 8
    eng = PagedGenerationEngine(tiny, slots=3, max_len=bs * nb,
                                block_size=bs, attention_impl=impl)
    eng.prefill(0, _p(0, 9))
    eng.prefill(2, _p(1, 21))          # slot 1 stays empty
    log = profiler.span_log()
    before = log.appended
    want = []
    for _ in range(4):
        want.append(sum(-(-(int(eng._pos[s]) + 1) // bs) for s in (0, 2)))
        eng.decode()
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    waits = [s["attrs"] for s in spans
             if s["name"] == "serving::decode.wait"]
    steps = [s["attrs"] for s in spans
             if s["name"] == "serving::decode_step"]
    assert len(waits) == 4 and all(a["attend"] == impl for a in steps)
    if impl == "gather":
        assert all("attn_blocks_read" not in a for a in waits)
        return
    assert [a["attn_blocks_read"] for a in waits] == want
    assert want[0] == 2 + 3 and want[-1] == 2 + 4
    assert all(a["attn_blocks_table"] == 3 * nb for a in waits)
