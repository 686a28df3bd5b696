"""EvaByte's language model through `HybridDecoder` (every layer an `eva`
mixer and a SwiGLU feed-forward; a head of several prediction heads) against
the benchmark's plain reference, at tiny sizes on seeded weights: the chunk
summaries and the window-blocked prefill, the served path (prefill, then
decode through the ring and the summaries) against the reference's full
forward on logits across window closings, what the layout declares (block
counts, ring reuse, summary addresses) and what the engine, the scheduler and
the KV ledger make of it."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import model_flops_evabyte as mf        # noqa: E402
from benchmark.harness import reference_evabyte as ref         # noqa: E402
from benchmark.harness import weights_evabyte as we            # noqa: E402
from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu.core.tensor import Tensor                      # noqa: E402
from paddle_tpu.serving import (PagedEngineConfig,             # noqa: E402
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)
from paddle_tpu.text.models import hybrid_ops as ops           # noqa: E402
from paddle_tpu.text.models.hybrid import (HybridConfig,       # noqa: E402
                                           HybridDecoder)

SEED = 2147483783          # past 2**31, as the driver's seeds are
WIN, CHUNK, BS = 8, 2, 2   # the tiny window, chunk and block
# N(0, 0.02) weights at hidden 64 give scores near 0 and a softmax near
# uniform, which hides which row a query reads; at 0.3 the scores spread
SHARP = {"std": 0.3, "bias_std": 0.02, "phi_std": 1.0, "mu_std": 0.5}
# float32 matmuls on both sides: what is left is the order of the sums. A
# program that rounded operands or rows to bfloat16 misses by 1e-2 and more
# (`test_bfloat16_operands_miss_the_tight_tolerance`)
TIGHT = 2e-4


def full_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte_6p5b_pp4_stage.json")) as f:
        return json.load(f)


def tiny_config(**over):
    config = tiny_of(full_config())
    config["init"] = SHARP
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
    model.load_arrays(we.named(config, seed, "float32"))
    return model


def reference_logits(config, ids, seed=SEED):
    """[T, P, V]: every prediction head's."""
    return np.asarray(ref.logits(
        config, lambda: we.make_globals(config, seed, "float32"),
        lambda i: we.make_layer(config, seed, i, "float32"),
        jnp.asarray(ids, jnp.int32)))


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


def engine(model, **kw):
    kw = {"slots": 2, "max_len": 64, "block_size": BS, **kw}
    return PagedGenerationEngine(model, PagedEngineConfig(**kw))


# ------------------------------------------- the mechanism against the text

def normed_input(config, t, seed=3):
    x = jax.random.normal(jax.random.key(seed), (t, config["hidden_size"]))
    return ops.rms_norm(x, jnp.ones((config["hidden_size"],)), 1e-5)


# mid-chunk, mid-window on a chunk's edge, on a window's edge, one past it,
# and a length that crosses two closings; each in a bucket with padding
@pytest.mark.parametrize("length,bucket", [(5, 8), (6, 16), (8, 16),
                                           (9, 16), (21, 32)])
def test_summaries_and_prefill_are_the_references(float32_matmuls, length,
                                                  bucket):
    config = tiny_config()
    cfg = model_config(config)
    w = we.make_layer(config, SEED, 1, "float32")
    x = normed_input(config, bucket)
    q, row = ops.eva_project(x, w, cfg, jnp.arange(bucket), jnp.float32)
    live = (jnp.arange(bucket) < length).reshape(-1, CHUNK)
    summaries = ops.eva_summaries(row.reshape(bucket // CHUNK, CHUNK, -1),
                                  live, w, cfg)
    # the reference sees the real tokens alone
    n, d = 4, 16
    t = length
    proj = lambda name: (x[:t] @ w[name]).reshape(t, n, d)
    k = ref.rotary(proj("wk"), jnp.arange(t), config["rope_theta"])
    kbar, vbar = ref.chunk_summaries(k, proj("wv"), w["phi"], w["mu"],
                                     CHUNK)
    begun = -(-length // CHUNK)
    got = np.asarray(summaries[:begun]).reshape(begun, 2, n, d)
    close(got[:, 0], kbar, TIGHT)
    close(got[:, 1], vbar, TIGHT)
    want = ref.eva_mixer(x[:t], w, config, "float32")
    close(ops.eva_prefill(q, row, summaries, w, cfg)[:t], want, TIGHT)


def test_a_dropped_eva_mechanism_misses_the_reference(float32_matmuls):
    """What the tolerance can see: `mu` left out, uniform pooling, the open
    window's chunks shown, rotary left off the keys."""
    config = tiny_config()
    cfg = model_config(config)
    w = we.make_layer(config, SEED, 0, "float32")
    t = 24
    x = normed_input(config, t)
    want = np.asarray(ref.eva_mixer(x, w, config, "float32"))

    def program(w, cfg=cfg, positions=jnp.arange(t)):
        q, row = ops.eva_project(x, w, cfg, positions, jnp.float32)
        s = ops.eva_summaries(row.reshape(t // CHUNK, CHUNK, -1),
                              jnp.ones((t // CHUNK, CHUNK), bool), w, cfg)
        return np.asarray(ops.eva_prefill(q, row, s, w, cfg))

    close(program(w), want, TIGHT)
    for got in (program(dict(w, mu=jnp.zeros_like(w["mu"]))),
                program(dict(w, phi=jnp.zeros_like(w["phi"]))),
                program(w, cfg=model_config(config, eva_window=4)),
                program(w, positions=jnp.zeros((t,), jnp.int32))):
        assert np.abs(got - want).max() > 2e-2 * np.abs(want).max()


def test_prefill_blocks_its_queries_when_the_scores_would_not_fit(
        float32_matmuls, monkeypatch):
    """A window of 8 taken in blocks of 4 queries (the 128-row floor is
    for the real sizes): the same numbers as the window whole."""
    config = tiny_config()
    cfg = model_config(config)
    w = we.make_layer(config, SEED, 2, "float32")
    x = normed_input(config, 24)
    q, row = ops.eva_project(x, w, cfg, jnp.arange(24), jnp.float32)
    s = ops.eva_summaries(row.reshape(12, CHUNK, -1),
                          jnp.ones((12, CHUNK), bool), w, cfg)
    whole = ops.eva_prefill(q, row, s, w, cfg)
    monkeypatch.setattr(ops, "eva_prefill_block", lambda n, win, keys: 4)
    close(ops.eva_prefill(q, row, s, w, cfg), whole, 1e-6)


def test_prefill_block_at_the_published_sizes():
    """32 heads, a window of 2 048, six windows: blocks of 1 024 queries
    keep the float32 scores under half a GiB; a short prompt goes whole."""
    real = HybridConfig(**full_config()["program"]["model_config"])
    assert real.eva_window == 2048 and real.eva_chunk == 16
    assert ops.eva_prefill_block(32, 2048, 2048 + 5 * 128) == 1024
    assert 4 * 32 * 1024 * (2048 + 640) <= ops.EVA_SCORE_BYTES
    assert ops.eva_prefill_block(4, 8, 8) == 8


# ------------------------------------------------------ what it declares

def test_layout_says_blocks_addresses_and_visible_rows():
    spec = blocks.WindowSpec(2 * 32 * 128, 2048, 16)
    assert spec.ring_blocks(16) == 128
    # ISSUE 38: min(ceil(n/16), 128) + ceil(ceil(n/16)/16), 178 at 12 800
    assert spec.blocks_for(12800, 16) == 178 == spec.table_blocks(12800, 16)
    assert [spec.blocks_for(n, 16) for n in (1, 16, 17, 256, 257, 2048,
                                             2049, 4096)] == \
        [2, 2, 3, 17, 19, 136, 137, 144]
    assert spec.blocks_for(12800, 16) == mf.blocks_for(12800, full_config())
    # position p writes ring block (p % W) // 16 and its chunk's summary
    # block 128 + (p // 16) // 16
    assert spec.entries(0, 0, 16) == [0, 128]
    assert spec.entries(2047, 2048, 16) == [0, 127, 135, 136]
    assert spec.entries(12799, 12799, 16) == [511 // 16, 128 + 49]
    assert len(spec.entries(0, 12799, 16)) == 178
    for n in (1, 100, 2048, 2049, 7000, 12800):
        assert len(spec.entries(0, n - 1, 16)) == spec.blocks_for(n, 16)
    config = full_config()
    for p in (0, 2047, 2048, 12799):
        assert tuple(map(int, spec.visible_rows(p))) == \
            mf.visible_rows(p, config)
    assert [mf.visible_rows(p, config) for p in (0, 2047, 2048, 12799)] == \
        [(1, 0), (2048, 0), (1, 128), (512, 768)]
    for n in (1, 2048, 2049, 5000, 12288):
        by_hand = sum(sum(mf.visible_rows(p, config)) for p in range(n))
        assert spec.prefill_pairs(n) == mf.prefill_pairs(n, config) \
            == by_hand


def test_model_declares_a_window_layer_and_its_leaves():
    config = tiny_config()
    model = build(config)
    assert model.kinds == [("eva", "swiglu")] * 3
    assert model.cache_layout() == (blocks.WindowSpec(2 * 4 * 16, 8, 2),) * 3
    shapes = model.parameter_shapes()
    mine = {n.split(".", 2)[2]: s for n, s in shapes.items()
            if n.startswith("layers.1.")}
    assert mine == we.layer_shapes(config)
    assert shapes["top.head"] == (64, 8 * 320)
    assert {"layers.0.phi", "layers.0.mu", "layers.2.norm1",
            "top.norm_f"} <= model.float32_parameters()
    big = HybridDecoder(HybridConfig(
        **full_config()["program"]["model_config"]))
    assert big.cache_layout() == (blocks.WindowSpec(8192, 2048, 16),) * 8
    assert big.parameter_shapes()["top.head"] == (4096, 8 * 320)
    # 202.4 M parameters a layer
    layer = sum(int(np.prod(s)) for n, s in big.parameter_shapes().items()
                if n.startswith("layers.0."))
    assert round(layer / 1e6, 1) == 202.4


def test_engine_sizes_its_table_and_pool_from_the_layout():
    eng = engine(build(tiny_config()))
    # ring 8 / 2 = 4 entries, 32 chunks of 64 positions in 16 blocks
    assert eng.config.max_blocks_per_slot == 20
    assert eng.config.num_blocks == 1 + 2 * 20
    assert eng._pool[0].rows.shape == (41, 2, 128)
    assert eng._tables.shape == (2, 20)
    # a pool the caller sized is the caller's
    small = engine(build(tiny_config()), num_blocks=23)
    assert small.config.num_blocks == 23
    assert small.config.max_blocks_per_slot == 20
    # at the published sizes: 178 entries a slot, 4 273 blocks
    spec = blocks.WindowSpec(8192, 2048, 16)
    assert 1 + 24 * spec.table_blocks(12800, 16) == 4273


@pytest.mark.parametrize("bad", [
    {"eva_window": 7}, {"eva_chunk": 0}, {"head_dim": 15},
    {"num_pred_heads": 0}, {"mixers": ["eva", "eva", "evo"]},
    {"mixers": ["eva"] * 2}])
def test_configuration_that_cannot_be_built_raises(bad):
    with pytest.raises(ValueError):
        model_config(tiny_config(), **bad)


@pytest.mark.parametrize("what", ["two_geometries", "blocks_across_windows",
                                  "kernel", "int8"])
def test_what_the_layout_cannot_be_combined_with_raises_at_construction(
        what):
    if what == "two_geometries":
        # a window layer beside a one-row-a-token layer needs a table each
        model = HybridDecoder(HybridConfig(
            num_layers=2, mixers=["eva", "gqa"], num_kv_heads=1,
            eva_window=8, eva_chunk=2))
        with pytest.raises(ValueError, match="same window geometry"):
            engine(model, max_len=32)
        return
    model = build(tiny_config())
    with pytest.raises(ValueError):
        engine(model, **{"blocks_across_windows": {"block_size": 3},
                         "kernel": {"attention_impl": "kernel"},
                         "int8": {"kv_dtype": "int8"}}[what])


# ------------------------------------------------------------ served path

def served_logits(model, prompt, new_tokens, other=(7, 8, 9), **engine_kw):
    """Logits of the served path at every generated position, and the
    tokens: prefill, then decode through the cache in slot 1 while slot 0
    serves another request at another phase of its window."""
    eng = engine(model, capture_logits=True, **engine_kw)
    eng.prefill(0, list(other))
    tokens = [eng.prefill(1, prompt)]
    rows = []
    for _ in range(new_tokens):
        out = eng.decode()
        rows.append(eng.last_logits[1])
        tokens.append(int(out[1]))
    return np.stack(rows), tokens, eng


@pytest.mark.parametrize("plen", [5, 8, 9, 21])
def test_prefill_then_decode_matches_reference_full_forward(
        float32_matmuls, plen):
    """30 tokens behind a prompt that ends mid-chunk, on a window's edge,
    one past it, and after two closings: three closings and more inside
    each decode (window 8), every logit of head 0 against the reference's
    full forward, in float32 on both sides."""
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(plen).integers(0, 320, plen).tolist()
    got, tokens, _ = served_logits(model, prompt, 30)
    assert (plen + 30) // WIN - plen // WIN >= 3
    full = reference_logits(config, prompt + tokens[:-1])
    close(got, full[plen:plen + 30, 0], TIGHT)
    first = full[plen - 1, 0]
    assert first.max() - first[tokens[0]] <= TIGHT * np.abs(first).max()
    assert got.shape == (30, 320)


def test_bfloat16_operands_miss_the_tight_tolerance():
    """The program as served (bfloat16 matmul operands) lies within a few
    per cent of the reference and well outside TIGHT: the float32
    comparison above could not pass by accident of a loose tolerance."""
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(9).integers(0, 320, 9).tolist()
    got, tokens, _ = served_logits(model, prompt, 20)
    want = reference_logits(config, prompt + tokens[:-1])[9:29, 0]
    err = np.abs(got - want).max() / np.abs(want).max()
    assert 50 * TIGHT < err < 0.25, err


def test_bfloat16_engine_serves_and_stays_near_reference():
    config = tiny_config(init={**SHARP, "std": 0.1})
    model = build(config)
    prompt = np.random.default_rng(2).integers(0, 320, 13).tolist()
    got, tokens, eng = served_logits(model, prompt, 12,
                                     weight_dtype="bfloat16",
                                     kv_dtype="bfloat16")
    assert eng._pool[0].rows.dtype == jnp.bfloat16
    want = reference_logits(config, prompt + tokens[:-1])[13:25, 0]
    close(got, want, 0.1)


def test_two_slots_at_different_window_phases_in_one_step(float32_matmuls):
    """Slot 0 stands two tokens into its second window while slot 1 closes
    its first: one decode executable, each slot's own mask."""
    config = tiny_config()
    model = build(config)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 320, n).tolist() for n in (10, 7)]
    eng = engine(model, capture_logits=True)
    tokens = [[eng.prefill(s, p)] for s, p in enumerate(prompts)]
    rows = [[], []]
    for _ in range(12):
        out = eng.decode()
        for s in (0, 1):
            rows[s].append(eng.last_logits[s])
            tokens[s].append(int(out[s]))
    assert eng.trace_counts["decode"] == 1
    for s, p in enumerate(prompts):
        full = reference_logits(config, p + tokens[s][:-1])
        close(np.stack(rows[s]), full[len(p):len(p) + 12, 0], TIGHT)


def test_all_prediction_heads_in_the_plain_forward(float32_matmuls):
    """The model called directly, a prompt through a hand-made cache: every
    head's logits against the reference's; the served step's are head 0."""
    config = tiny_config()
    model = build(config)
    ids = np.random.default_rng(4).integers(0, 320, 19)
    spec = model.cache_layout()[0]
    table = np.zeros((1, spec.table_blocks(32, BS)), np.int32)
    table[0, spec.entries(0, 18, BS)] = 1 + np.arange(
        spec.blocks_for(19, BS))
    pool = blocks.alloc_layers(model.cache_layout(), 40, BS, 1, jnp.float32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :19] = ids
    cache = blocks.PagedDecodeCache(
        tuple(type(l)(*(Tensor(x) for x in l)) for l in pool),
        Tensor(jnp.asarray(table)), Tensor(jnp.zeros((1,), jnp.int32)),
        Tensor(jnp.asarray([19], jnp.int32)),
        Tensor(jnp.asarray(0, jnp.int32)))
    every, _, _ = model(Tensor(jnp.asarray(padded)), cache, all_heads=True)
    first, _, _ = model(Tensor(jnp.asarray(padded)), cache)
    want = reference_logits(config, ids)
    assert every.shape == [1, 32, 8, 320] and want.shape == (19, 8, 320)
    close(every._data[0, :19], want, TIGHT)
    close(first._data, every._data[:, :, 0], 1e-5)
    heads = np.asarray(every._data[0, 18])
    assert np.abs(heads[1] - heads[0]).max() > 0.1 * np.abs(heads[0]).max()


# ------------------------------------- the manager: ring, summaries, ledger

def test_ring_blocks_are_written_again_and_summaries_stay():
    """A slot decoded through three windows keeps its 4 ring blocks (the
    table entries do not change once the first window is full) and gains a
    summary block every 4 positions; what it holds is `blocks_for(pos)`."""
    model = build(tiny_config())
    eng = engine(model)
    spec = eng._window
    eng.prefill(0, list(range(1, 6)))
    assert eng.block_pool.in_use == spec.blocks_for(5, BS) == 3 + 2
    ring_after_first_window = None
    for _ in range(26):
        eng.ensure_decode_capacity()
        pos = int(eng.slot_positions()[0])
        held = [int(b) for b in eng._tables[0] if b]
        assert len(held) == len(set(held)) == eng.block_pool.in_use \
            == spec.blocks_for(pos + 1, BS)
        if pos >= WIN:
            ring = eng._tables[0, :4].tolist()
            ring_after_first_window = ring_after_first_window or ring
            assert ring == ring_after_first_window and all(ring)
        eng.decode()
    assert int(eng.slot_positions()[0]) == 31
    # the summary of chunk c lies at entry 4 + c // 2, row c % 2, and equals
    # the pooling of the chunk's two rows while they are still in the ring
    view = np.asarray(blocks.gather_rows(eng._pool[0].rows,
                                         jnp.asarray(eng._tables[:1])))[0]
    w = {k.split(".", 2)[2]: v._data for k, v in model.named_parameters()
         if k.startswith("layers.0.")}
    for c in (12, 14):                   # chunks of the open window, 24-31
        rows = view[(c * CHUNK) % WIN:(c * CHUNK) % WIN + CHUNK]
        want = ops.eva_summaries(jnp.asarray(rows)[None],
                                 jnp.ones((1, CHUNK), bool), w, model.cfg)
        close(view[WIN + c], want[0], 1e-6)
    eng.reset_slot(0)
    assert eng.block_pool.in_use == 0


def run_requests(sched, prompts, new=6):
    handles = [sched.submit(p, new) for p in prompts]
    while sched.step():
        pass
    return [h.tokens for h in handles]


def test_no_block_leaks_and_the_ledger_reconciles_every_step():
    from paddle_tpu import profiler
    model = build(tiny_config())
    eng = engine(model)
    sched = Scheduler(eng, ServingConfig(max_queue=8))
    assert sched._kv_reconciler is not None
    check, found = sched._kv_reconciler.check, []
    sched._kv_reconciler.check = lambda: found.append(check())
    log = profiler.span_log()
    before = log.appended
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 320, n).tolist() for n in (5, 9, 17, 30)]
    run_requests(sched, prompts, new=20)
    assert len(found) > 30 and not any(found)
    assert eng.block_pool.in_use == 0
    assert eng.prefix_cache.bypassed == 4 and len(eng.prefix_cache) == 0
    # the spans: rows really held beside the positions stood for
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    config = tiny_config()
    steps = [s["attrs"] for s in spans if s["name"] == "serving::step"
             and s["attrs"].get("active_slots")]
    assert steps
    for a in steps:
        assert {"window_rows_held", "summary_rows_held",
                "kv_tokens_held"} <= set(a)
        assert a["window_rows_held"] <= WIN * a["active_slots"]
        assert a["window_rows_held"] + CHUNK * a["summary_rows_held"] \
            <= a["kv_tokens_held"]
    prefills = [s["attrs"] for s in spans if s["name"] == "serving::prefill"]
    assert [a["length"] for a in prefills] == [5, 9, 17, 30]
    assert [a["eva_windows"] for a in prefills] == [1, 2, 3, 4]
    assert [a["eva_chunks_summarised"] for a in prefills] == [3, 5, 9, 15]
    assert [a["eva_pairs"] for a in prefills] == \
        [mf.prefill_pairs(n, config) for n in (5, 9, 17, 30)]
    waits = [s["attrs"] for s in spans
             if s["name"] == "serving::decode.wait"]
    assert all(a["latent_rows_read"] == 3 * 2 * 20 * BS for a in waits)
    assert all(0 < a["latent_rows_held"] <= a["latent_rows_read"]
               for a in waits)


def test_step_gauges_are_the_visible_rows_of_the_slots_positions():
    """`window_rows_held` and `summary_rows_held` at a step's end are what
    `model_flops_evabyte.visible_rows` gives for the last position each
    active slot wrote; `kv_tokens_held` is still the positions."""
    config = tiny_config()
    eng = engine(build(config))
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    rng = np.random.default_rng(6)
    for n in (6, 19):
        sched.submit(rng.integers(0, 320, n).tolist(), 25)
    seen = 0
    while True:
        attrs = {}
        more = sched._step()
        sched._boundary_counts(attrs)
        pos = [int(p) for p, r in zip(eng.slot_positions(), sched._slots)
               if r is not None]
        if pos:
            rows = [mf.visible_rows(p - 1, config) for p in pos]
            assert attrs["window_rows_held"] == sum(r[0] for r in rows)
            assert attrs["summary_rows_held"] == sum(r[1] for r in rows)
            assert attrs["kv_tokens_held"] == sum(pos)
            assert eng.layout_gauges()["latent_bytes_in_use"] == \
                attrs["kv_blocks_in_use"] * eng.kv_block_bytes
            seen += 1
        if not more:
            break
    assert seen > 20
    assert eng.kv_block_bytes == 3 * BS * 128 * 4


def test_preempted_request_is_recomputed_to_the_same_tokens():
    """Too few blocks for two long requests at once: one is preempted, its
    ring and summaries dropped with its slot, and the recompute-prefill
    over prompt + tokens so far rebuilds them."""
    model = build(tiny_config())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 320, 20).tolist() for _ in range(2)]
    roomy = Scheduler(engine(model), ServingConfig(max_queue=4))
    want = run_requests(roomy, prompts, new=24)
    spec = blocks.WindowSpec(128, WIN, CHUNK)
    tight_engine = engine(model, num_blocks=1 + spec.blocks_for(44, BS) + 12)
    tight = Scheduler(tight_engine, ServingConfig(max_queue=4))
    got = run_requests(tight, prompts, new=24)
    assert tight.counts["serving.preempted"] >= 1
    assert got == want
    assert tight_engine.block_pool.in_use == 0


def test_recompute_prefill_rebuilds_the_same_rows(float32_matmuls):
    """What decode left in a slot after prompt + 13 tokens is what a prefill
    over the same 22 positions writes: every visible ring row and every
    summary of a closed chunk."""
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(8).integers(0, 320, 9).tolist()
    eng = engine(model)
    tokens = [eng.prefill(0, prompt)]
    for _ in range(13):
        tokens.append(int(eng.decode()[0]))
    eng.prefill(1, prompt + tokens[:-1])
    assert eng.slot_positions().tolist() == [22, 22]
    ring, chunks = mf.visible_rows(21, config)
    for layer in eng._pool:
        view = np.asarray(blocks.gather_rows(layer.rows,
                                             jnp.asarray(eng._tables)))
        close(view[1, :ring], view[0, :ring], TIGHT)
        close(view[1, WIN:WIN + 11], view[0, WIN:WIN + 11], TIGHT)
        assert np.abs(view[0, WIN:WIN + chunks]).max() > 0
