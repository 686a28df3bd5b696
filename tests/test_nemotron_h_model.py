"""Nemotron-H's language model through `HybridDecoder` (blocks of ONE part: a
Mamba-2 state-space mixer, a grouped-query attention mixer, a relu2 expert
layer's share) against the benchmark's plain reference, at tiny sizes on
seeded weights: each mechanism alone, the chunked scan against the one-token
step, the served path (prefill, then decode through the cache) against the
reference's full forward, on logits, and what a model of single-part blocks
asks of the engine (the programs of every serve configuration are pinned in
`test_serving_programs.py`)."""
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

from benchmark.harness import reference_nemotron_h as ref      # noqa: E402
from benchmark.harness import weights_nemotron_h as wn         # noqa: E402
from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu.serving import (PagedEngineConfig,             # noqa: E402
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)
from paddle_tpu.text.models import hybrid_ops as ops           # noqa: E402
from paddle_tpu.text.models.hybrid import (HybridConfig,       # noqa: E402
                                           HybridDecoder)

SEED = 2147483783          # past 2**31, as the driver's seeds are
CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def full_config(name="nemotron3_nano_ep8_share"):
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
    model.load_arrays(wn.named(config, seed, "float32"))
    return model


def reference_logits(config, ids, seed=SEED):
    return np.asarray(ref.logits(
        config, wn.layer_kinds(config),
        lambda: wn.make_globals(config, seed, "float32"),
        lambda i: wn.make_layer(config, seed, i, "float32"),
        jnp.asarray(ids, jnp.int32)))


def layer_weights(config, kind, seed=SEED):
    return wn.make_layer(config, seed, wn.layer_kinds(config).index(kind),
                         "float32")


def normed_input(config, t, seed=3):
    x = jax.random.normal(jax.random.key(seed), (t, config["hidden_size"]))
    return ops.rms_norm(x, jnp.ones((config["hidden_size"],)), 1e-5)


# as in test_hybrid_model.py: the program rounds matmul operands to bfloat16
# (`ops.mm`), the reference does not; 2e-2 of the output's scale holds every
# mechanism, a wrong formula misses by the scale itself
def close(got, want, tol=2e-2):
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


# ---------------------------------------------------------------- Mamba-2

# N(0, 0.02) weights at hidden 64 give x, B and C near 0, so the state adds
# half a per cent to `D x` and a wrong recurrence would hide behind the
# skip; at 0.1 the state's part is of the skip's size, as at the published
# widths
LIVELY = {"std": 0.1, "bias_std": 0.02, "conv_std": 0.3}


def mamba2_program(cfg, x, w, chunked, length=None):
    """The mixer over x [T, H] from a zero state, by the chunked scan or a
    token at a time -> (what it adds to h, final state, conv tail)."""
    t = x.shape[0]
    z, xbc, dt = ops.mamba2_project(x, w, cfg, jnp.float32)
    history = jnp.zeros((cfg.conv_kernel - 1, xbc.shape[1]))
    if chunked:
        xs, b, c, dt, a = ops.mamba2_inputs(xbc, dt, w, cfg, history)
        valid = jnp.arange(t) < (t if length is None else length)
        y, state = ops.mamba2_chunked(xs, b, c, dt, a, valid, cfg.ssm_chunk)
        return ops.mamba2_output(y, xs, z, w, cfg), state, None
    state = jnp.zeros((1, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state_size))
    tail, outs = history[None], []
    for i in range(t if length is None else length):
        xs, b, c, dt_i, a = ops.mamba2_inputs(
            xbc[None, i:i + 1], dt[None, i:i + 1], w, cfg, tail)
        state, y = ops.mamba2_recurrent_step(
            state, xs[:, 0], b[:, 0], c[:, 0], dt_i[:, 0], a)
        tail = jnp.concatenate([tail[:, 1:], xbc[None, i:i + 1]], 1)
        outs.append(ops.mamba2_output(y, xs[:, 0], z[None, i], w, cfg)[0])
    return jnp.stack(outs), state[0], tail[0]


@pytest.mark.parametrize("t", [16, 21, 5])
def test_mamba2_chunked_is_the_step_is_the_reference_scan(float32_matmuls,
                                                          t):
    """Chunks of 8: a length that is a multiple of the chunk, one that is
    not, one shorter than a chunk."""
    config = tiny_config(init=LIVELY)
    cfg = model_config(config)
    w = layer_weights(config, "mamba2")
    x = normed_input(config, t)
    want = ref.mamba2_mixer(x, w, config, "float32")
    got_c, state_c, _ = mamba2_program(cfg, x, w, chunked=True)
    got_r, state_r, _ = mamba2_program(cfg, x, w, chunked=False)
    close(got_c, want, 1e-5)
    close(got_r, want, 1e-5)
    close(state_c, state_r, 1e-5)
    assert np.abs(np.asarray(state_c)).max() > 0


def test_mamba2_in_bfloat16_operands_stays_near_the_reference():
    config = tiny_config(init=LIVELY)
    cfg = model_config(config)
    w = layer_weights(config, "mamba2")
    x = normed_input(config, 24)
    got, _, _ = mamba2_program(cfg, x, w, chunked=True)
    close(got, ref.mamba2_mixer(x, w, config, "float32"))


def test_a_dropped_mamba2_mechanism_misses_the_reference(float32_matmuls):
    """What the tolerance can see: the skip left out, the convolution's bias
    moved, the step's bias moved."""
    config = tiny_config(init=LIVELY)
    cfg = model_config(config)
    w = layer_weights(config, "mamba2")
    x = normed_input(config, 24)
    want = np.asarray(ref.mamba2_mixer(x, w, config, "float32"))
    for weights in (dict(w, d_skip=jnp.zeros_like(w["d_skip"])),
                    dict(w, conv_b=w["conv_b"] + 0.5),
                    dict(w, dt_bias=w["dt_bias"] + 1.0)):
        got, _, _ = mamba2_program(cfg, x, weights, chunked=True)
        assert np.abs(np.asarray(got) - want).max() \
            > 2e-2 * np.abs(want).max()


def test_mamba2_chunked_equals_the_step_under_fast_decay():
    """Steps of dt a down to -80 a token in chunks of 128: a chunk's
    cumulative decay passes e^-5000, and every exponent stays a difference
    that is taken before `exp`. The tolerance is the float32 rounding of a
    cumulative sum that reaches thousands (6e-8 x 5000 in an exponent)."""
    heads, p, g, n, t = 4, 8, 2, 16, 256
    ks = jax.random.split(jax.random.key(1), 4)
    xs = jax.random.normal(ks[0], (t, heads, p))
    b = jax.random.normal(ks[1], (t, g, n))
    c = jax.random.normal(ks[2], (t, g, n))
    dt = jnp.full((t, heads), 5.0).at[::7].set(0.001)
    a = -jnp.array([16.0, 1.0, 8.0, 0.01])
    y, state = ops.mamba2_chunked(xs, b, c, dt, a, jnp.ones((t,), bool), 128)
    s = jnp.zeros((1, heads, p, n))
    outs = []
    for i in range(t):
        s, y_i = ops.mamba2_recurrent_step(s, xs[i][None], b[i][None],
                                           c[i][None], dt[i][None], a)
        outs.append(y_i[0])
    assert np.isfinite(np.asarray(y)).all()
    close(y, jnp.stack(outs), 5e-4)
    close(state, s[0], 5e-4)


def test_mamba2_padding_leaves_state_and_tail_untouched(float32_matmuls):
    """13 real tokens in a bucket of 24: the state and the tail are what the
    13 alone give, token by token."""
    config = tiny_config(init=LIVELY)
    cfg = model_config(config)
    w = layer_weights(config, "mamba2")
    x = normed_input(config, 24)
    _, state_c, _ = mamba2_program(cfg, x, w, chunked=True, length=13)
    _, state_r, tail_r = mamba2_program(cfg, x, w, chunked=False, length=13)
    close(state_c, state_r, 1e-5)
    # and through the engine: a bucket of 32 against one of 64
    model = build(config)
    prompt = np.random.default_rng(0).integers(0, 1000, 19)
    states = []
    for bucket in ((32, 64), (64,)):
        eng = PagedGenerationEngine(model, PagedEngineConfig(
            slots=2, max_len=64, block_size=8, prefill_buckets=bucket))
        eng.prefill(1, prompt)
        states.append([(np.asarray(l.state[1]), np.asarray(l.tail[1]))
                       for l in eng._pool
                       if isinstance(l, blocks.StateLayer)])
    assert len(states[0]) == 3
    for (s32, t32), (s64, t64) in zip(*states):
        close(s32, s64, 1e-5)
        close(t32, t64, 1e-5)
        assert np.abs(t32).max() > 0 and np.abs(s32).max() > 0


# ------------------------------------------------- grouped-query attention

# N(0, 0.02) weights at hidden 64 give scores near 0 and a softmax near
# uniform, which hides which key a query reads; at 0.3 the scores spread
SHARP = {"std": 0.3, "bias_std": 0.02, "conv_std": 0.3}


@pytest.mark.parametrize("init", [None, SHARP])
def test_gqa_prefill_is_decode_over_rows_is_the_reference(init):
    config = tiny_config(**({"init": init} if init else {}))
    cfg = model_config(config)
    w = layer_weights(config, "gqa")
    x = normed_input(config, 40)
    q, row = ops.gqa_project(x, w, cfg)
    assert row.shape == (40, 2 * 2 * 16) and row.dtype == jnp.bfloat16
    want = ref.gqa_mixer(x, w, config, "float32")
    close(ops.gqa_prefill(q, row, w, cfg), want)
    rows = jnp.broadcast_to(row[None], (40,) + row.shape)
    close(ops.gqa_decode(q, rows, jnp.arange(40), w, cfg), want)


def test_query_heads_read_their_own_key_value_head():
    """Heads 0-1 read key/value head 0 and heads 2-3 head 1: with the two
    key/value heads swapped the sharp model misses."""
    config = tiny_config(init=SHARP)
    cfg = model_config(config)
    w = layer_weights(config, "gqa")
    x = normed_input(config, 40)
    want = np.asarray(ref.gqa_mixer(x, w, config, "float32"))
    swap = lambda m: jnp.concatenate([m[:, 16:], m[:, :16]], 1)
    q, row = ops.gqa_project(x, dict(w, wk=swap(w["wk"]), wv=swap(w["wv"])),
                             cfg)
    got = np.asarray(ops.gqa_prefill(q, row, w, cfg))
    assert np.abs(got - want).max() > 2e-2 * np.abs(want).max()


# ----------------------------------------------------------- expert shares

def test_relu2_expert_share_is_the_reference(float32_matmuls):
    config = tiny_config()
    cfg = model_config(config)
    w = layer_weights(config, "moe")
    assert "we_gate" not in w and "ws_gate" not in w
    x = normed_input(config, 40)
    got, counters = ops.moe_share(x, w, cfg, jnp.ones((40,), bool))
    close(got, ref.moe_ffn(x, w, config, "float32", (0, 4)), 1e-5)
    assert int(counters[0]) == 40 * 3


def test_eight_shares_add_up_to_the_uncut_expert_block():
    """The 8 chips' routed parts (2 of 16 experts each), plus the shared
    expert counted once, are the uncut reference's whole block."""
    config = tiny_config(router_width=16, n_routed_experts=2, num_experts=2)
    x = normed_input(config, 48)
    whole = dict(config, n_routed_experts=16, num_experts=16,
                 experts_held_first=0)
    w_all = layer_weights(whole, "moe")
    uncut = ref.moe_ffn(x, w_all, whole, "float32", (0, 16))
    total = ref.relu2_mlp(x, w_all["ws_up"], w_all["ws_down"], "float32")
    picks = 0
    for chip in range(8):
        share = dict(config, experts_held_first=2 * chip)
        w = layer_weights(share, "moe")
        np.testing.assert_array_equal(
            np.asarray(w["we_down"]),
            np.asarray(w_all["we_down"])[2 * chip:2 * chip + 2])
        cfg = model_config(share, n_routed_experts=16, num_experts=2,
                           experts_first=2 * chip)
        got, counters = ops.moe_share(x, w, cfg, jnp.ones((48,), bool))
        close(got, ref.moe_ffn(x, w, share, "float32", (2 * chip, 2)))
        # what every chip computes alike is counted once: the reference's
        # above; each share gives up the program's own
        total = total + got - ops.relu2_mlp(x, w["ws_up"], w["ws_down"])
        picks += int(counters[1])
    close(total, uncut)
    assert picks == 48 * 3               # every pick fell on some chip


# ------------------------------------------------------ what it declares

def test_blocks_are_what_the_configuration_declares():
    config = tiny_config()
    model = build(config)
    assert model.kinds == [("mamba2", None), (None, "moe"), ("mamba2", None),
                           (None, "moe"), ("mamba2", None), ("gqa", None),
                           (None, "moe")]
    assert [k or f for k, f in model.kinds] == wn.layer_kinds(config)
    layout = model.cache_layout()
    assert layout[0] == blocks.StateSpec((4, 8, 16), (3, 32 + 2 * 2 * 16))
    assert layout[5] == blocks.LatentSpec(2 * 2 * 16)
    assert layout[1] == layout[3] == layout[6] == blocks.NoCache()
    # one norm a block, no gate matrix, and the names the weights bring
    shapes = model.parameter_shapes()
    for i, kind in enumerate(wn.layer_kinds(config)):
        mine = {n.split(".", 2)[2]: s for n, s in shapes.items()
                if n.startswith(f"layers.{i}.")}
        assert mine == wn.layer_shapes(config, kind)
        assert ("norm1" in mine) != ("norm2" in mine)
    assert {"layers.0.dt_bias", "layers.0.a_log", "layers.0.d_skip",
            "layers.0.ssm_norm", "layers.1.router"} \
        <= model.float32_parameters()
    assert "layers.0.conv_b" not in model.float32_parameters()
    # the published sizes: the state a slot and a token's K/V row
    big = HybridDecoder(HybridConfig(
        **full_config()["program"]["model_config"])).cache_layout()
    assert big[0] == blocks.StateSpec((64, 64, 128), (3, 6144))
    assert big[5] == blocks.LatentSpec(512)
    assert [type(s).__name__ for s in big].count("NoCache") == 8


@pytest.mark.parametrize("bad", [
    {"blocks": ["mamba2", "moe"]},                         # too few
    {"blocks": ["mamba2"] * 6 + ["ssm"]},                  # no such part
    {"mixers": ["mla"] * 7},                               # both lists
    {"num_kv_heads": 3}, {"num_kv_heads": None},
    {"ssm_groups": 3}, {"moe_act": "gelu"}])
def test_configuration_that_cannot_be_built_raises(bad):
    with pytest.raises(ValueError):
        model_config(tiny_config(), **bad)


def test_mixers_may_name_the_new_mixers_in_paired_layers():
    """A layer of (mixer, feed-forward) takes the new mixers too: the
    layout and the leaves follow from the names."""
    cfg = HybridConfig(num_layers=3, mixers=["mamba2", "gqa", "kda"],
                       num_kv_heads=1, first_k_dense=1)
    model = HybridDecoder(cfg)
    assert model.kinds == [("mamba2", "swiglu"), ("gqa", "moe"),
                           ("kda", "moe")]
    kinds = [type(s).__name__ for s in model.cache_layout()]
    assert kinds == ["StateSpec", "LatentSpec", "StateSpec"]
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=32, block_size=8))
    eng.prefill(0, list(range(1, 12)))
    assert eng.decode().shape == (2,)


# ------------------------------------------------------------ served path

def served_logits(model, prompt, new_tokens, **engine_kw):
    """Logits of the served path at every generated position, and the
    tokens: prefill, then decode through the cache in slot 1 while slot 0
    serves another request."""
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=8, capture_logits=True,
        **engine_kw))
    eng.prefill(0, [7, 8, 9, 10, 11])
    tokens = [eng.prefill(1, prompt)]
    rows = []
    for _ in range(new_tokens):
        out = eng.decode()
        rows.append(eng.last_logits[1])
        tokens.append(int(out[1]))
    return np.stack(rows), tokens


def test_prefill_then_decode_matches_reference_full_forward():
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(1).integers(0, 1000, 21).tolist()
    got, tokens = served_logits(model, prompt, 12)
    full = reference_logits(config, prompt + tokens[:-1])
    close(got, full[len(prompt):len(prompt) + 12])
    first = full[len(prompt) - 1]
    assert first.max() - first[tokens[0]] < 2e-2 * np.abs(first).max()


def test_bfloat16_engine_serves_and_stays_near_reference():
    config = tiny_config()
    model = build(config)
    prompt = np.random.default_rng(2).integers(0, 1000, 17).tolist()
    got, tokens = served_logits(model, prompt, 6, weight_dtype="bfloat16",
                                kv_dtype="bfloat16")
    want = reference_logits(config, prompt + tokens[:-1])[17:23]
    close(got, want, 0.1)


def run_requests(sched, prompts, new=6):
    handles = [sched.submit(p, new) for p in prompts]
    while sched.step():
        pass
    return [h.tokens for h in handles]


def test_preempted_request_is_recomputed_to_the_same_tokens():
    """Too few blocks for two long requests at once: one is preempted, its
    state dropped with its slot, and the recompute-prefill rebuilds it."""
    model = build(tiny_config())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 1000, 30).tolist() for _ in range(2)]
    roomy = Scheduler(PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=8)), ServingConfig(max_queue=4))
    want = run_requests(roomy, prompts, new=12)
    tight_engine = PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=8, num_blocks=1 + 9))
    tight = Scheduler(tight_engine, ServingConfig(max_queue=4))
    got = run_requests(tight, prompts, new=12)
    assert tight.counts["serving.preempted"] >= 1
    assert got == want
    assert tight_engine.state_store.in_use == 0
    assert tight_engine.block_pool.in_use == 0


def test_prefix_cache_is_bypassed_and_a_slot_starts_clean():
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    prompt = list(range(1, 30))
    first = [eng.prefill(slot, prompt) for slot in (0, 1)]
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 0
    assert eng.prefix_cache.bypassed == 2 and len(eng.prefix_cache) == 0
    assert eng.state_store.in_use == 2 and first[0] == first[1]
    assert eng.slot_positions().tolist() == [29, 29]
    for _ in range(3):
        out = eng.decode()
        assert out[0] == out[1]
    eng.reset_slot(0)
    eng.reset_slot(1)
    assert eng.block_pool.in_use == 0 and eng.state_store.in_use == 0


def test_spans_carry_the_state_the_rows_and_the_scan_counters():
    from paddle_tpu import profiler
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    assert eng.state_store.bytes_per_slot == 3 * (4 * 4 * 8 * 16
                                                  + 4 * 3 * 96)
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    log = profiler.span_log()
    before = log.appended
    run_requests(sched, [list(range(3, 20))], new=4)
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    waits = [s["attrs"] for s in spans
             if s["name"] == "serving::decode.wait" and s["attrs"]["fetches"]]
    assert waits and all(a["pool_donated"] == 1 for a in waits)
    # ONE attention block caches rows; the dense view gathers both slots'
    # whole tables; the live slot holds its 17 prompt rows and one more a
    # step. Three expert blocks, one live slot, top-3
    assert [a["latent_rows_read"] for a in waits] == [2 * 64] * 3
    assert [a["latent_rows_held"] for a in waits] == [18, 19, 20]
    assert all(a["moe_pairs_total"] == 3 * 3 for a in waits)
    prefill = next(s["attrs"] for s in spans
                   if s["name"] == "serving::prefill")
    assert prefill["moe_pairs_total"] == 17 * 3 * 3
    assert prefill["pool_donated"] == 1 and prefill["bucket"] == 32
    # three state-space blocks scanned the bucket; 17 positions were real
    assert prefill["ssm_tokens_scanned"] == 3 * 32
    assert prefill["ssm_tokens_valid"] == 3 * 17
    busy = [s["attrs"] for s in spans if s["name"] == "serving::step"
            and s["attrs"]["active_slots"]]
    assert busy and all(a["state_slots_in_use"] == 1 for a in busy)
    assert busy[0]["state_bytes"] == eng.state_store.bytes_per_slot
    assert busy[0]["latent_bytes_in_use"] == \
        busy[0]["kv_blocks_in_use"] * 8 * 64 * 4
    assert busy[-1]["prefix_cache_bypassed"] == 1
    assert eng.trace_counts["decode"] == 1


def test_a_block_that_caches_nothing_goes_through_the_pool():
    """`NoCache` is its own empty layer: the pool keeps one entry a block,
    holds no array for it, and is donated whole."""
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    kinds = [type(l).__name__ for l in eng._pool]
    assert kinds == ["StateLayer", "NoCache", "StateLayer", "NoCache",
                     "StateLayer", "LatentLayer", "NoCache"]
    assert len(eng._kv_arrays()) == 3 * 2 + 1
    block, slot = blocks.layout_bytes(eng._layout, 8, np.float32)
    assert (block, slot) == (8 * 64 * 4, eng.state_store.bytes_per_slot)
    with pytest.raises(TypeError, match="unknown cache spec"):
        blocks.alloc_layers((None,), 4, 8, 2, np.float32)
    old = eng._pool
    eng.prefill(0, list(range(1, 12)))
    eng.decode()
    assert all(x.is_deleted() for layer in old for x in layer)
    assert [type(l).__name__ for l in eng._pool] == kinds


def test_what_the_model_cannot_be_combined_with_raises_at_construction():
    model = build(tiny_config())
    base = dict(slots=2, max_len=64, block_size=8)
    for bad in ({"kv_dtype": "int8"}, {"enable_kv_tiers": True},
                {"attention_impl": "kernel"}, {"weight_dtype": "int8"},
                {"numerics_taps": True}):
        with pytest.raises(ValueError, match="cache layout"):
            PagedGenerationEngine(model, PagedEngineConfig(**base, **bad))
    eng = PagedGenerationEngine(model, PagedEngineConfig(**base))
    with pytest.raises(NotImplementedError):
        eng.extract_kv(0)
    with pytest.raises(ValueError, match="max_len"):
        PagedGenerationEngine(model, PagedEngineConfig(
            slots=2, max_len=128, block_size=8))
