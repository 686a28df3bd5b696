"""The hybrid decoder (KDA + MLA mixers, dense and expert feed-forwards)
against the benchmark's plain reference, at tiny sizes on seeded weights:
each mechanism alone, the chunked prefill against the token-by-token
recurrence, and the served path (prefill, then decode through the two kinds
of cache) against the reference's full forward, on logits."""
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

from benchmark.harness import reference_hybrid as ref          # noqa: E402
from benchmark.harness import weights_hybrid as wh             # noqa: E402
from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu.serving import (PagedEngineConfig,             # noqa: E402
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)
from paddle_tpu.text.models import hybrid_ops as ops           # noqa: E402
from paddle_tpu.text.models.hybrid import (HybridConfig,       # noqa: E402
                                           HybridDecoder)

SEED = 2147483659          # past 2**31, as the driver's seeds are


def tiny_config(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling3_flash_ep4_share.json")) as f:
        config = tiny_of(json.load(f))
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
    model.load_arrays(wh.named(config, seed, "float32"))
    return model


def reference_logits(config, ids, seed=SEED):
    return np.asarray(ref.logits(
        config, wh.layer_kinds(config),
        lambda: wh.make_globals(config, seed, "float32"),
        lambda i: wh.make_layer(config, seed, i, "float32"),
        jnp.asarray(ids, jnp.int32)))


def layer_weights(config, kind, seed=SEED):
    i = wh.layer_kinds(config).index(kind)
    return wh.make_layer(config, seed, i, "float32")


def normed_input(config, t, seed=3):
    x = jax.random.normal(jax.random.key(seed), (t, config["hidden_size"]))
    return ops.rms_norm(x, jnp.ones((config["hidden_size"],)), 1e-6)


# f32 weights and activations on the CPU: the program rounds matmul operands
# to bfloat16 (`ops.mm`), the reference does not. 2e-2 of the output's scale
# holds every mechanism; a wrong formula misses by the scale itself.
def close(got, want, tol=2e-2):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max(), scale)


def kda_program(config, x, w, chunked):
    cfg = model_config(config)
    conv_in = ops.kda_conv_in(x, w, jnp.float32)
    history = jnp.zeros((cfg.conv_kernel - 1, conv_in.shape[1]))
    q, k, v, g, beta, gate = ops.kda_inputs(x, w, cfg, conv_in, history)
    if chunked:
        o, state = ops.kda_chunked(q, k, v, g, beta,
                                   jnp.ones((x.shape[0],), bool))
    else:
        state = jnp.zeros((1, cfg.num_heads, cfg.head_dim, cfg.head_dim))
        outs = []
        for t in range(x.shape[0]):
            state, o_t = ops.kda_recurrent_step(
                state, q[t][None], k[t][None], v[t][None], g[t][None],
                beta[t][None])
            outs.append(o_t[0])
        o, state = jnp.stack(outs), state[0]
    return ops.kda_output(o, gate, w, cfg), state


def test_mechanisms_are_the_reference_exactly_in_float32(monkeypatch):
    """With the program's matmuls widened to float32 nothing but rounding
    separates it from the reference: the formulas are the same ones."""
    monkeypatch.setattr(ops, "mm", lambda spec, a, b: jnp.einsum(
        spec, a.astype(jnp.float32), b.astype(jnp.float32),
        precision=ops.HIGHEST))
    config = tiny_config()
    with jax.default_matmul_precision("highest"):
        w = layer_weights(config, ("kda", "moe"))
        x = normed_input(config, 80)
        for chunked in (False, True):
            got, _ = kda_program(config, x, w, chunked)
            close(got, ref.kda_mixer(x, w, config, "float32"), 1e-5)
        got, _ = ops.moe_share(x, w, model_config(config),
                               jnp.ones((80,), bool))
        close(got, ref.moe_ffn(x, w, config, "float32", (0, 4)), 1e-5)


@pytest.mark.parametrize("chunked", [False, True])
def test_kda_mixer_matches_reference(chunked):
    config = tiny_config()
    w = layer_weights(config, ("kda", "moe"))
    x = normed_input(config, 80)          # a whole chunk and a part of one
    got, _ = kda_program(config, x, w, chunked)
    close(got, ref.kda_mixer(x, w, config, "float32"))


def test_kda_chunked_equals_recurrence_under_strong_decay():
    """Every step decaying by e^-5 (the lower bound): 64 of them leave
    float32 if a chunk's exponents are measured from one end."""
    n, d, t = 2, 16, 128
    ks = jax.random.split(jax.random.key(1), 4)
    q = ops.l2norm(jax.random.normal(ks[0], (t, n, d)))
    k = ops.l2norm(jax.random.normal(ks[1], (t, n, d)))
    v = jax.random.normal(ks[2], (t, n, d))
    g = jnp.full((t, n, d), -5.0).at[::7].set(-0.01)
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (t, n)))
    o, state = ops.kda_chunked(q, k, v, g, beta, jnp.ones((t,), bool))
    s = jnp.zeros((1, n, d, d))
    outs = []
    for i in range(t):
        s, o_i = ops.kda_recurrent_step(s, q[i][None], k[i][None],
                                        v[i][None], g[i][None],
                                        beta[i][None])
        outs.append(o_i[0])
    assert np.isfinite(np.asarray(o)).all()
    close(o, jnp.stack(outs), 1e-4)
    close(state, s[0], 1e-4)


def test_kda_padding_leaves_state_and_tail_untouched():
    """A prompt of 19 tokens in a bucket of 32 leaves the slot the state and
    the convolution tail the 19 tokens alone give."""
    config = tiny_config()
    model = build(config)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 1000, 19)
    states = []
    for bucket in ((32, 64), (64,)):
        eng = PagedGenerationEngine(model, PagedEngineConfig(
            slots=2, max_len=64, block_size=8, prefill_buckets=bucket))
        eng.prefill(1, prompt)
        states.append([(np.asarray(l.state[1]), np.asarray(l.tail[1]))
                       for l in eng._pool
                       if isinstance(l, blocks.StateLayer)])
    assert len(states[0]) == 6
    for (s32, t32), (s64, t64) in zip(*states):
        close(s32, s64, 1e-5)
        close(t32, t64, 1e-5)
        assert np.abs(t32).max() > 0


def test_mla_mixer_matches_reference():
    config = tiny_config()
    cfg = model_config(config)
    w = layer_weights(config, ("mla", "moe"))
    x = normed_input(config, 40)
    q_n, q_r, latent, gate = ops.mla_project(x, w, cfg, jnp.arange(40))
    want = ref.mla_mixer(x, w, config, "float32")
    close(ops.mla_prefill(q_n, q_r, latent, gate, w, cfg), want)
    # the absorbed form, a token at a time over the rows before it
    rows = jnp.broadcast_to(latent[None], (40,) + latent.shape)
    got = ops.mla_decode(q_n, q_r, rows, jnp.arange(40), gate, w, cfg)
    close(got, want)


@pytest.mark.parametrize("first", [0, 12])
def test_expert_layer_matches_reference(first):
    """The held share's output and the four counters, for the first and
    for the last quarter of the experts."""
    config = tiny_config(experts_held_first=first)
    cfg = model_config(config, experts_first=first)
    w = layer_weights(config, ("kda", "moe"))
    x = normed_input(config, 48)
    got, counters = ops.moe_share(x, w, cfg, jnp.arange(48) < 40)
    close(got, ref.moe_ffn(x, w, config, "float32", (first, 4)))
    total, local, hit, fullest = map(int, counters)
    weights = np.asarray(ref.route(x, w, config))[:40]
    held = weights[:, first:first + 4] > 0
    assert total == 40 * 2
    assert local == int(held.sum())
    assert hit == int((held.sum(0) > 0).sum())
    assert fullest == int(held.sum(0).max())
    assert ((weights > 0).sum(1) == 2).all()
    close(weights.sum(1), np.full(40, 2.5), 1e-5)


def test_shares_add_up_to_the_uncut_expert_layer():
    """The four chips' routed parts, plus the shared expert counted once,
    are the uncut reference's whole layer."""
    config = tiny_config()
    x = normed_input(config, 48)
    whole_cfg = dict(config, num_experts=16, experts_held_first=0)
    w_all = layer_weights(whole_cfg, ("kda", "moe"))
    uncut = ref.moe_ffn(x, w_all, whole_cfg, "float32", (0, 16))
    shared = ref.swiglu(x, w_all["ws_gate"], w_all["ws_up"],
                        w_all["ws_down"], "float32")
    total = shared
    for chip in range(4):
        share = dict(config, num_experts=4, experts_held_first=4 * chip)
        w = layer_weights(share, ("kda", "moe"))
        np.testing.assert_array_equal(
            np.asarray(w["we_up"]), np.asarray(w_all["we_up"])[4 * chip:
                                                              4 * chip + 4])
        cfg = model_config(share, experts_first=4 * chip)
        got, _ = ops.moe_share(x, w, cfg, jnp.ones((48,), bool))
        # the program's share against the reference's same share ...
        close(got, ref.moe_ffn(x, w, share, "float32", (4 * chip, 4)))
        total = total + got - shared
    # ... and the program's four shares against the reference's uncut layer
    close(total, uncut)


def served_logits(model, prompt, new_tokens, **engine_kw):
    """Logits of the served path at every generated position, and the
    tokens: prefill, then decode through the caches in slot 1 while slot 0
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
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 1000, 21).tolist()
    got, tokens = served_logits(model, prompt, 12)
    ids = prompt + tokens[:-1]
    want = reference_logits(config, ids)[len(prompt):len(prompt) + 12]
    close(got, want)
    # every served token within the comparison's reach of the reference's best
    first = reference_logits(config, ids)[len(prompt) - 1]
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
    config = tiny_config()
    model = build(config)
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


def test_reset_slot_leaves_no_state_for_the_next_request():
    config = tiny_config()
    model = build(config)
    rng = np.random.default_rng(4)
    a, b = (rng.integers(0, 1000, n).tolist() for n in (25, 9))
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        slots=1, max_len=64, block_size=8))
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    after_a = run_requests(sched, [a, b])[1]
    fresh = Scheduler(PagedGenerationEngine(model, PagedEngineConfig(
        slots=1, max_len=64, block_size=8)), ServingConfig(max_queue=4))
    assert run_requests(fresh, [b])[0] == after_a
    assert eng.state_store.in_use == 0


def test_prefix_cache_is_bypassed_and_counted():
    config = tiny_config()
    eng = PagedGenerationEngine(build(config), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    prompt = list(range(1, 30))
    eng.prefill(0, prompt)
    eng.prefill(1, prompt)             # the same 3 full blocks: no reuse
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 0
    assert eng.prefix_cache.bypassed == 2 and len(eng.prefix_cache) == 0
    assert eng.state_store.in_use == 2
    eng.reset_slot(0)
    eng.reset_slot(1)
    assert eng.block_pool.in_use == 0 and eng.state_store.in_use == 0


def test_pool_is_donated_and_counters_ride_the_decode_span():
    from paddle_tpu import profiler
    config = tiny_config()
    eng = PagedGenerationEngine(build(config), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    log = profiler.span_log()
    before = log.appended
    run_requests(sched, [list(range(3, 20))], new=4)
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    waits = [s for s in spans if s["name"] == "serving::decode.wait"]
    assert waits and all(s["attrs"]["pool_donated"] == 1 for s in waits)
    for s in waits:
        assert s["attrs"]["moe_pairs_total"] == 6 * 2      # 1 live slot
        assert 0 <= s["attrs"]["moe_pairs_local"] <= 12
        assert s["attrs"]["moe_experts_hit"] <= s["attrs"]["moe_pairs_local"]
    prefills = [s for s in spans if s["name"] == "serving::prefill"]
    assert prefills[0]["attrs"]["moe_pairs_total"] == 17 * 6 * 2
    steps = [s for s in spans if s["name"] == "serving::step"]
    busy = [s["attrs"] for s in steps if s["attrs"]["active_slots"]]
    assert busy and all(a["state_slots_in_use"] == 1 for a in busy)
    assert busy[0]["state_bytes"] == eng.state_store.bytes_per_slot
    assert busy[0]["latent_bytes_in_use"] == \
        busy[0]["kv_blocks_in_use"] * 8 * 40 * 4
    assert busy[-1]["prefix_cache_bypassed"] == 1
    assert eng.trace_counts["decode"] == 1


def test_what_the_model_cannot_be_combined_with_raises_at_construction():
    from paddle_tpu.serving import SpecDecodeConfig, SpeculativeEngine
    model = build(tiny_config())
    base = dict(slots=2, max_len=64, block_size=8)
    for bad in ({"kv_dtype": "int8"}, {"weight_dtype": "int8"},
                {"enable_kv_tiers": True}, {"attention_impl": "kernel"},
                {"numerics_taps": True}):
        with pytest.raises(ValueError, match="cache layout"):
            PagedGenerationEngine(model, PagedEngineConfig(**base, **bad))
    with pytest.raises(TypeError, match="cache layout"):
        SpeculativeEngine(model, SpecDecodeConfig(**base))
    from paddle_tpu.serving import GenerationEngine
    with pytest.raises(TypeError, match="cache layout"):
        GenerationEngine(model, slots=2, max_len=64)
    eng = PagedGenerationEngine(model, PagedEngineConfig(**base))
    eng.prefill(0, [1, 2, 3, 4, 5])
    with pytest.raises(NotImplementedError):
        eng.extract_kv(0)
    with pytest.raises(NotImplementedError):
        eng.adopt_kv(1, [], [], 1, 0)
    with pytest.raises(NotImplementedError):
        eng.attach_adapters(object())


def test_gpt_serves_in_bfloat16_near_its_float32_tokens():
    """`weight_dtype` and `kv_dtype` "bfloat16" on the GPT path: weights cast
    once, pools bfloat16, logits near the float32 engine's."""
    import paddle_tpu
    from paddle_tpu.text.models import gpt_tiny
    paddle_tpu.seed(0)
    model = gpt_tiny()
    model.eval()
    prompt = list(range(5, 25))
    rows = {}
    for dt in ("float32", "bfloat16"):
        eng = PagedGenerationEngine(model, PagedEngineConfig(
            slots=1, max_len=64, block_size=8, capture_logits=True,
            weight_dtype=dt, kv_dtype=dt))
        eng.prefill(0, prompt)
        eng.decode()
        rows[dt] = eng.last_logits[0]
        assert eng._pool[0].k.dtype == jnp.dtype(dt)
    close(rows["bfloat16"], rows["float32"], 0.1)
