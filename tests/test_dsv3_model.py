"""DeepSeek-V3's main model through `HybridDecoder` (MLA in every layer
behind a query bottleneck, YaRN rotary, no gate, the expert layer's share)
against the benchmark's plain reference, at tiny sizes on seeded weights:
each mechanism alone, the absorbed and the blocked forms against the expanded
one, the served path (prefill, then decode through the latent cache) against
the reference's full forward, on logits, and what an all-latent model asks
of the engine: no state store, a prefix cache that reports no hit."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_dsv3 as ref            # noqa: E402
from benchmark.harness import reference_hybrid                 # noqa: E402
from benchmark.harness import weights_dsv3 as wd               # noqa: E402
from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu.serving import (PagedEngineConfig,             # noqa: E402
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)
from paddle_tpu.text.models import hybrid_ops as ops           # noqa: E402
from paddle_tpu.text.models.hybrid import (HybridConfig,       # noqa: E402
                                           HybridDecoder)

SEED = 2147483783          # past 2**31, as the driver's seeds are
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "deepseek_v3_ep16_share.json")


def full_config():
    with open(CONFIG_FILE) as f:
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
    model.load_arrays(wd.named(config, seed, "float32"))
    return model


def reference_logits(config, ids, seed=SEED):
    return np.asarray(ref.logits(
        config, wd.layer_kinds(config),
        lambda: wd.make_globals(config, seed, "float32"),
        lambda i: wd.make_layer(config, seed, i, "float32"),
        jnp.asarray(ids, jnp.int32)))


def layer_weights(config, kind, seed=SEED):
    i = wd.layer_kinds(config).index(kind)
    return wd.make_layer(config, seed, i, "float32")


def normed_input(config, t, seed=3):
    x = jax.random.normal(jax.random.key(seed), (t, config["hidden_size"]))
    return ops.rms_norm(x, jnp.ones((config["hidden_size"],)), 1e-6)


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


# ------------------------------------------------------------------ YaRN

def test_yarn_at_the_published_numbers_is_the_hand_count():
    """Pairs 0-10 keep their frequency, pairs 23-31 are slowed 40 times,
    a linear ramp between; the softmax scale is 0.1352."""
    config = full_config()
    freq, factor, scale = ref.yarn_frequencies(config)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(32) - 10) / 13
    np.testing.assert_allclose(
        freq[11:23], (plain * (1 - ramp) + plain / 40 * ramp)[11:23],
        rtol=1e-5)
    assert factor == 1.0
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert scale == pytest.approx(0.1352, abs=5e-5)


@pytest.mark.parametrize("which", ["published", "tiny", "plain",
                                   "unequal_mscales"])
def test_program_and_reference_agree_on_the_rotary_numbers(which):
    config = full_config() if which == "published" else tiny_config()
    scaling = config["rope_scaling"]
    if which == "plain":
        scaling = None
    elif which == "unequal_mscales":
        scaling = dict(scaling, mscale=0.7, mscale_all_dim=1.3)
    config = dict(config, rope_scaling=scaling)
    want = ref.yarn_frequencies(config)
    cfg = model_config(config, rope_scaling=scaling)
    inv, factor = ops.rope_frequencies(cfg)
    np.testing.assert_allclose(inv, want[0], rtol=1e-6)
    assert (factor, ops.mla_scale(cfg)) == pytest.approx(want[1:])
    if which == "tiny":                  # the ramp is inside the 4 pairs
        plain = 10000.0 ** (-np.arange(4) / 4)
        np.testing.assert_allclose(
            want[0], plain * np.array([1, 0.625, 0.25, 0.25]), rtol=1e-6)
    if which == "unequal_mscales":
        assert want[1] != 1.0
    x = jax.random.normal(jax.random.key(1),
                          (9, 3, config["qk_rope_head_dim"]))
    freq, factor, _ = want
    close(ops.rotary(x, jnp.arange(9) + 20, cfg),
          ref.rotary(x, jnp.arange(9) + 20, freq, factor), 1e-5)


# ------------------------------------------------------------- mechanisms

def test_mechanisms_are_the_reference_exactly_in_float32(float32_matmuls):
    config = tiny_config()
    cfg = model_config(config)
    w = layer_weights(config, ("mla", "moe"))
    x = normed_input(config, 40)
    q_n, q_r, latent, gate = ops.mla_project(x, w, cfg, jnp.arange(40))
    assert gate is None
    # the cached row is bfloat16: the tolerance is its rounding
    close(ops.mla_prefill(q_n, q_r, latent, gate, w, cfg),
          ref.mla_mixer(x, w, config, "float32"), 5e-3)
    got, _ = ops.moe_share(x, w, cfg, jnp.ones((40,), bool))
    close(got, reference_hybrid.moe_ffn(x, w, ref.expert_sizes(config),
                                        "float32", (0, 4)), 1e-5)


# N(0, 0.02) weights at hidden 64 give scores near 0 and a softmax near
# uniform, which hides the rotary; at 0.1 the scores spread over +-1 as they
# do at the published widths
SHARP = {"std": 0.1, "bias_std": 0.02}


@pytest.mark.parametrize("init", [{"std": 0.02, "bias_std": 0.02}, SHARP])
def test_mla_with_bottleneck_and_yarn_matches_reference(init):
    """Expanded prefill and absorbed decode, a token at a time over the rows
    before it, are both the reference's mixer."""
    config = tiny_config(init=init)
    cfg = model_config(config)
    w = layer_weights(config, ("mla", "swiglu"))
    assert {"wq_a", "qnorm", "wq_b"} <= set(w) and "wgate" not in w
    x = normed_input(config, 40)
    q_n, q_r, latent, gate = ops.mla_project(x, w, cfg, jnp.arange(40))
    want = ref.mla_mixer(x, w, config, "float32")
    close(ops.mla_prefill(q_n, q_r, latent, gate, w, cfg), want)
    rows = jnp.broadcast_to(latent[None], (40,) + latent.shape)
    close(ops.mla_decode(q_n, q_r, rows, jnp.arange(40), gate, w, cfg), want)


def test_a_dropped_mechanism_misses_the_reference():
    """What the tolerance can see: plain rotary in YaRN's place, or the
    query norm's scale left out, is off by more than it allows."""
    config = tiny_config(init=SHARP)
    w = layer_weights(config, ("mla", "swiglu"))
    x = normed_input(config, 40)
    want = np.asarray(ref.mla_mixer(x, w, config, "float32"))
    for cfg, weights in (
            (model_config(config, rope_scaling=None), w),
            (model_config(config), dict(w, qnorm=jnp.ones_like(w["qnorm"])
                                        * 1.5))):
        q_n, q_r, latent, gate = ops.mla_project(x, weights, cfg,
                                                 jnp.arange(40))
        got = np.asarray(ops.mla_prefill(q_n, q_r, latent, gate, weights,
                                         cfg))
        assert np.abs(got - want).max() > 2e-2 * np.abs(want).max()


@pytest.mark.parametrize("t", [256, 300])
def test_blocked_prefill_attention_equals_unblocked(monkeypatch, t):
    """Queries a block of 128 at a time against the keys before the block's
    end (a last block of 44 at T = 300) give the whole square's numbers."""
    config = tiny_config(init=SHARP)
    cfg = model_config(config)
    w = layer_weights(config, ("mla", "moe"))
    x = normed_input(config, t)
    q_n, q_r, latent, gate = ops.mla_project(x, w, cfg, jnp.arange(t))
    assert ops.mla_prefill_block(cfg.num_heads, t) == t
    whole = ops.mla_prefill(q_n, q_r, latent, gate, w, cfg)
    monkeypatch.setattr(ops, "MLA_SCORE_BYTES", 4 * cfg.num_heads * t * 130)
    assert ops.mla_prefill_block(cfg.num_heads, t) == 128
    blocked = ops.mla_prefill(q_n, q_r, latent, gate, w, cfg)
    close(blocked, whole, 1e-4)
    close(blocked, ref.mla_mixer(x, w, config, "float32"))


def test_prefill_blocks_at_the_published_sizes():
    """128 heads: the ladder's buckets from 1 152 up go in blocks, and no
    block's float32 scores pass the budget; the sibling's 32 heads at its
    longest bucket stay one block (its program is unchanged)."""
    for t in (1152, 1792, 2816, 4096):
        block = ops.mla_prefill_block(128, t)
        assert block % 128 == 0 and block < t
        assert 4 * 128 * block * t <= ops.MLA_SCORE_BYTES
    assert ops.mla_prefill_block(128, 768) == 768
    assert ops.mla_prefill_block(32, 2048) == 2048


def test_reference_attention_in_blocks_is_the_plain_softmax(monkeypatch):
    """The reference's own blocking (heads and queries) changes nothing."""
    monkeypatch.setattr(ref, "HEAD_BLOCK", 2)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    t, n, d, r = 48, 4, 16, 8
    ks = jax.random.split(jax.random.key(5), 5)
    q_n, k_n, v = (jax.random.normal(k, (t, n, d)) for k in ks[:3])
    q_r = jax.random.normal(ks[3], (t, n, r))
    k_r = jax.random.normal(ks[4], (t, r))
    with jax.default_matmul_precision("highest"):
        got = ref.causal_attention(q_n, q_r, k_n, k_r, v, 0.2, "float32")
        scores = (jnp.einsum("qnd,knd->nqk", q_n, k_n)
                  + jnp.einsum("qnd,kd->nqk", q_r, k_r)) * 0.2
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                           -jnp.inf)
        want = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)
    close(got, want, 1e-5)


# ----------------------------------------------------------- expert shares

def test_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """The 16 chips' routed parts (2 of 32 experts each), plus the shared
    expert counted once, are the uncut reference's whole layer."""
    config = tiny_config(router_width=32, n_routed_experts=2, num_experts=2)
    x = normed_input(config, 48)
    whole = dict(config, n_routed_experts=32, num_experts=32,
                 experts_held_first=0)
    w_all = layer_weights(whole, ("mla", "moe"))
    sizes = ref.expert_sizes(whole)
    uncut = reference_hybrid.moe_ffn(x, w_all, sizes, "float32", (0, 32))
    shared = reference_hybrid.swiglu(x, w_all["ws_gate"], w_all["ws_up"],
                                     w_all["ws_down"], "float32")
    total, picks = shared, 0
    for chip in range(16):
        share = dict(config, experts_held_first=2 * chip)
        w = layer_weights(share, ("mla", "moe"))
        np.testing.assert_array_equal(
            np.asarray(w["we_down"]),
            np.asarray(w_all["we_down"])[2 * chip:2 * chip + 2])
        cfg = model_config(share, n_routed_experts=32, num_experts=2,
                           experts_first=2 * chip)
        got, counters = ops.moe_share(x, w, cfg, jnp.ones((48,), bool))
        close(got, reference_hybrid.moe_ffn(x, w, sizes, "float32",
                                            (2 * chip, 2)))
        # what every chip computes alike is counted once: the reference's
        # above; each share gives up the program's own
        total = total + got - ops.swiglu(x, w["ws_gate"], w["ws_up"],
                                         w["ws_down"])
        picks += int(counters[1])
    close(total, uncut)
    assert picks == 48 * 2               # every pick fell on some chip


# ------------------------------------------------------------ served path

def served_logits(model, prompt, new_tokens, **engine_kw):
    """Logits of the served path at every generated position, and the
    tokens: prefill, then decode through the latent cache in slot 1 while
    slot 0 serves another request."""
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


def test_layer_list_is_what_the_configuration_declares():
    config = tiny_config()
    model = build(config)
    assert model.kinds == [("mla", "swiglu")] + [("mla", "moe")] * 4 \
        == wd.layer_kinds(config)
    assert all(isinstance(s, blocks.LatentSpec) and s.width == 40
               for s in model.cache_layout())
    assert {"layers.0.qnorm", "layers.3.cnorm", "layers.2.router"} \
        <= model.float32_parameters()
    # the sibling's rule still holds where no list is declared
    assert [m for m, _ in HybridConfig(num_layers=7).layer_kinds()] == \
        ["kda"] * 5 + ["mla", "kda"]
    mixed = HybridConfig(num_layers=3, mixers=["mla", "kda", "mla"])
    assert [m for m, _ in mixed.layer_kinds()] == ["mla", "kda", "mla"]


@pytest.mark.parametrize("bad", [
    {"mixers": ["mla", "mla"]}, {"mixers": ["mla"] * 4 + ["gqa"]},
    {"rope_scaling": {"type": "linear", "factor": 2}}])
def test_configuration_that_cannot_be_built_raises(bad):
    with pytest.raises(ValueError):
        model_config(tiny_config(), **bad)


def test_prefill_then_decode_matches_reference_full_forward():
    config = tiny_config()
    model = build(config)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 1000, 21).tolist()
    got, tokens = served_logits(model, prompt, 12)
    ids = prompt + tokens[:-1]
    full = reference_logits(config, ids)
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


# ------------------------------------- what an all-latent model asks of it

def test_all_latent_model_bypasses_the_prefix_cache_and_is_counted():
    """No state store, and still no prefix hit: one prompt prefilled into
    two slots leaves both at `pos == len(prompt)` with the same token."""
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    assert eng.state_store is None
    prompt = list(range(1, 30))
    first = [eng.prefill(slot, prompt) for slot in (0, 1)]
    assert eng.last_prefill_stats["prefix_hit_tokens"] == 0
    assert eng.prefix_cache.bypassed == 2 and len(eng.prefix_cache) == 0
    assert eng.slot_positions().tolist() == [29, 29]
    assert first[0] == first[1]
    for _ in range(3):
        out = eng.decode()
        assert out[0] == out[1]
    eng.reset_slot(0)
    eng.reset_slot(1)
    assert eng.block_pool.in_use == 0


def run_requests(sched, prompts, new=6):
    handles = [sched.submit(p, new) for p in prompts]
    while sched.step():
        pass
    return [h.tokens for h in handles]


def test_spans_carry_the_latent_counters_and_no_state():
    from paddle_tpu import profiler
    eng = PagedGenerationEngine(build(tiny_config()), PagedEngineConfig(
        slots=2, max_len=64, block_size=8))
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    log = profiler.span_log()
    before = log.appended
    run_requests(sched, [list(range(3, 20))], new=4)
    spans = [s for s in log.window(0, 2**62)][-(log.appended - before):]
    waits = [s["attrs"] for s in spans
             if s["name"] == "serving::decode.wait"]
    assert waits and all(a["pool_donated"] == 1 for a in waits)
    # five latent layers; the dense view gathers both slots' whole tables;
    # the one live slot holds its 17 prompt rows and one more each step
    assert [a["latent_rows_read"] for a in waits] == [5 * 2 * 64] * 3
    assert [a["latent_rows_held"] for a in waits] == \
        [5 * 18, 5 * 19, 5 * 20]
    assert all(a["moe_pairs_total"] == 4 * 2 for a in waits)
    prefill = next(s["attrs"] for s in spans
                   if s["name"] == "serving::prefill")
    assert prefill["moe_pairs_total"] == 17 * 4 * 2
    assert prefill["prefix_hit_tokens"] == 0 and prefill["pool_donated"] == 1
    busy = [s["attrs"] for s in spans if s["name"] == "serving::step"
            and s["attrs"]["active_slots"]]
    assert busy and all(a["state_slots_in_use"] == 0 and a["state_bytes"] == 0
                        for a in busy)
    assert busy[0]["latent_bytes_in_use"] == \
        busy[0]["kv_blocks_in_use"] * 8 * 40 * 4 * 5
    assert busy[-1]["prefix_cache_bypassed"] == 1
    assert eng.trace_counts["decode"] == 1


def test_what_the_model_cannot_be_combined_with_raises_at_construction():
    model = build(tiny_config())
    base = dict(slots=2, max_len=64, block_size=8)
    for bad in ({"kv_dtype": "int8"}, {"enable_kv_tiers": True},
                {"attention_impl": "kernel"}):
        with pytest.raises(ValueError, match="cache layout"):
            PagedGenerationEngine(model, PagedEngineConfig(**base, **bad))
    # the latent pool follows the weights' storage, and nothing narrower
    with pytest.raises(ValueError, match="cache layout"):
        PagedGenerationEngine(model, PagedEngineConfig(
            **base, weight_dtype="int8"))
