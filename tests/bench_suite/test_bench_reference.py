"""The plain reference against the program at a tiny size, and the control:
the reference put in the program's place one precision down must come out
as not correct."""
import json
import os

import numpy as np
import pytest

from bench_helpers import ROOT

from benchmark import run as bench_run
from benchmark.harness import correctness, reference_gpt, weights


def tiny(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return bench_run.tiny_of(json.load(f))


def test_stacked_and_named_weights_are_the_same_values():
    import jax.numpy as jnp
    cfg = tiny("cerebras_gpt_1p3b")
    a = weights.make(cfg, 3000000001, "float32")
    b = weights.make(cfg, 3000000001, "float32", layout="named")
    c = weights.make(cfg, 3000000002, "float32")
    np.testing.assert_array_equal(a["w_qkv"][1],
                                  b["blocks.1.attn.qkv.weight"])
    np.testing.assert_array_equal(a["lnf_b"], b["ln_f.bias"])
    assert float(jnp.abs(a["wte"] - c["wte"]).max()) > 0
    assert abs(float(a["ln1_w"].mean()) - 1.0) < 0.01
    assert float(jnp.abs(a["b_fc1"]).max()) > 0      # biases are not zero


def test_reference_forward_matches_the_programs_gpt():
    """The served model's plain forward (no cache) against reference_gpt,
    on the same seeded weights, float32."""
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor

    loop = bench_run.load_module("loops", "closed_loop")
    cfg = tiny("cerebras_gpt_1p3b")
    model = loop.build_model(cfg, 11)
    ids = np.random.default_rng(0).integers(0, 1000, (2, 48), dtype=np.int32)
    got = np.asarray(model(Tensor(jnp.asarray(ids)))._data)
    params = weights.make(cfg, 11, "float32")
    want = np.asarray(reference_gpt.logits(
        params, jnp.asarray(ids), heads=cfg["n_head"],
        layout=cfg["qkv_layout"], eps=cfg["layer_norm_epsilon"]))
    assert got.shape == want.shape == (2, 48, 1024)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # and the head-major layout is a different function of the same weights
    other = np.asarray(reference_gpt.logits(
        params, jnp.asarray(ids), heads=cfg["n_head"], layout="head_major"))
    assert np.abs(other - want).max() > 1e-2


def ctx_for(cell, seed=7):
    _, cell, config, traffic = bench_run.load_cell(cell, tiny=True)
    return bench_run.make_ctx(cell, config, traffic, seed, 1.0, tiny=True)


def test_train_control_comes_out_not_correct():
    """The reference in the program's place at the tiny configuration's
    next precision down (bfloat16 for its float32) fails a limit; the
    reference itself passes every one."""
    loop = bench_run.load_module("loops", "train_job")
    ctx = ctx_for("gpt2m_train_b8")
    batches = loop.make_batches(ctx["traffic"], ctx["config"]["draw_vocab"],
                                ctx["seed"])
    want = loop.reference_readings(ctx, batches)
    low = loop.reference_readings(ctx, batches,
                                  ctx["config"]["dtype"]["control"])
    limits = correctness.load_limits("gpt2m_train_b8", tiny=True)
    limits.pop("compiles_in_window")
    same = correctness.checks_from(
        correctness.train_readings_gap(want, want), limits)
    assert all(c["ok"] for c in same)
    checks = correctness.checks_from(
        correctness.train_readings_gap(low, want), limits)
    assert not all(c["ok"] for c in checks), checks


def test_serve_control_comes_out_not_correct():
    """At each position of the same prompts and tokens, the token that
    bfloat16 puts first lies further below the reference's best than the
    limits allow; the reference's own first token lies nowhere below it."""
    loop = bench_run.load_module("loops", "closed_loop")
    ctx = ctx_for("cgpt1p3b_serve_closed8")
    cfg = ctx["config"]
    rng = np.random.default_rng(5)
    # read at every position: a one-token prompt, the rest as if served
    samples = []
    for n in (64, 61, 50, 64, 33, 57):
        ids = rng.integers(0, 1000, n).tolist()
        samples.append((ids[:1], ids[1:] + [0]))
    gaps, n_tokens = loop.served_gap_readings(
        cfg, ctx["seed"], samples, modes=("float32", "bfloat16"))
    limits = correctness.load_limits("cgpt1p3b_serve_closed8", tiny=True)
    assert n_tokens == 329
    low = gaps["bfloat16"]
    assert low["served_logit_gap"] > limits["served_logit_gap"] \
        or low["served_logit_gap_mean"] > limits["served_logit_gap_mean"], low


def test_worst_leaf_gap_and_the_rule_for_leaves_that_do_not_move():
    want = {"a": np.array([1.0, 2.0]), "b": np.array([1e-6]),
            "c": np.array([3.0])}
    got = {"a": np.array([1.0, 2.2]), "b": np.array([2e-6]),
           "c": np.array([3.0])}
    gap, leaf = correctness.worst_norm_gap(got, want)
    # median leaf norm is 1.5: b's gap is measured against it, not itself
    assert leaf == "a[1]" and gap == pytest.approx(0.1)
    keep = correctness.moved_leaves(want)
    assert keep.tolist() == [True, True, False, True]
    doubled = {"a": np.array([1.0, 4.0]), "b": want["b"], "c": want["c"]}
    assert correctness.worst_norm_gap(doubled, want)[0] == pytest.approx(1.0)
    nan = {"a": np.array([np.nan, 2.0]), "b": want["b"], "c": want["c"]}
    assert not correctness.check("x", correctness.worst_norm_gap(
        nan, want)[0], 1e9)["ok"]
