"""Analytical cost model: jaxpr-walk FLOP/byte attribution + roofline.

Mirrors the reference's cost_model tests (test_cost_model.py builds a
program and asserts per-op cost extraction) with exact-FLOP asserts the
profile-based reference cannot make.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.cost_model import DEVICES, CostModel, estimate


def test_matmul_flops_exact():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    r = estimate(lambda a, b: a @ b, a, b)
    assert r.by_op["dot_general"].flops == 2 * 64 * 128 * 32
    # bytes: read a + b, write out
    assert r.by_op["dot_general"].bytes == 4 * (64 * 128 + 128 * 32 + 64 * 32)


def test_batched_dot_and_conv_flops():
    a = jnp.zeros((8, 64, 32), jnp.float32)
    b = jnp.zeros((8, 32, 16), jnp.float32)
    r = estimate(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    assert r.by_op["dot_general"].flops == 2 * 8 * 64 * 32 * 16

    x = jnp.zeros((2, 3, 16, 16), jnp.float32)
    w = jnp.zeros((8, 3, 3, 3), jnp.float32)
    r2 = estimate(
        lambda x, w: jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW")), x, w)
    # out (2,8,16,16); per out elem: 2*kh*kw*cin
    assert r2.by_op["conv_general_dilated"].flops == \
        2 * (2 * 8 * 16 * 16) * 3 * 3 * 3


def test_scan_multiplies_and_cond_takes_worst_branch():
    w = jnp.zeros((16, 16), jnp.float32)

    def body(h, _):
        return h @ w, None

    def fn(h):
        h, _ = jax.lax.scan(body, h, None, length=5)
        return h

    r = estimate(fn, jnp.zeros((4, 16), jnp.float32))
    assert r.by_op["dot_general"].flops == 5 * 2 * 4 * 16 * 16

    def fn2(p, x):
        return jax.lax.cond(p, lambda x: x @ w @ w, lambda x: x + 1.0, x)

    r2 = estimate(fn2, jnp.asarray(True), jnp.zeros((4, 16), jnp.float32))
    assert r2.by_op["dot_general"].flops == 2 * 2 * 4 * 16 * 16


def test_roofline_regimes():
    """A big matmul is compute-bound; an elementwise add is bandwidth-
    bound — the roofline picks the right wall for each."""
    dev = DEVICES["tpu-v5e"]
    a = jnp.zeros((4096, 4096), jnp.bfloat16)
    r = estimate(lambda a: a @ a, a)
    c = r.by_op["dot_general"]
    assert c.flops / dev.peak_flops > c.bytes / dev.hbm_bw
    r2 = estimate(lambda a: a + a, a)
    c2 = r2.by_op["add"]
    assert c2.bytes / dev.hbm_bw > c2.flops / dev.peak_flops


def test_gpt_step_flops_match_model_flop_formula():
    """The analytic total over the real flagship train step must agree
    with the 6N+attention model-FLOP accounting
    (benchmark/harness/model_flops.py) within 15% (tiny dims:
    embedding/LN/loss overheads are relatively larger)."""
    from paddle_tpu.parallel import GPTSpmdConfig, MeshPlan, make_train_step
    cfg = GPTSpmdConfig(vocab_size=256, max_seq_len=64, hidden=64,
                        layers=2, heads=4, remat=False)
    step_fn, init_fn, _ = make_train_step(cfg, MeshPlan(),
                                          learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    B, S = 4, 64
    toks = jnp.zeros((B, S), jnp.int32)
    r = estimate(step_fn, params, state, toks, toks, jnp.float32(1e-3))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    fpt = 6 * n_params + 6 * cfg.layers * S * cfg.hidden
    expected = B * S * fpt
    assert r.total_flops == pytest.approx(expected, rel=0.15)
    assert r.time_ms > 0


def test_cost_model_static_table():
    cm = CostModel()
    a = jnp.zeros((64, 64), jnp.float32)
    report = cm.static_costs(lambda a: jnp.tanh(a @ a), a)
    t = cm.get_static_op_time("dot_general")
    assert t["flops"] == 2 * 64 ** 3
    assert t["time"] > 0
    assert "dot_general" in report.table()
