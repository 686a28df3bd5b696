"""incubate.autotune: real kernel tiling autotune with a persistent cache
(reference: python/paddle/incubate/autotune.py + phi/kernels/autotune)."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate import autotune


def test_config_surface():
    autotune.set_config({"kernel": {"enable": True}})
    assert autotune.get_config()["kernel"]["enable"]
    assert autotune.kernel_tuning_enabled()


def test_autotune_picks_a_valid_block_and_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "cache.json"))
    autotune._block_cache.clear()
    autotune._disk_cache.clear()
    autotune._disk_loaded = False
    bq, bk = autotune.autotune_flash_blocks(1, 2, 256, 64, causal=True,
                                            dtype="float32",
                                            candidates=(128, 256),
                                            n_iters=1)
    assert 256 % bq == 0 and 256 % bk == 0
    # cached in memory and on disk
    assert autotune.lookup_flash_blocks(1, 2, 256, 64, True) == (bq, bk)
    assert (tmp_path / "cache.json").exists()
    # a fresh process (empty memory cache, disk not yet read) reloads
    autotune._block_cache.clear()
    autotune._disk_cache.clear()
    autotune._disk_loaded = False
    assert autotune.lookup_flash_blocks(1, 2, 256, 64, True) == (bq, bk)


def test_tuned_blocks_feed_the_flash_entry(monkeypatch):
    """ops.flash_attention consults the cache: a valid tuned entry is
    passed through to the kernel — an unequal causal pair included, since
    PR 29 moved the key loop into the kernels — while a poisoned entry
    (stale disk table: blocks that don't divide S, or a pair of which
    neither divides the other) falls back to the kernel default, with a
    warning, instead of raising mid-forward (ISSUE 6 satellite: the
    block-table fix)."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa_mod = importlib.import_module("paddle_tpu.ops.flash_attention")
    pallas_mod = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")

    seen = {}

    def fake_flash(q, k, v, block_q=None, block_k=None, **kw):
        seen["blocks"] = (block_q, block_k)
        return q

    monkeypatch.setattr(pallas_mod, "flash_attention", fake_flash)
    autotune._block_cache.clear()
    key = (jax.default_backend(), 2, 256, 64, True)
    q = jnp.ones((1, 2, 256, 64), jnp.float32)

    autotune._block_cache[key] = (128, 128)     # valid: divides S=256
    fa_mod._pallas_flash_bhsd(q, q, q, True, 0.125)
    assert seen["blocks"] == (128, 128)

    autotune._block_cache[key] = (96, 96)       # poisoned: 256 % 96 != 0
    with pytest.warns(UserWarning, match="does not tile"):
        fa_mod._pallas_flash_bhsd(q, q, q, True, 0.125)
    assert seen["blocks"] == (None, None)       # fell back, no raise

    autotune._block_cache[key] = (128, 256)     # causal and unequal: honoured
    fa_mod._pallas_flash_bhsd(q, q, q, True, 0.125)
    assert seen["blocks"] == (128, 256)

    key = (jax.default_backend(), 2, 384, 64, True)
    q = jnp.ones((1, 2, 384, 64), jnp.float32)
    autotune._block_cache[key] = (192, 128)     # neither divides the other
    with pytest.warns(UserWarning, match="does not tile"):
        fa_mod._pallas_flash_bhsd(q, q, q, True, 0.125)
    assert seen["blocks"] == (None, None)
    autotune._block_cache.clear()


def test_an_unequal_causal_pair_runs_through_the_real_kernel():
    """What the old kernels refused ("causal masking requires block_q ==
    block_k") the public entry now runs, and matches the XLA reference."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.flash_attention import _ref_attention_bhsd
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, 256, 64))
               for i in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=256, block_k=128,
                          interpret=True)
    want = _ref_attention_bhsd(q, k, v, True, 0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)
    assert jnp.isfinite(out).all()
