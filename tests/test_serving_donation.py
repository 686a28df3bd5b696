"""The paged engine donates its KV pool (ISSUE 27): decode, prefill and
adopt take the pool in place; every cache tier hands back a runner that
still does; an engine with numerics taps armed keeps its replayable,
non-donating decode; a failed fetch leaves the engine on live buffers;
the step's spans say whether it engaged."""
import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import profiler
from paddle_tpu.framework import compile_cache as cc
from paddle_tpu.observability.flight_recorder import SpanLog
from paddle_tpu.serving import (GenerationEngine, PagedEngineConfig,
                                PagedGenerationEngine)
from paddle_tpu.text.models import gpt_tiny

PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]
STEPS = 16


@pytest.fixture(scope="module")
def tiny():
    paddle_tpu.seed(0)
    model = gpt_tiny()
    model.eval()
    return model


def build(model, **kwargs):
    return PagedGenerationEngine(model, PagedEngineConfig(
        slots=2, max_len=64, block_size=8, prefill_buckets=(16, 32),
        **kwargs))


def leaves(engine):
    return list(engine._kv_arrays())


def live(engine):
    return not any(x.is_deleted() for x in leaves(engine))


def greedy(engine, steps=STEPS):
    toks = [engine.prefill(0, PROMPT)]
    toks += [int(engine.decode()[0]) for _ in range(steps)]
    return toks


def kv_digest(engine):
    """Every resident K/V byte of slot 0: gpt_tiny's greedy tokens repeat,
    the pool's contents do not."""
    ks, vs, _ = engine.extract_kv(0)
    return hashlib.sha1(b"".join(a.tobytes() for a in ks + vs)).hexdigest()


@pytest.fixture(scope="module")
def copying(tiny):
    """The same 16 greedy steps, and the pool they leave, from an engine
    whose `_cached` drops the donation: what the parent commit computed."""
    mp = pytest.MonkeyPatch()
    plain = GenerationEngine._cached
    mp.setattr(PagedGenerationEngine, "_cached",
               lambda self, fn, name, donate_argnums=(): plain(self, fn, name))
    try:
        engine = build(tiny)
        before = leaves(engine)
        toks = greedy(engine)
        assert not any(x.is_deleted() for x in before)
    finally:
        mp.undo()
    return toks, kv_digest(engine)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_every_pool_executable_consumes_the_pool_it_was_given(tiny, kv_dtype):
    engine = build(tiny, kv_dtype=kv_dtype)
    for call in (lambda: engine.prefill(0, PROMPT), engine.decode,
                 engine.decode):
        before = leaves(engine)
        call()
        assert all(x.is_deleted() for x in before), call
        assert live(engine)
    ks, vs, plen = engine.extract_kv(0)
    other = build(tiny, kv_dtype=kv_dtype)
    before = leaves(other)
    other.adopt_kv(0, ks, vs, plen, first_token=7)
    assert all(x.is_deleted() for x in before)
    assert live(other)
    assert other.trace_counts["adopt"] == {16: 1}


def test_tokens_and_pool_are_those_of_the_copying_engine(tiny, copying):
    engine = build(tiny)
    assert (greedy(engine), kv_digest(engine)) == copying
    assert engine.trace_counts["decode"] == 1


def test_a_warm_cache_load_still_donates(tiny, tmp_path, copying):
    first = build(tiny, compile_cache_dir=str(tmp_path))
    assert (greedy(first), kv_digest(first)) == copying
    second = build(tiny, compile_cache_dir=str(tmp_path))
    before = leaves(second)
    toks = [second.prefill(0, PROMPT)]
    assert all(x.is_deleted() for x in before)
    for _ in range(STEPS):
        before = leaves(second)
        toks.append(int(second.decode()[0]))
        assert all(x.is_deleted() for x in before)
    assert (toks, kv_digest(second)) == copying
    assert second.compile_cache.stats["hits"] >= 2
    assert second.compile_cache.stats["misses"] == 0
    assert second.trace_counts["decode"] == 0
    assert second.trace_counts["prefill"] == {}


def _bump(pool, x):
    return pool.at[0].add(x), x * 2


def test_an_exported_entry_is_rejitted_with_its_donation(tmp_path,
                                                         monkeypatch):
    """jax.export keeps no donation: the entry's own is put back at
    load, so the compile-at-load tier aliases like the compile run."""
    from jax.experimental import serialize_executable

    def refuse(compiled):
        raise ValueError("not serializable here")
    monkeypatch.setattr(serialize_executable, "serialize", refuse)
    cache = cc.CompileCache(str(tmp_path))
    f1 = cc.cached_jit(_bump, "t.donate", cache=cache, donate_argnums=(0,))
    pool, x = jnp.zeros((4, 3)), jnp.ones((3,))
    out, _ = f1(pool, x)
    assert pool.is_deleted() and not x.is_deleted()
    (entry,) = cache.entries()
    with open(os.path.join(cache._entry_dir(entry), cc.ENTRY_META)) as f:
        assert json.load(f)["format"] == "exported"

    fresh = cc.CompileCache(str(tmp_path))
    f2 = cc.cached_jit(_bump, "t.donate", cache=fresh, donate_argnums=(0,))
    out2, _ = f2(out, x)
    assert fresh.stats["hits"] == 1 and fresh.stats["misses"] == 0
    assert out.is_deleted() and not x.is_deleted()
    np.testing.assert_array_equal(np.asarray(out2)[0], np.full(3, 2.0))
    # and a function that donates nothing gets nothing donated on load
    g1 = cc.cached_jit(_bump, "t.keep", cache=fresh)
    g1(out2, x)
    g2 = cc.cached_jit(_bump, "t.keep", cache=cc.CompileCache(str(tmp_path)))
    g2(out2, x)
    assert not out2.is_deleted()


def test_an_armed_engine_keeps_its_replayable_decode(tiny, copying):
    profiler.span_log().clear()
    engine = build(tiny, numerics_taps=True)
    assert engine._decode_donate == ()
    toks = [engine.prefill(0, PROMPT)]
    before = leaves(engine)
    toks.append(int(engine.decode()[0]))
    assert not any(x.is_deleted() for x in before)
    loc = engine.localize_numerics()
    assert loc is not None and loc["first_unhealthy_layer"] is None
    assert loc["probes"] >= 1
    # a prefill between two decodes takes the pool of ITS call, never
    # the one the localizer would replay
    engine.prefill(1, PROMPT[:5])
    assert engine.localize_numerics()["first_unhealthy_layer"] is None
    toks += [int(engine.decode()[0]) for _ in range(STEPS - 1)]
    assert toks == copying[0]
    waits = [r for r in profiler.span_log().spans()
             if r[0] == "serving::decode.wait"]
    assert len(waits) == STEPS
    assert all(r[5] == {"pool_donated": 0, "fetches": 1} for r in waits)


def test_a_failed_fetch_leaves_the_engine_on_live_buffers(tiny, copying):
    engine = build(tiny)
    toks = [engine.prefill(0, PROMPT)]
    toks += [int(engine.decode()[0]) for _ in range(5)]
    real = engine._decode

    class Unfetchable:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("the transfer failed")

    def dispatch_then_fail(*args):
        res = real(*args)
        return (Unfetchable(),) + tuple(res[1:])
    engine._decode = dispatch_then_fail
    before = leaves(engine)
    with pytest.raises(RuntimeError, match="transfer failed"):
        engine.decode()
    engine._decode = real
    assert all(x.is_deleted() for x in before)
    assert live(engine)
    # the step is run again at the same positions: the stream is whole
    toks += [int(engine.decode()[0]) for _ in range(STEPS - 5)]
    assert (toks, kv_digest(engine)) == copying


def test_the_spans_say_whether_the_donation_engaged(tiny):
    assert not profiler._tracer.enabled and profiler._tracer.ring is None
    profiler.span_log().clear()
    engine = build(tiny)
    greedy(engine, steps=4)
    ks, vs, plen = engine.extract_kv(0)
    engine.adopt_kv(1, ks, vs, plen, first_token=7)
    spans = [dict(zip(SpanLog.FIELDS, r))
             for r in profiler.span_log().spans()]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["attrs"] or {})
    assert [a["pool_donated"] for a in by_name["serving::decode.wait"]] \
        == [1] * 4
    assert [a["pool_donated"] for a in by_name["serving::prefill"]] == [1]
    assert [a["pool_donated"] for a in by_name["serving::adopt_kv"]] == [1]
    assert "pool_donated" not in by_name["serving::decode.dispatch"][0]


def test_the_tier_restore_takes_the_pool_in_place(tiny, copying):
    """A promoted chain is scattered into the pool that is there (one
    compiled restore, donated like decode), and what it restores is what
    was demoted."""
    engine = build(tiny, enable_kv_tiers=True, host_tier_blocks=8)
    engine.prefill(0, PROMPT)
    blk = int(engine._tables[0][0])
    record = engine._tier_read_block(blk)
    engine.reset_slot(0)
    before = leaves(engine)
    engine._tier_write_blocks([blk], [record["arrays"]])
    assert all(x.is_deleted() for x in before)
    assert live(engine)
    again = engine._tier_read_block(blk)
    for name, arr in record["arrays"].items():
        np.testing.assert_array_equal(again["arrays"][name], arr)
    assert engine.trace_counts["tier_restore"] == 1
    assert (greedy(engine), kv_digest(engine)) == copying
