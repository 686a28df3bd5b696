"""Serving engine slice: static KV cache, prefill/decode split, continuous
batching, and the serving metrics contract.

The two load-bearing properties (ISSUE 3 acceptance):
  - the decode step compiles exactly once per (model, slot-config) and is
    token-exact against the uncached full-forward recompute;
  - iteration-level batching demonstrably refills: a retired slot is
    reused mid-flight by a queued request while other slots keep
    decoding, and the backpressure/timeout paths fire.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.serving import (
    GenerationEngine, QueueFullError, Scheduler, save_for_generation,
)
from paddle_tpu.text.models import GPTForGeneration, gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import serve_report  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    m = gpt_tiny()
    m.eval()
    return m


def _prompt(seed, n, vocab=1000):
    return np.random.RandomState(seed).randint(0, vocab, n)


def _reference_tokens(model, prompt, max_new):
    """Single-request greedy trajectory through the Layer-level cache."""
    gen = GPTForGeneration(model)
    ids = paddle.to_tensor(np.asarray(prompt)[None, :].astype("int64"))
    out, _ = gen.generate(ids, max_new_tokens=max_new)
    return list(out.numpy()[0])


# ---------------------------------------------------------------- parity
def test_cached_generate_matches_uncached(tiny):
    """Acceptance: cached generate() is token-exact vs the no-cache
    full-forward recompute argmax trajectory."""
    gen = GPTForGeneration(tiny)
    ids = paddle.to_tensor(
        np.stack([_prompt(0, 9), _prompt(1, 9)]).astype("int64"))
    cached, cached_len = gen.generate(ids, max_new_tokens=10, use_cache=True)
    plain, plain_len = gen.generate(ids, max_new_tokens=10, use_cache=False)
    np.testing.assert_array_equal(cached.numpy(), plain.numpy())
    np.testing.assert_array_equal(cached_len.numpy(), plain_len.numpy())


def test_cached_prompt_logits_match_full_forward(tiny):
    ids = paddle.to_tensor(_prompt(3, 11)[None, :].astype("int64"))
    want = tiny(ids).numpy()
    cache = tiny.gen_cache(1, 32)
    got, cache = tiny(ids, cache=cache)
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-5)
    assert int(np.asarray(cache.pos._data)[0]) == 11


def test_mha_static_decode_cache_matches_growing_cache():
    """MultiHeadAttention: the fixed-shape decode cache and the
    reference's growing concat cache produce the same outputs token by
    token."""
    mha = nn.MultiHeadAttention(32, 4)
    mha.eval()
    x = paddle.to_tensor(
        np.random.RandomState(7).rand(2, 6, 32).astype("float32"))

    growing = mha.gen_cache(x[:, :1])          # empty growing cache
    static = mha.gen_static_decode_cache(2, 8)
    for t in range(6):
        tok = x[:, t:t + 1]
        out_g, growing = mha(tok, tok, tok, None, cache=growing)
        out_s, static = mha(tok, tok, tok, None, cache=static)
        np.testing.assert_allclose(out_s.numpy(), out_g.numpy(),
                                   rtol=1e-5, atol=1e-5)


def test_generation_sampling_strategies_run(tiny):
    gen = GPTForGeneration(tiny)
    ids = paddle.to_tensor(_prompt(5, 6)[None, :].astype("int64"))
    out, _ = gen.generate(ids, max_new_tokens=4, decode_strategy="sampling",
                          temperature=0.8, top_k=16, top_p=0.9)
    toks = out.numpy()
    assert toks.shape == (1, 4)
    assert ((toks >= 0) & (toks < tiny.cfg.vocab_size)).all()


def test_generate_rejects_over_length(tiny):
    """Position lookups clamp under XLA, so a request that would run past
    max_position_embeddings must raise instead of silently degrading."""
    gen = GPTForGeneration(tiny)
    max_pos = tiny.cfg.max_position_embeddings
    ids = paddle.to_tensor(_prompt(0, max_pos - 4)[None, :].astype("int64"))
    with pytest.raises(ValueError, match="max_position_embeddings"):
        gen.generate(ids, max_new_tokens=20)
    with pytest.raises(ValueError, match="max_cache_len"):
        gen.generate(paddle.to_tensor(_prompt(0, 8)[None, :].astype("int64")),
                     max_new_tokens=20, max_cache_len=16)


def test_generate_eos_stops_and_pads(tiny):
    gen = GPTForGeneration(tiny)
    ids = paddle.to_tensor(_prompt(0, 5)[None, :].astype("int64"))
    free, _ = gen.generate(ids, max_new_tokens=6)
    eos = int(free.numpy()[0, 1])      # force eos at the 2nd generated token
    out, length = gen.generate(ids, max_new_tokens=6, eos_token_id=eos)
    toks = out.numpy()[0]
    n = int(length.numpy()[0])
    assert toks[n - 1] == eos
    assert (toks[n:] == eos).all()     # eos-padded tail


# --------------------------------------------------------- compile-once
def test_decode_compiles_exactly_once(tiny):
    """Acceptance: 16+ decode steps after warmup add ZERO new
    compilations (the jitted decode body's python trace counter stays 1)."""
    eng = GenerationEngine(tiny, slots=2, max_len=64, prefill_buckets=(16,))
    eng.prefill(0, _prompt(0, 5))
    eng.prefill(1, _prompt(1, 12))
    eng.decode()                               # warmup: the one compile
    assert eng.trace_counts["decode"] == 1
    for _ in range(16):
        eng.decode()
    assert eng.trace_counts["decode"] == 1     # zero new compilations
    assert eng.trace_counts["prefill"] == {16: 1}

    # refill a slot with a different-length prompt in the same bucket:
    # still no new executables anywhere
    eng.reset_slot(0)
    eng.prefill(0, _prompt(2, 9))
    for _ in range(4):
        eng.decode()
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["prefill"] == {16: 1}


def test_engine_matches_layer_level_generate(tiny):
    """The engine's prefill+decode trajectory is token-exact vs the
    Layer-level cached generate for every slot."""
    prompts = [_prompt(0, 4), _prompt(1, 11)]
    eng = GenerationEngine(tiny, slots=2, max_len=64)
    firsts = [eng.prefill(s, p) for s, p in enumerate(prompts)]
    rows = [[f] for f in firsts]
    for _ in range(5):
        step = eng.decode()
        for s in range(2):
            rows[s].append(int(step[s]))
    for s, p in enumerate(prompts):
        assert rows[s] == _reference_tokens(tiny, p, 6)


# -------------------------------------------------- continuous batching
def test_refill_mid_flight(tiny):
    """Acceptance: a short request retires mid-flight and a queued request
    takes its slot while the other slot keeps decoding; every request's
    stream is token-exact vs its single-request trajectory."""
    eng = GenerationEngine(tiny, slots=2, max_len=64)
    sched = Scheduler(eng, max_queue=4)
    pa, pb, pc = _prompt(0, 3), _prompt(1, 5), _prompt(2, 7)
    ha = sched.submit(pa, max_new_tokens=2)    # retires early
    hb = sched.submit(pb, max_new_tokens=9)    # keeps decoding throughout
    hc = sched.submit(pc, max_new_tokens=3)    # queued; takes A's slot

    sched.step()                               # A,B prefilled + 1 decode
    assert hc.status == "QUEUED"
    while not ha.done():
        sched.step()
    assert ha.status == "DONE" and len(ha.tokens) == 2
    sched.step()                               # refill: C takes A's slot
    assert hc.status == "RUNNING"
    assert not hb.done()                       # B still mid-flight
    sched.run_until_idle()

    assert ha.tokens == _reference_tokens(tiny, pa, 2)
    assert hb.tokens == _reference_tokens(tiny, pb, 9)
    assert hc.tokens == _reference_tokens(tiny, pc, 3)
    # the whole run used the one decode executable
    assert eng.trace_counts["decode"] == 1


def test_queue_cap_rejection(tiny):
    eng = GenerationEngine(tiny, slots=1, max_len=32)
    sched = Scheduler(eng, max_queue=1)
    sched.submit(_prompt(0, 3), max_new_tokens=2)
    with pytest.raises(QueueFullError, match="full"):
        sched.submit(_prompt(1, 3), max_new_tokens=2)
    assert sched.counts["serving.rejected"] == 1
    sched.run_until_idle()


def test_one_token_request_gets_exactly_one(tiny):
    """A max_new_tokens=1 request completes at prefill — the same step's
    decode must not append a second token — and its slot refills
    immediately."""
    eng = GenerationEngine(tiny, slots=1, max_len=32)
    sched = Scheduler(eng, max_queue=4)
    h1 = sched.submit(_prompt(0, 3), max_new_tokens=1)
    h2 = sched.submit(_prompt(1, 4), max_new_tokens=2)
    sched.step()       # prefill h1 -> done at once; h2 takes the slot
    assert h1.status == "DONE" and len(h1.tokens) == 1
    assert h1.tokens == _reference_tokens(tiny, _prompt(0, 3), 1)
    sched.run_until_idle()
    assert h2.status == "DONE" and len(h2.tokens) == 2


def test_submit_validates_engine_limits(tiny):
    """Admission rejects what prefill cannot serve instead of stranding
    the request inside step(); odd max_len still gets a terminal bucket."""
    eng = GenerationEngine(tiny, slots=1, max_len=48)
    assert eng.config.prefill_buckets[-1] == 48
    assert eng.max_prompt_len == 47
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit([])
    with pytest.raises(ValueError, match="engine limits"):
        sched.submit(_prompt(0, 40), max_new_tokens=20)
    # over the bucket ladder even with headroom for max_new
    eng2 = GenerationEngine(tiny, slots=1, max_len=64,
                            prefill_buckets=(16,))
    sched2 = Scheduler(eng2)
    with pytest.raises(ValueError, match="engine limits"):
        sched2.submit(_prompt(0, 20), max_new_tokens=4)
    h = sched2.submit(_prompt(0, 12), max_new_tokens=2)
    sched2.run_until_idle()
    assert h.status == "DONE"


def test_request_timeouts(tiny):
    """Deadline paths: a queued request expires before ever running; a
    running request is cut off mid-generation keeping partial output."""
    now = [0.0]
    eng = GenerationEngine(tiny, slots=1, max_len=64)
    sched = Scheduler(eng, clock=lambda: now[0])
    running = sched.submit(_prompt(0, 3), max_new_tokens=50, timeout_s=10.0)
    queued = sched.submit(_prompt(1, 3), max_new_tokens=5, timeout_s=1.0)
    sched.step()
    assert running.status == "RUNNING"
    now[0] = 5.0                       # queued's deadline (1.0) passed
    sched.step()
    assert queued.status == "TIMEOUT" and queued.tokens == []
    now[0] = 50.0                      # running's deadline passed mid-flight
    sched.step()
    assert running.status == "TIMEOUT"
    assert 0 < len(running.tokens) < 50          # partial stream kept
    assert sched.counts["serving.timeout"] == 2


def test_drain_rejects_new_work(tiny):
    eng = GenerationEngine(tiny, slots=1, max_len=32)
    sched = Scheduler(eng)
    h = sched.submit(_prompt(0, 3), max_new_tokens=2)
    sched.drain()
    assert h.status == "DONE"
    with pytest.raises(QueueFullError, match="drain"):
        sched.submit(_prompt(1, 3))


# ------------------------------------------------------- smoke + metrics
def test_serving_smoke_mixed_lengths(tiny, tmp_path):
    """CI smoke: N mixed-length requests all complete, streamed token
    order is correct per request, and the metrics JSONL validates against
    the serve_report schema."""
    metrics = str(tmp_path / "serve_metrics.jsonl")
    eng = GenerationEngine(tiny, slots=2, max_len=64)
    sched = Scheduler(eng, max_queue=8, metrics_path=metrics)
    lengths = (3, 9, 14, 5, 7)
    handles = [sched.submit(_prompt(i, n), max_new_tokens=3 + i % 3)
               for i, n in enumerate(lengths)]
    sched.drain()

    for i, (h, n) in enumerate(zip(handles, lengths)):
        assert h.status == "DONE"
        assert h.tokens == _reference_tokens(tiny, _prompt(i, n), 3 + i % 3)
        assert h.ttft_s is not None and h.ttft_s >= 0

    records = serve_report.load(metrics)
    assert serve_report.validate_records(records) == []
    summary = serve_report.summarize(records)
    assert summary["requests"] == {"DONE": len(lengths)}
    assert summary["decode_tokens_per_s"] is None \
        or summary["decode_tokens_per_s"] > 0
    assert "serving report" in serve_report.render(summary)

    m = sched.metrics()
    assert m["tokens_generated"] == sum(3 + i % 3 for i in range(len(lengths)))
    assert m["requests"]["serving.completed"] == len(lengths)
    assert m["decode_tokens_per_s"] > 0


# ------------------------------------------------- predictor integration
def test_predictor_generate_cold_load(tiny, tmp_path):
    """save_for_generation -> cold Predictor -> generate, token-exact vs
    the live model."""
    from paddle_tpu.inference import Config, create_predictor
    path = str(tmp_path / "gpt")
    save_for_generation(tiny, path)
    assert os.path.exists(path + ".gencfg")

    pred = create_predictor(Config(path + ".pdmodel", path + ".pdiparams"))
    prompts = [_prompt(0, 4), _prompt(1, 9)]
    outs = pred.generate(prompts, max_new_tokens=4, slots=2, max_len=32)
    for p, got in zip(prompts, outs):
        assert got == _reference_tokens(tiny, p, 4)
