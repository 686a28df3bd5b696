"""A paged engine call is ONE upload, one enqueue and one fetch, and runs no
eager device program (ISSUE 37): the upload and wait spans say so
(`transfers`, `fetches`) and an independent count agrees; tokens are what
they were (greedy against the dense engine, a sampled stream the same
whether a request is left to decode or restarted through
`prefill(rng=(seed, gen))`); positions are the host's to advance, only
once the tokens are back; `precompile()` warms the calling convention
that serves."""
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

import paddle_tpu                                              # noqa: E402
from benchmark.harness import weights_hybrid as wh             # noqa: E402
from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu import profiler                                # noqa: E402
from paddle_tpu.observability.flight_recorder import SpanLog   # noqa: E402
from paddle_tpu.serving import (GenerationEngine,              # noqa: E402
                                PagedEngineConfig,
                                PagedGenerationEngine, Scheduler,
                                ServingConfig)
from paddle_tpu.serving.distributed import (                   # noqa: E402
    TensorParallelEngineConfig, TensorParallelPagedEngine)
from paddle_tpu.serving.tenancy import (AdapterBank,           # noqa: E402
                                        init_adapter_state)
from paddle_tpu.text.models import gpt_tiny                    # noqa: E402
from paddle_tpu.text.models.hybrid import (HybridConfig,       # noqa: E402
                                           HybridDecoder)

P = "serving::"
ENGINE_KW = dict(slots=2, max_len=64, block_size=8,
                 prefill_buckets=(16, 32, 64))


@pytest.fixture(scope="module")
def gpt():
    paddle_tpu.seed(0)
    model = gpt_tiny()
    model.eval()
    return model


@pytest.fixture(scope="module")
def hybrid():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling3_flash_ep4_share.json")) as f:
        config = tiny_of(json.load(f))
    kw = dict(config["program"]["model_config"])
    kw.update(param_dtype="float32", init_weights=False)
    model = HybridDecoder(HybridConfig(**kw))
    model.eval()
    model.load_arrays(wh.named(config, 2147483659, "float32"))
    return model


def adapter_bank(cfg):
    bank = AdapterBank(cfg, n_adapters=3, rank=4)
    bank.load("acme", init_adapter_state(cfg, 4, seed=1, scale=1.0))
    return bank


def make_engine(model, kind, **over):
    """The engine of one test case: `kind` names what rides the call
    beside tables, positions and tokens."""
    kw = dict(ENGINE_KW, **over)
    if kind == "tp2":
        return TensorParallelPagedEngine(
            model, TensorParallelEngineConfig(tp=2, **kw))
    if kind == "int8":
        kw["kv_dtype"] = "int8"
    if kind == "sampling":
        kw.update(decode_strategy="sampling", temperature=0.9, top_k=20)
    engine = PagedGenerationEngine(model, PagedEngineConfig(**kw))
    if kind == "adapters":
        engine.attach_adapters(adapter_bank(model.cfg))
    return engine


def prompts(n, lo=5, hi=30, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 100, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def logged(since):
    return [dict(zip(SpanLog.FIELDS, r))
            for r in profiler.span_log().spans()[since:]]


# ------------------------------- (a) one upload, one fetch, no eager program

CASES = ["gpt", "hybrid", "sampling", "adapters", "int8", "tp2"]


def engine_of(case, gpt, hybrid):
    return make_engine(hybrid if case == "hybrid" else gpt,
                       "greedy" if case in ("gpt", "hybrid") else case)


@pytest.mark.parametrize("case", CASES)
def test_every_upload_is_one_transfer_and_every_wait_one_fetch(
        case, gpt, hybrid):
    engine = engine_of(case, gpt, hybrid)
    sched = Scheduler(engine, ServingConfig(max_queue=8))
    since = len(profiler.span_log().spans())
    handles = [sched.submit(p, max_new_tokens=4 + i)
               for i, p in enumerate(prompts(4))]
    while sched.step():
        pass
    assert all(h.status == "DONE" for h in handles)
    spans = logged(since)
    for phase, n_calls in (("decode", 5), ("prefill", 4)):
        uploads = [s for s in spans if s["name"] == f"{P}{phase}.upload"]
        waits = [s for s in spans if s["name"] == f"{P}{phase}.wait"]
        assert len(uploads) == len(waits) >= n_calls
        assert {s["attrs"]["transfers"] for s in uploads} == {1}
        assert {s["attrs"]["fetches"] for s in waits} == {1}
    donated = [s["attrs"]["pool_donated"] for s in spans
               if s["name"] in (P + "decode.wait", P + "prefill")]
    assert donated and set(donated) == {1}


class CallCounter:
    """Counts, for as long as it is entered, every way the host can put
    something on the device or start a device program of its own."""

    WATCHED = [(jax, "device_put"), (jnp, "asarray"), (jnp, "array"),
               (jax.random, "split"), (jax.random, "fold_in"),
               (jax.random, "key"), (jax.random, "PRNGKey")]

    def __init__(self, monkeypatch):
        self.monkeypatch, self.calls = monkeypatch, []

    def __enter__(self):
        for owner, name in self.WATCHED:
            real = getattr(owner, name)

            def counted(*a, _real=real, _name=name, **kw):
                self.calls.append(_name)
                return _real(*a, **kw)
            self.monkeypatch.setattr(owner, name, counted)
        return self

    def __exit__(self, *exc):
        self.monkeypatch.undo()
        return False


@pytest.mark.parametrize("case", CASES)
def test_an_independent_count_of_one_call_agrees(case, gpt, hybrid,
                                                 monkeypatch):
    engine = engine_of(case, gpt, hybrid)
    one, two = prompts(2, lo=9, hi=15, seed=1)      # one bucket
    engine.prefill(0, one)          # compile outside the count
    engine.decode()
    fetched = []
    real_fetch = engine._fetch
    engine._fetch = lambda arr: fetched.append(1) or real_fetch(arr)
    with CallCounter(monkeypatch) as prefill_count:
        engine.prefill(1, two)
    assert prefill_count.calls == ["device_put"] and len(fetched) == 1
    with CallCounter(monkeypatch) as decode_count:
        engine.decode()
    assert decode_count.calls == ["device_put"] and len(fetched) == 2


def test_the_packed_layout_follows_what_the_engine_can_see(gpt):
    greedy = make_engine(gpt, "greedy")
    sampling = make_engine(gpt, "sampling")
    adapters = make_engine(gpt, "adapters")
    slots, row = 2, 64 // 8
    base = slots * row + 2 * slots
    assert greedy._pack().size == base
    assert sampling._pack().size == base + 2 * slots      # seeds, counters
    assert adapters._pack().size == base + slots          # adapter ids
    assert list(adapters._pack().fields)[-1] == "adapter"
    assert greedy._pack(16).size == row + 3 + 16
    assert sampling._pack(32).size == row + 5 + 32
    # a seed past 2**31 rides as its bits
    buf = sampling._pack(16).pack(
        row=np.arange(row), slot=1, length=3, start=0,
        seed=np.uint32(0xfedcba98), gen=np.int32(7),
        ids=np.zeros(16, np.int32))
    lo, hi, _ = sampling._pack(16).fields["seed"]
    assert buf[lo:hi].view(np.uint32)[0] == 0xfedcba98


# ----------------------------------------------- (b), (c) the same tokens

def scheduler_tokens(engine, reqs, new=8):
    sched = Scheduler(engine, ServingConfig(max_queue=16))
    handles = [sched.submit(p, max_new_tokens=new) for p in reqs]
    while sched.step():
        pass
    return [h.tokens for h in handles]


@pytest.mark.parametrize("case", ["gpt", "int8", "tp2", "adapters"])
def test_greedy_tokens_are_the_dense_engines(case, gpt):
    reqs = prompts(5, seed=2)
    dense = scheduler_tokens(GenerationEngine(
        gpt, slots=2, max_len=64, prefill_buckets=(16, 32, 64)), reqs)
    # an attached bank whose slots all point at the base row adds an exact
    # zero; int8 pools round K and V, so they are held to themselves
    got = scheduler_tokens(make_engine(gpt, case), reqs)
    if case == "int8":
        assert got == scheduler_tokens(make_engine(gpt, case), reqs)
        assert [len(t) for t in got] == [len(t) for t in dense]
    else:
        assert got == dense


def sampled_stream(engine, prompt, seed, new, restart_at=None):
    """`new` sampled tokens of one request in slot 0; with `restart_at`,
    the request is dropped after that many tokens and prefilled again
    (prompt + delivered tokens) with its sampler state, as a preemption
    or a failover does."""
    tokens = [engine.prefill(0, prompt, rng=(seed, 0))]
    while len(tokens) < new:
        if restart_at is not None and len(tokens) == restart_at:
            engine.reset_slot(0)
            tokens.append(engine.prefill(0, list(prompt) + tokens,
                                         rng=(seed, len(tokens))))
            continue
        tokens.append(int(engine.decode()[0]))
    return tokens


@pytest.mark.parametrize("case", ["sampling", "sampling+adapters",
                                  "sampling+int8", "sampling+tp2"])
def test_a_restarted_sampled_stream_is_the_one_left_to_decode(case, gpt):
    over = dict(decode_strategy="sampling", temperature=0.9, top_k=20)
    kind = case.partition("+")[2] or "greedy"
    prompt = prompts(1, seed=3)[0]
    seed = 0x9e3779b9               # past 2**31: the bits must survive
    left = sampled_stream(make_engine(gpt, kind, **over), prompt, seed, 12)
    again = sampled_stream(make_engine(gpt, kind, **over), prompt, seed, 12,
                           restart_at=5)
    if kind == "int8":
        # the restart's prefill quantises the whole prefix at once, the
        # original a token at a time: the prefix up to the restart, and
        # the restarted token's key, are what can be held equal
        assert again[:6] == left[:6] or again[:5] == left[:5]
    else:
        assert again == left
    other = sampled_stream(make_engine(gpt, kind, **over), prompt, seed + 1,
                           12)
    assert other != left


def test_sampled_paged_stream_is_the_dense_engines(gpt):
    over = dict(decode_strategy="sampling", temperature=0.9, top_k=20)
    prompt = prompts(1, seed=4)[0]
    dense = GenerationEngine(gpt, slots=2, max_len=64,
                             prefill_buckets=(16, 32, 64), **over)
    assert sampled_stream(make_engine(gpt, "greedy", **over), prompt, 77,
                          10) == sampled_stream(dense, prompt, 77, 10)


def test_hybrid_counters_still_ride_behind_the_tokens(hybrid):
    engine = make_engine(hybrid, "greedy")
    since = len(profiler.span_log().spans())
    engine.prefill(0, prompts(1, seed=5)[0])
    out = engine.decode()
    assert out.shape == (2,) and out.dtype == np.int32
    assert set(engine.last_counters) == set(hybrid.serving_counters)
    wait = [s for s in logged(since) if s["name"] == P + "decode.wait"][-1]
    for name in hybrid.serving_counters:
        assert wait["attrs"][name] == engine.last_counters[name]
    assert wait["attrs"]["fetches"] == 1


# ------------------------------ (d) positions are the host's, after the fetch

@pytest.mark.parametrize("case", ["gpt", "hybrid"])
def test_a_decode_whose_fetch_raises_leaves_positions_and_repeats(
        case, gpt, hybrid):
    model = hybrid if case == "hybrid" else gpt
    reqs = prompts(2, seed=6)

    def run(fail_at):
        engine = make_engine(model, "greedy")
        for slot, p in enumerate(reqs):
            engine.prefill(slot, p)
        real, steps, seen = engine._fetch, [], []
        for step in range(4):
            if step == fail_at:
                def broken(arr):
                    engine._fetch = real
                    raise RuntimeError("fetch lost")
                engine._fetch = broken
                before = engine.slot_positions()
                with pytest.raises(RuntimeError, match="fetch lost"):
                    engine.decode()
                assert (engine.slot_positions() == before).all()
                assert not engine._pool[0][0].is_deleted()
            steps.append(engine.decode().tolist())
            seen.append(engine.slot_positions().tolist())
        return steps, seen, [np.asarray(x, np.float32)
                             for x in jax.tree_util.tree_leaves(engine._pool)]

    want_steps, want_pos, want_pool = run(None)
    got_steps, got_pos, got_pool = run(2)
    assert got_pos == want_pos
    # the step that ran twice wrote the same K/V at the same positions. A
    # model that keeps per-slot state has advanced it twice (a step updates
    # it in place, on the parent too): it is held to its positions alone
    if case == "gpt":
        assert got_steps == want_steps
        for got, want in zip(got_pool, want_pool):
            np.testing.assert_array_equal(got, want)


def test_positions_advance_on_the_host_and_clamp(gpt):
    engine = make_engine(gpt, "greedy", max_len=32, prefill_buckets=(16, 32))
    prompt = prompts(1, lo=20, hi=21, seed=7)[0]
    engine.prefill(0, prompt)
    assert engine.slot_positions().tolist() == [20, 0]
    assert engine._pos.dtype == np.int32
    for _ in range(14):
        engine.decode()
    # free slots and slots at the end stay in bounds for ever
    assert engine.slot_positions().tolist() == [31, 14]
    assert engine._pos.dtype == np.int32
    # a prefix hit starts the suffix where the shared blocks end
    engine.reset_slot(0)
    engine.prefill(1, prompt)
    assert engine.last_prefill_stats["prefix_hit_tokens"] == 16
    assert engine.slot_positions()[1] == 20


# ------------------------------------------ (e) precompile warms what serves

@pytest.mark.parametrize("case", ["gpt", "hybrid", "sampling", "adapters"])
def test_precompile_then_serving_compiles_nothing_more(case, gpt, hybrid,
                                                       tmp_path):
    model = hybrid if case == "hybrid" else gpt
    engine = make_engine(model, "greedy" if case in ("gpt", "hybrid")
                         else case, compile_cache_dir=str(tmp_path / "cc"))
    report = engine.precompile()
    assert set(report) == set(engine.executable_names())
    assert set(report.values()) == {"miss"}
    traced = json.dumps(engine.trace_counts, sort_keys=True, default=str)
    assert engine.trace_counts["decode"] == 1
    scheduler_tokens(engine, prompts(4, lo=5, hi=60, seed=8), new=4)
    assert json.dumps(engine.trace_counts, sort_keys=True,
                      default=str) == traced
    assert set(engine.precompile().values()) == {"hit"}


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_old_argument_list_fails_loudly(which, gpt, tmp_path):
    engine = make_engine(gpt, "greedy",
                         compile_cache_dir=str(tmp_path / "cc"))
    tables, pos = jnp.asarray(engine._tables), jnp.asarray(engine._pos)
    key = engine._warm_key()
    with pytest.raises(TypeError):
        if which == "decode":
            engine._decode.warm(engine._decode_params, engine._pool, tables,
                                pos, jnp.zeros((2,), jnp.int32), key)
        else:
            engine._prefill[16] = engine._make_prefill(16)
            engine._prefill[16].warm(
                engine._params, engine._pool, tables, pos,
                jnp.asarray(0, jnp.int32), jnp.zeros((16,), jnp.int32),
                jnp.asarray(1, jnp.int32), jnp.asarray(0, jnp.int32), key)
    # ... and so does an upload of another layout's size
    with pytest.raises(TypeError, match="packed"):
        engine._decode.warm(engine._decode_params, engine._pool,
                            jnp.zeros((3,), jnp.int32))
    assert engine.trace_counts["prefill"].get(16, 0) <= 1


def test_the_numerics_localizer_replays_the_new_arguments(gpt):
    engine = make_engine(gpt, "greedy", numerics_taps=True)
    engine.prefill(0, prompts(1, seed=9)[0])
    engine.decode()
    args = engine._last_decode_args
    assert len(args) == 3 and args[2].shape == (engine._pack().size,)
    record = engine.localize_numerics()
    assert record["first_unhealthy_layer"] is None and record["probes"] >= 1
    assert engine.trace_counts["decode"] == 1
