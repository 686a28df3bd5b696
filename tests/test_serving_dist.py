"""Multi-host serving (ISSUE 10): TP decode, KV handoff, hot-swap, failover.

Acceptance, mapped:
  - tensor-parallel decode token-exact vs the single-device paged engine,
    decode executable compiled exactly once, pools genuinely sharded
    (test_tp_decode_token_exact_and_compile_once);
  - KV-block wire serialization round-trip + truncated-frame rejection,
    standalone AND relayed as in-band error frames over the fabric
    (test_kv_bundle_*);
  - disaggregated prefill->decode handoff bit-exact vs single-process,
    through the engines directly, the scheduler's staged path, and a
    full in-process router+workers fleet (test_adopt_*, test_staged_*,
    test_frontend_*);
  - zero-downtime weight hot-swap: swapped mid-traffic, zero dropped
    requests, in-flight greedy streams token-exact across the swap,
    version gauge flip (test_weight_hot_swap_*);
  - chaos: handoff faults degrade to recompute (bit-exact), a KILLED
    decode worker's requests fail over and complete bit-identical, and
    the merged chrome trace shows ONE trace id spanning router, prefill,
    and decode processes (test_failover_*, test_multiprocess_* — the
    SIGKILL + trace-merge run is `slow`, riding real forked workers);
  - gray failures (ISSUE 20): a 10x-slow decode worker is suspected by
    the health plane and its streams migrate off bit-exact with ZERO
    extra deadline misses, a dark-marked worker that still answers
    OP_HEALTH rejoins placement, the affinity probe sweep is capped at
    the suspicion-scaled hedge deadline, rolling_drain restarts a live
    fleet with zero drops, and the {slow, flaky, SIGKILL} x {prefill,
    decode mid-stream, drain-in-progress} chaos matrix holds stream
    bit-identity plus a replay-valid decisions.v1 trail in every cell
    (test_gray_*, test_chaos_matrix_*, test_rolling_drain_*).
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.distributed.ps.rpc import PSServer, PSServerError
from paddle_tpu.observability import decisions as _dec
from paddle_tpu.observability import faults, metrics, tracecontext
from paddle_tpu.serving import (PagedEngineConfig, PagedGenerationEngine,
                                Scheduler, ServingConfig)
from paddle_tpu.serving.distributed import (
    DistFrontend, KVWireError, ServingShardClient, ServingWorker,
    TensorParallelEngineConfig, TensorParallelPagedEngine, pack_kv_bundle,
    save_swap_checkpoint, unpack_kv_bundle)
from paddle_tpu.serving.distributed.worker import OP_KV_PUT
from paddle_tpu.text.models import gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_SEED = 2024                  # what worker_main seeds by default

VOCAB = 1024
ENGINE_KW = dict(slots=2, max_len=64, block_size=8)


@pytest.fixture(scope="module")
def tiny():
    # the autouse seed fixture ran paddle_tpu.seed(2024) just before the
    # first use, so these weights are IDENTICAL to what a forked
    # worker_main --seed 2024 builds — cross-process exactness tests
    # compare streams against this model
    m = gpt_tiny()
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, VOCAB, n).tolist()


def _engine(model, **over):
    kw = dict(ENGINE_KW)
    kw.update(over)
    return PagedGenerationEngine(model, PagedEngineConfig(**kw))


def _clone(model):
    """A distinct Layer OBJECT over the same weight arrays. In-process
    multi-worker tests need one per worker: `functional_call` swaps a
    Layer's params during TRACING, so two worker threads tracing through
    one shared Layer would race (real deployments have one process per
    host and never hit this)."""
    m = gpt_tiny()
    m.eval()
    m.set_state_dict(model.state_dict())
    return m


def _worker_pair(model):
    """(model, engine) for one in-process worker — over its own Layer
    clone so concurrent workers never trace through a shared object."""
    m = _clone(model)
    return m, _engine(m)


def _reference_streams(model, prompts, max_new):
    """Single-process greedy streams through the ordinary paged
    scheduler — THE oracle every distributed run must match."""
    sched = Scheduler(_engine(model),
                      ServingConfig(default_max_new_tokens=max_new))
    handles = [sched.submit(p) for p in prompts]
    while sched.step():
        pass
    return {tuple(p): h.tokens for p, h in zip(prompts, handles)}


def _counter(name, **labels):
    flat = metrics.flatten_snapshot(metrics.registry().snapshot(),
                                    kinds=("counter",))
    key = name
    if labels:
        key += "{" + ",".join(f"{k}={labels[k]}"
                              for k in sorted(labels)) + "}"
    return flat.get(key, 0.0)


def _gauge(name):
    flat = metrics.flatten_snapshot(metrics.registry().snapshot(),
                                    kinds=("gauge",))
    return flat.get(name)


# ------------------------------------------------------- KV wire format

def test_kv_bundle_roundtrip_preserves_dtype_shape_layers():
    rng = np.random.RandomState(0)
    ks = [rng.randn(9, 4, 8).astype(np.float32) for _ in range(3)]
    vs = [rng.randn(9, 4, 8).astype(np.float32) for _ in range(3)]
    buf = pack_kv_bundle(ks, vs, meta={"first_token": 7, "plen": 9})
    k2, v2, meta = unpack_kv_bundle(buf)
    assert len(k2) == len(v2) == 3
    assert meta == {"first_token": 7, "plen": 9}
    for a, b in zip(ks + vs, k2 + v2):
        assert b.dtype == np.float32 and b.shape == (9, 4, 8)
        np.testing.assert_array_equal(a, b)


def test_kv_bundle_rejects_truncation_and_lies():
    ks = [np.ones((4, 2, 8), np.float32)] * 2
    buf = pack_kv_bundle(ks, ks)
    # truncation anywhere — inside the frame head, the header, the
    # array tail — must raise, never yield a short-but-plausible bundle
    for cut in (2, 6, len(buf) // 2, len(buf) - 1):
        with pytest.raises(KVWireError):
            unpack_kv_bundle(buf[:cut])
    with pytest.raises(KVWireError):
        unpack_kv_bundle(buf + b"\x00")         # padded is a lie too
    with pytest.raises(KVWireError):
        unpack_kv_bundle(b"\xff" * len(buf))    # foreign magic
    with pytest.raises(KVWireError):            # mismatched layer shapes
        pack_kv_bundle([np.ones((4, 2, 8), np.float32)],
                       [np.ones((3, 2, 8), np.float32)])


def test_kv_bundle_truncation_relays_as_inband_error_frame():
    """A torn bundle arriving over the fabric answers with an in-band
    error frame (PSServerError naming the wire violation) — the
    connection survives and serves the corrected retry."""
    from paddle_tpu.serving.distributed import kv_handoff as kvh

    staged = {}

    def kv_put(body, aux, reqid, rctx):
        obj, tail = kvh.unpack_payload(body)
        ks, vs, meta = kvh.unpack_kv_bundle(tail)
        staged[obj["key"]] = (ks, vs, meta)
        return kvh.pack_payload({"ok": 1})

    server = PSServer(handlers={OP_KV_PUT: kv_put})
    client = ServingShardClient([server.endpoint])
    try:
        ks = [np.ones((4, 2, 8), np.float32)] * 2
        bundle = pack_kv_bundle(ks, ks, meta={"plen": 4})
        with pytest.raises(PSServerError, match="truncated"):
            client.kv_put(0, "k1", bundle[:len(bundle) // 2])
        assert "k1" not in staged           # never adopted torn
        client.kv_put(0, "k1", bundle)      # same connection still fine
        assert "k1" in staged
    finally:
        client.stop_servers()
        client.close()


# ------------------------------------------------- disaggregated handoff

def test_adopt_kv_is_bit_exact_vs_local_prefill_and_compiles_once(tiny):
    """Engine-level handoff: prefill on host A, extract, adopt on host
    B — B's continued greedy stream is bit-identical to one engine doing
    everything, and adoption adds exactly one executable per bucket."""
    prompt = _prompt(3, 11)
    ref = _engine(tiny)
    stream_ref = [ref.prefill(0, prompt)]
    for _ in range(6):
        ref.ensure_decode_capacity()
        stream_ref.append(int(ref.decode()[0]))

    A, B = _engine(tiny), _engine(tiny)
    first = A.prefill(0, prompt)
    ks, vs, plen = A.extract_kv(0)
    A.reset_slot(0)
    assert plen == len(prompt)
    # ship through the real wire format
    k2, v2, meta = unpack_kv_bundle(pack_kv_bundle(
        ks, vs, meta={"first_token": first, "plen": plen}))
    B.adopt_kv(0, k2, v2, meta["plen"], meta["first_token"])
    stream = [meta["first_token"]]
    for _ in range(6):
        B.ensure_decode_capacity()
        stream.append(int(B.decode()[0]))
    assert stream == stream_ref
    assert B.trace_counts["decode"] == 1
    assert list(B.trace_counts["adopt"].values()) == [1]


def test_scheduler_staged_placement_token_exact_and_fallbacks(tiny):
    """The scheduler's staged path: a handed bundle is adopted (counted,
    flagged on the handle), a WRONG bundle silently degrades to local
    recompute prefill — both streams exactly match the oracle."""
    prompt = _prompt(5, 9)
    max_new = 6
    oracle = _reference_streams(tiny, [prompt], max_new)[tuple(prompt)]

    A = _engine(tiny)
    first = A.prefill(0, prompt)
    ks, vs, plen = A.extract_kv(0)
    A.reset_slot(0)

    sched = Scheduler(_engine(tiny),
                      ServingConfig(default_max_new_tokens=max_new))
    adopted_before = _counter("serving_kv_adopted_total")
    good = sched.submit(prompt, staged_kv=(ks, vs, plen, first))
    # a bundle whose K/V shapes lie (wrong layer count) must fall back
    bad = sched.submit(prompt, staged_kv=(ks[:1], vs[:1], plen, first))
    while sched.step():
        pass
    assert good.status == "DONE" and good.adopted
    assert bad.status == "DONE" and not bad.adopted
    assert good.tokens == oracle
    assert bad.tokens == oracle
    assert _counter("serving_kv_adopted_total") == adopted_before + 1


@pytest.mark.slow
def test_frontend_disaggregated_pools_token_exact(tiny):
    """Router + 1 prefill + 2 decode workers (in-process): every request
    rides the remote-prefill handoff, streams match the single-process
    oracle, placement spreads over both decode workers, and handoff
    bytes/latency land in the registry."""
    prompts = [_prompt(10 + i, 7 + i) for i in range(4)]
    max_new = 5
    oracle = _reference_streams(tiny, prompts, max_new)
    bytes_before = _counter("serving_kv_handoff_bytes_total")

    workers = [ServingWorker(*_worker_pair(tiny), role="prefill")]
    # a light decode pace keeps the requests in flight long enough for
    # the least-loaded placement to see real concurrent load
    workers += [ServingWorker(*_worker_pair(tiny), role="decode",
                              serving_config=ServingConfig(
                                  default_max_new_tokens=max_new),
                              step_interval_s=0.02)
                for _ in range(2)]
    fe = DistFrontend([w.endpoint for w in workers[1:]],
                      [workers[0].endpoint])
    try:
        reqs = [fe.submit(p, max_new=max_new) for p in prompts]
        fe.run(timeout_s=90)
        for r in reqs:
            assert r.status == "DONE", (r.status, r.error)
            assert r.staged, "remote prefill handoff did not stick"
            assert r.tokens == oracle[tuple(r.prompt)]
        assert {r.worker for r in reqs} == {0, 1}, "placement collapsed"
        assert _counter("serving_kv_handoff_bytes_total") > bytes_before
    finally:
        fe.close()
        for w in workers:
            w.shutdown()


@pytest.mark.slow
def test_handoff_chaos_degrades_to_recompute_bit_exact(tiny):
    """serving.kv_handoff armed: every second handoff raises on the
    sender — the router falls back to decode-local recompute prefill
    and every stream still matches the oracle (the chaos only costs the
    disaggregation win)."""
    prompts = [_prompt(30 + i, 8) for i in range(4)]
    max_new = 4
    oracle = _reference_streams(tiny, prompts, max_new)

    pw = ServingWorker(*_worker_pair(tiny), role="prefill")
    dw = ServingWorker(*_worker_pair(tiny), role="decode",
                       serving_config=ServingConfig(
                           default_max_new_tokens=max_new))
    fe = DistFrontend([dw.endpoint], [pw.endpoint])
    # the site fires once per pack and once per unpack; nth=1 with
    # max_fires=2 deterministically kills the first two handoffs at the
    # sender's pack and spares the rest
    faults.arm("serving.kv_handoff", mode="raise", nth=1, max_fires=2)
    try:
        reqs = [fe.submit(p, max_new=max_new) for p in prompts]
        fe.run(timeout_s=90)
        staged = [r.staged for r in reqs]
        for r in reqs:
            assert r.status == "DONE", (r.status, r.error)
            assert r.tokens == oracle[tuple(r.prompt)]
        assert staged == [False, False, True, True], staged
    finally:
        faults.disarm_all()
        fe.close()
        pw.shutdown()
        dw.shutdown()


@pytest.mark.slow
def test_failover_to_live_worker_completes_bit_exact(tiny):
    """A decode worker dies mid-stream (in-process shutdown — the
    subprocess SIGKILL variant is the slow tier): its requests fail
    over to the surviving worker and the MERGED streams are
    bit-identical to an unkilled single-process run."""
    prompts = [_prompt(40 + i, 6) for i in range(4)]
    max_new = 12
    oracle = _reference_streams(tiny, prompts, max_new)
    failover_before = _counter("serving_failover_total")

    d0 = ServingWorker(*_worker_pair(tiny), role="decode",
                       serving_config=ServingConfig(
                           default_max_new_tokens=max_new),
                       step_interval_s=0.03)
    d1 = ServingWorker(*_worker_pair(tiny), role="decode",
                       serving_config=ServingConfig(
                           default_max_new_tokens=max_new),
                       step_interval_s=0.03)
    fe = DistFrontend([d0.endpoint, d1.endpoint])
    try:
        reqs = [fe.submit(p, max_new=max_new) for p in prompts]
        victims = [r for r in reqs if r.worker == 1]
        assert victims, "placement never used worker 1"
        # let the victims stream a few tokens, then take their host down
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            fe.pump()
            if all(len(r.tokens) >= 2 for r in victims):
                break
            time.sleep(0.01)
        assert all(len(r.tokens) >= 2 for r in victims)
        mid = {r.key: list(r.tokens) for r in victims}
        d1.kill()                # sever connections like a dead host
        fe.run(timeout_s=90)
        for r in reqs:
            assert r.status == "DONE", (r.status, r.error)
            assert r.tokens == oracle[tuple(r.prompt)], \
                f"{r.key} diverged after failover"
        for r in victims:
            assert r.failovers >= 1
            assert r.tokens[:len(mid[r.key])] == mid[r.key], \
                "delivered prefix mutated across failover"
        assert _counter("serving_failover_total") > failover_before
    finally:
        fe.close()
        d0.shutdown()
        d1.shutdown()


# ------------------------------------------------------ weight hot-swap

def test_weight_hot_swap_mid_traffic_zero_drops_token_exact(tiny,
                                                            tmp_path):
    """Acceptance: a ckpt_commit-committed checkpoint is pushed into a
    running engine between decode steps — zero dropped requests,
    in-flight greedy streams token-exact across the swap (same-weights
    swap == bit-identical run), version gauge flip, and NO recompile."""
    prompts = [_prompt(50 + i, 7) for i in range(3)]
    max_new = 10
    oracle = _reference_streams(tiny, prompts, max_new)

    ckpt = str(tmp_path / "ckpt" / "step-0001")
    assert save_swap_checkpoint(tiny.state_dict(), ckpt)

    from paddle_tpu.serving.distributed.worker import \
        load_checkpoint_params
    engine = _engine(tiny)
    sched = Scheduler(engine, ServingConfig(default_max_new_tokens=max_new))
    handles = [sched.submit(p) for p in prompts]
    for _ in range(3):                   # traffic is mid-flight
        sched.step()
    assert any(h.status == "RUNNING" for h in handles)
    ev = sched.schedule_weight_swap(load_checkpoint_params(ckpt),
                                    version=2)
    while sched.step():
        pass
    assert ev.is_set() and sched.last_swap["ok"], sched.last_swap
    assert sched.last_swap["inflight"] >= 1   # swapped under live slots
    assert sched.model_version == 2
    assert _gauge("serving_model_version") == 2.0
    assert _counter("serving_swap_dropped_requests_total") == 0
    for p, h in zip(prompts, handles):
        assert h.status == "DONE"
        assert h.tokens == oracle[tuple(p)], \
            "same-weights swap perturbed an in-flight stream"
    assert engine.trace_counts["decode"] == 1, "hot-swap recompiled"


@pytest.mark.slow
def test_weight_hot_swap_new_weights_change_output_not_avals(tiny,
                                                             tmp_path):
    """Swapping genuinely NEW weights: requests in flight complete
    (zero drops), later requests decode under the new model (different
    stream), still zero recompiles."""
    prompt = _prompt(60, 8)
    max_new = 6
    oracle = _reference_streams(tiny, [prompt], max_new)[tuple(prompt)]
    new_state = {k: np.asarray(v.numpy()) * -1.0
                 for k, v in tiny.state_dict().items()}
    ckpt = str(tmp_path / "ckpt" / "step-0002")
    assert save_swap_checkpoint(new_state, ckpt)

    from paddle_tpu.serving.distributed.worker import \
        load_checkpoint_params
    engine = _engine(tiny)
    sched = Scheduler(engine, ServingConfig(default_max_new_tokens=max_new))
    inflight = sched.submit(prompt)
    for _ in range(2):
        sched.step()
    sched.schedule_weight_swap(load_checkpoint_params(ckpt), version=3)
    while sched.step():
        pass
    assert inflight.status == "DONE"          # zero drops across swap
    after = sched.submit(prompt)
    while sched.step():
        pass
    assert after.status == "DONE"
    assert after.tokens != oracle, "swap never took effect"
    assert engine.trace_counts["decode"] == 1
    assert _counter("serving_swap_dropped_requests_total") == 0


@pytest.mark.slow
def test_weight_swap_fault_rejects_atomically(tiny):
    """serving.weight_swap armed: the swap FAILS, the old weights keep
    serving (streams unchanged), the failure is counted, nothing
    dropped."""
    prompt = _prompt(70, 7)
    max_new = 5
    oracle = _reference_streams(tiny, [prompt], max_new)[tuple(prompt)]
    failed_before = _counter("serving_weight_swaps_total", status="failed")

    engine = _engine(tiny)
    sched = Scheduler(engine, ServingConfig(default_max_new_tokens=max_new))
    h = sched.submit(prompt)
    sched.step()
    faults.arm("serving.weight_swap", mode="raise", max_fires=1)
    bogus = {k: np.asarray(v.numpy()) * 0.0
             for k, v in tiny.state_dict().items()}
    ev = sched.schedule_weight_swap(bogus, version=9)
    while sched.step():
        pass
    assert ev.is_set() and not sched.last_swap["ok"]
    assert "fault-injection" in sched.last_swap["error"]
    assert sched.model_version is None        # gauge never flipped
    assert h.status == "DONE" and h.tokens == oracle
    assert _counter("serving_weight_swaps_total",
                    status="failed") == failed_before + 1


@pytest.mark.slow
def test_worker_fleet_swap_verb_flips_every_version(tiny, tmp_path):
    """The SWAP verb end-to-end over the fabric: router pushes one
    committed checkpoint into a prefill+decode fleet; every worker
    reports ok + the new version, traffic before/after completes."""
    ckpt = str(tmp_path / "ckpt" / "step-0003")
    assert save_swap_checkpoint(tiny.state_dict(), ckpt)
    max_new = 4
    prompts = [_prompt(80 + i, 6) for i in range(2)]
    oracle = _reference_streams(tiny, prompts, max_new)

    pw = ServingWorker(*_worker_pair(tiny), role="prefill")
    dw = ServingWorker(*_worker_pair(tiny), role="decode",
                       serving_config=ServingConfig(
                           default_max_new_tokens=max_new))
    fe = DistFrontend([dw.endpoint], [pw.endpoint])
    try:
        r0 = fe.submit(prompts[0], max_new=max_new)
        fe.run(timeout_s=60)
        out = fe.swap_all(ckpt, version=5)
        assert all(rep.get("ok") for rep in out.values()), out
        stats = fe.stats()
        assert {s["version"] for s in stats.values()} == {5}
        r1 = fe.submit(prompts[1], max_new=max_new)
        fe.run(timeout_s=60)
        assert r0.tokens == oracle[tuple(prompts[0])]
        assert r1.status == "DONE"
        assert r1.tokens == oracle[tuple(prompts[1])]  # same weights
    finally:
        fe.close()
        pw.shutdown()
        dw.shutdown()


# -------------------------------------------------- tensor-parallel decode

def test_tp_decode_token_exact_and_compile_once(tiny):
    """Acceptance: the mesh-sharded decode step emits the SAME tokens as
    the single-device paged engine, its decode executable compiles
    exactly once, and each of the tp devices holds heads/tp of the KV
    pool (the memory win is real, not cosmetic)."""
    ref = _engine(tiny)
    tp = TensorParallelPagedEngine(
        tiny, TensorParallelEngineConfig(tp=2, **ENGINE_KW))
    prompts = [_prompt(90 + s, 9 + s) for s in range(2)]
    for s, p in enumerate(prompts):
        assert ref.prefill(s, p) == tp.prefill(s, p)
    for _ in range(8):
        ref.ensure_decode_capacity()
        tp.ensure_decode_capacity()
        assert ref.decode().tolist() == tp.decode().tolist()
    assert tp.trace_counts["decode"] == 1, tp.trace_counts
    report = tp.kv_shard_report()
    heads = tiny.cfg.num_heads
    assert len(report) == 2 and set(report.values()) == {heads // 2}, \
        report


@pytest.mark.slow
def test_tp_engine_handoff_and_swap_compose(tiny):
    """The layers compose: a single-device prefill hands its KV to a
    TENSOR-PARALLEL decode engine (adopt re-shards transparently), and
    a hot-swap onto the TP engine re-applies every param's mesh
    sharding."""
    prompt = _prompt(95, 10)
    ref = _engine(tiny)
    stream_ref = [ref.prefill(0, prompt)]
    for _ in range(5):
        ref.ensure_decode_capacity()
        stream_ref.append(int(ref.decode()[0]))

    A = _engine(tiny)
    first = A.prefill(0, prompt)
    ks, vs, plen = A.extract_kv(0)
    tp = TensorParallelPagedEngine(
        tiny, TensorParallelEngineConfig(tp=2, **ENGINE_KW))
    tp.adopt_kv(0, ks, vs, plen, first)
    stream = [first]
    for _ in range(2):
        tp.ensure_decode_capacity()
        stream.append(int(tp.decode()[0]))
    # hot-swap same weights mid-stream: sharding re-applied, stream
    # continues exactly
    tp.swap_params({k: np.asarray(v.numpy())
                    for k, v in tiny.state_dict().items()})
    for _ in range(3):
        tp.ensure_decode_capacity()
        stream.append(int(tp.decode()[0]))
    assert stream == stream_ref
    assert tp.trace_counts["decode"] == 1
    shards = tp._params["blocks.0.attn.qkv.weight"].sharding
    assert not shards.is_fully_replicated, "swap lost the param sharding"


def test_tp_config_validation(tiny):
    with pytest.raises(ValueError, match="divide num_heads"):
        TensorParallelPagedEngine(
            tiny, TensorParallelEngineConfig(tp=3, **ENGINE_KW))
    with pytest.raises(ValueError, match="devices"):
        TensorParallelPagedEngine(
            tiny, TensorParallelEngineConfig(tp=999, **ENGINE_KW))
    cfg = TensorParallelEngineConfig(tp=2, **ENGINE_KW)
    assert type(cfg)(**cfg.as_dict()).tp == 2   # .gencfg round-trip


# ------------------------------------------- multi-process chaos (slow)

def _scrubbed_env(extra=None):
    env = dict(os.environ)
    for k in list(env):
        if (k.startswith(("TPU_", "LIBTPU", "PJRT_"))
                or k in ("JAX_PLATFORM_NAME", "XLA_FLAGS",
                         "JAX_PLATFORMS", "PTN_FAULTS",
                         "PTN_TRACE_EXPORT_DIR")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _ROOT
    env.update(extra or {})
    return env


def _worker_identical_model():
    """The exact model a forked `worker_main --seed 2024` builds —
    reseed immediately before construction so the oracle weights match
    the workers' bit for bit."""
    paddle_tpu.seed(WORKER_SEED)
    m = gpt_tiny()
    m.eval()
    return m


def _spawn_worker(role, index, ep_file, max_new, env_extra=None):
    return subprocess.Popen(
        [sys.executable, "-m",
         "paddle_tpu.serving.distributed.worker_main",
         "--role", role, "--engine", "paged", "--model", "gpt_tiny",
         "--seed", str(WORKER_SEED), "--index", str(index),
         "--engine-config", json.dumps(ENGINE_KW),
         "--serving-config", json.dumps(
             {"default_max_new_tokens": max_new}),
         "--step-interval", "0.03",
         "--endpoint-file", ep_file],
        env=_scrubbed_env(env_extra), cwd=_ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _await_endpoint(proc, ep_file, deadline_s=180):
    deadline = time.time() + deadline_s
    while not os.path.exists(ep_file):
        if proc.poll() is not None:
            _, err = proc.communicate()
            raise RuntimeError(f"worker died:\n{err[-4000:]}")
        if time.time() > deadline:
            proc.kill()
            raise TimeoutError("worker never published its endpoint")
        time.sleep(0.05)
    with open(ep_file) as f:
        return f.read().strip()


@pytest.mark.slow
def test_multiprocess_sigkill_failover_bit_exact_one_trace(tmp_path):
    """THE chaos acceptance run: 1 prefill + 2 decode workers as real
    forked processes, traffic streaming through the router under a
    profiler window. One decode worker is SIGKILLed mid-stream; its
    requests fail over and every stream completes BIT-IDENTICAL to the
    single-process oracle. The surviving processes' chrome exports merge
    with the router's into ONE trace id spanning router, prefill, and
    decode handler spans."""
    from paddle_tpu.profiler import Profiler, export_chrome_tracing

    prompts = [_prompt(100 + i, 6 + (i % 3)) for i in range(4)]
    max_new = 16
    oracle = _reference_streams(_worker_identical_model(), prompts,
                                max_new)
    failover_before = _counter("serving_failover_total")

    trace_dir = str(tmp_path / "traces")
    procs, eps = [], []
    for i, role in enumerate(("prefill", "decode", "decode")):
        ep_file = str(tmp_path / f"ep_{i}")
        procs.append(_spawn_worker(role, i, ep_file, max_new,
                                   {"PTN_TRACE_EXPORT_DIR": trace_dir}))
        eps.append((procs[-1], ep_file))
    try:
        endpoints = [_await_endpoint(p, f) for p, f in eps]
        fe = DistFrontend(endpoints[1:], [endpoints[0]])
        prof = Profiler(timer_only=True,
                        on_trace_ready=export_chrome_tracing(
                            trace_dir, worker_name="router"))
        with prof:
            reqs = [fe.submit(p, max_new=max_new) for p in prompts]
            victims = [r for r in reqs if r.worker == 1]
            assert victims, "nothing placed on the worker we will kill"
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                fe.pump()
                if all(len(r.tokens) >= 3 for r in victims):
                    break
                time.sleep(0.01)
            assert all(len(r.tokens) >= 3 for r in victims), \
                "victim requests never started streaming"
            os.kill(procs[2].pid, signal.SIGKILL)   # decode worker 1
            procs[2].wait(timeout=30)
            fe.run(timeout_s=240)
            for r in reqs:
                assert r.status == "DONE", (r.key, r.status, r.error)
                assert r.tokens == oracle[tuple(r.prompt)], \
                    f"{r.key} diverged from the unkilled oracle"
            assert all(r.failovers >= 1 for r in victims)
            assert _counter("serving_failover_total") > failover_before
            fe.stop_workers()                        # clean exits export
        fe.close()
    finally:
        # let the surviving workers finish their chrome exports before
        # the hard-kill fallback
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)

    # ---- the merged timeline: ONE trace id across three processes ----
    deadline = time.time() + 60
    files = []
    while time.time() < deadline:
        names = os.listdir(trace_dir) if os.path.isdir(trace_dir) else []
        files = [os.path.join(trace_dir, n) for n in names
                 if n.endswith(".json")]
        if any("router" in n for n in names) \
                and any("prefill" in n for n in names) \
                and any("decode" in n for n in names):
            break
        time.sleep(0.1)
    assert len(files) >= 3, f"missing trace exports: {files}"
    merged = tracecontext.merge_chrome_traces(
        sorted(files), str(tmp_path / "merged.json"))
    events = merged["traceEvents"]
    rpc_spans = [e for e in events
                 if e.get("name", "").startswith(("ps.client::",
                                                  "ps.server::"))
                 and (e.get("args") or {}).get("trace_id")]
    verbs = {e["name"].split("::")[1] for e in rpc_spans}
    assert {"PREFILL", "KVPUT", "SUBMIT", "POLL"} <= verbs, verbs
    assert len({e["pid"] for e in rpc_spans}) >= 3, \
        "expected spans from router + prefill + decode processes"
    traces = {e["args"]["trace_id"] for e in rpc_spans}
    assert len(traces) == 1, f"trace ids diverged across hosts: {traces}"


# ---------------------------------------- gray failures (ISSUE 20, slow)

def _decode_fleet(tiny, n=2, max_new=12, step_interval_s=0.03, **fe_kw):
    """n in-process decode workers behind a frontend with a fast health
    sweep cadence (the gray tests want detection inside a test budget,
    not the production default)."""
    workers = [ServingWorker(*_worker_pair(tiny), role="decode",
                             serving_config=ServingConfig(
                                 default_max_new_tokens=max_new),
                             step_interval_s=step_interval_s)
               for _ in range(n)]
    fe_kw.setdefault("health_interval_s", 0.1)
    fe = DistFrontend([w.endpoint for w in workers], **fe_kw)
    return workers, fe


def test_health_replays_and_retry_budget_replays():
    """The health-state and retry-budget decision rules are pure
    functions over their recorded inputs (decisions.v1 replays)."""
    base = {"suspect_threshold": 3.0, "dark_threshold": 8.0,
            "reachable": True}
    assert _dec.replay_health(dict(base, suspicion=0.0)) == "healthy"
    assert _dec.replay_health(dict(base, suspicion=3.5)) == "suspect"
    assert _dec.replay_health(dict(base, suspicion=9.0)) == "dark"
    ok = {"worker": 1, "cost": 1.0, "tokens_available": 2.0}
    assert _dec.replay_retry_budget(ok) is None
    dry = {"worker": 1, "cost": 1.0, "tokens_available": 0.25}
    assert "retry budget exhausted" in _dec.replay_retry_budget(dry)
    assert _dec.replay_migrate({"state": "suspect", "tokens_remaining": 3,
                                "eligible_workers": [0]})
    assert not _dec.replay_migrate({"state": "healthy",
                                    "tokens_remaining": 3,
                                    "eligible_workers": [0]})
    assert not _dec.replay_migrate({"state": "suspect",
                                    "tokens_remaining": 0,
                                    "eligible_workers": [0]})


@pytest.mark.slow
def test_health_and_drain_verbs_roundtrip(tiny):
    """OP_HEALTH reports a worker's vitals read-only; OP_DRAIN flips
    admission off and back on (the enter=None form is a pure query)."""
    w = ServingWorker(*_worker_pair(tiny), role="decode",
                      serving_config=ServingConfig(
                          default_max_new_tokens=4))
    client = ServingShardClient([w.endpoint])
    try:
        h = client.health(0)
        assert h["role"] == "decode"
        assert h["endpoint"] == w.endpoint
        assert h["draining"] is False
        assert h["queue_depth"] >= 0 and h["inflight"] == 0
        assert "last_step_age_s" in h
        assert client.drain(0, enter=True)["draining"] is True
        assert client.health(0)["draining"] is True
        with pytest.raises(PSServerError, match="draining"):
            client.submit(0, "k0", _prompt(1, 5), max_new=2)
        assert client.drain(0)["draining"] is True     # query form
        assert client.drain(0, enter=False)["draining"] is False
        reply = client.submit(0, "k1", _prompt(1, 5), max_new=2)
        assert reply["ok"]
    finally:
        client.close()
        w.shutdown()


@pytest.mark.slow
def test_gray_slow_worker_suspected_migrated_bit_exact(tiny):
    """THE gray-failure acceptance: one decode worker turns 10x slow
    mid-stream (serving.rpc.serve slow, scoped to its endpoint). The
    health plane must suspect it, its streams must migrate off and
    finish BIT-IDENTICAL to the healthy oracle, with suspect-reason
    migrations counted, ZERO deadline misses beyond the healthy
    baseline, and a replay-valid decisions.v1 trail (health + migrate
    records included)."""
    prompts = [_prompt(200 + i, 6) for i in range(4)]
    max_new = 20
    oracle = _reference_streams(tiny, prompts, max_new)
    mig_before = _counter("serving_migrations_total", reason="suspect")
    miss_before = (_counter("serving_deadline_missed_total", where="router")
                   + _counter("serving_deadline_missed_total",
                              where="worker"))

    # a deliberately slow decode pace: the streams must still be
    # mid-flight when the health plane's detection latency (~3 sweeps)
    # has elapsed, so there is something left to migrate
    (d0, d1), fe = _decode_fleet(tiny, max_new=max_new,
                                 step_interval_s=0.15)
    try:
        reqs = [fe.submit(p, max_new=max_new, timeout_s=60)
                for p in prompts]
        victims = [r for r in reqs if r.worker == 1]
        assert victims, "placement never used worker 1"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            fe.pump()
            if all(len(r.tokens) >= 2 for r in victims):
                break
            time.sleep(0.01)
        assert all(len(r.tokens) >= 2 for r in victims)
        mid = {r.key: list(r.tokens) for r in victims}
        # the gray failure: every RPC worker 1 serves now sleeps ~0.3s
        # (its decode loop keeps running — this is NOT a crash)
        faults.arm("serving.rpc.serve", mode="slow", delay_s=0.3,
                   target=d1.endpoint)
        fe.run(timeout_s=120)
        for r in reqs:
            assert r.status == "DONE", (r.key, r.status, r.error)
            assert r.tokens == oracle[tuple(r.prompt)], \
                f"{r.key} diverged after gray migration"
        for r in victims:
            assert r.tokens[:len(mid[r.key])] == mid[r.key], \
                "delivered prefix mutated across migration"
        assert fe._health[1].state != "healthy", \
            "the slow worker was never suspected"
        assert _gauge("serving_worker_state{worker=1}") >= 1.0
        assert _counter("serving_migrations_total",
                        reason="suspect") > mig_before
        miss_after = (_counter("serving_deadline_missed_total",
                               where="router")
                      + _counter("serving_deadline_missed_total",
                                 where="worker"))
        assert miss_after == miss_before, \
            "gray handling cost deadline misses the healthy run had not"
        recs = fe.decision_records()
        errs = _dec.validate_records(recs)
        assert errs == [], errs[:3]
        assert any(r["action"] == "health"
                   and r["outcome"]["state"] != "healthy" for r in recs)
        assert any(r["action"] == "migrate" and r["outcome"]["migrated"]
                   for r in recs)
    finally:
        faults.disarm_all()
        fe.close()
        d0.shutdown()
        d1.shutdown()


@pytest.mark.slow
def test_dead_marked_worker_rejoins_on_health_recovery(tiny):
    """Satellite: _mark_dead is no longer forever — a worker that was
    marked dead (here: a transient poll blip, simulated directly) but
    still answers OP_HEALTH is reinstated by the next sweep, with a
    replayable `health` record carrying reinstated=True."""
    (d0, d1), fe = _decode_fleet(tiny, health_interval_s=0.05)
    try:
        fe._mark_dead(1)
        assert 1 not in fe._live
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and 1 not in fe._live:
            fe.pump()                     # sweeps ride the pump cadence
            time.sleep(0.02)
        assert 1 in fe._live, "healthy worker never reinstated"
        recs = [r for r in fe.decision_records()
                if r["action"] == "health"]
        assert any(r["outcome"].get("reinstated") for r in recs)
        assert _dec.validate_records(recs) == []
        # and placement actually uses it again
        reqs = [fe.submit(_prompt(90 + i, 6), max_new=4, timeout_s=30)
                for i in range(4)]
        fe.run(timeout_s=60)
        assert all(r.status == "DONE" for r in reqs)
        assert {r.worker for r in reqs} == {0, 1}, \
            "reinstated worker never placed"
    finally:
        fe.close()
        d0.shutdown()
        d1.shutdown()


@pytest.mark.slow
def test_probe_sweep_capped_for_suspect_worker(tiny):
    """Satellite: the affinity probe sweep joins each worker's probe at
    the suspicion-scaled hedge deadline — a gray worker's slow
    OP_PREFIX_LOOKUP must not stall placement for its full RPC
    timeout."""
    (d0, d1), fe = _decode_fleet(tiny, prefix_affinity=True)
    try:
        with fe._lock:
            fe._health[1].suspicion = 9.0
            fe._health[1].state = "suspect"
        faults.arm("serving.rpc.serve", mode="slow", delay_s=1.0,
                   target=d1.endpoint)
        t0 = time.monotonic()
        matches = fe._probe_matches([0, 1], _prompt(5, 8), None)
        elapsed = time.monotonic() - t0
        # cap = 2*hedge_delay / (1+9) = ~0.1s at the 0.5s delay ceiling;
        # well under the armed 1.0s sleep (0.5..1.5s jittered)
        assert elapsed < 0.5, \
            f"probe sweep stalled {elapsed:.2f}s behind the gray worker"
        assert matches.get(0) is not None, "healthy probe lost"
    finally:
        faults.disarm_all()
        fe.close()
        d0.shutdown()
        d1.shutdown()


@pytest.mark.slow
def test_rolling_drain_zero_drop_bit_exact(tiny):
    """Acceptance: rolling_drain over a live 2-worker fleet mid-stream
    drops ZERO requests — every stream migrates off the draining worker
    and finishes bit-identical, both workers rejoin placement, and the
    drain/migrate decisions replay valid."""
    prompts = [_prompt(220 + i, 6) for i in range(4)]
    max_new = 16
    oracle = _reference_streams(tiny, prompts, max_new)

    (d0, d1), fe = _decode_fleet(tiny, max_new=max_new)
    try:
        reqs = [fe.submit(p, max_new=max_new, timeout_s=60)
                for p in prompts]
        assert {r.worker for r in reqs} == {0, 1}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            fe.pump()
            if all(len(r.tokens) >= 2 for r in reqs):
                break
            time.sleep(0.01)
        report = fe.rolling_drain(timeout_s=60)
        assert set(report) == {d0.endpoint, d1.endpoint}
        assert all(v["drained"] for v in report.values()), report
        fe.run(timeout_s=120)
        for r in reqs:
            assert r.status == "DONE", (r.key, r.status, r.error)
            assert r.tokens == oracle[tuple(r.prompt)], \
                f"{r.key} diverged across the rolling drain"
        assert fe._draining_workers == set()
        assert fe._live == {0, 1}
        # fresh traffic lands on both restarted workers
        fresh = [fe.submit(_prompt(300 + i, 6), max_new=4, timeout_s=30)
                 for i in range(4)]
        fe.run(timeout_s=60)
        assert all(r.status == "DONE" for r in fresh)
        assert {r.worker for r in fresh} == {0, 1}
        recs = fe.decision_records()
        errs = _dec.validate_records(recs)
        assert errs == [], errs[:3]
        assert any(r["action"] == "drain" for r in recs)
    finally:
        fe.close()
        d0.shutdown()
        d1.shutdown()


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["slow", "flaky", "kill"])
@pytest.mark.parametrize("cell", ["prefill", "decode", "drain"])
def test_chaos_matrix_streams_bit_exact(tiny, mode, cell):
    """Satellite: the {slow, flaky, SIGKILL} x {prefill worker, decode
    worker mid-stream, drain-in-progress} chaos matrix. Every cell must
    hold the same two invariants: streams bit-identical to the unkilled
    oracle, and a decisions.v1 trail that replays valid."""
    prompts = [_prompt(400 + i, 6) for i in range(3)]
    max_new = 10
    oracle = _reference_streams(tiny, prompts, max_new)
    scfg = ServingConfig(default_max_new_tokens=max_new)

    pw = None
    if cell == "prefill":
        pw = ServingWorker(*_worker_pair(tiny), role="prefill",
                           serving_config=scfg)
    d0 = ServingWorker(*_worker_pair(tiny), role="decode",
                       serving_config=scfg, step_interval_s=0.03)
    d1 = ServingWorker(*_worker_pair(tiny), role="decode",
                       serving_config=scfg, step_interval_s=0.03)
    fe = DistFrontend([d0.endpoint, d1.endpoint],
                      [pw.endpoint] if pw else None,
                      health_interval_s=0.1)
    try:
        if cell == "prefill":
            # chaos strikes the prefill pool before any traffic: every
            # remote prefill is slow / errors in-band / the pool is
            # dead — placement degrades to decode-local recompute
            if mode == "kill":
                pw.kill()
            elif mode == "slow":
                faults.arm("serving.rpc.serve", mode="slow",
                           delay_s=0.15, target=pw.endpoint)
            else:
                faults.arm("serving.rpc.serve", mode="flaky", p=1.0,
                           target=pw.endpoint)
            reqs = [fe.submit(p, max_new=max_new, timeout_s=60)
                    for p in prompts]
            fe.run(timeout_s=120)
        else:
            reqs = [fe.submit(p, max_new=max_new, timeout_s=60)
                    for p in prompts]
            victims = [r for r in reqs if r.worker == 1]
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                fe.pump()
                if all(len(r.tokens) >= 2 for r in victims):
                    break
                time.sleep(0.01)
            if mode == "kill":
                d1.kill()
            elif mode == "slow":
                faults.arm("serving.rpc.serve", mode="slow",
                           delay_s=0.25, target=d1.endpoint)
            else:
                faults.arm("serving.rpc.serve", mode="flaky", p=0.4,
                           seed=7, target=d1.endpoint)
            if cell == "drain":
                # the fault lands WHILE worker 1 is being drained
                fe.rolling_drain([1], timeout_s=60)
            fe.run(timeout_s=120)
        for r in reqs:
            assert r.status == "DONE", (r.key, r.status, r.error)
            assert r.tokens == oracle[tuple(r.prompt)], \
                f"{r.key} diverged under {mode} x {cell} chaos"
        errs = _dec.validate_records(fe.decision_records())
        assert errs == [], errs[:3]
    finally:
        faults.disarm_all()
        fe.close()
        for w in (pw, d0, d1):
            if w is not None:
                w.shutdown()
