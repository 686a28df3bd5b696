"""Persistent compile cache + AOT serving warmup (ISSUE 8).

The load-bearing properties:

  - a process (or engine) restarted against a warm cache performs ZERO
    fresh compilations for the serving executable set — proven by the
    engine trace counters staying 0 (they tick only when jax traces)
    plus compile_cache hits (test_engine_restart_zero_compiles,
    test_cold_predictor_serves_warm_with_zero_compiles,
    test_spec_engine_restart_zero_compiles);
  - cache corruption in every flavor (torn write via fault injection,
    SIGKILL inside the commit window, post-commit truncation, version
    skew) degrades to a miss-and-recompile — never a crash, never a
    wrong executable;
  - `device.clear_op_cache()` is coherent across tiers: a cleared
    in-memory cache cannot resurrect a pre-clear persistent entry.

Crash cases reuse the test_checkpoint.py kill-window pattern and the
`observability/faults.py` `checkpoint.write` site, which fires inside
`ckpt_commit.atomic_commit` — the same protocol cache entries commit
through.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.device as device
from paddle_tpu.framework import ckpt_commit
from paddle_tpu.framework import compile_cache as cc
from paddle_tpu.observability import faults
from paddle_tpu.serving import EngineConfig, GenerationEngine

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    faults.disarm_all()
    cc.detach()


def _mul_add(x, y):
    return x * y + 1.0


# ------------------------------------------------------------ fundamentals

def test_cached_jit_roundtrip_and_stats(tmp_path):
    import jax.numpy as jnp
    cache = cc.CompileCache(str(tmp_path))
    a, b = jnp.ones((4, 4)), jnp.full((4, 4), 2.0)
    f1 = cc.cached_jit(_mul_add, "t.f", static_sig={"v": 1}, cache=cache)
    r1 = np.asarray(f1(a, b))
    assert cache.stats == {"hits": 0, "misses": 1, "bypass": 0,
                           "corrupt": 0, "uncacheable": 0, "evicted": 0}
    assert len(cache.entries()) == 1
    # a FRESH CachedFunction (fresh jit, as in a restarted process)
    # deserializes instead of compiling
    f2 = cc.cached_jit(_mul_add, "t.f", static_sig={"v": 1}, cache=cache)
    np.testing.assert_array_equal(np.asarray(f2(a, b)), r1)
    assert cache.stats["hits"] == 1 and cache.stats["misses"] == 1
    # a different static signature is a different program
    f3 = cc.cached_jit(_mul_add, "t.f", static_sig={"v": 2}, cache=cache)
    assert f3.warm(a, b) == "miss"
    # a different aval signature too
    assert f2.warm(jnp.ones((2, 2)), jnp.ones((2, 2))) == "miss"
    # no cache anywhere: transparently plain jit
    f4 = cc.cached_jit(_mul_add, "t.f", static_sig={"v": 1})
    assert f4.warm(a, b) == "off"
    np.testing.assert_array_equal(np.asarray(f4(a, b)), r1)


def test_lowering_mode_is_content_addressed(tmp_path):
    import jax.numpy as jnp
    cache = cc.CompileCache(str(tmp_path))
    a = jnp.ones((3, 3))
    cc.cached_jit(_mul_add, "op.x", key_mode="lowering", cache=cache)(a, a)
    before = cache.stats["hits"]
    # a DIFFERENT python callable with the SAME program content hits
    other = cc.cached_jit(lambda x, y: x * y + 1.0, "op.x",
                          key_mode="lowering", cache=cache)
    other(a, a)
    assert cache.stats["hits"] == before + 1
    # a semantically different program misses
    changed = cc.cached_jit(lambda x, y: x * y + 2.0, "op.x",
                            key_mode="lowering", cache=cache)
    assert changed.warm(a, a) == "miss"


# ------------------------------------------------- op-cache tier coherence

def test_eager_op_runners_use_persistent_tier(tmp_path):
    cc.attach(str(tmp_path))
    device.clear_op_cache()            # drop pre-test runners; stamp is
    cc.active()._min_ts = 0.0          # reset so this test sees its writes
    t = paddle.to_tensor(np.arange(6.0, dtype=np.float32))
    base = dict(cc.active().stats)
    r = (t * 3.0)
    np.testing.assert_array_equal(r.numpy(), np.arange(6.0) * 3.0)
    assert cc.active().stats["misses"] == base["misses"] + 1
    assert any(e.startswith("op.") for e in cc.active().entries())
    # a fresh runner for the same op (in-memory cache cleared, stamp
    # bypassed for entries already re-committed AFTER the clear) hits
    stamp = cc.active()._min_ts
    device.clear_op_cache()
    assert cc.active()._min_ts > stamp


def test_clear_op_cache_cannot_resurrect_stale_entry(tmp_path):
    """Satellite regression: after clear_op_cache(), a persistent entry
    committed BEFORE the clear must not be served again in this process
    (in-memory clear + persistent bypass are one coherent operation)."""
    cc.attach(str(tmp_path))
    device.clear_op_cache()            # fresh runners; then re-open the
    cc.active()._min_ts = 0.0          # stamp so this test's writes serve
    t = paddle.to_tensor(np.ones(4, np.float32))
    _ = (t + 7.0)
    stats0 = dict(cc.active().stats)
    n_entries = len(cc.active().entries())
    assert n_entries >= 1
    device.clear_op_cache()
    _ = (t + 7.0)                      # same op identity, post-clear
    stats1 = dict(cc.active().stats)
    # served as a bypass-miss and recompiled — NOT a hit on the old entry
    assert stats1["hits"] == stats0["hits"]
    assert stats1["bypass"] > stats0["bypass"]
    assert stats1["misses"] > stats0["misses"]
    # the entry was recommitted (fresh timestamp): hits again within the
    # post-clear epoch
    t2 = paddle.to_tensor(np.ones(4, np.float32))
    from paddle_tpu.core import tensor as _ct
    _ct._EAGER_CACHE.clear()           # in-memory only, no invalidate
    _ = (t2 + 7.0)
    assert cc.active().stats["hits"] == stats1["hits"] + 1


# --------------------------------------------------------- crash/corruption

def test_injected_torn_write_never_commits(tmp_path):
    """faults `checkpoint.write` truncate fires inside the entry commit:
    the store fails CONTAINED (warning, no entry), the call still
    returns, and the next lookup recompiles."""
    import jax.numpy as jnp
    cache = cc.CompileCache(str(tmp_path))
    a = jnp.ones((4,))
    faults.arm("checkpoint.write", mode="truncate", nth=1)
    with pytest.warns(UserWarning, match="commit .* failed|not persisted"):
        f = cc.cached_jit(_mul_add, "t.torn", cache=cache)
        out = np.asarray(f(a, a))      # computes fine despite the tear
    np.testing.assert_array_equal(out, np.ones(4) * 2.0)
    assert cache.entries() == []
    assert cache.stats["uncacheable"] == 1
    faults.disarm_all()
    # with the fault gone the same program commits and then hits
    f2 = cc.cached_jit(_mul_add, "t.torn", cache=cache)
    f2(a, a)
    assert len(cache.entries()) == 1
    f3 = cc.cached_jit(_mul_add, "t.torn", cache=cache)
    assert f3.warm(a, a) == "hit"


def test_sigkill_mid_commit_recovers(tmp_path):
    """Kill -9 inside the commit window (data files written, manifest
    not): the survivor sees no entry — hidden tempdir only — and
    recompiles; the stale tempdir is swept by the next commit."""
    cache_dir = str(tmp_path / "cache")
    script = f"""
import os
import paddle_tpu
from paddle_tpu.framework import compile_cache as cc
import jax.numpy as jnp
cache = cc.CompileCache({cache_dir!r})
f = cc.cached_jit(lambda x: x * 2.0 + 1.0, "t.kill", cache=cache)
print("READY", flush=True)
f(jnp.ones((8,)))                      # commit blocks in the delay window
print("DONE", flush=True)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PTN_FAULTS="checkpoint.write=delay:delay=120:max=1")
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True, env=env,
                            cwd=_ROOT)
    try:
        assert proc.stdout.readline().strip() == "READY"
        # the child is now compiling, then holds the commit open for
        # 120s; give the data files time to land, then kill the window
        deadline = time.time() + 120
        while time.time() < deadline:
            if any(n.startswith(".") for n in
                   os.listdir(cache_dir) if os.path.isdir(
                       os.path.join(cache_dir, n))):
                break
            time.sleep(0.1)
        time.sleep(0.3)                # inside the held-open window
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    # survivor: nothing committed, lookup is a clean miss + recompile
    cache = cc.CompileCache(cache_dir)
    assert cache.entries() == []
    import jax.numpy as jnp
    f = cc.cached_jit(lambda x: x * 2.0 + 1.0, "t.kill", cache=cache)
    out = np.asarray(f(jnp.ones((8,))))
    np.testing.assert_array_equal(out, np.full(8, 3.0))
    assert cache.stats == {"hits": 0, "misses": 1, "bypass": 0,
                           "corrupt": 0, "uncacheable": 0, "evicted": 0}
    assert len(cache.entries()) == 1
    # the dead child's hidden tempdir was swept by the commit
    assert not any(n.startswith(".") and ".tmp." in n
                   for n in os.listdir(cache_dir))


def test_truncated_entry_recovers(tmp_path):
    """Post-commit bit rot: a truncated entry file fails manifest
    verification at load — the entry is deleted and recompiled, the call
    succeeds."""
    import jax.numpy as jnp
    cache = cc.CompileCache(str(tmp_path))
    a = jnp.ones((5,))
    cc.cached_jit(_mul_add, "t.rot", cache=cache)(a, a)
    (entry,) = cache.entries()
    victim = None
    for name in os.listdir(str(tmp_path / entry)):
        if name != ckpt_commit.MANIFEST:
            victim = os.path.join(str(tmp_path / entry), name)
            break
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.truncate(size // 2)
    f2 = cc.cached_jit(_mul_add, "t.rot", cache=cache)
    with pytest.warns(UserWarning, match="failed verification"):
        out = np.asarray(f2(a, a))
    np.testing.assert_array_equal(out, np.full(5, 2.0))
    assert cache.stats["corrupt"] == 1
    # recompiled and recommitted: a third function hits cleanly
    assert len(cache.entries()) == 1
    f3 = cc.cached_jit(_mul_add, "t.rot", cache=cache)
    assert f3.warm(a, a) == "hit"


def test_version_skew_entry_rejected(tmp_path):
    """Defense in depth: an entry whose manifest verifies but whose meta
    names another jax build reads as a miss (deleted + recompiled),
    never a deserialization of a foreign executable."""
    import jax.numpy as jnp
    cache = cc.CompileCache(str(tmp_path))
    a = jnp.ones((3,))
    cc.cached_jit(_mul_add, "t.skew", cache=cache)(a, a)
    (entry,) = cache.entries()
    full = str(tmp_path / entry)
    with open(os.path.join(full, cc.ENTRY_META)) as f:
        meta = json.load(f)
    meta["jax_version"] = "0.0.0"
    # recommit THROUGH the protocol so the manifest stays valid — only
    # the meta lies
    with ckpt_commit.atomic_commit(full) as tmp:
        with open(os.path.join(tmp, cc.ENTRY_META), "w") as f:
            json.dump(meta, f)
        import shutil
        for name in os.listdir(full):
            if name not in (cc.ENTRY_META, ckpt_commit.MANIFEST):
                shutil.copy2(os.path.join(full, name),
                             os.path.join(tmp, name))
    f2 = cc.cached_jit(_mul_add, "t.skew", cache=cache)
    with pytest.warns(UserWarning, match="failed to load"):
        out = np.asarray(f2(a, a))
    np.testing.assert_array_equal(out, np.full(3, 2.0))
    assert cache.stats["corrupt"] == 1


# ------------------------------------------------------ serving AOT warmup

def test_engine_restart_zero_compiles(tmp_path):
    """The acceptance core, engine-level: a second engine over a warm
    cache deserializes its whole executable set — trace counters stay 0
    through precompile AND live serving, and tokens are exact."""
    from paddle_tpu.text.models import gpt_tiny
    model = gpt_tiny()
    model.eval()
    mk = lambda: EngineConfig(slots=2, max_len=32,  # noqa: E731
                              compile_cache_dir=str(tmp_path))
    e1 = GenerationEngine(model, mk())
    rep = e1.precompile()
    assert set(rep) == set(e1.executable_names())
    assert all(v == "miss" for v in rep.values())
    assert e1.trace_counts["decode"] == 1

    e2 = GenerationEngine(model, mk())
    rep2 = e2.precompile()
    assert all(v == "hit" for v in rep2.values()), rep2
    assert e2.trace_counts["decode"] == 0
    assert e2.trace_counts["prefill"] == {}
    assert e2.compile_cache.stats["misses"] == 0

    prompt = np.random.RandomState(3).randint(0, model.cfg.vocab_size, 6)
    t1 = [e1.prefill(0, prompt)]
    t2 = [e2.prefill(0, prompt)]
    for _ in range(4):
        t1.append(int(e1.decode()[0]))
        t2.append(int(e2.decode()[0]))
    assert t1 == t2
    # the proof the ISSUE names: zero fresh compilations at serve time
    assert e2.trace_counts["decode"] == 0
    assert e2.trace_counts["prefill"] == {}
    assert e2.compile_cache.stats["hits"] >= 2


def test_spec_engine_restart_zero_compiles(tmp_path):
    """The speculative set (draft decode/prefill + the [slots, γ+1]
    verify) rides the same cache: a restarted spec engine deserializes
    ALL of it and decodes bit-identically with zero traces."""
    from paddle_tpu.serving import SpecDecodeConfig, SpeculativeEngine
    from paddle_tpu.text.models import gpt_tiny
    model = gpt_tiny()
    model.eval()
    mk = lambda: SpecDecodeConfig(  # noqa: E731
        slots=2, max_len=32, block_size=8, gamma=2, draft_layers=1,
        compile_cache_dir=str(tmp_path))
    e1 = SpeculativeEngine(model, mk())
    rep1 = e1.precompile()
    assert set(rep1) == set(e1.executable_names())
    assert all(v == "miss" for v in rep1.values()), rep1

    e2 = SpeculativeEngine(model, mk())
    rep2 = e2.precompile()
    assert all(v == "hit" for v in rep2.values()), rep2
    for k in ("decode", "draft_decode", "spec_verify"):
        assert e2.trace_counts[k] == 0
    assert e2.trace_counts["prefill"] == {}
    assert e2.trace_counts["draft_prefill"] == {}

    prompt = [3, 1, 4, 1, 5]
    e1.prefill(0, prompt)
    e2.prefill(0, prompt)
    t1, _ = e1.decode_many()
    t2, _ = e2.decode_many()
    np.testing.assert_array_equal(t1, t2)
    for k in ("decode", "draft_decode", "spec_verify"):
        assert e2.trace_counts[k] == 0
    assert e2.trace_counts["prefill"] == {}
    assert e2.trace_counts["draft_prefill"] == {}
    assert e2.compile_cache.stats["misses"] == 0


def test_cold_predictor_serves_warm_with_zero_compiles(tmp_path):
    """Process-restart acceptance: a builder PROCESS precompiles the
    artifact's executable set; this (restarted) process loads a cold
    Predictor whose engine never traces — compile_cache hits are the
    only source of executables — and generates token-exactly."""
    artifact = str(tmp_path / "gpt")
    script = f"""
import paddle_tpu
from paddle_tpu.serving import EngineConfig, save_for_generation
from paddle_tpu.text.models import gpt_tiny
m = gpt_tiny(); m.eval()
rep = save_for_generation(m, {artifact!r},
                          engine_config=EngineConfig(slots=2, max_len=32),
                          precompile=True)
assert all(v == "miss" for v in rep.values()), rep
print("BUILT", len(rep), flush=True)
"""
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=420,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith("BUILT")

    from paddle_tpu.inference import Config, create_predictor
    pred = create_predictor(Config(artifact + ".pdmodel",
                                   artifact + ".pdiparams"))
    engine = pred._gen_sched.engine
    assert engine.trace_counts["decode"] == 0
    assert engine.trace_counts["prefill"] == {}
    assert engine.compile_cache.stats["misses"] == 0
    assert engine.compile_cache.stats["hits"] >= 2
    got = pred.generate([[5, 6, 7, 8]], max_new_tokens=4)[0]
    # still zero compiles after serving real requests
    assert engine.trace_counts["decode"] == 0
    assert engine.trace_counts["prefill"] == {}
    # never a wrong executable: token-exact vs a cache-free engine over
    # the same loaded weights
    ref = GenerationEngine(engine._model, EngineConfig(slots=2, max_len=32))
    want = [ref.prefill(0, [5, 6, 7, 8])]
    for _ in range(3):
        want.append(int(ref.decode()[0]))
    assert got == want
    # explicit engine kwargs still win over the recorded engine: the
    # auto-built scheduler is replaced, not silently kept
    got2 = pred.generate([[5, 6]], max_new_tokens=2, slots=3, max_len=16)
    assert pred._gen_sched.engine.config.slots == 3
    assert len(got2[0]) == 2


def test_gencfg_records_executable_set(tmp_path):
    """The sidecar carries the serving record even without precompile,
    so any later loader knows the full executable set."""
    from paddle_tpu.serving import save_for_generation
    from paddle_tpu.text.models import gpt_tiny
    m = gpt_tiny()
    m.eval()
    path = str(tmp_path / "gpt")
    save_for_generation(m, path,
                        engine_config=EngineConfig(slots=2, max_len=32))
    with open(path + ".gencfg") as f:
        meta = json.load(f)
    assert meta["serving"]["engine"] == "dense"
    assert meta["serving"]["config"]["slots"] == 2
    assert "decode" in meta["serving"]["executables"]
    assert "prefill[32]" in meta["serving"]["executables"]
    # precompile without an engine_config is a loud error
    with pytest.raises(ValueError, match="engine_config"):
        save_for_generation(m, path, precompile=True)


def test_retention_cap_evicts_lru_by_mtime(tmp_path):
    """ISSUE 10 satellite (ROADMAP item 5 retention debt): a capped
    cache keeps at most max_entries committed entries, sweeping
    least-recently-USED at commit time — lookups refresh recency, the
    just-committed entry is never evicted, and evicted entries simply
    recompile (miss, never a crash)."""
    import jax.numpy as jnp
    cache = cc.CompileCache(str(tmp_path), max_entries=3)
    a = jnp.ones((4, 4))
    fns = [cc.cached_jit(_mul_add, "t.ret", static_sig={"v": i},
                         cache=cache) for i in range(5)]
    for i in range(3):
        fns[i](a, a)
        time.sleep(0.05)               # distinct mtimes
    assert len(cache.entries()) == 3
    # touch v=0 via a warm lookup from a fresh function: it becomes the
    # most recently USED even though it was committed first
    f0 = cc.cached_jit(_mul_add, "t.ret", static_sig={"v": 0},
                       cache=cache)
    assert f0.warm(a, a) == "hit"
    time.sleep(0.05)
    fns[3](a, a)                       # 4th entry: evicts v=1 (LRU)...
    time.sleep(0.05)
    fns[4](a, a)                       # 5th: evicts v=2
    assert len(cache.entries()) == 3
    assert cache.stats["evicted"] == 2
    # v=0 survived BECAUSE the lookup refreshed it; v=1/v=2 are gone
    assert cc.cached_jit(_mul_add, "t.ret", static_sig={"v": 0},
                         cache=cache).warm(a, a) == "hit"
    assert cc.cached_jit(_mul_add, "t.ret", static_sig={"v": 1},
                         cache=cache).warm(a, a) == "miss"
    # the flag wires the same cap into flag-built caches
    from paddle_tpu.framework import flags as _flags
    _flags.set_flags({"FLAGS_compile_cache_max_entries": 7})
    try:
        assert cc.CompileCache(str(tmp_path)).max_entries == 7
    finally:
        _flags.set_flags({"FLAGS_compile_cache_max_entries": 0})
    assert cc.CompileCache(str(tmp_path)).max_entries == 0  # unlimited


# ------------------------------------------------ placement (ISSUE 21)

def test_entry_records_devices_and_reloads_on_them(tmp_path):
    """An executable reloads onto the devices it was compiled for — one
    device of eight, or a four-device mesh — never onto "every device of
    the backend" (the jax default that broke every warm load on a
    multi-device host)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cache = cc.CompileCache(str(tmp_path))
    x1 = jnp.arange(8.0)
    mesh = Mesh(np.asarray(jax.devices()[2:6]), ("x",))
    x4 = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("x")))
    for name, x, want_ids in (("one", x1, [0]), ("four", x4, [2, 3, 4, 5])):
        assert cc.cached_jit(_mul_add, name, static_sig=name,
                             cache=cache).warm(x, x) == "miss"
        entry, = [e for e in cache.entries() if e.startswith(name)]
        with open(os.path.join(cache.path, entry, cc.ENTRY_META)) as f:
            assert json.load(f)["device_ids"] == want_ids
        again = cc.cached_jit(_mul_add, name, static_sig=name, cache=cache)
        assert again.warm(x, x) == "hit"
        np.testing.assert_allclose(np.asarray(again(x, x)),
                                   np.arange(8.0) ** 2 + 1.0)
    assert cache.stats["corrupt"] == 0


def test_cache_root_unset_is_the_checkout(monkeypatch):
    """With $JAX_COMPILATION_CACHE_DIR unset the one root is
    <checkout>/.jax_cache (git-ignored), and the executable entries
    default to a sub-directory of it."""
    monkeypatch.delenv(cc.CACHE_ENV)
    assert cc.cache_root() == os.path.join(_ROOT, ".jax_cache")
    assert cc.default_dir() == os.path.join(_ROOT, ".jax_cache",
                                            "executables")
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_PLACEMENT_SCRIPT = r"""
import json, os, sys
import numpy as np
import jax
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework import compile_cache as cc
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.static import InputSpec
after_import = jax.config.jax_compilation_cache_dir
net = nn.Sequential(nn.Linear(8, 4))
path = os.path.join(sys.argv[1], "net")
paddle.jit.save(net, path, input_spec=[InputSpec([2, 8], "float32")])
pred = create_predictor(Config(path + ".pdmodel", path + ".pdiparams"))
pred.run([np.ones((2, 8), np.float32)])
print(json.dumps({"after_import": after_import,
                  "after_predictor": jax.config.jax_compilation_cache_dir,
                  "root": cc.cache_root()}))
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_one_variable_places_every_cache(tmp_path, env_set):
    """$JAX_COMPILATION_CACHE_DIR set: jax's cache directory is that value
    after `import paddle_tpu` and still after building a Predictor (code
    sets no directory), and nothing is written beside the artifact.
    Unset: building the Predictor places the cache at the checkout
    default — here an exported copy of the package, so the test never
    writes into the real checkout."""
    art = tmp_path / "artifact"
    art.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if env_set:
        cache = str(tmp_path / "cache")
        env[cc.CACHE_ENV] = cache
        root, want_import = _ROOT, cache
    else:
        # a stand-in checkout: <copy>/paddle_tpu -> the package
        env.pop(cc.CACHE_ENV)
        root = str(tmp_path / "checkout")
        os.makedirs(root)
        import shutil
        shutil.copytree(os.path.join(_ROOT, "paddle_tpu"),
                        os.path.join(root, "paddle_tpu"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        cache, want_import = os.path.join(root, ".jax_cache"), None
    env["PYTHONPATH"] = root
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT, str(art)],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["after_import"] == want_import
    assert rec["after_predictor"] == cache
    assert rec["root"] == cache
    assert os.listdir(cache), "the placed cache directory was never written"
    # nothing beside the artifact: no _xla_cache / _compile_cache sibling
    assert sorted(os.listdir(art)) == ["net.pdiparams", "net.pdmodel"]


_RESERIALIZE_SCRIPT = r"""
import json, sys
import jax.numpy as jnp
from paddle_tpu.framework import compile_cache as cc
cc.place()
cache = cc.CompileCache(sys.argv[1])
f = cc.cached_jit(lambda x, y: jnp.where(x > 0, x + y, y) @ y.T, "reser",
                  static_sig="reser", cache=cache)
x = jnp.ones((64, 64))
print(json.dumps({"out": float(f(x, x).sum()), "stats": cache.stats,
                  "entries": cache.entries()}))
"""


def test_executable_from_jax_cache_is_not_stored_again(tmp_path):
    """An executable that jax's own persistent cache SERVED is not
    re-serialized into this cache: on XLA:CPU such a payload loads and
    then fails at its first call ("Function ... not found"). Reached when
    the entry key moved (a source edit) while the program did not."""
    import shutil
    entries = str(tmp_path / "entries")

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _RESERIALIZE_SCRIPT, entries],
            capture_output=True, text=True, timeout=240, cwd=_ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    fresh = run()                       # compiles; both caches store it
    assert len(fresh["entries"]) == 1 and fresh["stats"]["misses"] == 1
    shutil.rmtree(entries)              # as if the entry key had moved
    served = run()                      # jax's cache serves the compile
    assert served["entries"] == [] and served["stats"]["uncacheable"] == 1
    assert fresh["out"] == served["out"]
