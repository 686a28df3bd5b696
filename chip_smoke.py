"""Does the system still start on the chip? One process, no arguments.

    python chip_smoke.py             # on a machine with one TPU chip
    python chip_smoke.py --chips 4   # adds the four-chip phases
    python chip_smoke.py --cpu-tiny  # builder's dry run: gpt_tiny sizes,
                                     # CPU, interpret-mode kernels — NOT a
                                     # chip run, and the only form that
                                     # passes without a chip

Phases run in order and the first failure ends the run with a traceback
and a non-zero exit — nothing is caught and carried past:

  device   jax must report a TPU (else exit at once); versions, native
           runtime, compile-cache directory, peaks by device_kind
  kernels  compiled (not interpreted) flash fwd/dq/dk/dv against the XLA
           reference at the train shape; paged attention against the
           gather oracle at the serve shape, float and int8 pools
  train    parallel.make_train_step at GPT-350M, B=8 S=1024 bf16
           remat=dots: 2 warm-up + 6 steps on one batch, every step ends
           in a host fetch of the loss; loss finite and falling; the
           compiled step contains the Pallas custom call
  serve    text.models.gpt_1p3b through PagedGenerationEngine +
           Scheduler.submit/step: 8 slots x 1024, block 16, f32; 8
           requests of 40..700 prompt tokens, 32 new tokens each; all
           DONE, one decode executable; decode logits against the plain
           forward; then the same requests with attention_impl="kernel"
  cache    a one-device executable stored through framework/compile_cache
           reloads from disk and runs
  multichip_train / multichip_serve (--chips 4) the hybrid plans of
           __graft_entry__ against their one-device goldens and
           GPT-1.3B-width train steps under MeshPlan(sharding=4) (before
           the serve weights exist: the training state fills the chips);
           gpt_1p3b through TensorParallelPagedEngine(tp=4); a sharded
           executable through the cache; after each, every device holds
           bytes

The last line of stdout is one JSON object with exactly two keys,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` —
the device as jax reports it. The line before it is the report, also one
JSON object: per phase its status and wall seconds with compile seconds
apart. The times are smoke times — how long this script took, compile
cache state included — and are not metrics of the system.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

_T0 = time.perf_counter()


def log(msg):
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


class Sizes:
    """What each phase runs at. `full` is the contract; `tiny` is the
    --cpu-tiny dry run of the same code at gpt_tiny size."""

    def __init__(self, tiny):
        self.tiny = tiny
        if tiny:
            self.flash = dict(B=1, H=4, S=128, D=32, dtype="float32")
            self.paged = dict(slots=2, H=4, D=32, block=8, max_len=64)
            self.train = dict(vocab_size=1024, max_seq_len=128, hidden=128,
                              layers=2, heads=4, param_dtype="float32",
                              compute_dtype="float32", remat="dots")
            self.train_batch = 2
            self.serve_model = "gpt_tiny"
            self.serve = dict(slots=2, max_len=64, block_size=8)
            self.prompt_lens = [5, 11, 20, 30]
            self.new_tokens = 4
            self.check_lens = [6, 29]
            self.ref_len = 32
            self.mc_train = self.train
            self.mc_batch = 4
        else:
            self.flash = dict(B=8, H=16, S=1024, D=64, dtype="bfloat16")
            self.paged = dict(slots=8, H=16, D=128, block=16, max_len=1024)
            # GPT-350M, full size
            self.train = dict(vocab_size=50304, max_seq_len=1024,
                              hidden=1024, layers=24, heads=16,
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16", remat="dots")
            self.train_batch = 8
            self.serve_model = "gpt_1p3b"
            self.serve = dict(slots=8, max_len=1024, block_size=16)
            self.prompt_lens = [40, 96, 150, 230, 310, 450, 600, 700]
            self.new_tokens = 32
            self.check_lens = [45, 690]
            self.ref_len = 768
            # GPT-1.3B width (hidden 2048, 16 heads, vocab 50304) at full
            # depth: bf16 params + ZeRO-2 f32 m/v/master over 4 chips
            self.mc_train = dict(vocab_size=50304, max_seq_len=1024,
                                 hidden=2048, layers=24, heads=16,
                                 param_dtype="bfloat16",
                                 compute_dtype="bfloat16", remat="dots")
            self.mc_batch = 4       # one sequence a chip: HBM headroom


def rel_err(got, want):
    """max |got - want| over max |want| — one number a tolerance can be
    stated against whatever the tensor's scale."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite values"
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def timed_compile(fn, *args):
    """(compiled, seconds): AOT lower+compile, so compile time is apart
    from run time in every phase."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


class CacheCounter:
    """jax's own persistent-cache hit/miss events, per phase."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.hits, self.misses


# ------------------------------------------------------------------ device

def phase_device(cpu_tiny):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    # exactly what the result line reports: the device as jax sees it
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    if d0.platform != "tpu" and not cpu_tiny:
        sys.exit(f"chip_smoke: jax found no TPU (platform {d0.platform!r}, "
                 f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
                 f"Only --cpu-tiny runs without a chip, and that is not a "
                 f"chip run.")
    import jaxlib

    from paddle_tpu import native
    from paddle_tpu.cost_model.analytical import device_spec
    from paddle_tpu.framework import compile_cache
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    spec = device_spec(d0.device_kind)    # unknown kind: KeyError, not v5e
    cache_dir = compile_cache.place()
    entries = sum(len(files) for _, _, files in os.walk(cache_dir))
    info = {"device": device,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version, "native": bool(native.available()),
            "cache_dir": cache_dir,
            "cache_env": os.environ.get(compile_cache.CACHE_ENV),
            "cache_files_at_start": entries,
            "peaks": {"name": spec.name, "flops": spec.peak_flops,
                      "hbm_bytes_per_s": spec.hbm_bw}}
    log(f"versions/cache/peaks: {info}")
    return info, 0.0


# ----------------------------------------------------------------- kernels

def phase_kernels(sz):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.flash_attention import (_pallas_flash_bhsd,
                                                _ref_attention_bhsd,
                                                flash_blocks)
    from paddle_tpu.ops.pallas.flash_attention import (default_block,
                                                       flash_plan)
    from paddle_tpu.serving import blocks

    out = {}
    compile_s = 0.0
    # --- flash forward / dq / dk / dv against the XLA reference
    f = sz.flash
    B, H, S, D = f["B"], f["H"], f["S"], f["D"]
    scale = 1.0 / D ** 0.5
    bq, bk = flash_blocks(B, H, S, D, True)
    used = (bq or default_block(S), bk or default_block(S))
    plan = flash_plan(S, D, *used, True)
    log(f"flash: shape {(B, H, S, D)} {f['dtype']} causal, blocks {used} "
        f"({'tuned row' if bq else 'kernel default'}); plan: keys resident "
        f"{plan.major_k}, queries resident {plan.major_q}, chunks run "
        f"{plan.chunks_run}/{plan.chunks_total} a head, masked "
        f"{plan.chunks_masked}/{plan.chunks_run}")
    ks = jax.random.split(jax.random.key(7), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, S, D), f["dtype"]) * 0.5
                   for kk in ks)

    def both(attn):
        def fwd_and_grads(q, k, v, do):
            o, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, True, scale),
                             q, k, v)
            return (o,) + vjp(do)
        return fwd_and_grads

    run_p, c1 = timed_compile(both(_pallas_flash_bhsd), q, k, v, do)
    run_r, c2 = timed_compile(both(_ref_attention_bhsd), q, k, v, do)
    compile_s += c1 + c2
    if not sz.tiny:
        assert "tpu_custom_call" in run_p.as_text(), \
            "flash program holds no Pallas custom call"
    got = run_p(q, k, v, do)
    want = run_r(q, k, v, do)
    # bf16 inputs, S-long reductions: the XLA reference rounds its scores
    # to bf16 before the softmax, so a few percent of the largest value is
    # the honest floor; a wrong kernel is O(1) off
    tol = 1e-4 if f["dtype"] == "float32" else 4e-2
    for name, g, w in zip(("fwd", "dq", "dk", "dv"), got, want):
        err = rel_err(g, w)
        out[f"flash_{name}_rel_err"] = round(err, 6)
        assert err < tol, f"flash {name}: rel err {err} >= {tol}"
    out["flash_blocks"] = list(used)
    out["flash_tol"] = tol
    log(f"flash ok: {out}")

    # --- paged attention against the gather oracle, float and int8 pools
    p = sz.paged
    slots, H, D, bs = p["slots"], p["H"], p["D"], p["block"]
    nb = p["max_len"] // bs
    N = slots * nb + 1
    rng = np.random.RandomState(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))
                         .reshape(slots, nb).astype(np.int32))
    kp = jnp.asarray(rng.randn(N, bs, H, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(N, bs, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randint(-127, 128, (N, bs, H, D)).astype(np.int8))
    vc = jnp.asarray(rng.randint(-127, 128, (N, bs, H, D)).astype(np.int8))
    ksc = jnp.asarray((rng.rand(N, H) + 0.1).astype(np.float32))
    vsc = jnp.asarray((rng.rand(N, H) + 0.1).astype(np.float32))
    # f32 operands. The oracle runs at the highest matmul precision; on
    # the TPU both the kernel's MXU dots and XLA's default f32 matmul are
    # one bf16 pass, so the kernel is held to that rounding band (a wrong
    # block walk is O(1) off) and the gather path's own default-precision
    # distance from the same oracle is printed beside it for scale.
    tol = 1e-4 if sz.tiny else 2e-2
    for T in (1, min(128, p["max_len"] // 2)):
        qq = jnp.asarray(rng.randn(slots, T, H, D).astype(np.float32))
        pos = jnp.asarray(rng.randint(0, p["max_len"] - T, slots)
                          .astype(np.int32))
        cases = {
            "float": (blocks.attend_kernel, blocks.attend,
                      (qq, kp, vp, tables, pos)),
            "int8": (blocks.attend_kernel_quant, blocks.attend_quant,
                     (qq, kc, vc, ksc, vsc, tables, pos)),
        }
        for name, (kern, oracle, args) in cases.items():
            run_k, c1 = timed_compile(kern, *args)
            run_d, c2 = timed_compile(oracle, *args)
            with jax.default_matmul_precision("highest"):
                run_o, c3 = timed_compile(oracle, *args)
            compile_s += c1 + c2 + c3
            if not sz.tiny:
                assert "tpu_custom_call" in run_k.as_text(), \
                    "paged program holds no Pallas custom call"
            want = run_o(*args)
            err = rel_err(run_k(*args), want)
            out[f"paged_{name}_T{T}_rel_err"] = round(err, 7)
            out[f"gather_{name}_T{T}_rel_err"] = round(
                rel_err(run_d(*args), want), 7)
            assert err < tol, f"paged {name} T={T}: rel err {err} >= {tol}"
    # the decode kernel (T = 1 over float pools: one call walks every
    # slot's block table and fetches only the blocks it holds) at ragged
    # positions, table entries past a slot's blocks naming a garbage block
    # full of inf/NaN. The CPU tests interpret it; only this compiles the
    # DMA path.
    qq = jnp.asarray(rng.randn(slots, 1, H, D).astype(np.float32))
    pos_np = rng.randint(0, p["max_len"], slots).astype(np.int32)
    pos_np[0], pos_np[-1] = 0, p["max_len"] - 1
    live = (pos_np.astype(np.int64) + bs) // bs
    ragged = jnp.asarray(np.where(np.arange(nb)[None, :] < live[:, None],
                                  np.asarray(tables), 0).astype(np.int32))
    args = (qq, kp.at[0].set(jnp.nan), vp.at[0].set(jnp.inf), ragged,
            jnp.asarray(pos_np))
    run_k, c1 = timed_compile(blocks.attend_kernel, *args)
    with jax.default_matmul_precision("highest"):
        run_o, c2 = timed_compile(blocks.attend, *args)
    compile_s += c1 + c2
    if not sz.tiny:
        assert "paged_attn_decode" in run_k.as_text(), \
            "T=1 over float pools did not reach the decode kernel"
    got = run_k(*args)
    assert bool(jnp.isfinite(got).all()), \
        "decode kernel: the garbage block leaked into the output"
    err = rel_err(got, run_o(*args))
    out["paged_decode_rel_err"] = round(err, 7)
    out["paged_decode_blocks_read_share"] = round(
        float(live.sum()) / (slots * nb), 4)
    assert err < tol, f"paged decode kernel: rel err {err} >= {tol}"
    out["paged_tol"] = tol
    log(f"paged ok: { {k: v for k, v in out.items() if 'flash' not in k} }")
    return out, compile_s


# ------------------------------------------------------------------- train

def train_steps(cfg_kwargs, plan, batch, want_pallas, n_warm=2, n_steps=6):
    """make_train_step -> lower+compile (timed) -> warm-up + steps on one
    fixed batch, each step ending in a host fetch of the loss. On a plan
    of several devices, each must hold bytes while the state is live."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import GPTSpmdConfig, make_train_step

    cfg = GPTSpmdConfig(**cfg_kwargs)
    step_fn, init_fn, mesh = make_train_step(cfg, plan, learning_rate=2e-4)
    params, state = init_fn(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    rng = np.random.RandomState(0)
    S = cfg.max_seq_len
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, S)))
    labs = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, S)))
    lr = jnp.float32(2e-4)
    t0 = time.perf_counter()
    compiled = step_fn.jitted.lower(params, state, toks, labs, lr).compile()
    compile_s = time.perf_counter() - t0
    n_pallas = compiled.as_text().count("tpu_custom_call")
    log(f"train: {n_params / 1e6:.1f}M params, B={batch} S={S} "
        f"{cfg.param_dtype} remat={cfg.remat} plan={plan.dims}; compiled in "
        f"{compile_s:.1f}s, {n_pallas} Pallas custom calls in the step")
    if want_pallas:
        assert n_pallas > 0, \
            "the compiled train step holds no Pallas custom call: the " \
            "XLA reference attention was taken"
    losses = []
    for i in range(n_warm + n_steps):
        # the entry point a user calls; after the AOT compile above this
        # is a persistent-cache load, not a second compilation
        loss, params, state = step_fn(params, state, toks, labs, lr)
        losses.append(float(loss))          # host fetch ends every step
    log(f"train losses: {[round(x, 4) for x in losses]}")
    assert all(np.isfinite(losses)), f"non-finite loss in {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    info = {"params": n_params, "batch": batch, "seq": S,
            "pallas_custom_calls": n_pallas,
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4)}
    if plan.n_devices > 1:
        info["bytes_in_use"] = assert_all_hold_bytes(
            plan.n_devices, f"train {plan.dims}")
    del params, state
    return info, compile_s


def phase_train(sz):
    from paddle_tpu.parallel import MeshPlan
    return train_steps(sz.train, MeshPlan(), sz.train_batch,
                       want_pallas=not sz.tiny)


# ------------------------------------------------------------------- serve

def make_prompts(lens, vocab, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).tolist() for n in lens]


def serve_requests(engine, prompts, new_tokens):
    """The scheduler path a server takes: submit everything, step until
    idle. Every request must end DONE with its full token budget, through
    ONE decode executable (traced here, or loaded from the cache)."""
    from paddle_tpu.serving import Scheduler, ServingConfig

    t0 = time.perf_counter()
    report = engine.precompile()
    compile_s = time.perf_counter() - t0
    sched = Scheduler(engine, ServingConfig(
        max_queue=max(64, len(prompts)),
        default_max_new_tokens=new_tokens))
    handles = [sched.submit(p) for p in prompts]
    steps = 0
    while sched.step():
        steps += 1
        assert steps < 100 * new_tokens, "scheduler did not drain"
    sched.close()
    status = [h.status for h in handles]
    assert all(s == "DONE" for s in status), \
        f"requests not DONE: {[(s, h.error) for s, h in zip(status, handles)]}"
    assert all(len(h.tokens) == new_tokens for h in handles), \
        [len(h.tokens) for h in handles]
    traced = engine.trace_counts["decode"]
    loaded = int(report["decode"] == "hit")
    assert traced + loaded == 1, \
        f"decode executables: {traced} traced + {loaded} loaded, want 1"
    return ([h.tokens for h in handles],
            {"requests_done": len(handles), "scheduler_steps": steps,
             "decode_traced": traced, "decode_loaded": loaded,
             "executables": len(report),
             "executable_cache": dict(engine.compile_cache.stats)},
            compile_s)


def phase_serve(sz, model):
    """`model` comes in from main so the multichip phase serves the same
    weights without building them twice."""
    import gc

    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.nn.layer.layers import functional_call, functional_state
    from paddle_tpu.serving import PagedEngineConfig, PagedGenerationEngine

    vocab = model.cfg.vocab_size
    prompts = make_prompts(sz.prompt_lens, vocab, seed=1)
    out = {"model": sz.serve_model, "layers": model.cfg.num_layers,
           "depth_cut": False, "prompt_lens": sz.prompt_lens,
           "new_tokens": sz.new_tokens}
    compile_s = 0.0
    streams = {}
    captured = None
    for impl in ("gather", "kernel"):
        engine = PagedGenerationEngine(model, PagedEngineConfig(
            attention_impl=impl, capture_logits=True,
            compile_cache_dir=compile_cache.default_dir(), **sz.serve))
        log(f"serve[{impl}]: engine up "
            f"({engine.config.slots} slots x {engine.config.max_len}, "
            f"block {engine.config.block_size}, "
            f"{engine.config.kv_dtype} pools)")
        streams[impl], info, c = serve_requests(engine, prompts,
                                                sz.new_tokens)
        compile_s += c
        out[impl] = info
        log(f"serve[{impl}]: {info}; precompile {c:.1f}s")
        if impl == "gather":
            # decode logits of two fresh prompts (no prefix hit) for the
            # comparison below: prefill writes the K/V, one decode step
            # reads it back — both executables are on the hook
            check = make_prompts(sz.check_lens, vocab, seed=2)
            firsts = [engine.prefill(slot, p)
                      for slot, p in enumerate(check)]
            engine.decode()
            captured = (check, firsts,
                        engine.last_logits[:len(check)].copy())
        del engine
        gc.collect()
    agree = np.mean([a == b for sa, sb in zip(streams["gather"],
                                              streams["kernel"])
                     for a, b in zip(sa, sb)])
    # reported, not asserted: random-init logits are near ties, and two
    # programs may break a tie differently without either being wrong
    out["kernel_vs_gather_token_agreement"] = round(float(agree), 4)

    # plain forward of [prompt + first token], right-padded to one length
    # (causal: padding cannot reach the positions read), same device
    check, firsts, got = captured
    ids = np.zeros((len(check), sz.ref_len), np.int64)
    for i, (p, t0) in enumerate(zip(check, firsts)):
        ids[i, :len(p)] = p
        ids[i, len(p)] = t0
    params, buffers = functional_state(model)

    def plain_forward(params, ids):
        logits, _ = functional_call(model, params, buffers,
                                    args=(Tensor(ids),), train=False)
        return jnp.stack([logits._data[i, len(p)]
                          for i, p in enumerate(check)])

    run_ref, c = timed_compile(plain_forward, params, jnp.asarray(ids))
    compile_s += c
    want = np.asarray(run_ref(params, jnp.asarray(ids)))
    err = rel_err(got, want)
    # f32 weights, but XLA's default f32 matmul on the TPU is one bf16
    # pass, and the two programs round differently through every layer:
    # percents of the largest logit. A wrong cache read is O(1) off.
    tol = 1e-3 if sz.tiny else 5e-2
    out["decode_logits_rel_err_vs_plain_forward"] = round(err, 6)
    out["logits_tol"] = tol
    assert err < tol, f"decode logits vs plain forward: {err} >= {tol}"
    log(f"serve logits ok: rel err {err:.2e} < {tol}; token agreement "
        f"kernel/gather {agree:.3f}")
    return out, compile_s


# ------------------------------------------------------------------- cache

def phase_cache(n_devices):
    """Store -> reload -> run through framework/compile_cache, on the
    devices the executable was compiled for (one chip; and, when there
    are several, a program sharded over all of them). A fresh
    CachedFunction per call, so the second one can only come from disk."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.framework import compile_cache

    cache = compile_cache.CompileCache(compile_cache.default_dir())
    # a constant that moves with the entry key (the source fingerprint):
    # whenever the entry is new, the program is new to jax's own cache
    # too, so this phase always exercises a FRESH store (an executable
    # jax's cache served is, by rule, not stored again)
    salt = int(compile_cache.framework_fingerprint()[:6], 16) / 2.0 ** 24

    def fn(a):
        return jnp.tanh(a) * 2.0 + salt

    def roundtrip(name, x):
        want = np.asarray(fn(x))
        first = compile_cache.cached_jit(fn, name, static_sig=name,
                                         cache=cache).warm(x)
        again = compile_cache.cached_jit(fn, name, static_sig=name,
                                         cache=cache)
        assert again.warm(x) == "hit", f"{name}: second load was not a hit"
        np.testing.assert_allclose(np.asarray(again(x)), want, rtol=1e-6)
        return first

    x = jnp.arange(1024, dtype=jnp.float32).reshape(8, 128) / 1024.0
    out = {"one_device": roundtrip("smoke.cache.one_device", x)}
    if n_devices > 1:
        mesh = Mesh(np.asarray(jax.devices()[:n_devices]), ("x",))
        xs = jax.device_put(x, NamedSharding(mesh, P("x")))
        out[f"{n_devices}_devices"] = roundtrip(
            f"smoke.cache.{n_devices}_devices", xs)
    log(f"cache ok: {out} (first-load state: miss = compiled and stored "
        f"now, hit = found from an earlier run)")
    return out, 0.0


# --------------------------------------------------------------- multichip

def bytes_in_use(n):
    import jax
    stats = [d.memory_stats() for d in jax.devices()[:n]]
    if any(s is None for s in stats):
        return None                # the CPU backend reports no statistics
    return [int(s["bytes_in_use"]) for s in stats]


def assert_all_hold_bytes(n, what):
    used = bytes_in_use(n)
    log(f"{what}: bytes_in_use per device {used}")
    if used is not None:
        assert all(b > 0 for b in used), \
            f"{what}: a device holds nothing: {used}"
    return used


def phase_multichip_train(sz, n):
    """Runs BEFORE the serve model is built: GPT-1.3B-width training
    state fills most of each chip, and the serve weights would sit on
    device 0 beside it."""
    import gc

    import jax

    from paddle_tpu.parallel import MeshPlan

    assert len(jax.devices()) >= n, \
        f"--chips {n} but jax reports {len(jax.devices())} devices"
    # every hybrid plan against its one-device golden — the body of the
    # CPU dry run, on the real devices. The parity tolerance is about the
    # sharded PROGRAM, so f32 matmuls run at full precision on both sides.
    import __graft_entry__
    with jax.default_matmul_precision("highest"):
        __graft_entry__._dryrun_multichip_impl(n)
    gc.collect()
    info, compile_s = train_steps(sz.mc_train, MeshPlan(sharding=n),
                                  sz.mc_batch, want_pallas=not sz.tiny)
    info["hybrid_plans"] = "ok"
    gc.collect()
    return info, compile_s


def phase_multichip_serve(sz, model, n):
    import gc

    from paddle_tpu.framework import compile_cache
    from paddle_tpu.serving.distributed.tp import (
        TensorParallelEngineConfig, TensorParallelPagedEngine)

    engine = TensorParallelPagedEngine(model, TensorParallelEngineConfig(
        tp=n, compile_cache_dir=compile_cache.default_dir(), **sz.serve))
    log(f"serve[tp={n}]: engine up, kv shards {engine.kv_shard_report()}")
    prompts = make_prompts(sz.prompt_lens, model.cfg.vocab_size, seed=1)
    _, info, compile_s = serve_requests(engine, prompts, sz.new_tokens)
    info["bytes_in_use"] = assert_all_hold_bytes(n, f"serve tp={n}")
    del engine
    gc.collect()
    return info, compile_s


# -------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="dry run on the CPU at gpt_tiny size with "
                         "interpret-mode kernels; NOT a chip run")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 adds the four-chip phases")
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        print("chip_smoke --cpu-tiny: NOT A CHIP RUN — CPU backend, "
              "gpt_tiny sizes, interpret-mode kernels. Nothing below is a "
              "device result.", flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1 and "host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()

    phases = {}
    counter = CacheCounter()

    def run(name, fn, *a):
        log(f"== phase {name}")
        hits0, miss0 = counter.snapshot()
        t0 = time.perf_counter()
        info, compile_s = fn(*a)       # a failure propagates: exit != 0
        wall = time.perf_counter() - t0
        hits1, miss1 = counter.snapshot()
        phases[name] = {"status": "ok", "wall_s": round(wall, 2),
                        "compile_s": round(compile_s, 2),
                        "jax_cache_hits": hits1 - hits0,
                        "jax_cache_misses": miss1 - miss0, **info}
        log(f"== phase {name} ok: {wall:.1f}s wall, {compile_s:.1f}s of it "
            f"compile; jax cache {hits1 - hits0} hits / "
            f"{miss1 - miss0} misses")

    run("device", phase_device, args.cpu_tiny)
    device = phases["device"].pop("device")
    sz = Sizes(args.cpu_tiny)

    run("kernels", phase_kernels, sz)
    run("train", phase_train, sz)
    if args.chips > 1:
        run("multichip_train", phase_multichip_train, sz, args.chips)

    import paddle_tpu
    from paddle_tpu.text import models
    paddle_tpu.seed(0)
    t0 = time.perf_counter()
    model = getattr(models, sz.serve_model)()
    model.eval()
    log(f"{sz.serve_model} built in {time.perf_counter() - t0:.1f}s "
        f"({model.num_params() / 1e6:.0f}M params, random init, seed 0)")
    run("serve", phase_serve, sz, model)
    run("cache", phase_cache, args.chips)
    if args.chips > 1:
        run("multichip_serve", phase_multichip_serve, sz, model, args.chips)

    report = {"cpu_tiny": args.cpu_tiny, "chips": args.chips,
              "times": "smoke wall seconds incl. compile-cache state; "
                       "not metrics",
              "total_wall_s": round(time.perf_counter() - _T0, 1),
              "phases": phases}
    print(json.dumps(report), flush=True)
    # the result line: exactly these two keys, nothing after it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
