"""Profile the flagship GPT-350M train step on the attached device.

Decompose the step, name the top time consumers, and A/B the candidate
levers (remat mode, batch, Pallas-vs-XLA attention, flash block sizes).
Prints a markdown table.

Honest-sync rules as bench.py: every timed unit ends in a host fetch of a
value data-dependent on the work; K units per dispatch pay one dispatch
and one fetch.

ONE process runs every experiment, one after another: a chip belongs to
one process, so there is no parent that probes the backend and no child
per experiment. An experiment that fails (out of memory included) prints
its row and the sweep goes on; memory is reclaimed between experiments.

Usage:  python tools/profile_step.py            # full sweep (TPU)
        python tools/profile_step.py --quick    # step decomposition only
        python tools/profile_step.py --one NAME # one experiment
Optionally XPLANE=<dir> captures a profiler trace of the main config for
offline inspection. On a backend that is not a TPU this is a harness
smoke at tiny sizes: it prints wall times of that backend and no MFU.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, args, n=None, k=1, label=""):
    """Median wall time of fn(*args) with a host fetch per call; first call
    compiles (untimed). Returns seconds per unit."""
    import jax
    if n is None:
        n = 8 if jax.default_backend() == "tpu" else 2
    out = fn(*args)
    _sync(out)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        ts.append((time.perf_counter() - t0) / k)
    return float(np.median(ts))


def _sync(out):
    import jax
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        np.asarray(jax.device_get(leaves[0]))


def build(B, S, remat, lr=2e-4, unroll=1, fused_ce=False):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import GPTSpmdConfig, MeshPlan, make_train_step

    on_tpu = jax.default_backend() == "tpu"
    # CPU runs are harness smoke tests, not measurements: tiny model
    cfg = GPTSpmdConfig(
        vocab_size=50304 if on_tpu else 1024,
        max_seq_len=S,
        hidden=1024 if on_tpu else 128,
        layers=24 if on_tpu else 2,
        heads=16 if on_tpu else 4,
        param_dtype="bfloat16" if on_tpu else "float32",
        compute_dtype="bfloat16" if on_tpu else "float32",
        remat={"none": False, "full": True, "dots": "dots",
               "dots+attn": "dots+attn"}[remat],
        scan_unroll=unroll,
        fused_ce_chunks=8 if fused_ce else 0)
    plan = MeshPlan()
    step_fn, init_fn, _ = make_train_step(cfg, plan, learning_rate=lr)
    params, state = init_fn(jax.random.key(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    labs = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    return cfg, plan, step_fn, params, state, toks, labs, n_params


def _mfu_str(mfu):
    return f"MFU {mfu:.3f}" if mfu is not None else "MFU not measured (no TPU)"


def step_mfu(B, S, remat, scan_k=10, n=3, unroll=1, fused_ce=False):
    """Steady-state step time via scan-K dispatch; returns (ms/step, MFU),
    MFU against the attached chip's peak (by device_kind through
    cost_model.analytical.DEVICES; an unknown kind raises) and None off
    the chip."""
    import jax
    import jax.numpy as jnp
    cfg, plan, step_fn, params, state, toks, labs, n_params = \
        build(B, S, remat, unroll=unroll, fused_ce=fused_ce)
    lr = jnp.float32(2e-4)

    def multi(params, state):
        def body(c, _):
            p, s = c
            loss, p, s = step_fn(p, s, toks, labs, lr)
            return (p, s), loss
        (p, s), losses = jax.lax.scan(body, (params, state), None,
                                      length=scan_k)
        return losses[-1], p, s

    fn = jax.jit(multi, donate_argnums=(0, 1))
    loss, params, state = fn(params, state)
    _sync(loss)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        loss, params, state = fn(params, state)
        _sync(loss)
        ts.append((time.perf_counter() - t0) / scan_k)
    dt = float(np.median(ts))
    if jax.devices()[0].platform != "tpu":
        return 1000 * dt, None
    from paddle_tpu.cost_model.analytical import device_spec
    fpt = 6 * n_params + 6 * cfg.layers * S * cfg.hidden
    mfu = B * S * fpt / dt / device_spec().peak_flops
    return 1000 * dt, mfu


def decompose(B, S, remat):
    """Piece timings (fwd, fwd+bwd, blocks, loss) at the bench config."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.gpt_spmd import (_embed, _stage_blocks,
                                              _vocab_parallel_loss)
    cfg, plan, step_fn, params, state, toks, labs, n_params = \
        build(B, S, remat)

    def fwd_loss(params):
        h = _embed(toks, params, cfg, plan)
        h = _stage_blocks(h, params, cfg, plan)
        return _vocab_parallel_loss(h, labs, params, cfg, plan)

    def blocks_only(params, h0):
        return _stage_blocks(h0, params, cfg, plan).astype(jnp.float32).sum()

    h0 = jax.jit(lambda p: _embed(toks, p, cfg, plan))(params)
    rows = []
    rows.append(("forward only", 1000 * timed(jax.jit(fwd_loss), (params,))))
    rows.append(("fwd+bwd", 1000 * timed(
        jax.jit(jax.grad(fwd_loss)), (params,))))
    rows.append(("blocks fwd", 1000 * timed(
        jax.jit(blocks_only), (params, h0))))
    rows.append(("blocks fwd+bwd", 1000 * timed(
        jax.jit(jax.grad(blocks_only, argnums=1)), (params, h0))))

    def loss_only(params, h):
        return _vocab_parallel_loss(h, labs, params, cfg, plan)

    rows.append(("vocab loss fwd+bwd", 1000 * timed(
        jax.jit(jax.grad(loss_only, argnums=1)), (params, h0))))
    return rows


def flash_ab(B, S, H=16, D=64):
    """Pallas flash vs XLA fallback, fwd+bwd, at the bench shape."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import (_pallas_flash_bhsd,
                                                _ref_attention_bhsd)
    scale = 1.0 / D ** 0.5
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), "bfloat16") * 0.5
               for kk in ks)

    def run(f):
        loss = lambda q, k, v: f(q, k, v).astype(jnp.float32).sum()
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return 1000 * timed(g, (q, k, v))

    t_pallas = run(lambda q, k, v: _pallas_flash_bhsd(q, k, v, True, scale))
    t_xla = run(lambda q, k, v: _ref_attention_bhsd(q, k, v, True, scale))
    return t_pallas, t_xla


def flash_blocks_sweep(B, S, H=16, D=64):
    """block_q x block_k sweep for the Pallas kernel; returns sorted list
    and records the winner in the autotune cache."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    scale = 1.0 / D ** 0.5
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), "bfloat16") * 0.5
               for kk in ks)
    results = []
    for bq in (128, 256, 512):
        for bk in (128, 256, 512):
            if bq > S or bk > S:
                continue
            try:
                f = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=True, sm_scale=scale,
                    block_q=bq, block_k=bk).astype(jnp.float32).sum())
                g = jax.jit(jax.grad(
                    lambda q, k, v, bq=bq, bk=bk: flash_attention(
                        q, k, v, causal=True, sm_scale=scale, block_q=bq,
                        block_k=bk).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2)))
                t = 1000 * (timed(f, (q, k, v)) + timed(g, (q, k, v)))
                results.append(((bq, bk), t))
            except Exception as e:                           # noqa: BLE001
                results.append(((bq, bk), f"fail: {str(e)[:60]}"))
    ok = [r for r in results if isinstance(r[1], float)]
    if ok:
        best = min(ok, key=lambda r: r[1])[0]
        try:
            from paddle_tpu.incubate import autotune as at
            at.record_flash_blocks(H, S, D, True, best)
            if at._cache_path():
                print(f"autotune: recorded flash blocks {best} for "
                      f"(B={B},H={H},S={S},D={D}) -> {at._cache_path()}")
            else:
                print(f"autotune: best flash blocks {best} recorded "
                      "IN-MEMORY ONLY — set PADDLE_TPU_AUTOTUNE_CACHE to "
                      "a file path to persist for training runs")
        except Exception as e:                               # noqa: BLE001
            print(f"autotune record failed: {e}")
    return results


def _reclaim():
    """Free device memory between experiments: exception tracebacks pin
    buffers (observed: a failed B=12 run OOM'd every later experiment),
    so clear the last-exception state, the jit caches, and collect."""
    import gc
    import jax
    sys.last_exc = sys.last_value = sys.last_traceback = None
    jax.clear_caches()
    gc.collect()


def section(label, fn):
    """Run one experiment section; a failure prints a row, never aborts
    the sweep."""
    try:
        fn()
    except Exception as e:                                   # noqa: BLE001
        print(f"| {label} | fail: {str(e)[:80]} |")
    finally:
        _reclaim()


def _experiments(B, S, on_tpu, quick):
    """Ordered (name, fn) list; each fn prints its own row(s)."""
    exps = []

    def full(remat, BB=B):
        def run():
            ms, mfu = step_mfu(BB, S, remat, scan_k=10 if on_tpu else 2)
            print(f"| full step B={BB} remat={remat} | {ms:.1f} ms/step, "
                  f"{_mfu_str(mfu)} |", flush=True)
        return run

    # decision-relevant experiments FIRST: the dots+attn A/B, flash A/B
    # and block sweep are the rows that choose the next optimization —
    # none/full/decompose are confirmatory
    exps.append(("dots", full("dots")))
    if not quick:
        if on_tpu:
            # fused-CE A/B first: the race ladder's top rungs depend on it
            def run_fused(BB):
                def run():
                    ms, mfu = step_mfu(BB, S, "dots", scan_k=10,
                                       fused_ce=True)
                    print(f"| full step B={BB} remat=dots fused_ce | "
                          f"{ms:.1f} ms/step, {_mfu_str(mfu)} |", flush=True)
                return run
            exps.append(("b12fused", run_fused(12)))
            exps.append(("b16fused", run_fused(16)))
        exps.append(("dots+attn", full("dots+attn")))
        if on_tpu:
            exps.append(("b12attn", full("dots+attn", 12)))

            def run_unroll():
                ms, mfu = step_mfu(B, S, "dots+attn", scan_k=10, unroll=2)
                print(f"| full step B={B} dots+attn unroll=2 | "
                      f"{ms:.1f} ms/step, {_mfu_str(mfu)} |", flush=True)

            exps.append(("unroll2", run_unroll))

    if on_tpu and not quick:
        def run_flash_ab():
            tp, tx = flash_ab(B, S)
            print(f"| flash fwd+bwd Pallas | {tp:.1f} ms |")
            print(f"| flash fwd+bwd XLA fallback | {tx:.1f} ms |", flush=True)

        exps.append(("flash_ab", run_flash_ab))

        # whole-model A/B through the dispatch switch (not just the kernel)
        def run_xla_attn():
            os.environ["PADDLE_TPU_DISABLE_PALLAS_FLASH"] = "1"
            try:
                ms4, mfu4 = step_mfu(B, S, "dots", scan_k=10)
                print(f"| full step B={B} remat=dots XLA-attention | "
                      f"{ms4:.1f} ms/step, {_mfu_str(mfu4)} |", flush=True)
            finally:
                del os.environ["PADDLE_TPU_DISABLE_PALLAS_FLASH"]

        exps.append(("xla_attn", run_xla_attn))

        def run_sweep():
            for blocks, t in flash_blocks_sweep(B, S):
                t_s = f"{t:.1f} ms" if isinstance(t, float) else t
                print(f"| flash blocks bq={blocks[0]} bk={blocks[1]} | "
                      f"{t_s} |", flush=True)

        exps.append(("sweep", run_sweep))

    def run_xplane():
        """Device-profile closed loop over the main config (ISSUE 9): the
        capture API (observability.deviceprof) replaces the old raw
        jax.profiler.trace dump — the artifact is parsed, JOINED against
        the analytical cost model, and schema-validated on the spot, so
        an on-chip session can never again ship an unreadable capture."""
        xdir = os.environ.get("XPLANE")
        if not xdir:
            return
        import jax.numpy as jnp
        from paddle_tpu.cost_model import analytical
        from paddle_tpu.observability import deviceprof
        cfg, plan, step_fn, params, state, toks, labs, _ = \
            build(B, S, "dots")
        lr = jnp.float32(2e-4)
        loss, params, state = step_fn(params, state, toks, labs, lr)
        _sync(loss)                                    # compile untraced
        spec = analytical.device_spec()     # unknown device_kind raises
        try:
            report = analytical.estimate(
                step_fn, params, state, toks, labs, lr, device=spec)
            per_op = {name: 1e3 * spec.roofline_s(c.flops, c.bytes)
                      for name, c in report.by_op.items()}
        except Exception as e:                           # noqa: BLE001
            per_op = None
            print(f"| xplane cost model | fail: {str(e)[:80]} |", flush=True)
        steps = 3
        ctrl = deviceprof.OneShotCapture(xdir, label="profile_step")
        if not ctrl.start():
            print(f"| xplane | fail: {ctrl.error} |", flush=True)
            return
        for _ in range(steps):
            loss, params, state = step_fn(params, state, toks, labs, lr)
        _sync(loss)                     # sync INSIDE the trace window
        ctrl.stop()
        block = ctrl.finalize(cost_model_per_op=per_op, steps=steps)
        if block.get("state") != "reported":
            print(f"| xplane | fail: {block.get('error', block)} |",
                  flush=True)
            return
        print(f"| xplane | {block['total_device_ms']:.1f} ms device / "
              f"{steps} steps, ratio {block['device_wall_ratio']}, "
              f"artifacts {block['jsonl']} + {block['report']} |",
              flush=True)
        for row in block["top_ops"][:5]:
            eff = row["efficiency"]
            eff_s = f"{eff:.3f}" if eff is not None else "-"
            print(f"| xplane op {row['op'][:40]} | "
                  f"{row['measured_ms_per_step']:.3f} ms/step, "
                  f"eff {eff_s} |", flush=True)

    if os.environ.get("XPLANE"):
        exps.append(("xplane", run_xplane))

    # confirmatory experiments last (see ordering note above)
    if not quick:
        for remat in ("none", "full"):
            exps.append((remat, full(remat)))
        if on_tpu:
            exps.append(("b12", full("dots", 12)))

    def run_decompose():
        for name, ms_i in decompose(B, S, "dots"):
            print(f"| {name} | {ms_i:.1f} ms |", flush=True)

    exps.append(("decompose", run_decompose))
    return exps


def main():
    quick = "--quick" in sys.argv
    one = sys.argv[sys.argv.index("--one") + 1] if "--one" in sys.argv \
        else None

    import jax

    import paddle_tpu  # noqa: F401
    from paddle_tpu.framework import compile_cache
    compile_cache.place()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    B, S = (8, 1024) if on_tpu else (2, 128)
    exps = _experiments(B, S, on_tpu, quick)
    if one is not None:
        section(one, dict(exps)[one])
        return
    print(f"## profile_step on {dev.platform} / {dev.device_kind} "
          f"(B={B}, S={S})\n", flush=True)
    print("| experiment | result |")
    print("|---|---|", flush=True)
    for name, fn in exps:
        section(name, fn)


if __name__ == "__main__":
    main()
