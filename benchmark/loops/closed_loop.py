"""kind "closed_loop": N clients over `Scheduler.submit` / `Scheduler.step`.

Each client submits its next request the moment its last one ends, before
the next `step()`; one thread drives everything, as the scheduler is built
to be driven. The harness stamps a token when the `step()` that produced it
returns, on its own clock. After the window closes no client submits, and
the requests in flight are stepped to their end: a first token that comes
late is late, not missing.
"""
import gc
import statistics
import time

import numpy as np


def build_model(config, seed):
    """The program's GPT with the benchmark's weights: the model is built
    the program's way (its eager initialisers run; only the program can
    shorten that), then every parameter is replaced by the seed's, made on
    the device in one jitted call."""
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    from benchmark.harness import weights

    paddle_tpu.seed(0)
    model = GPT(GPTConfig(**config["program"]["gpt_config"]))
    model.eval()
    params = dict(model.named_parameters())
    shapes = {n: tuple(p.shape) for n, p in params.items()}
    for p in params.values():                 # free the eager weights first
        p._data = jnp.zeros((), jnp.float32)
    made = weights.make(config, seed, config["dtype"]["param"],
                        layout="named")
    assert set(made) == set(shapes), sorted(set(made) ^ set(shapes))
    for n, p in params.items():
        assert tuple(made[n].shape) == shapes[n], (n, made[n].shape)
        p._data = made[n]
    return model


def timed(fn, sink, clock):
    def wrapper(*a, **kw):
        t = clock()
        try:
            return fn(*a, **kw)
        finally:
            sink.append((t, clock() - t))
    return wrapper


def reference_logits_fn(config, mode):
    import jax

    from benchmark.harness import reference_gpt
    return jax.jit(lambda p, ids: reference_gpt.logits(
        p, ids, heads=config["n_head"], layout=config["qkv_layout"],
        eps=config["layer_norm_epsilon"], mode=mode))


def reference_rows(config, params, fn, prompt, tokens, pad_to=256):
    """The reference's logits at the positions that produced `tokens`: one
    forward over prompt + served tokens (right-padded; causal, so padding
    cannot reach the rows read)."""
    import jax.numpy as jnp
    ids = list(prompt) + list(tokens[:-1])
    n = -(-len(ids) // pad_to) * pad_to
    padded = np.zeros((1, n), np.int32)
    padded[0, :len(ids)] = ids
    lg = fn(params, jnp.asarray(padded))
    first = len(prompt) - 1
    return np.asarray(lg[0, first:first + len(tokens)], np.float32)


def served_gap_readings(config, seed, samples, modes=("float32",)):
    """The widest gap by which a served token's logit lies below the
    reference's best, and the mean gap (steadier: it grows with the square
    of the error), over `samples` = [(prompt, tokens)]. With a lower-
    precision mode beside "float32", also the gaps of the token that mode
    puts first at the same positions (the control's readings)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import correctness, weights
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32),
        weights.make(config, seed, config["dtype"]["param"]))
    fns = {m: reference_logits_fn(config, m) for m in modes}
    gaps = {m: [] for m in modes}
    for prompt, tokens in samples:
        ref = reference_rows(config, params, fns["float32"], prompt, tokens)
        gaps["float32"].append(correctness.served_token_gaps(ref, tokens))
        for m in modes:
            if m != "float32":
                low = reference_rows(config, params, fns[m], prompt, tokens)
                gaps[m].append(correctness.served_token_gaps(
                    ref, low.argmax(axis=-1)))
    out = {}
    for m, g in gaps.items():
        g = np.concatenate(g)
        out[m] = {"served_logit_gap": float(g.max()),
                  "served_logit_gap_mean": float(g.mean())}
    return out, sum(len(t) for _, t in samples)


def run(ctx):
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.serving import (PagedEngineConfig, PagedGenerationEngine,
                                    Scheduler, ServingConfig)

    from benchmark.harness import correctness, model_flops, traffic_gen
    from benchmark.harness.lastline import memory_peak_bytes

    config, mix = ctx["config"], ctx["traffic"]
    clock = time.perf_counter
    compile_cache.place()
    counter = ctx["compile_counter"]

    # ---- set-up
    model = build_model(config, ctx["seed"])
    ctx["mark"]("model_built")
    engine = PagedGenerationEngine(model, PagedEngineConfig(
        compile_cache_dir=compile_cache.default_dir(),
        **config["program"]["paged_engine_config"]))
    ctx["mark"]("engine_built")
    warm_report = engine.precompile()
    ctx["mark"]("precompiled")
    sched = Scheduler(engine, ServingConfig(
        max_queue=4 * mix["clients"],
        default_max_new_tokens=mix["output_len"]["hi"]))
    traffic = traffic_gen.ClosedLoopTraffic(mix, config["draw_vocab"],
                                            ctx["seed"])
    # run every executable the mix will use once: one request per prefill
    # bucket its sizes fall in, a few decode steps each
    by_bucket = {}
    for plen, _ in traffic.pairs:
        by_bucket.setdefault(engine.bucket_for(plen), plen)
    warm_rng = np.random.default_rng([int(ctx["seed"]), 0x3a3a])
    for plen in by_bucket.values():
        sched.submit(warm_rng.integers(0, config["draw_vocab"],
                                       plen).tolist(), 3)
    while sched.step():
        pass
    ctx["mark"]("warmed")
    samples = {"prefill_s": [], "decode_s": [], "step_s": []}
    if ctx["trace"]:
        engine.prefill = timed(engine.prefill, samples["prefill_s"], clock)
        engine.decode = timed(engine.decode, samples["decode_s"], clock)
    if ctx.get("wrap_engine"):
        ctx["wrap_engine"](engine)
    traces_before = (engine.trace_counts["decode"],
                     dict(engine.trace_counts["prefill"]))
    compiles_before = counter.requests
    setup_s = clock() - ctx["t0"]

    # ---- the window
    clients = [None] * mix["clients"]   # each client's request in flight
    requests = []                       # every request, in submit order

    def submit(i, phase):
        prompt, olen = traffic.next_request()
        t = clock()
        with ctx["span"]("submit"):
            handle = sched.submit(prompt, olen)
        clients[i] = {"prompt": prompt, "want": olen, "submit": t,
                      "stamps": [], "phase": phase, "handle": handle}
        requests.append(clients[i])

    def harvest(now, phase):
        """Stamp the tokens the last step() produced; refill the clients
        whose request ended (no-one submits once `phase` is None)."""
        for i, r in enumerate(clients):
            if r is None:
                continue
            tokens = r["handle"].tokens
            r["stamps"].extend([now] * (len(tokens) - len(r["stamps"])))
            if len(tokens) >= r["want"] or r["handle"].done():
                r["tokens"] = tokens
                clients[i] = None
                if phase is not None:
                    submit(i, phase)

    def drive(until, phase):
        while True:
            a = clock()
            with ctx["span"]("step"):
                sched.step()
            now = clock()
            samples["step_s"].append((a, now - a))
            harvest(now, phase)
            if now >= until:
                return

    t0 = clock()
    t_end = t0 + ctx["seconds"]
    for i in range(len(clients)):
        submit(i, "window")
    drive(t_end, "window")
    compiles_in_window = counter.requests - compiles_before
    record = {}
    if ctx["trace"]:
        # the traced window: the same loop, still under load
        record["trace"] = ctx["capture"](
            lambda: drive(clock() + ctx["trace_seconds"], "trace"))
    guard = clock() + 60.0            # wait for each, a minute if need be
    while any(r is not None for r in clients) and clock() < guard:
        sched.step()
        harvest(clock(), None)
    while sched.step():               # retire what has ended
        if clock() > guard:
            break
    for r in clients:                 # one that never came
        if r is not None:
            r["tokens"] = r["handle"].tokens
    retraced = (engine.trace_counts["decode"] - traces_before[0]) + sum(
        n - traces_before[1].get(b, 0)
        for b, n in engine.trace_counts["prefill"].items())

    # ---- the window's numbers
    in_window = [r for r in requests if r["phase"] == "window"]
    failed = [r for r in in_window if len(r["tokens"]) != r["want"]
              or r["handle"].status != "DONE"]
    ttft = [r["stamps"][0] - r["submit"] for r in in_window if r["stamps"]]
    missing = len(in_window) - len(ttft)
    gaps, out_tokens, prompt_tokens, decode_tokens, pairs = [], 0, 0, 0, 0
    for r in requests:
        n = len(r["prompt"])
        for k, s in enumerate(r["stamps"]):
            if not t0 <= s <= t_end:
                continue
            out_tokens += 1
            if k == 0:
                prompt_tokens += n
                pairs += model_flops.causal_pairs(n)
            else:
                decode_tokens += 1
                pairs += n + k
                if r["stamps"][k - 1] >= t0:
                    gaps.append(s - r["stamps"][k - 1])
    # a request with no first token counts as a miss: it sits beyond every
    # percentile, so the median is taken with it at +infinity
    ttft_all = sorted(ttft) + [float("inf")] * missing

    def in_the_window(stamped):
        return [d for t, d in stamped if t0 <= t <= t_end]

    timed_s = {k: in_the_window(v) for k, v in samples.items()}
    record.update({
        "end_to_end": {
            "setup_s": setup_s,
            "serve_tokens_per_s": out_tokens / ctx["seconds"],
            "ttft_p50_ms": 1e3 * statistics.median(ttft_all),
            "gap_p95_ms": 1e3 * statistics.quantiles(gaps, n=20)[-1],
        },
        "attempted": len(in_window), "failed": len(failed),
        "window_s": ctx["seconds"],
        "samples": {
            "ttft_s": ttft,
            # the scheduler's own stamps, for a test to cross-check against
            "sched_ttft_s": [r["handle"].ttft_s for r in in_window
                             if r["stamps"]],
            **timed_s},
        "counters": {"requests_in_window": len(in_window),
                     "output_tokens": out_tokens,
                     "prompt_tokens": prompt_tokens,
                     "output_tokens_processed": decode_tokens,
                     "context_pairs": pairs, "gaps": len(gaps),
                     "compiles_in_window": compiles_in_window,
                     "retraced_in_window": retraced,
                     "executables": len(warm_report),
                     "prefill_buckets_used": sorted(by_bucket)},
    })
    record["spans"] = {"steps": len(timed_s["step_s"]),
                       **{k: sum(v) for k, v in timed_s.items()}}
    record["memory_peak_bytes"] = memory_peak_bytes()

    # ---- the comparison: a sample of the finished requests, drawn from the
    # seed, the longest among them; once the program's state is freed
    failed_ids = {id(r) for r in failed}
    done = [r for r in in_window if id(r) not in failed_ids]
    rng = np.random.default_rng([int(ctx["seed"]), 0xc4ec])
    picked = []
    if done:
        longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        k = min(mix["check_requests"] - 1, len(rest))
        picked = [longest] + [rest[i] for i in
                              rng.choice(len(rest), k, replace=False)]
    check_samples = [(r["prompt"], r["tokens"]) for r in picked]
    sched.close()
    del sched, engine, model, clients, requests, in_window, done, picked
    gc.collect()
    t_ref = clock()
    readings = {"requests_failed": len(failed),
                "compiles_in_window": compiles_in_window + retraced}
    if check_samples:
        control = ctx.get("control_mode")      # tools/limits.py only
        gap, n_tokens = served_gap_readings(
            config, ctx["seed"], check_samples,
            modes=("float32", control) if control else ("float32",))
        readings.update(gap["float32"])
        if control:
            record["control_readings"] = gap[control]
        record["counters"]["checked_tokens"] = n_tokens
    record["readings"] = readings
    record["reference_s"] = clock() - t_ref
    record["checks"] = correctness.checks_from(
        readings, correctness.load_limits(ctx["cell"]["name"], ctx["tiny"]))
    return record
