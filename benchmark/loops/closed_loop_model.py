"""kind "closed_loop_model": `closed_loop.py`'s loop for any model the
configuration file names.

The window, the stamps, the record's keys, the hooks (`wrap_engine`,
`control_mode`) and the checks are closed_loop.py's. What differs: set-up
ends with a run-in (all clients submit, and the loop runs until each one's
first request has ended), so the window measures the steady loop; and the
model and its reference come from where the configuration file says:

    program.model, program.model_config_class   "module:Class"
    program.model_config                         kwargs of the config class
    harness.weights      module under harness/ with `named(config, seed,
                         dtype)` -> {parameter name: array}
    harness.reference    module under harness/ with `served_rows(config,
                         weights module, seed, samples, modes)`
    harness.flops        module under harness/ the readers use

The model is built without materialised weights where its config class
allows (`init_weights`), then takes the seed's (`load_arrays`). So the next
configuration needs a weights module and a reference, and no loop of its
own. Beside closed_loop.py's counters the record carries what the program's
expert layers counted on their spans (`counters["moe"]`).
"""
import gc
import importlib
import statistics
import time

import numpy as np

from benchmark.loops.closed_loop import timed

MOE_KEYS = ("moe_pairs_total", "moe_pairs_local", "moe_experts_hit",
            "moe_expert_max")


def _named(path):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def _harness(config, what):
    return importlib.import_module(
        f"benchmark.harness.{config['harness'][what]}")


def build_model(config, seed):
    """The program's model class with the benchmark's weights, made on the
    device a layer at a time."""
    program = config["program"]
    model = _named(program["model"])(
        _named(program["model_config_class"])(**program["model_config"]))
    model.eval()
    model.load_arrays(_harness(config, "weights").named(
        config, seed, config["dtype"]["param"]))
    return model


def served_gap_readings(config, seed, samples, modes=("float32",)):
    """closed_loop.served_gap_readings over the configuration's own
    reference: the widest and the mean gap by which a served token's logit
    lies below the reference's best; for a lower-precision mode the gaps of
    the token that mode puts first at the same positions."""
    from benchmark.harness import correctness
    rows = _harness(config, "reference").served_rows(
        config, _harness(config, "weights"), seed, samples, modes)
    gaps = {m: [] for m in modes}
    for i, (_, tokens) in enumerate(samples):
        ref = rows["float32"][i]
        for m in modes:
            gaps[m].append(correctness.served_token_gaps(
                ref, tokens if m == "float32"
                else rows[m][i].argmax(axis=-1)))
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

    from benchmark.harness import (correctness, model_flops,
                                   program_counters, traffic_gen)
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
    # the generator takes its number of prompt-length bands from `clients`
    traffic = traffic_gen.ClosedLoopTraffic(
        dict(mix, clients=mix.get("order_bands", mix["clients"])),
        config["draw_vocab"], ctx["seed"])
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

    def drive(ended, phase):
        while True:
            a = clock()
            with ctx["span"]("step"):
                sched.step()
            now = clock()
            samples["step_s"].append((a, now - a))
            harvest(now, phase)
            if ended(now):
                return

    # ---- the run-in, still set-up: every client submits at once, and the
    # loop is driven until each one's first request has ended. The window
    # then opens on the steady closed loop, its clients out of step with
    # each other, and not on as many prefills back to back as it has clients
    for i in range(len(clients)):
        submit(i, "run_in")
    first_wave = list(requests)
    guard = clock() + 120.0
    drive(lambda now: all("tokens" in r for r in first_wave) or now > guard,
          "run_in")
    ctx["mark"]("run_in")
    traces_before = (engine.trace_counts["decode"],
                     dict(engine.trace_counts["prefill"]))
    compiles_before = counter.requests

    # ---- the window
    t0 = clock()
    setup_s = t0 - ctx["t0"]
    t_end = t0 + ctx["seconds"]
    drive(lambda now: now >= t_end, "window")
    compiles_in_window = counter.requests - compiles_before
    record = {}
    if ctx["trace"]:
        # the traced window: the same loop, still under load
        record["trace"] = ctx["capture"](
            lambda: drive(lambda now, end=clock() + ctx["trace_seconds"]:
                          now >= end, "trace"))
    guard = clock() + 120.0           # wait for each, two minutes if need be
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
                     "prefill_buckets_used": sorted(by_bucket),
                     "slots": engine.slots},
    })
    # what the expert layers counted, on the spans of the window's decode
    # steps and prefills (None from a program that counts nothing)
    record["counters"]["moe"] = {
        "decode": program_counters.attr_sums(record, "decode.wait",
                                             MOE_KEYS),
        "prefill": program_counters.attr_sums(record, "prefill", MOE_KEYS)}
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
