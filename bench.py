"""Benchmark: flagship GPT training throughput on one TPU chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "device"}.
A failure prints the same line with an "error" field (and the
flight-recorder postmortem path in "extra") and EXITS NON-ZERO: a run that
did not measure must never look like one that measured 0.0.

The device is whatever jax attaches in THIS process (one process owns a
chip; nothing is probed in a child first). The default train rung needs a
TPU and fails without one; its peak comes from the device_kind through the
one table, `cost_model.analytical.DEVICES`, and an unknown kind is an
error. An explicit config (BENCH_B / BENCH_REMAT) on another backend is a
PIPELINE DRY RUN: it is emitted under its own metric name with no MFU — a
number from a CPU run is never written under a device metric's name.

Honest-measurement rules: every timed dispatch fetches float(loss) to the
host — a device->host transfer of a value that data-depends on the whole
dispatch, so it cannot complete before the work does. The timed unit is a
jit(lax.scan) of K full steps (params/opt-state as carry), so one dispatch
and one fetch are paid per K steps.

OOM ladder: on an XLA RESOURCE_EXHAUSTED (16GB v5e chip) the bench steps
down through smaller batch / heavier remat configs and reports which one
actually ran. Anything that is not the runtime saying "out of memory" is a
real failure and ends the run.

Pallas parity preflight: on TPU, before timing, the Pallas flash-attention
fwd+grads are compared against the XLA reference at the bench shape
(compiled, real Mosaic lowering). A preflight that crashes or diverges
ends the run — timing a wrong kernel measures nothing.

Every rung runs under a wall-clock watchdog that dumps a flight-recorder
postmortem (thread stacks, span ring, last metrics) and exits non-zero.
"""
import gc
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

METRIC = "gpt350m_train_mfu_1chip"
UNIT = "MFU (fraction of the attached chip's bf16 peak)"
# the explicit-config train rung on a backend that is not a TPU: the
# pipeline (profile/xplane/numerics artifacts) ran, nothing was measured
DRYRUN_METRIC = "train_pipeline_dryrun_steps"
DRYRUN_UNIT = "train steps completed (pipeline dry run off the chip; " \
              "not a device metric)"

# --profile artifacts directory (set by main from argv; run_config reads the
# global so its signature stays stable for the ladder tests)
_PROFILE_DIR = None

# --xplane one-shot device-capture controller (observability.deviceprof.
# OneShotCapture, armed by main; run_config fires it in the first healthy
# window — past warmup, watchdog quiet). Armed state rides the flight
# recorder's annotations, so a wedged run's postmortem records the
# armed-but-unfired capture instead of losing it.
_XPLANE_CTRL = None


# {"platform", "kind", "count"} of the device the numbers came from, as jax
# reports it; set by main once jax is up (the --cold-start parent, which
# stays off jax, takes it from its build child)
DEVICE = None


def attached_device():
    """The device jax attaches in this process — decided here, in-process,
    never by a throwaway child that would claim the chip first."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def emit(value, vs_baseline, extra=None, error=None):
    rec = {"metric": METRIC, "value": value, "unit": UNIT,
           "vs_baseline": vs_baseline, "device": DEVICE}
    if extra:
        rec["extra"] = extra
    if error:
        rec["error"] = error
    print(json.dumps(rec))
    sys.stdout.flush()


def emit_failure(error, extra=None):
    emit(0.0, 0.0, extra=extra, error=error)


_FR_MODULE = None


def _flight_recorder_module():
    """The flight-recorder module WITHOUT a jax import: use the package
    when paddle_tpu is already loaded; otherwise load the module file
    standalone (it is stdlib-only by contract) — so a postmortem can be
    written by a process that must stay off jax (the --cold-start parent:
    one process per chip) or whose own import of it hung."""
    global _FR_MODULE
    if _FR_MODULE is not None:
        return _FR_MODULE
    try:
        # key on the fully-imported SUBMODULE, never on "paddle_tpu": a
        # wedge inside `import paddle_tpu` leaves the package partially
        # initialized in sys.modules with the import lock held — a fresh
        # package import from the watchdog thread would block behind it
        fr = sys.modules.get("paddle_tpu.observability.flight_recorder")
        if fr is None:
            import importlib.util
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "paddle_tpu", "observability", "flight_recorder.py")
            spec = importlib.util.spec_from_file_location(
                "_bench_flight_recorder", path)
            fr = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(fr)
        _FR_MODULE = fr
    except Exception as e:                                   # noqa: BLE001
        print(f"bench: flight recorder unavailable: {e}", file=sys.stderr)
    return _FR_MODULE


def _postmortem_extra(reason):
    """Dump a flight-recorder postmortem and return the structured-failure
    extra: the artifact path + a flat last-metrics snapshot. Never raises
    — the failure line must go out even if forensics fail."""
    fr = _flight_recorder_module()
    if fr is None:
        return {}
    out = {}
    try:
        out["postmortem"] = fr.dump_postmortem(reason)
    except Exception as e:                                   # noqa: BLE001
        out["postmortem_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    try:
        mm = sys.modules.get("paddle_tpu.observability.metrics")
        if mm is not None:
            out["last_metrics_snapshot"] = mm.flatten_snapshot(
                mm.registry().snapshot())
    except Exception:                                        # noqa: BLE001
        pass
    return out


def _is_oom(e):
    """Only what the runtime itself says when device memory runs out: the
    ladder steps down on these and on nothing else."""
    s = str(e)
    return any(t in s for t in (
        "RESOURCE_EXHAUSTED", "Out of memory", "Ran out of memory",
        "Exceeded hbm capacity"))


def start_watchdog(seconds, what, on_fire=None):
    """Emit the structured-failure line and hard-exit NON-ZERO if
    `seconds` pass before cancel() — the wall-clock bound on a rung that
    hangs (a hung device call blocks in native code and releases the
    GIL). `on_fire` lets other benches (bench_eager) emit their own
    metric's failure record; it must accept (reason, extra=None) and
    include `extra` (postmortem path + last metrics) in its record.

    Before the line goes out, the flight recorder dumps a postmortem
    (thread stacks incl. the wedged one, span ring, metrics snapshot) and
    its path + the last metrics ride the record's `extra` — a wedged run
    can no longer end with `value: 0.0` and zero evidence. The forensics
    themselves run under a second hard timer: if the dump wedges too
    (e.g. a metrics collector touching the stuck runtime), the bare
    failure line still goes out — evidence is best-effort, the record is
    guaranteed."""
    def fire():
        reason = f"watchdog: {what} wedged for >{seconds}s"
        emitter = on_fire or emit_failure
        # exactly ONE record may reach stdout (the one-JSON-line bench
        # contract): whichever of the two paths below wins this lock emits
        emit_once = threading.Lock()

        def bare_exit():
            if emit_once.acquire(blocking=False):
                emitter(reason)
                os._exit(1)

        backstop = threading.Timer(20, bare_exit)
        backstop.daemon = True
        backstop.start()
        extra = _postmortem_extra(reason)   # artifact lands on disk here
        backstop.cancel()
        if emit_once.acquire(blocking=False):
            emitter(reason, extra=extra)    # all emitters take extra=
            os._exit(1)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def flash_parity_preflight(S, dtype="bfloat16"):
    """Pallas flash attention vs XLA fallback at the bench sequence length,
    on the real backend (non-interpret): fwd + dq/dk/dv max abs error."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import (_pallas_flash_bhsd,
                                                _ref_attention_bhsd)

    B, H, D = 2, 4, 64
    scale = 1.0 / D ** 0.5
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (B, H, S, D), dtype) * 0.5
    k = jax.random.normal(kk, (B, H, S, D), dtype) * 0.5
    v = jax.random.normal(kv, (B, H, S, D), dtype) * 0.5

    def loss_pallas(q, k, v):
        return _pallas_flash_bhsd(q, k, v, True, scale).astype(
            jnp.float32).sum()

    def loss_ref(q, k, v):
        return _ref_attention_bhsd(q, k, v, True, scale).astype(
            jnp.float32).sum()

    fwd_p = jax.jit(lambda q, k, v: _pallas_flash_bhsd(q, k, v, True, scale))
    fwd_r = jax.jit(lambda q, k, v: _ref_attention_bhsd(q, k, v, True, scale))
    o_p = np.asarray(fwd_p(q, k, v), np.float32)
    o_r = np.asarray(fwd_r(q, k, v), np.float32)
    fwd_err = float(np.abs(o_p - o_r).max())

    g_p = jax.jit(jax.grad(loss_pallas, argnums=(0, 1, 2)))(q, k, v)
    g_r = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    grad_err = float(max(
        np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
        for a, b in zip(g_p, g_r)))
    # bf16 inputs, S-long softmax reductions: ~1e-1 abs is the honest noise
    # floor for grads; "ok" flags catastrophic divergence (r2's corrupt-dK
    # episode was O(1) wrong), not rounding.
    return {"flash_parity_fwd_max_err": round(fwd_err, 5),
            "flash_parity_grad_max_err": round(grad_err, 5),
            "flash_parity_ok": bool(fwd_err < 0.05 and grad_err < 0.25)}


def _cost_model_predict(step_fn, args, top=8):
    """Analytical per-op prediction for ONE train step (abstract eval
    only — no execution), priced for the ATTACHED device (its
    device_kind through analytical.DEVICES). Returns the `cost_model`
    extra block with predicted totals + per-op rows, and publishes the
    prediction as a registry gauge IMMEDIATELY, so even a run that hangs
    in the timed loop leaves its analytical expectation in the
    postmortem metrics snapshot."""
    from paddle_tpu.cost_model import analytical
    spec = analytical.device_spec()     # unknown device_kind: an error
    try:
        from paddle_tpu.observability import metrics as _obs_metrics
        device = spec.name
        report = analytical.estimate(step_fn, *args, device=spec)
        rows = sorted(report.by_op.items(),
                      key=lambda kv: -spec.roofline_s(kv[1].flops,
                                                      kv[1].bytes))[:top]
        per_op = {name: {"predicted_ms": round(
                             1e3 * spec.roofline_s(c.flops, c.bytes), 4),
                         "gflop": round(c.flops / 1e9, 3),
                         "mbytes": round(c.bytes / 1e6, 2)}
                  for name, c in rows}
        block = {"device": device,
                 "predicted_step_ms": round(report.time_ms, 3),
                 "predicted_gflop": round(report.total_flops / 1e9, 3),
                 "per_op": per_op,
                 "has_while": report.has_while}
        _obs_metrics.gauge(
            "bench_cost_model_predicted_step_ms",
            "Analytical roofline prediction for one train step"
        ).set(block["predicted_step_ms"])
        return block
    except Exception as e:                                   # noqa: BLE001
        # the prediction is evidence, not a dependency — a cost-model
        # regression must not take the bench down
        return {"error": f"{type(e).__name__}: {str(e)[:300]}"}


def _cost_model_measure(block, step_ms):
    """Fold the measured step time into the prediction block and publish
    the measured/predicted gauges `tools/metrics_report.py --compare`
    gates on (a ratio that GROWS past the threshold = the analytical
    model lost contact with the hardware, or the hardware regressed)."""
    if not block or "predicted_step_ms" not in block:
        return block
    from paddle_tpu.observability import metrics as _obs_metrics
    block["measured_step_ms"] = round(step_ms, 3)
    pred = block["predicted_step_ms"]
    ratio = (step_ms / pred) if pred > 0 else 0.0
    block["measured_vs_predicted"] = round(ratio, 4)
    # per-op deltas: each op's predicted ms against its share of the
    # measured step AT THE PREDICTED MIX (the roofline says where the
    # time should go; the measured total says how much there was).
    # Shares divide by the FULL predicted total — not the truncated
    # top-N sum — so a perfect prediction yields zero deltas
    for r in block["per_op"].values():
        share = r["predicted_ms"] / pred if pred else 0.0
        r["measured_share_ms"] = round(share * step_ms, 4)
        r["delta_ms"] = round(r["measured_share_ms"] - r["predicted_ms"], 4)
    _obs_metrics.gauge(
        "bench_cost_model_measured_step_ms",
        "Measured train-step wall time").set(block["measured_step_ms"])
    _obs_metrics.gauge(
        "bench_cost_model_measured_vs_predicted",
        "Measured / analytically-predicted step time (gap gauge: growth "
        "past the --compare threshold is a failure-class regression)"
    ).set(block["measured_vs_predicted"])
    return block


def run_config(B, S, remat, n_steps, on_tpu, scan_k, fused_ce=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import GPTSpmdConfig, MeshPlan, make_train_step

    # GPT-350M-class: fits one v5e chip (16GB) with AdamW f32 states.
    # BENCH_LAYERS/HIDDEN/HEADS/VOCAB shrink the model for the CI smoke test
    # of the --profile pipeline (defaults are the flagship config).
    cfg = GPTSpmdConfig(
        vocab_size=int(os.environ.get("BENCH_VOCAB", 50304)),
        max_seq_len=S,
        hidden=int(os.environ.get("BENCH_HIDDEN", 1024)),
        layers=int(os.environ.get("BENCH_LAYERS", 24)),
        heads=int(os.environ.get("BENCH_HEADS", 16)),
        param_dtype="bfloat16" if on_tpu else "float32",
        compute_dtype="bfloat16" if on_tpu else "float32",
        remat={"none": False, "full": True, "dots": "dots",
               "dots+attn": "dots+attn"}[remat],
        scan_unroll=int(os.environ.get("BENCH_UNROLL", 1)),
        # chunked fused linear-CE: 50304 = 8 x 6288; frees the multi-GB f32
        # logits tensors (ops/fused_ce.py)
        fused_ce_chunks=8 if fused_ce else 0)

    plan = MeshPlan()
    step_fn, init_fn, _ = make_train_step(cfg, plan, learning_rate=2e-4)
    params, state = init_fn(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    labs = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    lr = jnp.float32(2e-4)

    # K full train steps per dispatch: params/opt-state are the scan carry,
    # so step i+1 data-depends on step i and nothing can be elided; one
    # dispatch + one host fetch is paid per K steps instead of per step.
    if scan_k > 1:
        def multi(params, state, toks, labs, lr):
            def body(carry, _):
                p, s = carry
                loss, p, s = step_fn(p, s, toks, labs, lr)
                return (p, s), loss
            (params, state), losses = jax.lax.scan(
                body, (params, state), None, length=scan_k)
            return losses[-1], params, state
        dispatch = jax.jit(multi, donate_argnums=(0, 1))
    else:
        dispatch = step_fn
    n_dispatch = max(1, n_steps // scan_k)

    # analytical expectation for ONE step, published before the timed
    # loop (a wedged run still leaves its prediction in the postmortem)
    cost_model = _cost_model_predict(step_fn,
                                     (params, state, toks, labs, lr))

    # warmup: compile + 2 synced dispatches (OOM, if any, surfaces here)
    for _ in range(2):
        loss, params, state = dispatch(params, state, toks, labs, lr)
        loss_val = float(loss)          # host fetch = true device sync

    # healthy window: compiled, warmed, watchdog quiet — if a one-shot
    # device capture is armed (--xplane), fire it NOW on one extra
    # dispatch OUTSIDE the timed loop (the capture must not perturb the
    # measurement), with a full host sync before the window closes so
    # every device op of the dispatch lands inside it
    xplane = _XPLANE_CTRL
    if xplane is not None and xplane.armed and xplane.start():
        try:
            loss, params, state = dispatch(params, state, toks, labs, lr)
            loss_val = float(loss)      # sync INSIDE the trace window
        except BaseException as e:
            # close the trace window before the ladder steps down, or it
            # would poison every later rung's start_trace
            xplane.abort(f"{type(e).__name__}: {str(e)[:200]}")
            raise
        xplane.stop()

    prof = None
    profile_paths = {}
    if _PROFILE_DIR:
        from paddle_tpu.profiler import (Profiler, RecordEvent,
                                         TracerEventType)
        os.makedirs(_PROFILE_DIR, exist_ok=True)
        tl_path = os.path.join(_PROFILE_DIR, "step_timeline.jsonl")
        if os.path.exists(tl_path):
            os.remove(tl_path)          # one run per artifact set
        profile_paths = {"timeline": tl_path,
                         "attribution": os.path.join(_PROFILE_DIR,
                                                     "attribution.md")}
        prof = Profiler(timer_only=True, timeline=tl_path)
        prof.start()

    # Timed loop: EVERY dispatch's last-step loss is fetched to the host,
    # but the fetch of dispatch i overlaps dispatch i+1 — one deep. The
    # timer stops only after the LAST loss reaches the host, which
    # transitively requires every step to have finished.
    t0 = time.perf_counter()
    prev = None
    losses = []
    if prof is None:
        for _ in range(n_dispatch):
            loss, params, state = dispatch(params, state, toks, labs, lr)
            if prev is not None:
                loss_val = float(prev)
                losses.append(loss_val)
            prev = loss
        loss_val = float(prev)
        losses.append(loss_val)
    else:
        # profiled variant: one Forward span per dispatch (dispatch + the
        # overlapped host fetch), one profiler step + JSONL record per
        # dispatch. The span bookkeeping is O(µs) against ~100ms dispatches.
        for _ in range(n_dispatch):
            with RecordEvent(f"bench.dispatch(x{scan_k} steps)",
                             TracerEventType.Forward):
                loss, params, state = dispatch(params, state, toks, labs, lr)
                if prev is not None:
                    loss_val = float(prev)
                    losses.append(loss_val)
            prev = loss
            prof.step(num_samples=B * S * scan_k)
        with RecordEvent("bench.final_loss_fetch", TracerEventType.Forward):
            loss_val = float(prev)
            losses.append(loss_val)
    dt = time.perf_counter() - t0
    # fold the measurement in BEFORE the profiler's registry snapshot is
    # written, so the predicted-vs-measured gauges ride the artifact set
    cost_model = _cost_model_measure(cost_model,
                                     1000 * dt / (n_dispatch * scan_k))

    deviceprof_block = None
    if xplane is not None and xplane.captured:
        # parse + join the capture against the analytical per-op
        # predictions; the deviceprof_* gauges land in the registry here,
        # BEFORE the --profile snapshot below is written
        deviceprof_block = xplane.finalize(
            cost_model_per_op=(cost_model or {}).get("per_op"),
            steps=scan_k)

    if prof is not None:
        prof.stop()
        report = prof.analyze()         # the attached device's spec
        with open(profile_paths["attribution"], "w") as f:
            f.write(report.render() + "\n\n")
            f.write(f"config: B={B} S={S} remat={remat} scan_k={scan_k} "
                    f"fused_ce={fused_ce} backend={jax.default_backend()}\n"
                    f"note: the train step is ONE fused XLA program, so "
                    f"host attribution lands in the Forward dispatch span; "
                    f"per-op rows appear for eager workloads.\n")
        # unified-registry artifacts next to the timeline: one JSONL
        # snapshot (metrics.v1) + the Prometheus text dump, both
        # schema-validated by tests/test_perf_pipeline.py and rendered/
        # compared by tools/metrics_report.py
        from paddle_tpu.observability import metrics as _obs_metrics
        reg = _obs_metrics.registry()
        profile_paths["metrics"] = os.path.join(_PROFILE_DIR,
                                                "metrics.jsonl")
        reg.write_snapshot(profile_paths["metrics"])
        profile_paths["metrics_prom"] = os.path.join(_PROFILE_DIR,
                                                     "metrics.prom")
        with open(profile_paths["metrics_prom"], "w") as f:
            f.write(reg.dump_prometheus())

    # numerics sentinel pass (ISSUE 19): one armed in-trace sweep over
    # the final params plus the fetched loss trajectory through the
    # online detector — the healthy train rung must latch ZERO anomalies
    numerics_block = _train_numerics_block(params, losses)

    total_steps = n_dispatch * scan_k
    extra = {"params": n_params, "batch": B, "seq": S, "remat": remat,
             "fused_ce": bool(fused_ce), "backend": jax.default_backend(),
             "n_steps": total_steps, "scan_k": scan_k,
             "step_ms": round(1000 * dt / total_steps, 1),
             "loss": loss_val, "cost_model": cost_model,
             "numerics": numerics_block,
             **({"deviceprof": deviceprof_block} if deviceprof_block else {}),
             **({"profile_artifacts": profile_paths} if profile_paths
                else {})}
    if not on_tpu:
        # pipeline dry run (explicit config off the chip): the steps ran
        # and the artifacts exist; there is no rate and no utilization to
        # report — main() emits this under DRYRUN_METRIC
        return {"value": total_steps, "vs_baseline": 0.0, "extra": extra}
    tokens_per_sec = B * S * total_steps / dt
    # model flops/token: 6N (fwd+bwd matmul params) + causal attention term
    # 6 * L * S * H (QK^T and AV, fwd+bwd, x0.5 causal). Remat recompute is
    # NOT counted (standard MFU convention).
    flops_per_token = 6 * n_params + 6 * cfg.layers * S * cfg.hidden
    achieved_flops = tokens_per_sec * flops_per_token
    from paddle_tpu.cost_model.analytical import device_spec
    spec = device_spec()                # peak by device_kind; unknown raises
    mfu = achieved_flops / spec.peak_flops
    assert 0.0 < mfu < 1.0, f"impossible MFU {mfu}: measurement is broken"
    assert np.isfinite(loss_val), f"non-finite loss {loss_val}"
    extra["tokens_per_sec"] = round(tokens_per_sec, 1)
    extra["peak"] = {"device": spec.name, "flops": spec.peak_flops}
    return {"value": round(mfu, 4), "vs_baseline": round(mfu / 0.40, 4),
            "extra": extra}


def _train_numerics_block(params, losses):
    """The ISSUE 19 train-rung sentinel pass: tap the final parameter
    tree through an ARMED jitted sweep (the in-trace tap path — a
    sink_scope opened at trace time, the fused stats vector returned as
    the program's output) and feed it, plus the whole fetched loss
    trajectory, through the online detector. The healthy rung must
    latch ZERO anomalies — a NaN that slipped through training fails
    the bench here, not in a downstream eval."""
    import jax

    from paddle_tpu.observability import numerics as _numerics

    mon = _numerics.NumericsMonitor(auto_bundle=False)

    def sweep(ps):
        with _numerics.sink_scope() as sink:
            _numerics.tap_tree("train.param_global_norm", ps)
        return sink

    mon.observe_sink(jax.jit(sweep)(params))
    # ONE fused observation over the loss history: any non-finite loss
    # shows in finite_frac, and a single vector can never false-latch
    # the drift rule on a (healthy) converging trajectory
    mon.observe("train.loss",
                _numerics.np_tree_stats([np.asarray(losses,
                                                    dtype=np.float32)]))
    rep = mon.report()
    assert rep["anomalies"] == 0, \
        f"numerics anomalies latched on the healthy train rung: " \
        f"{rep['counts']}"
    return rep


def _parse_args(argv):
    """Minimal flag parsing (--profile / --steps N / --profile-dir D). Env
    vars stay the primary config surface; argv is additive so the driver's
    `python bench.py` invocation is unchanged."""
    import argparse
    p = argparse.ArgumentParser(description="flagship GPT train bench")
    p.add_argument("--profile", action="store_true",
                   help="attach the profiler; write step-timeline JSONL + "
                        "MFU attribution next to the BENCH json")
    p.add_argument("--profile-dir", default="./bench_profile",
                   help="artifact directory for --profile")
    p.add_argument("--steps", type=int, default=None,
                   help="override the number of timed train steps")
    p.add_argument("--xplane", nargs="?", const="__default__", default=None,
                   metavar="DIR",
                   help="arm a one-shot device-profile capture "
                        "(jax.profiler XPlane) that fires in the first "
                        "healthy window of the train rung — one extra "
                        "dispatch between warmup and the timed loop — and "
                        "writes the raw trace + parsed deviceprof.v1 JSONL "
                        "+ cost-model join report under DIR (default "
                        "<profile-dir>/xplane); works identically on the "
                        "CPU backend")
    p.add_argument("--decode", action="store_true",
                   help="decode-throughput rung: steady-state tokens/sec "
                        "through the serving engine's single decode "
                        "executable instead of the train ladder")
    p.add_argument("--serve-load", action="store_true",
                   help="traffic-replay rung: tools/load_harness.py "
                        "shared-prefix mixture through the paged engine, "
                        "with the dense per-slot engine raced at the same "
                        "KV memory budget for the concurrency comparison")
    p.add_argument("--serve-dist", action="store_true",
                   help="multi-host serving rung: forked prefill+decode "
                        "worker pools behind the router (KV bundles "
                        "handed off over the PS RPC fabric) raced against "
                        "ONE single-process paged scheduler at the same "
                        "allocatable KV budget — tokens/sec, p50/p99 "
                        "TTFT, and handoff bytes per arm")
    p.add_argument("--pp-stages", type=int, default=None,
                   help="--serve-dist: run each decode worker GROUP as a "
                        "pipeline-parallel engine with this many stages "
                        "over its local devices (ISSUE 13; also "
                        "$BENCH_DIST_PP_STAGES); per-group tensor degree "
                        "via $BENCH_DIST_TP")
    p.add_argument("--gray-chaos", action="store_true",
                   help="--serve-dist: add a GRAY-FAILURE arm (ISSUE 20) "
                        "— same traffic through a fleet whose last decode "
                        "worker serves RPCs 10x slow (PTN_FAULTS "
                        "serving.rpc.serve=slow), streams asserted "
                        "bit-identical; extra records the suspicion-"
                        "triggered migration latency p99 and the "
                        "deadline-miss delta vs the healthy arm")
    p.add_argument("--cold-start", action="store_true",
                   help="cold-start rung: a child builds a serving "
                        "artifact, then a COLD process (empty compile "
                        "cache, full XLA compilation) is raced against a "
                        "WARM one (executables deserialized from the "
                        "persistent compile cache); reports "
                        "executable-ready + TTFT for both. This parent "
                        "never initialises jax — one process per chip")
    p.add_argument("--cold-start-child", metavar="ARTIFACT", default=None,
                   help="(internal) one measured Predictor process of the "
                        "--cold-start rung")
    p.add_argument("--cold-start-build", action="store_true",
                   help="(internal) the --cold-start rung's artifact-build "
                        "process")
    return p.parse_args(argv)


def run_decode_bench(on_tpu, n_steps=None):
    """Serving-engine decode rung: S slots advance one token per step
    through the one compiled decode executable; reports steady-state
    decode tokens/sec (warmup excluded) plus the compile-once counters.
    Model/size come from BENCH_DECODE_* envs so the CI smoke can shrink it."""
    import jax

    import paddle_tpu  # noqa: F401  (registers the framework)
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.text.models import gpt_125m, gpt_tiny

    model_name = os.environ.get("BENCH_DECODE_MODEL",
                                "gpt_125m" if on_tpu else "gpt_tiny")
    slots = int(os.environ.get("BENCH_DECODE_SLOTS", 8 if on_tpu else 2))
    max_len = int(os.environ.get("BENCH_DECODE_MAXLEN",
                                 1024 if on_tpu else 64))
    prompt_len = int(os.environ.get("BENCH_DECODE_PROMPT",
                                    128 if on_tpu else 8))
    steps = n_steps or int(os.environ.get("BENCH_DECODE_STEPS",
                                          64 if on_tpu else 8))
    model = {"gpt_125m": gpt_125m, "gpt_tiny": gpt_tiny}[model_name]()
    model.eval()
    engine = GenerationEngine(model, slots=slots, max_len=max_len)
    rng = np.random.RandomState(0)
    for s in range(slots):
        engine.prefill(s, rng.randint(0, model.cfg.vocab_size, prompt_len))
    engine.decode()                     # compile + warm the decode step
    t0 = time.perf_counter()
    for _ in range(steps):
        last = engine.decode()
    _ = int(last[0])                    # host sync: data-dependent fetch
    dt = time.perf_counter() - t0
    tok_s = slots * steps / dt
    return {
        "value": tok_s,
        "vs_baseline": 0.0,             # first decode rung IS the baseline
        "extra": {"metric_name": "decode_tokens_per_s",
                  "model": model_name, "slots": slots, "max_len": max_len,
                  "prompt_len": prompt_len, "steps": steps,
                  "step_ms": round(1000 * dt / steps, 2),
                  "trace_counts": {
                      "decode": engine.trace_counts["decode"],
                      "prefill": dict(engine.trace_counts["prefill"])},
                  "backend": jax.default_backend()},
    }


def run_serve_load_bench(on_tpu, n_requests=None):
    """Serving load rung: the deterministic traffic-replay harness
    (tools/load_harness.py) at a shared-prefix mixture — dense, paged,
    and speculative-decode engines AT THE SAME KV MEMORY BUDGET, plus
    (ISSUE 13) a pipeline-parallel arm at EQUAL MEASURED PER-HOST HBM
    (hbm_accounting-gated <=1.05x the paged arm; per-stage compile
    bounds asserted), plus (ISSUE 14) a spec×pp arm at the pp arm's
    pool budget — per-stage verify compile bounds asserted, acceptance
    rate + bubble fraction reported together, and steady-state
    tokens/sec asserted >= the pp-alone ring on warmed executables. The
    metric is the paged engine's replay tokens/sec; extra carries every
    arm's summary (tokens/sec, p50/p99 TTFT, peak concurrency, prefix
    hits, preemptions, and the spec arm's acceptance rate) plus the
    compile-once counters — ASSERTED bounded here, so a rung that quietly
    recompiles per step cannot report a throughput number — and
    vs_baseline is the paged/dense concurrency ratio (>1.0 is the
    paged-KV win)."""
    import jax

    import paddle_tpu  # noqa: F401  (registers the framework)
    from paddle_tpu.text import models as _models

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import load_harness

    model_name = os.environ.get("BENCH_SERVE_MODEL",
                                "gpt_125m" if on_tpu else "gpt_tiny")
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 4 if on_tpu else 3))
    max_len = int(os.environ.get("BENCH_SERVE_MAXLEN",
                                 512 if on_tpu else 64))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 16 if on_tpu else 8))
    requests = n_requests or int(os.environ.get("BENCH_SERVE_REQUESTS",
                                                64 if on_tpu else 12))
    budget = slots * max_len
    num_blocks = budget // block
    paged_slots = int(os.environ.get("BENCH_SERVE_PAGED_SLOTS",
                                     min(2 * slots, num_blocks - 1)))
    model = getattr(_models, model_name)()
    model.eval()
    traffic = load_harness.TrafficConfig(
        users=int(os.environ.get("BENCH_SERVE_USERS", 8)),
        requests=requests,
        rate_rps=float(os.environ.get("BENCH_SERVE_RPS", 500.0)),
        prefix_len=int(os.environ.get("BENCH_SERVE_PREFIX", 2 * block)),
        max_new_tokens=int(os.environ.get("BENCH_SERVE_MAXNEW", 4)),
        seed=0)
    gamma = int(os.environ.get("BENCH_SERVE_GAMMA", 3))
    draft_layers = int(os.environ.get("BENCH_SERVE_DRAFT_LAYERS", 1))
    attention_impl = os.environ.get("BENCH_SERVE_ATTEND", "gather")
    # quant arm sizing (ISSUE 11): EQUAL HBM BYTES, not equal tokens.
    # One f32 block is block*h*d*4 bytes per K/V side; an int8 block is
    # block*h*d*1 plus a 4*h-byte scale row — so the same byte budget
    # holds ~4x the int8 blocks on these f32 CPU pools (2x on a bf16
    # serving baseline; docs/PERF_NOTES.md prices both). Streams are
    # provisioned at 2x the paged slots — the acceptance figure — with
    # the block surplus absorbing per-slot fragmentation.
    h, d = model.cfg.num_heads, model.cfg.hidden_size // model.cfg.num_heads
    f32_block_bytes = block * h * d * 4
    int8_block_bytes = block * h * d + 4 * h
    quant_blocks = max(num_blocks + 1,
                       num_blocks * f32_block_bytes // int8_block_bytes)
    quant_slots = int(os.environ.get("BENCH_SERVE_QUANT_SLOTS",
                                     2 * paged_slots))
    # the decision audit log rides the DEFAULT (paged) arm (ISSUE 15):
    # the scheduler's serving JSONL (request/timeline records + every
    # decisions.v1 admit/shed/preempt/place record) is schema-validated
    # below and cross-checked record-by-record against the terminal
    # request outcomes
    serve_jsonl = os.path.join(
        tempfile.mkdtemp(prefix="bench_serve_load_"), "serve.jsonl")
    results = {}
    paged_engines = []
    # divergence counters are process-global; a chaos test that armed the
    # leak fault earlier in this process must not fail THIS run's audit
    kv_div_baseline = _kv_divergence_totals()
    for kind, n_slots, n_blocks in (
            ("dense", slots, num_blocks), ("paged", paged_slots, num_blocks),
            ("spec", paged_slots, num_blocks),
            ("quant", quant_slots, quant_blocks)):
        results[kind] = load_harness.run_harness(
            model, kind, traffic, slots=n_slots, max_len=max_len,
            block_size=block, num_blocks=n_blocks, gamma=gamma,
            draft_layers=draft_layers, attention_impl=attention_impl,
            serve_jsonl=serve_jsonl if kind == "paged" else None,
            engine_sink=paged_engines if kind == "paged" else None)
    paged, dense, spec, quant = (results["paged"], results["dense"],
                                 results["spec"], results["quant"])
    decision_audit = _audit_serve_decisions(serve_jsonl)
    # the KV-ledger end-of-run reconciliation rides the same default arm
    # (ISSUE 16): the paged engine's full kvledger.v1 stream must replay
    # into an exact reconstruction of the pool — zero leaked blocks
    kv_ledger_audit = _audit_kv_ledger(paged_engines[0], kv_div_baseline) \
        if paged_engines else None
    # pp arm (ISSUE 13): pipeline-parallel serving at EQUAL PER-HOST
    # HBM. Each of the pp stage groups holds 1/pp of the layers, so at
    # the paged arm's per-device byte budget the pp pool takes pp× the
    # blocks (and pp× the slots ride the decode ring). The budget is
    # GATED below on the MEASURED per-device footprint
    # (hbm_accounting), not dtype/count arithmetic — weights shrink per
    # device too (1/pp + the tied-embedding copy), so pool-equality is
    # the conservative sizing.
    pp_stages = int(os.environ.get("BENCH_SERVE_PP", 2))
    pp_tp = int(os.environ.get("BENCH_SERVE_PP_TP", 1))
    pp_arm = None
    pp_engines = []
    if pp_stages * pp_tp <= len(jax.devices()):
        pp_blocks = pp_stages * (num_blocks - 1) + 1
        pp_slots = pp_stages * paged_slots
        results["pp"] = load_harness.run_harness(
            model, "pp", traffic, slots=pp_slots, max_len=max_len,
            block_size=block, num_blocks=pp_blocks,
            attention_impl=attention_impl, tp=pp_tp, pp=pp_stages,
            engine_sink=pp_engines)
        pp_arm = results["pp"]
        pp_hbm_ratio = (pp_arm["hbm_max_device_bytes"]
                        / max(paged["hbm_max_device_bytes"], 1))
        assert pp_hbm_ratio <= 1.05, \
            f"pp arm exceeds the per-host HBM budget: " \
            f"{pp_hbm_ratio:.3f}x the paged arm's measured per-device " \
            f"bytes"
    else:
        # a 1-device host (no virtual-device XLA_FLAGS, single real
        # chip): the hybrid-parallel arm is impossible — record why
        # instead of failing the whole rung
        pp_hbm_ratio = None
        results["pp"] = {"skipped":
                         f"needs {pp_stages * pp_tp} devices, have "
                         f"{len(jax.devices())}"}
    # spec×pp arm (ISSUE 14): speculative verify windows on the
    # pipeline ring, at the pp arm's pool sizing (equal target-pool
    # budget, the ISSUE 7 spec-arm precedent; the draft's stage-0
    # weights + dense cache are REPORTED via the measured HBM ratio,
    # not hidden — at production shape they are ~1/12 of a stage
    # shard, priced in docs/PERF_NOTES.md). Skips explicitly on hosts
    # with < pp*tp devices, per the PR 13 precedent.
    spec_pp_arm = None
    spec_pp_hbm_ratio = None
    spec_pp_rates = None
    if pp_arm is not None:
        results["spec_pp"] = load_harness.run_harness(
            model, "spec_pp", traffic, slots=pp_slots, max_len=max_len,
            block_size=block, num_blocks=pp_blocks, gamma=gamma,
            draft_layers=draft_layers, attention_impl=attention_impl,
            tp=pp_tp, pp=pp_stages, engine_sink=pp_engines)
        spec_pp_arm = results["spec_pp"]
        spec_pp_hbm_ratio = (spec_pp_arm["hbm_max_device_bytes"]
                             / max(pp_arm["hbm_max_device_bytes"], 1))
        # the composed-throughput acceptance — spec×pp >= pp-alone —
        # measured STEADY-STATE on the harness arms' already-WARMED
        # engines: a tiny CPU replay's wall clock is compile-dominated
        # (the spec arm compiles pp more executables than the one-token
        # ring), compile time must not decide a throughput claim, and
        # rebuilding the two most compile-heavy engine families just to
        # probe them would spend scarce tier-1 wall clock for no signal
        spec_pp_rates = _spec_pp_steady_rate(model, *pp_engines)
        assert spec_pp_rates["spec_pp_tokens_per_s"] >= \
            spec_pp_rates["pp_tokens_per_s"], \
            f"spec×pp steady-state decode " \
            f"{spec_pp_rates['spec_pp_tokens_per_s']} tok/s fell below " \
            f"the pp-alone ring's {spec_pp_rates['pp_tokens_per_s']} " \
            f"tok/s at equal pool budget"
    else:
        results["spec_pp"] = {"skipped":
                              f"needs {pp_stages * pp_tp} devices, have "
                              f"{len(jax.devices())}"}
    # the quality gate rides the rung: teacher-forced greedy match +
    # logit KL vs the f32 oracle, exported as serving_quant_* gauges.
    # Sample size matters against the 0.99 gate below: 5 slots x 40
    # steps = 200 decisions (prompts <= 2*block+4 tokens keep 40 steps
    # inside max_len), so the gate tolerates a stray near-tie argmax
    # flip (199/200 = 0.995) instead of demanding perfection of a
    # 72-decision sample where one flip alone means 0.986 < 0.99
    quality = load_harness.quant_quality(
        model, slots=min(5, quant_slots), max_len=max_len,
        block_size=block, steps=int(os.environ.get(
            "BENCH_SERVE_QUALITY_STEPS", 40)),
        attention_impl=attention_impl, seed=0)
    # multi-tenant isolation gate (ISSUE 17): two tenants at the paged
    # arm's exact KV budget — tenant A bursts behind its adapter, token
    # bucket and namespace quota; tenant B's p99 TTFT, B's resident
    # system-prompt blocks, and the one-executable adapter trace are
    # all ASSERTED inside (a breach fails the rung, not just a number)
    tenant_iso = _isolation_gate(model, load_harness, traffic,
                                 paged_slots, max_len, block, num_blocks,
                                 attention_impl)
    # KV memory hierarchy gate (ISSUE 18): host/disk tiers at the paged
    # arm's exact pool — 2x-provisioned streams, live demote/promote
    # traffic, compile-once with tiering on, zero cross-tier ledger
    # leaks, and the cold-chain restore-beats-recompute TTFT claim, all
    # ASSERTED inside
    kv_tier_gate = _kv_tier_gate(model, load_harness, traffic,
                                 paged_slots, max_len, block, num_blocks,
                                 attention_impl)
    # numerics health gate (ISSUE 19): the int8 arm re-runs the serve
    # shape with the sentinel plane ARMED — zero anomalies on the
    # healthy path and compile-once with taps on, ASSERTED inside
    numerics_gate = _numerics_gate(model, max_len, block, quant_blocks,
                                   quant_slots, attention_impl)
    # compile-count discipline, asserted per arm: ONE decode executable
    # (dense/paged/quant) or ONE draft-decode + ONE verify executable
    # (spec) — a rung that recompiles per step must fail, not report
    # throughput
    compile_bounds = {
        "dense": dense["trace_counts"]["decode"] == 1,
        "paged": paged["trace_counts"]["decode"] == 1,
        "quant": quant["trace_counts"]["decode"] == 1,
        "spec": (spec["trace_counts"]["spec_verify"] == 1
                 and spec["trace_counts"]["draft_decode"] == 1
                 and spec["trace_counts"]["decode"] == 0),
        # pp: every STAGE's decode ring executable compiles exactly
        # once, and so does each (stage, chunk) prefill executable
        # (vacuously true on hosts too small for the pp arm)
        "pp": pp_arm is None or (
            len(pp_arm["trace_counts"]["decode_pp"]) == pp_stages
            and all(v == 1 for v in
                    pp_arm["trace_counts"]["decode_pp"].values())
            and all(v == 1 for v in
                    pp_arm["trace_counts"]["prefill_pp"].values())
            and pp_arm["trace_counts"]["decode"] == 0),
        # spec×pp (ISSUE 14): ONE verify executable per stage, ONE
        # draft decode, and the one-token paths NEVER trace during the
        # spec run — per-stage decode_pp stays empty, and so do both
        # single-device decode counters
        "spec_pp": spec_pp_arm is None or (
            len(spec_pp_arm["trace_counts"]["verify_pp"]) == pp_stages
            and all(v == 1 for v in
                    spec_pp_arm["trace_counts"]["verify_pp"].values())
            and spec_pp_arm["trace_counts"]["draft_decode"] == 1
            and spec_pp_arm["trace_counts"]["spec_verify"] == 0
            and not spec_pp_arm["trace_counts"]["decode_pp"]
            and spec_pp_arm["trace_counts"]["decode"] == 0),
    }
    assert all(compile_bounds.values()), \
        f"decode compile counts unbounded: {compile_bounds}"
    quant_ratio = (quant["max_concurrent"] / paged["max_concurrent"]
                   if paged["max_concurrent"] else 0.0)
    # the ISSUE 11 acceptance pair: ~2x streams at equal HBM, and a
    # quantized path that still agrees with its float oracle
    assert quant_ratio >= 1.8, \
        f"quant arm concurrency {quant['max_concurrent']} vs paged " \
        f"{paged['max_concurrent']} = {quant_ratio:.2f}x < 1.8x"
    assert quality["greedy_match"] >= 0.99, \
        f"quant greedy-match {quality['greedy_match']:.4f} < 0.99"
    ratio = (paged["max_concurrent"] / dense["max_concurrent"]
             if dense["max_concurrent"] else 0.0)
    return {
        "value": paged["tokens_per_s"] or 0.0,
        "vs_baseline": round(ratio, 3),     # paged/dense concurrency ratio
        "extra": {"metric_name": "serve_load_tokens_per_s",
                  "model": model_name, "kv_memory_tokens": budget,
                  "paged": paged, "dense": dense, "spec": spec,
                  "quant": quant,
                  "spec_acceptance_rate": spec["spec_acceptance_rate"],
                  "spec_gamma": gamma,
                  "attention_impl": attention_impl,
                  "compile_bounds": compile_bounds,
                  "paged_beats_dense_concurrency":
                      paged["max_concurrent"] > dense["max_concurrent"],
                  "quant_vs_paged_concurrency": round(quant_ratio, 3),
                  "quant_blocks": quant_blocks,
                  "quant_hbm_bytes_per_f32_block":
                      {"f32": f32_block_bytes, "int8": int8_block_bytes},
                  "quant_greedy_match": quality["greedy_match"],
                  "quant_logit_kl": quality["logit_kl"],
                  "pp": results["pp"], "pp_stages": pp_stages,
                  "pp_tp": pp_tp,
                  "pp_hbm_vs_paged": round(pp_hbm_ratio, 4)
                  if pp_hbm_ratio is not None else None,
                  "pp_vs_paged_concurrency": round(
                      pp_arm["max_concurrent"]
                      / max(paged["max_concurrent"], 1), 3)
                  if pp_arm is not None else None,
                  "spec_pp": results["spec_pp"],
                  "spec_pp_acceptance_rate":
                      spec_pp_arm["spec_acceptance_rate"]
                  if spec_pp_arm is not None else None,
                  "spec_pp_hbm_vs_pp": round(spec_pp_hbm_ratio, 4)
                  if spec_pp_hbm_ratio is not None else None,
                  "spec_pp_steady_rates": spec_pp_rates,
                  "decision_audit": decision_audit,
                  "kv_ledger_audit": kv_ledger_audit,
                  "tenant_isolation": tenant_iso,
                  "kv_tier_gate": kv_tier_gate,
                  "numerics": numerics_gate,
                  "backend": jax.default_backend()},
    }


def _audit_serve_decisions(serve_jsonl):
    """The ISSUE 15 CI gate over the --serve-load default arm's serving
    JSONL: every record schema-valid (decision records additionally
    REPLAY-verified by the validator — inputs must reproduce the stored
    outcome), and the audit log COMPLETE: every terminal SHED request
    has exactly one shed decision naming it, and every request's
    preemption count matches the preempt decisions naming it as victim.
    Returns the audit summary dict (asserts on any violation)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import serve_report
    recs = [json.loads(line) for line in open(serve_jsonl)
            if line.strip()]
    errs = serve_report.validate_records(recs)
    assert not errs, f"serving JSONL schema/replay errors: {errs[:5]}"
    decs = [r for r in recs if r["kind"] == "decision"]
    req_recs = [r for r in recs if r["kind"] == "request"]
    shed_by_req = {}
    preempt_by_req = {}
    for d in decs:
        if d["action"] == "shed":
            rid = d.get("request_id")
            shed_by_req[rid] = shed_by_req.get(rid, 0) + 1
        elif d["action"] == "preempt":
            rid = d["outcome"].get("victim_request_id")
            preempt_by_req[rid] = preempt_by_req.get(rid, 0) + 1
    for r in req_recs:
        rid = r["request_id"]
        if r["status"] == "SHED":
            assert shed_by_req.get(rid) == 1, \
                f"request {rid} SHED with {shed_by_req.get(rid, 0)} " \
                f"shed decision records (want exactly 1)"
        assert preempt_by_req.get(rid, 0) == r["preempted"], \
            f"request {rid} preempted {r['preempted']}x but " \
            f"{preempt_by_req.get(rid, 0)} preempt decisions name it"
    return {"records": len(recs), "decisions": len(decs),
            "by_action": {a: sum(1 for d in decs if d["action"] == a)
                          for a in sorted({d["action"] for d in decs})},
            "path": serve_jsonl}


def _kv_divergence_totals():
    """{labels-json: value} of serving_kv_ledger_divergence_total from a
    fresh registry snapshot (the counter is process-global, so audits
    compare deltas, never absolutes)."""
    from paddle_tpu.observability import metrics as _obs_metrics
    snap = _obs_metrics.registry().snapshot()
    return {json.dumps(s["labels"], sort_keys=True): s["value"]
            for m in snap["metrics"]
            if m["name"] == "serving_kv_ledger_divergence_total"
            for s in m["samples"]}


def _audit_kv_ledger(engine, div_baseline):
    """The ISSUE 16 end-of-run gate, the ledger analogue of the decision
    audit above: replay the paged arm's FULL kvledger.v1 event stream
    through a fresh shadow pool and require it to RECONSTRUCT the real
    BlockPool exactly — identical free list, identical per-block
    refcounts, zero leaked blocks (every block still resident after the
    replay drained is a prefix-cache holding, never a retired request's
    orphan) — with a clean event stream and zero reconciler divergences
    latched during the run. Returns the audit summary dict (asserts on
    any violation); None when the ledger is disabled (PTN_KV_LEDGER=0)."""
    from paddle_tpu.observability import kvledger as _kvl

    ledger = getattr(engine, "kv_ledger", None)
    if ledger is None:
        return None
    pool = engine.block_pool
    shadow = _kvl.replay_events(ledger.events, pool.num_blocks)
    assert not shadow.errors, \
        f"kvledger stream has impossible transitions: {shadow.errors[:3]}"
    real_refs = [int(r) for r in pool._refs]
    assert shadow.refs == real_refs, \
        f"ledger replay refcounts diverge from the pool at blocks " \
        f"{[b for b in range(pool.num_blocks) if shadow.refs[b] != real_refs[b]][:8]}"
    assert shadow.free_set() == set(int(b) for b in pool._free), \
        f"ledger replay free list diverges from the pool: " \
        f"{sorted(shadow.free_set() ^ set(int(b) for b in pool._free))[:8]}"
    # zero leaked blocks: with every request retired, each still-resident
    # block must be a cache insertion (its only holders of kind 'cached')
    leaked = sorted(b for b in shadow.allocated if b not in shadow.cached)
    assert not leaked, \
        f"blocks {leaked[:8]} resident after drain but not prefix-cached " \
        f"(leaked by a retired request)"
    diverged = {k: v - div_baseline.get(k, 0)
                for k, v in _kv_divergence_totals().items()
                if v - div_baseline.get(k, 0)}
    assert not diverged, \
        f"reconciler latched divergences during the run: {diverged}"
    return {"events": len(ledger.events),
            "blocks_resident": len(shadow.allocated),
            "blocks_cached": len(shadow.cached),
            "tenant_kind_blocks": {
                f"{t}/{k}": n
                for (t, k), n in sorted(shadow.tenant_kind_blocks().items())}}


def _isolation_gate(model, load_harness, base_traffic, slots, max_len,
                    block, num_blocks, attention_impl):
    """The ISSUE 17 multi-tenant isolation gate: two tenants share ONE
    paged engine at the same KV budget as the default arm — tenant A
    carries its own LoRA adapter, a token bucket, and a prefix-namespace
    quota; tenant B is the well-behaved neighbor. Two deterministic
    virtual-clock replays run back to back: a no-burst BASELINE, then
    the same trace with tenant A's arrival rate multiplied inside a
    burst window. The gate (asserted, so a regression fails the rung
    like a compile-count breach would):

      1. tenant B's burst-run p99 TTFT stays within GATE x its own
         no-burst baseline (floor-clamped — a tiny CPU replay's p99 is
         a handful of virtual steps);
      2. tenant B's namespace loses ZERO blocks to A's pressure — the
         quota-aware eviction order reclaims A's own leaves first and
         never a protected neighbor's system prompt;
      3. the mixed-tenant adapter-on batch still decodes through ONE
         compiled executable (trace count == 1), per-tenant behavior
         riding the gather-by-slot arrays as data, not program.

    All knobs env-tunable (BENCH_SERVE_ISO_*); both replays run the
    injectable virtual clock, so the verdict is bit-reproducible on CPU
    CI."""
    rate_a = float(os.environ.get("BENCH_SERVE_ISO_RATE_A", 400.0))
    rate_b = float(os.environ.get("BENCH_SERVE_ISO_RATE_B", 100.0))
    burst_mult = float(os.environ.get("BENCH_SERVE_ISO_BURST_MULT", 6.0))
    requests = int(os.environ.get("BENCH_SERVE_ISO_REQUESTS",
                                  2 * base_traffic.requests))
    gate_mult = float(os.environ.get("BENCH_SERVE_ISO_GATE", 2.0))
    gate_floor_s = float(os.environ.get("BENCH_SERVE_ISO_FLOOR", 0.25))
    # A's token bucket prices a request at prompt+max_new tokens. The
    # refill rate covers A's STEADY arrival rate exactly; the burst
    # capacity holds ~10 requests of clump slack — so baseline traffic
    # flows, and the burst window overdraws and gets denied: the rate
    # limiter, not tenant B, absorbs A's excess
    cost = base_traffic.prefix_len + base_traffic.suffix_max \
        + base_traffic.max_new_tokens
    bucket_a = float(os.environ.get("BENCH_SERVE_ISO_BUCKET_A",
                                    rate_a * cost))
    burst_a = float(os.environ.get("BENCH_SERVE_ISO_BURST_CAP_A",
                                   10 * cost))
    quota = max(2, (num_blocks - 1) // 2)
    tenancy = load_harness.build_tenancy(
        ("tenant_a", "tenant_b"),
        adapters_arg=os.environ.get("BENCH_SERVE_ISO_ADAPTERS",
                                    "tenant_a:4"),
        quotas_arg=f"tenant_a:{quota},tenant_b:{quota}",
        rates_arg=f"tenant_a:{bucket_a:.0f}/{burst_a:.0f}")
    tenants = {"tenant_a": rate_a, "tenant_b": rate_b}
    arms = {}
    engines = []
    for arm, burst in (
            ("baseline", None),
            ("burst", {"tenant": "tenant_a",
                       "t0": float(os.environ.get(
                           "BENCH_SERVE_ISO_BURST_T0", 0.0)),
                       "dur_s": float(os.environ.get(
                           "BENCH_SERVE_ISO_BURST_DUR", 0.05)),
                       "mult": burst_mult})):
        traffic = load_harness.TrafficConfig(
            users=base_traffic.users, requests=requests,
            prefix_len=base_traffic.prefix_len,
            suffix_min=base_traffic.suffix_min,
            suffix_max=base_traffic.suffix_max,
            max_new_tokens=base_traffic.max_new_tokens,
            seed=base_traffic.seed, tenants=tenants, burst=burst)
        arms[arm] = load_harness.run_harness(
            model, "paged", traffic, slots=slots, max_len=max_len,
            block_size=block, num_blocks=num_blocks,
            attention_impl=attention_impl, virtual_step_s=0.01,
            engine_sink=engines, tenancy=tenancy)
    base_b = arms["baseline"]["tenants"]["tenant_b"]
    burst_b = arms["burst"]["tenants"]["tenant_b"]
    burst_a = arms["burst"]["tenants"]["tenant_a"]
    gate_s = max(gate_floor_s, gate_mult * (base_b["ttft_p99_s"] or 0.0))
    assert (burst_b["ttft_p99_s"] or 0.0) <= gate_s, \
        f"tenant isolation breached: tenant B p99 TTFT " \
        f"{burst_b['ttft_p99_s']}s under tenant A's burst exceeds the " \
        f"gate {gate_s:.4f}s (baseline {base_b['ttft_p99_s']}s x " \
        f"{gate_mult}, floor {gate_floor_s}s)"
    assert burst_b.get("ns_blocks_evicted", 0) == 0, \
        f"tenant B lost {burst_b['ns_blocks_evicted']} namespaced " \
        f"prefix blocks to tenant A's burst (quota eviction must " \
        f"reclaim A's own leaves, never a protected neighbor's)"
    assert arms["burst"]["trace_counts"]["decode"] == 1, \
        f"adapter-on mixed-tenant decode recompiled: " \
        f"{arms['burst']['trace_counts']['decode']} traces (want 1)"
    return {
        "gate_p99_s": round(gate_s, 4),
        "gate_mult": gate_mult,
        "tenant_b_p99_baseline_s": base_b["ttft_p99_s"],
        "tenant_b_p99_burst_s": burst_b["ttft_p99_s"],
        "tenant_b_ns_evicted": burst_b.get("ns_blocks_evicted", 0),
        "tenant_a_rate_limited": burst_a.get("rate_limited", 0),
        "tenant_a_shed": burst_a.get("shed", 0),
        "adapter_decode_traces": arms["burst"]["trace_counts"]["decode"],
        "burst_mult": burst_mult,
        "requests": requests,
        "baseline": arms["baseline"]["tenants"],
        "burst": arms["burst"]["tenants"],
    }


def _numerics_gate(model, max_len, block, num_blocks, slots,
                   attention_impl):
    """The ISSUE 19 serving-side numerics gate: an int8 paged engine
    (quantized KV + decode weights — the arm with the most tapped
    surfaces: code saturation, scale rows, logits) runs the serve shape
    with the sentinel plane ARMED. Asserted (a breach fails the rung):

      1. zero anomalies latched over prefill + decode on the healthy
         path — the armed plane must not cry wolf;
      2. ONE decode executable with taps armed — arming is a different
         traced program, not a per-step retrace.

    Returns the detector report (per-site stats block) for `extra`."""
    import numpy as np

    from paddle_tpu.serving import PagedGenerationEngine

    steps = int(os.environ.get("BENCH_SERVE_NUMERICS_STEPS", 8))
    eng = PagedGenerationEngine(
        model, slots=slots, max_len=max_len, block_size=block,
        num_blocks=num_blocks, attention_impl=attention_impl,
        kv_dtype="int8", weight_dtype="int8", numerics_taps=True)
    rng = np.random.RandomState(7)
    for s in range(min(slots, 2)):
        eng.prefill(s, rng.randint(1, model.cfg.vocab_size,
                                   2 * block + 1).astype(np.int32))
    for _ in range(steps):
        eng.decode()
    rep = eng.numerics_monitor.report()
    assert rep["anomalies"] == 0, \
        f"numerics anomalies latched on the healthy int8 serve path: " \
        f"{rep['counts']}"
    assert eng.trace_counts["decode"] == 1, \
        f"armed decode recompiled: {eng.trace_counts['decode']} traces " \
        f"(want 1)"
    # the armed program tapped the full quantized surface
    want = {"decode.logits", "kv.codes", "kv.scale",
            "weights.q", "weights.scale"}
    missing = want - set(rep["sites"])
    assert not missing, f"armed int8 arm missing tap sites: {missing}"
    return rep


def _tier_counter_totals():
    """{(name, tier-label): value} of the serving_kv_tier_* counters from
    a fresh registry snapshot (process-global — gates compare deltas)."""
    from paddle_tpu.observability import metrics as _obs_metrics
    snap = _obs_metrics.registry().snapshot()
    out = {}
    for m in snap["metrics"]:
        if not m["name"].startswith("serving_kv_tier_"):
            continue
        for s in m["samples"]:
            out[(m["name"], s["labels"].get("tier", ""))] = s["value"]
    return out


def _kv_tier_gate(model, load_harness, base_traffic, paged_slots, max_len,
                  block, num_blocks, attention_impl):
    """The ISSUE 18 KV-tier gate: the host/disk memory hierarchy earns
    its keep at the SAME HBM pool as the untiered paged arm — the pool
    holds only ACTIVE chains, the prefix working set lives cold — so the
    tiered arm is provisioned at 2x the paged streams (the quant-arm
    precedent: the enabling claim, asserted below, is that eviction
    under that oversubscription demotes instead of destroys, and a
    returning chain restores instead of recomputes). The workload
    rotates a prefix pool WIDER than HBM can keep resident, so the
    untiered comparator's hits die by eviction while the tiered arm's
    ride host RAM. Asserted (a breach fails the rung):

      1. tiered max_concurrent >= GATE x the untiered arm's, at the
         IDENTICAL block pool (default 1.5x);
      2. the tier plane actually carried traffic: demotions AND
         promotions both > 0 over the replay — the ratio above cannot
         be claimed off an idle tier;
      3. ONE decode executable with tiering enabled — promote/demote
         are eager host+transfer work, never traced programs;
      4. zero reconciler divergences (the tier_residency invariant runs
         every scheduler step: a demote the ledger missed, or a dropped
         entry it still counts, is a cross-tier leak) — checked as a
         process-global counter delta PLUS one explicit end-of-run
         reconciliation;
      5. the cold-chain TTFT claim: restoring a demoted chain from the
         host tier (promote + suffix-only prefill) is measured against
         recomputing the same prompt through a cache-less twin, median
         of BENCH_SERVE_TIER_REPEATS interleaved rounds each — restore
         must win (<= RESTORE_SLACK x recompute, default 1.0).
    """
    import time as _time

    import numpy as np

    from paddle_tpu.observability import kvledger as _kvl

    ratio_gate = float(os.environ.get("BENCH_SERVE_TIER_RATIO", 1.5))
    restore_slack = float(os.environ.get("BENCH_SERVE_TIER_RESTORE_SLACK",
                                         1.0))
    requests = int(os.environ.get("BENCH_SERVE_TIER_REQUESTS",
                                  2 * base_traffic.requests))
    prefix_pool = int(os.environ.get("BENCH_SERVE_TIER_PREFIXES", 4))
    tier_slots = int(os.environ.get("BENCH_SERVE_TIER_SLOTS",
                                    2 * paged_slots))
    repeats = int(os.environ.get("BENCH_SERVE_TIER_REPEATS", 9))
    tier_dir = tempfile.mkdtemp(prefix="bench_kv_tiers_")
    # short suffixes keep each stream's PRIVATE footprint ~1 block, so
    # the pool genuinely fits 2x the streams once the prefix working
    # set (prefix_pool x prefix_len/block blocks — wider than HBM
    # headroom under load) is free to go cold
    traffic = load_harness.TrafficConfig(
        users=base_traffic.users, requests=requests,
        rate_rps=float(os.environ.get("BENCH_SERVE_TIER_RPS", 4000.0)),
        prefix_pool=prefix_pool, prefix_len=base_traffic.prefix_len,
        suffix_min=1, suffix_max=2, max_new_tokens=2,
        seed=base_traffic.seed)
    div_baseline = _kv_divergence_totals()
    tier_baseline = _tier_counter_totals()
    engines = []
    tiered = load_harness.run_harness(
        model, "paged", traffic, slots=tier_slots, max_len=max_len,
        block_size=block, num_blocks=num_blocks,
        attention_impl=attention_impl, virtual_step_s=0.01,
        engine_sink=engines,
        tier_kwargs=dict(enable_kv_tiers=True,
                         host_tier_blocks=4 * num_blocks,
                         disk_tier_dir=tier_dir,
                         disk_tier_blocks=8 * num_blocks))
    untiered = load_harness.run_harness(
        model, "paged", traffic, slots=paged_slots, max_len=max_len,
        block_size=block, num_blocks=num_blocks,
        attention_impl=attention_impl, virtual_step_s=0.01)
    eng = engines[0]
    # the cold-return wave: demote the flood's whole prefix working set
    # (the eviction hook — the same demote the allocator's pressure path
    # runs), then replay the SAME prefix mixture through a fresh
    # scheduler over the same engine — every placement's match now walks
    # into the host tier and promotes, so the promote figure below is
    # the scheduler-path restore, not an engine-internal shortcut
    from paddle_tpu.serving import Scheduler
    eng.prefix_cache.evict(num_blocks)
    vclock = load_harness.VirtualClock()
    wave_sched = Scheduler(eng, clock=vclock)
    load_harness.replay(
        wave_sched,
        load_harness.synth_trace(traffic, model.cfg.vocab_size),
        virtual_clock=vclock)
    deltas = {f"{name}{{{tier}}}" if tier else name: v - tier_baseline.get(
        (name, tier), 0)
        for (name, tier), v in _tier_counter_totals().items()
        if v - tier_baseline.get((name, tier), 0)}
    ratio = (tiered["max_concurrent"] / untiered["max_concurrent"]
             if untiered["max_concurrent"] else 0.0)
    assert ratio >= ratio_gate, \
        f"tiered arm concurrency {tiered['max_concurrent']} vs untiered " \
        f"{untiered['max_concurrent']} = {ratio:.2f}x < {ratio_gate}x " \
        f"at the identical {num_blocks}-block pool"
    assert deltas.get("serving_kv_tier_demote_total{host}", 0) > 0 \
        and deltas.get("serving_kv_tier_promote_total{host}", 0) > 0, \
        f"tier plane idle over the replay (demote/promote deltas " \
        f"{deltas}): the concurrency ratio above is vacuous without " \
        f"chains actually cycling through the cold tiers"
    assert tiered["trace_counts"]["decode"] == 1, \
        f"tiering-enabled decode recompiled: " \
        f"{tiered['trace_counts']['decode']} traces (want 1)"
    recon_msgs = _kvl.LedgerReconciler(
        eng.kv_ledger, eng.block_pool, eng.prefix_cache,
        tier_store=eng.kv_tiers).check()
    assert not recon_msgs, \
        f"end-of-run tier reconciliation diverged: {recon_msgs[:3]}"
    diverged = {k: v - div_baseline.get(k, 0)
                for k, v in _kv_divergence_totals().items()
                if v - div_baseline.get(k, 0)}
    assert not diverged, \
        f"reconciler latched divergences during the tiered replay " \
        f"(cross-tier leak): {diverged}"
    assert eng.trace_counts.get("tier_restore", 0) == 1, \
        f"tier restore scatter traced " \
        f"{eng.trace_counts.get('tier_restore', 0)}x over the " \
        f"replay + cold-return wave (want exactly 1 — one fixed-shape " \
        f"program serves every run length)"
    # --- cold-chain TTFT: restore vs recompute, on a dedicated engine
    # pair sized for a SYSTEM-PROMPT-grade prefix — the workload the
    # hierarchy exists for. Restore cost is one compiled scatter + a
    # suffix-only prefill, flat in the prefix length; recompute pays
    # the full forward
    mb_max_len = int(os.environ.get("BENCH_SERVE_TIER_MB_MAXLEN", 256))
    pblocks = int(os.environ.get("BENCH_SERVE_TIER_PREFIX_BLOCKS",
                                 mb_max_len // block - 2))
    plen = pblocks * block
    mb_blocks = pblocks + 4
    teng = load_harness.build_engine(
        model, "paged", 2, mb_max_len, block_size=block,
        num_blocks=mb_blocks, attention_impl=attention_impl,
        tier_kwargs=dict(enable_kv_tiers=True,
                         host_tier_blocks=2 * mb_blocks))
    oracle = load_harness.build_engine(
        model, "paged", 2, mb_max_len, block_size=block,
        num_blocks=mb_blocks, prefix_cache=False,
        attention_impl=attention_impl)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, model.cfg.vocab_size, plen + 2).tolist()
    t_restore, t_recompute = [], []
    for i in range(repeats + 1):
        teng.prefill(0, prompt)              # prime the chain into HBM
        teng.reset_slot(0)
        teng.prefix_cache.evict(pblocks + 4)  # ... and demote it cold
        t0 = _time.perf_counter()
        teng.prefill(0, prompt)              # promote + suffix prefill
        dt_r = _time.perf_counter() - t0
        teng.reset_slot(0)
        t0 = _time.perf_counter()
        oracle.prefill(0, prompt)            # full forward, no cache
        dt_o = _time.perf_counter() - t0
        oracle.reset_slot(0)
        if i:                                # round 0 warms both buckets
            t_restore.append(dt_r)
            t_recompute.append(dt_o)
    assert teng.trace_counts.get("tier_restore", 0) == 1, \
        f"microbench restore scatter traced " \
        f"{teng.trace_counts.get('tier_restore', 0)}x across " \
        f"{repeats + 1} cold restores (want 1)"
    restore_s = sorted(t_restore)[len(t_restore) // 2]
    recompute_s = sorted(t_recompute)[len(t_recompute) // 2]
    assert restore_s <= restore_slack * recompute_s, \
        f"cold-chain restore {restore_s * 1e3:.2f}ms lost to recompute " \
        f"{recompute_s * 1e3:.2f}ms (slack {restore_slack}x): the tier " \
        f"restore path must beat a full prefill at {plen} prefix tokens"
    return {
        "concurrency_ratio": round(ratio, 3),
        "ratio_gate": ratio_gate,
        "tiered_max_concurrent": tiered["max_concurrent"],
        "untiered_max_concurrent": untiered["max_concurrent"],
        "tier_counter_deltas": deltas,
        "tiered": tiered, "untiered": untiered,
        "cold_restore_ms": round(restore_s * 1e3, 3),
        "cold_recompute_ms": round(recompute_s * 1e3, 3),
        "restore_vs_recompute": round(restore_s / recompute_s, 3)
        if recompute_s else None,
        "prefix_tokens": plen,
        "decode_traces": tiered["trace_counts"]["decode"],
        "residency": eng.kv_tiers.stats(),
    }


def _spec_pp_steady_rate(model, pp_e, sp_e):
    """Steady-state decode tokens/sec: the spec×pp engine vs the
    one-token pp ring, driven on the harness arms' already-built,
    already-WARMED engines (same (tp, pp) mesh and pool budget by
    construction — no second compile bill). A few slots are re-armed
    with fresh prompts after the replay drained; BOTH engines run their
    full slot batch per pass (free lanes do the same garbage work on
    each side), and both rates count only the ACTIVE slots' tokens, so
    the asserted ratio compares identical work on identical footing.
    The spec figure counts EMITTED tokens (n_emit over active slots),
    so the acceptance rate is priced in exactly as the analytical
    (E[acc]+1)/(1+γ/L_frac) factor says — a draft that rots to zero
    acceptance loses this comparison, as it should."""
    import time as _time

    import numpy as np

    active = min(int(os.environ.get("BENCH_SERVE_SPECPP_SLOTS", 4)),
                 pp_e.slots)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, model.cfg.vocab_size, 8).tolist()
               for _ in range(active)]

    def arm(engine):
        # re-prefill resets the active slots' positions, so a repeat
        # never grows past the pool the replay was sized for
        for s, p in enumerate(prompts):
            engine.prefill(s, p)
    arm(pp_e)
    arm(sp_e)
    pp_e.decode()                                   # re-warm the ring
    sp_e.decode_many()                              # re-warm draft+verify
    steps = int(os.environ.get("BENCH_SERVE_SPECPP_STEPS", 8))
    repeats = max(int(os.environ.get("BENCH_SERVE_SPECPP_REPEATS", 3)), 1)
    # PER-CALL MEDIANS, interleaved: one ring pass is a few ms on CPU
    # and the scheduler/GC regularly lands 10x spikes inside any timing
    # window, so whole-window rates (and max-of-window racing) flip the
    # asserted ratio on noise. Alternating one pp step with one spec
    # round makes load shifts hit both sides equally, and the median of
    # steps*repeats per-call samples is immune to the spikes. Each
    # repeat re-arms and runs two UNMEASURED spec rounds so the active
    # lanes reach their greedy fixed point — the timed rounds then
    # carry STEADY-STATE acceptance, the figure the analytical pricing
    # is stated for. Both entry points run ensure_decode_capacity
    # themselves — no extra host work charged to either side.
    t_pp, t_sp, emitted = [], [], []
    for _ in range(repeats):
        arm(pp_e)
        arm(sp_e)
        for _ in range(2):                          # converge, untimed
            sp_e.decode_many()
        for _ in range(steps):
            t0 = _time.perf_counter()
            pp_e.decode()
            t_pp.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            _, n_emit = sp_e.decode_many()
            t_sp.append(_time.perf_counter() - t0)
            emitted.append(int(n_emit[:active].sum()))
    pp_rate = active / sorted(t_pp)[len(t_pp) // 2]
    sp_rate = (sum(emitted) / len(emitted)) \
        / sorted(t_sp)[len(t_sp) // 2]
    return {"pp_tokens_per_s": round(pp_rate, 2),
            "spec_pp_tokens_per_s": round(sp_rate, 2),
            "slots": active, "steps": steps, "repeats": repeats}


def run_serve_dist_bench(on_tpu, n_requests=None, pp_stages=None,
                         gray_chaos=False):
    """Multi-host serving rung (ISSUE 10): the same traffic through (a)
    ONE paged scheduler in this process and (b) a forked 1-prefill +
    N-decode worker fleet behind the router, at EQUAL allocatable KV
    budget (the single process gets the fleet's summed usable blocks).
    Metric = the distributed arm's replay tokens/sec; vs_baseline =
    dist/single tokens-per-sec ratio (the disaggregation overhead
    figure — expect <1 off-chip, where RPC+adoption costs are not
    amortized by real accelerator prefill times). Extra carries both
    arms' p50/p99 TTFT, handoff bytes, and the compile-once counters;
    the streams of the two arms are ASSERTED identical, so the rung can
    never trade correctness for throughput.

    `gray_chaos` (ISSUE 20, --gray-chaos) adds a THIRD arm: the same
    traffic through a fresh fleet whose LAST decode worker serves every
    RPC through a jittered sleep (PTN_FAULTS serving.rpc.serve=slow in
    its env — its own process, so no target scoping is needed). The
    health plane must notice (suspicion -> migration off the victim),
    the streams must STILL be bit-identical to the single-process arm,
    and extra.gray_chaos records the migration latency p99 (from the
    migrate decisions' outcomes) and the deadline-miss delta vs the
    healthy arm — the number the acceptance gate wants at ~0.

    Fleet observability artifacts (ISSUE 12): the distributed arm runs
    under a FleetPlane — the router's poll loop federates every
    worker's full metrics registry over OP_METRICS into
    `fleet_metrics.jsonl` + ONE merged Prometheus exposition
    (`fleet_metrics.prom`), and every request's end-to-end phase
    timeline lands in `timelines.jsonl` (written under
    $BENCH_DIST_OBS_DIR, default the rung's workdir). The rung asserts
    each completed request has a timeline record whose phase durations
    sum to within 5%% of its end-to-end latency."""
    if on_tpu:
        # one process owns a chip: this process holds it for arm 1, and
        # the 1+N forked workers of arm 2 would each wait on it until
        # their endpoint timeout. Say so in the first second.
        raise RuntimeError(
            "--serve-dist is CPU-only today: it forks 1+N worker "
            "processes that each initialise jax, and on a TPU backend "
            "the chip already belongs to this process. A "
            "one-process-per-chip fleet is a later issue (README, "
            "'One process per chip').")
    import json as _json
    import subprocess
    import tempfile

    import jax

    import paddle_tpu
    from paddle_tpu.serving import (PagedEngineConfig,
                                    PagedGenerationEngine, Scheduler,
                                    ServingConfig)
    from paddle_tpu.observability import fleet as _fleet
    from paddle_tpu.serving.distributed import DistFrontend

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import serve_report

    model_name = os.environ.get("BENCH_DIST_MODEL",
                                "gpt_125m" if on_tpu else "gpt_tiny")
    seed = int(os.environ.get("BENCH_DIST_SEED", 2024))
    slots = int(os.environ.get("BENCH_DIST_SLOTS", 4 if on_tpu else 2))
    max_len = int(os.environ.get("BENCH_DIST_MAXLEN",
                                 512 if on_tpu else 64))
    block = int(os.environ.get("BENCH_DIST_BLOCK", 16 if on_tpu else 8))
    n_decode = int(os.environ.get("BENCH_DIST_DECODE_WORKERS", 2))
    requests = n_requests or int(os.environ.get("BENCH_DIST_REQUESTS",
                                                32 if on_tpu else 8))
    max_new = int(os.environ.get("BENCH_DIST_MAXNEW", 16 if on_tpu else 6))
    prompt_len = int(os.environ.get("BENCH_DIST_PROMPT",
                                    64 if on_tpu else 8))
    # --pp-stages / $BENCH_DIST_PP_STAGES (ISSUE 13): each decode
    # worker GROUP serves a pipeline-parallel engine over its local
    # devices (tensor degree per stage via $BENCH_DIST_TP). The KV
    # budget math is unchanged — block tables and the allocator are
    # shared across a group's stages, so num_blocks means the same
    # thing in both engine kinds.
    pp_stages = pp_stages if pp_stages is not None else \
        int(os.environ.get("BENCH_DIST_PP_STAGES", 0)) or None
    worker_cfg = {"slots": slots, "max_len": max_len, "block_size": block}
    per_worker = PagedEngineConfig(**worker_cfg)
    engine_kind = "paged"
    if pp_stages:
        engine_kind = "pp"
        worker_cfg = dict(worker_cfg, pp=int(pp_stages),
                          tp=int(os.environ.get("BENCH_DIST_TP", 1)))
    # equal ALLOCATABLE budget: each worker reserves its own garbage
    # block, so the single process gets the summed usable blocks + one
    single_blocks = n_decode * (per_worker.num_blocks - 1) + 1
    budget_tokens = n_decode * (per_worker.num_blocks - 1) * block

    rng = np.random.RandomState(0)
    paddle_tpu.seed(seed)
    from paddle_tpu.text import models as _models
    model = getattr(_models, model_name)()
    model.eval()
    vocab = model.cfg.vocab_size
    prompts = [rng.randint(0, vocab, prompt_len).tolist()
               for _ in range(requests)]

    def _summary(ttfts, tokens_total, wall_s, extra):
        out = {"tokens_per_s": tokens_total / wall_s if wall_s else 0.0,
               "tokens_total": tokens_total, "wall_s": round(wall_s, 4),
               "ttft_p50_s": serve_report._pct(ttfts, 0.50),
               "ttft_p99_s": serve_report._pct(ttfts, 0.99),
               "requests_done": len(ttfts)}
        out.update(extra)
        return out

    # ---- arm 1: single process ------------------------------------------
    engine = PagedGenerationEngine(model, PagedEngineConfig(
        slots=n_decode * slots, max_len=max_len, block_size=block,
        num_blocks=single_blocks))
    sched = Scheduler(engine, ServingConfig(
        max_queue=max(64, requests),
        default_max_new_tokens=max_new))
    t0 = time.perf_counter()
    handles = [sched.submit(p) for p in prompts]
    while sched.step():
        pass
    single_wall = time.perf_counter() - t0
    single_streams = [h.tokens for h in handles]
    single = _summary(
        [h.ttft_s for h in handles if h.ttft_s is not None],
        sum(len(t) for t in single_streams), single_wall,
        {"kv_memory_tokens": engine.kv_usable_tokens,
         "trace_counts": {"decode": engine.trace_counts["decode"]},
         "handoff_bytes": 0})

    # ---- arm 2: forked prefill + decode pools ---------------------------
    roles = ["prefill"] + ["decode"] * n_decode
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", jax.default_backend())
    if pp_stages and jax.default_backend() == "cpu" and \
            "host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        # a pp worker group needs pp*tp local devices; on the CPU
        # backend those are virtual
        need = int(pp_stages) * int(worker_cfg.get("tp", 1))
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{max(need, 1)}").strip()

    def _fork_fleet(workdir, victim_faults=None):
        """Fork the 1-prefill + N-decode fleet into `workdir` and wait
        for every worker's endpoint. `victim_faults` arms the LAST
        decode worker's fault sites via PTN_FAULTS (it is its own
        process, so no target scoping is needed). Returns
        (procs, endpoints)."""
        procs, ep_files = [], []
        for i, role in enumerate(roles):
            ep = os.path.join(workdir, f"ep_{i}")
            wenv = env
            if victim_faults and i == len(roles) - 1:
                wenv = dict(env, PTN_FAULTS=victim_faults)
            procs.append(subprocess.Popen(
                [sys.executable, "-m",
                 "paddle_tpu.serving.distributed.worker_main",
                 "--role", role,
                 "--engine", engine_kind if role == "decode" else "paged",
                 "--model", model_name, "--seed", str(seed),
                 "--index", str(i),
                 "--engine-config", _json.dumps(
                     worker_cfg if role == "decode"
                     else {"slots": slots, "max_len": max_len,
                           "block_size": block}),
                 "--serving-config", _json.dumps(
                     {"max_queue": max(64, requests),
                      "default_max_new_tokens": max_new}),
                 "--endpoint-file", ep],
                env=wenv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
            ep_files.append(ep)
        endpoints = []
        for proc, ep in zip(procs, ep_files):
            deadline = time.time() + 300
            while not os.path.exists(ep):
                if proc.poll() is not None:
                    _, err = proc.communicate()
                    raise RuntimeError(
                        f"serve-dist worker died:\n{err[-4000:]}")
                if time.time() > deadline:
                    raise TimeoutError("serve-dist worker never "
                                       "published its endpoint")
                time.sleep(0.05)
            with open(ep) as f:
                endpoints.append(f.read().strip())
        return procs, endpoints

    def _router_misses():
        """In-process (router-side) serving_deadline_missed_total sum —
        the router rides THIS process's registry, which persists across
        arms, so callers take before/after deltas."""
        from paddle_tpu.observability import metrics as _obs_metrics
        flat = _obs_metrics.flatten_snapshot(
            _obs_metrics.registry().snapshot(), kinds=("counter",))
        return sum(v for k, v in flat.items()
                   if k.startswith("serving_deadline_missed_total"))

    def _worker_misses(merged):
        """Worker-side deadline misses out of a fleet-merged snapshot
        (fresh worker processes per arm, so absolute == delta)."""
        total = 0.0
        for m in merged["metrics"]:
            if m["name"] != "serving_deadline_missed_total":
                continue
            for s in m["samples"]:
                if (s.get("labels") or {}).get("worker_id") != "router":
                    total += s["value"]
        return total

    # every request carries a (generous) deadline when the gray-chaos
    # arm runs, so the healthy arm is the miss-delta baseline
    req_timeout = float(os.environ.get("BENCH_DIST_REQ_TIMEOUT_S", 120))
    workdir = tempfile.mkdtemp(prefix="bench_serve_dist_")
    procs, endpoints = _fork_fleet(workdir)
    fe = None
    healthy_misses = 0.0
    misses_before = _router_misses()
    try:
        obs_dir = os.environ.get("BENCH_DIST_OBS_DIR") \
            or os.path.join(workdir, "obs")
        fe = DistFrontend(endpoints[1:], [endpoints[0]],
                          timeline_path=os.path.join(obs_dir,
                                                     "timelines.jsonl"))
        plane = _fleet.FleetPlane(
            fe, jsonl_path=os.path.join(obs_dir, "fleet_metrics.jsonl"),
            poll_interval_s=0.2)
        t0 = time.perf_counter()
        reqs = [fe.submit(p, max_new=max_new,
                          timeout_s=req_timeout if gray_chaos else None)
                for p in prompts]
        fe.run(timeout_s=float(os.environ.get("BENCH_DIST_TIMEOUT_S",
                                              600)))
        dist_wall = time.perf_counter() - t0
        # final federation sweep (workers still alive) + the ONE merged
        # fleet Prometheus exposition
        merged = plane.poll_now()
        plane.write_prometheus(os.path.join(obs_dir,
                                            "fleet_metrics.prom"))
        healthy_misses = (_router_misses() - misses_before) \
            + _worker_misses(merged)
        bad = [r for r in reqs if r.status != "DONE"]
        assert not bad, f"{len(bad)} dist requests not DONE: " \
                        f"{[(r.key, r.status, r.error) for r in bad[:3]]}"
        # correctness gate: both arms must emit the SAME greedy streams
        assert [r.tokens for r in reqs] == single_streams, \
            "distributed streams diverged from the single-process arm"
        stats = fe.stats()
        handoff = sum(s.get("handoff_bytes", 0) for s in stats.values())
        dist_budget = sum(s.get("kv_usable_tokens", 0)
                          for s in stats.values()
                          if s.get("role") == "decode")
        staged = sum(1 for r in reqs if r.staged)
        # ISSUE 12 gates: every completed request decomposes — one
        # timeline record each, phase durations summing to e2e within
        # the 5% acceptance tolerance — and the federated snapshot
        # carries every fleet member under worker_id labels
        timelines = fe.timeline_records()
        assert len(timelines) == len(reqs), \
            f"{len(timelines)} timeline records for {len(reqs)} requests"
        tl_errs = serve_report.validate_records(timelines)
        assert not tl_errs, \
            f"timeline contract violations: {tl_errs[:3]}"
        fleet_members = {s2.get("labels", {}).get("worker_id")
                         for m2 in merged["metrics"]
                         for s2 in m2["samples"]}
        want_members = {f"decode{i}" for i in range(n_decode)} \
            | {"prefill0", "router"}
        assert want_members <= fleet_members, \
            f"fleet snapshot missing members: " \
            f"{want_members - fleet_members}"
        phase_means = serve_report.timeline_phase_means(timelines)
        dist = _summary(
            [r.ttft_s for r in reqs if r.ttft_s is not None],
            sum(len(r.tokens) for r in reqs), dist_wall,
            {"kv_memory_tokens": dist_budget, "handoff_bytes": handoff,
             "staged_requests": staged, "decode_workers": n_decode,
             "engine": engine_kind, "pp_stages": pp_stages,
             "fleet_polls": plane.polls, "obs_dir": obs_dir,
             "timeline_phase_means_s": phase_means,
             "tail_attribution": serve_report.tail_attribution(
                 timelines)})
        assert staged > 0, "no request rode the prefill->decode handoff"
        assert dist_budget == budget_tokens == single["kv_memory_tokens"]
    finally:
        if fe is not None:
            # stop on EVERY path — a failed assert must not leave the
            # fleet serving until the per-process wait timeouts expire
            try:
                fe.stop_workers()
            except Exception:                            # noqa: BLE001
                pass
            fe.close()
        for proc in procs:
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()

    # ---- arm 3 (optional): gray-chaos fleet -----------------------------
    chaos = None
    if gray_chaos:
        slow_s = float(os.environ.get("BENCH_DIST_CHAOS_SLOW_S", 0.25))
        cworkdir = tempfile.mkdtemp(prefix="bench_serve_dist_chaos_")
        cprocs, cendpoints = _fork_fleet(
            cworkdir,
            victim_faults=f"serving.rpc.serve=slow:delay={slow_s}:seed=7")
        cfe = None
        c_before = _router_misses()
        try:
            cfe = DistFrontend(
                cendpoints[1:], [cendpoints[0]],
                health_interval_s=0.1,
                timeline_path=os.path.join(cworkdir, "timelines.jsonl"))
            cplane = _fleet.FleetPlane(
                cfe,
                jsonl_path=os.path.join(cworkdir, "fleet_metrics.jsonl"),
                poll_interval_s=0.2)
            t0 = time.perf_counter()
            creqs = [cfe.submit(p, max_new=max_new, timeout_s=req_timeout)
                     for p in prompts]
            cfe.run(timeout_s=float(os.environ.get("BENCH_DIST_TIMEOUT_S",
                                                   600)))
            chaos_wall = time.perf_counter() - t0
            cmerged = cplane.poll_now()
            bad = [r for r in creqs if r.status != "DONE"]
            assert not bad, \
                f"{len(bad)} gray-chaos requests not DONE: " \
                f"{[(r.key, r.status, r.error) for r in bad[:3]]}"
            assert [r.tokens for r in creqs] == single_streams, \
                "gray-chaos streams diverged from the single-process arm"
            mig_lat = sorted(
                rec["outcome"].get("latency_s") or 0.0
                for rec in cfe.decision_records()
                if rec["action"] == "migrate"
                and rec["outcome"].get("migrated"))
            chaos_misses = (_router_misses() - c_before) \
                + _worker_misses(cmerged)
            chaos = {
                "wall_s": round(chaos_wall, 4),
                "victim": cendpoints[-1], "slow_s": slow_s,
                "migrations": len(mig_lat),
                "migration_latency_p99_s":
                    serve_report._pct(mig_lat, 0.99) if mig_lat else None,
                "deadline_misses": chaos_misses,
                "deadline_miss_delta_vs_healthy":
                    chaos_misses - healthy_misses,
                "streams_identical": True,
            }
        finally:
            if cfe is not None:
                try:
                    cfe.stop_workers()
                except Exception:                        # noqa: BLE001
                    pass
                cfe.close()
            for proc in cprocs:
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()

    ratio = (dist["tokens_per_s"] / single["tokens_per_s"]
             if single["tokens_per_s"] else 0.0)
    extra = {"metric_name": "serve_dist_tokens_per_s",
             "model": model_name, "requests": requests,
             "max_new": max_new, "dist": dist, "single": single,
             "streams_identical": True,
             "backend": jax.default_backend()}
    if chaos is not None:
        extra["gray_chaos"] = chaos
        extra["dist"]["deadline_misses"] = healthy_misses
    return {
        "value": dist["tokens_per_s"],
        "vs_baseline": round(ratio, 3),   # dist/single tokens-per-sec
        "extra": extra,
    }


def run_cold_start_child(artifact):
    """One measured serving process of the --cold-start rung: build a
    Predictor over `artifact` (AOT warmup included — against a warm
    cache that is deserialization, cold it is compilation) and serve one
    token. Prints ONE JSON line the parent parses; exit code carries
    success."""
    import paddle_tpu  # noqa: F401  (registers the framework)
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.observability import metrics as _obs_metrics

    proc_t0 = float(os.environ.get("BENCH_CHILD_T0", 0) or 0)
    prompt = list(range(1, 1 + int(os.environ.get("BENCH_COLDSTART_PROMPT",
                                                  4))))
    t0 = time.perf_counter()
    pred = create_predictor(Config(artifact + ".pdmodel",
                                   artifact + ".pdiparams"))
    ready_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    out = pred.generate([prompt], max_new_tokens=1)
    ttft_s = time.perf_counter() - t1
    engine = pred._gen_sched.engine
    cache = engine.compile_cache
    _obs_metrics.gauge(
        "serving_cold_start_ttft_seconds",
        "Predictor build + first generated token, one process"
    ).set(ready_s + ttft_s)
    rec = {
        "executable_ready_s": round(ready_s, 4),
        "ttft_s": round(ttft_s, 4),
        "total_s": round(ready_s + ttft_s, 4),
        "process_total_s": round(time.time() - proc_t0, 4) if proc_t0
        else None,
        "first_token": int(out[0][0]),
        "trace_counts": {
            k: (dict(v) if isinstance(v, dict) else v)
            for k, v in engine.trace_counts.items()},
        "compile_cache": dict(cache.stats) if cache is not None else None,
        "compile_cache_dir": cache.path if cache is not None else None,
    }
    print(json.dumps(rec))
    sys.stdout.flush()


def run_cold_start_build():
    """The --cold-start rung's first child: decide the sizes from the
    device this process attaches, build the serving artifact, and EMPTY
    the rung's compile cache so the next child is cold. Everything lives
    at a fixed place under the one cache root
    (framework/compile_cache.cache_root) — never a temporary directory,
    which would move the cache on every run. Prints ONE JSON line."""
    import shutil

    import paddle_tpu  # noqa: F401
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.serving import EngineConfig, save_for_generation
    from paddle_tpu.text import models as _models

    device = attached_device()
    on_tpu = device["platform"] == "tpu"
    model_name = os.environ.get("BENCH_COLDSTART_MODEL",
                                "gpt_125m" if on_tpu else "gpt_tiny")
    slots = int(os.environ.get("BENCH_COLDSTART_SLOTS", 4 if on_tpu else 2))
    max_len = int(os.environ.get("BENCH_COLDSTART_MAXLEN",
                                 256 if on_tpu else 32))
    workdir = os.environ.get("BENCH_COLDSTART_DIR") or os.path.join(
        compile_cache.cache_root(), "cold_start_rung")
    cache_dir = os.path.join(workdir, "cache")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(os.path.join(workdir, "artifact"), exist_ok=True)
    artifact = os.path.join(workdir, "artifact", "gpt")
    model = getattr(_models, model_name)()
    model.eval()
    # the artifact records WHAT to serve; the measured children decide
    # when the compiling happens — precompile stays False so nothing this
    # process compiles can leak into the cold child's measurement
    save_for_generation(model, artifact,
                        engine_config=EngineConfig(slots=slots,
                                                   max_len=max_len),
                        precompile=False)
    print(json.dumps({"artifact": artifact, "cache_dir": cache_dir,
                      "workdir": workdir, "model": model_name,
                      "slots": slots, "max_len": max_len,
                      "device": device}))
    sys.stdout.flush()


def run_cold_start_bench():
    """Cold-start rung. THIS process never touches jax: one process owns
    a chip, so a parent that had initialised it would starve every
    child. Three children run one after another, each exiting before the
    next starts: a BUILD child (artifact + emptied rung cache), then the
    SAME measured command twice — against the empty compile cache (cold:
    every serving executable compiles and commits), then against the
    populated one (warm: every executable deserializes). Both measured
    children get the rung's cache as $JAX_COMPILATION_CACHE_DIR, so jax's
    cache and the executable entries land in one fixed place. value =
    warm executable-ready seconds; vs_baseline = cold/warm ready ratio
    (>1 is the cache's win). The warm child's zero-compile contract is
    ASSERTED, not just reported — a rung whose warm process still
    compiles must fail."""
    global DEVICE
    assert "jax" not in sys.modules, \
        "--cold-start parent imported jax: it would hold the chip"

    def child(tag, argv, env=None):
        env = dict(env or os.environ, BENCH_CHILD_T0=repr(time.time()))
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv,
            capture_output=True, text=True, env=env,
            timeout=float(os.environ.get("BENCH_RUNG_BUDGET_S", 900)))
        if out.returncode != 0:
            raise RuntimeError(f"{tag} cold-start child failed "
                               f"(rc={out.returncode}): "
                               f"{out.stderr[-1000:]}")
        return json.loads(out.stdout.strip().splitlines()[-1])

    built = child("build", ["--cold-start-build"])
    DEVICE = built["device"]
    measured_env = dict(os.environ,
                        JAX_COMPILATION_CACHE_DIR=built["cache_dir"])
    argv = ["--cold-start-child", built["artifact"]]
    cold = child("cold", argv, measured_env)
    warm = child("warm", argv, measured_env)
    # the contract, asserted: a warm restart performs ZERO fresh
    # compilations for the serving executable set
    warm_traces = warm["trace_counts"]
    fresh = warm_traces["decode"] + sum(warm_traces["prefill"].values()) \
        + warm_traces.get("spec_verify", 0) \
        + warm_traces.get("draft_decode", 0) \
        + sum(warm_traces.get("draft_prefill", {}).values())
    assert fresh == 0, f"warm child traced {warm_traces}"
    assert warm["compile_cache"]["misses"] == 0, warm["compile_cache"]
    assert warm["compile_cache"]["hits"] > 0, warm["compile_cache"]
    assert warm["first_token"] == cold["first_token"], \
        "warm executable decoded a different token than the cold compile"
    ratio = cold["executable_ready_s"] / warm["executable_ready_s"] \
        if warm["executable_ready_s"] else 0.0
    return {
        "value": warm["executable_ready_s"],
        "vs_baseline": round(ratio, 3),   # cold/warm ready-time ratio
        "extra": {"metric_name": "cold_start_warm_ready_s",
                  "model": built["model"], "slots": built["slots"],
                  "max_len": built["max_len"],
                  "artifact_dir": built["workdir"],
                  "cache_dir": built["cache_dir"],
                  "cold": cold, "warm": warm,
                  "warm_beats_cold":
                      warm["executable_ready_s"]
                      < cold["executable_ready_s"],
                  "backend": DEVICE["platform"]},
    }


def _run_rung(metric, unit, what, fn, budget_s):
    """One rung under its wall-clock watchdog: name the metric, run,
    emit. A failure propagates to __main__ (failure record, exit 1)."""
    global METRIC, UNIT
    METRIC, UNIT = metric, unit
    wd = start_watchdog(budget_s, what)
    try:
        result = fn()
        emit(result["value"], result["vs_baseline"], extra=result["extra"])
    finally:
        wd.cancel()


def main(argv=None):
    global _PROFILE_DIR, _XPLANE_CTRL, METRIC, UNIT, DEVICE
    args = _parse_args(argv or [])
    if args.cold_start_child:
        run_cold_start_child(args.cold_start_child)
        return
    if args.cold_start_build:
        run_cold_start_build()
        return
    if args.cold_start:
        # before ANY jax import: this parent must never hold the chip
        _run_rung("gpt_cold_start_warm_ready_s",
                  "seconds to serving-ready (warm-cache process)",
                  "cold-start rung", run_cold_start_bench,
                  3 * float(os.environ.get("BENCH_RUNG_BUDGET_S", 900)))
        return
    if args.profile:
        _PROFILE_DIR = args.profile_dir

    # the device is decided HERE, in the process that will use it
    DEVICE = attached_device()
    on_tpu = DEVICE["platform"] == "tpu"
    import jax

    # from here paddle_tpu will load: keep the last spans + metrics in a
    # ring so every watchdog/crash path below has forensics to dump
    import paddle_tpu  # noqa: F401
    from paddle_tpu.framework import compile_cache as _compile_cache
    from paddle_tpu.observability import flight_recorder as _fr
    _compile_cache.place()
    _fr.enable(capacity=int(os.environ.get("BENCH_FR_CAPACITY", 512)),
               install_signal_handler=True)

    if args.xplane is not None:
        # arm BEFORE any work: from this point the flight recorder's
        # annotations carry {state: armed}, so even a hang before the
        # healthy window leaves the capture's fate in the postmortem
        from paddle_tpu.observability import deviceprof as _dp
        xdir = args.xplane if args.xplane != "__default__" \
            else os.path.join(args.profile_dir, "xplane")
        _XPLANE_CTRL = _dp.OneShotCapture(xdir, label="bench")

    # test hook (tests/test_observability.py): simulate a hung rung —
    # block inside an open span until the rung watchdog fires, and assert
    # the failure record points at a real postmortem artifact
    wedge_s = float(os.environ.get("BENCH_INJECT_WEDGE_S", 0) or 0)
    if wedge_s:
        from paddle_tpu.profiler import RecordEvent, TracerEventType
        with RecordEvent("bench.pre_wedge_setup",
                         TracerEventType.UserDefined):
            pass                        # a closed span for the ring
        start_watchdog(wedge_s, "test-injected wedge")
        with RecordEvent("bench.wedged_probe", TracerEventType.UserDefined):
            time.sleep(3600)            # the watchdog ends the process
        return

    rung_budget = float(os.environ.get("BENCH_RUNG_BUDGET_S", 900))
    if args.decode:
        _run_rung("gpt_decode_tokens_per_s", "decode tokens/sec",
                  "decode rung",
                  lambda: run_decode_bench(on_tpu, n_steps=args.steps),
                  rung_budget)
        return
    if args.serve_load:
        _run_rung("gpt_serve_load_tokens_per_s",
                  "replay decode tokens/sec (paged engine)",
                  "serve-load rung", lambda: run_serve_load_bench(on_tpu),
                  rung_budget)
        return
    if args.serve_dist:
        _run_rung("gpt_serve_dist_tokens_per_s",
                  "replay decode tokens/sec (distributed worker fleet)",
                  "serve-dist rung",
                  lambda: run_serve_dist_bench(on_tpu,
                                               pp_stages=args.pp_stages,
                                               gray_chaos=args.gray_chaos),
                  rung_budget)
        return

    explicit = "BENCH_B" in os.environ or "BENCH_REMAT" in os.environ
    if not on_tpu:
        if not explicit:
            # no chip -> fail, never a CPU run under the MFU metric's name
            raise RuntimeError(
                f"no TPU: jax attached {DEVICE}. {METRIC} is measured on "
                f"the chip only; an explicit BENCH_B/BENCH_REMAT config "
                f"runs the pipeline off the chip as a dry run under "
                f"{DRYRUN_METRIC!r}.")
        METRIC, UNIT = DRYRUN_METRIC, DRYRUN_UNIT

    n_steps = args.steps if args.steps is not None else \
        int(os.environ.get("BENCH_STEPS", 30 if on_tpu else 3))
    S = int(os.environ.get("BENCH_S", 1024 if on_tpu else 128))
    scan_k = int(os.environ.get("BENCH_K", 10 if on_tpu else 1))

    parity = {}
    if on_tpu and os.environ.get("BENCH_SKIP_PREFLIGHT") != "1":
        wd = start_watchdog(rung_budget, "flash parity preflight")
        try:
            # a crash propagates; a divergence is a failure too — timing
            # a wrong kernel measures nothing
            parity = flash_parity_preflight(S)
            if not parity["flash_parity_ok"]:
                raise RuntimeError(f"Pallas flash attention diverges from "
                                   f"the XLA reference: {parity}")
        finally:
            wd.cancel()

    def finish(result, rung=None):
        extra = result["extra"]
        extra.update(parity)
        if rung:
            extra["ladder_rung"] = rung
        emit(result["value"], result["vs_baseline"], extra=extra)

    if explicit:
        # explicit config: no ladder, fail loudly
        B = int(os.environ.get("BENCH_B", 16 if on_tpu else 2))
        remat = os.environ.get("BENCH_REMAT", "dots" if on_tpu else "full")
        fused = os.environ.get("BENCH_FUSED_CE") == "1"
        wd = start_watchdog(rung_budget, f"explicit config B={B}")
        try:
            finish(run_config(B, S, remat, n_steps, on_tpu, scan_k,
                              fused_ce=fused))
        finally:
            wd.cancel()
        return

    # Two-phase ladder for the 16GB chip (leaves with ROADMAP A1's fixed
    # cells). Phase 1 races the near-best configs and reports the FASTEST
    # that fits; Phase 2 is the OOM step-down tail where first-success
    # wins. rung = (B, remat, fused_ce). fused_ce chunks the LM-head loss
    # so the multi-GB f32 logits never materialize. A rung may fail by
    # running out of device memory — that is what the ladder is for —
    # and by nothing else: any other failure ends the run.
    race = [(16, "dots", True), (12, "dots", True), (12, "dots", False),
            (12, "dots+attn", False)]
    tail = [(8, "dots", True), (8, "dots", False), (8, "dots+attn", False),
            (8, "full", False), (4, "full", False), (2, "full", False)]
    best, contenders, errors = None, {}, []
    for B, remat, fused in race:
        rung_name = f"B={B},remat={remat}" + (",fused_ce" if fused else "")
        wd = start_watchdog(rung_budget, f"race rung {rung_name}")
        try:
            try:
                result = run_config(B, S, remat, n_steps, on_tpu, scan_k,
                                    fused_ce=fused)
                contenders[rung_name] = result["extra"]["step_ms"]
                if best is None or result["value"] > best[0]["value"]:
                    best = (result, rung_name)
            except Exception as e:          # noqa: BLE001
                if not _is_oom(e):
                    raise
                errors.append((rung_name, e))
                print(f"bench: race rung {rung_name} out of memory: "
                      f"{str(e)[:200]}", file=sys.stderr)
            # free the finished rung's executable + live buffers before the
            # next rung compiles: both race configs are near the 16GB limit,
            # and a retained previous rung would turn a fitting config into
            # a false OOM
            gc.collect()
            jax.clear_caches()
        finally:
            wd.cancel()
    if best is not None:
        result, rung_name = best
        result["extra"]["race"] = contenders
        if errors:
            result["extra"]["race_oom"] = {
                r: f"{type(e).__name__}: {str(e)[:300]}" for r, e in errors}
        finish(result, rung=rung_name)
        return
    last_err = None
    for B, remat, fused in tail:
        rung_name = f"B={B},remat={remat}" + (",fused_ce" if fused else "")
        wd = start_watchdog(rung_budget, f"ladder rung {rung_name}")
        try:
            result = run_config(B, S, remat, n_steps, on_tpu, scan_k,
                                fused_ce=fused)
            wd.cancel()
            finish(result, rung=rung_name)
            return
        except Exception as e:          # noqa: BLE001
            wd.cancel()
            if not _is_oom(e):
                raise
            last_err = f"{rung_name}: {str(e)[:500]}"
            print(f"bench: out of memory at {rung_name}; stepping down",
                  file=sys.stderr)
            gc.collect()
            jax.clear_caches()
    raise RuntimeError(f"all ladder rungs ran out of memory; last: "
                       f"{last_err}")


if __name__ == "__main__":
    try:
        main(sys.argv[1:])
    except SystemExit:      # argparse --help / usage error, not a bench fail
        raise
    except BaseException as e:                               # noqa: BLE001
        err = f"{type(e).__name__}: {str(e)[:600]}"
        # the record carries the flight-recorder artifact + last metrics,
        # never a bare 0.0 — and the exit code says it is a failure
        emit_failure(err, extra=_postmortem_extra(err))
        sys.exit(1)
