"""Eager (per-op dispatch) training-loop benchmark — SURVEY §7 hard part #1.

The reference's default UX is eager (paddle/fluid/eager/ exists to make
per-op dispatch fast). Here every eager op goes through the per-op
executable cache (core/tensor.py): first use compiles one XLA program per
op, later uses dispatch the cached executable. This benchmark measures the
end-to-end cost of that dispatch on the CURRENT backend for a small MLP
train step (fwd + bwd + SGD), against the same math as ONE jit program.

Prints one JSON line:
  {"metric": "eager_mlp_step_ms", ..., "extra": {"jit_step_ms", "ratio",
   "cache": {...}}}

Same honest-sync rules as bench.py: a host fetch of a step-dependent value
closes every timed iteration.
"""
import json
import os
import time

import numpy as np


def main():
    import bench
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.core.tensor import _CACHE_STATS
    from paddle_tpu.framework import compile_cache

    # the device is whatever jax attaches in THIS process (one process per
    # chip: nothing probes it from a child first)
    device = bench.attached_device()
    backend = device["platform"]
    compile_cache.place()
    # run-phase watchdog: the wall-clock bound on a hung measurement
    # (bench.py per-rung pattern; exits non-zero). Cancelled in main's
    # finally so the BaseException handler never races a second failure
    # line out of the timer thread.
    global _run_wd
    _run_wd = bench.start_watchdog(
        float(os.environ.get("BENCH_RUNG_BUDGET_S", 900)),
        "eager bench run", on_fire=_emit_failure)
    B, D, H, C = 256, 64, 256, 8
    rng = np.random.RandomState(0)
    x_np = rng.rand(B, D).astype("float32")
    y_np = rng.randint(0, C, B)

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(D, H), nn.ReLU(),
                        nn.Linear(H, H), nn.ReLU(), nn.Linear(H, C))
    o = opt.SGD(0.05, parameters=net.parameters())
    lf = nn.CrossEntropyLoss()
    x = paddle.to_tensor(x_np)
    y = paddle.to_tensor(y_np)

    def eager_step():
        loss = lf(net(x), y)
        loss.backward()
        o.step()
        o.clear_grad()
        return float(loss)           # host fetch = sync

    n = int(os.environ.get("BENCH_EAGER_STEPS", 20))

    def time_rung(step, warmup=3, iters=n):
        for _ in range(warmup):      # warmup fills the per-op cache
            step()
        t0 = time.perf_counter()
        for _ in range(iters):
            val = step()
        return (time.perf_counter() - t0) / iters * 1000, val

    eager_ms, loss_val = time_rung(eager_step)

    # ---- model-shaped rungs: conv/BN and attention dispatch through the
    # executable cache, not just matmul+relu. Fewer iters: these steps are
    # hundreds of per-op dispatches each.
    n_model = int(os.environ.get("BENCH_EAGER_MODEL_STEPS", max(n // 4, 5)))

    class ResBlock(nn.Layer):
        def __init__(self, ch):
            super().__init__()
            self.c1 = nn.Conv2D(ch, ch, 3, padding=1)
            self.b1 = nn.BatchNorm2D(ch)
            self.c2 = nn.Conv2D(ch, ch, 3, padding=1)
            self.b2 = nn.BatchNorm2D(ch)

        def forward(self, t):
            h = paddle.nn.functional.relu(self.b1(self.c1(t)))
            return paddle.nn.functional.relu(t + self.b2(self.c2(h)))

    paddle.seed(1)
    rb = ResBlock(32)
    rb_opt = opt.SGD(0.01, parameters=rb.parameters())
    img = paddle.to_tensor(rng.rand(16, 32, 16, 16).astype("float32"))

    def resnet_step():
        loss = rb(img).mean()
        loss.backward()
        rb_opt.step()
        rb_opt.clear_grad()
        return float(loss)

    resnet_ms, _ = time_rung(resnet_step, iters=n_model)

    paddle.seed(2)
    tl = nn.TransformerEncoderLayer(d_model=128, nhead=4,
                                    dim_feedforward=256, dropout=0.0)
    tl_opt = opt.SGD(0.01, parameters=tl.parameters())
    seq = paddle.to_tensor(rng.rand(8, 64, 128).astype("float32"))

    def transformer_step():
        loss = tl(seq).mean()
        loss.backward()
        tl_opt.step()
        tl_opt.clear_grad()
        return float(loss)

    transformer_ms, _ = time_rung(transformer_step, iters=n_model)

    # jit reference: identical math, one compiled program
    params = {i: (l.weight._data, l.bias._data)
              for i, l in enumerate(net) if hasattr(l, "weight")}

    @jax.jit
    def jit_step(params, xj, yj):
        def loss_fn(params):
            h = xj
            ks = sorted(params)
            for i, k in enumerate(ks):
                w, b = params[k]
                h = h @ w + b
                if i < len(ks) - 1:
                    h = jax.nn.relu(h)
            logz = jax.nn.logsumexp(h, axis=-1)
            picked = jnp.take_along_axis(h, yj[:, None], axis=-1)[:, 0]
            return jnp.mean(logz - picked)

        l, g = jax.value_and_grad(loss_fn)(params)
        new = {k: (w - 0.05 * gw, b - 0.05 * gb)
               for (k, (w, b)), (gw, gb) in
               zip(params.items(), (g[k] for k in params))}
        return l, new

    xj = jnp.asarray(x_np)
    yj = jnp.asarray(y_np)
    for _ in range(3):
        l, params = jit_step(params, xj, yj)
        _ = float(l)
    t0 = time.perf_counter()
    for _ in range(n):
        l, params = jit_step(params, xj, yj)
        _ = float(l)
    jit_ms = (time.perf_counter() - t0) / n * 1000

    print(json.dumps({
        "metric": "eager_mlp_step_ms",
        "value": round(eager_ms, 2),
        "unit": "ms per eager train step (fwd+bwd+SGD)",
        "vs_baseline": round(jit_ms / eager_ms, 4) if eager_ms else 0,
        "device": device,
        "extra": {"jit_step_ms": round(jit_ms, 2),
                  "eager_over_jit": round(eager_ms / jit_ms, 1),
                  "backend": backend, "steps": n, "loss": loss_val,
                  "rungs": {"resnet_block_ms": round(resnet_ms, 2),
                            "transformer_layer_ms": round(transformer_ms, 2),
                            "model_steps": n_model},
                  "cache": dict(_CACHE_STATS)},
    }))


def _emit_failure(error, extra=None):
    # the one-JSON-line contract holds on failure too (bench.py rule);
    # `extra` carries the watchdog's flight-recorder evidence (postmortem
    # path + last metrics snapshot) when the failure came from a wedge
    rec = {
        "metric": "eager_mlp_step_ms", "value": 0.0,
        "unit": "ms per eager train step (fwd+bwd+SGD)",
        "vs_baseline": 0.0, "error": error}
    if extra:
        rec["extra"] = extra
    print(json.dumps(rec))


_run_wd = None

if __name__ == "__main__":
    try:
        main()
    except BaseException as e:                               # noqa: BLE001
        if _run_wd is not None:
            _run_wd.cancel()
        _emit_failure(f"{type(e).__name__}: {str(e)[:600]}")
        raise SystemExit(1)             # a failure record never exits 0
    finally:
        if _run_wd is not None:
            _run_wd.cancel()
