#!/usr/bin/env python
"""Config-driven op microbenchmark.

Reference: paddle/fluid/operators/benchmark/op_tester.cc (+
op_tester_config.cc): run one op by name with configured shapes/dtypes,
report latency. Here: ops resolve from paddle_tpu.tensor / nn.functional,
each case runs jit-compiled (compile excluded) and eager.

Usage:
  python tools/op_bench.py                          # built-in suite
  python tools/op_bench.py --op matmul --shape 1024x1024,1024x1024 \
      --dtype bfloat16 --repeat 50
  python tools/op_bench.py --dsa-select             # the selection's
      # threshold: the Pallas kernel beside the XLA bisection, one
      # [512, W] query block at each prefill bucket's width W
"""
import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


DEFAULT_SUITE = [
    {"op": "matmul", "shapes": ["1024x1024", "1024x1024"]},
    {"op": "add", "shapes": ["4096x4096", "4096x4096"]},
    {"op": "softmax", "shapes": ["64x4096"]},
    {"op": "mean", "shapes": ["4096x4096"]},
    {"op": "relu", "shapes": ["4096x4096"]},
    {"op": "layer_norm", "shapes": ["64x4096"]},
]


def _parse_shape(s):
    return tuple(int(d) for d in s.split("x"))


def _resolve(op_name):
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    if op_name == "layer_norm":
        import jax.numpy as jnp

        def ln(x):
            w = paddle.to_tensor(np.ones(x.shape[-1], np.float32))
            b = paddle.to_tensor(np.zeros(x.shape[-1], np.float32))
            return F.layer_norm(x, x.shape[-1:], weight=w, bias=b)
        return ln
    for mod in (paddle, F):
        fn = getattr(mod, op_name, None)
        if fn is not None:
            return fn
    raise SystemExit(f"unknown op {op_name!r}")


def bench_case(op_name, shapes, dtype="float32", repeat=20):
    import jax

    import paddle_tpu as paddle

    fn = _resolve(op_name)
    rng = np.random.RandomState(0)
    args = [paddle.to_tensor(rng.rand(*s).astype("float32"), dtype=dtype)
            for s in shapes]

    # eager
    fn(*args)  # warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out._data)
    eager_us = (time.perf_counter() - t0) / repeat * 1e6

    # jit
    raw = [a._data for a in args]
    jfn = jax.jit(lambda *xs: fn(*[paddle.Tensor(x) for x in xs])._data)
    jax.block_until_ready(jfn(*raw))  # compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = jfn(*raw)
    jax.block_until_ready(out)
    jit_us = (time.perf_counter() - t0) / repeat * 1e6

    return {"op": op_name, "shapes": ["x".join(map(str, s)) for s in shapes],
            "dtype": dtype, "eager_us": round(eager_us, 1),
            "jit_us": round(jit_us, 1), "repeat": repeat}


# the selecting configuration's prefill buckets, its query block and topk
DSA_WIDTHS = (10240, 12288, 28672, 32768)
DSA_BLOCK, DSA_TOPK = 512, 2048


def bench_dsa_select(width, repeat=20):
    """One query block of the selecting prefill at `width` keys, the last
    (its rows see every column up to the diagonal): the time of the XLA
    passes the prefill ran before the kernel (`dsa_select_mask` over the
    causal row, the mask cut to causality) beside `row_threshold`'s, which
    reads the causal columns once and leaves the mask to a comparison."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.topk_threshold import row_threshold
    from paddle_tpu.text.models import hybrid_ops as ops

    b, k = DSA_BLOCK, DSA_TOPK
    scores = jax.random.normal(jax.random.key(0), (b, width), jnp.float32)
    causal = jnp.arange(width)[None, :] <= width - b + jnp.arange(b)[:, None]
    xla = jax.jit(lambda s: ops.dsa_select_mask(
        jnp.where(causal, s, -jnp.inf), k) & causal)
    kernel = jax.jit(lambda s: row_threshold(s, jnp.int32(width), k))

    def time_of(fn, arg):
        """(host ms a call, device ms a call): the device's from a profile
        of the calls (the programs' spans on the chip's `XLA Modules`
        line), None where there is no such line (off the TPU). A call of
        well under a millisecond is bound by its dispatch on the host."""
        jax.block_until_ready(fn(arg))                  # compile
        with tempfile.TemporaryDirectory() as trace:
            jax.profiler.start_trace(trace)
            t0 = time.perf_counter()
            for _ in range(repeat):
                out = fn(arg)
            jax.block_until_ready(out)
            host = (time.perf_counter() - t0) / repeat * 1e3
            jax.profiler.stop_trace()
            device = _device_ms(trace)
        return round(host, 4), None if device is None \
            else round(device / repeat, 4)

    xla_host, xla_device = time_of(xla, scores)
    kernel_host, kernel_device = time_of(kernel, scores)
    return {"op": "dsa_select", "shape": f"{b}x{width}", "topk": k,
            "device": jax.devices()[0].device_kind,
            "xla_passes_ms": xla_device, "kernel_ms": kernel_device,
            "xla_passes_host_ms": xla_host, "kernel_host_ms": kernel_host,
            "repeat": repeat}


def _device_ms(trace_dir):
    """Milliseconds of the programs a profile holds on its first TPU, or
    None without one."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    return sum(e.duration_ns for e in line.events) / 1e6
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op")
    ap.add_argument("--shape", help="comma-separated, e.g. 64x128,128x256")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--dsa-select", action="store_true",
                    help="the selection's threshold at the prefill widths")
    a = ap.parse_args()
    if a.dsa_select:
        for width in DSA_WIDTHS:
            print(json.dumps(bench_dsa_select(width, a.repeat)))
        return
    if a.op:
        cases = [{"op": a.op,
                  "shapes": (a.shape or "1024x1024").split(","),
                  "dtype": a.dtype}]
    else:
        cases = DEFAULT_SUITE
    for c in cases:
        res = bench_case(c["op"], [_parse_shape(s) for s in c["shapes"]],
                         c.get("dtype", a.dtype), a.repeat)
        print(json.dumps(res))


if __name__ == "__main__":
    main()
