"""geometric message passing, LBFGS/BFGS minimizers, jacobian/hessian,
op_bench tool."""
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import geometric
from paddle_tpu.incubate.optimizer.functional import (minimize_bfgs,
                                                      minimize_lbfgs)


def test_send_u_recv_sum():
    x = paddle.to_tensor(np.asarray([[1., 2.], [3., 4.], [5., 6.]],
                                    np.float32))
    src = np.asarray([0, 1, 2, 0])
    dst = np.asarray([1, 2, 1, 0])
    out = geometric.send_u_recv(x, src, dst, reduce_op="sum")
    want = np.zeros((3, 2), np.float32)
    want[1] = x.numpy()[0] + x.numpy()[2]
    want[2] = x.numpy()[1]
    want[0] = x.numpy()[0]
    np.testing.assert_array_equal(out.numpy(), want)


def test_send_u_recv_mean_max():
    x = paddle.to_tensor(np.asarray([[2.], [4.], [6.]], np.float32))
    src = np.asarray([0, 1, 2])
    dst = np.asarray([0, 0, 0])
    mean = geometric.send_u_recv(x, src, dst, reduce_op="mean", out_size=1)
    np.testing.assert_allclose(mean.numpy(), [[4.]])
    mx = geometric.send_u_recv(x, src, dst, reduce_op="max", out_size=1)
    np.testing.assert_allclose(mx.numpy(), [[6.]])


def test_send_ue_recv():
    x = paddle.to_tensor(np.asarray([[1.], [2.]], np.float32))
    e = paddle.to_tensor(np.asarray([[10.], [20.]], np.float32))
    out = geometric.send_ue_recv(x, e, np.asarray([0, 1]),
                                 np.asarray([0, 0]), message_op="add",
                                 reduce_op="sum", out_size=2)
    np.testing.assert_allclose(out.numpy(), [[33.], [0.]])


def test_segment_ops_differentiable():
    x = paddle.to_tensor(np.asarray([[1., 1.], [2., 2.], [3., 3.]],
                                    np.float32), stop_gradient=False)
    seg = np.asarray([0, 0, 1])
    out = geometric.segment_sum(x, seg)
    np.testing.assert_array_equal(out.numpy(), [[3., 3.], [3., 3.]])
    out.sum().backward()
    np.testing.assert_array_equal(x.grad.numpy(), np.ones((3, 2)))


def test_lbfgs_rosenbrock():
    def rosen(x):
        return ((1 - x[0]) ** 2 + 100 * (x[1] - x[0] ** 2) ** 2)

    res = minimize_lbfgs(rosen, paddle.to_tensor(np.asarray([-1.2, 1.0],
                                                            np.float32)),
                         max_iters=1000)
    assert bool(res.is_converge.numpy()) or float(res.fx) < 1e-5
    np.testing.assert_allclose(res.x.numpy(), [1.0, 1.0], atol=1e-2)


def test_bfgs_quadratic():
    A = np.asarray([[3., 1.], [1., 2.]], np.float32)
    b = np.asarray([1., -1.], np.float32)

    def quad(x):
        return 0.5 * (x * paddle.to_tensor(A) @ x).sum() - \
            (paddle.to_tensor(b) * x).sum()

    # minimum at A x = b
    res = minimize_bfgs(lambda x: 0.5 * paddle.matmul(
        paddle.matmul(x.reshape([1, 2]), paddle.to_tensor(A)),
        x.reshape([2, 1])).sum() - (paddle.to_tensor(b) * x).sum(),
        paddle.to_tensor(np.zeros(2, np.float32)), max_iters=100)
    want = np.linalg.solve(A, b)
    np.testing.assert_allclose(res.x.numpy(), want, atol=1e-3)


def test_jacobian_hessian():
    from paddle_tpu.autograd import hessian, jacobian

    def f(x):
        return (x * x).sum()

    x = paddle.to_tensor(np.asarray([1., 2., 3.], np.float32))
    j = jacobian(f, x)
    np.testing.assert_allclose(j.numpy(), 2 * x.numpy())
    h = hessian(f, x)
    np.testing.assert_allclose(h.numpy(), 2 * np.eye(3), atol=1e-6)


def test_op_bench_tool():
    out = subprocess.run(
        [sys.executable, "tools/op_bench.py", "--op", "matmul",
         "--shape", "64x64,64x64", "--repeat", "3"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "."})
    assert out.returncode == 0, out.stderr
    import json
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["op"] == "matmul" and rec["jit_us"] > 0


def test_op_bench_times_the_dsa_threshold():
    """`--dsa-select`'s case at one narrow width: the XLA passes and the
    threshold kernel both run and are timed (the kernel interpreted off
    the chip, so the times here are no device's)."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    import op_bench
    rec = op_bench.bench_dsa_select(2560, repeat=1)
    assert rec["shape"] == "512x2560" and rec["topk"] == 2048
    assert rec["xla_passes_host_ms"] > 0 and rec["kernel_host_ms"] > 0
    # no device time off the chip
    assert rec["xla_passes_ms"] is None and rec["kernel_ms"] is None
