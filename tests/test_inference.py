"""Inference/deploy slice: jit.save/load AOT programs + Predictor serving.

Mirrors the reference's inference API tests (inference/tests/api/) and
jit save/load suites (test_jit_save_load.py): save an eval-mode model,
reload it cold, and check numerical identity with the live layer.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.static import InputSpec


def _small_net():
    net = nn.Sequential(
        nn.Linear(8, 16), nn.ReLU(),
        nn.BatchNorm1D(16),
        nn.Linear(16, 4),
    )
    net.eval()
    return net


def test_jit_save_load_roundtrip(tmp_path):
    net = _small_net()
    x = paddle.to_tensor(np.random.RandomState(0).rand(3, 8).astype("float32"))
    want = net(x).numpy()

    path = str(tmp_path / "m")
    paddle.jit.save(net, path, input_spec=[InputSpec([None, 8], "float32")])
    loaded = paddle.jit.load(path)
    got = loaded(x).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_jit_load_polymorphic_batch(tmp_path):
    net = _small_net()
    path = str(tmp_path / "m")
    paddle.jit.save(net, path, input_spec=[InputSpec([None, 8], "float32")])
    loaded = paddle.jit.load(path)
    for bs in (1, 5, 17):
        x = paddle.to_tensor(np.ones((bs, 8), np.float32))
        assert list(loaded(x).shape) == [bs, 4]


def test_jit_save_requires_input_spec(tmp_path):
    with pytest.raises(ValueError):
        paddle.jit.save(_small_net(), str(tmp_path / "m"))


def test_predictor_handles(tmp_path):
    net = _small_net()
    x = np.random.RandomState(1).rand(4, 8).astype("float32")
    want = net(paddle.to_tensor(x)).numpy()

    path = str(tmp_path / "m")
    paddle.jit.save(net, path, input_spec=[InputSpec([None, 8], "float32")])

    cfg = Config(path + ".pdmodel", path + ".pdiparams")
    cfg.enable_memory_optim()
    pred = create_predictor(cfg)
    names = pred.get_input_names()
    assert names == ["input_0"]
    pred.get_input_handle(names[0]).copy_from_cpu(x)
    assert pred.run() is True
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    # direct-list form
    out2 = pred.run([x])[0]
    np.testing.assert_allclose(out2, want, rtol=1e-5, atol=1e-5)


def test_save_load_inference_model(tmp_path):
    net = _small_net()
    path = str(tmp_path / "inf")
    paddle.static.save_inference_model(
        path, [InputSpec([None, 8], "float32")], net)
    prog, feeds, fetches = paddle.static.load_inference_model(path)
    assert feeds == ["input_0"]
    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    np.testing.assert_allclose(prog(x).numpy(), net(x).numpy(),
                               rtol=1e-5, atol=1e-5)


def test_save_inference_model_function_form(tmp_path):
    def fn(a, b):
        return paddle.matmul(a, b)

    path = str(tmp_path / "fn")
    paddle.static.save_inference_model(
        path, [InputSpec([2, 3], "float32"), InputSpec([3, 2], "float32")],
        fn)
    loaded = paddle.jit.load(path)
    a = np.random.RandomState(2).rand(2, 3).astype("float32")
    b = np.random.RandomState(3).rand(3, 2).astype("float32")
    np.testing.assert_allclose(
        loaded(paddle.to_tensor(a), paddle.to_tensor(b)).numpy(), a @ b,
        rtol=1e-5, atol=1e-5)


def test_multi_input_shared_batch_dim(tmp_path):
    class TwoIn(nn.Layer):
        def forward(self, a, b):
            return paddle.matmul(a + b, paddle.transpose(a, [1, 0]))

    net = TwoIn()
    path = str(tmp_path / "two")
    paddle.jit.save(net, path, input_spec=[InputSpec([None, 8], "float32"),
                                           InputSpec([None, 8], "float32")])
    loaded = paddle.jit.load(path)
    for bs in (2, 6):
        a = paddle.to_tensor(np.ones((bs, 8), np.float32))
        b = paddle.to_tensor(np.ones((bs, 8), np.float32))
        assert list(loaded(a, b).shape) == [bs, bs]


def test_executor_runs_loaded_program(tmp_path):
    net = _small_net()
    path = str(tmp_path / "exe")
    paddle.static.save_inference_model(
        path, [InputSpec([None, 8], "float32")], net)
    prog, feeds, fetches = paddle.static.load_inference_model(path)
    exe = paddle.static.Executor()
    x = np.random.RandomState(4).rand(3, 8).astype("float32")
    outs = exe.run(prog, feed={feeds[0]: x}, fetch_list=fetches)
    np.testing.assert_allclose(outs[0], net(paddle.to_tensor(x)).numpy(),
                               rtol=1e-5, atol=1e-5)


def test_multi_output_fetch_names(tmp_path):
    class TwoOut(nn.Layer):
        def forward(self, x):
            return x * 2.0, x.sum()

    path = str(tmp_path / "mo")
    paddle.static.save_inference_model(
        path, [InputSpec([2, 2], "float32")], TwoOut())
    _, feeds, fetches = paddle.static.load_inference_model(path)
    assert fetches == ["output_0", "output_1"]


def test_jit_save_uses_to_static_spec(tmp_path):
    net = _small_net()
    net = paddle.jit.to_static(net,
                               input_spec=[InputSpec([None, 8], "float32")])
    path = str(tmp_path / "ts")
    paddle.jit.save(net, path)   # no explicit input_spec
    loaded = paddle.jit.load(path)
    x = paddle.to_tensor(np.ones((3, 8), np.float32))
    assert list(loaded(x).shape) == [3, 4]


def test_bf16_params_roundtrip(tmp_path):
    net = nn.Linear(4, 4)
    net._cast_all("bfloat16")
    net.eval()
    path = str(tmp_path / "bf")
    paddle.jit.save(net, path, input_spec=[InputSpec([2, 4], "bfloat16")])
    loaded = paddle.jit.load(path)
    x = paddle.to_tensor(np.ones((2, 4), np.float32), dtype="bfloat16")
    want = net(x).astype("float32").numpy()
    got = loaded(x).astype("float32").numpy()
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_predictor_persistent_compile_cache(tmp_path):
    """Warm Predictor load provably skips XLA compilation (VERDICT r2
    missing #4): the second process reports persistent-cache hits for the
    served program and produces the same output."""
    import json
    import subprocess
    import sys
    import os

    net = _small_net()
    x = np.random.RandomState(7).rand(4, 8).astype("float32")
    want = net(paddle.to_tensor(x)).numpy()
    path = str(tmp_path / "pc")
    paddle.jit.save(net, path, input_spec=[InputSpec([4, 8], "float32")])
    np.save(str(tmp_path / "x.npy"), x)

    script = r"""
import json, logging, io, sys
import numpy as np
buf = io.StringIO()
h = logging.StreamHandler(buf)
lg = logging.getLogger("jax._src.compiler")
lg.setLevel(logging.DEBUG); lg.addHandler(h)
from paddle_tpu.inference import Config, create_predictor
path, xpath = sys.argv[1], sys.argv[2]
cfg = Config(path + ".pdmodel", path + ".pdiparams")
pred = create_predictor(cfg)
out = pred.run([np.load(xpath)])[0]
hits = buf.getvalue().count("Persistent compilation cache hit")
print(json.dumps({"hits": hits, "out": np.asarray(out).tolist()}))
"""
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_")) \
                or k in ("JAX_PLATFORM_NAME", "XLA_FLAGS"):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))

    def run_once():
        p = subprocess.run([sys.executable, "-c", script, path,
                            str(tmp_path / "x.npy")],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode == 0, p.stderr[-3000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    cold = run_once()
    # the one placement rule: the children inherit this test's
    # $JAX_COMPILATION_CACHE_DIR (conftest), never a dir beside the artifact
    cache_dir = tmp_path / "jax_cache"
    assert cache_dir.is_dir() and any(cache_dir.iterdir()), \
        "cold run must populate the executable cache"
    warm = run_once()
    assert warm["hits"] > 0, "warm run must hit the persistent cache"
    np.testing.assert_allclose(np.asarray(warm["out"]), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cold["out"]), want,
                               rtol=1e-5, atol=1e-5)


def test_program_capture_ir_surface():
    """Program.capture exposes the ProgramDesc-style op/var graph over the
    traced jaxpr (reference: framework/program_desc.h inspection APIs)."""
    from paddle_tpu.static import InputSpec, Program

    def fn(x, y):
        return (x @ y).sum() * 2.0

    prog = Program.capture(fn, InputSpec([4, 8], "float32"),
                           InputSpec([8, 2], "float32"))
    types = [op.type() for op in prog.ops()]
    assert "dot_general" in types, types
    assert prog.num_blocks == 1
    assert len(prog.var_names()) >= 3
    s = prog.to_string()
    assert "dot_general" in s
    # OpDesc surface
    op = prog.ops()[0]
    assert op.input_arg_names() and op.output_arg_names()


def test_quantized_deploy_roundtrip(tmp_path):
    """The PTQ deploy story end-to-end: calibrate (KL, per-channel
    weights), jit.save the quantized model, serve it via Predictor, and
    check the served outputs match the in-process quantized model —
    the reference's save_quantized_model -> AnalysisPredictor flow."""
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.quantization import PostTrainingQuantization
    from paddle_tpu.static import InputSpec

    paddle.seed(7)
    net = _small_net()
    rng = np.random.RandomState(2)
    batches = [(paddle.to_tensor(rng.rand(4, 8).astype("float32")),)
               for _ in range(4)]
    model, scales = PostTrainingQuantization(net, algo="KL").quantize(
        batches, batch_nums=4)
    assert len(scales) == 2 and all(
        s["activation"] > 0 for s in scales.values())

    x = rng.rand(5, 8).astype("float32")
    want = model(paddle.to_tensor(x)).numpy()

    path = str(tmp_path / "q")
    paddle.jit.save(model, path, input_spec=[InputSpec([None, 8],
                                                       "float32")])
    pred = create_predictor(Config(path + ".pdmodel", path + ".pdiparams"))
    inp = pred.get_input_handle(pred.get_input_names()[0])
    inp.copy_from_cpu(x)
    pred.run()
    got = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_executor_legacy_feed_fallback_warns_loudly(tmp_path):
    """An artifact saved WITHOUT feed names falls back to natural-sorted
    feed keys — that silent-reorder hazard must now announce itself with a
    DeprecationWarning naming the artifact and the assumption (ISSUE 2
    satellite)."""
    import warnings

    net = _small_net()
    path = str(tmp_path / "legacy")
    paddle.static.save_inference_model(
        path, [InputSpec([None, 8], "float32")], net)
    prog, feeds, fetches = paddle.static.load_inference_model(path)
    exe = paddle.static.Executor()
    x = np.random.RandomState(4).rand(3, 8).astype("float32")

    # modern artifact: exact-name matching, NO deprecation chatter
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        want = exe.run(prog, feed={feeds[0]: x}, fetch_list=fetches)[0]

    # legacy artifact (pre-feed-names save): loud, named fallback
    prog._feed_names = None
    with pytest.warns(DeprecationWarning,
                      match="NATURAL-SORTED.*TranslatedLayer"
                            "|TranslatedLayer.*NATURAL-SORTED"):
        got = exe.run(prog, feed={feeds[0]: x}, fetch_list=fetches)[0]
    np.testing.assert_allclose(got, want)
