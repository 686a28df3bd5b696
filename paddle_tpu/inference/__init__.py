"""paddle.inference equivalent — the deploy product.

Reference: paddle/fluid/inference (§2.7 of SURVEY.md): `AnalysisPredictor`
(inference/api/analysis_predictor.h:95) loads a saved ProgramDesc + params,
runs IR fusion passes, optionally offloads subgraphs to TensorRT, and serves
through zero-copy input/output handles (details/zero_copy_tensor.cc), with
`AnalysisConfig` (inference/api/analysis_config.cc) as the knob surface.

TPU-native design: the saved artifact is an AOT-exported StableHLO program
(`paddle_tpu.jit.save`) — the XLA compiler IS the analysis/fusion pass
pipeline, so `switch_ir_optim`-style knobs are accepted-and-absorbed. The
Predictor deserializes the program once, compiles per concrete input shape
(shape-polymorphic artifacts recompile per batch size, cached), and serves
through handle objects whose `copy_from_cpu`/`copy_to_cpu` map to device
put/get — the TPU analogue of zero-copy CPU tensors.
"""
import os

import numpy as np

__all__ = ["Config", "Predictor", "PrecisionType", "PlaceType",
           "create_predictor", "get_version"]


class PrecisionType:
    Float32 = "float32"
    Bfloat16 = "bfloat16"
    Half = "float16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    TPU = "tpu"
    # reference enum also has GPU/XPU/NPU — single-backend build
    GPU = "tpu"


class Config:
    """AnalysisConfig-compatible surface. Knobs that XLA owns are recorded
    but have no effect (noted per method)."""

    def __init__(self, prog_file=None, params_file=None):
        if prog_file is not None and params_file is None and \
                os.path.isdir(prog_file):
            # Config(model_dir) form: find the single jit.save artifact
            d = prog_file
            models = sorted(f for f in os.listdir(d)
                            if f.endswith(".pdmodel"))
            if not models:
                raise FileNotFoundError(f"no .pdmodel in {d}")
            prog_file = os.path.join(d, models[0])
            params_file = prog_file[:-len(".pdmodel")] + ".pdiparams"
        self._prog_file = prog_file
        self._params_file = params_file
        self._device = "tpu"
        self._precision = PrecisionType.Float32
        self._memory_optim = True
        self._ir_optim = True
        self._profile = False
        self._glog_info = True
        self._cpu_math_threads = 1
        # persistent executable cache: a second process deserializes XLA
        # executables instead of compiling (AnalysisPredictor's
        # pay-analysis-once intent). None = the one placement rule
        # (framework/compile_cache.place: $JAX_COMPILATION_CACHE_DIR, else
        # <checkout>/.jax_cache) — never a directory beside the artifact,
        # which moves with it and then never hits.
        self._compile_cache_dir = None
        self._compile_cache = True
        # AOT serving warmup: when the artifact's .gencfg records a serving
        # engine (save_for_generation(engine_config=...)), the Predictor
        # builds it AT LOAD and precompiles the whole executable set —
        # against a warm compile cache that is a deserialize, not a
        # compile, and the first request pays zero compilation.
        self._aot_warmup = True

    def enable_compile_cache(self, path=None):
        self._compile_cache = True
        self._compile_cache_dir = path

    def disable_compile_cache(self):
        self._compile_cache = False

    def enable_aot_warmup(self):
        self._aot_warmup = True

    def disable_aot_warmup(self):
        """Skip the load-time engine build/warmup (serving executables
        then compile lazily on the first generate(), pre-PR-8 style)."""
        self._aot_warmup = False

    # -- model location ----------------------------------------------------
    def set_prog_file(self, path):
        self._prog_file = path

    def set_params_file(self, path):
        self._params_file = path

    def prog_file(self):
        return self._prog_file

    def params_file(self):
        return self._params_file

    def set_model(self, prog_file, params_file):
        self._prog_file = prog_file
        self._params_file = params_file

    # -- device ------------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        """Single-backend build: selects the TPU (memory pool is managed by
        the XLA runtime allocator, the size hint is ignored)."""
        self._device = "tpu"

    def disable_gpu(self):
        self._device = "cpu"

    def use_gpu(self):
        return self._device == "tpu"

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_math_threads = n

    # -- optimization knobs (absorbed by XLA) --------------------------------
    def switch_ir_optim(self, x=True):
        """Graph fusion/layout passes are XLA's job; kept for parity."""
        self._ir_optim = x

    def enable_memory_optim(self, x=True):
        """Buffer reuse is XLA's job; kept for parity."""
        self._memory_optim = x

    def enable_tensorrt_engine(self, *a, **k):
        """TensorRT is CUDA-specific; the XLA TPU compiler plays this role.
        Accepted as a no-op so deploy scripts port unchanged."""

    def enable_profile(self):
        self._profile = True

    def disable_glog_info(self):
        self._glog_info = False

    def switch_use_feed_fetch_ops(self, x=False):
        pass

    def switch_specify_input_names(self, x=True):
        pass

    def summary(self):
        return (f"Config(prog={self._prog_file}, params={self._params_file}, "
                f"device={self._device}, precision={self._precision})")


class _Handle:
    """Zero-copy-style IO handle (reference: ZeroCopyTensor). Inputs stage a
    host array and device-put lazily at run(); outputs hold the device
    array and copy_to_cpu fetches it."""

    def __init__(self, name, shape=None, dtype=None):
        self.name = name
        self._shape = shape
        self._dtype = dtype
        self._value = None

    def reshape(self, shape):
        self._shape = tuple(shape)

    def copy_from_cpu(self, arr):
        self._value = np.ascontiguousarray(arr)

    def share_external_data(self, arr):
        self._value = arr  # no copy; caller keeps it alive

    def copy_to_cpu(self):
        return np.asarray(self._value)

    def shape(self):
        v = self._value
        return list(v.shape) if v is not None else list(self._shape or [])

    def type(self):
        return self._dtype


class Predictor:
    """AnalysisPredictor equivalent over a deserialized AOT program."""

    def __init__(self, config):
        from jax import export as jexport

        import jax.numpy as jnp

        from ..framework.io import load as _load

        self._config = config
        if getattr(config, "_compile_cache", False):
            from ..framework import compile_cache as _compile_cache
            _compile_cache.place(config._compile_cache_dir)
        with open(config.prog_file(), "rb") as f:
            self._exported = jexport.deserialize(f.read())
        payload = _load(config.params_file(), return_numpy=True)
        self._params = {n: jnp.asarray(v) for n, v in payload["params"].items()}
        self._buffers = {n: jnp.asarray(v)
                         for n, v in payload["buffers"].items()}

        # in_avals is the FLATTENED calling convention: one aval per
        # param/buffer leaf, then the user inputs
        n_state = len(self._params) + len(self._buffers)
        in_avals = self._exported.in_avals[n_state:]
        # user-facing input names: the REAL names saved with the artifact
        # (jit.save feed_names), falling back to positional input_{i} for
        # legacy artifacts — keeps Predictor / load_inference_model /
        # Executor.run agreeing on one name set
        saved = payload.get("feed_names")
        if saved and len(saved) == len(in_avals):
            self._input_names = list(saved)
        else:
            self._input_names = [f"input_{i}" for i in range(len(in_avals))]
        self._inputs = {n: _Handle(n, tuple(a.shape), str(a.dtype))
                        for n, a in zip(self._input_names, in_avals)}
        self._output_names = []
        self._outputs = {}

        # AOT serving warmup: a .gencfg that records a serving engine is
        # built NOW (executables deserialize from the compile cache when
        # warm), so the first generate() compiles nothing. A failure
        # here raises: on the chip it is a compile refusal, and a lazy
        # fallback would only hide it until the first request.
        self._gen_sched = None
        self._gen_sched_from_record = False
        self._serving_meta = self._read_serving_meta()
        if self._serving_meta and getattr(config, "_aot_warmup", False) \
                and getattr(config, "_compile_cache", False):
            import time as _time
            from ..observability import metrics as _obs_metrics
            t0 = _time.perf_counter()
            self._generation_scheduler()
            _obs_metrics.gauge(
                "predictor_executable_ready_seconds",
                "Predictor load-to-serving-ready wall time (AOT "
                "warmup included)").set(_time.perf_counter() - t0)

    def _read_serving_meta(self):
        """The .gencfg 'serving' record (engine kind + config +
        executable set), or None for pre-recording artifacts."""
        import json

        from ..serving.engine import GENCFG_SUFFIX
        base = self._config.prog_file()
        if base.endswith(".pdmodel"):
            base = base[:-len(".pdmodel")]
        try:
            with open(base + GENCFG_SUFFIX) as f:
                return json.load(f).get("serving")
        except (OSError, ValueError):
            return None

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    def run(self, inputs=None):
        """Execute. Either feed via handles then run(), or pass a list of
        numpy arrays directly (returns list of numpy outputs)."""
        import jax.numpy as jnp

        if inputs is not None:
            for n, arr in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(np.asarray(arr))
        args = [jnp.asarray(self._inputs[n]._value) for n in self._input_names]
        out = self._exported.call(self._params, self._buffers, *args)
        outs = list(out) if isinstance(out, (tuple, list)) else [out]
        self._output_names = [f"output_{i}" for i in range(len(outs))]
        self._outputs = {}
        for n, o in zip(self._output_names, outs):
            h = _Handle(n, tuple(o.shape), str(o.dtype))
            h._value = o
            self._outputs[n] = h
        if inputs is not None:
            return [np.asarray(o) for o in outs]
        return True

    def get_output_names(self):
        return list(self._output_names)

    def get_output_handle(self, name):
        return self._outputs[name]

    # -- generation entry point (serving/) ----------------------------------
    def _generation_scheduler(self, **engine_kwargs):
        """Build (or return) the serving engine + scheduler from the
        `.gencfg` sidecar `serving.save_for_generation` wrote next to
        the artifact. The params already loaded for the one-shot path
        are reused — one weight copy serves both run() and generate().

        When the sidecar records a serving engine and no explicit engine
        kwargs are given, the RECORDED engine (dense/paged/spec, exact
        config) is rebuilt with the artifact's persistent compile cache
        attached and `precompile()`d — against a warm cache that is all
        deserialization, so a restarted Predictor performs zero fresh
        compilations for the serving set.

        Explicit engine kwargs keep their pre-record contract: they win.
        A scheduler auto-built from the record is REPLACED when the
        first generate() carries engine kwargs (the caller asked for a
        different engine than the artifact recorded); once a
        kwargs-built scheduler exists, later calls reuse it as before."""
        if getattr(self, "_gen_sched", None) is not None:
            if not engine_kwargs or \
                    not getattr(self, "_gen_sched_from_record", False):
                return self._gen_sched
            self._gen_sched = None     # record-built, caller overrides
        from ..framework import compile_cache as _compile_cache
        from ..serving.engine import load_generation_model, make_engine
        model = load_generation_model(self._config.prog_file(), self._params)
        if model is None:
            raise RuntimeError(
                "this artifact has no generation sidecar; save it with "
                "paddle_tpu.serving.save_for_generation to enable "
                "Predictor.generate")
        from ..serving import GenerationEngine, Scheduler
        sched_keys = ("max_queue", "default_max_new_tokens",
                      "default_timeout_s", "metrics_path")
        sched_kwargs = {k: engine_kwargs.pop(k) for k in sched_keys
                        if k in engine_kwargs}
        meta = getattr(self, "_serving_meta", None)
        from_record = bool(meta) and not engine_kwargs
        if from_record:
            cache_dir = None
            if getattr(self._config, "_compile_cache", False):
                cache_dir = self._config._compile_cache_dir or \
                    _compile_cache.default_dir()
            engine = make_engine(model, meta["engine"], meta["config"],
                                 compile_cache_dir=cache_dir)
            if getattr(self._config, "_aot_warmup", False):
                engine.precompile()
        else:
            engine = GenerationEngine(model, **engine_kwargs)
        self._gen_sched = Scheduler(engine, **sched_kwargs)
        self._gen_sched_from_record = from_record
        return self._gen_sched

    def generate(self, input_ids, max_new_tokens=32, **engine_kwargs):
        """Generate continuations for a batch of prompts (list of
        token-id lists, or a [B, S] int array) through the continuous-
        batching engine. Returns list-of-lists of generated ids.
        Engine/scheduler knobs (slots, max_len, decode_strategy,
        temperature, top_k, top_p, eos_token_id, max_queue, ...) pass
        through on the FIRST call; later calls reuse the built engine."""
        from ..serving import QueueFullError
        prompts = [list(map(int, np.asarray(p).reshape(-1)))
                   for p in input_ids]
        sched = self._generation_scheduler(**engine_kwargs)
        handles = []
        for p in prompts:
            while True:
                try:
                    handles.append(sched.submit(
                        p, max_new_tokens=max_new_tokens))
                    break
                except QueueFullError:
                    sched.step()   # drain a slot's worth, then retry
        sched.run_until_idle()
        # the scheduler degrades gracefully for SERVING callers (per-
        # request status), but this batch API has no consumer watching
        # handle.status — a decode failure must be loud, not a silently
        # truncated generation
        failed = [h for h in handles if h.status == "ERROR"]
        if failed:
            raise RuntimeError(
                f"decode failed for {len(failed)}/{len(handles)} "
                f"request(s): {failed[0].error}")
        return [h.tokens for h in handles]

    def clear_intermediate_tensor(self):
        pass

    def try_shrink_memory(self):
        pass


def create_predictor(config):
    return Predictor(config)


def get_version():
    from .. import __version__
    return __version__


class DataType:
    """reference: paddle_infer.DataType enum."""
    FLOAT32 = 0
    INT64 = 1
    INT32 = 2
    UINT8 = 3
    INT8 = 4
    FLOAT16 = 5
    BFLOAT16 = 6


def get_num_bytes_of_data_type(dtype):
    return {DataType.FLOAT32: 4, DataType.INT64: 8, DataType.INT32: 4,
            DataType.UINT8: 1, DataType.INT8: 1, DataType.FLOAT16: 2,
            DataType.BFLOAT16: 2}[dtype]


# paddle_infer.Tensor is the zero-copy handle type; ours is _Handle
Tensor = _Handle


class PredictorPool:
    """reference: paddle_infer.PredictorPool — N predictors sharing one
    config (thread-per-predictor serving)."""

    def __init__(self, config, size=1):
        self._predictors = [Predictor(config) for _ in range(max(size, 1))]

    def retrive(self, idx):
        return self._predictors[idx]

    retrieve = retrive


def convert_to_mixed_precision(model_file, params_file, mixed_model_file,
                               mixed_params_file, mixed_precision=None,
                               backend=None, keep_io_types=True,
                               black_list=None, **kwargs):
    """reference: inference convert_to_mixed_precision — rewrites a saved
    model to fp16/bf16. The StableHLO artifact stays dtype-typed; bf16
    serving comes from exporting the model with bf16 params (jit.save of a
    bf16-cast Layer), so this converter re-saves with a dtype cast."""
    raise NotImplementedError(
        "convert the LAYER before export: cast params to bfloat16 "
        "(layer.to(dtype='bfloat16') / astype) and jit.save it — the "
        "exported StableHLO then serves in bf16 end-to-end")


def get_trt_compile_version():
    """No TensorRT on TPU (PARITY: TensorRT row) — version tuple of 0s."""
    return (0, 0, 0)


def get_trt_runtime_version():
    return (0, 0, 0)


def _get_phi_kernel_name(op_name):
    """reference: maps fluid op names to phi kernel names; one generation
    here — identity."""
    return op_name
