"""Runtime flags (reference: paddle/fluid/platform/flags.cc — 69 FLAGS_*
gflags exported to python via global_value_getter_setter.cc).

TPU-native: a python-level registry; flags that map to XLA behavior document
their equivalent. Settable from env (FLAGS_xxx) like the reference.
"""
import os

_FLAGS = {
    # numerics / debugging
    "FLAGS_check_nan_inf": False,          # hapi/debug nan scan after each step
    "FLAGS_benchmark": False,
    # allocator knobs are absorbed by PjRt/XLA's BFC allocator:
    "FLAGS_allocator_strategy": "xla_bfc",
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_eager_delete_tensor_gb": 0.0,
    # rng
    "FLAGS_cudnn_deterministic": True,     # XLA is deterministic by default
    # executor knobs are no-ops (XLA owns scheduling)
    "FLAGS_use_standalone_executor": True,
    "FLAGS_sync_nccl_allreduce": False,
    "FLAGS_max_inplace_grad_add": 0,
    "FLAGS_embedding_deterministic": 1,
    "FLAGS_cudnn_exhaustive_search": False,
    "FLAGS_conv_workspace_size_limit": 512,
    "FLAGS_flash_attention": True,         # route MHA through pallas kernel
    "FLAGS_profile": False,
    # first-class persistent executable cache (framework/compile_cache.py):
    # set to a directory to attach the process-global tier — device-layer op
    # runners and serving engines without a private cache then serialize
    # executables there and deserialize them on later runs. Entries ride the
    # ckpt_commit atomic protocol (torn-write safe) and report through
    # compile_cache_{hits,misses}_total. jax's own persistent cache is placed
    # by $JAX_COMPILATION_CACHE_DIR alone (compile_cache.place()).
    "FLAGS_compile_cache_dir": "",
    # retention cap for compile-cache dirs (ROADMAP item 5 debt): keep at
    # most this many committed entries per cache directory, sweeping the
    # least-recently-USED (by dir mtime — lookups touch it) at commit
    # time. 0 = unlimited. Applies to every CompileCache built without an
    # explicit max_entries, engine-private and process-global alike.
    "FLAGS_compile_cache_max_entries": 0,
    # int64 boundary policy escape hatch (PARITY dtype-policy section): on
    # device, int64 requests canonicalize to int32 (x64 off, TPU-native
    # widths). Consumers that np.save/type-check against reference-written
    # int64 state set this to get int64 back at the NUMPY boundary only
    # (per-call form: Tensor.numpy(force_int64=True)).
    "FLAGS_int64_numpy_boundary": False,
}


def _load_env():
    for k in list(_FLAGS):
        if k in os.environ:
            v = os.environ[k]
            cur = _FLAGS[k]
            if isinstance(cur, bool):
                _FLAGS[k] = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, int):
                _FLAGS[k] = int(v)
            elif isinstance(cur, float):
                _FLAGS[k] = float(v)
            else:
                _FLAGS[k] = v


_load_env()

if _FLAGS["FLAGS_compile_cache_dir"]:
    # attach is import-light (no jax until the first lookup/compile)
    from . import compile_cache as _compile_cache
    _compile_cache.attach(_FLAGS["FLAGS_compile_cache_dir"])


def get_flags(flags=None):
    if flags is None:
        return dict(_FLAGS)
    if isinstance(flags, str):
        return {flags: _FLAGS[flags]}
    return {f: _FLAGS[f] for f in flags}


def set_flags(flags):
    for k, v in flags.items():
        _FLAGS[k] = v
