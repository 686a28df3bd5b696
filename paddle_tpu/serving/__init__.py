"""paddle_tpu.serving — TPU-native generation & serving engine.

The reference deploy story stops at a one-shot Predictor (SURVEY §2.7);
this package is the generation tier above it, built from the ideas that
turn a compiled decoder into a serving engine:

  kv_cache.py     — static-shape preallocated KV cache (one decode
                    executable, ever; vLLM's preallocation insight)
  blocks.py       — the served-model protocol (what each layer of a
                    model caches, what that allows and counts) and
                    paged KV: fixed-size block pool + per-slot block
                    tables, refcounted for copy-on-write sharing; int8
                    pools with per-block per-head scales (ISSUE 11 —
                    2x the KV tokens per HBM byte, dequant in-kernel)
  prefix_cache.py — shared system-prompt blocks, keyed on prompt-token
                    hash, LRU-evicted under allocation pressure
  sampling.py     — greedy / temperature / top-k / top-p token selection
                    + the speculative accept/resample rule
  engine.py       — prefill/decode split: length-bucketed prefill
                    executables feed the single decode executable
                    (dense GenerationEngine + PagedGenerationEngine,
                    gather or in-kernel Pallas paged attention)
  spec_decode.py  — speculative multi-token decode: draft proposals +
                    one fixed-shape verify forward per round, greedy
                    output bit-identical to the one-token loop
  scheduler.py    — SLO-aware continuous batching: priority classes,
                    deadline/priority preemption that frees blocks back
                    to the pool, watermark load shedding, queue caps,
                    graceful drain, serving metrics; staged-KV
                    placement (multi-host handoff sink) and
                    between-steps weight hot-swap
  distributed/    — the multi-host tier (ISSUE 10): tensor-parallel
                    decode over a mesh, disaggregated prefill/decode
                    worker pools on the PS RPC fabric with KV-bundle
                    handoff, SLO-aware router with bit-exact failover,
                    zero-downtime weight hot-swap. Imported lazily
                    (`paddle_tpu.serving.distributed`) — single-process
                    serving never pays for the fabric.

`inference.Predictor.generate`, `benchmark/loops/closed_loop.py` and
`tools/load_harness.py` ride the same engines. See docs/serving.md.
"""
from . import blocks, kv_cache, prefix_cache, sampling, spec_decode  # noqa: F401,E501
from .blocks import (  # noqa: F401
    BlockAllocError, BlockPool, PagedLayerKV, QuantPagedLayerKV,
)
from .engine import (  # noqa: F401
    EngineConfig, GenerationEngine, PagedEngineConfig, PagedGenerationEngine,
    make_engine, save_for_generation,
)
from .prefix_cache import PrefixCache  # noqa: F401
from .scheduler import (  # noqa: F401
    PRIORITIES, LoadShedError, QueueFullError, RateLimitedError, Request,
    RequestHandle, Scheduler, ServingConfig,
)
from .spec_decode import (  # noqa: F401
    SpecDecodeConfig, SpeculativeEngine, truncated_draft,
)

__all__ = [
    "kv_cache", "blocks", "prefix_cache", "sampling", "spec_decode",
    "BlockAllocError", "BlockPool", "PagedLayerKV", "QuantPagedLayerKV",
    "PrefixCache",
    "PRIORITIES",
    "EngineConfig", "GenerationEngine", "PagedEngineConfig",
    "PagedGenerationEngine", "save_for_generation", "make_engine",
    "SpecDecodeConfig", "SpeculativeEngine", "truncated_draft",
    "Scheduler", "ServingConfig", "Request", "RequestHandle",
    "QueueFullError", "LoadShedError", "RateLimitedError",
]
