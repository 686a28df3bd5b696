"""paddle_tpu.serving.distributed — multi-host serving (ISSUE 10).

The single-process engines (serving/engine.py) scale until one host's
HBM or one chip's FLOPs run out; this package is the tier above them,
un-descoping PARITY §2.7's multi-host row with three composable layers:

  tp.py          — TENSOR-PARALLEL serving: prefill AND decode sharded
                   over a device mesh ('mp' axis — KV pools and
                   attention heads split across devices, weights laid
                   out by their training-time `split_axis` annotations).
                   Token-exact vs the single-device paged engine and
                   still compiles exactly once; CPU-testable on the
                   virtual-device mesh.
  pp.py          — PIPELINE-PARALLEL serving (ISSUE 13): GPT blocks
                   partitioned into stages over the second mesh axis,
                   each stage holding its own resident KV pool slice on
                   its own (optionally tensor-parallel) device group —
                   models bigger than one host's HBM serve end-to-end.
                   Decode is a steady-state microbatch ring, prefill
                   streams chunks through the stages 1F1B-style.
  kv_handoff.py  — KV-block WIRE FORMAT for disaggregated prefill/decode
                   pools: one request's per-layer K/V slices as a
                   validated, truncation-rejecting bundle.
  worker.py      — one serving HOST: engine + scheduler behind new verbs
                   on the PR 5 self-healing PS RPC fabric (KVPUT /
                   PREFILL / SUBMIT / POLL / SWAP / STAT / HEALTH /
                   DRAIN), a decode step loop, and zero-downtime weight
                   hot-swap from ckpt_commit checkpoints.
  router.py      — the FRONTEND: SLO-aware placement over prefill and
                   decode pools, request streaming, and failover — a
                   killed decode host's requests restart recompute-style
                   on a live host, bit-identical under greedy decoding.
                   Gray failures (ISSUE 20): a phi-accrual health plane
                   (healthy → suspect → dark) over OP_HEALTH heartbeats,
                   deadline-propagated RPCs with hedged readonly calls +
                   per-worker retry budgets, proactive KV migration off
                   suspect hosts, and `rolling_drain` — a zero-drop
                   rolling-restart primitive (docs/robustness.md §5).
  worker_main.py — `python -m paddle_tpu.serving.distributed.worker_main`
                   process entry (tests, deploys).

Deliberately NOT imported by `paddle_tpu.serving` at import time: the
multi-host tier pulls in the RPC fabric and mesh machinery, which
single-process serving must not pay for.
"""
from .kv_handoff import (KVWireError, pack_kv_bundle,  # noqa: F401
                         unpack_kv_bundle)
from .pp import (PipelineParallelEngineConfig,  # noqa: F401
                 PipelineParallelPagedEngine, PipelineParallelSpecConfig,
                 PipelineParallelSpeculativeEngine,
                 free_eager_device_copies)
from .router import DistFrontend, ServingShardClient  # noqa: F401
from .tp import (TensorParallelEngineConfig,  # noqa: F401
                 TensorParallelPagedEngine)
from .worker import (ServingWorker, load_checkpoint_params,  # noqa: F401
                     save_swap_checkpoint)

__all__ = [
    "TensorParallelEngineConfig", "TensorParallelPagedEngine",
    "PipelineParallelEngineConfig", "PipelineParallelPagedEngine",
    "PipelineParallelSpecConfig", "PipelineParallelSpeculativeEngine",
    "free_eager_device_copies",
    "KVWireError", "pack_kv_bundle", "unpack_kv_bundle",
    "ServingWorker", "load_checkpoint_params", "save_swap_checkpoint",
    "DistFrontend", "ServingShardClient",
]
