"""Hybrid-parallel SPMD execution (the reference's fleet static-graph path,
re-designed TPU-first — SURVEY §2.10).

The compute path here is raw-jax functional (no eager tape): one
jit-compiled train step per configuration, shard_map'd over a Mesh with
explicit XLA collectives. This is the performance path used by
benchmark/loops/train_job.py and __graft_entry__.dryrun_multichip.
"""
from .gpt_spmd import (  # noqa: F401
    GPTSpmdConfig, MeshPlan, init_gpt_params, make_train_step, make_forward_fn,
)
from .ring_attention import ring_attention, ulysses_attention  # noqa: F401
