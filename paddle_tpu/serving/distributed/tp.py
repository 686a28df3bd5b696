"""Tensor-parallel paged serving: prefill AND decode over a device mesh.

One chip's HBM bounds both the weights and the KV pool a paged engine
can hold; tensor parallelism splits BOTH over a mesh's 'mp' axis the
same way the training stack does (parallel/gpt_spmd.py, reference
Megatron mp_layers):

  - weights shard by their `split_axis` annotations (qkv/fc1 column-
    parallel, out_proj/fc2 row-parallel, wte vocab-parallel, norms and
    wpe replicated) — the annotations the GPT Layer already carries for
    the fleet runner;
  - the KV pools shard over the HEADS axis
    ([num_blocks, block_size, heads/mp, head_dim] per device), so a
    tp-degree mesh holds a tp-times-larger pool at the same per-device
    memory — the serving-side win;
  - block tables, positions and tokens stay replicated (tiny int32): a
    call's one packed upload (`_put`) goes to every device of the mesh.

Decode AND prefill are the SAME traced programs as the single-device
paged engine (`functional_call` over the same Layer forward — token
exactness is inherited, not re-proven), partitioned by XLA's SPMD
partitioner from the input shardings, with `with_sharding_constraint`
pinning every new-pool output to the heads-sharded layout (the
`_constrain_pools` hook — the per-bucket prefill executables pin their
output pools exactly like decode, so prefill K/V lands straight in the
head-sharded blocks and the per-chip prefill FLOPs drop tp× with the
column/row weight splits; ISSUE 13 asserts this with a prefill-only
shard check). Pinning outputs is what preserves the
compile-exactly-once invariant on a mesh: unpinned outputs could come
back with a drifted sharding, and re-feeding them would change the
input shardings — a silent retrace. The per-op collectives (all-reduce
after attention out-proj and MLP fc2, the Megatron pattern) are
inserted by the partitioner along the same 'mp' axis the hand-written
training collectives use.

HBM accounting caveat (ISSUE 13): with `weight_dtype="int8"` the int8
decode set shards next to the FLOAT set — prefill keeps serving the
float shards, so per-device weight bytes are float_shard + int8_shard
(~1.25× the float shard), NOT a quarter. `hbm_accounting()` measures
the true footprint from the arrays' actual shards; equal-HBM bench
arms must size against it, never against dtype-width arithmetic.

CPU-testable: the tests run on the 8 virtual host devices
(`--xla_force_host_platform_device_count`), asserting token-exact
streams vs the single-device paged engine, per-executable trace counts
of 1, and genuinely partitioned pool shards after prefill alone.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine import PagedEngineConfig, PagedGenerationEngine

__all__ = ["TensorParallelEngineConfig", "TensorParallelPagedEngine",
           "param_partition_specs", "quant_scale_sharding"]


class TensorParallelEngineConfig(PagedEngineConfig):
    """PagedEngineConfig plus the mesh degree. `tp` devices (from
    `jax.devices()` order) form a 1-D 'mp' mesh; `num_heads` must divide
    by it (heads are the sharded attention axis)."""

    def __init__(self, tp=2, **kwargs):
        super().__init__(**kwargs)
        self.tp = int(tp)
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")

    _DICT_FIELDS = PagedEngineConfig._DICT_FIELDS + ("tp",)


def quant_scale_sharding(mesh, sharding, axis, scale_ndim):
    """THE int8 scale-sharding rule, shared by the TP and PP engines:
    the per-channel scale vector follows its weight's split only when
    the channel axis IS the sharded axis (qkv/fc1 column splits, the
    wte vocab split); row-parallel weights keep replicated scales —
    every shard holds all output channels."""
    split = sharding.spec[axis] if axis < len(sharding.spec) else None
    sparts = [None] * scale_ndim
    if split is not None:
        sparts[axis] = split
    return NamedSharding(mesh, P(*sparts))


def param_partition_specs(model):
    """{param name: PartitionSpec} over the 'mp' axis, derived from the
    `split_axis` annotations the GPT parameters already carry for the
    training-side TP runner (qkv.weight axis 1, out_proj.weight axis 0,
    fc1/fc2 likewise, wte.weight axis 0 = vocab-parallel). Unannotated
    params replicate."""
    specs = {}
    for name, p in model.named_parameters():
        ax = getattr(p, "split_axis", None)
        if ax is None:
            specs[name] = P()
            continue
        parts = [None] * p._data.ndim
        parts[int(ax)] = "mp"
        specs[name] = P(*parts)
    return specs


class TensorParallelPagedEngine(PagedGenerationEngine):
    """PagedGenerationEngine whose params and KV pools live sharded over
    a 1-D 'mp' mesh. Public contract unchanged — prefill/decode/adopt/
    extract/reset, compile-once trace counters, block accounting all
    host-side and mesh-oblivious — only array placement differs."""

    def __init__(self, model, config=None, **kwargs):
        config = config or TensorParallelEngineConfig(**kwargs)
        if not isinstance(config, TensorParallelEngineConfig):
            raise TypeError("TensorParallelPagedEngine needs a "
                            "TensorParallelEngineConfig")
        devices = jax.devices()
        if config.tp > len(devices):
            raise ValueError(
                f"tp={config.tp} exceeds the {len(devices)} visible "
                f"devices")
        if model.cfg.num_heads % config.tp:
            raise ValueError(
                f"tp={config.tp} must divide num_heads="
                f"{model.cfg.num_heads} (heads are the sharded axis)")
        self._mesh = Mesh(np.asarray(devices[:config.tp]), ("mp",))
        self._pool_sharding = NamedSharding(
            self._mesh, P(None, None, "mp", None))
        # a quantized pool's [num_blocks, heads] scale arrays split over
        # the SAME heads axis as the codes they scale — per-shard scales
        # follow the head split, so dequant stays shard-local
        self._scale_sharding = NamedSharding(self._mesh, P(None, "mp"))
        self._replicated = NamedSharding(self._mesh, P())
        # a call's packed upload replicates over the mesh, as the block
        # tables, positions and tokens it holds always did
        self._upload_sharding = self._replicated
        super().__init__(model, config)

    # -- placement -----------------------------------------------------------
    def _alloc_state(self):
        """Paged state, then mesh placement: params per their
        `split_axis` specs, pools heads-sharded. Runs before any
        executable is built, so the FIRST trace already sees the final
        shardings — no step-one recompile."""
        super()._alloc_state()
        specs = param_partition_specs(self._model)
        self._param_shardings = {
            name: NamedSharding(self._mesh, specs.get(name, P()))
            for name in self._params}
        self._params = {
            name: jax.device_put(arr, self._param_shardings[name])
            for name, arr in self._params.items()}
        self._buffers = {name: jax.device_put(arr, self._replicated)
                         for name, arr in self._buffers.items()}
        self._pool = tuple(type(layer)(
            *(jax.device_put(x, self._pool_sharding if x.ndim == 4
                             else self._scale_sharding) for x in layer))
            for layer in self._pool)

    def _constrain_pools(self, pool):
        """Pin every new-pool output (codes AND, for a quantized pool,
        the scale arrays) to its sharded layout at trace time — input
        and output shardings stay identical forever, which is what keeps
        the decode executable compiled exactly once on a mesh (see
        module docstring)."""
        return tuple(type(layer)(
            *(jax.lax.with_sharding_constraint(
                x, self._pool_sharding if x.ndim == 4
                else self._scale_sharding) for x in layer))
            for layer in pool)

    def _place_param(self, name, arr):
        """Hot-swapped weights re-apply the original mesh sharding."""
        return jax.device_put(arr, self._param_shardings[name])

    def _place_adapter_tree(self, tree):
        """Per-tenant LoRA banks (ISSUE 17) replicate over the mesh: the
        rank-r factors are tiny next to the sharded base weights, and a
        replicated delta keeps the partitioner's collective pattern
        identical to the adapter-off trace (the all-reduce after
        out_proj/fc2 still runs over the same 'mp' axis)."""
        return jax.device_put(tree, self._replicated)

    def _place_quant_weight(self, name, codes, scale_b, axis):
        """Quantized decode weights shard EXACTLY like their float
        originals (same shape, same split_axis spec). The per-channel
        scale vector follows the split only when the channel axis IS the
        sharded axis (qkv/fc1 column splits, the wte vocab split);
        row-parallel weights (out_proj/fc2: split axis 0, channels on
        axis 1) keep replicated scales — every shard holds all output
        channels."""
        sharding = self._param_shardings.get(
            name, NamedSharding(self._mesh, P()))
        return {"q": jax.device_put(codes, sharding),
                "scale": jax.device_put(scale_b, quant_scale_sharding(
                    self._mesh, sharding, axis, scale_b.ndim))}

    # -- introspection (what the tests assert) -------------------------------
    @property
    def mesh(self):
        return self._mesh

    def kv_shard_report(self):
        """Per-device pool placement proof: {device: heads} for layer
        0's K pool — each of the tp devices must hold heads/tp."""
        shards = self._pool[0].k.addressable_shards
        return {str(s.device): int(s.data.shape[2]) for s in shards}
