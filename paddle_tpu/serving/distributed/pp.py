"""Pipeline-parallel serving over a (tp, pp) mesh (ISSUE 13).

Tensor parallelism (tp.py) stops scaling when ONE host's HBM cannot
hold even its 1/tp shard of the weights next to a useful KV pool — the
reference's Fleet stack answers with the second mesh axis: pipeline
parallelism. This module is the serving half of that answer, reusing
the two conventions the training stack already proved:

  - the STAGE SPLIT is `text.models.gpt.gpt_pipeline_stages` — the
    LayerDesc/`ernie_pipeline_descs` convention (embed | blocks | head,
    tied embedding resident on first AND last stage like a
    SharedLayerDesc), partitioned uniformly like
    `fleet.meta_parallel.PipelineLayer`;
  - the TICK SCHEDULE is `parallel.pipeline_schedule` — the same
    static-table machinery that drives the compiled 1F1B trainer, minus
    the backward half (`build_serving_tables`).

Topology: `pp * tp` devices; stage s owns devices [s*tp, (s+1)*tp) as
its own 1-D 'mp' mesh. WITHIN a stage everything is exactly tp.py —
weights sharded by their `split_axis` annotations, the stage's KV pool
sharded over heads, outputs pinned with `with_sharding_constraint` so
each stage executable compiles EXACTLY once. ACROSS stages the only
traffic is the [microbatch, 1, H] hidden activation (decode) or the
[1, chunk, H] prefill chunk — `jax.device_put` onto the next stage's
mesh is the stage boundary, and the `serving.pp_handoff` fault site
fires on every hop.

DECODE is a ring over the slot microbatches: slots split into M
contiguous microbatches, and one `decode()` call runs the
`build_serving_tables(M, pp)` schedule — microbatch g enters stage 0 at
tick g, rides one hop per tick, and its sampled/greedy token exits the
last stage pp-1 ticks later. After the fill every stage works every
tick (steady-state, bubble-free); only the fill/drain triangles idle,
so the call's bubble fraction is (pp-1)/(M+pp-1), exported as
`serving_pp_bubble_fraction` (+ per-stage `serving_pp_stage_busy`) and
failure-class gated by tools/metrics_report.py. Every slot still
advances exactly one token per decode() — the scheduler contract is
unchanged, and token-exactness vs the single-device paged engine is
inherited (same ops, same order, per-slot rows are batch-independent).

PREFILL is microbatched THROUGH the stages the same way: the padded
suffix splits into fixed-size chunks (`prefill_chunk`; default one
chunk = the bucket), chunk c enters stage 0 at tick c — the forward
half of 1F1B — writing each stage's K/V slice into that stage's
resident pool as it passes. The first token taps the final chunk's
last-stage hidden through a tiny head executable.

The per-slot state the block math needs (tables, positions, allocator,
prefix cache) is HOST state shared by all stages — block ids mean the
same thing in every stage's pool, so handoff/adopt/hot-swap/int8
compose per stage: `extract_kv`/`adopt_kv` walk the stages' layer
slices in model order (wire format unchanged), `swap_params` re-places
each stage's params on its own mesh, and kv_dtype/weight_dtype="int8"
quantize per stage exactly as on one device.
"""
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core.tensor import Tensor
from ...nn.layer.layers import functional_call, functional_state
from ...observability import faults as _faults
from ...observability import metrics as _metrics
from ...observability import numerics as _numerics
from ...parallel import pipeline_schedule as _psched
from ...profiler import RecordEvent, TracerEventType
from .. import blocks
from .. import kv_cache as kvc
from .. import sampling
from .. import spec_decode as _spec
from ..engine import (PagedEngineConfig, PagedGenerationEngine,
                      _quantize_weight)
from .tp import param_partition_specs, quant_scale_sharding

__all__ = ["PipelineParallelEngineConfig", "PipelineParallelPagedEngine",
           "PipelineParallelSpecConfig", "PipelineParallelSpeculativeEngine",
           "free_eager_device_copies", "pp_executable_names"]


def pp_executable_names(config, spec=False):
    """The pipeline engines' executable-name set, derived from config
    alone — ONE derivation shared by the engines' `executable_names()`
    and the `.gencfg` recording path (`engine._executable_set`), so the
    serving record's AOT set can never drift from what the engine
    actually builds (the chunk-collapse rule lives only here)."""
    names = [f"decode_stage[{s}]" for s in range(config.pp)]
    for b in config.prefill_buckets:
        chunk = min(config.prefill_chunk or b, b)
        names += [f"prefill_stage[{s}][{chunk}]"
                  for s in range(config.pp)]
        names.append(f"prefill_head[{chunk}]")
    names = sorted(set(names))
    if spec:
        names += ["draft_decode"]
        names += [f"draft_prefill[{b}]" for b in config.prefill_buckets]
        names += [f"verify_stage[{s}]" for s in range(config.pp)]
    return names

_M_BUBBLE = _metrics.gauge(
    "serving_pp_bubble_fraction",
    "Idle fraction of the pipeline-serving tick schedule since engine "
    "start (fill/drain triangles over all decode/prefill rotations; "
    "0 = every stage worked every tick). Growth is failure-class in "
    "tools/metrics_report.py --compare")
_M_STAGE_BUSY = _metrics.gauge(
    "serving_pp_stage_busy",
    "Per-stage busy fraction of the pipeline-serving tick schedule "
    "since engine start",
    labelnames=("stage",))


class PipelineParallelEngineConfig(PagedEngineConfig):
    """PagedEngineConfig plus the (tp, pp) mesh shape.

    pp: pipeline stages (>= 2; pp=1 is just the paged/TP engine).
    tp: tensor degree WITHIN each stage (num_heads must divide by it).
    decode_microbatches: slot groups riding the decode ring (must
      divide `slots`; default = the largest divisor of `slots` that is
      <= pp — more microbatches shrink the per-call bubble as
      (pp-1)/(M+pp-1)).
    prefill_chunk: tokens per pipelined prefill chunk (None = one chunk
      per suffix bucket — the unchunked ladder; a fixed chunk size
      collapses the per-stage prefill executables to ONE each).
    stage_layers: explicit per-stage block counts (default: the uniform
      PipelineLayer split)."""

    def __init__(self, pp=2, tp=1, decode_microbatches=None,
                 prefill_chunk=None, stage_layers=None, **kwargs):
        super().__init__(**kwargs)
        self.pp = int(pp)
        self.tp = int(tp)
        if self.pp < 2:
            raise ValueError(f"pp must be >= 2 (got {pp}); a one-stage "
                             f"pipeline is the paged/tp engine")
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if decode_microbatches:
            self.decode_microbatches = int(decode_microbatches)
            if self.slots % self.decode_microbatches:
                raise ValueError(
                    f"decode_microbatches={self.decode_microbatches} "
                    f"must divide slots={self.slots}")
        else:
            # default: the largest divisor of slots within the stage
            # count — always valid, bubble-minimal for the slot shape
            self.decode_microbatches = max(
                d for d in range(1, min(self.pp, self.slots) + 1)
                if self.slots % d == 0)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 or None")
        self.stage_layers = tuple(int(x) for x in stage_layers) \
            if stage_layers else None

    _DICT_FIELDS = PagedEngineConfig._DICT_FIELDS + (
        "pp", "tp", "decode_microbatches", "prefill_chunk",
        "stage_layers")


class _Stage:
    """Per-stage placement record: the GPTStage module, its 'mp' mesh,
    placed params/buffers (+ the int8 decode set), its resident KV pool
    slice, and the stage-local -> global param-name map."""
    __slots__ = ("module", "mesh", "replicated", "pool_sharding",
                 "scale_sharding", "param_shardings", "params",
                 "buffers", "decode_params", "pool", "name_map",
                 "layers")


class PipelineParallelPagedEngine(PagedGenerationEngine):
    """PagedGenerationEngine partitioned into pipeline stages over a
    (tp, pp) device grid. Public contract unchanged (prefill / decode /
    adopt / extract / reset / swap, compile-once trace counters — now
    PER STAGE under `decode_pp` / `prefill_pp` / `adopt_pp`); block
    accounting is host-side and shared across stages."""

    def __init__(self, model, config=None, **kwargs):
        config = config or PipelineParallelEngineConfig(**kwargs)
        if not isinstance(config, PipelineParallelEngineConfig):
            raise TypeError("PipelineParallelPagedEngine needs a "
                            "PipelineParallelEngineConfig")
        devices = jax.devices()
        if config.pp * config.tp > len(devices):
            raise ValueError(
                f"(tp={config.tp}) x (pp={config.pp}) needs "
                f"{config.pp * config.tp} devices, have {len(devices)}")
        if model.cfg.num_heads % config.tp:
            raise ValueError(
                f"tp={config.tp} must divide num_heads="
                f"{model.cfg.num_heads} (heads are the sharded axis)")
        if model.cfg.num_layers < config.pp:
            raise ValueError(
                f"pp={config.pp} exceeds num_layers="
                f"{model.cfg.num_layers}")
        super().__init__(model, config)
        self.trace_counts["decode_pp"] = {}
        self.trace_counts["prefill_pp"] = {}
        self.trace_counts["adopt_pp"] = {}
        self._stage_decode = [self._make_stage_decode(s)
                              for s in range(config.pp)]
        self._stage_prefill = {}      # (stage, chunk_len) -> cached fn
        self._pp_head = {}            # chunk_len -> cached head fn
        self._pp_adopt = {}           # (stage, bucket) -> cached fn

    # -- placement ------------------------------------------------------------
    def _alloc_state(self):
        from ...text.models.gpt import gpt_pipeline_stages
        cfg = self._model.cfg
        c = self.config
        devices = jax.devices()
        modules = gpt_pipeline_stages(self._model, c.pp,
                                      stage_layers=c.stage_layers)
        self._stages = []
        for s, mod in enumerate(modules):
            st = _Stage()
            st.module = mod
            st.layers = mod.stop - mod.start
            st.mesh = Mesh(np.asarray(devices[s * c.tp:(s + 1) * c.tp]),
                           ("mp",))
            st.replicated = NamedSharding(st.mesh, P())
            st.pool_sharding = NamedSharding(st.mesh,
                                             P(None, None, "mp", None))
            st.scale_sharding = NamedSharding(st.mesh, P(None, "mp"))
            # stage-local functional names -> global model names (the
            # swap/quantization join): blocks re-index by the stage's
            # start offset, the tied head matrix IS wte.weight
            st.name_map = {}
            for name in functional_state(mod)[0]:
                if name.startswith("blocks."):
                    i, rest = name[len("blocks."):].split(".", 1)
                    st.name_map[name] = f"blocks.{mod.start + int(i)}.{rest}"
                elif name.startswith("head_wte."):
                    st.name_map[name] = "wte." + name[len("head_wte."):]
                else:
                    st.name_map[name] = name
            self._stages.append(st)
        self._place_stage_params()
        # the master param copy stays HOST-resident: it is the
        # hot-swap validation record, not serving state — per-device
        # HBM accounting must see only the per-stage placed shards
        # (buffers too: each stage holds its own placed copy)
        self._params = {k: np.asarray(jax.device_get(v))
                        for k, v in self._params.items()}
        self._buffers = {k: np.asarray(jax.device_get(v))
                         for k, v in self._buffers.items()}
        heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        for st in self._stages:
            raw = blocks.alloc_quant_pools(
                st.layers, c.num_blocks, c.block_size, heads, hd) \
                if self.kv_quantized else blocks.alloc_pools(
                    st.layers, c.num_blocks, c.block_size, heads, hd)
            st.pool = tuple(type(l)(
                *(jax.device_put(x, st.pool_sharding if x.ndim == 4
                                 else st.scale_sharding) for x in l))
                for l in raw)
        self._alloc_host_state()
        # tick/bubble accounting across the engine lifetime
        self._pp_ticks = 0
        self._pp_busy = np.zeros((c.pp,), np.int64)
        self._decode_tbl = _psched.build_serving_tables(
            c.decode_microbatches, c.pp)

    def _place_stage_params(self):
        """(Re-)place every stage's float params + buffers on its mesh
        from the master copy — at build and after every hot-swap."""
        for st in self._stages:
            specs = param_partition_specs(st.module)
            st.param_shardings = {
                name: NamedSharding(st.mesh, specs.get(name, P()))
                for name in st.name_map}
            st.params = {
                name: jax.device_put(self._params[st.name_map[name]],
                                     st.param_shardings[name])
                for name in st.name_map}
            fs_buffers = functional_state(st.module)[1]
            st.buffers = {name: jax.device_put(arr, st.replicated)
                          for name, arr in fs_buffers.items()}

    def _build_decode_params(self):
        """Per-stage decode param sets: identity (float) or the int8
        codes+scales re-expression, placed on the stage's mesh with the
        scale vector following the split only when the channel axis IS
        the sharded axis (the tp.py rule, per stage)."""
        self._decode_params = {}      # unused: decode() is per-stage
        for st in getattr(self, "_stages", ()):
            if self.config.weight_dtype != "int8":
                st.decode_params = st.params
                continue
            out = {}
            for name, arr in st.params.items():
                axis = self._weight_quant_axis(st.name_map[name], arr)
                if axis is None:
                    out[name] = arr
                    continue
                codes, s_b = _quantize_weight(arr, axis)
                sharding = st.param_shardings[name]
                out[name] = {
                    "q": jax.device_put(codes, sharding),
                    "scale": jax.device_put(s_b, quant_scale_sharding(
                        st.mesh, sharding, axis, s_b.ndim))}
            st.decode_params = out

    def _place_param(self, name, arr):
        """The swapped-in master copy stays HOST-resident: staging the
        whole float model through one device would defeat the
        bigger-than-one-host claim exactly in the swap window. Stage
        placement happens in `_after_param_swap`, device by device."""
        return np.asarray(arr)

    def _after_param_swap(self):
        self._place_stage_params()
        self._build_decode_params()

    def _place_adapter_tree(self, tree):
        """Per-tenant LoRA banks (ISSUE 17) shard WITH the stage: stage
        s holds only its own blocks' [n_slots, r, ...] factors, sliced
        from the bank tree by the stage's layer range and replicated on
        its 'mp' mesh next to the stage shard — no stage ever stores
        another stage's deltas. Returns a per-stage tuple; the stage
        executables receive their own element."""
        placed = []
        for st in self._stages:
            sl = {"layers": tuple(
                tree["layers"][st.module.start:st.module.stop])}
            placed.append(jax.device_put(sl, st.replicated))
        return tuple(placed)

    def _stage_adapter_args(self, s, lo, hi):
        """Adapter extras for one (stage, microbatch) cell: stage s's
        layer slice + the microbatch's slot->adapter-slot ids. Empty
        when no bank is attached, so adapter-off stage traces keep
        today's exact signatures."""
        if self._adapter_bank is None:
            return ()
        return (self._adapter_tree[s],
                jnp.asarray(self._slot_adapter[lo:hi]))

    @property
    def _pool(self):
        """The whole-model pool view, stage slices in layer order —
        what the extract/handoff paths walk. Read-only: every writer in
        this engine commits to `self._stages[s].pool` instead."""
        return tuple(l for st in self._stages for l in st.pool)

    def _weight_sources(self):
        """Per-stage placed params only: the host master copy is the
        swap-validation record, not device state (the base walk also
        skips numpy leaves by construction)."""
        return [src for st in self._stages
                for src in (st.params, st.decode_params)]

    # -- stage forward --------------------------------------------------------
    def _run_stage(self, st, params, pool, tables, pos, x, op,
                   valid=None, adapters=None):
        """functional_call of one GPTStage over raw arrays -> (out,
        new stage pool). `params` may be the int8 decode set (dequant
        at trace time, like the single-device engine). `adapters` is
        this STAGE's per-tenant LoRA view ({"slot", "layers": the
        stage's own slice}); the kwarg is added only when present so
        adapter-off traces stay byte-identical."""
        cache = blocks.PagedDecodeCache(
            tuple(type(l)(*(Tensor(a) for a in l)) for l in pool),
            Tensor(tables), Tensor(pos),
            None if valid is None else Tensor(valid))
        kwargs = {"cache": cache, "pos": cache.pos,
                  "tables": cache.tables, "valid": cache.valid,
                  "op": op}
        if adapters is not None:
            kwargs["adapters"] = adapters
        out, _ = functional_call(
            st.module, self._dequant_params(params), st.buffers,
            args=(Tensor(x),), kwargs=kwargs, train=False)
        y, new_layers = out
        return y._data, tuple(type(l)(*(a._data for a in l))
                              for l in new_layers)

    def _constrain_stage(self, st, pool):
        return tuple(type(l)(
            *(jax.lax.with_sharding_constraint(
                x, st.pool_sharding if x.ndim == 4 else st.scale_sharding)
              for x in l)) for l in pool)

    # -- decode: ONE executable PER STAGE ------------------------------------
    def _make_stage_forward(self, s, counter, name):
        """A NON-LAST stage's ring executable — the one-token decode
        hop and the spec verify hop (ISSUE 14) share this exact shape:
        run the stage's blocks over the hop input, pin the activation
        and pool output shardings. Only the trace counter and the
        cache name differ."""
        st = self._stages[s]

        def fn(params, pool, tables, pos, x, *extra):
            adapters, _ = self._split_extra(extra)
            self.trace_counts[counter][s] = \
                self.trace_counts[counter].get(s, 0) + 1
            with self._numerics_scope() as sink:
                y, npool = self._run_stage(st, params, pool, tables,
                                           pos, x, op="block",
                                           adapters=adapters)
                # per-stage sentinel: the hop activation leaving stage s
                _numerics.tap(f"stage{s}.act", y)
            y = jax.lax.with_sharding_constraint(y, st.replicated)
            if sink is None:
                return y, self._constrain_stage(st, npool)
            return y, self._constrain_stage(st, npool), sink
        return self._cached(fn, name)

    def _make_stage_decode(self, s):
        st = self._stages[s]

        if not st.module.is_last:
            return self._make_stage_forward(s, "decode_pp",
                                            f"decode_stage[{s}]")

        def fn(params, pool, tables, pos, x, key, *extra):
            adapters, rng = self._split_extra(extra)
            self.trace_counts["decode_pp"][s] = \
                self.trace_counts["decode_pp"].get(s, 0) + 1
            with self._numerics_scope() as sink:
                logits, npool = self._run_stage(st, params, pool, tables,
                                                pos, x, op="block_head",
                                                adapters=adapters)
                nxt = self._select_slots(logits[:, 0, :], key, *rng)
                _numerics.tap("decode.logits", logits[:, 0, :])
            npool = self._constrain_stage(st, npool)
            out = (nxt, npool)
            if self.config.capture_logits:
                out = out + (logits[:, 0, :],)
            if sink is not None:
                out = out + (sink,)      # the sink rides LAST, always
            return out
        return self._cached(fn, f"decode_stage[{s}]")

    def _ride_ring(self, tbl, mb_count, stage_call):
        """Walk a forward-1F1B tick table: for every busy (tick, stage)
        cell, move the microbatch's activation one hop onto the stage's
        mesh (the `serving.pp_handoff` chaos site fires per hop), call
        `stage_call(s, st, g, x)` -> (out, new_pool) — `x` is None on
        the FIRST stage, whose callable owns its own input — commit the
        stage pool, and keep the busy/tick accounting. Returns the
        per-microbatch LAST-stage outputs, still on device (a host
        fetch per tick would serialize exactly the cross-stage overlap
        the ring exists for). ONE walker shared by one-token decode and
        the spec verify ring (ISSUE 14), so handoff chaos, busy
        accounting, and pool-commit semantics can never diverge between
        them. 3-D (tokens-per-tick) tables walk the same skeleton —
        each cell's token slots collapse to their microbatch."""
        hidden = [None] * mb_count
        out = [None] * mb_count
        for t in range(tbl.shape[0]):
            for s in range(self.config.pp):
                g = int(tbl[t, s] if tbl.ndim == 2 else tbl[t, s, 0])
                if g < 0:
                    continue
                if tbl.ndim == 3:
                    g //= tbl.shape[2]       # token slot -> microbatch
                st = self._stages[s]
                if st.module.is_first:
                    x = None
                else:
                    # the stage boundary: the chaos site fires, then
                    # the activation moves onto this stage's mesh
                    _faults.fire("serving.pp_handoff")
                    x = jax.device_put(hidden[g], st.replicated)
                self._pp_busy[s] += 1
                res, npool = stage_call(s, st, g, x)
                if st.module.is_last:
                    out[g] = res
                else:
                    hidden[g] = res
                st.pool = npool
            self._pp_ticks += 1
        return out

    def decode(self):
        """Advance every slot one token by running the M-microbatch
        serving ring through the pp stages (module docstring). Returns
        np.int32 [slots] exactly like the single-device engine."""
        _faults.fire("serving.decode_step")
        self._fire_kv_quant_chaos()
        self._fire_numerics_chaos()
        self.ensure_decode_capacity()
        c = self.config
        M = c.decode_microbatches
        mbs = c.slots // M
        tokens = self._last_tokens
        key = self._next_key()
        out_tokens = np.zeros((c.slots,), np.int32)
        out_logits = [None] * M
        sinks = []
        # tables/pos are immutable for the whole call: upload each
        # microbatch's slices ONCE, not once per (tick, stage) — each
        # mb runs pp stages, so this saves (pp-1)/pp of the transfers
        # on the per-token hot path
        mb_slices = [(jnp.asarray(self._tables[g * mbs:(g + 1) * mbs]),
                      jnp.asarray(self._pos[g * mbs:(g + 1) * mbs]))
                     for g in range(M)]

        def stage_call(s, st, g, x):
            lo, hi = g * mbs, (g + 1) * mbs
            mb_tables, mb_pos = mb_slices[g]
            adp = self._stage_adapter_args(s, lo, hi)
            if st.module.is_first:
                x = jnp.asarray(tokens[lo:hi].reshape(mbs, 1))
            if not st.module.is_last:
                res = self._stage_decode[s](st.decode_params, st.pool,
                                            mb_tables, mb_pos, x, *adp)
                if self._numerics_armed:
                    y, npool, sink = res
                    sinks.append(sink)
                    return y, npool
                return res
            args = [st.decode_params, st.pool, mb_tables, mb_pos, x, key,
                    *adp]
            if self._sampling:
                args += [jnp.asarray(self._slot_seeds[lo:hi]),
                         jnp.asarray(self._slot_gen[lo:hi])]
            res = self._stage_decode[s](*args)
            if self._numerics_armed:
                sinks.append(res[-1])
                res = res[:-1]
            if c.capture_logits:
                nxt, npool, lg = res
                out_logits[g] = lg
                return nxt, npool
            return res

        with RecordEvent("serving::decode_step",
                         TracerEventType.UserDefined,
                         {"slots": c.slots, "paged": True, "pp": c.pp,
                          "tp": c.tp, "microbatches": M,
                          "kv_dtype": c.kv_dtype,
                          "attend": self.attention_impl}), \
                blocks.attention_impl(self.attention_impl):
            out_nxt = self._ride_ring(self._decode_tbl, M, stage_call)
        for sink in sinks:
            self._ingest_numerics(sink)
        for g in range(M):
            out_tokens[g * mbs:(g + 1) * mbs] = np.asarray(out_nxt[g],
                                                           np.int32)
        self._pos = np.minimum(self._pos + 1,
                               c.max_len - 1).astype(np.int32)
        self._slot_gen += 1
        if c.capture_logits:
            self.last_logits = np.concatenate(
                [np.asarray(l, np.float32) for l in out_logits], axis=0)
        self._export_pp_stats()
        self._last_tokens = out_tokens.copy()
        return out_tokens

    def _apply_numerics_corruption(self, name, mode):
        """numerics.corrupt over per-stage param dicts: poison the named
        tensor on whichever stage holds it (stage dicts keep the parent
        model's global param names)."""
        if not name:
            return
        for st in self._stages:
            entry = st.decode_params.get(name)
            if entry is None:
                continue
            entry = self._corrupt_entry(entry, mode)
            if entry is not None:
                st.decode_params = dict(st.decode_params, **{name: entry})
            return

    def _fire_kv_quant_chaos(self):
        """The serving.kv_quant site over per-stage pools: corrupt one
        in-use block's scale row of stage 0's first resident layer."""
        if not self.kv_quantized:
            return
        spec = _faults.fire("serving.kv_quant")
        if spec is None or spec.mode != "truncate":
            return
        victim = next((int(b) for b in range(1, self.block_pool.num_blocks)
                       if self.block_pool.refcount(b) > 0), None)
        if victim is None:
            return
        st = self._stages[0]
        layer = st.pool[0]
        st.pool = (type(layer)(
            layer.k, layer.v,
            layer.k_scale.at[victim].mul(64.0),
            layer.v_scale.at[victim].mul(64.0)),) + st.pool[1:]

    # -- prefill: chunks pipelined through the stages -------------------------
    def _make_stage_prefill(self, s, chunk):
        st = self._stages[s]
        nb = self.config.max_blocks_per_slot

        def fn(params, pool, tables, slot, x, start, valid):
            key = (s, chunk)
            self.trace_counts["prefill_pp"][key] = \
                self.trace_counts["prefill_pp"].get(key, 0) + 1
            slot = slot.astype(jnp.int32)
            row = jax.lax.dynamic_slice(tables, (slot, 0), (1, nb))
            y, npool = self._run_stage(st, params, pool, row,
                                       start[None], x, op="block",
                                       valid=valid[None])
            y = jax.lax.with_sharding_constraint(y, st.replicated)
            return y, self._constrain_stage(st, npool)
        return self._cached(fn, f"prefill_stage[{s}][{chunk}]")

    def _make_pp_head(self, chunk):
        st = self._stages[-1]

        def fn(params, hidden, idx, key):
            tag = ("head", chunk)
            self.trace_counts["prefill_pp"][tag] = \
                self.trace_counts["prefill_pp"].get(tag, 0) + 1
            logits, _ = functional_call(
                st.module, params, st.buffers, args=(Tensor(hidden),),
                kwargs={"op": "head"}, train=False)
            last = jax.lax.dynamic_index_in_dim(logits._data[0], idx,
                                                keepdims=False)
            return self._select(last[None, :], key)[0]
        return self._cached(fn, f"prefill_head[{chunk}]")

    def _prefill_execute(self, slot, padded, length, start, bucket):
        """The pipelined prefill: pad the suffix to whole chunks, run
        only the chunks carrying real tokens, and stream them through
        the stages on the forward-1F1B tick table — chunk c enters
        stage 0 at tick c while chunk c-1 runs stage 1. Each hop fires
        `serving.pp_handoff`; K/V lands in each stage's own pool as the
        chunk passes. Returns the first token from the head tap over
        the final chunk's last-stage hidden."""
        c = self.config
        chunk = min(c.prefill_chunk or bucket, bucket)
        n_run = max(1, -(-length // chunk))
        ids = np.zeros((n_run * chunk,), np.int32)
        n_copy = min(padded.shape[0], n_run * chunk)
        ids[:n_copy] = padded[:n_copy]
        tables = jnp.asarray(self._tables)
        slot_j = jnp.asarray(slot, jnp.int32)

        def stage_call(s, st, g, x):
            if (s, chunk) not in self._stage_prefill:
                self._stage_prefill[(s, chunk)] = \
                    self._make_stage_prefill(s, chunk)
            if st.module.is_first:
                x = jnp.asarray(ids[g * chunk:(g + 1) * chunk][None, :])
            start_g = start + g * chunk
            valid_g = int(np.clip(length - g * chunk, 0, chunk))
            return self._stage_prefill[(s, chunk)](
                st.params, st.pool, tables, slot_j, x,
                jnp.asarray(start_g, jnp.int32),
                jnp.asarray(valid_g, jnp.int32))

        # the prefill chunks ride the SAME walker as the decode/verify
        # rings — a chunk is one microbatch of the forward-1F1B table
        hidden = self._ride_ring(
            _psched.build_serving_tables(n_run, c.pp), n_run, stage_call)
        if chunk not in self._pp_head:
            self._pp_head[chunk] = self._make_pp_head(chunk)
        idx = (length - 1) - (n_run - 1) * chunk
        first = self._pp_head[chunk](
            self._stages[-1].params, hidden[n_run - 1],
            jnp.asarray(idx, jnp.int32), self._slot_key(slot))
        self._pos[slot] = start + length
        self._export_pp_stats()
        return int(first)

    # -- KV adopt (multi-host handoff sink), per stage ------------------------
    def _adopt_scatter(self, slot, bucket, pad_ks, pad_vs):
        off = 0
        for s, st in enumerate(self._stages):
            n = st.layers
            if (s, bucket) not in self._pp_adopt:
                self._pp_adopt[(s, bucket)] = \
                    self._make_stage_adopt(s, bucket)
            st.pool = self._pp_adopt[(s, bucket)](
                st.pool, jnp.asarray(self._tables),
                jnp.asarray(slot, jnp.int32),
                pad_ks[off:off + n], pad_vs[off:off + n])
            off += n

    def _make_stage_adopt(self, s, bucket):
        st = self._stages[s]
        nb = self.config.max_blocks_per_slot

        def adopt_fn(pool, tables, slot, new_ks, new_vs):
            key = (s, bucket)
            self.trace_counts["adopt_pp"][key] = \
                self.trace_counts["adopt_pp"].get(key, 0) + 1
            slot = slot.astype(jnp.int32)
            row = jax.lax.dynamic_slice(tables, (slot, 0), (1, nb))
            zero = jnp.zeros((1,), jnp.int32)
            npool = []
            for layer, k, v in zip(pool, new_ks, new_vs):
                if hasattr(layer, "k_scale"):
                    kq, ksc = blocks.quant_write(layer.k, layer.k_scale,
                                                 k[None], row, zero)
                    vq, vsc = blocks.quant_write(layer.v, layer.v_scale,
                                                 v[None], row, zero)
                    npool.append(blocks.QuantPagedLayerKV(kq, vq, ksc,
                                                          vsc))
                else:
                    npool.append(blocks.PagedLayerKV(
                        blocks.write(layer.k, k[None], row, zero),
                        blocks.write(layer.v, v[None], row, zero)))
            return self._constrain_stage(st, tuple(npool))
        return self._cached(adopt_fn, f"adopt_stage[{s}][{bucket}]")

    # -- observability / introspection ----------------------------------------
    def _export_pp_stats(self):
        stats = self.pp_stats()
        _M_BUBBLE.set(stats["bubble_fraction"])
        for s, b in enumerate(stats["stage_busy"]):
            _M_STAGE_BUSY.labels(stage=str(s)).set(b)

    def pp_stats(self):
        """Lifetime tick accounting: {bubble_fraction, stage_busy[s],
        ticks} — what the gauges, the scheduler step records, and
        serve_report's per-stage column carry."""
        t = max(self._pp_ticks, 1)
        busy = [float(b) / t for b in self._pp_busy]
        work = int(self._pp_busy.sum())
        return {"ticks": int(self._pp_ticks),
                "stage_busy": busy,
                "bubble_fraction":
                    float(1.0 - work / (t * self.config.pp))}

    def stage_report(self):
        """Per-stage placement proof: layer range, devices, and the
        heads each device holds of that stage's layer-0 K pool."""
        out = []
        for st in self._stages:
            shards = st.pool[0].k.addressable_shards
            out.append({
                "layers": [st.module.start, st.module.stop],
                "devices": sorted(str(d) for d in st.mesh.devices.flat),
                "heads_per_device": {str(s.device): int(s.data.shape[2])
                                     for s in shards}})
        return out

    # -- AOT warmup ------------------------------------------------------------
    def executable_names(self):
        return pp_executable_names(self.config)

    def precompile(self):
        """AOT-build the per-stage executable set (decode ring + every
        bucket's prefill chunk set + the head taps)."""
        c = self.config
        mbs = c.slots // c.decode_microbatches
        H = self._model.cfg.hidden_size
        key = self._warm_key()
        out = {}
        with blocks.attention_impl(self.attention_impl):
            for s, st in enumerate(self._stages):
                mb_tables = jnp.asarray(self._tables[:mbs])
                mb_pos = jnp.asarray(self._pos[:mbs])
                if st.module.is_first:
                    x = jnp.zeros((mbs, 1), jnp.int32)
                else:
                    x = jax.device_put(jnp.zeros((mbs, 1, H), jnp.float32),
                                       st.replicated)
                adp = self._stage_adapter_args(s, 0, mbs)
                if st.module.is_last:
                    args = [st.decode_params, st.pool, mb_tables, mb_pos,
                            x, key, *adp]
                    if self._sampling:
                        args += [jnp.zeros((mbs,), jnp.uint32),
                                 jnp.zeros((mbs,), jnp.int32)]
                    out[f"decode_stage[{s}]"] = \
                        self._stage_decode[s].warm(*args)
                else:
                    out[f"decode_stage[{s}]"] = self._stage_decode[s].warm(
                        st.decode_params, st.pool, mb_tables, mb_pos, x,
                        *adp)
            for b in c.prefill_buckets:
                chunk = min(c.prefill_chunk or b, b)
                for s, st in enumerate(self._stages):
                    if (s, chunk) not in self._stage_prefill:
                        self._stage_prefill[(s, chunk)] = \
                            self._make_stage_prefill(s, chunk)
                    if st.module.is_first:
                        x = jnp.zeros((1, chunk), jnp.int32)
                    else:
                        x = jax.device_put(
                            jnp.zeros((1, chunk, H), jnp.float32),
                            st.replicated)
                    out[f"prefill_stage[{s}][{chunk}]"] = \
                        self._stage_prefill[(s, chunk)].warm(
                            st.params, st.pool, jnp.asarray(self._tables),
                            jnp.asarray(0, jnp.int32), x,
                            jnp.asarray(0, jnp.int32),
                            jnp.asarray(1, jnp.int32))
                if chunk not in self._pp_head:
                    self._pp_head[chunk] = self._make_pp_head(chunk)
                out[f"prefill_head[{chunk}]"] = self._pp_head[chunk].warm(
                    self._stages[-1].params,
                    jax.device_put(jnp.zeros((1, chunk, H), jnp.float32),
                                   self._stages[-1].replicated),
                    jnp.asarray(0, jnp.int32), key)
        return out


class PipelineParallelSpecConfig(_spec.SpecDecodeConfig,
                                 PipelineParallelEngineConfig):
    """The spec×pp knob set (ISSUE 14): SpecDecodeConfig's speculative
    half (gamma, draft_layers, greedy-only, no capture_logits) over
    PipelineParallelEngineConfig's mesh half (pp, tp,
    decode_microbatches, prefill_chunk, stage_layers). The cooperative
    __init__ chain resolves the pp shape first, then the speculative
    validation runs — one config, every knob of both parents."""

    _DICT_FIELDS = PipelineParallelEngineConfig._DICT_FIELDS + (
        "gamma", "draft_layers")


class PipelineParallelSpeculativeEngine(_spec.SpeculativeEngine,
                                        PipelineParallelPagedEngine):
    """Speculative decode ON the pipeline ring (ISSUE 14): the two
    biggest decode-throughput layers in the stack, composed so their
    wins multiply.

    DRAFT — on the first stage's mesh. The truncated shared-weight
    draft (target's first `draft_layers` blocks + embeddings + final
    LN, one logical weight set) is placed REPLICATED on stage 0's 'mp'
    mesh next to that stage's shard: γ single-token draft decodes run
    there against the draft's dense cache exactly as on one device.
    The pp master copy is host numpy, so the single-device engine's
    no-second-DEVICE-copy identity share becomes a real stage-0 byte
    bill here (draft weights + its dense KV) — counted by
    `hbm_accounting`, priced in docs/PERF_NOTES.md, and small by
    construction at production shape (1/12 of the layers).

    VERIFY — ONE fixed-shape [mbs, γ+1] window per microbatch rides
    the SAME forward-1F1B tick tables as one-token pp decode
    (`build_serving_tables(M, pp, tokens_per_tick=γ+1)`), one
    compile-once executable per stage (`verify_pp` trace counters:
    stage 0 embeds the window tokens, interior stages forward the
    [mbs, γ+1, H] activation, the last stage taps logits and runs
    `sampling.greedy_verify` in-trace). Each stage writes the window's
    K/V into its own resident pool slice through the shared block
    tables — so a REJECTION needs no cross-stage protocol at all:
    exactly as in PR 7, pos advances by n_accepted+1 on the host and
    the rejected tail stays physically in already-owned blocks of
    every stage, invisible by position masking and overwritten next
    round. No block reference moves, on any stage.

    WHY IT MULTIPLIES — each ring pass costs the same M+pp-1 ticks as
    one-token decode but emits up to (γ+1)× the tokens, so the
    fill/drain bubble amortizes per emitted token by the acceptance-
    weighted window width ON TOP of the (1+γ/12)/(E[acc]+1) per-token
    compute ratio the single-device engine buys (PERF_NOTES prices the
    product). Greedy streams are BIT-IDENTICAL to both parents — the
    one-token pp engine and the single-device speculative engine — and
    per-slot sampler generation counters advance by n_emit, so v3 RNG
    KV-handoff bundles stay failover-exact mid-window."""

    def __init__(self, model, config=None, draft=None, **kwargs):
        config = config or PipelineParallelSpecConfig(**kwargs)
        if not isinstance(config, PipelineParallelSpecConfig):
            raise TypeError("PipelineParallelSpeculativeEngine needs a "
                            "PipelineParallelSpecConfig")
        # an auto-built truncated draft tracks the target by NAME across
        # hot-swaps (the host master copy forecloses identity sharing);
        # an explicit draft keeps its own weights — it only ever moves
        # the acceptance rate, never the emitted stream
        self._draft_shares_target = draft is None
        _spec.SpeculativeEngine.__init__(self, model, config, draft=draft)
        # the single-device verify executable must never run here — the
        # window rides the stage ring instead. Poisoned loudly (None),
        # and its trace counter staying 0 is asserted by the tests.
        self._spec_verify = None
        self.trace_counts["verify_pp"] = {}
        self._stage_verify = [self._make_stage_verify(s)
                              for s in range(config.pp)]
        self._verify_tbl = _psched.build_serving_tables(
            config.decode_microbatches, config.pp,
            tokens_per_tick=config.gamma + 1)

    # -- draft placement: stage 0's mesh --------------------------------------
    def _place_draft_kv(self, layers):
        st = self._stages[0]
        return tuple(kvc.LayerKV(jax.device_put(l.k, st.replicated),
                                 jax.device_put(l.v, st.replicated))
                     for l in layers)

    def _draft_feed(self, vec):
        return jax.device_put(vec, self._stages[0].replicated)

    def _build_draft_decode_params(self):
        """Draft-on-first-stage: params AND buffers device_put
        replicated onto stage 0's mesh (the draft is small next to a
        stage shard; a second partition-spec map would buy little).
        weight_dtype="int8" re-expresses the placed set exactly like
        the target's per-stage decode sets. Re-run after every
        hot-swap, so a swapped target never serves against a stale
        draft."""
        st = self._stages[0]
        self._draft_params = {
            name: jax.device_put(arr, st.replicated)
            for name, arr in self._draft_params.items()}
        self._draft_buffers = {
            name: jax.device_put(arr, st.replicated)
            for name, arr in self._draft_buffers.items()}
        if self.config.weight_dtype != "int8":
            self._draft_decode_params = self._draft_params
            return
        out = {}
        for name, arr in self._draft_params.items():
            axis = self._weight_quant_axis(name, arr)
            if axis is None:
                out[name] = arr
                continue
            codes, s_b = _quantize_weight(arr, axis)
            out[name] = {"q": jax.device_put(codes, st.replicated),
                         "scale": jax.device_put(s_b, st.replicated)}
        self._draft_decode_params = out

    def swap_params(self, new_params):
        """Hot-swap for the spec×pp pair: the target swaps through the
        pp path (host master copy, per-stage re-placement), then the
        auto-built truncated draft re-sources every param from the NEW
        master by name — same between-steps window, so acceptance never
        degrades against a stale draft. An explicit draft keeps its own
        arrays."""
        n = PipelineParallelPagedEngine.swap_params(self, new_params)
        if self._draft_shares_target:
            for name in list(self._draft_params):
                if name in self._params:
                    self._draft_params[name] = self._params[name]
            self._build_draft_decode_params()
        return n

    # -- the per-stage verify executables --------------------------------------
    def _make_stage_verify(self, s):
        st = self._stages[s]

        if not st.module.is_last:
            # same hop shape as the one-token ring — only the counter
            # and the avals (a γ+1 window instead of one token) differ
            return self._make_stage_forward(s, "verify_pp",
                                            f"verify_stage[{s}]")

        def fn(params, pool, tables, pos, x, window, *extra):
            adapters, _ = self._split_extra(extra)
            self.trace_counts["verify_pp"][s] = \
                self.trace_counts["verify_pp"].get(s, 0) + 1
            with self._numerics_scope() as sink:
                logits, npool = self._run_stage(st, params, pool, tables,
                                                pos, x, op="block_head",
                                                adapters=adapters)
                choices, n_acc, last = sampling.greedy_verify(logits,
                                                              window)
                _numerics.tap("spec.verify_logits", logits)
            npool = self._constrain_stage(st, npool)
            if sink is None:
                return choices, n_acc, last, npool
            return choices, n_acc, last, npool, sink
        return self._cached(fn, f"verify_stage[{s}]")

    # -- public compute API ----------------------------------------------------
    def decode_many(self):
        """One speculative round over the stage ring: γ draft decodes on
        stage 0's mesh, then the [mbs, γ+1] verify window of every slot
        microbatch rides the forward-1F1B tick table through the pp
        stages — each stage writing its own pool slice — and the host
        rolls every position back to committed+accepted+1. Returns
        (tokens [S, γ+1], n_emit [S]) exactly like the single-device
        speculative engine."""
        _faults.fire("serving.decode_step")
        self._fire_kv_quant_chaos()
        self._fire_numerics_chaos()
        self.ensure_decode_capacity()          # γ+1-wide block growth
        c = self.config
        gamma = c.gamma
        W = gamma + 1
        M = c.decode_microbatches
        mbs = c.slots // M
        t0 = time.perf_counter()
        with RecordEvent("serving::spec_draft", TracerEventType.UserDefined,
                         {"gamma": gamma, "slots": c.slots, "pp": c.pp,
                          "tp": c.tp}):
            window, dk, dv, dpos = self._draft_propose()
        draft_s = time.perf_counter() - t0
        _spec._M_DRAFT_SECONDS.observe(draft_s)
        t1 = time.perf_counter()
        # tables/pos upload once per microbatch (the pp decode rule);
        # the window slices stay ON DEVICE — stage 0 embeds them, the
        # last stage compares against them
        mb_slices = [(jnp.asarray(self._tables[g * mbs:(g + 1) * mbs]),
                      jnp.asarray(self._pos[g * mbs:(g + 1) * mbs]))
                     for g in range(M)]
        mb_windows = [window[g * mbs:(g + 1) * mbs] for g in range(M)]
        sinks = []

        def stage_call(s, st, g, x):
            lo, hi = g * mbs, (g + 1) * mbs
            mb_tables, mb_pos = mb_slices[g]
            adp = self._stage_adapter_args(s, lo, hi)
            if st.module.is_first:
                x = mb_windows[g]
            if not st.module.is_last:
                res = self._stage_verify[s](st.decode_params, st.pool,
                                            mb_tables, mb_pos, x, *adp)
                if self._numerics_armed:
                    y, npool, sink = res
                    sinks.append(sink)
                    return y, npool
                return res
            win = jax.device_put(mb_windows[g], st.replicated)
            res = self._stage_verify[s](
                st.decode_params, st.pool, mb_tables, mb_pos, x, win,
                *adp)
            if self._numerics_armed:
                sinks.append(res[-1])
                res = res[:-1]
            ch, na, la, npool = res
            return (ch, na, la), npool

        with RecordEvent("serving::spec_verify",
                         TracerEventType.UserDefined,
                         {"window": W, "slots": c.slots, "pp": c.pp,
                          "microbatches": M,
                          "attend": self.attention_impl}), \
                blocks.attention_impl(self.attention_impl):
            out = self._ride_ring(self._verify_tbl, M, stage_call)
        for sink in sinks:
            self._ingest_numerics(sink)
        verify_s = time.perf_counter() - t1
        _spec._M_VERIFY_SECONDS.observe(verify_s)
        choices = np.concatenate([np.asarray(o[0], np.int32)
                                  for o in out])
        n_acc = np.concatenate([np.asarray(o[1], np.int32) for o in out])
        last = np.concatenate([np.asarray(o[2], np.int32) for o in out])
        # the rollback, host-side across every stage at once: rejected-
        # tail K/V stays physically resident beyond the new pos in each
        # stage's pool — invisible, overwritten next round, no block
        # reference moves (the PR 7 rule, unchanged by the mesh)
        self._pos = np.minimum(self._pos + n_acc + 1,
                               c.max_len - 1).astype(np.int32)
        self._draft_kv = tuple(kvc.LayerKV(k, v) for k, v in zip(dk, dv))
        self._draft_pos = self._pos.copy()
        n_emit = (n_acc + 1).astype(np.int32)
        self._slot_gen += n_emit               # v3 RNG stays stream-exact
        self._last_tokens = last.astype(np.int32).copy()
        self.last_spec_stats = {
            "proposed_per_slot": gamma,
            "draft_s": draft_s, "verify_s": verify_s}
        self._export_pp_stats()
        return choices, n_emit

    # -- AOT warmup -------------------------------------------------------------
    def executable_names(self):
        return pp_executable_names(self.config, spec=True)

    def precompile(self):
        """The pp executable set (decode ring + prefill chunks + head
        taps) plus the speculative set: draft decode/prefills on stage
        0's mesh and every stage's [mbs, γ+1] verify."""
        out = PipelineParallelPagedEngine.precompile(self)
        c = self.config
        mbs = c.slots // c.decode_microbatches
        W = c.gamma + 1
        H = self._model.cfg.hidden_size
        dk = [l.k for l in self._draft_kv]
        dv = [l.v for l in self._draft_kv]
        dpos = jnp.asarray(self._draft_pos)
        out["draft_decode"] = self._draft_decode.warm(
            self._draft_decode_params, dk, dv, self._draft_feed(dpos),
            self._draft_feed(jnp.zeros((c.slots,), jnp.int32)))
        for b in c.prefill_buckets:
            if b not in self._draft_prefill:
                self._draft_prefill[b] = self._make_draft_prefill(b)
            out[f"draft_prefill[{b}]"] = self._draft_prefill[b].warm(
                self._draft_params, dk, dv, dpos,
                jnp.asarray(0, jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.asarray(1, jnp.int32))
        with blocks.attention_impl(self.attention_impl):
            for s, st in enumerate(self._stages):
                mb_tables = jnp.asarray(self._tables[:mbs])
                mb_pos = jnp.asarray(self._pos[:mbs])
                win = jax.device_put(jnp.zeros((mbs, W), jnp.int32),
                                     st.replicated)
                if st.module.is_first:
                    x = win
                else:
                    x = jax.device_put(jnp.zeros((mbs, W, H), jnp.float32),
                                       st.replicated)
                adp = self._stage_adapter_args(s, 0, mbs)
                if st.module.is_last:
                    out[f"verify_stage[{s}]"] = self._stage_verify[s].warm(
                        st.decode_params, st.pool, mb_tables, mb_pos, x,
                        win, *adp)
                else:
                    out[f"verify_stage[{s}]"] = self._stage_verify[s].warm(
                        st.decode_params, st.pool, mb_tables, mb_pos, x,
                        *adp)
        return out


def free_eager_device_copies(model):
    """Host-side model materialization (ROADMAP item 4d): re-point every
    eager parameter/buffer of `model` at a HOST numpy copy, freeing the
    default-device arrays the Layer build materialized. The pp engines
    keep their master weight copy host-resident and place per-stage
    shards themselves, so after engine construction the eager device
    copies are pure waste — and on a genuinely bigger-than-one-host
    deployment, waste that does not FIT next to a stage shard.
    `worker_main --engine pp|spec_pp` calls this right after engine
    construction; the eager Layer stays fully usable (state_dict for
    hot-swap sources, even eager forwards — jnp re-uploads on demand).
    A spec_pp engine's truncated DRAFT Layer aliases the same device
    arrays through its own Tensors — call this on `engine.draft_model`
    too (worker_main does), or the aliased arrays stay alive and the
    bytes figure returned for the target alone overstates what was
    actually released. Returns (arrays_moved, bytes_freed)."""
    moved, freed = 0, 0
    for t in model.state_dict().values():
        data = t._data
        if isinstance(data, np.ndarray):
            continue
        t._data = np.asarray(jax.device_get(data))
        moved += 1
        freed += int(data.nbytes)
    return moved, freed
