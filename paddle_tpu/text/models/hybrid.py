"""A decoder built from a per-layer list of (mixer, feed-forward) kinds.

Mixers: `kda` (linear attention: the gated delta rule with a per-channel
decay, a short causal convolution and SiLU; its cache is a per-slot state
matrix and convolution tail) and `mla` (softmax attention over a shared
latent: its cache is one paged row a token; queries straight from the
hidden state or through a bottleneck `q_lora_rank` wide, a head-wise output
gate or none, plain or YaRN-scaled rotary: what the configuration
declares). Feed-forwards: `swiglu` (dense) and `moe` (sigmoid-scored,
group-limited top-k experts plus a shared expert, of which this chip may
hold a share: `num_experts` of `n_routed_experts`, global ids from
`experts_first`). The mixers come from the configuration's `mixers` list
(one name a layer) or, without one, from the rule "every
`layer_group_size`-th layer is MLA, the others KDA". RMSNorm, partial rotary
on the MLA layers only, untied head. `hybrid_ops.py` has the mathematics.

The model serves through `serving.PagedGenerationEngine` like GPT does; what
differs is that it tells the engine what each layer caches
(`cache_layout()`), takes and returns that cache in `forward`, and hands
back a few counters of its expert layers with the logits
(`serving_counters`). docs/serving.md has the protocol.
"""
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...core.tensor import Parameter, Tensor
from ...nn import Layer, LayerList
from . import hybrid_ops as ops

# parameters that stay float32 whatever the weights' type
FLOAT32_LEAVES = ("norm1", "norm2", "norm_f", "a_log", "bf", "onorm",
                  "cnorm", "qnorm", "router", "router_bias")
_NORM_LEAVES = ("norm1", "norm2", "norm_f", "onorm", "cnorm", "qnorm")
_RESIDUAL_LEAVES = ("wo", "w_down", "we_down", "ws_down")


@dataclass
class HybridConfig:
    vocab_size: int = 1024
    hidden_size: int = 64
    num_layers: int = 7
    num_heads: int = 2
    head_dim: int = 16                   # KDA key and value size per head
    layer_group_size: int = 6            # every group's last layer is MLA
    mixers: tuple = None                 # one mixer a layer; None: that rule
    first_k_dense: int = 1               # leading layers with a dense FFN
    intermediate_size: int = 128
    max_position_embeddings: int = 256
    rms_norm_eps: float = 1e-6
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    q_lora_rank: int = None              # MLA's query bottleneck, or none
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    mla_gate: bool = True                # MLA's head-wise output gate
    rope_theta: float = 6e6
    rope_scaling: dict = None            # YaRN's numbers, or plain rotary
    n_routed_experts: int = 16           # the router's width
    num_experts: int = 16                # held here
    experts_first: int = 0               # global id of the first held
    experts_per_tok: int = 2
    n_group: int = 4
    topk_group: int = 2
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 32
    shared_intermediate_size: int = 32
    param_dtype: str = "float32"
    initializer_range: float = 0.02
    init_weights: bool = True            # False: shapes only, load later

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if not 0 <= self.experts_first <= \
                self.n_routed_experts - self.num_experts:
            raise ValueError("the held experts lie outside the routed ones")
        if self.mixers is not None:
            self.mixers = tuple(self.mixers)
            if len(self.mixers) != self.num_layers \
                    or set(self.mixers) - {"kda", "mla"}:
                raise ValueError(
                    f"mixers must name 'kda' or 'mla' for each of the "
                    f"{self.num_layers} layers, got {self.mixers!r}")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling: only YaRN is built, got "
                             f"{self.rope_scaling!r}")

    def layer_kinds(self):
        mixers = self.mixers or [
            "mla" if (i + 1) % self.layer_group_size == 0 else "kda"
            for i in range(self.num_layers)]
        return [(mixer, "swiglu" if i < self.first_k_dense else "moe")
                for i, mixer in enumerate(mixers)]


def leaf_shapes(cfg, kinds):
    """{leaf: shape} of one layer of kinds (mixer, feed-forward)."""
    mixer, ffn = kinds
    h, n = cfg.hidden_size, cfg.num_heads
    out = {"norm1": (h,), "norm2": (h,)}
    if mixer == "kda":
        c, k = n * cfg.head_dim, cfg.conv_kernel
        out.update({"wq": (h, c), "wk": (h, c), "wv": (h, c),
                    "conv_q": (k, c), "conv_k": (k, c), "conv_v": (k, c),
                    "a_log": (n,), "wf": (h, c), "bf": (c,), "wb": (h, n),
                    "wg": (h, c), "onorm": (cfg.head_dim,), "wo": (c, h)})
    else:
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            out.update({"wq_a": (h, cfg.q_lora_rank),
                        "qnorm": (cfg.q_lora_rank,),
                        "wq_b": (cfg.q_lora_rank, n * (nope + rope))})
        else:
            out["wq"] = (h, n * (nope + rope))
        out.update({"wa": (h, cfg.kv_lora_rank + rope),
                    "cnorm": (cfg.kv_lora_rank,),
                    "wkvb": (cfg.kv_lora_rank, n * (nope + cfg.v_head_dim))})
        if cfg.mla_gate:
            out["wgate"] = (h, n)
        out["wo"] = (n * cfg.v_head_dim, h)
    if ffn == "swiglu":
        f = cfg.intermediate_size
        out.update({"w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)})
    else:
        e, f = cfg.num_experts, cfg.moe_intermediate_size
        fs = cfg.shared_intermediate_size
        out.update({"router": (h, cfg.n_routed_experts),
                    "router_bias": (cfg.n_routed_experts,),
                    "we_gate": (e, h, f), "we_up": (e, h, f),
                    "we_down": (e, f, h), "ws_gate": (h, fs),
                    "ws_up": (h, fs), "ws_down": (fs, h)})
    return out


class _Leaves(Layer):
    """A bag of parameters made from {leaf: shape}. With
    `cfg.init_weights` False each is a scalar placeholder and `shapes`
    says what `HybridDecoder.load_arrays` must bring."""

    def __init__(self, cfg, shapes, key):
        super().__init__()
        self.shapes = dict(shapes)
        for i, (leaf, shape) in enumerate(shapes.items()):
            dtype = jnp.float32 if leaf in FLOAT32_LEAVES \
                else jnp.dtype(cfg.param_dtype)
            if not cfg.init_weights:
                data = jnp.zeros((), dtype)
            elif leaf in _NORM_LEAVES:
                data = jnp.ones(shape, dtype)
            elif leaf in ("a_log", "bf", "router_bias"):
                data = jnp.zeros(shape, dtype)
            else:
                std = cfg.initializer_range
                if leaf in _RESIDUAL_LEAVES:
                    std /= (2 * cfg.num_layers) ** 0.5
                data = (std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dtype)
            setattr(self, leaf, Parameter(data))


class HybridDecoder(Layer):
    # the counters `forward` returns with the logits, in this order; the
    # first three add up over the expert layers, the last is their maximum
    serving_counters = ops.COUNTERS

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.kinds = cfg.layer_kinds()
        key = jax.random.key(0)
        h, v = cfg.hidden_size, cfg.vocab_size
        self.top = _Leaves(cfg, {"embed": (v, h), "norm_f": (h,),
                                 "head": (h, v)}, key)
        self.layers = LayerList([
            _Leaves(cfg, leaf_shapes(cfg, k), jax.random.fold_in(key, i + 1))
            for i, k in enumerate(self.kinds)])

    # -- weights --------------------------------------------------------
    def parameter_shapes(self):
        """{parameter name: shape}, whether or not it is materialised."""
        out = {f"top.{leaf}": s for leaf, s in self.top.shapes.items()}
        for i, layer in enumerate(self.layers):
            out.update({f"layers.{i}.{leaf}": s
                        for leaf, s in layer.shapes.items()})
        return out

    def load_arrays(self, arrays):
        """Replace every parameter by `arrays[name]` (raw arrays), checked
        against `parameter_shapes()` and each parameter's type."""
        shapes = self.parameter_shapes()
        if set(arrays) != set(shapes):
            raise ValueError(f"load_arrays: names differ: "
                             f"{sorted(set(arrays) ^ set(shapes))[:6]}")
        for name, p in self.named_parameters():
            arr = arrays[name]
            if tuple(arr.shape) != tuple(shapes[name]) \
                    or arr.dtype != p._data.dtype:
                raise ValueError(
                    f"load_arrays: {name} is {arr.dtype}{tuple(arr.shape)},"
                    f" want {p._data.dtype}{tuple(shapes[name])}")
            p._data = arr

    def float32_parameters(self):
        """Names an engine's `weight_dtype` must leave float32."""
        return {n for n in self.parameter_shapes()
                if n.rsplit(".", 1)[-1] in FLOAT32_LEAVES}

    # -- what each layer caches (the engine allocates it) ----------------
    def cache_layout(self):
        from ...serving import blocks
        cfg = self.cfg
        n, d = cfg.num_heads, cfg.head_dim
        return tuple(
            blocks.StateSpec((n, d, d), (cfg.conv_kernel - 1, 3 * n * d))
            if mixer == "kda"
            else blocks.LatentSpec(cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            for mixer, _ in self.kinds)

    # -- forward ----------------------------------------------------------
    def forward(self, input_ids, cache):
        """`input_ids` [S, T] against `cache` (serving.blocks
        .PagedDecodeCache over `cache_layout()`'s layers). Decode: S slots,
        T = 1, `cache.slot` None. Prefill: S = 1, the request's T (bucket-
        padded) tokens from position 0, `cache.valid` [1] its real length,
        `cache.slot` the slot whose state rows it fills. Returns (logits
        [S, T, V] float32, the new cache, counters int32 [4])."""
        from ...serving import blocks
        ids = input_ids._data
        pool = tuple(type(l)(*(x._data for x in l)) for l in cache.layers)
        tables, pos = cache.tables._data, cache.pos._data
        prefill = cache.slot is not None
        run = self._prefill if prefill else self._decode
        logits, new_pool, counters = run(
            {n: p._data for n, p in self.named_parameters()}, pool, tables,
            pos, ids, *((cache.valid._data[0], cache.slot._data)
                        if prefill else ()))
        new_layers = tuple(type(l)(*(Tensor(x) for x in l))
                           for l in new_pool)
        return Tensor(logits), blocks.PagedDecodeCache(
            new_layers, cache.tables, cache.pos, cache.valid,
            cache.slot), Tensor(counters)

    def _layer_params(self, params, i):
        prefix = f"layers.{i}."
        return {n[len(prefix):]: a for n, a in params.items()
                if n.startswith(prefix)}

    @staticmethod
    def _merge(counters, new):
        if counters is None:
            return new
        return jnp.concatenate([counters[:3] + new[:3],
                                jnp.maximum(counters[3:], new[3:])])

    def _ffn(self, h, w, ffn, live, counters):
        x = ops.rms_norm(h, w["norm2"], self.cfg.rms_norm_eps)
        if ffn == "swiglu":
            return h + ops.swiglu(x, w["w_gate"], w["w_up"],
                                  w["w_down"]), counters
        y, new = ops.moe_share(x, w, self.cfg, live)
        return h + y, self._merge(counters, new)

    def _finish(self, params, h, counters):
        x = ops.rms_norm(h, params["top.norm_f"], self.cfg.rms_norm_eps)
        if counters is None:
            counters = jnp.zeros((len(self.serving_counters),), jnp.int32)
        return ops.mm("...h,hv->...v", x, params["top.head"]), counters

    def _decode(self, params, pool, tables, pos, ids):
        from ...serving import blocks
        cfg = self.cfg
        tokens = ids[:, 0]
        live = tables[:, 0] != blocks.GARBAGE_BLOCK     # a slot with a row
        h = params["top.embed"][tokens].astype(jnp.float32)       # [S, H]
        new_pool, counters = [], None
        for i, ((mixer, ffn), cached) in enumerate(zip(self.kinds, pool)):
            w = self._layer_params(params, i)
            x = ops.rms_norm(h, w["norm1"], cfg.rms_norm_eps)
            if mixer == "kda":
                conv_in = ops.kda_conv_in(x, w, cached.tail.dtype)[:, None]
                q, k, v, g, beta, gate = ops.kda_inputs(
                    x[:, None], w, cfg, conv_in, cached.tail)
                state, o = ops.kda_recurrent_step(
                    cached.state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0])
                tail = jnp.concatenate([cached.tail[:, 1:], conv_in], 1)
                new_pool.append(blocks.StateLayer(state, tail))
                h = h + ops.kda_output(o, gate[:, 0], w, cfg)
            else:
                q_n, q_r, latent, gate = ops.mla_project(
                    x[:, None], w, cfg, pos[:, None])
                rows = blocks.write(cached.rows, latent, tables, pos)
                new_pool.append(blocks.LatentLayer(rows))
                h = h + ops.mla_decode(
                    q_n[:, 0], q_r[:, 0], blocks.gather_rows(rows, tables),
                    pos, None if gate is None else gate[:, 0], w, cfg)
            h, counters = self._ffn(h, w, ffn, live, counters)
        logits, counters = self._finish(params, h, counters)
        return logits[:, None], tuple(new_pool), counters

    def _prefill(self, params, pool, tables, pos, ids, length, slot):
        from ...serving import blocks
        cfg = self.cfg
        t = ids.shape[1]
        valid = jnp.arange(t) < length
        h = params["top.embed"][ids[0]].astype(jnp.float32)       # [T, H]
        new_pool, counters = [], None
        for i, ((mixer, ffn), cached) in enumerate(zip(self.kinds, pool)):
            w = self._layer_params(params, i)
            x = ops.rms_norm(h, w["norm1"], cfg.rms_norm_eps)
            if mixer == "kda":
                conv_in = ops.kda_conv_in(x, w, cached.tail.dtype)  # [T, C]
                history = jnp.zeros((cfg.conv_kernel - 1,
                                     conv_in.shape[1]), conv_in.dtype)
                q, k, v, g, beta, gate = ops.kda_inputs(
                    x, w, cfg, conv_in, history)
                o, state = ops.kda_chunked(q, k, v, g, beta, valid)
                # the rows before position `length`: padding stays out
                tail = jax.lax.dynamic_slice_in_dim(
                    jnp.concatenate([history, conv_in]), length,
                    cfg.conv_kernel - 1)
                new_pool.append(blocks.StateLayer(
                    jax.lax.dynamic_update_index_in_dim(
                        cached.state, state, slot, 0),
                    jax.lax.dynamic_update_index_in_dim(
                        cached.tail, tail, slot, 0)))
                h = h + ops.kda_output(o, gate, w, cfg)
            else:
                q_n, q_r, latent, gate = ops.mla_project(
                    x, w, cfg, jnp.arange(t))
                # padded rows land beyond `length` in the slot's last block
                # or in the garbage block: masked by position, overwritten
                new_pool.append(blocks.LatentLayer(blocks.write(
                    cached.rows, latent[None], tables, pos)))
                h = h + ops.mla_prefill(q_n, q_r, latent, gate, w, cfg)
            h, counters = self._ffn(h, w, ffn, valid, counters)
        logits, counters = self._finish(params, h, counters)
        return logits[None], tuple(new_pool), counters
