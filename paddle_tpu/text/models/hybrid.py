"""A decoder built from a per-layer list of (mixer, feed-forward) kinds, of
which a layer has both (each behind its own norm) or one alone.

Mixers: `kda` (linear attention: the gated delta rule with a per-channel
decay, a short causal convolution and SiLU; its cache is a per-slot state
matrix and convolution tail), `mamba2` (a selective state-space layer: a
scalar decay a head, B and C shared by a group of heads, a convolution with
bias, a gated grouped norm; the same kind of cache), `mla` (softmax
attention over a shared latent: its cache is one paged row a token; queries
straight from the hidden state or through a bottleneck `q_lora_rank` wide, a
head-wise output gate or none, plain or YaRN-scaled rotary: what the
configuration declares), `gqa` (softmax attention of `num_heads` query
heads over `num_kv_heads` key/value heads, no rotary; a token's keys and
values are one paged row) and `eva` (EVA attention, `num_heads` heads with
rotary over the whole head: exact softmax inside the aligned window of
`eva_window` positions a token lies in, and of every window closed before it
one learned summary row per `eva_chunk` positions, under one softmax; a
token's row lives one window, a summary as long as the request:
`blocks.WindowSpec`). Feed-forwards: `swiglu` (dense) and `moe`
(sigmoid-scored, group-limited top-k experts plus a shared expert, SwiGLU or
`relu2` as `moe_act` declares, of which this chip may hold a share:
`num_experts` of `n_routed_experts`, global ids from `experts_first`). The
layers come from the configuration: `blocks` (one part a layer: a mixer or a
feed-forward alone, behind ONE norm), or `mixers` (one mixer a layer, each
followed by a feed-forward) or, without either, the rule "every
`layer_group_size`-th layer is MLA, the others KDA". RMSNorm (scales `w`, or
`1 + w` under `norm_unit_offset`), partial rotary on the MLA layers, untied
head: `num_pred_heads` prediction heads of `vocab_size` side by side, of which
the served step computes the first. `hybrid_ops.py` has the mathematics.

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
                  "cnorm", "qnorm", "router", "router_bias", "dt_bias",
                  "d_skip", "ssm_norm", "phi", "mu")
_ONES_LEAVES = ("norm1", "norm2", "norm_f", "onorm", "cnorm", "qnorm",
                "d_skip", "ssm_norm")
_RESIDUAL_LEAVES = ("wo", "w_down", "we_down", "ws_down", "w_out")
_NORM_LEAVES = ("norm1", "norm2", "norm_f")
MIXERS = ("kda", "mla", "mamba2", "gqa", "eva")
FFNS = ("swiglu", "moe")


@dataclass
class HybridConfig:
    vocab_size: int = 1024
    hidden_size: int = 64
    num_layers: int = 7
    num_heads: int = 2
    head_dim: int = 16                   # KDA, GQA, EVA key and value head
    num_kv_heads: int = None             # GQA's key/value heads
    eva_window: int = 2048               # EVA: positions a window, and
    eva_chunk: int = 16                  # positions a summary row
    num_pred_heads: int = 1              # heads of vocab_size in `head`
    norm_unit_offset: bool = False       # RMSNorm scales are 1 + w
    layer_group_size: int = 6            # every group's last layer is MLA
    mixers: tuple = None                 # one mixer a layer; None: that rule
    blocks: tuple = None                 # one part a layer, nothing paired
    first_k_dense: int = 1               # leading layers with a dense FFN
    intermediate_size: int = 128
    max_position_embeddings: int = 256
    rms_norm_eps: float = 1e-6
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    ssm_heads: int = 4                   # Mamba-2: heads of ssm_head_dim,
    ssm_head_dim: int = 8                # B and C shared by a group,
    ssm_groups: int = 2                  # the state [heads, head_dim, size]
    ssm_state_size: int = 16
    ssm_chunk: int = 128                 # tokens a chunk of its prefill
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
    moe_act: str = "swiglu"              # or "relu2": no gate matrix
    param_dtype: str = "float32"
    initializer_range: float = 0.02
    init_weights: bool = True            # False: shapes only, load later

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if not 0 <= self.experts_first <= \
                self.n_routed_experts - self.num_experts:
            raise ValueError("the held experts lie outside the routed ones")
        for name, parts in (("mixers", MIXERS), ("blocks", MIXERS + FFNS)):
            given = getattr(self, name)
            if given is None:
                continue
            given = tuple(given)
            setattr(self, name, given)
            if len(given) != self.num_layers or set(given) - set(parts):
                raise ValueError(
                    f"{name} must name one of {parts} for each of the "
                    f"{self.num_layers} layers, got {given!r}")
        if self.mixers is not None and self.blocks is not None:
            raise ValueError("a layer list is `mixers` (each followed by a "
                             "feed-forward) or `blocks` (one part a layer),"
                             " not both")
        kinds = set(self.blocks or self.mixers or ())
        if "gqa" in kinds and (not self.num_kv_heads
                               or self.num_heads % self.num_kv_heads):
            raise ValueError("gqa: num_kv_heads must divide num_heads")
        if "eva" in kinds and (self.eva_chunk < 1
                               or self.eva_window % self.eva_chunk
                               or self.head_dim % 2):
            raise ValueError("eva: eva_window must be whole chunks of "
                             "eva_chunk, and head_dim even (rotary)")
        if self.num_pred_heads < 1:
            raise ValueError("num_pred_heads must be at least 1")
        if "mamba2" in kinds and self.ssm_heads % self.ssm_groups:
            raise ValueError("mamba2: ssm_groups must divide ssm_heads")
        if self.moe_act not in ("swiglu", "relu2"):
            raise ValueError(f"moe_act: 'swiglu' or 'relu2', got "
                             f"{self.moe_act!r}")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling: only YaRN is built, got "
                             f"{self.rope_scaling!r}")

    def layer_kinds(self):
        """[(mixer | None, feed-forward | None)] of each layer."""
        if self.blocks is not None:
            return [(b, None) if b in MIXERS else (None, b)
                    for b in self.blocks]
        mixers = self.mixers or [
            "mla" if (i + 1) % self.layer_group_size == 0 else "kda"
            for i in range(self.num_layers)]
        return [(mixer, "swiglu" if i < self.first_k_dense else "moe")
                for i, mixer in enumerate(mixers)]


def leaf_shapes(cfg, kinds):
    """{leaf: shape} of one layer of kinds (mixer, feed-forward); a part
    that is None brings neither its leaves nor its norm."""
    mixer, ffn = kinds
    h, n = cfg.hidden_size, cfg.num_heads
    out = {name: (h,) for name, part in (("norm1", mixer), ("norm2", ffn))
           if part is not None}
    if mixer == "kda":
        c, k = n * cfg.head_dim, cfg.conv_kernel
        out.update({"wq": (h, c), "wk": (h, c), "wv": (h, c),
                    "conv_q": (k, c), "conv_k": (k, c), "conv_v": (k, c),
                    "a_log": (n,), "wf": (h, c), "bf": (c,), "wb": (h, n),
                    "wg": (h, c), "onorm": (cfg.head_dim,), "wo": (c, h)})
    elif mixer == "mamba2":
        heads, inner = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim
        chan = inner + 2 * cfg.ssm_groups * cfg.ssm_state_size
        out.update({"w_in": (h, inner + chan + heads),
                    "conv_w": (cfg.conv_kernel, chan), "conv_b": (chan,),
                    "dt_bias": (heads,), "a_log": (heads,),
                    "d_skip": (heads,), "ssm_norm": (inner,),
                    "w_out": (inner, h)})
    elif mixer == "gqa":
        d = cfg.head_dim
        out.update({"wq": (h, n * d), "wk": (h, cfg.num_kv_heads * d),
                    "wv": (h, cfg.num_kv_heads * d), "wo": (n * d, h)})
    elif mixer == "eva":
        d = cfg.head_dim
        out.update({"wq": (h, n * d), "wk": (h, n * d), "wv": (h, n * d),
                    "phi": (n, d), "mu": (n, d), "wo": (n * d, h)})
    elif mixer == "mla":
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
    elif ffn == "moe":
        e, f = cfg.num_experts, cfg.moe_intermediate_size
        fs = cfg.shared_intermediate_size
        out.update({"router": (h, cfg.n_routed_experts),
                    "router_bias": (cfg.n_routed_experts,),
                    "we_gate": (e, h, f), "we_up": (e, h, f),
                    "we_down": (e, f, h), "ws_gate": (h, fs),
                    "ws_up": (h, fs), "ws_down": (fs, h)})
        if cfg.moe_act == "relu2":
            del out["we_gate"], out["ws_gate"]
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
            elif leaf in _NORM_LEAVES and cfg.norm_unit_offset:
                data = jnp.zeros(shape, dtype)
            elif leaf in _ONES_LEAVES:
                data = jnp.ones(shape, dtype)
            elif leaf in ("a_log", "bf", "router_bias", "dt_bias",
                          "conv_b"):
                data = jnp.zeros(shape, dtype)
            else:
                std = cfg.initializer_range
                if leaf in ("phi", "mu"):    # neither uniform nor one-hot
                    std = cfg.head_dim ** -0.5
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
                                 "head": (h, cfg.num_pred_heads * v)}, key)
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
        n, d, tail = cfg.num_heads, cfg.head_dim, cfg.conv_kernel - 1

        def spec(mixer):
            if mixer == "kda":
                return blocks.StateSpec((n, d, d), (tail, 3 * n * d))
            if mixer == "mamba2":
                inner = cfg.ssm_heads * cfg.ssm_head_dim
                return blocks.StateSpec(
                    (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size),
                    (tail, inner + 2 * cfg.ssm_groups * cfg.ssm_state_size))
            if mixer == "mla":
                return blocks.LatentSpec(cfg.kv_lora_rank
                                         + cfg.qk_rope_head_dim)
            if mixer == "gqa":   # a token's keys and values: one row
                return blocks.LatentSpec(2 * cfg.num_kv_heads * d)
            if mixer == "eva":   # [k, v] of a token, [kbar, vbar] of a chunk
                return blocks.WindowSpec(2 * n * d, cfg.eva_window,
                                         cfg.eva_chunk)
            return blocks.NoCache()      # a feed-forward alone

        return tuple(spec(mixer) for mixer, _ in self.kinds)

    # -- forward ----------------------------------------------------------
    def forward(self, input_ids, cache, all_heads=False):
        """`input_ids` [S, T] against `cache` (serving.blocks
        .PagedDecodeCache over `cache_layout()`'s layers). Decode: S slots,
        T = 1, `cache.slot` None. Prefill: S = 1, the request's T (bucket-
        padded) tokens from position 0, `cache.valid` [1] its real length,
        `cache.slot` the slot whose state rows it fills. Returns (logits
        [S, T, V] float32, the new cache, counters int32 [4]); the logits
        are the first prediction head's, as the served step reads them, or
        with `all_heads` every head's, [S, T, num_pred_heads, V]."""
        from ...serving import blocks
        ids = input_ids._data
        pool = tuple(type(l)(*(x._data for x in l)) for l in cache.layers)
        tables, pos = cache.tables._data, cache.pos._data
        prefill = cache.slot is not None
        run = self._prefill if prefill else self._decode
        logits, new_pool, counters = run(
            {n: p._data for n, p in self.named_parameters()}, pool, tables,
            pos, ids, *((cache.valid._data[0], cache.slot._data)
                        if prefill else ()), all_heads=all_heads)
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

    def _norm(self, h, scale):
        if self.cfg.norm_unit_offset:
            scale = 1.0 + scale
        return ops.rms_norm(h, scale, self.cfg.rms_norm_eps)

    def _ffn(self, h, w, ffn, live, counters):
        if ffn is None:
            return h, counters
        x = self._norm(h, w["norm2"])
        if ffn == "swiglu":
            return h + ops.swiglu(x, w["w_gate"], w["w_up"],
                                  w["w_down"]), counters
        y, new = ops.moe_share(x, w, self.cfg, live)
        return h + y, self._merge(counters, new)

    def _finish(self, params, h, counters, all_heads=False):
        x = self._norm(h, params["top.norm_f"])
        if counters is None:
            counters = jnp.zeros((len(self.serving_counters),), jnp.int32)
        head, heads = params["top.head"], self.cfg.num_pred_heads
        if heads > 1 and not all_heads:
            # the served step reads the first head: its columns alone
            head = head[:, :self.cfg.vocab_size]
        logits = ops.mm("...h,hv->...v", x, head)
        if all_heads:
            logits = logits.reshape(logits.shape[:-1] + (heads, -1))
        return logits, counters

    # One token a slot through a mixer: x [S, H] normed, `cached` the
    # layer's cache -> (what the mixer adds to h, the layer's new cache)
    def _mix_decode(self, mixer, x, w, cached, tables, pos):
        from ...serving import blocks
        cfg = self.cfg
        if mixer == "kda":
            conv_in = ops.kda_conv_in(x, w, cached.tail.dtype)[:, None]
            q, k, v, g, beta, gate = ops.kda_inputs(
                x[:, None], w, cfg, conv_in, cached.tail)
            state, o = ops.kda_recurrent_step(
                cached.state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                beta[:, 0])
            tail = jnp.concatenate([cached.tail[:, 1:], conv_in], 1)
            return ops.kda_output(o, gate[:, 0], w, cfg), \
                blocks.StateLayer(state, tail)
        if mixer == "mamba2":
            z, xbc, dt = ops.mamba2_project(x[:, None], w, cfg,
                                            cached.tail.dtype)
            xs, b, c, dt, a = ops.mamba2_inputs(xbc, dt, w, cfg,
                                                cached.tail)
            state, y = ops.mamba2_recurrent_step(
                cached.state, xs[:, 0], b[:, 0], c[:, 0], dt[:, 0], a)
            tail = jnp.concatenate([cached.tail[:, 1:], xbc], 1)
            return ops.mamba2_output(y, xs[:, 0], z[:, 0], w, cfg), \
                blocks.StateLayer(state, tail)
        if mixer == "gqa":
            q, row = ops.gqa_project(x[:, None], w, cfg)
            rows = blocks.write(cached.rows, row, tables, pos)
            return ops.gqa_decode(q[:, 0], blocks.gather_rows(rows, tables),
                                  pos, w, cfg), blocks.LatentLayer(rows)
        if mixer == "eva":
            bs, win, chunk = cached.rows.shape[1], cfg.eva_window, \
                cfg.eva_chunk
            every = jnp.ones((pos.shape[0], 1), bool)
            q, row = ops.eva_project(x[:, None], w, cfg, pos[:, None],
                                     cached.rows.dtype)
            at = (pos % win)[:, None]                  # the ring row
            rows = blocks.window_write(cached.rows, row, tables, at // bs,
                                       at % bs, every)
            view = blocks.gather_rows(rows, tables)
            y = ops.eva_decode(q[:, 0], view, pos, w, cfg)
            # the open chunk's summary, from its tokens' rows so far; it is
            # final when the chunk's last token lands, seen once the window
            # has closed
            c = (pos // chunk)[:, None]
            of_chunk = c * chunk + jnp.arange(chunk)[None, :]     # [S, C]
            summary = ops.eva_summaries(
                jnp.take_along_axis(view, (of_chunk % win)[..., None],
                                    1)[:, None],
                (of_chunk <= pos[:, None])[:, None], w, cfg)
            rows = blocks.window_write(rows, summary, tables,
                                       win // bs + c // bs, c % bs, every)
            return y, blocks.LatentLayer(rows)
        q_n, q_r, latent, gate = ops.mla_project(
            x[:, None], w, cfg, pos[:, None])
        rows = blocks.write(cached.rows, latent, tables, pos)
        return ops.mla_decode(
            q_n[:, 0], q_r[:, 0], blocks.gather_rows(rows, tables),
            pos, None if gate is None else gate[:, 0], w, cfg), \
            blocks.LatentLayer(rows)

    def _decode(self, params, pool, tables, pos, ids, all_heads=False):
        from ...serving import blocks
        cfg = self.cfg
        tokens = ids[:, 0]
        live = tables[:, 0] != blocks.GARBAGE_BLOCK     # a slot with a row
        h = params["top.embed"][tokens].astype(jnp.float32)       # [S, H]
        new_pool, counters = [], None
        for i, ((mixer, ffn), cached) in enumerate(zip(self.kinds, pool)):
            w = self._layer_params(params, i)
            if mixer is not None:
                x = self._norm(h, w["norm1"])
                y, cached = self._mix_decode(mixer, x, w, cached, tables,
                                             pos)
                h = h + y
            new_pool.append(cached)
            h, counters = self._ffn(h, w, ffn, live, counters)
        logits, counters = self._finish(params, h, counters, all_heads)
        return logits[:, None], tuple(new_pool), counters

    # One request's (bucket-padded) tokens from position 0 through a mixer:
    # x [T, H] normed, `valid` [T] the real ones, `length` their count
    def _mix_prefill(self, mixer, x, w, cached, tables, pos, valid, length,
                     slot):
        from ...serving import blocks
        cfg = self.cfg

        def no_history(conv_in):
            return jnp.zeros((cfg.conv_kernel - 1, conv_in.shape[1]),
                             conv_in.dtype)

        def stored(state, history, conv_in):
            # the tail: the rows before position `length`, padding left out
            tail = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([history, conv_in]), length,
                cfg.conv_kernel - 1)
            return blocks.StateLayer(
                jax.lax.dynamic_update_index_in_dim(
                    cached.state, state, slot, 0),
                jax.lax.dynamic_update_index_in_dim(
                    cached.tail, tail, slot, 0))

        if mixer == "kda":
            conv_in = ops.kda_conv_in(x, w, cached.tail.dtype)    # [T, C]
            history = no_history(conv_in)
            q, k, v, g, beta, gate = ops.kda_inputs(
                x, w, cfg, conv_in, history)
            o, state = ops.kda_chunked(q, k, v, g, beta, valid)
            new = stored(state, history, conv_in)
            return ops.kda_output(o, gate, w, cfg), new
        if mixer == "mamba2":
            z, xbc, dt = ops.mamba2_project(x, w, cfg, cached.tail.dtype)
            history = no_history(xbc)
            xs, b, c, dt, a = ops.mamba2_inputs(xbc, dt, w, cfg, history)
            y, state = ops.mamba2_chunked(xs, b, c, dt, a, valid,
                                          cfg.ssm_chunk)
            new = stored(state, history, xbc)
            return ops.mamba2_output(y, xs, z, w, cfg), new
        # padded rows land beyond `length` in the slot's last block or in
        # the garbage block: masked by position, overwritten
        if mixer == "gqa":
            q, row = ops.gqa_project(x, w, cfg)
            new = blocks.LatentLayer(
                blocks.write(cached.rows, row[None], tables, pos))
            return ops.gqa_prefill(q, row, w, cfg), new
        if mixer == "eva":
            bs, win, chunk = cached.rows.shape[1], cfg.eva_window, \
                cfg.eva_chunk
            t = x.shape[0]
            q, row = ops.eva_project(x, w, cfg, jnp.arange(t),
                                     cached.rows.dtype)
            m = -(-t // chunk)                  # chunks the bucket begins
            summaries = ops.eva_summaries(
                jnp.pad(row, ((0, m * chunk - t), (0, 0)))
                .reshape(m, chunk, -1),
                (jnp.arange(m * chunk) < length).reshape(m, chunk), w, cfg)
            y = ops.eva_prefill(q, row, summaries, w, cfg)
            # kept: the token rows of the window the last real token lies
            # in (a span of one window that holds it: rows of the window
            # before land on ring rows no query sees before they are
            # written again) and the summary of every chunk begun
            span = min(win, t)
            start = jnp.clip((length - 1) // win * win, 0, t - span)
            at = start + jnp.arange(span)
            rows = blocks.window_write(
                cached.rows,
                jax.lax.dynamic_slice_in_dim(row, start, span)[None],
                tables, (at % win // bs)[None], (at % bs)[None],
                (at < length)[None])
            c = jnp.arange(m)
            rows = blocks.window_write(
                rows, summaries[None], tables, (win // bs + c // bs)[None],
                (c % bs)[None], (c * chunk < length)[None])
            return y, blocks.LatentLayer(rows)
        q_n, q_r, latent, gate = ops.mla_project(
            x, w, cfg, jnp.arange(x.shape[0]))
        new = blocks.LatentLayer(
            blocks.write(cached.rows, latent[None], tables, pos))
        return ops.mla_prefill(q_n, q_r, latent, gate, w, cfg), new

    def _prefill(self, params, pool, tables, pos, ids, length, slot,
                 all_heads=False):
        cfg = self.cfg
        valid = jnp.arange(ids.shape[1]) < length
        h = params["top.embed"][ids[0]].astype(jnp.float32)       # [T, H]
        new_pool, counters = [], None
        for i, ((mixer, ffn), cached) in enumerate(zip(self.kinds, pool)):
            w = self._layer_params(params, i)
            if mixer is not None:
                x = self._norm(h, w["norm1"])
                y, cached = self._mix_prefill(mixer, x, w, cached, tables,
                                              pos, valid, length, slot)
                h = h + y
            new_pool.append(cached)
            h, counters = self._ffn(h, w, ffn, valid, counters)
        logits, counters = self._finish(params, h, counters, all_heads)
        return logits[None], tuple(new_pool), counters
