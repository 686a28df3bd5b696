"""Weights from the seed for the hybrid decoder (KDA / MLA mixers, dense
SwiGLU / expert feed-forwards): made on the device, one jitted call a layer,
in the type they are served in.

Every (leaf, layer) pair has its own key, `fold_in(fold_in(root, leaf),
layer)`, and every routed expert its own key under that,
`fold_in(key, global expert id)`: an expert's values do not depend on which
share of the experts a chip holds, which is what lets a test add the shares
up to the uncut layer. Matmul weights are stored [in, out].

Leaves of a layer (`H` hidden, `n` heads, `dk`/`dv` the KDA head sizes,
`E` experts held here, `R` experts routed over, `f` the expert width):

    norm1 [H]  norm2 [H]                              float32
  kda:  wq wk [H, n*dk]  wv [H, n*dv]  conv_q conv_k [K, n*dk]  conv_v [K, n*dv]
        a_log [n] f32  wf [H, n*dk]  bf [n*dk] f32  wb [H, n]
        wg [H, n*dv]  onorm [dv] f32  wo [n*dv, H]
  mla:  wq [H, n*(nope+rope)]  wa [H, rank+rope]  cnorm [rank] f32
        wkvb [rank, n*(nope+v)]  wgate [H, n]  wo [n*v, H]
  swiglu: w_gate w_up [H, F]  w_down [F, H]
  moe:  router [H, R] f32  router_bias [R] f32
        we_gate we_up [E, H, f]  we_down [E, f, H]
        ws_gate ws_up [H, fs]  ws_down [fs, H]
Global: embed [V, H]  norm_f [H] f32  head [H, V].

Values: matmul and embedding weights N(0, std); the projections that write
into the residual (wo, w_down, we_down, ws_down) N(0, std/sqrt(2L)); norm
scales 1 + N(0, bias_std) so a dropped scale shows; convolution taps
N(0, conv_std); the decay's bias N(decay_bias_mean, bias_std) so the
recurrent state remembers tens of tokens and not one; the router's bias
N(0, bias_std).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32_LEAVES = ("norm1", "norm2", "a_log", "bf", "onorm", "cnorm", "router",
              "router_bias", "norm_f")
RESIDUAL_LEAVES = ("wo", "w_down", "we_down", "ws_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
# the order is the key: append, never insert
LEAVES = ("embed", "norm_f", "head", "norm1", "norm2",
          "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "a_log", "wf", "bf",
          "wb", "wg", "onorm", "wo",
          "wa", "cnorm", "wkvb", "wgate",
          "w_gate", "w_up", "w_down",
          "router", "router_bias", "we_gate", "we_up", "we_down",
          "ws_gate", "ws_up", "ws_down")


def layer_kinds(config):
    """[(mixer, feed-forward)] of each layer the configuration keeps: layer
    `i` is MLA where `(i + 1) % layer_group_size == 0`, else KDA; the first
    `first_k_dense_replace` layers have the dense SwiGLU, the rest experts."""
    return [("mla" if (i + 1) % config["layer_group_size"] == 0 else "kda",
             "swiglu" if i < config["first_k_dense_replace"] else "moe")
            for i in range(config["num_hidden_layers"])]


def global_shapes(config):
    h, v = config["hidden_size"], config["vocab_size"]
    return {"embed": (v, h), "norm_f": (h,), "head": (h, v)}


def layer_shapes(config, kinds):
    """{leaf: shape} of one layer of kinds (mixer, feed-forward)."""
    mixer, ffn = kinds
    h, n = config["hidden_size"], config["num_attention_heads"]
    out = {"norm1": (h,), "norm2": (h,)}
    if mixer == "kda":
        dk = dv = config["head_dim"]
        k = config["short_conv_kernel_size"]
        out.update({"wq": (h, n * dk), "wk": (h, n * dk), "wv": (h, n * dv),
                    "conv_q": (k, n * dk), "conv_k": (k, n * dk),
                    "conv_v": (k, n * dv), "a_log": (n,),
                    "wf": (h, n * dk), "bf": (n * dk,), "wb": (h, n),
                    "wg": (h, n * dv), "onorm": (dv,), "wo": (n * dv, h)})
    else:
        nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
        vd, rank = config["v_head_dim"], config["kv_lora_rank"]
        out.update({"wq": (h, n * (nope + rope)), "wa": (h, rank + rope),
                    "cnorm": (rank,), "wkvb": (rank, n * (nope + vd)),
                    "wgate": (h, n), "wo": (n * vd, h)})
    if ffn == "swiglu":
        f = config["intermediate_size"]
        out.update({"w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)})
    else:
        e, f = config["num_experts"], config["moe_intermediate_size"]
        fs = config["moe_shared_expert_intermediate_size"]
        r = config["n_routed_experts"]
        out.update({"router": (h, r), "router_bias": (r,),
                    "we_gate": (e, h, f), "we_up": (e, h, f),
                    "we_down": (e, f, h),
                    "ws_gate": (h, fs), "ws_up": (h, fs),
                    "ws_down": (fs, h)})
    return out


def root_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _value(key, leaf, shape, config):
    init = config["init"]
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("norm1", "norm2", "norm_f", "onorm", "cnorm"):
        return 1.0 + init["bias_std"] * noise
    if leaf == "bf":
        return init["decay_bias_mean"] + init["bias_std"] * noise
    if leaf in ("a_log", "router_bias"):
        return init["bias_std"] * noise
    if leaf.startswith("conv_"):
        return init["conv_std"] * noise
    if leaf in RESIDUAL_LEAVES:
        return init["std"] / np.sqrt(2 * config["num_hidden_layers"]) * noise
    return init["std"] * noise


def _leaf(root, leaf, layer, shape, config, dtype):
    key = jax.random.fold_in(jax.random.fold_in(root, LEAVES.index(leaf)),
                             layer)
    dtype = jnp.float32 if leaf in F32_LEAVES else dtype
    if leaf in EXPERT_LEAVES:
        first = config.get("experts_held_first", 0)
        ids = first + jnp.arange(shape[0], dtype=jnp.uint32)
        one = lambda e: _value(jax.random.fold_in(key, e), leaf, shape[1:],
                               config).astype(dtype)
        return jax.lax.map(one, ids)
    return _value(key, leaf, shape, config).astype(dtype)


@functools.lru_cache(maxsize=None)
def _layer_maker(config_key, layer, dtype):
    config = _CONFIGS[config_key]
    shapes = layer_shapes(config, layer_kinds(config)[layer])
    return jax.jit(lambda root: {
        leaf: _leaf(root, leaf, layer, shape, config, dtype)
        for leaf, shape in shapes.items()})


@functools.lru_cache(maxsize=None)
def _global_maker(config_key, dtype):
    config = _CONFIGS[config_key]
    return jax.jit(lambda root: {
        leaf: _leaf(root, leaf, 0, shape, config, dtype)
        for leaf, shape in global_shapes(config).items()})


_CONFIGS = {}


def _key_of(config):
    """The sizes and the init of a configuration, as a hashable key for the
    jitted makers (a configuration file is a dict)."""
    import json
    sized = {k: v for k, v in config.items()
             if isinstance(v, (int, float, str, bool)) or k == "init"}
    key = json.dumps(sized, sort_keys=True)
    _CONFIGS.setdefault(key, config)
    return key


def make_layer(config, seed, layer, dtype):
    """{leaf: array} of one layer, one jitted call."""
    return _layer_maker(_key_of(config), int(layer),
                        jnp.dtype(dtype).name)(root_key(seed))


def make_globals(config, seed, dtype):
    return _global_maker(_key_of(config), jnp.dtype(dtype).name)(
        root_key(seed))


def named(config, seed, dtype):
    """{parameter name of the served model: array}: `top.embed`,
    `top.norm_f`, `top.head` and `layers.<i>.<leaf>`. The one place that knows the layout of
    the program's `HybridDecoder.named_parameters()`. A layer at a time, so
    that no call holds more than one layer's float32 noise."""
    out = {f"top.{leaf}": value for leaf, value in
           make_globals(config, seed, dtype).items()}
    for i in range(config["num_hidden_layers"]):
        for leaf, value in make_layer(config, seed, i, dtype).items():
            out[f"layers.{i}.{leaf}"] = value
    return out
