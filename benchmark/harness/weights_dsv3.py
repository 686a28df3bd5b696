"""Weights from the seed for DeepSeek-V3's main model (MLA behind a query
bottleneck in every layer, dense SwiGLU or expert feed-forwards): made on the
device, one jitted call a layer, in the type they are served in.

The scheme is weights_hybrid.py's: every (leaf, layer) pair has its own key,
`fold_in(fold_in(root, leaf), layer)`, and every routed expert its own key
under that, `fold_in(key, global expert id)`: an expert's values do not
depend on which share of the experts a chip holds, which is what lets a test
add the 16 shares up to the uncut layer. Matmul weights are stored [in, out].

Leaves of a layer (`H` hidden, `n` heads, `E` experts held here
(`num_experts`), `R` experts the router scores (`router_width`), `f` the
expert width):

    norm1 [H]  norm2 [H]                                  float32
  mla:  wq_a [H, q_rank]  qnorm [q_rank] f32  wq_b [q_rank, n*(nope+rope)]
        wa [H, rank+rope]  cnorm [rank] f32  wkvb [rank, n*(nope+v)]
        wo [n*v, H]
  swiglu: w_gate w_up [H, F]  w_down [F, H]
  moe:  router [H, R] f32  router_bias [R] f32
        we_gate we_up [E, H, f]  we_down [E, f, H]
        ws_gate ws_up [H, fs]  ws_down [fs, H]      fs = n_shared_experts * f
Global: embed [V, H]  norm_f [H] f32  head [H, V].

Values: matmul and embedding weights N(0, std); the projections that write
into the residual (wo, w_down, we_down, ws_down) N(0, std/sqrt(2L)); norm
scales 1 + N(0, bias_std) so a dropped scale shows; the router's bias
N(0, bias_std).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from .weights_hybrid import root_key

NORM_LEAVES = ("norm1", "norm2", "norm_f", "qnorm", "cnorm")
F32_LEAVES = NORM_LEAVES + ("router", "router_bias")
RESIDUAL_LEAVES = ("wo", "w_down", "we_down", "ws_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
# the order is the key: append, never insert
LEAVES = ("embed", "norm_f", "head", "norm1", "norm2",
          "wq_a", "qnorm", "wq_b", "wa", "cnorm", "wkvb", "wo",
          "w_gate", "w_up", "w_down",
          "router", "router_bias", "we_gate", "we_up", "we_down",
          "ws_gate", "ws_up", "ws_down")


def layer_kinds(config):
    """[(mixer, feed-forward)] of each layer the configuration keeps: every
    mixer is MLA; the first `first_k_dense_replace` layers have the dense
    SwiGLU, the rest the expert layer."""
    return [("mla", "swiglu" if i < config["first_k_dense_replace"]
             else "moe") for i in range(config["num_hidden_layers"])]


def global_shapes(config):
    h, v = config["hidden_size"], config["vocab_size"]
    return {"embed": (v, h), "norm_f": (h,), "head": (h, v)}


def layer_shapes(config, kinds):
    """{leaf: shape} of one layer of kinds ("mla", feed-forward)."""
    _, ffn = kinds
    h, n = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    q_rank = config["q_lora_rank"]
    out = {"norm1": (h,), "norm2": (h,),
           "wq_a": (h, q_rank), "qnorm": (q_rank,),
           "wq_b": (q_rank, n * (nope + rope)),
           "wa": (h, rank + rope), "cnorm": (rank,),
           "wkvb": (rank, n * (nope + vd)), "wo": (n * vd, h)}
    if ffn == "swiglu":
        f = config["intermediate_size"]
        out.update({"w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)})
    else:
        e, f = config["num_experts"], config["moe_intermediate_size"]
        fs = config["n_shared_experts"] * f
        r = config["router_width"]
        out.update({"router": (h, r), "router_bias": (r,),
                    "we_gate": (e, h, f), "we_up": (e, h, f),
                    "we_down": (e, f, h),
                    "ws_gate": (h, fs), "ws_up": (h, fs),
                    "ws_down": (fs, h)})
    return out


def _value(key, leaf, shape, config):
    init = config["init"]
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in NORM_LEAVES:
        return 1.0 + init["bias_std"] * noise
    if leaf == "router_bias":
        return init["bias_std"] * noise
    if leaf in RESIDUAL_LEAVES:
        return init["std"] / np.sqrt(2 * config["num_hidden_layers"]) * noise
    return init["std"] * noise


def _leaf(root, leaf, layer, shape, config, dtype):
    key = jax.random.fold_in(jax.random.fold_in(root, LEAVES.index(leaf)),
                             layer)
    dtype = jnp.float32 if leaf in F32_LEAVES else dtype
    if leaf in EXPERT_LEAVES:
        ids = config.get("experts_held_first", 0) \
            + jnp.arange(shape[0], dtype=jnp.uint32)
        return jax.lax.map(
            lambda e: _value(jax.random.fold_in(key, e), leaf, shape[1:],
                             config).astype(dtype), ids)
    return _value(key, leaf, shape, config).astype(dtype)


_CONFIGS = {}


def _key_of(config):
    """The sizes and the init of a configuration, as a hashable key for the
    jitted makers (a configuration file is a dict)."""
    sized = {k: v for k, v in config.items()
             if isinstance(v, (int, float, str, bool)) or k == "init"}
    key = json.dumps(sized, sort_keys=True)
    _CONFIGS.setdefault(key, config)
    return key


@functools.lru_cache(maxsize=None)
def _maker(config_key, layer, dtype):
    """The jitted maker of layer `layer`'s leaves (None: the globals)."""
    config = _CONFIGS[config_key]
    shapes = global_shapes(config) if layer is None \
        else layer_shapes(config, layer_kinds(config)[layer])
    return jax.jit(lambda root: {
        leaf: _leaf(root, leaf, layer or 0, shape, config, dtype)
        for leaf, shape in shapes.items()})


def make_layer(config, seed, layer, dtype):
    """{leaf: array} of one layer, one jitted call."""
    return _maker(_key_of(config), int(layer),
                  jnp.dtype(dtype).name)(root_key(seed))


def make_globals(config, seed, dtype):
    return _maker(_key_of(config), None,
                  jnp.dtype(dtype).name)(root_key(seed))


def named(config, seed, dtype):
    """{parameter name of the served model: array}: `top.embed`,
    `top.norm_f`, `top.head` and `layers.<i>.<leaf>`, the layout of the
    program's `HybridDecoder.named_parameters()`. A layer at a time, so that
    no call holds more than one layer's float32 noise."""
    out = {f"top.{leaf}": value for leaf, value in
           make_globals(config, seed, dtype).items()}
    for i in range(config["num_hidden_layers"]):
        for leaf, value in make_layer(config, seed, i, dtype).items():
            out[f"layers.{i}.{leaf}"] = value
    return out
