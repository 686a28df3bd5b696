"""The plain reference of Keye-VL-2.0's language model: every layer
grouped-query attention behind DeepSeek Sparse Attention's learned selection
of positions, and an expert layer of softmax-routed experts, in
straightforward jax.numpy.

Imports nothing of the program and takes nothing the program has made; the
precision helpers (`_dot`, `_act`, `rms_norm`, `rotary`, `swiglu`) and the
frame's `_sizes_of` and `_head_fn` are reference_hybrid.py's. Float32 under
`jax.default_matmul_precision("highest")`. No cache, no batching: one
sequence [T] at a time, and for every position the set it attends to by its
definition: the full row of index scores, `lax.top_k`, a masked softmax over
every key. Queries go a block of BLOCK at a time, so that 33 280 positions
fit; that is the only concession to size.

Per layer (`x` the normed input, n query heads over kv key/value heads of d,
m indexer heads of e, k = `topk`, theta = `rope_theta`):

  q_t = rotary(RMSNorm_head(W_q x_t)), k_t = rotary(RMSNorm_head(W_k x_t)),
      v_t = W_v x_t; rotary over the whole head, half-split pairs, at t.
  q_I[t, j] = W_qI x_t (head j),  k_I[s] = LayerNorm(W_kI x_s), both with
      rotary on their first e/2 dims;  w[t, j] = (W_w x_t)_j (m e)^-1/2.
  I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])   for s <= t.
  S_t = the min(k, t + 1) positions s <= t of largest I[t, s], ties to the
      lower s (`lax.top_k`'s order); one set for all n heads.
  o_t = sum_{s in S_t} softmax_s(q_t . k_s / sqrt(d)) v_s, query head h
      reading key/value head h // (n / kv);  out W_o o_t.

Experts: p = softmax(W_r x) over all R routed experts (float32); the top
`num_experts_per_tok`, their p renormalised to sum 1; the layer computes only
the experts held (`experts_held_first`, `num_experts` of them), each
`W_down(silu(W_gate x) * W_up x)`; no shared expert, no bias, no groups.

Block: `h += Mixer(RMSNorm(h)); h += Experts(RMSNorm(h))`, final RMSNorm,
untied head over the vocabulary slice.

`mode`: "float32" (the reference itself), "bfloat16" and "fp8_e4m3" (the
control: both operands of every projection, index and attention matmul
rounded to float8_e4m3fn, per-tensor abs-max scale), as in
reference_hybrid.py. The norms, the router, the softmaxes and the residual
stream stay float32 in every mode, as the configuration states.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .reference_hybrid import (_act, _dot, _head_fn, _sizes_of, rms_norm,
                               rotary, swiglu)

MODES = ("float32", "bfloat16", "fp8_e4m3")
BLOCK = 256                  # queries a block of the index and attention


def flat(config):
    """The configuration with the indexer's numbers at the top level, where
    `_sizes_of` reads numbers."""
    return dict(config, **{"sa_" + k: v
                           for k, v in config["sa_config"].items()})


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def dsa_mixer(x, w, cfg, mode):
    t = x.shape[0]
    n, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    m, e, k = cfg["sa_indexer_num_heads"], cfg["sa_indexer_head_dim"], \
        cfg["sa_topk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    proj = lambda name: _act(_dot("th,hc->tc", x, w[name], mode), mode)
    q = rotary(rms_norm(proj("wq").reshape(t, n, d), w["q_norm"], eps), pos,
               theta)
    kk = rotary(rms_norm(proj("wk").reshape(t, kv, d), w["k_norm"], eps),
                pos, theta)
    v = proj("wv").reshape(t, kv, d)

    def half_rope(a):
        return jnp.concatenate([rotary(a[..., :e // 2], pos, theta),
                                a[..., e // 2:]], -1)

    qi = half_rope(proj("wq_idx").reshape(t, m, e))
    ki = half_rope(layer_norm(proj("wk_idx"), w["k_idx_norm"],
                              w["k_idx_bias"], eps))
    wts = proj("w_idx") / jnp.sqrt(jnp.float32(m * e))
    q = q.reshape(t, kv, n // kv, d)
    size = math.gcd(t, BLOCK)

    def block(lo):
        rows = lo + jnp.arange(size)
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        qi_b = jax.lax.dynamic_slice_in_dim(qi, lo, size)
        w_b = jax.lax.dynamic_slice_in_dim(wts, lo, size)
        index = jnp.sum(w_b[:, :, None] * jax.nn.relu(
            _dot("qje,se->qjs", qi_b, ki, mode)), 1)
        index = jnp.where(causal, index, -jnp.inf)
        picked = jax.lax.top_k(index, min(k, t))[1]
        chosen = jnp.zeros((size, t), bool).at[
            jnp.arange(size)[:, None], picked].set(True) & causal
        q_b = jax.lax.dynamic_slice_in_dim(q, lo, size)
        scores = _dot("qkgd,skd->kgqs", q_b, kk, mode) / jnp.sqrt(
            jnp.float32(d))
        probs = _act(jax.nn.softmax(
            jnp.where(chosen, scores, -jnp.inf), -1), mode)
        return _dot("kgqs,skd->qkgd", probs, v, mode).reshape(size, n * d)

    o = jax.lax.map(block, jnp.arange(0, t, size)).reshape(t, n * d)
    return _dot("tc,ch->th", _act(o, mode), w["wo"], mode)


def route(x, w, cfg):
    """[T, R] float32: the weight of each routed expert for each token, zero
    where it was not chosen. Float32 whatever the mode."""
    p = jax.nn.softmax(jnp.einsum("th,hr->tr", x.astype(jnp.float32),
                                  w["router"]), -1)
    top = jax.lax.top_k(p, cfg["num_experts_per_tok"])[1]
    chosen = jnp.zeros_like(p, bool).at[
        jnp.arange(x.shape[0])[:, None], top].set(True)
    weights = jnp.where(chosen, p, 0.0)
    return weights / jnp.sum(weights, -1, keepdims=True)


def moe_ffn(x, w, cfg, mode):
    """The routed part the experts held here give; no shared expert."""
    first = cfg.get("experts_held_first", 0)
    weights = route(x, w, cfg)[:, first:first + cfg["num_experts"]]

    def one(acc, xs):
        gate, up, down, w_e = xs
        return acc + w_e[:, None] * swiglu(x, gate, up, down, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (w["we_gate"], w["we_up"], w["we_down"], weights.T))
    return y


def layer(h, w, cfg, mode="float32"):
    """One layer on one sequence h [T, H] (float32 residual); queries go
    the largest block that divides T and BLOCK at a time."""
    eps = cfg["rms_norm_eps"]
    h = h + dsa_mixer(_act(rms_norm(h, w["norm1"], eps), mode), w, cfg,
                      mode)
    return h + moe_ffn(_act(rms_norm(h, w["norm2"], eps), mode), w, cfg,
                       mode)


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes, mode):
    cfg = dict(sizes)
    return jax.jit(lambda h, w: layer(h, w, cfg, mode))


def _hidden(cfg, embed, make_layer, ids_list, modes):
    """{mode: [final residual [T, H] of each id sequence]}: layer after
    layer, `make_layer(i)` called once when its turn comes, used for every
    sequence and mode, and dropped."""
    sizes = _sizes_of(flat(cfg))
    hidden = {md: [embed[ids].astype(jnp.float32) for ids in ids_list]
              for md in modes}
    for i in range(cfg["num_hidden_layers"]):
        w = make_layer(i)
        for md in modes:
            hidden[md] = [_layer_fn(sizes, md)(h, w) for h in hidden[md]]
        del w
    return hidden


def _padded(ids, pad_to):
    n = -(-len(ids) // pad_to) * pad_to
    return jnp.asarray(list(ids) + [0] * (n - len(ids)), jnp.int32)


def logits(cfg, make_globals, make_layer, ids, mode="float32"):
    """[T] token ids -> [T, V] float32 logits. `make_globals()` gives
    `embed`, `norm_f`, `head`; `make_layer(i)` the leaves of layer `i`
    (weights_keye.py has the names)."""
    with jax.default_matmul_precision("highest"):
        g = make_globals()
        h = _hidden(cfg, g["embed"], make_layer, [_padded(ids, BLOCK)],
                    (mode,))
        return _head_fn(_sizes_of(cfg), mode)(h[mode][0], g)[:len(ids)] \
            .astype(jnp.float32)


def served_rows(config, weights, seed, samples, modes=("float32",),
                pad_to=None):
    """{mode: [the logits at the positions that produced `tokens`, one
    [len(tokens), V] array per (prompt, tokens) of `samples`]}: one forward
    over prompt + served tokens each, right-padded to `pad_to` (the positions
    served, unless given): ONE shape then serves every request of every
    seed, so the layer program compiles once (causal throughout, so padding
    cannot reach the rows read). Each layer's leaves are regenerated from
    the seed once, in the type they are served in, and widened to
    float32."""
    import numpy as np
    pad_to = pad_to or config["max_position_embeddings"]
    dtype = config["dtype"]["param"]
    widen = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)
    padded = [_padded(list(p) + list(t[:-1]), pad_to) for p, t in samples]
    with jax.default_matmul_precision("highest"):
        g = widen(weights.make_globals(config, seed, dtype))
        hidden = _hidden(
            config, g["embed"],
            lambda i: widen(weights.make_layer(config, seed, i, dtype)),
            padded, modes)
        out = {}
        for md in modes:
            head = _head_fn(_sizes_of(config), md)
            out[md] = [np.asarray(
                head(h, g)[len(prompt) - 1:len(prompt) - 1 + len(tokens)],
                np.float32)
                for h, (prompt, tokens) in zip(hidden[md], samples)]
    return out
