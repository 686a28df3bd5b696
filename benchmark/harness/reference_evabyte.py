"""The plain reference of EvaByte's language model: every layer an EVA
attention mixer and a SwiGLU feed-forward, in straightforward jax.numpy.

Imports nothing of the program and takes nothing the program has made; the
precision helpers (`_dot`, `_act`, `rms_norm`, `rotary`) and the frame's
`_sizes_of` are reference_hybrid.py's, one copy of what the configurations
share. Float32 under `jax.default_matmul_precision("highest")`. No cache, no
ring, no batching: one sequence [T] at a time, and for every position the two
sets it attends to by their definition. Attention runs in blocks of one
window of queries, so 12 800 positions fit; that is the only concession to
size.

Per head (d = hidden / heads, s = d^-1/2, W = `window_size`, C =
`chunk_size`), `x` the layer's normed input:

  q_t, k_t, v_t = W_q x_t, W_k x_t, W_v x_t split in heads; rotary (theta
      `rope_theta`, the whole head, half-split pairs, absolute position t)
      on q_t and k_t.
  chunk c = tokens 16c .. 16c + 15, with the layer's per-head `phi`, `mu`
      [heads, d] float32: a_j = softmax_j(s <k_j, phi>) over the chunk,
      kbar_c = sum_j a_j k_j + mu,  vbar_c = sum_j a_j v_j   (k rotated).
  position t in window w = t // W:  local set L_t = {j : w W <= j <= t},
      scores s <q_t, k_j>;  remote set G_t = {c : c < w W / C} (every chunk
      of every CLOSED window, none of the open one), scores s <q_t, kbar_c>;
      ONE softmax over L_t U G_t;
      o_t = sum_L p_j v_j + sum_G p_c vbar_c;  out W_o o_t.

Layer: h += W_o eva(norm1(h));  h += W_down(silu(W_gate x) * W_up x) of
norm2(h). RMSNorm scales are `1 + w` (`norm_add_unit_offset`), eps
`rms_norm_eps`; residual stream float32. After the last layer the final norm
and the head [hidden, P x V]: P = `num_pred_heads` heads of V side by side,
head p the columns [p V, (p + 1) V). No bias, untied embedding.

`mode`: "float32" (the reference itself), "bfloat16" and "fp8_e4m3" (the
control: both operands of every projection and attention matmul rounded to
float8_e4m3fn, per-tensor abs-max scale), as in reference_hybrid.py. The
norms, `phi`, `mu`, the pooling weights, the softmax and the residual stream
stay float32 in every mode, as the configuration states.
"""
import functools

import jax
import jax.numpy as jnp

from .reference_hybrid import _act, _dot, _sizes_of, rms_norm, rotary

MODES = ("float32", "bfloat16", "fp8_e4m3")


def chunk_summaries(k, v, phi, mu, chunk):
    """k, v [T, n, d] (k rotated) -> kbar, vbar [ceil(T / C), n, d]. A last
    chunk that the sequence does not fill pools the tokens it has."""
    t, n, d = k.shape
    m = -(-t // chunk)
    pad = ((0, m * chunk - t), (0, 0), (0, 0))
    kc = jnp.pad(k, pad).reshape(m, chunk, n, d)
    vc = jnp.pad(v, pad).reshape(m, chunk, n, d)
    there = (jnp.arange(m * chunk) < t).reshape(m, chunk, 1)
    score = jnp.einsum("mcnd,nd->mcn", kc, phi) / jnp.sqrt(jnp.float32(d))
    a = jax.nn.softmax(jnp.where(there, score, -jnp.inf), axis=1)
    return jnp.einsum("mcn,mcnd->mnd", a, kc) + mu, \
        jnp.einsum("mcn,mcnd->mnd", a, vc)


def eva_mixer(x, w, cfg, mode):
    t = x.shape[0]
    n = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // n
    win, chunk = cfg["window_size"], cfg["chunk_size"]
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    proj = lambda name: _act(
        _dot("th,hc->tc", x, w[name], mode), mode).reshape(t, n, d)
    pos = jnp.arange(t)
    q = rotary(proj("wq"), pos, cfg["rope_theta"])
    k = rotary(proj("wk"), pos, cfg["rope_theta"])
    v = proj("wv")
    kbar, vbar = chunk_summaries(k, v, w["phi"], w["mu"], chunk)
    out = []
    for lo in range(0, t, win):              # one window of queries
        hi, closed = min(lo + win, t), lo // chunk
        local = _dot("qnd,knd->nqk", q[lo:hi], k[lo:hi], mode) * scale
        local = jnp.where(jnp.tril(jnp.ones((hi - lo, hi - lo), bool)),
                          local, -jnp.inf)
        if not closed:                       # the first window: no G_t
            probs = _act(jax.nn.softmax(local, axis=-1), mode)
            out.append(_dot("nqk,knd->qnd", probs, v[lo:hi], mode))
            continue
        remote = _dot("qnd,cnd->nqc", q[lo:hi], kbar[:closed], mode) * scale
        probs = _act(jax.nn.softmax(
            jnp.concatenate([remote, local], -1), axis=-1), mode)
        out.append(
            _dot("nqc,cnd->qnd", probs[..., :closed], vbar[:closed], mode)
            + _dot("nqk,knd->qnd", probs[..., closed:], v[lo:hi], mode))
    o = jnp.concatenate(out).reshape(t, n * d)
    return _dot("tc,ch->th", _act(o, mode), w["wo"], mode)


def swiglu(x, w, mode):
    u = _act(jax.nn.silu(_dot("th,hf->tf", x, w["w_gate"], mode))
             * _dot("th,hf->tf", x, w["w_up"], mode), mode)
    return _dot("tf,fh->th", u, w["w_down"], mode)


def layer(h, w, cfg, mode="float32"):
    """One layer on one sequence h [T, H] (float32 residual)."""
    eps = cfg["rms_norm_eps"]
    h = h + eva_mixer(_act(rms_norm(h, 1.0 + w["norm1"], eps), mode), w,
                      cfg, mode)
    return h + swiglu(_act(rms_norm(h, 1.0 + w["norm2"], eps), mode), w,
                      mode)


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes, mode):
    cfg = dict(sizes)
    return jax.jit(lambda h, w: layer(h, w, cfg, mode))


@functools.lru_cache(maxsize=None)
def _head_fn(sizes, mode):
    """[T, H] -> every prediction head's logits [T, P, V]."""
    cfg = dict(sizes)
    return jax.jit(lambda h, g: _dot(
        "th,hv->tv", _act(rms_norm(h, 1.0 + g["norm_f"],
                                   cfg["rms_norm_eps"]), mode),
        g["head"], mode).reshape(h.shape[0], cfg["num_pred_heads"], -1))


def _hidden(cfg, embed, make_layer, ids_list, modes):
    """{mode: [final residual [T, H] of each id sequence]}: layer after
    layer, `make_layer(i)` called once when its turn comes, used for every
    sequence and mode, and dropped."""
    sizes = _sizes_of(cfg)
    hidden = {m: [embed[ids].astype(jnp.float32) for ids in ids_list]
              for m in modes}
    for i in range(cfg["num_hidden_layers"]):
        w = make_layer(i)
        for m in modes:
            fn = _layer_fn(sizes, m)
            hidden[m] = [fn(h, w) for h in hidden[m]]
        del w
    return hidden


def logits(cfg, make_globals, make_layer, ids, mode="float32"):
    """[T] token ids -> [T, P, V] float32 logits of all P prediction heads.
    `make_globals()` gives `embed`, `norm_f`, `head`; `make_layer(i)` the
    leaves of layer `i` (weights_evabyte.py has the names)."""
    with jax.default_matmul_precision("highest"):
        g = make_globals()
        h = _hidden(cfg, g["embed"], make_layer, [ids], (mode,))
        return _head_fn(_sizes_of(cfg), mode)(h[mode][0], g) \
            .astype(jnp.float32)


def served_rows(config, weights, seed, samples, modes=("float32",),
                pad_to=None):
    """{mode: [the FIRST head's logits at the positions that produced
    `tokens`, one [len(tokens), V] array per (prompt, tokens) of
    `samples`]}: one forward over prompt + served tokens each, right-padded
    to `pad_to` (the positions served, unless given): ONE shape then serves
    every request of every seed, so the layer program compiles once
    (causal throughout, and a chunk is seen only from a later window, so
    padding cannot reach the rows read). Each layer's leaves are regenerated
    from the seed once, in the type they are served in, and widened to
    float32."""
    import numpy as np
    pad_to = pad_to or config["max_position_embeddings"]
    dtype = config["dtype"]["param"]
    widen = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)
    padded = []
    for prompt, tokens in samples:
        ids = list(prompt) + list(tokens[:-1])
        row = np.zeros((-(-len(ids) // pad_to) * pad_to,), np.int32)
        row[:len(ids)] = ids
        padded.append(jnp.asarray(row))
    with jax.default_matmul_precision("highest"):
        g = widen(weights.make_globals(config, seed, dtype))
        hidden = _hidden(
            config, g["embed"],
            lambda i: widen(weights.make_layer(config, seed, i, dtype)),
            padded, modes)
        out = {}
        for m in modes:
            head = _head_fn(_sizes_of(config), m)
            out[m] = [np.asarray(
                head(h, g)[len(prompt) - 1:len(prompt) - 1 + len(tokens), 0],
                np.float32)
                for h, (prompt, tokens) in zip(hidden[m], samples)]
    return out
