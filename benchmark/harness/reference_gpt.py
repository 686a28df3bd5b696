"""The plain reference: a GPT-2-family decoder in straightforward jax.numpy.

Imports nothing of the program and takes nothing the program has made. Float32
with every matmul at `Precision.HIGHEST` (on a TPU a default f32 matmul is one
bf16 pass). Pre-LN blocks, learned positions, tied embedding, tanh GELU, causal
softmax attention written out in full: no kernels, no cache, no batching tricks.
Layers run under `lax.scan` over the stacked weights (see weights.py for the
layout) and, for training, rows go through in blocks with the block's
activations recomputed in the backward pass, so that the whole fits beside
nothing else on one chip.

`mode` puts the reference in the program's place at a lower precision, which
is the control of the correctness check:

    "float32"   the reference itself
    "bfloat16"  weights and activations in bfloat16 (statistics of layer norm
                and softmax in float32, as bf16 inference does)
    "fp8_e4m3"  both operands of every matmul rounded to float8_e4m3fn with a
                per-tensor abs-max scale (straight-through in the backward
                pass), accumulation in float32
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("float32", "bfloat16", "fp8_e4m3")


def _fp8_round(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _dot(a, b, spec, mode):
    if mode == "fp8_e4m3":
        a, b = _fp8_round(a), _fp8_round(b)
    if mode == "bfloat16":
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _gelu_tanh(x):
    xf = x.astype(jnp.float32)
    y = 0.5 * xf * (1.0 + jnp.tanh(0.7978845608028654
                                   * (xf + 0.044715 * xf ** 3)))
    return y.astype(x.dtype)


def _split_qkv(qkv, heads, layout):
    b, s, three_h = qkv.shape
    d = three_h // 3 // heads
    if layout == "head_major":          # columns [head][q|k|v][d]
        qkv = qkv.reshape(b, s, heads, 3, d)
        return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    if layout == "qkv_major":           # columns [q|k|v][head][d]
        qkv = qkv.reshape(b, s, 3, heads, d)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    raise ValueError(f"unknown qkv_layout {layout!r}")


def _block(x, blk, heads, layout, eps, mode):
    b, s, h = x.shape
    a = _layer_norm(x, blk["ln1_w"], blk["ln1_b"], eps)
    qkv = _dot(a, blk["w_qkv"], "bsh,hk->bsk", mode) + blk["b_qkv"]
    q, k, v = _split_qkv(qkv, heads, layout)            # [b, s, heads, d]
    d = q.shape[-1]
    scores = _dot(q, k, "bqnd,bknd->bnqk", mode).astype(jnp.float32) \
        / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _dot(probs, v, "bnqk,bknd->bqnd", mode).reshape(b, s, h)
    x = x + _dot(o, blk["w_proj"], "bsh,hk->bsk", mode) + blk["b_proj"]
    a = _layer_norm(x, blk["ln2_w"], blk["ln2_b"], eps)
    u = _gelu_tanh(_dot(a, blk["w_fc1"], "bsh,hf->bsf", mode) + blk["b_fc1"])
    return x + _dot(u, blk["w_fc2"], "bsf,fh->bsh", mode) + blk["b_fc2"]


_BLOCK_LEAVES = ("ln1_w", "ln1_b", "w_qkv", "b_qkv", "w_proj", "b_proj",
                 "ln2_w", "ln2_b", "w_fc1", "b_fc1", "w_fc2", "b_fc2")


def logits(params, ids, *, heads, layout, eps=1e-5, mode="float32",
           remat=False):
    """[B, S] token ids -> [B, S, V] float32 logits. `params` is the
    canonical stacked pytree (float32; cast here for the bfloat16 mode)."""
    if mode == "bfloat16":
        params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                        params)
    s = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:s]
    body = functools.partial(_block, heads=heads, layout=layout, eps=eps,
                             mode=mode)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda x, blk: (body(x, blk), None), x,
                        {k: params[k] for k in _BLOCK_LEAVES})
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], eps)
    if mode == "bfloat16":
        return jnp.einsum("bsh,vh->bsv", x, params["wte"],
                          preferred_element_type=jnp.float32)
    return _dot(x, params["wte"], "bsh,vh->bsv", mode).astype(jnp.float32)


def loss(params, ids, labels, **kw):
    """Mean next-token cross entropy over every position of every row."""
    lg = logits(params, ids, **kw)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def loss_and_grads(params, ids, labels, row_block, **kw):
    """Loss and gradients of the whole batch, rows taken `row_block` at a
    time (equal blocks, so the mean of block means is the batch mean)."""
    b = ids.shape[0]
    assert b % row_block == 0, (b, row_block)
    n = b // row_block
    ids = ids.reshape(n, row_block, -1)
    labels = labels.reshape(n, row_block, -1)
    vg = jax.value_and_grad(functools.partial(loss, remat=True, **kw))

    def one(acc, xs):
        l, g = vg(params, xs[0], xs[1])
        return jax.tree_util.tree_map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(one, zero, (ids, labels))
    return l / n, jax.tree_util.tree_map(lambda x: x / n, g)


def adamw_step(params, m, v, t, grads, opt):
    """Global-norm clip, then AdamW with decoupled weight decay on every
    leaf and bias correction, as the configuration states. Returns the new
    (params, m, v) and the clipped gradient the optimizer got."""
    sq = sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(jnp.sqrt(sq),
                                                             1e-6))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    b1, b2, lr = opt["b1"], opt["b2"], opt["lr"]
    t = t + 1
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                               grads)

    def upd(p, m, v):
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p * (1 - lr * opt["weight_decay"]) \
            - lr * mhat / (jnp.sqrt(vhat) + opt["eps"])

    return jax.tree_util.tree_map(upd, params, m, v), m, v, grads


def leaf_norms(tree, layout, heads):
    """{leaf: norms per layer} — a stacked leaf gives one norm per layer, a
    global leaf a single norm, and the fused qkv leaves count as q, k and v
    apart: the unit by which the worst leaf is found."""
    from .weights import split_qkv
    out = {}
    for name, x in split_qkv(tree, layout, heads).items():
        x = x.astype(jnp.float32)
        rows = x.reshape(x.shape[0], -1) \
            if name.split(".")[0] in _BLOCK_LEAVES else x.reshape(1, -1)
        out[name] = jnp.sqrt(jnp.sum(rows * rows, axis=1))
    return out


def train_readings(make_params, batches, opt, *, row_block, steps=3, **kw):
    """Follow the first `steps` steps on `batches` (a list of (ids, labels))
    from the float32 parameters `make_params()` gives (called twice: the
    step donates its state, and the start is wanted again at the end).
    Returns the losses, the per-leaf norms of the first clipped gradient and
    of the parameters' change after the last step."""
    step = jax.jit(lambda p, m, v, t, ids, labels: _train_step(
        p, m, v, t, ids, labels, opt, row_block, kw),
        donate_argnums=(0, 1, 2))
    p = make_params()
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for t in range(steps):
        ids, labels = batches[t]
        l, p, m, v, gn = step(p, m, v, jnp.float32(t), ids, labels)
        losses.append(float(l))
        if t == 0:
            grad_norms = jax.device_get(gn)
    del m, v
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b), kw["layout"],
        kw["heads"]))(p, make_params())
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta)}


def _train_step(p, m, v, t, ids, labels, opt, row_block, kw):
    l, g = loss_and_grads(p, ids, labels, row_block, **kw)
    p, m, v, g = adamw_step(p, m, v, t, g, opt)
    return l, p, m, v, leaf_norms(g, kw["layout"], kw["heads"])
