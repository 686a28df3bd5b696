"""Hybrid-parallel GPT: one jit-compiled train step, shard_map'd over a Mesh.

This is the TPU-native equivalent of the reference's entire static-graph
hybrid-parallel stack (SURVEY §2.10): DP (data), MP (Megatron tensor
parallel: mp_layers.py), PP (1F1B SectionWorker / pp_layers.py), sharding
(ZeRO group_sharded), plus SP (ring attention — net-new, absent upstream).
Where the reference composes program rewrites + NCCL ops + stream sync, here
each strategy is a few explicit collectives inside ONE shard_map'd function;
XLA's latency-hiding scheduler overlaps them with compute.

Axes (canonical order): dp, pp, sharding, sp, mp
- batch is sharded over (dp, sharding); sequence over sp; vocab/heads/ffn
  over mp; layers over pp.
- gradients: pmean over (dp, sp); ZeRO-2 update: psum_scatter over
  'sharding' -> per-shard AdamW with f32 master weights -> all_gather.
- pipeline: GPipe microbatch schedule written as lax.scan over
  (microbatches + pp - 1) ticks with ppermute hand-off; autodiff through the
  scan yields the reverse pipeline schedule automatically (the reference
  needed a hand-written SectionWorker for this).
"""
import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .pipeline_schedule import (arrival_tables, build_interleaved_tables,
                                build_tables, required_slots)
from .ring_attention import ring_attention, ulysses_attention

AXES = ("dp", "pp", "sharding", "sp", "mp")

_BLOCK_LEAVES = ("ln1_w", "ln1_b", "w_qkv", "b_qkv", "w_proj", "b_proj",
                 "ln2_w", "ln2_b", "w_fc1", "b_fc1", "w_fc2", "b_fc2")


@dataclass
class GPTSpmdConfig:
    vocab_size: int = 50304
    max_seq_len: int = 1024
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    ffn: int = None
    param_dtype: str = "float32"     # storage dtype ("bfloat16" for bench)
    compute_dtype: str = "float32"   # activation dtype
    # remat: False = none, True = full per-block checkpoint (max HBM saving),
    # "dots" = save matmul outputs, recompute elementwise (recompute is cheap
    # VPU work, the MXU results are kept), "dots+attn" = dots AND the flash
    # attention output: flash is a custom_vjp whose bwd kernel recomputes
    # attention internally, so letting block-level remat recompute its fwd
    # pays the attention FLOPs a third time — saving the (B,S,H) output
    # (16 MB/layer at the bench shape) skips that (best MFU/HBM trade on TPU)
    remat: object = True
    init_std: float = 0.02
    # lax.scan unroll over the layer stack: >1 lets XLA software-pipeline
    # adjacent blocks (weight prefetch overlapping compute) at the cost of
    # program size
    scan_unroll: int = 1
    # >1 enables the chunked fused linear-CE LM head (ops/fused_ce.py):
    # logits never materialize, saving ~2.5GB peak f32 at the bench shape
    # for one extra logits matmul of backward recompute. mp=1 only (the
    # vocab-parallel path shards the same memory mp ways instead). Must
    # divide vocab_size.
    fused_ce_chunks: int = 0

    def __post_init__(self):
        if self.ffn is None:
            self.ffn = 4 * self.hidden
        if int(self.scan_unroll) < 1:
            raise ValueError(
                f"scan_unroll must be >= 1, got {self.scan_unroll}")
        if int(self.fused_ce_chunks) > 1 and \
                self.vocab_size % int(self.fused_ce_chunks):
            raise ValueError(
                f"fused_ce_chunks {self.fused_ce_chunks} must divide "
                f"vocab_size {self.vocab_size}")


@dataclass
class MeshPlan:
    dp: int = 1
    pp: int = 1
    sharding: int = 1
    sp: int = 1
    mp: int = 1
    microbatches: int = 1            # pipeline microbatches (per-device batch)
    # pipeline schedule: "1f1b" (activation buffer bounded by pp — the 1F1B
    # memory guarantee), "eager1f1b" (minimum ticks, ~2x the buffer, still
    # O(pp) and M-independent), or "gpipe" (autodiff-through-scan reverse
    # schedule; activation memory grows with microbatches — comparison only)
    schedule: str = "1f1b"
    vpp: int = 1                     # interleaved virtual stages per device
    # sequence-parallel attention flavor: "ring" (K/V ppermute rotation,
    # O(S/sp) residency) or "ulysses" (head<->seq all-to-all, full-S local
    # attention — fewer/larger ICI transfers, flash-kernel friendly)
    sp_mode: str = "ring"

    def __post_init__(self):
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sp_mode {self.sp_mode!r}; use 'ring' or 'ulysses'")

    @property
    def dims(self):
        return {"dp": self.dp, "pp": self.pp, "sharding": self.sharding,
                "sp": self.sp, "mp": self.mp}

    @property
    def n_devices(self):
        return self.dp * self.pp * self.sharding * self.sp * self.mp

    def build_mesh(self, devices=None):
        devs = np.asarray(devices if devices is not None else jax.devices())
        dims = tuple(self.dims.values())
        return Mesh(devs[:int(np.prod(dims))].reshape(dims), AXES)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_specs(cfg: GPTSpmdConfig):
    """PartitionSpec per leaf: pp on the stacked-layer dim, mp megatron-style."""
    return {
        "wte": P("mp", None),            # vocab-parallel embedding rows
        "wpe": P(),
        "ln1_w": P("pp", None), "ln1_b": P("pp", None),
        "w_qkv": P("pp", None, "mp"), "b_qkv": P("pp", "mp"),
        "w_proj": P("pp", "mp", None), "b_proj": P("pp", None),
        "ln2_w": P("pp", None), "ln2_b": P("pp", None),
        "w_fc1": P("pp", None, "mp"), "b_fc1": P("pp", "mp"),
        "w_fc2": P("pp", "mp", None), "b_fc2": P("pp", None),
        "lnf_w": P(), "lnf_b": P(),
    }


def init_gpt_params(cfg: GPTSpmdConfig, key):
    """Global (logical) parameter pytree; stacked over layers for scan/pp."""
    L, H, F, V = cfg.layers, cfg.hidden, cfg.ffn, cfg.vocab_size
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    std = cfg.init_std
    proj_std = std / np.sqrt(2 * L)  # GPT-2 residual-scaled init

    def nrm(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    return {
        "wte": nrm(ks[0], (V, H), std),
        "wpe": nrm(ks[1], (cfg.max_seq_len, H), std),
        "ln1_w": jnp.ones((L, H), dt), "ln1_b": jnp.zeros((L, H), dt),
        "w_qkv": nrm(ks[2], (L, H, 3 * H), std),
        "b_qkv": jnp.zeros((L, 3 * H), dt),
        "w_proj": nrm(ks[3], (L, H, H), proj_std),
        "b_proj": jnp.zeros((L, H), dt),
        "ln2_w": jnp.ones((L, H), dt), "ln2_b": jnp.zeros((L, H), dt),
        "w_fc1": nrm(ks[4], (L, H, F), std),
        "b_fc1": jnp.zeros((L, F), dt),
        "w_fc2": nrm(ks[5], (L, F, H), proj_std),
        "b_fc2": jnp.zeros((L, H), dt),
        "lnf_w": jnp.ones((H,), dt), "lnf_b": jnp.zeros((H,), dt),
    }


# ---------------------------------------------------------------------------
# Forward pieces (run inside shard_map; shapes are LOCAL shards)
# ---------------------------------------------------------------------------

def _axis_psum(x, axis):
    """psum forward / identity backward (reference mp_ops.py _mp_allreduce).

    Under shard_map(check_vma=False) a raw lax.psum transposes to another
    psum, inflating cotangents by the axis size; since every use here feeds
    axis-replicated downstream compute, the true cotangent is replicated and
    the transpose must be identity — exactly Megatron's g-function.
    """
    @jax.custom_vjp
    def f(v):
        return jax.lax.psum(v, axis)

    def fwd(v):
        return jax.lax.psum(v, axis), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f(x)


def _mp_copy(x, plan):
    """Identity forward / psum-over-mp backward — the manual-TP input marker
    (reference: fleet mp_ops.py _c_identity). Needed because each mp rank's
    local backward only sees its own weight shard; upstream (replicated)
    tensors must accumulate cotangents from all ranks."""
    if plan.mp == 1:
        return x

    @jax.custom_vjp
    def f(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (jax.lax.psum(g, "mp"),)

    f.defvjp(fwd, bwd)
    return f(x)


def _ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _allgather_sp_attention(q, k, v, causal=True):
    """Sequence-parallel attention via all-gather of K/V over the sp axis.

    q/k/v: (B, h_loc, S_loc, d), S_loc = S/sp. K and V are gathered to the
    full sequence (group-scoped collective — safe inside lax.cond, unlike
    ppermute) and attention runs locally over the (S_loc, S) tile with the
    causal mask offset by this shard's global row position.
    """
    from ..ops.flash_attention import flash_attention_bhsd

    S_loc = q.shape[2]
    k_full = jax.lax.all_gather(k, "sp", axis=2, tiled=True)
    v_full = jax.lax.all_gather(v, "sp", axis=2, tiled=True)
    mask = None
    if causal:
        row0 = jax.lax.axis_index("sp") * S_loc
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (S_loc, k_full.shape[2]), 0)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (S_loc, k_full.shape[2]), 1)
        mask = jnp.where(rows >= cols, 0.0, -jnp.inf)[None, None]
    return flash_attention_bhsd(q, k_full, v_full, causal=False, mask=mask)


def _attention(h, blk, cfg, plan):
    B, S, _ = h.shape
    heads_loc = cfg.heads // plan.mp
    d = cfg.hidden // cfg.heads
    # w_qkv column layout is head-major [h0:(q|k|v), h1:(q|k|v), ...] so an
    # mp shard of the last dim is a whole number of heads (Megatron layout)
    h = _mp_copy(h, plan)
    qkv = h @ blk["w_qkv"] + blk["b_qkv"]          # (B,S,3H/mp)
    qkv = qkv.reshape(B, S, heads_loc, 3, d)
    q = jnp.moveaxis(qkv[:, :, :, 0], 2, 1)        # (B,h_loc,S,d)
    k = jnp.moveaxis(qkv[:, :, :, 1], 2, 1)
    v = jnp.moveaxis(qkv[:, :, :, 2], 2, 1)
    if plan.sp > 1 and plan.pp > 1:
        # Inside the 1F1B/interleaved tick body, stage compute is gated by
        # lax.cond on the (t, stage)-dependent tick table. XLA lowers
        # ppermute to CollectivePermute, a FULL-participation op (every
        # device must execute it, pairs or not), so the RING's ppermute
        # inside stage-divergent branches deadlocks the mesh. all_gather,
        # all_to_all and psum are group-scoped (replica_groups) and legal
        # there — so pp+sp honors sp_mode="ulysses" and otherwise uses
        # all-gather sequence parallelism instead of the ring.
        if plan.sp_mode == "ulysses":
            o = ulysses_attention(q, k, v, "sp", causal=True)
        else:
            o = _allgather_sp_attention(q, k, v, causal=True)
    elif plan.sp > 1:
        if plan.sp_mode == "ulysses":
            o = ulysses_attention(q, k, v, "sp", causal=True)
        else:
            o = ring_attention(q, k, v, "sp", causal=True)
    else:
        from ..ops.flash_attention import flash_attention_bhsd
        o = flash_attention_bhsd(q, k, v, causal=True)
    o = checkpoint_name(o, "flash_out")
    o = jnp.moveaxis(o, 1, 2).reshape(B, S, cfg.hidden // plan.mp)
    out = o @ blk["w_proj"]                        # partial sums over mp
    if plan.mp > 1:
        out = _axis_psum(out, "mp")
    return out + blk["b_proj"]


def _mlp(h, blk, plan):
    h = _mp_copy(h, plan)
    u = h @ blk["w_fc1"] + blk["b_fc1"]
    u = jax.nn.gelu(u, approximate=True)
    out = u @ blk["w_fc2"]
    if plan.mp > 1:
        out = _axis_psum(out, "mp")
    return out + blk["b_fc2"]


def _block(h, blk, cfg, plan):
    h = h + _attention(_ln(h, blk["ln1_w"], blk["ln1_b"]), blk, cfg, plan)
    h = h + _mlp(_ln(h, blk["ln2_w"], blk["ln2_b"]), blk, plan)
    return h


def _stage_blocks(h, params, cfg, plan):
    """Apply this pp-stage's local stack of blocks via lax.scan."""
    stacked = {k: params[k] for k in _BLOCK_LEAVES}

    def apply_block(h, blk):
        return _block(h, blk, cfg, plan)

    if cfg.remat == "dots":
        apply_block = jax.checkpoint(
            apply_block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif cfg.remat == "dots+attn":
        apply_block = jax.checkpoint(
            apply_block,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names("flash_out")))
    elif cfg.remat:
        apply_block = jax.checkpoint(apply_block)

    def body(h, blk):
        return apply_block(h, blk), None

    h, _ = jax.lax.scan(body, h, stacked, unroll=int(cfg.scan_unroll))
    return h


def _embed(tokens, params, cfg, plan):
    """Vocab-parallel embedding + position embedding (sp-offset aware)."""
    wte = params["wte"]                            # (V/mp, H) local
    if plan.mp > 1:
        per = wte.shape[0]
        start = jax.lax.axis_index("mp") * per
        ids = tokens.astype(jnp.int32) - start
        ok = (ids >= 0) & (ids < per)
        emb = jnp.take(wte, jnp.clip(ids, 0, per - 1), axis=0)
        emb = jnp.where(ok[..., None], emb, 0)
        emb = _axis_psum(emb, "mp")
    else:
        emb = jnp.take(wte, tokens.astype(jnp.int32), axis=0)
    S_loc = tokens.shape[-1]
    if plan.sp > 1:
        pos0 = jax.lax.axis_index("sp") * S_loc
        emb = emb + jax.lax.dynamic_slice_in_dim(params["wpe"], pos0, S_loc, 0)
    else:
        emb = emb + params["wpe"][:S_loc]
    return emb.astype(jnp.dtype(cfg.compute_dtype))


@jax.custom_vjp
def _logits_matmul(h, wte):
    """bf16 x bf16 -> f32 logits with a bf16-cotangent backward.

    Without this, the backward matmuls (dh = g @ wte, dw = g^T @ h) inherit
    the f32 cotangent as an operand and XLA runs them at the f32 MXU rate
    (~1/4-1/8 of bf16) — and they are the two largest matmuls in the model
    (B*S x V x H). Casting g to the param dtype first keeps full MXU rate;
    accumulation stays f32 via preferred_element_type (the standard
    mixed-precision recipe, and what the reference's fused
    c_softmax_with_cross_entropy kernel does by computing in fp16/bf16
    with fp32 softmax statistics)."""
    return jnp.einsum("bsh,vh->bsv", h, wte,
                      preferred_element_type=jnp.float32)


def _logits_matmul_fwd(h, wte):
    return _logits_matmul(h, wte), (h, wte)


def _logits_matmul_bwd(res, g):
    h, wte = res
    gl = g.astype(h.dtype)
    dh = jnp.einsum("bsv,vh->bsh", gl, wte,
                    preferred_element_type=jnp.float32).astype(h.dtype)
    dw = jnp.einsum("bsv,bsh->vh", gl, h,
                    preferred_element_type=jnp.float32).astype(wte.dtype)
    return dh, dw


_logits_matmul.defvjp(_logits_matmul_fwd, _logits_matmul_bwd)


def _vocab_parallel_loss(h, labels, params, cfg, plan):
    """Tied-embedding LM head + vocab-parallel softmax CE (reference:
    c_softmax_with_cross_entropy). Returns mean NLL over local tokens."""
    h = _ln(h, params["lnf_w"], params["lnf_b"])
    h = _mp_copy(h, plan)
    wte = params["wte"]                            # (V/mp, H) local
    if cfg.fused_ce_chunks > 1:
        # chunked fused linear-CE: logits never materialize (HBM-bound LM
        # head -> online logsumexp over vocab chunks; ops/fused_ce.py).
        # Under mp the op crosses the axis for softmax stats itself and
        # returns a partial dh that _mp_copy's backward psums.
        if wte.shape[0] % cfg.fused_ce_chunks:
            # erroring (not silently falling back to unfused) — the user
            # sized memory around this knob
            raise ValueError(
                f"(InvalidArgument) fused_ce_chunks={cfg.fused_ce_chunks} "
                f"must divide the vocab shard rows {wte.shape[0]} "
                f"(= vocab_size/mp); pick a chunk count that divides the "
                f"LOCAL shard")
        from ..ops.fused_ce import fused_linear_cross_entropy
        B, S, H = h.shape
        nll = fused_linear_cross_entropy(
            h.reshape(B * S, H), wte, labels.reshape(B * S),
            cfg.fused_ce_chunks, "mp" if plan.mp > 1 else None)
        return jnp.mean(nll)
    # bf16 operands, f32 accumulation: full MXU rate with f32-safe softmax
    # statistics downstream (vs. upcasting operands, which halves+ MXU
    # throughput for the biggest matmul in the model)
    logits = _logits_matmul(h, wte)
    local_max = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    gmax = jax.lax.stop_gradient(jax.lax.pmax(local_max, "mp")) \
        if plan.mp > 1 else local_max
    shifted = logits - gmax
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    if plan.mp > 1:
        sumexp = _axis_psum(sumexp, "mp")
    logz = jnp.log(sumexp)[..., 0]
    li = labels.astype(jnp.int32)
    if plan.mp > 1:
        per = wte.shape[0]
        start = jax.lax.axis_index("mp") * per
        lid = li - start
        ok = (lid >= 0) & (lid < per)
        picked = jnp.take_along_axis(shifted, jnp.clip(lid, 0, per - 1)[..., None],
                                     axis=-1)[..., 0]
        picked = _axis_psum(jnp.where(ok, picked, 0.0), "mp")
    else:
        picked = jnp.take_along_axis(shifted, li[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# ---------------------------------------------------------------------------
# Pipeline forward (GPipe ticks over ppermute)
# ---------------------------------------------------------------------------

def _pipeline_loss(tokens, labels, params, cfg, plan):
    """tokens/labels: (B_loc, S_loc) local shard. Returns scalar local loss."""
    pp = plan.pp
    if pp == 1:
        h = _embed(tokens, params, cfg, plan)
        h = _stage_blocks(h, params, cfg, plan)
        return _vocab_parallel_loss(h, labels, params, cfg, plan)

    M = plan.microbatches
    B_loc, S_loc = tokens.shape
    B_mb = B_loc // M
    tok_mb = tokens.reshape(M, B_mb, S_loc)
    lab_mb = labels.reshape(M, B_mb, S_loc)
    stage = jax.lax.axis_index("pp")
    is_first = stage == 0
    is_last = stage == pp - 1
    cdt = jnp.dtype(cfg.compute_dtype)
    T = M + pp - 1
    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

    def tick(carry, t):
        h_recv, loss_sum = carry
        # first stage feeds microbatch t (clamped); others use received act
        mb_in = jnp.clip(t, 0, M - 1)
        x_first = _embed(tok_mb[mb_in], params, cfg, plan)
        x = jnp.where(is_first, x_first, h_recv)
        h_out = _stage_blocks(x, params, cfg, plan)
        # last stage: loss for microbatch t-(pp-1) when in range
        mb_out = t - (pp - 1)
        valid = (mb_out >= 0) & (mb_out < M)
        lab = lab_mb[jnp.clip(mb_out, 0, M - 1)]
        mb_loss = _vocab_parallel_loss(h_out, lab, params, cfg, plan)
        loss_sum = loss_sum + jnp.where(is_last & valid, mb_loss, 0.0)
        h_send = jax.lax.ppermute(h_out, "pp", fwd_perm)
        return (h_send, loss_sum), None

    h0 = jnp.zeros((B_mb, S_loc, cfg.hidden), cdt)
    (_, loss_sum), _ = jax.lax.scan(tick, (h0, jnp.zeros((), jnp.float32)),
                                    jnp.arange(T))
    # defined on the last stage; broadcast to all pp ranks
    return _axis_psum(jnp.where(is_last, loss_sum / M, 0.0), "pp")


# ---------------------------------------------------------------------------
# 1F1B / interleaved pipeline: manual fwd+bwd schedule (no autodiff-through-
# scan). Reference: fleet/meta_parallel/pipeline_parallel.py:120 (1F1B),
# :464 (interleaved virtual stages). TPU-native design:
#   - the schedule is a static tick table (pipeline_schedule.py); the
#     compiled program is ONE lax.scan whose body runs at most one microbatch
#     forward and one backward per stage per tick, gated by lax.cond — so
#     embedding runs only on stage 0 and the LM head only on the last stage
#     (each pp row shares the predicate, so mp/sp collectives inside the
#     branches stay consistent).
#   - activation memory: only STAGE INPUTS are buffered, in a circular
#     buffer of `slots` = cap+1 entries (pp+1 for 1F1B) — M-independent.
#     The backward recomputes the stage forward from the saved input via
#     jax.vjp (Megatron "full recompute" style), which is also what bounds
#     the buffer to inputs rather than per-layer activations.
#   - gradients accumulate in f32 carries; the tied wte receives its
#     embedding contribution on stage 0 and its LM-head contribution on the
#     last stage (summed by the caller's psum over pp).
# ---------------------------------------------------------------------------

def interleave_permutation(L, pp, vpp):
    """Stacked-layer storage order for interleaved pipelining: device s's
    contiguous local shard holds its vpp chunks back-to-back, chunk c of
    device s being virtual stage k = c*pp + s (logical layers
    [k*L/D, (k+1)*L/D), D = pp*vpp). perm[new_pos] = logical_layer.

    This is a storage LAYOUT only — the pipeline body composes chunks in
    logical order, so the computed function is identical to the unpermuted
    model (checkpoints written under vpp>1 store this layout).
    """
    D = pp * vpp
    Lk = L // D
    perm = []
    for s in range(pp):
        for c in range(vpp):
            k = c * pp + s
            perm.extend(range(k * Lk, (k + 1) * Lk))
    return np.asarray(perm)


def _pipeline_manual_loss_and_grads(tokens, labels, params, cfg, plan):
    """1F1B/interleaved pipeline step: returns (local mean loss, grads pytree)
    with grads already divided by microbatch count (same semantics as
    value_and_grad of the mean loss). Runs inside shard_map."""
    pp, M, V = plan.pp, plan.microbatches, plan.vpp
    stage = jax.lax.axis_index("pp")
    is_first = stage == 0
    is_last = stage == pp - 1
    cdt = jnp.dtype(cfg.compute_dtype)
    B_loc, S_loc = tokens.shape
    B_mb = B_loc // M
    tok_mb = tokens.reshape(M, B_mb, S_loc)
    lab_mb = labels.reshape(M, B_mb, S_loc)
    Hd = cfg.hidden

    if V > 1:
        fwd_tbl, bwd_tbl, _ = build_interleaved_tables(M, pp, V)
    else:
        f_t, b_t, _ = build_tables(M, pp, plan.schedule)
        fwd_tbl, bwd_tbl = f_t[:, :, None], b_t[:, :, None]
    farr, garr = arrival_tables(fwd_tbl, bwd_tbl, pp, V)
    W = required_slots(fwd_tbl, bwd_tbl, farr, garr, M, pp, V)
    T = fwd_tbl.shape[0]
    fwd_tbl = jnp.asarray(fwd_tbl)
    bwd_tbl = jnp.asarray(bwd_tbl)
    farr = jnp.asarray(farr)
    garr = jnp.asarray(garr)

    bp_all = {k: params[k] for k in _BLOCK_LEAVES}
    hp = {k: params[k] for k in ("lnf_w", "lnf_b", "wte")}
    ep = {k: params[k] for k in ("wte", "wpe")}
    L_loc = bp_all["w_qkv"].shape[0]
    Lk = L_loc // V

    def chunk_params(c):
        return {k: jax.lax.slice_in_dim(v, c * Lk, (c + 1) * Lk, axis=0)
                for k, v in bp_all.items()}

    def stage_fn(bp_, x):
        return _stage_blocks(x, bp_, cfg, plan)

    def zeros_like_t(tree):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), tree)

    zero_act = jnp.zeros((B_mb, S_loc, Hd), cdt)
    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
    bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]
    f32 = jnp.float32

    def acc(a_tree, g_tree):
        return jax.tree_util.tree_map(
            lambda a, g: a + g.astype(f32), a_tree, g_tree)

    def tick(carry, t):
        buf, gbuf, fchan, gchan, loss_sum, g_bp, g_hp, g_ep = carry
        new_ys, new_gs = [], []
        for c in range(V):
            f_idx = fwd_tbl[t, stage, c]
            b_idx = bwd_tbl[t, stage, c]
            valid_f = f_idx >= 0
            valid_b = b_idx >= 0
            fi = jnp.clip(f_idx, 0, M - 1)
            bi = jnp.clip(b_idx, 0, M - 1)
            bp_c = chunk_params(c)

            # ---- park arrivals: the ppermute channels are overwritten every
            # tick, so incoming activations/cotangents go into the circular
            # buffers NOW even if this stage runs them later ----
            a_f = farr[t, stage, c]
            inc = fchan[c] if c == 0 else jnp.where(is_first, fchan[c - 1],
                                                    fchan[c])
            buf = jax.lax.cond(
                a_f >= 0,
                lambda: buf.at[c, jnp.clip(a_f, 0, M - 1) % W].set(inc),
                lambda: buf)
            a_g = garr[t, stage, c]
            g_inc = gchan[c] if c == V - 1 else jnp.where(is_last,
                                                          gchan[c + 1],
                                                          gchan[c])
            gbuf = jax.lax.cond(
                a_g >= 0,
                lambda: gbuf.at[c, jnp.clip(a_g, 0, M - 1) % W].set(g_inc),
                lambda: gbuf)

            # ---- forward: stage 0 chunk 0 embeds its input (and parks it
            # for the backward recompute); everyone else reads the buffer ----
            if c == 0:
                x_f = jax.lax.cond(
                    is_first,
                    lambda: _embed(tok_mb[fi], ep, cfg, plan),
                    lambda: buf[c, fi % W])
                buf = jax.lax.cond(
                    valid_f & is_first,
                    lambda: buf.at[c, fi % W].set(x_f),
                    lambda: buf)
            else:
                x_f = buf[c, fi % W]
            # the last virtual stage's output is consumed nowhere (its
            # backward recomputes the forward inside value_and_grad), so
            # skip that compute instead of shipping a dead activation
            run_f = valid_f if c < V - 1 else (valid_f & ~is_last)
            y_f = jax.lax.cond(
                run_f, lambda: stage_fn(bp_c, x_f), lambda: zero_act)
            new_ys.append(y_f)

            # ---- backward: last virtual stage seeds from the loss; others
            # apply the parked cotangent through the stage vjp ----
            x_b = buf[c, bi % W]
            g_in = gbuf[c, bi % W]

            def mid_branch():
                _, vjp = jax.vjp(stage_fn, bp_c, x_b)
                gb, gx = vjp(g_in)
                return jnp.zeros((), f32), gb, zeros_like_t(hp), gx

            if c == V - 1:
                def last_branch():
                    def head(bp_, hp_, x):
                        y = stage_fn(bp_, x)
                        return _vocab_parallel_loss(y, lab_mb[bi], hp_,
                                                    cfg, plan)
                    l, (gb, gh, gx) = jax.value_and_grad(
                        head, argnums=(0, 1, 2))(bp_c, hp, x_b)
                    return l, gb, gh, gx

                def do_b():
                    return jax.lax.cond(is_last, last_branch, mid_branch)
            else:
                do_b = mid_branch

            def skip_b():
                return (jnp.zeros((), f32), zeros_like_t(bp_c),
                        zeros_like_t(hp), zero_act)

            l_b, gb_c, gh_c, g_x = jax.lax.cond(valid_b, do_b, skip_b)
            new_gs.append(g_x)

            if c == 0:
                def emb_b():
                    _, evjp = jax.vjp(
                        lambda e: _embed(tok_mb[bi], e, cfg, plan), ep)
                    return evjp(g_x)[0]
                g_ep = acc(g_ep, jax.lax.cond(
                    is_first & valid_b, emb_b, lambda: zeros_like_t(ep)))
            g_bp = {k: g_bp[k].at[c * Lk:(c + 1) * Lk]
                    .add(gb_c[k].astype(f32)) for k in g_bp}
            g_hp = acc(g_hp, gh_c)
            loss_sum = loss_sum + l_b

        fchan = jax.lax.ppermute(jnp.stack(new_ys), "pp", fwd_perm)
        gchan = jax.lax.ppermute(jnp.stack(new_gs).astype(cdt), "pp",
                                 bwd_perm)
        return (buf, gbuf, fchan, gchan, loss_sum, g_bp, g_hp, g_ep), None

    carry0 = (
        jnp.zeros((V, W, B_mb, S_loc, Hd), cdt),
        jnp.zeros((V, W, B_mb, S_loc, Hd), cdt),
        jnp.zeros((V, B_mb, S_loc, Hd), cdt),
        jnp.zeros((V, B_mb, S_loc, Hd), cdt),
        jnp.zeros((), f32),
        {k: jnp.zeros(v.shape, f32) for k, v in bp_all.items()},
        {k: jnp.zeros(v.shape, f32) for k, v in hp.items()},
        {k: jnp.zeros(v.shape, f32) for k, v in ep.items()},
    )
    (_, _, _, _, loss_sum, g_bp, g_hp, g_ep), _ = jax.lax.scan(
        tick, carry0, jnp.arange(T))

    loss = _axis_psum(jnp.where(is_last, loss_sum / M, 0.0), "pp")
    grads = {k: v / M for k, v in g_bp.items()}
    grads["wte"] = (g_ep["wte"] + g_hp["wte"]) / M
    grads["wpe"] = g_ep["wpe"] / M
    grads["lnf_w"] = g_hp["lnf_w"] / M
    grads["lnf_b"] = g_hp["lnf_b"] / M
    return loss, grads


# ---------------------------------------------------------------------------
# ZeRO-2 sharded AdamW (f32 master weights)
# ---------------------------------------------------------------------------

def init_opt_state_leaf(p, plan):
    n = plan.sharding
    size = int(np.prod(p.shape))
    shard = (size + n - 1) // n
    return {"m": jnp.zeros((shard,), jnp.float32),
            "v": jnp.zeros((shard,), jnp.float32),
            "master": jnp.zeros((shard,), jnp.float32),  # filled on 1st step
            "t": jnp.zeros((), jnp.int32)}


def _zero2_adamw_update(p, g, st, lr, plan, wd=0.1, b1=0.9, b2=0.95, eps=1e-8):
    """Reduce-scatter grad -> shard update -> all-gather params.

    Matches paddle's GroupShardedOptimizerStage2 semantics (reference:
    fleet/meta_parallel/sharding/group_sharded_optimizer_stage2.py:51):
    optimizer states live sharded; comm = 1x reduce-scatter + 1x all-gather.
    """
    n = plan.sharding
    size = int(np.prod(p.shape))
    shard = (size + n - 1) // n
    pad = shard * n - size

    gf = jnp.pad(g.reshape(-1).astype(jnp.float32), (0, pad))
    if n > 1:
        g_sh = jax.lax.psum_scatter(gf, "sharding", scatter_dimension=0,
                                    tiled=True) / n
        idx = jax.lax.axis_index("sharding")
    else:
        g_sh = gf
        idx = 0
    pf = jnp.pad(p.reshape(-1).astype(jnp.float32), (0, pad))
    p_sh = jax.lax.dynamic_slice_in_dim(pf, idx * shard, shard, 0)

    t = st["t"] + 1
    # master weights: on step 1 adopt the (possibly bf16) param value
    master = jnp.where(st["t"] == 0, p_sh, st["master"])
    m = b1 * st["m"] + (1 - b1) * g_sh
    v = b2 * st["v"] + (1 - b2) * g_sh * g_sh
    mhat = m / (1 - b1 ** t.astype(jnp.float32))
    vhat = v / (1 - b2 ** t.astype(jnp.float32))
    master = master * (1 - lr * wd)
    master = master - lr * mhat / (jnp.sqrt(vhat) + eps)

    if n > 1:
        p_full = jax.lax.all_gather(master, "sharding", axis=0, tiled=True)
    else:
        p_full = master
    p_new = p_full[:size].reshape(p.shape).astype(p.dtype)
    return p_new, {"m": m, "v": v, "master": master, "t": t}


# ---------------------------------------------------------------------------
# The train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: GPTSpmdConfig, plan: MeshPlan, mesh=None,
                    learning_rate=3e-4, weight_decay=0.1, grad_clip=1.0):
    """Returns (step_fn, init_fn, mesh). step_fn(params, opt_state, tokens,
    labels, lr=None) -> (loss, params, opt_state), jit-compiled over the
    mesh; lr defaults to the `learning_rate` given here.

    tokens/labels are GLOBAL arrays (B_global, S_global); in_shardings place
    them as (('dp','sharding'), 'sp').
    """
    mesh = mesh or plan.build_mesh()
    specs = param_specs(cfg)
    data_spec = P(("dp", "sharding"), "sp")

    def _state_leaf_spec(pspec):
        # m/v/master are per-device 1-D shards; for params sharded over pp/mp
        # each of those ranks holds genuinely different state, so the logical
        # dim-0 is sharded over (those axes x sharding). Claiming replication
        # would corrupt state on any reshard/checkpoint round-trip.
        axes = tuple(a for ax in (pspec or ()) if ax is not None
                     for a in ((ax,) if isinstance(ax, str) else tuple(ax))
                     if a in ("pp", "mp"))
        v = P(axes + ("sharding",))
        return {"m": v, "v": v, "master": v, "t": P()}

    state_spec = {name: _state_leaf_spec(s) for name, s in specs.items()}

    def local_loss(params, tokens, labels):
        return _pipeline_loss(tokens, labels, params, cfg, plan)

    def sharded_step(params, opt_state, tokens, labels, lr):
        if plan.pp > 1 and (plan.vpp > 1 or plan.schedule != "gpipe"):
            loss, grads = _pipeline_manual_loss_and_grads(
                tokens, labels, params, cfg, plan)
        else:
            loss, grads = jax.value_and_grad(local_loss)(params, tokens, labels)
        # grad sync over all data axes BEFORE clipping so the global-norm
        # clip sees the true batch gradient (paddle semantics). The ZeRO
        # psum_scatter then acts as a slice of the replicated mean.
        sync_axes = tuple(a for a, d in (("dp", plan.dp), ("sp", plan.sp),
                                         ("sharding", plan.sharding)) if d > 1)
        if sync_axes:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, sync_axes), grads)
            loss = jax.lax.pmean(loss, sync_axes)
        if plan.pp > 1:
            # pp-replicated leaves (wte/wpe/lnf) get stage-disjoint grad
            # contributions (embedding on stage 0, LM head on the last);
            # total = psum over pp. pp-sharded leaves already hold their own.
            grads = {n: (jax.lax.psum(g, "pp")
                         if "pp" not in (specs[n] or ()) else g)
                     for n, g in grads.items()}
        # mp grads for replicated-over-mp params need psum? No: every mp rank
        # computes the same loss value; params sharded over mp get their own
        # shard grads; replicated params (ln, wpe) get identical grads on
        # every mp rank because the loss is mp-identical. Same for pp via the
        # psum broadcast in _pipeline_loss.
        if grad_clip:
            # global norm must include all shards of mp/pp-sharded params;
            # _global_grad_sq sums per-leaf with its spec so replicated
            # leaves aren't double counted
            psum_axes = tuple(a for a, d in (("mp", plan.mp), ("pp", plan.pp))
                              if d > 1)
            if psum_axes:
                sq = _global_grad_sq(grads, specs, plan)
            else:
                sq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads))
            gnorm = jnp.sqrt(sq)
            scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        new_params, new_state = {}, {}
        for name, p in params.items():
            p_new, s_new = _zero2_adamw_update(
                p, grads[name], opt_state[name], lr, plan, wd=weight_decay)
            new_params[name] = p_new
            new_state[name] = s_new
        return loss, new_params, new_state

    shmapped = jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(specs, state_spec, data_spec, data_spec, P()),
        out_specs=(P(), specs, state_spec),
        check_vma=False)
    jitted = jax.jit(shmapped, donate_argnums=(0, 1))

    def step_fn(params, opt_state, tokens, labels, lr=None):
        lr_val = jnp.asarray(learning_rate if lr is None else lr, jnp.float32)
        return jitted(params, opt_state, tokens, labels, lr_val)

    # the jitted program itself, for callers that inspect what was compiled
    # (chip_smoke asserts the Pallas custom call is in it): step_fn.jitted
    # .lower(params, opt_state, tokens, labels, lr_array)
    step_fn.jitted = jitted

    def init_fn(key):
        params = init_gpt_params(cfg, key)
        if plan.vpp > 1:
            # interleaved storage layout (same logical model — see
            # interleave_permutation)
            perm = interleave_permutation(cfg.layers, plan.pp, plan.vpp)
            params = {k: (v[perm] if k in _BLOCK_LEAVES else v)
                      for k, v in params.items()}
        params = jax.tree_util.tree_map(
            lambda p, s: _put_global(p, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))

        def init_state(params):
            return {k: init_opt_state_leaf(p, plan) for k, p in params.items()}

        state = jax.jit(jax.shard_map(
            init_state, mesh=mesh, in_specs=(specs,), out_specs=state_spec,
            check_vma=False))(params)
        return params, state

    return step_fn, init_fn, mesh


def _put_global(x, sharding):
    """Place a host-replicated value onto a (possibly multi-process) mesh.

    Single-controller: plain device_put. Multi-controller (jax.distributed,
    the DCN path): the sharding spans non-addressable devices, so each
    process contributes its addressable shards from the identical host copy
    (reference role: broadcast-from-rank-0 parameter init in
    fleet/meta_parallel — here every host derives the same init from the
    same seed, so no broadcast is needed)."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    host = np.asarray(x)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


def _global_grad_sq(grads, specs, plan):
    """Sum of squares across ALL logical gradient elements, correcting for
    mp/pp sharding per leaf."""
    total = jnp.zeros((), jnp.float32)
    for name, g in grads.items():
        leaf_sq = jnp.sum(g.astype(jnp.float32) ** 2)
        spec = specs[name]
        axes = [a for a in (spec or ()) if a in ("mp", "pp")]
        for a in axes:
            if (a == "mp" and plan.mp > 1) or (a == "pp" and plan.pp > 1):
                leaf_sq = jax.lax.psum(leaf_sq, a)
        total = total + leaf_sq
    return total


def make_forward_fn(cfg: GPTSpmdConfig):
    """Single-chip jittable forward (logits) for compile checks / serving."""
    plan = MeshPlan()

    def fwd(params, tokens):
        h = _embed(tokens, params, cfg, plan)
        h = _stage_blocks(h, params, cfg, plan)
        h = _ln(h, params["lnf_w"], params["lnf_b"])
        return jnp.einsum("bsh,vh->bsv", h.astype(jnp.float32),
                          params["wte"].astype(jnp.float32))
    return fwd
