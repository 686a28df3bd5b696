"""GPT model family — the flagship decoder LM.

Reference capability: PaddleNLP-style GPT trained via fleet hybrid parallel
(the GPT-3 1.3B/6.7B reference configurations, examples/README.md).
TPU-native: pre-LN transformer with
the Pallas flash-attention path (ops/flash_attention.py), TP-annotated
parameters (split_axis) so the fleet/jit runner can shard over 'mp', and a
single jit-compiled train step (see paddle_tpu.parallel.gpt_train).
"""
from dataclasses import dataclass

import jax.numpy as jnp

from ...core.tensor import Tensor, apply_op
from ...nn import (Dropout, Embedding, GELU, Layer, LayerList, LayerNorm, Linear)
from ...nn import functional as F
from ...nn.initializer import Normal
from ...observability import numerics as _numerics


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    max_position_embeddings: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = None
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    initializer_range: float = 0.02
    tie_embeddings: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        init = Normal(0.0, cfg.initializer_range)
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.qkv = Linear(h, 3 * h, weight_attr=init)
        self.qkv.weight.split_axis = 1  # column-parallel over mp
        self.qkv.bias.split_axis = 0
        self.out_proj = Linear(h, h, weight_attr=init)
        self.out_proj.weight.split_axis = 0  # row-parallel over mp
        self.dropout = cfg.attention_dropout

    def _proj(self, out, adapters):
        proj = self.out_proj(out)
        if adapters is not None:
            from ...serving.tenancy.adapters import lora_apply
            proj = lora_apply(proj, out, adapters, "out_proj")
        return proj

    def forward(self, x, cache=None, pos=None, tables=None, valid=None,
                adapters=None):
        """Train/prefill-uncached path when cache is None. With a
        `serving.kv_cache.LayerKV` cache (+ per-slot `pos`), the projected
        k/v are written into the preallocated buffers at pos via
        dynamic_update_slice and attention runs over the full static
        buffer — the single-token decode step keeps one set of avals and
        compiles once (docs/serving.md). With `tables` given, the cache
        is a `serving.blocks.PagedLayerKV` pool instead: writes scatter
        into the slot's physical blocks and attention gathers them back
        through the block table — same avals forever, same compile-once
        property. `valid` (quantized pools only) is the per-slot count
        of REAL tokens in this write — bucket padding must not ride the
        block scales. `adapters` (decode only) is this layer's per-slot
        LoRA view {"slot": ids, "qkv": (a, b), "out_proj": (a, b)} —
        deltas gathered BY SLOT so mixed-tenant batches keep one trace
        (serving/tenancy/adapters.py)."""
        B, S, H = x.shape
        qkv = self.qkv(x)  # B,S,3H
        if adapters is not None:
            from ...serving.tenancy.adapters import lora_apply
            qkv = lora_apply(qkv, x, adapters, "qkv")
        qkv = qkv.reshape([B, S, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # B,S,h,d
        if cache is not None and tables is not None:
            from ...serving import blocks as _blk
            kernel = _blk.kernel_attends(q._data, cache.k._data)
            if hasattr(cache, "k_scale"):
                # QUANTIZED pool (serving.blocks.QuantPagedLayerKV): the
                # write requantizes the touched blocks (abs-max per block
                # per head) and attention dequantizes — in-kernel for the
                # "kernel" impl, via the gathered dense view for "gather"
                k_pool, k_sc = apply_op(_blk.quant_write, cache.k,
                                        cache.k_scale, k, tables, pos,
                                        valid)
                v_pool, v_sc = apply_op(_blk.quant_write, cache.v,
                                        cache.v_scale, v, tables, pos,
                                        valid)
                attend = _blk.attend_kernel_quant if kernel \
                    else _blk.attend_quant
                out = apply_op(attend, q, k_pool, v_pool, k_sc, v_sc,
                               tables, pos)
                out = out.reshape([B, S, H])
                return self._proj(out, adapters), _blk.QuantPagedLayerKV(
                    k_pool, v_pool, k_sc, v_sc)
            k_pool = apply_op(_blk.write, cache.k, k, tables, pos)
            v_pool = apply_op(_blk.write, cache.v, v, tables, pos)
            # trace-time dispatch (serving.blocks.attention_impl):
            # "gather" rebuilds the dense view (bit-exact oracle),
            # "kernel" walks the block table inside a Pallas kernel,
            # "decode_kernel" does so for one query a slot only —
            # distinct function objects, so executables can never mix
            attend = _blk.attend_kernel if kernel else _blk.attend
            out = apply_op(attend, q, k_pool, v_pool, tables, pos)
            out = out.reshape([B, S, H])
            return self._proj(out, adapters), _blk.PagedLayerKV(k_pool,
                                                                v_pool)
        if cache is not None:
            from ...serving import kv_cache as _kvc
            k_buf = apply_op(_kvc.write, cache.k, k, pos)
            v_buf = apply_op(_kvc.write, cache.v, v, pos)
            out = apply_op(_kvc.attend, q, k_buf, v_buf, pos)
            out = out.reshape([B, S, H])
            return self._proj(out, adapters), _kvc.LayerKV(k_buf, v_buf)
        out = F.scaled_dot_product_attention(
            q, k, v, dropout_p=self.dropout, is_causal=True,
            training=self.training)
        out = out.reshape([B, S, H])
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        self.fc1 = Linear(cfg.hidden_size, cfg.intermediate_size, weight_attr=init)
        self.fc1.weight.split_axis = 1
        self.fc1.bias.split_axis = 0
        self.fc2 = Linear(cfg.intermediate_size, cfg.hidden_size, weight_attr=init)
        self.fc2.weight.split_axis = 0
        self.act = GELU(approximate=True)

    def forward(self, x, adapters=None):
        h = self.fc1(x)
        if adapters is not None:
            from ...serving.tenancy.adapters import lora_apply
            h = lora_apply(h, x, adapters, "fc1")
        mid = self.act(h)
        y = self.fc2(mid)
        if adapters is not None:
            from ...serving.tenancy.adapters import lora_apply
            y = lora_apply(y, mid, adapters, "fc2")
        return y


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size)
        self.mlp = GPTMLP(cfg)
        self.dropout = Dropout(cfg.hidden_dropout)

    def forward(self, x, cache=None, pos=None, tables=None, valid=None,
                adapters=None):
        if cache is not None:
            attn_out, new_cache = self.attn(self.ln1(x), cache=cache,
                                            pos=pos, tables=tables,
                                            valid=valid, adapters=adapters)
            x = x + self.dropout(attn_out)
            x = x + self.dropout(self.mlp(self.ln2(x), adapters=adapters))
            return x, new_cache
        x = x + self.dropout(self.attn(self.ln1(x)))
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return x


class GPT(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = Normal(0.0, cfg.initializer_range)
        self.wte = Embedding(cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        self.wte.weight.split_axis = 0  # vocab-parallel
        self.wpe = Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                             weight_attr=init)
        self.drop = Dropout(cfg.hidden_dropout)
        self.blocks = LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size)
        if not cfg.tie_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  weight_attr=init, bias_attr=False)

    def gen_cache(self, batch, max_len, dtype=None):
        """Preallocated static decode cache (serving/kv_cache.py): one
        [batch, max_len, heads, head_dim] K/V pair per block, pos=0.
        max_len must not exceed max_position_embeddings (the position
        table is the other static buffer)."""
        from ...serving import kv_cache as _kvc
        if max_len > self.cfg.max_position_embeddings:
            raise ValueError(
                f"gen_cache max_len={max_len} exceeds "
                f"max_position_embeddings={self.cfg.max_position_embeddings}")
        dtype = dtype or self.wte.weight.dtype
        raw = _kvc.alloc_cache(self.cfg.num_layers, batch, max_len,
                               self.cfg.num_heads,
                               self.cfg.hidden_size // self.cfg.num_heads,
                               dtype)
        return _kvc.DecodeCache(
            tuple(_kvc.LayerKV(Tensor(l.k), Tensor(l.v)) for l in raw.layers),
            Tensor(raw.pos))

    # -- what it serves from (serving/blocks.py has the protocol) ---------
    serving_counters = ()

    def cache_layout(self):
        """K and V of every head a token, paged, in every layer."""
        from ...serving import blocks as _blk
        cfg = self.cfg
        return (_blk.KVSpec(cfg.num_heads,
                            cfg.hidden_size // cfg.num_heads),) \
            * cfg.num_layers

    def prefill_keeps_last_row(self, tokens):
        return False

    def float32_parameters(self):
        return frozenset()

    def forward(self, input_ids, cache=None, adapters=None):
        B, S = input_ids.shape
        from ...tensor.creation import arange
        if cache is not None:
            from ...serving import kv_cache as _kvc
            # a paged cache (serving.blocks.PagedDecodeCache) carries its
            # block tables alongside the pools; the dense DecodeCache has
            # no `tables` field — same forward, two memory layouts
            tables = getattr(cache, "tables", None)
            valid = getattr(cache, "valid", None)
            pos = cache.pos
            positions = apply_op(
                lambda p, ids: p.astype(jnp.int32)[:, None]
                + jnp.arange(ids.shape[1], dtype=jnp.int32),
                pos, input_ids)
            x = self.drop(self.wte(input_ids) + self.wpe(positions))
            new_layers = []
            for i, (blk, lkv) in enumerate(zip(self.blocks, cache.layers)):
                lv = None if adapters is None else \
                    {"slot": adapters["slot"], **adapters["layers"][i]}
                x, new_lkv = blk(x, cache=lkv, pos=pos, tables=tables,
                                 valid=valid, adapters=lv)
                new_layers.append(new_lkv)
                # per-layer sentinel (ISSUE 19): dormant unless a
                # numerics sink with a layer filter is armed — the
                # bisection localizer's probe sites
                _numerics.tap_layer(i, "act", x._data)
            logits = self._head(self.ln_f(x))
            if tables is not None:
                # the paged engine's protocol: the new cache, no counters
                from ...serving import blocks as _blk
                return logits, _blk.PagedDecodeCache(
                    tuple(new_layers), tables, pos + S), \
                    Tensor(jnp.zeros((0,), jnp.int32))
            return logits, _kvc.DecodeCache(tuple(new_layers), pos + S)
        pos = arange(0, S, dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        for blk in self.blocks:
            x = blk(x)
        return self._head(self.ln_f(x))

    def _head(self, x):
        if self.cfg.tie_embeddings:
            return apply_op(lambda h, w: jnp.einsum("bsh,vh->bsv", h, w),
                            x, self.wte.weight)
        return self.lm_head(x)

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(logits.reshape([-1, self.cfg.vocab_size]),
                               labels.reshape([-1]))

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())


class GPTStage(Layer):
    """One pipeline stage of a GPT for hybrid-parallel SERVING (ISSUE
    13) — the `LayerDesc`/`ernie_pipeline_descs` stage-split convention
    (embed | N blocks | head), collapsed to constructed layers sharing
    the parent model's sublayer objects (no second weight copy at
    build; the serving engine places each stage's params on its own
    device group). The tied embedding plays the `SharedLayerDesc` role:
    it appears on the FIRST stage as the input table and on the LAST as
    the head matrix (`head_wte`) — one logical parameter, resident on
    both stages' devices, exactly how a shared desc materializes across
    a pipeline.

    `forward(x, cache=..., pos=..., tables=..., op=...)` runs the
    cached paged path of `GPT.forward` for this stage's slice:
      op="block"       embed (first stage only) + this stage's blocks
                        -> (hidden, new layer KVs)
      op="block_head"  block + final LN + LM head -> (logits, new KVs)
      op="head"        x is block output; final LN + head -> logits
                        (the chunked-prefill first-token tap)
    """

    def __init__(self, gpt, start, stop):
        super().__init__()
        cfg = gpt.cfg
        self.cfg = cfg
        self.start, self.stop = int(start), int(stop)
        self.is_first = self.start == 0
        self.is_last = self.stop == cfg.num_layers
        if self.is_first:
            self.wte = gpt.wte
            self.wpe = gpt.wpe
            self.drop = gpt.drop
        self.blocks = LayerList([gpt.blocks[i]
                                 for i in range(self.start, self.stop)])
        if self.is_last:
            self.ln_f = gpt.ln_f
            if cfg.tie_embeddings:
                if not self.is_first:
                    self.head_wte = gpt.wte    # the SharedLayerDesc tie
            else:
                self.lm_head = gpt.lm_head

    def _head(self, x):
        if not self.cfg.tie_embeddings:
            return self.lm_head(x)
        w = self.wte.weight if self.is_first else self.head_wte.weight
        return apply_op(lambda h, wt: jnp.einsum("bsh,vh->bsv", h, wt),
                        x, w)

    def forward(self, x, cache=None, pos=None, tables=None, valid=None,
                op="block", adapters=None):
        if op == "head":
            return self._head(self.ln_f(x))
        if self.is_first:
            positions = apply_op(
                lambda p, ids: p.astype(jnp.int32)[:, None]
                + jnp.arange(ids.shape[1], dtype=jnp.int32), pos, x)
            x = self.drop(self.wte(x) + self.wpe(positions))
        new_layers = []
        for i, (blk, lkv) in enumerate(zip(self.blocks, cache.layers)):
            # `adapters["layers"]` is already THIS stage's slice — the
            # engine shards the bank with the stage (distributed/pp.py)
            lv = None if adapters is None else \
                {"slot": adapters["slot"], **adapters["layers"][i]}
            x, new_lkv = blk(x, cache=lkv, pos=pos, tables=tables,
                             valid=valid, adapters=lv)
            new_layers.append(new_lkv)
            # GLOBAL layer index: localizer sites stay unique across
            # pipeline stages
            _numerics.tap_layer(self.start + i, "act", x._data)
        if op == "block_head":
            return self._head(self.ln_f(x)), tuple(new_layers)
        return x, tuple(new_layers)


def gpt_stage_ranges(num_layers, pp, stage_layers=None):
    """Contiguous [start, stop) block ranges for `pp` stages — the
    uniform partition `fleet.meta_parallel.PipelineLayer` applies to a
    LayerDesc list, or an explicit per-stage layer-count override (must
    sum to num_layers)."""
    pp = int(pp)
    if stage_layers is not None:
        counts = [int(c) for c in stage_layers]
        if len(counts) != pp or sum(counts) != num_layers \
                or min(counts) < 1:
            raise ValueError(
                f"stage_layers {counts} must be {pp} positive counts "
                f"summing to {num_layers}")
    else:
        if not 1 <= pp <= num_layers:
            raise ValueError(f"pp={pp} must be in 1..num_layers="
                             f"{num_layers}")
        base, rem = divmod(num_layers, pp)
        counts = [base + (1 if s < rem else 0) for s in range(pp)]
    ranges, at = [], 0
    for c in counts:
        ranges.append((at, at + c))
        at += c
    return ranges


def gpt_pipeline_stages(model, pp, stage_layers=None):
    """Partition `model` (a GPT) into `pp` GPTStage layers sharing its
    sublayer objects — what `serving.distributed.pp` places over the
    pipeline mesh axis."""
    stages = [GPTStage(model, a, b)
              for a, b in gpt_stage_ranges(model.cfg.num_layers, pp,
                                           stage_layers)]
    for st in stages:
        st.eval()
    return stages


class GPTForGeneration(Layer):
    """Autoregressive decoding head over a GPT (reference capability:
    PaddleNLP GPTForGeneration / generation_utils). `use_cache=True` runs
    the static-cache decode path — prefill writes the prompt's K/V once,
    then each step is a fixed-shape single-token forward; `use_cache=False`
    recomputes the full forward per token (the parity oracle, and the only
    mode the reference's growing cache could offer without per-token
    recompiles)."""

    def __init__(self, gpt: GPT):
        super().__init__()
        self.gpt = gpt

    def forward(self, input_ids, **kwargs):
        return self.generate(input_ids, **kwargs)

    def _select(self, logits, strategy, temperature, top_k, top_p):
        from ...core.random import next_key
        from ...serving import sampling as _sampling
        key = next_key() if strategy == "sampling" else None
        return apply_op(
            lambda lg: _sampling.select_tokens(
                lg, key=key, strategy=strategy, temperature=temperature,
                top_k=top_k, top_p=top_p), logits)

    def generate(self, input_ids, max_new_tokens=20, decode_strategy="greedy",
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 use_cache=True, max_cache_len=None):
        """input_ids [B, S] -> (generated_ids [B, max_new_tokens] int32,
        lengths [B] int32). Rows that hit eos are padded with eos; lengths
        count tokens up to and including it. Stops early once every row
        is done."""
        import numpy as np
        B, S = input_ids.shape
        limit = max_cache_len or S + max_new_tokens
        if S + max_new_tokens > limit or \
                S + max_new_tokens > self.gpt.cfg.max_position_embeddings:
            # position lookups/cache writes past the table CLAMP under XLA
            # (silently wrong tokens), so over-length requests must raise
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_position_embeddings="
                f"{self.gpt.cfg.max_position_embeddings}"
                + (f" / max_cache_len={max_cache_len}" if max_cache_len
                   else ""))
        picked = []
        if use_cache:
            cache = self.gpt.gen_cache(B, limit)
            logits, cache = self.gpt(input_ids, cache=cache)
            nxt = self._select(logits[:, -1], decode_strategy, temperature,
                               top_k, top_p)
        else:
            ids = input_ids
            logits = self.gpt(ids)
            nxt = self._select(logits[:, -1], decode_strategy, temperature,
                               top_k, top_p)
        done = np.zeros((B,), bool)
        for _ in range(max_new_tokens):
            step_tokens = np.asarray(nxt.numpy(), np.int32)
            if eos_token_id is not None:
                step_tokens = np.where(done, eos_token_id, step_tokens)
                done |= step_tokens == eos_token_id
            picked.append(step_tokens)
            if len(picked) == max_new_tokens or \
                    (eos_token_id is not None and done.all()):
                break
            tok = Tensor(jnp.asarray(step_tokens)[:, None])
            if use_cache:
                logits, cache = self.gpt(tok, cache=cache)
                nxt = self._select(logits[:, 0], decode_strategy, temperature,
                                   top_k, top_p)
            else:
                from ...tensor.manipulation import concat
                ids = concat([ids, tok.astype(ids.dtype)], axis=1)
                logits = self.gpt(ids)
                nxt = self._select(logits[:, -1], decode_strategy,
                                   temperature, top_k, top_p)
        out = np.stack(picked, axis=1)
        if eos_token_id is None:
            lengths = np.full((B,), out.shape[1], np.int32)
        else:
            hit = out == eos_token_id
            first = np.where(hit.any(1), hit.argmax(1) + 1, out.shape[1])
            lengths = first.astype(np.int32)
        return Tensor(jnp.asarray(out)), Tensor(jnp.asarray(lengths))


def gpt_tiny(**kw):
    return GPT(GPTConfig(hidden_size=128, num_layers=2, num_heads=4,
                         max_position_embeddings=256, vocab_size=1024, **kw))


def gpt_125m(**kw):
    return GPT(GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw))


def gpt_350m(**kw):
    return GPT(GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw))


def gpt_1p3b(**kw):
    return GPT(GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                         max_position_embeddings=2048, **kw))


def gpt_6p7b(**kw):
    return GPT(GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                         max_position_embeddings=2048, **kw))
