"""Reference configuration 3: GPT-3 1.3B with sharding stage-2 (ZeRO-2).

Reference UX: fleet DistributedStrategy sharding_degree / stage=2
(python/paddle/distributed/fleet/meta_optimizers/sharding_optimizer.py).
Here: `MeshPlan(sharding=N)` — the AdamW moments and f32 master weights
are sharded over the axis and gradients arrive via psum_scatter
(reduce-scatter over ICI), exactly the stage-2 memory equation. Run:

    python examples/gpt_sharding_stage2.py             # tiny smoke
    python examples/gpt_sharding_stage2.py --full      # 1.3B dims (v5p+)
    python examples/gpt_sharding_stage2.py --sharding 8
"""
import argparse

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.parallel import GPTSpmdConfig, MeshPlan, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="GPT-1.3B (hidden 2048 x 24 layers)")
    ap.add_argument("--sharding", type=int, default=None)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    shard = args.sharding or len(jax.devices())
    if args.full:
        cfg = GPTSpmdConfig(vocab_size=50304, max_seq_len=1024, hidden=2048,
                            layers=24, heads=16, param_dtype="bfloat16",
                            compute_dtype="bfloat16", remat="dots+attn",
                            fused_ce_chunks=8)   # logits never materialize
    else:
        cfg = GPTSpmdConfig(vocab_size=512, max_seq_len=64, hidden=64,
                            layers=2, heads=4, remat=False,
                            fused_ce_chunks=4)
    plan = MeshPlan(sharding=shard)
    step_fn, init_fn, mesh = make_train_step(cfg, plan, learning_rate=2e-4)
    params, state = init_fn(jax.random.key(0))

    # the sharding axis also shards the batch (ZeRO = DP memory-sharded),
    # so B must be a multiple of it
    B = args.batch or shard
    if B % shard:
        raise SystemExit(f"--batch {B} must be divisible by sharding={shard}")
    S = cfg.max_seq_len
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
        labs = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
        loss, params, state = step_fn(params, state, toks, labs,
                                      jnp.float32(2e-4))
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
