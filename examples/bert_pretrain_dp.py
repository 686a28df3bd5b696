"""Reference configuration 2: BERT-base pretraining, dygraph data parallelism.

Reference UX: paddle.DataParallel + fleet DP (python/paddle/fluid/dygraph/
parallel.py); here DP comes from a `dp` mesh axis — `Model.fit` (or the
eager loop below) shards the batch and the gradient psum runs over ICI
inside the compiled step. Run:

    python examples/bert_pretrain_dp.py                # tiny, dp over all
                                                       # local devices
    python examples/bert_pretrain_dp.py --full         # BERT-base dims
    python examples/bert_pretrain_dp.py --dp 8         # explicit axis size

Pretraining batches are synthetic (zero-egress): random token ids with a
15% MLM mask, ignore_index=-1 elsewhere — the reference's masking scheme.
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed.env as dist_env
from paddle_tpu.text.models import BertConfig, BertForPretraining


def synth_batch(rng, B, S, vocab, mask_rate=0.15):
    ids = rng.randint(4, vocab, (B, S))
    mlm = np.full((B, S), -1, np.int64)
    m = rng.rand(B, S) < mask_rate
    mlm[m] = ids[m]
    ids2 = ids.copy()
    ids2[m] = 3                         # [MASK]
    nsp = rng.randint(0, 2, (B,))
    return (paddle.to_tensor(ids2), paddle.to_tensor(mlm),
            paddle.to_tensor(nsp))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="BERT-base dims")
    ap.add_argument("--dp", type=int, default=None)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    import jax
    dp = args.dp or len(jax.devices())
    dist_env.build_mesh({"dp": dp})
    paddle.seed(0)

    cfg = BertConfig() if args.full else BertConfig(
        vocab_size=1024, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=128)
    net = BertForPretraining(cfg)
    B = args.batch or (dp * (32 if args.full else 2))
    S = 128 if args.full else 32

    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters())
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        ids, mlm, nsp = synth_batch(rng, B, S, cfg.vocab_size)
        loss = net.loss(ids, mlm, nsp_labels=nsp)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
