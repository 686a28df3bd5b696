"""Reference configuration 4: ERNIE-3.0-Base with mp+pp hybrid via `Model.fit`.

Reference UX: fleet hybrid_configs {mp_degree, pp_degree} + hapi
(python/paddle/hapi/model.py:591-599 routes any fleet strategy). Here the
mesh carries both axes: pipeline stages run the compiled 1F1B schedule
(p2p over ICI), fleet mp layers inside stages run Megatron column/row
collectives (allgather/psum over ICI), tied embeddings via
SharedLayerDesc. Run:

    python examples/ernie_mp_pp.py                   # tiny (pp=2 x mp=2)
    python examples/ernie_mp_pp.py --full            # ERNIE-3.0-Base dims
    python examples/ernie_mp_pp.py --pp 4 --mp 2 --dp 2
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed.env as dist_env
from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
from paddle_tpu.text.models import (ernie_3_base_config, ernie_pipeline_descs,
                                    ernie_tiny_config)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="ERNIE-3.0-Base")
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--mp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--microbatches", type=int, default=2)
    args = ap.parse_args()

    axes = {"pp": args.pp, "mp": args.mp}
    if args.dp > 1:
        axes = {"dp": args.dp, **axes}
    dist_env.build_mesh(axes)
    paddle.seed(0)

    import paddle_tpu.nn.functional as F

    def mlm_loss(logits, labels):
        return F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                               labels.reshape([-1]), ignore_index=-1)

    cfg = ernie_3_base_config() if args.full else ernie_tiny_config()
    descs = ernie_pipeline_descs(cfg, loss_fn=mlm_loss)
    pl = PipelineLayer(descs, num_stages=args.pp, loss_fn=mlm_loss)
    m = paddle.Model(pl)
    m.prepare(paddle.optimizer.AdamW(1e-4, parameters=pl.parameters()),
              None, strategy={"microbatches": args.microbatches})

    B = max(args.microbatches * 2, 4) * max(args.dp, 1)
    S = 512 if args.full else 32
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        ids = rng.randint(4, cfg.vocab_size, (B, S))
        mlm = np.full((B, S), -1, np.int64)
        mask = rng.rand(B, S) < 0.15
        mlm[mask] = ids[mask]
        ids[mask] = 3
        (loss,), _ = m.train_batch([ids], [mlm])
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
