"""Reference configuration 5: PP-YOLOE detection training (conv/bn/SiLU + SyncBN).

Reference UX: PaddleDetection's PP-YOLOE (the reference repo carries its
kernel stack: conv + sync_batch_norm ops). Here SyncBatchNorm reduces
statistics over the `dp` axis inside the compiled step and the loss is
the varifocal + GIoU + DFL composite. Run:

    python examples/ppyoloe_detection.py             # tiny crn on synth boxes
    python examples/ppyoloe_detection.py --full      # ppyoloe_s, 640x640
    python examples/ppyoloe_detection.py --dp 4      # SyncBN over 4 devices
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed.env as dist_env
from paddle_tpu.vision.models import PPYOLOE, PPYOLOEConfig, ppyoloe_loss


def synth_dets(rng, B, size, max_boxes=4, num_classes=4):
    boxes = np.zeros((B, max_boxes, 4), np.float32)
    cls = np.zeros((B, max_boxes), np.int64)
    mask = np.zeros((B, max_boxes), np.float32)
    for b in range(B):
        n = rng.randint(1, max_boxes + 1)
        for i in range(n):
            x0, y0 = rng.randint(0, size // 2, 2)
            w, h = rng.randint(size // 8, size // 2, 2)
            boxes[b, i] = [x0, y0, min(x0 + w, size - 1),
                           min(y0 + h, size - 1)]
            cls[b, i] = rng.randint(0, num_classes)
            mask[b, i] = 1.0
    return (paddle.to_tensor(boxes), paddle.to_tensor(cls),
            paddle.to_tensor(mask))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="ppyoloe_s @ 640")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    if args.dp > 1:
        dist_env.build_mesh({"dp": args.dp})
    paddle.seed(0)

    if args.full:
        from paddle_tpu.vision.models import ppyoloe_s
        net = ppyoloe_s(num_classes=80, sync_bn=args.dp > 1)
        size, B = 640, 8 * args.dp
    else:
        net = PPYOLOE(PPYOLOEConfig(num_classes=4, width_mult=0.25,
                                    depth_mult=0.33, sync_bn=args.dp > 1))
        size, B = 64, 2 * args.dp

    opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        imgs = paddle.to_tensor(rng.rand(B, 3, size, size).astype("float32"))
        boxes, cls, mask = synth_dets(rng, B, size)
        loss = ppyoloe_loss(net, imgs, boxes, cls, mask)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
