"""Reference configuration 1: ResNet / CIFAR-10 via `Model.fit` on one TPU chip.

Reference UX: python/paddle/hapi/model.py Model.fit + vision zoo
(python/paddle/vision/models/resnet.py). Run:

    python examples/resnet_cifar10.py              # tiny smoke (any backend)
    python examples/resnet_cifar10.py --full       # resnet50, chip-sized
    python examples/resnet_cifar10.py --data cifar-10-python.tar.gz
                                # train on the real archive (reference format)

Without --data, trains on synthetic CIFAR-shaped data (zero-egress env).
"""
import argparse

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="resnet50 + real batch size")
    ap.add_argument("--data", default=None,
                    help="path to cifar-10-python.tar.gz (reference format)")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    paddle.seed(0)

    from paddle_tpu.vision.models import resnet18, resnet50
    net = resnet50(num_classes=10) if args.full else resnet18(num_classes=10)
    batch = args.batch or (256 if args.full else 16)

    if args.data:
        from paddle_tpu.vision.datasets import Cifar10
        train = Cifar10(args.data, mode="train")
    else:
        from paddle_tpu.vision.datasets import FakeData
        train = FakeData(batch * (8 if args.full else 2), (3, 32, 32), 10)

    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Momentum(0.1, momentum=0.9,
                                  parameters=net.parameters()),
        nn.CrossEntropyLoss(),
        paddle.metric.Accuracy())
    model.fit(train, batch_size=batch, epochs=args.epochs, verbose=1)


if __name__ == "__main__":
    main()
