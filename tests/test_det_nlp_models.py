"""PP-YOLOE + ERNIE model-zoo tests: forward shapes, loss decreases, PP
descs integrate with PipelineLayer (reference driver configs)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.text.models import (ErnieForPretraining,
                                    ErnieForSequenceClassification,
                                    ernie_pipeline_descs, ernie_tiny,
                                    ernie_tiny_config)
from paddle_tpu.vision.models import PPYOLOE, PPYOLOEConfig, ppyoloe_loss


def _tiny_det(sync_bn=False):
    return PPYOLOE(PPYOLOEConfig(num_classes=4, width_mult=0.25,
                                 depth_mult=0.33, sync_bn=sync_bn))


def test_ppyoloe_forward_shapes():
    m = _tiny_det()
    x = paddle.to_tensor(np.random.RandomState(0)
                         .rand(2, 3, 64, 64).astype("float32"))
    cls, reg = m(x)
    L = (64 // 8) ** 2 + (64 // 16) ** 2 + (64 // 32) ** 2
    assert list(cls.shape) == [2, L, 4]
    assert list(reg.shape) == [2, L, 4 * (16 + 1)]
    pts, strides = m.anchor_points((64, 64))
    assert pts.shape == (L, 2) and strides.shape == (L,)


def test_ppyoloe_loss_trains():
    paddle.seed(0)
    m = _tiny_det()
    o = opt.Adam(1e-3, parameters=m.parameters())
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.rand(2, 3, 64, 64).astype("float32"))
    gt_boxes = paddle.to_tensor(np.asarray(
        [[[8, 8, 40, 40], [0, 0, 0, 0]],
         [[16, 16, 56, 56], [4, 4, 20, 20]]], np.float32))
    gt_class = paddle.to_tensor(np.asarray([[1, 0], [2, 3]], np.int64))
    gt_mask = paddle.to_tensor(np.asarray([[1, 0], [1, 1]], np.float32))

    losses = []
    for _ in range(5):
        loss = ppyoloe_loss(m, x, gt_boxes, gt_class, gt_mask)
        loss.backward()
        o.step()
        o.clear_grad()
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_ppyoloe_sync_bn_variant():
    m = _tiny_det(sync_bn=True)
    x = paddle.to_tensor(np.ones((1, 3, 32, 32), np.float32))
    cls, reg = m(x)
    assert np.isfinite(cls.numpy()).all()


def test_ernie_forward_and_classification():
    cfg = ernie_tiny_config()
    m = ErnieForSequenceClassification(cfg, num_classes=3)
    ids = paddle.to_tensor(np.random.RandomState(0)
                           .randint(0, cfg.vocab_size, (2, 16)))
    logits = m(ids)
    assert list(logits.shape) == [2, 3]


def test_ernie_pretraining_loss_decreases():
    paddle.seed(1)
    cfg = ernie_tiny_config()
    m = ErnieForPretraining(cfg)
    o = opt.Adam(5e-4, parameters=m.parameters())
    rng = np.random.RandomState(2)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)))
    losses = []
    for _ in range(8):
        loss = m.loss(ids, labels)
        loss.backward()
        o.step()
        o.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3, losses


def test_ernie_pipeline_descs():
    from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
    cfg = ernie_tiny_config()
    descs = ernie_pipeline_descs(cfg)
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    assert pl.get_num_stages() == 2
    ids = paddle.to_tensor(np.random.RandomState(3)
                           .randint(0, cfg.vocab_size, (2, 8)))
    out = pl(ids)
    assert list(out.shape) == [2, 8, cfg.vocab_size]
