"""Model.fit auto data parallelism (VERDICT r1 item 7; the reference's "BERT-base
DP over 8 cores via the high-level API").

Reference: hapi/model.py:190 wraps the network in DataParallel and feeds a
DistributedBatchSampler. TPU-native: when a global mesh with a 'dp' axis is
installed, Model's jit-compiled train step shards the batch over 'dp' via
in_shardings and the GSPMD partitioner inserts the gradient all-reduce —
numerically identical to single-device training.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.text.models import Bert, BertConfig


@pytest.fixture
def dp_mesh():
    prev = dist_env.get_mesh()
    mesh = dist_env.build_mesh({"dp": 8})
    yield mesh
    dist_env._global_mesh = prev


def _mlp_losses(n_steps=4, batch=16):
    paddle.seed(3)
    net = nn.Sequential(nn.Flatten(), nn.Linear(12, 32), nn.ReLU(),
                        nn.Linear(32, 4))
    m = paddle.Model(net)
    m.prepare(opt.Adam(1e-2, parameters=net.parameters()),
              nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(n_steps):
        x = rng.rand(batch, 12).astype("float32")
        y = rng.randint(0, 4, batch)
        (l,), _ = m.train_batch([x], [y])
        losses.append(l)
    return losses


def test_model_fit_dp_matches_single_device(dp_mesh):
    dp_losses = _mlp_losses()
    dist_env._global_mesh = None
    single = _mlp_losses()
    np.testing.assert_allclose(dp_losses, single, rtol=2e-5, atol=1e-6)


def test_model_dp_step_is_really_sharded(dp_mesh):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 4))
    m = paddle.Model(net)
    m.prepare(opt.SGD(0.1, parameters=net.parameters()),
              nn.CrossEntropyLoss())
    x = np.random.rand(16, 8).astype("float32")
    y = np.random.randint(0, 4, 16)
    m.train_batch([x], [y])
    assert m._dp_mesh() is dp_mesh          # the sharded step was built


def test_model_dp_ragged_batch_falls_back(dp_mesh):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 4))
    m = paddle.Model(net)
    m.prepare(opt.SGD(0.1, parameters=net.parameters()),
              nn.CrossEntropyLoss())
    for b in (16, 13):                      # 13 % 8 != 0 -> replicated path
        x = np.random.rand(b, 8).astype("float32")
        y = np.random.randint(0, 4, b)
        (l,), _ = m.train_batch([x], [y])
        assert np.isfinite(l)
    assert m._train_step_plain is not None


def test_bert_tiny_fit_dp8(dp_mesh):
    """The reference's BERT row (tiny config) trains DP x 8 through Model.fit."""
    paddle.seed(5)
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=32)

    class BertCls(nn.Layer):
        def __init__(self):
            super().__init__()
            self.bert = Bert(cfg)
            self.head = nn.Linear(32, 2)

        def forward(self, ids):
            seq, pooled = self.bert(ids)
            return self.head(pooled)

    net = BertCls()
    m = paddle.Model(net)
    m.prepare(opt.Adam(1e-3, parameters=net.parameters()),
              nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(3):
        ids = rng.randint(0, 128, (16, 16))
        y = rng.randint(0, 2, 16)
        (l,), _ = m.train_batch([ids], [y])
        losses.append(l)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] + 0.5     # training, not diverging


def test_reduce_lr_on_plateau_callback():
    """hapi ReduceLROnPlateau (reference hapi/callbacks.py): flat metric
    shrinks the LR every `patience` epochs; improvement resets the wait."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.hapi.callbacks import ReduceLROnPlateau

    net = paddle.nn.Linear(4, 2)
    m = paddle.Model(net)
    o = opt.SGD(0.1, parameters=net.parameters())
    m.prepare(o, paddle.nn.CrossEntropyLoss())
    cb = ReduceLROnPlateau(patience=1, factor=0.5, verbose=0)
    cb.model = m
    cb.on_train_begin()
    cb.on_epoch_end(0, {"loss": 1.0})          # sets best
    cb.on_epoch_end(1, {"loss": 1.0})          # plateau -> 0.05
    assert abs(o.get_lr() - 0.05) < 1e-9
    cb.on_epoch_end(2, {"loss": 0.5})          # improvement resets wait
    cb.on_epoch_end(3, {"loss": 0.5})          # plateau -> 0.025
    assert abs(o.get_lr() - 0.025) < 1e-9
