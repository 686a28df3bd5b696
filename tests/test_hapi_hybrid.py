"""Model.fit beyond DP (VERDICT r2 next #10): tensor parallelism via GSPMD
param sharding and pipeline parallelism via the compiled 1F1B path, both
through the user-facing high-level API on the CPU mesh.

Reference: python/paddle/hapi/model.py:591-599 (static adapter runs fleet
strategies under Model.fit).
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.distributed.fleet.layers.mp_layers import (
    ColumnParallelLinear, RowParallelLinear)
from paddle_tpu.distributed.fleet.meta_parallel import (LayerDesc,
                                                        PipelineLayer)


@pytest.fixture
def clean_mesh():
    prev = dist_env.get_mesh()
    yield
    dist_env._global_mesh = prev


class TinyErnieBlock(nn.Layer):
    """ERNIE-style FFN block built from fleet mp layers (column->row)."""

    def __init__(self, hidden, ffn):
        super().__init__()
        self.ln = nn.LayerNorm(hidden)
        self.fc1 = ColumnParallelLinear(hidden, ffn, gather_output=False)
        self.act = nn.GELU()
        self.fc2 = RowParallelLinear(ffn, hidden, input_is_parallel=True)

    def forward(self, x):
        return x + self.fc2(self.act(self.fc1(self.ln(x))))


class TinyErnie(nn.Layer):
    def __init__(self, vocab=64, hidden=16, ffn=32, n_cls=4):
        super().__init__()
        self.emb = nn.Embedding(vocab, hidden)
        self.b1 = TinyErnieBlock(hidden, ffn)
        self.b2 = TinyErnieBlock(hidden, ffn)
        self.head = nn.Linear(hidden, n_cls)

    def forward(self, ids):
        h = self.emb(ids)
        h = self.b2(self.b1(h))
        return self.head(h.mean(axis=1))


def _ernie_losses(n_steps=4):
    paddle.seed(5)
    net = TinyErnie()
    m = paddle.Model(net)
    m.prepare(opt.Adam(1e-2, parameters=net.parameters()),
              nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(n_steps):
        x = rng.randint(0, 64, (8, 12))
        y = rng.randint(0, 4, 8)
        (l,), _ = m.train_batch([x], [y])
        losses.append(l)
    return losses


def test_model_fit_mp_matches_single_device(clean_mesh):
    """ERNIE-tiny with mp layers: dp=2 x mp=4 GSPMD fit == single device."""
    dist_env.build_mesh({"dp": 2, "mp": 4})
    mp_losses = _ernie_losses()
    dist_env._global_mesh = None
    single = _ernie_losses()
    np.testing.assert_allclose(mp_losses, single, rtol=5e-4, atol=1e-5)


def test_model_fit_mp_params_really_sharded(clean_mesh):
    mesh = dist_env.build_mesh({"dp": 2, "mp": 4})
    paddle.seed(1)
    net = TinyErnie()
    m = paddle.Model(net)
    m.prepare(opt.SGD(0.1, parameters=net.parameters()),
              nn.CrossEntropyLoss())
    x = np.random.RandomState(1).randint(0, 64, (8, 12))
    y = np.random.RandomState(2).randint(0, 4, 8)
    m.train_batch([x], [y])
    w = dict(net.named_parameters())["b1.fc1.weight"]
    # after a sharded step the updated param carries the mp sharding
    shards = w._data.sharding
    assert "mp" in str(shards.spec), shards
    np.testing.assert_equal(
        len({s.device for s in w._data.addressable_shards}), 8)


def test_model_fit_pp_pipeline_layer(clean_mesh):
    """PipelineLayer through Model.fit: pp=2 x dp=4 compiled 1F1B matches
    the same network trained unpipelined."""
    dist_env.build_mesh({"dp": 4, "pp": 2})
    paddle.seed(7)
    descs = [LayerDesc(nn.Linear, 12, 32), LayerDesc(nn.ReLU),
             LayerDesc(nn.Linear, 32, 32), LayerDesc(nn.ReLU),
             LayerDesc(nn.Linear, 32, 4)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    m = paddle.Model(pl)
    m.prepare(opt.SGD(0.1, parameters=pl.parameters()),
              nn.CrossEntropyLoss(), strategy={"microbatches": 4})

    golden = nn.Sequential(nn.Linear(12, 32), nn.ReLU(),
                           nn.Linear(32, 32), nn.ReLU(), nn.Linear(32, 4))
    for gp, pp_ in zip(golden.parameters(), pl.parameters()):
        gp._data = pp_._data
    o_g = opt.SGD(0.1, parameters=golden.parameters())
    lf = nn.CrossEntropyLoss()

    rng = np.random.RandomState(3)
    for _ in range(3):
        x = rng.rand(16, 12).astype("float32")
        y = rng.randint(0, 4, 16)
        (l_pp,), _ = m.train_batch([x], [y])
        l_g = lf(golden(paddle.to_tensor(x)), paddle.to_tensor(y))
        l_g.backward()
        o_g.step()
        o_g.clear_grad()
        np.testing.assert_allclose(l_pp, float(l_g), rtol=2e-5, atol=1e-6)


def test_model_fit_mp_x_pp_parity(clean_mesh):
    """VERDICT r3 item 5: mp=2 x pp=2 through Model.fit — a pipeline whose
    stages contain fleet mp layers (Column/RowParallelLinear) trains with
    loss parity vs the single-device golden."""
    dist_env.build_mesh({"pp": 2, "mp": 2})
    paddle.seed(11)
    descs = [LayerDesc(nn.Linear, 12, 16),
             LayerDesc(TinyErnieBlock, 16, 32),
             LayerDesc(TinyErnieBlock, 16, 32),
             LayerDesc(nn.Linear, 16, 4)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    m = paddle.Model(pl)
    m.prepare(opt.SGD(0.1, parameters=pl.parameters()),
              nn.CrossEntropyLoss(), strategy={"microbatches": 2})

    # golden: same weights, whole stack serial on one device, no mesh
    paddle.seed(11)
    golden = PipelineLayer(
        [LayerDesc(nn.Linear, 12, 16), LayerDesc(TinyErnieBlock, 16, 32),
         LayerDesc(TinyErnieBlock, 16, 32), LayerDesc(nn.Linear, 16, 4)],
        num_stages=2, loss_fn=nn.CrossEntropyLoss())
    for gp, pp_ in zip(golden.parameters(), pl.parameters()):
        gp._data = pp_._data
    o_g = opt.SGD(0.1, parameters=golden.parameters())
    lf = nn.CrossEntropyLoss()

    rng = np.random.RandomState(9)
    for _ in range(3):
        x = rng.rand(8, 12).astype("float32")
        y = rng.randint(0, 4, 8)
        (l_pp,), _ = m.train_batch([x], [y])
        l_g = lf(golden(paddle.to_tensor(x)), paddle.to_tensor(y))
        l_g.backward()
        o_g.step()
        o_g.clear_grad()
        np.testing.assert_allclose(l_pp, float(l_g), rtol=2e-4, atol=1e-5)


def test_model_fit_mp_x_pp_x_dp_parity(clean_mesh):
    """Full hybrid: dp=2 x pp=2 x mp=2 over the 8-device mesh via Model.fit."""
    dist_env.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    paddle.seed(13)
    descs = [LayerDesc(nn.Linear, 12, 16),
             LayerDesc(TinyErnieBlock, 16, 32),
             LayerDesc(nn.Linear, 16, 4)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    m = paddle.Model(pl)
    m.prepare(opt.SGD(0.1, parameters=pl.parameters()),
              nn.CrossEntropyLoss(), strategy={"microbatches": 2})

    paddle.seed(13)
    golden = PipelineLayer(
        [LayerDesc(nn.Linear, 12, 16), LayerDesc(TinyErnieBlock, 16, 32),
         LayerDesc(nn.Linear, 16, 4)],
        num_stages=2, loss_fn=nn.CrossEntropyLoss())
    for gp, pp_ in zip(golden.parameters(), pl.parameters()):
        gp._data = pp_._data
    o_g = opt.SGD(0.1, parameters=golden.parameters())
    lf = nn.CrossEntropyLoss()

    rng = np.random.RandomState(17)
    for _ in range(2):
        x = rng.rand(8, 12).astype("float32")
        y = rng.randint(0, 4, 8)
        (l_pp,), _ = m.train_batch([x], [y])
        l_g = lf(golden(paddle.to_tensor(x)), paddle.to_tensor(y))
        l_g.backward()
        o_g.step()
        o_g.clear_grad()
        np.testing.assert_allclose(l_pp, float(l_g), rtol=2e-4, atol=1e-5)


def test_pipeline_bn_buffers_written_back(clean_mesh):
    """BN running stats update through the compiled pipeline (previously a
    documented limitation): per-microbatch sequential updates, merged
    across stages, matching the serial per-microbatch golden."""
    import jax.numpy as jnp
    from paddle_tpu.distributed.fleet.meta_parallel.pp_compiled import \
        make_compiled_pipeline_step
    from paddle_tpu.nn.layer.layers import functional_call, functional_state

    dist_env.build_mesh({"pp": 2})
    paddle.seed(31)
    descs = [LayerDesc(nn.Linear, 6, 8), LayerDesc(nn.BatchNorm1D, 8),
             LayerDesc(nn.ReLU), LayerDesc(nn.Linear, 8, 8),
             LayerDesc(nn.BatchNorm1D, 8), LayerDesc(nn.Linear, 8, 3)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    mesh = dist_env.get_mesh()
    M = 2
    step = make_compiled_pipeline_step(pl, mesh, microbatches=M)
    params, buffers = functional_state(pl)
    rng = np.random.RandomState(0)
    x = rng.rand(8, 6).astype("float32")
    y = rng.randint(0, 3, 8)
    loss, grads, new_buffers = step(params, buffers, x, y)

    # serial golden: run the SAME per-microbatch sequence through the whole
    # stack, threading buffers between microbatches
    g_buf = dict(buffers)
    for m in range(M):
        _, g_buf = functional_call(
            pl, params, g_buf, args=(paddle.to_tensor(x[m * 4:(m + 1) * 4]),),
            train=True)
    changed = 0
    for n in new_buffers:
        got = np.asarray(new_buffers[n])
        want = np.asarray(g_buf[n])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                   err_msg=n)
        if not np.allclose(got, np.asarray(buffers[n])):
            changed += 1
    assert changed >= 2          # both stages' BN stats really moved


def test_pipeline_buffer_dependent_forward_grads(clean_mesh):
    """A stage whose FORWARD reads a buffer it also updates (SpectralNorm /
    QAT-scale pattern): the backward recompute must replay with the exact
    buffer snapshot the forward used, so grads match the serial
    per-microbatch golden."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.fleet.meta_parallel.pp_compiled import \
        make_compiled_pipeline_step
    from paddle_tpu.nn.layer.layers import functional_call, functional_state

    class ScaleDrift(nn.Layer):
        """out = x * scale; scale drifts each train forward."""

        def __init__(self, dim):
            super().__init__()
            self.lin = nn.Linear(dim, dim)
            self.register_buffer("scale", paddle.to_tensor(
                np.ones((1,), "float32")))

        def forward(self, x):
            out = self.lin(x) * self.scale
            if self.training:
                self.scale._data = self.scale._data * 1.1
            return out

    dist_env.build_mesh({"pp": 2})
    paddle.seed(41)
    descs = [LayerDesc(ScaleDrift, 6), LayerDesc(nn.ReLU),
             LayerDesc(ScaleDrift, 6), LayerDesc(nn.Linear, 6, 3)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    mesh = dist_env.get_mesh()
    M = 2
    step = make_compiled_pipeline_step(pl, mesh, microbatches=M)
    params, buffers = functional_state(pl)
    rng = np.random.RandomState(1)
    x = rng.rand(8, 6).astype("float32")
    y = rng.randint(0, 3, 8)
    loss, grads, new_buffers = step(params, buffers, x, y)

    # serial golden: per-microbatch value_and_grad threading buffers
    lf = nn.CrossEntropyLoss()
    g_buf = dict(buffers)
    tot_loss, tot_grads = 0.0, None
    for m in range(M):
        xm = paddle.to_tensor(x[m * 4:(m + 1) * 4])
        ym = paddle.to_tensor(y[m * 4:(m + 1) * 4])

        def loss_fn(p, bufs):
            out, nb = functional_call(pl, p, bufs, args=(xm,), train=True)
            return lf(out, ym)._data, nb

        (l_m, g_buf), g_m = jax.value_and_grad(loss_fn, has_aux=True)(
            params, g_buf)
        tot_loss += float(l_m) / M
        tot_grads = g_m if tot_grads is None else \
            {n: tot_grads[n] + g_m[n] for n in g_m}
    np.testing.assert_allclose(float(loss), tot_loss, rtol=1e-5)
    for n, g in grads.items():
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(tot_grads[n]) / M,
            rtol=1e-4, atol=1e-5, err_msg=n)
    # buffer write-back matches the serial sequence too
    for n in new_buffers:
        np.testing.assert_allclose(np.asarray(new_buffers[n]),
                                   np.asarray(g_buf[n]), rtol=1e-5,
                                   err_msg=n)


def test_pipeline_shared_layer_with_buffers_rejected(clean_mesh):
    from paddle_tpu.distributed.fleet.meta_parallel import SharedLayerDesc
    from paddle_tpu.distributed.fleet.meta_parallel.pp_compiled import \
        make_compiled_pipeline_step

    dist_env.build_mesh({"pp": 2})
    paddle.seed(43)
    descs = [SharedLayerDesc("tiedbn", nn.BatchNorm1D, forward_func=None,
                             shared_weight_attr="weight", num_features=6),
             LayerDesc(nn.Linear, 6, 6), LayerDesc(nn.ReLU),
             SharedLayerDesc("tiedbn", nn.BatchNorm1D, forward_func=None,
                             shared_weight_attr="weight", num_features=6)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.MSELoss())
    with pytest.raises(ValueError, match="shared across pipeline stages"):
        make_compiled_pipeline_step(pl, dist_env.get_mesh(), microbatches=2)


def test_sync_batch_norm_shard_map_grads(clean_mesh):
    """SyncBatchNorm inside a dp-live shard_map: stats AND grads must equal
    the full-batch single-device BN. Pins the RAW lax.pmean in the stat
    path: its psum-based transpose SUMS the distinct per-rank stat
    cotangents, which is correct under dp-sharded losses (an mp-style
    identity-backward collective here would drop cross-rank terms — see
    norm.py's comment)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer.layers import functional_call, functional_state

    mesh = dist_env.build_mesh({"dp": 2})
    paddle.seed(3)
    net = nn.Sequential(nn.Linear(6, 4), nn.SyncBatchNorm(4),
                        nn.Linear(4, 2))
    params, buffers = functional_state(net)
    x = np.random.RandomState(0).rand(8, 6).astype("float32")

    def loss_local(p, xx):
        with dist_env.axis_context(dp="dp"):
            out, _ = functional_call(net, p, buffers, args=(Tensor(xx),),
                                     train=True)
        return jnp.sum(out._data ** 2)

    g = jax.jit(jax.shard_map(
        lambda p, xx: jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, "dp"),
            jax.grad(loss_local)(p, xx)),
        mesh=mesh, in_specs=(P(), P("dp")), out_specs=P(),
        check_vma=False))(params, x)

    # golden: full batch on one device (plain BN == synced sharded stats)
    t = Tensor(jnp.asarray(x))
    out = net(t)
    (out ** 2).sum().backward()
    for n, p in net.named_parameters():
        # sharded loss is a sum of per-rank sums; pmean of grads = grad/2
        np.testing.assert_allclose(2 * np.asarray(g[n]), p.grad.numpy(),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_pipeline_sync_bn_stats(clean_mesh):
    """SyncBatchNorm inside a dp=2 x pp=2 compiled pipeline: dp is marked
    live, so stats sync across replicas and the written-back buffers match
    the serial full-microbatch golden."""
    from paddle_tpu.distributed.fleet.meta_parallel.pp_compiled import \
        make_compiled_pipeline_step
    from paddle_tpu.nn.layer.layers import functional_call, functional_state

    dist_env.build_mesh({"dp": 2, "pp": 2})
    paddle.seed(47)
    descs = [LayerDesc(nn.Linear, 6, 8), LayerDesc(nn.SyncBatchNorm, 8),
             LayerDesc(nn.ReLU), LayerDesc(nn.Linear, 8, 3)]
    pl = PipelineLayer(descs, num_stages=2, loss_fn=nn.CrossEntropyLoss())
    mesh = dist_env.get_mesh()
    M = 2
    step = make_compiled_pipeline_step(pl, mesh, microbatches=M)
    params, buffers = functional_state(pl)
    rng = np.random.RandomState(2)
    x = rng.rand(8, 6).astype("float32")
    y = rng.randint(0, 3, 8)
    loss, grads, new_buffers = step(params, buffers, x, y)

    # serial golden: full microbatches through the stack (eager SyncBN
    # falls back to plain BN == dp-synced sharded stats). NB microbatch m
    # is the UNION of each dp shard's m-th slice (the batch dim shards
    # over dp first, then microbatches within each shard).
    g_buf = dict(buffers)
    for m in range(M):
        xm = np.concatenate([x[r * 4 + m * 2: r * 4 + (m + 1) * 2]
                             for r in range(2)])
        _, g_buf = functional_call(
            pl, params, g_buf, args=(paddle.to_tensor(xm),), train=True)
    for n in new_buffers:
        np.testing.assert_allclose(np.asarray(new_buffers[n]),
                                   np.asarray(g_buf[n]), rtol=1e-4,
                                   atol=1e-5, err_msg=n)


def test_row_parallel_input_split_grads(clean_mesh):
    """RowParallelLinear(input_is_parallel=False): the input split must be
    transpose-safe (_c_split_manual) — upstream replicated params get the
    FULL recombined cotangent, not per-rank partials."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer.layers import functional_call, functional_state

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.pre = nn.Linear(8, 8)          # replicated upstream layer
            self.row = RowParallelLinear(8, 4, input_is_parallel=False)

        def forward(self, x):
            return self.row(self.pre(x))

    mesh = dist_env.build_mesh({"mp": 2})
    paddle.seed(2)
    net = Net()
    params, buffers = functional_state(net)
    x = np.random.RandomState(0).rand(4, 8).astype("float32")

    def loss_local(p, xx):
        with dist_env.axis_context(mp="mp"):
            out, _ = functional_call(net, p, buffers, args=(Tensor(xx),),
                                     train=True)
        return jnp.sum(out._data ** 2)

    specs = {"pre.weight": P(), "pre.bias": P(),
             "row.weight": P("mp", None), "row.bias": P()}
    g = jax.jit(jax.shard_map(
        lambda p, xx: jax.grad(loss_local)(p, xx), mesh=mesh,
        in_specs=(specs, P()), out_specs=specs, check_vma=False))(params, x)

    t = Tensor(jnp.asarray(x))
    out = net(t)
    (out ** 2).sum().backward()
    for n, p in net.named_parameters():
        np.testing.assert_allclose(np.asarray(g[n]), p.grad.numpy(),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_model_fit_ernie_tiny_pipeline(clean_mesh):
    """The reference's 'ERNIE mp+pp' configuration through the user-facing API: ERNIE-tiny
    as a PipelineLayer (tied embeddings across first/last stage) trained by
    Model.fit over a pp=2 x dp=2 mesh, loss matching the unpipelined run."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
    from paddle_tpu.text.models.ernie import (ernie_pipeline_descs,
                                              ernie_tiny_config)

    dist_env.build_mesh({"dp": 2, "pp": 2, "mp": 2})

    def mlm_loss(logits, labels):
        return F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                               labels.reshape([-1]))

    cfg = ernie_tiny_config(hidden_dropout_prob=0.0,
                            attention_probs_dropout_prob=0.0)
    paddle.seed(21)
    descs = ernie_pipeline_descs(cfg, loss_fn=mlm_loss)
    pl = PipelineLayer(descs, num_stages=2, loss_fn=mlm_loss)
    m = paddle.Model(pl)
    m.prepare(opt.SGD(0.05, parameters=pl.parameters()),
              None, strategy={"microbatches": 2})

    # golden: identical weights, plain forward (PipelineLayer.forward runs
    # the whole stack serially)
    paddle.seed(21)
    golden = PipelineLayer(ernie_pipeline_descs(cfg, loss_fn=mlm_loss),
                           num_stages=2, loss_fn=mlm_loss)
    for gp, pp_ in zip(golden.parameters(), pl.parameters()):
        gp._data = pp_._data
    o_g = opt.SGD(0.05, parameters=golden.parameters())

    rng = np.random.RandomState(5)
    for _ in range(2):
        ids = rng.randint(0, cfg.vocab_size, (8, 16))
        labs = rng.randint(0, cfg.vocab_size, (8, 16))
        (l_pp,), _ = m.train_batch([ids], [labs])
        l_g = mlm_loss(golden(paddle.to_tensor(ids)),
                       paddle.to_tensor(labs))
        l_g.backward()
        o_g.step()
        o_g.clear_grad()
        np.testing.assert_allclose(l_pp, float(l_g), rtol=5e-4, atol=1e-5)
