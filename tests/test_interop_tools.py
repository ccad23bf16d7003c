"""torch bridge, contrib.text, tensorboard callback, launch.py tests
(ref: reference torch plugin tests, tests/python/unittest/test_contrib_text.py,
tools/launch.py usage in ci/docker/runtime_functions.sh)."""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import assert_almost_equal


def test_torch_tensor_conversion():
    a = nd.array(onp.random.rand(3, 4).astype(onp.float32))
    t = mx.torch.to_torch(a)
    assert tuple(t.shape) == (3, 4)
    back = mx.torch.from_torch(t)
    assert_almost_equal(back, a.asnumpy())


def test_torch_op_gradients_match_torch_autograd():
    import torch as real_torch
    real_torch.manual_seed(0)
    lin = real_torch.nn.Linear(4, 2)
    op = mx.torch.TorchOp(lin)
    x_np = onp.random.rand(3, 4).astype(onp.float32)
    x = nd.array(x_np)
    x.attach_grad()
    with autograd.record():
        y = op(x)
        loss = (y * y).sum()
    loss.backward()
    tx = real_torch.from_numpy(x_np.copy())
    tx.requires_grad_(True)
    ty = lin(tx)
    (ty * ty).sum().backward()
    assert_almost_equal(y, lin(real_torch.from_numpy(x_np)).detach().numpy(),
                        rtol=1e-5, atol=1e-6)
    assert_almost_equal(x.grad, tx.grad.numpy(), rtol=1e-4, atol=1e-5)


def test_torch_op_inside_gluon_model():
    import torch as real_torch
    from mxnet_tpu import gluon
    torch_mid = mx.torch.TorchOp(real_torch.nn.Tanh())

    class Net(gluon.Block):
        def __init__(self):
            super().__init__()
            self.fc1 = gluon.nn.Dense(8)
            self.fc2 = gluon.nn.Dense(2)

        def forward(self, x):
            return self.fc2(torch_mid(self.fc1(x)))

    net = Net()
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    x = nd.array(onp.random.rand(4, 3).astype(onp.float32))
    y = nd.array(onp.array([0, 1, 0, 1], onp.float32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    trainer.step(4)  # no error and params move
    assert all(onp.isfinite(p.data().asnumpy()).all()
               for p in net.collect_params().values())


def test_vocabulary():
    from mxnet_tpu.contrib import text
    c = text.count_tokens_from_str("a b b c c c")
    v = text.Vocabulary(c, min_freq=2)
    assert len(v) == 3  # <unk>, c, b
    assert v.to_indices('c') == 1
    assert v.to_indices('missing') == 0
    assert v.to_tokens([1, 2]) == ['c', 'b']
    with pytest.raises(ValueError):
        v.to_tokens(99)
    v2 = text.Vocabulary(c, reserved_tokens=['<pad>'])
    assert v2.to_indices('<pad>') == 1


def test_custom_embedding(tmp_path):
    from mxnet_tpu.contrib import text
    f = tmp_path / 'emb.txt'
    f.write_text("hello 0.1 0.2\nworld 0.3 0.4\n")
    emb = text.CustomEmbedding(str(f))
    assert emb.vec_len == 2
    assert_almost_equal(emb.get_vecs_by_tokens('world'),
                        onp.array([0.3, 0.4], onp.float32))
    # unknown token → zeros (index 0)
    assert_almost_equal(emb.get_vecs_by_tokens('zzz'),
                        onp.zeros(2, onp.float32))
    emb.update_token_vectors('hello', nd.array([[9.0, 9.0]]))
    assert_almost_equal(emb.get_vecs_by_tokens('hello'),
                        onp.array([9.0, 9.0], onp.float32))


def test_tensorboard_callback(tmp_path):
    from mxnet_tpu.contrib.tensorboard import (LogMetricsCallback,
                                               JSONLWriter)
    from mxnet_tpu import metric as metric_mod

    class P:
        pass

    p = P()
    p.eval_metric = metric_mod.Accuracy()
    p.eval_metric.update(nd.array([0.0, 1.0]),
                         nd.array([[0.9, 0.1], [0.2, 0.8]]))
    # force the JSONL fallback so the test is hermetic
    w = JSONLWriter(str(tmp_path))
    cb = LogMetricsCallback(summary_writer=w, prefix='train')
    cb(p)
    content = (tmp_path / 'scalars.jsonl').read_text()
    assert 'train-accuracy' in content


def test_launch_local_two_workers(tmp_path):
    """tools/launch.py local launcher: 2 CPU processes do a psum
    (SURVEY §4: distributed tests as multiple local processes)."""
    worker = tmp_path / 'worker.py'
    worker.write_text(
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "import jax.numpy as jnp\n"
        "total = jax.process_count()\n"
        "assert total == 2, total\n"
        f"open(r'{tmp_path}/rank' + str(dist.rank()), 'w')"
        ".write(str(total))\n")
    env = dict(os.environ)
    env['PYTHONPATH'] = '/root/repo'
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run(
        [sys.executable, '/root/repo/tools/launch.py', '-n', '2',
         '-p', '29511', sys.executable, str(worker)],
        env=env, timeout=180, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert (tmp_path / 'rank0').read_text() == '2'
    assert (tmp_path / 'rank1').read_text() == '2'


def test_launch_local_refuses_to_share_tpu_chips(monkeypatch):
    """On a host with TPU chips every TPU process opens all of them, so
    n local workers cannot share it: launch_local refuses unless the
    workers are pinned to the CPU, and starts nothing."""
    import pytest
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import dist
    monkeypatch.setattr(dist, '_local_tpu_chips', lambda: 4)
    monkeypatch.setattr(dist.subprocess, 'Popen', lambda *a, **k: (
        _ for _ in ()).throw(AssertionError('a worker was started')))
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    with pytest.raises(MXNetError, match='One process drives all local'):
        dist.launch_local(['train.py'], n=4)
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    with pytest.raises(AssertionError, match='a worker was started'):
        dist.launch_local(['train.py'], n=4)     # pinned to CPU: allowed


def test_launch_multiprocess_dp_training(tmp_path):
    """2-process x 4-device DP training: params broadcast from rank 0,
    gradient allreduce spans processes, both ranks converge identically
    (ref: SURVEY §2.5 multi-host data parallel; kvstore init broadcast)."""
    worker = tmp_path / 'trainer.py'
    worker.write_text(
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd, gluon\n"
        "from mxnet_tpu.parallel import make_mesh, ShardedTrainStep\n"
        "assert jax.device_count() == 8\n"
        "mesh = make_mesh((8,), ('dp',))\n"
        "net = gluon.nn.HybridSequential()\n"
        "net.add(gluon.nn.Dense(16, activation='relu'), gluon.nn.Dense(2))\n"
        "net.initialize(mx.init.Xavier())\n"
        "loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()\n"
        "step = ShardedTrainStep(net, loss_fn, 'sgd',\n"
        "                        {'learning_rate': 0.1}, mesh=mesh)\n"
        "rng = onp.random.RandomState(dist.rank())  # different data/rank\n"
        "X = rng.randn(32, 8).astype(onp.float32)\n"
        "Y = (X.sum(1) > 0).astype(onp.float32)\n"
        "first = last = None\n"
        "for i in range(15):\n"
        "    v = float(step(nd.array(X), nd.array(Y)).asnumpy())\n"
        "    first = v if first is None else first\n"
        "    last = v\n"
        "assert last < first, (first, last)\n"
        f"open(r'{tmp_path}/loss' + str(dist.rank()), 'w')"
        ".write(f'{last:.6f}')\n")
    env = dict(os.environ)
    env['PYTHONPATH'] = '/root/repo'
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run(
        [sys.executable, '/root/repo/tools/launch.py', '-n', '2',
         '-p', '29531', sys.executable, str(worker)],
        env=env, timeout=240, capture_output=True, text=True)
    if "aren't implemented on the CPU backend" in r.stderr:
        pytest.skip("this jaxlib's CPU backend lacks multiprocess "
                    "collectives (cross-process gloo/mpi support landed "
                    "in a later jaxlib)")
    assert r.returncode == 0, r.stderr[-2000:]
    # synchronized training: the global loss is identical on every rank
    assert (tmp_path / 'loss0').read_text() == (tmp_path / 'loss1').read_text()
