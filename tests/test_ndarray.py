"""NDArray basics (ref: tests/python/unittest/test_ndarray.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


def test_creation():
    a = nd.zeros((2, 3))
    assert a.shape == (2, 3)
    assert a.dtype == onp.float32
    b = nd.ones((2, 3))
    assert_almost_equal(b, onp.ones((2, 3)))
    c = nd.full((2, 2), 7.0)
    assert_almost_equal(c, onp.full((2, 2), 7.0))
    d = nd.array([[1, 2], [3, 4]])
    assert_almost_equal(d, [[1, 2], [3, 4]])
    e = nd.arange(0, 10, 2)
    assert_almost_equal(e, onp.arange(0, 10, 2, dtype=onp.float32))


def test_arithmetic():
    a = nd.array([[1., 2.], [3., 4.]])
    b = nd.array([[5., 6.], [7., 8.]])
    assert_almost_equal(a + b, [[6, 8], [10, 12]])
    assert_almost_equal(a - b, [[-4, -4], [-4, -4]])
    assert_almost_equal(a * b, [[5, 12], [21, 32]])
    assert_almost_equal(b / a, [[5, 3], [7 / 3, 2]], rtol=1e-6)
    assert_almost_equal(a + 1, [[2, 3], [4, 5]])
    assert_almost_equal(2 * a, [[2, 4], [6, 8]])
    assert_almost_equal(1 / a, [[1, .5], [1 / 3, .25]], rtol=1e-6)
    assert_almost_equal(a ** 2, [[1, 4], [9, 16]])
    assert_almost_equal(-a, [[-1, -2], [-3, -4]])


def test_inplace():
    a = nd.ones((2, 2))
    orig = a
    a += 1
    assert_almost_equal(orig, onp.full((2, 2), 2.0))
    a *= 3
    assert_almost_equal(orig, onp.full((2, 2), 6.0))


def test_comparisons():
    a = nd.array([1., 2., 3.])
    b = nd.array([2., 2., 2.])
    assert_almost_equal(a > b, [0, 0, 1])
    assert_almost_equal(a >= b, [0, 1, 1])
    assert_almost_equal(a == b, [0, 1, 0])
    assert_almost_equal(a != b, [1, 0, 1])


def test_indexing():
    a = nd.array(onp.arange(12).reshape(3, 4))
    assert_almost_equal(a[1], [4, 5, 6, 7])
    assert_almost_equal(a[1:3], [[4, 5, 6, 7], [8, 9, 10, 11]])
    assert a[2, 3].asscalar() == 11
    a[1] = 0
    assert_almost_equal(a[1], [0, 0, 0, 0])
    a[:] = 5
    assert_almost_equal(a, onp.full((3, 4), 5.0))


def test_shape_methods():
    a = nd.array(onp.arange(24).reshape(2, 3, 4))
    assert a.reshape(6, 4).shape == (6, 4)
    assert a.reshape((-1,)).shape == (24,)
    assert a.reshape(0, -1).shape == (2, 12)
    assert a.transpose().shape == (4, 3, 2)
    assert a.transpose(1, 0, 2).shape == (3, 2, 4)
    assert a.flatten().shape == (2, 12)
    assert a.expand_dims(0).shape == (1, 2, 3, 4)
    assert a.swapaxes(0, 2).shape == (4, 3, 2)
    assert nd.concat(a, a, dim=1).shape == (2, 6, 4)
    assert nd.stack(a, a, axis=0).shape == (2, 2, 3, 4)
    parts = a.split(3, axis=1)
    assert len(parts) == 3 and parts[0].shape == (2, 1, 4)


def test_reduce():
    a = nd.array(onp.arange(6).reshape(2, 3).astype(onp.float32))
    assert a.sum().asscalar() == 15
    assert_almost_equal(a.sum(axis=0), [3, 5, 7])
    assert_almost_equal(a.mean(axis=1), [1, 4])
    assert a.max().asscalar() == 5
    assert a.min().asscalar() == 0
    assert_almost_equal(a.argmax(axis=1), [2, 2])
    assert_almost_equal(nd.norm(a), onp.linalg.norm(onp.arange(6)))


def test_dot():
    a = onp.random.rand(3, 4).astype(onp.float32)
    b = onp.random.rand(4, 5).astype(onp.float32)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)), a.dot(b), rtol=1e-5)
    x = onp.random.rand(2, 3, 4).astype(onp.float32)
    y = onp.random.rand(2, 4, 5).astype(onp.float32)
    assert_almost_equal(nd.batch_dot(nd.array(x), nd.array(y)),
                        onp.matmul(x, y), rtol=1e-5)


def test_astype_copy():
    a = nd.array([1.5, 2.5])
    b = a.astype('int32')
    assert b.dtype == onp.int32
    c = a.copy()
    c += 1
    assert_almost_equal(a, [1.5, 2.5])


def test_topk_sort():
    a = nd.array([[3., 1., 2.], [6., 5., 4.]])
    idx = nd.topk(a, k=2)
    assert_almost_equal(idx, [[0, 2], [0, 1]])
    vals = nd.topk(a, k=2, ret_typ='value')
    assert_almost_equal(vals, [[3, 2], [6, 5]])
    assert_almost_equal(nd.sort(a), [[1, 2, 3], [4, 5, 6]])
    assert_almost_equal(nd.argsort(a), [[1, 2, 0], [2, 1, 0]])


def test_save_load(tmp_path):
    fname = str(tmp_path / 'arrs')
    a = nd.array([1., 2.])
    b = nd.array([[3.]])
    nd.save(fname, {'a': a, 'b': b})
    loaded = nd.load(fname)
    assert_almost_equal(loaded['a'], a)
    assert_almost_equal(loaded['b'], b)
    nd.save(fname, [a, b])
    la = nd.load(fname)
    assert_almost_equal(la[0], a)


def test_wait_to_read():
    a = nd.ones((10, 10))
    b = a * 2
    b.wait_to_read()
    nd.waitall()
    assert_almost_equal(b, onp.full((10, 10), 2.0))


def test_context():
    a = nd.ones((2, 2), ctx=mx.cpu(0))
    assert a.context.device_type in ('cpu', 'gpu')
    b = a.as_in_context(mx.cpu(0))
    assert_almost_equal(b, a)


def test_accelerator_context_without_an_accelerator_raises():
    """mx.tpu(0) / mx.gpu(0) name an accelerator: on a backend that has
    none they raise instead of quietly resolving to the CPU, and an
    array with no stated context reports the device jax put it on."""
    import pytest
    from mxnet_tpu.base import MXNetError
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(MXNetError, match='no accelerator'):
            ctx.jax_device()
        with pytest.raises(MXNetError, match='no accelerator'):
            nd.ones((2,), ctx=ctx)
    assert mx.cpu(0).jax_device().platform == 'cpu'
    assert (nd.ones((2,)) + 1).context == mx.cpu(0)


def test_one_hot_embedding_take():
    idx = nd.array([0, 2])
    oh = nd.one_hot(idx, depth=3)
    assert_almost_equal(oh, [[1, 0, 0], [0, 0, 1]])
    w = nd.array(onp.arange(12).reshape(4, 3).astype(onp.float32))
    emb = nd.embedding(idx, w)
    assert_almost_equal(emb, [[0, 1, 2], [6, 7, 8]])
    tk = nd.take(w, nd.array([1, 3]))
    assert_almost_equal(tk, [[3, 4, 5], [9, 10, 11]])
