"""Core tensor API tests (reference pattern: unittests/test_var_base.py,
test_math_op_patch.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def test_to_tensor_basics():
    t = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]])
    assert t.shape == [2, 2]
    assert t.dtype == paddle.float32
    np.testing.assert_array_equal(t.numpy(), [[1, 2], [3, 4]])
    assert t.size == 4
    assert t.ndim == 2


def test_dtype_conversion():
    t = paddle.to_tensor([1, 2, 3])
    f = t.astype("float32")
    assert f.dtype == paddle.float32
    assert t.astype(paddle.float16).dtype == paddle.float16


def test_operator_overloads():
    a = paddle.to_tensor([1.0, 2.0])
    b = paddle.to_tensor([3.0, 4.0])
    np.testing.assert_allclose((a + b).numpy(), [4, 6])
    np.testing.assert_allclose((a - b).numpy(), [-2, -2])
    np.testing.assert_allclose((a * b).numpy(), [3, 8])
    np.testing.assert_allclose((b / a).numpy(), [3, 2])
    np.testing.assert_allclose((a ** 2).numpy(), [1, 4])
    np.testing.assert_allclose((2 + a).numpy(), [3, 4])
    np.testing.assert_allclose((-a).numpy(), [-1, -2])
    assert bool((a < b).all())
    assert bool((a == a).all())


def test_matmul_overload():
    a = paddle.ones([2, 3])
    b = paddle.ones([3, 4])
    assert (a @ b).shape == [2, 4]


def test_indexing():
    t = paddle.arange(12).reshape([3, 4])
    assert t[0].shape == [4]
    assert t[0, 1].item() == 1
    assert t[:, 1:3].shape == [3, 2]
    assert t[paddle.to_tensor([0, 2])].shape == [2, 4]
    bool_idx = t > 5
    t2 = t.clone()
    t2[0] = 99
    assert int(t2[0, 0]) == 99


def test_creation_ops():
    assert paddle.zeros([2, 3]).shape == [2, 3]
    assert paddle.ones([2], "int32").dtype == paddle.int32
    assert paddle.full([2, 2], 7.0).numpy().tolist() == [[7, 7], [7, 7]]
    assert paddle.arange(0, 10, 2).shape == [5]
    assert paddle.linspace(0, 1, 5).shape == [5]
    assert paddle.eye(3).numpy().trace() == 3
    x = paddle.ones([2, 2])
    assert paddle.zeros_like(x).numpy().sum() == 0
    assert paddle.tril(paddle.ones([3, 3])).numpy().sum() == 6


def test_manipulation():
    t = paddle.arange(24).reshape([2, 3, 4])
    assert t.transpose([2, 0, 1]).shape == [4, 2, 3]
    assert t.flatten().shape == [24]
    assert t.flatten(1).shape == [2, 12]
    assert paddle.concat([t, t], axis=1).shape == [2, 6, 4]
    assert paddle.stack([t, t]).shape == [2, 2, 3, 4]
    parts = paddle.split(t, 3, axis=1)
    assert len(parts) == 3 and parts[0].shape == [2, 1, 4]
    parts = paddle.split(t, [1, -1], axis=1)
    assert parts[1].shape == [2, 2, 4]
    assert t.unsqueeze(0).shape == [1, 2, 3, 4]
    assert t.unsqueeze(0).squeeze(0).shape == [2, 3, 4]
    assert paddle.tile(paddle.ones([2]), [3]).shape == [6]
    assert paddle.expand(paddle.ones([1, 3]), [4, 3]).shape == [4, 3]
    assert paddle.flip(paddle.arange(3), [0]).numpy().tolist() == [2, 1, 0]
    assert paddle.roll(paddle.arange(3), 1).numpy().tolist() == [2, 0, 1]


def test_gather_scatter():
    x = paddle.arange(12, dtype="float32").reshape([4, 3])
    idx = paddle.to_tensor([0, 2])
    assert paddle.gather(x, idx).shape == [2, 3]
    out = paddle.scatter(paddle.zeros([4, 3]), idx, paddle.ones([2, 3]))
    assert out.numpy().sum() == 6
    nd = paddle.gather_nd(x, paddle.to_tensor([[0, 1], [2, 2]]))
    np.testing.assert_allclose(nd.numpy(), [1.0, 8.0])


def test_reductions():
    t = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]])
    assert t.sum().item() == 10
    assert t.mean().item() == 2.5
    assert t.max().item() == 4
    assert t.min(axis=0).numpy().tolist() == [1, 2]
    assert t.prod().item() == 24
    assert t.sum(axis=1, keepdim=True).shape == [2, 1]
    assert paddle.logsumexp(t).item() == pytest.approx(np.log(np.exp([[1, 2], [3, 4]]).sum()), rel=1e-5)
    assert t.std().item() == pytest.approx(np.std([1, 2, 3, 4], ddof=1), rel=1e-5)
    assert t.var(unbiased=False).item() == pytest.approx(np.var([1, 2, 3, 4]), rel=1e-5)


def test_search_sort():
    t = paddle.to_tensor([3.0, 1.0, 2.0])
    assert t.argmax().item() == 0
    assert t.argmin().item() == 1
    assert t.argsort().numpy().tolist() == [1, 2, 0]
    v, i = paddle.topk(t, 2)
    assert v.numpy().tolist() == [3, 2]
    assert i.numpy().tolist() == [0, 2]
    s = paddle.sort(t)
    assert s.numpy().tolist() == [1, 2, 3]
    w = paddle.where(t > 1.5, t, paddle.zeros_like(t))
    assert w.numpy().tolist() == [3, 0, 2]
    nz = paddle.nonzero(paddle.to_tensor([0, 1, 0, 2]))
    assert nz.numpy().tolist() == [[1], [3]]


def test_linalg():
    a = paddle.to_tensor([[2.0, 0.0], [0.0, 3.0]])
    assert paddle.matmul(a, a).numpy()[1, 1] == 9
    assert paddle.inverse(a).numpy()[0, 0] == pytest.approx(0.5)
    assert paddle.norm(paddle.to_tensor([3.0, 4.0]), p=2).item() == pytest.approx(5.0)
    assert paddle.det(a).item() == pytest.approx(6.0)
    x = paddle.matmul(a, a, transpose_y=True)
    assert x.shape == [2, 2]
    b = paddle.to_tensor(np.random.randn(3, 3).astype(np.float32))
    spd = paddle.matmul(b, b, transpose_y=True) + 3.0 * paddle.eye(3)
    L = paddle.cholesky(spd)
    np.testing.assert_allclose((L @ L.t()).numpy(), spd.numpy(), atol=1e-4)


def test_random_shapes():
    assert paddle.rand([2, 3]).shape == [2, 3]
    assert paddle.randn([4]).shape == [4]
    assert paddle.randint(0, 10, [5]).shape == [5]
    assert paddle.randperm(6).shape == [6]
    u = paddle.uniform([100], min=0.0, max=1.0)
    assert 0 <= float(u.min()) and float(u.max()) <= 1
    assert paddle.bernoulli(paddle.full([10], 0.5)).shape == [10]
    assert paddle.multinomial(paddle.to_tensor([0.1, 0.9]), 3, replacement=True).shape == [3]


def test_einsum():
    a = paddle.ones([2, 3])
    b = paddle.ones([3, 4])
    out = paddle.einsum("ij,jk->ik", a, b)
    np.testing.assert_allclose(out.numpy(), np.full((2, 4), 3.0))


def test_cast_cumsum_clip():
    t = paddle.arange(5, dtype="float32")
    assert t.cumsum().numpy().tolist() == [0, 1, 3, 6, 10]
    assert t.clip(1, 3).numpy().tolist() == [1, 1, 2, 3, 3]


def test_shape_op():
    t = paddle.ones([3, 4])
    assert paddle.shape(t).numpy().tolist() == [3, 4]
    assert paddle.numel(t).item() == 12
    assert paddle.rank(t).item() == 2


def test_typed_error_codes():
    """enforce.h/errors.h parity: typed codes that also subclass the
    natural builtin (so existing `except ValueError` keeps working)."""
    from paddle_tpu.core import errors as E
    with pytest.raises(E.EnforceNotMet):
        E.enforce(False, "nope")
    with pytest.raises(ValueError):
        E.enforce(False, "nope")  # InvalidArgumentError IS a ValueError
    with pytest.raises(E.InvalidArgumentError, match=r"\[InvalidArgument\]"):
        E.enforce_eq(1, 2)
    assert issubclass(E.NotFoundError, FileNotFoundError)
    assert issubclass(E.UnimplementedError, NotImplementedError)
    assert issubclass(E.ResourceExhaustedError, MemoryError)
    # framework call sites raise typed errors that remain ValueError
    from paddle_tpu import parallel
    with pytest.raises(E.InvalidArgumentError):
        parallel.create_mesh({"bogus": 2})
    with pytest.raises(ValueError):
        parallel.create_mesh({"bogus": 2})
