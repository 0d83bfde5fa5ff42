"""Typed-error adoption at the public boundary (VERDICT r3 weak #5):
shape/dtype/argument validation raises the enforce.h-shaped error classes
(core/errors.py) with op-name + got-vs-expected context, while still
subclassing the builtin users naturally catch."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core.errors import (
    EnforceNotMet, InvalidArgumentError, NotFoundError,
)


def test_reshape_element_count():
    x = paddle.to_tensor(np.zeros((2, 3), "float32"))
    with pytest.raises(InvalidArgumentError, match=r"reshape.*6 elements"):
        paddle.reshape(x, [4, 2])
    with pytest.raises(InvalidArgumentError, match="one dimension"):
        paddle.reshape(x, [-1, -1])
    # valid reshapes still work, including -1 inference
    assert list(paddle.reshape(x, [3, -1]).shape) == [3, 2]


def test_concat_rank_and_axis():
    a = paddle.to_tensor(np.zeros((2, 3), "float32"))
    b = paddle.to_tensor(np.zeros((2,), "float32"))
    with pytest.raises(InvalidArgumentError, match="rank mismatch"):
        paddle.concat([a, b])
    with pytest.raises(InvalidArgumentError, match="axis 5 out of range"):
        paddle.concat([a, a], axis=5)
    with pytest.raises(InvalidArgumentError, match="empty"):
        paddle.concat([])


def test_matmul_contraction_dims():
    a = paddle.to_tensor(np.zeros((2, 3), "float32"))
    b = paddle.to_tensor(np.zeros((4, 5), "float32"))
    with pytest.raises(InvalidArgumentError, match="K=3.*K=4"):
        paddle.matmul(a, b)
    # transpose flags change the contraction dim
    assert list(paddle.matmul(
        a, paddle.to_tensor(np.zeros((5, 3), "float32")),
        transpose_y=True).shape) == [2, 5]


def test_conv2d_channel_group_checks():
    x = paddle.to_tensor(np.zeros((1, 4, 8, 8), "float32"))
    w_bad = paddle.to_tensor(np.zeros((8, 3, 3, 3), "float32"))
    with pytest.raises(InvalidArgumentError,
                       match=r"conv2d.*input channels 4"):
        F.conv2d(x, w_bad)
    with pytest.raises(InvalidArgumentError, match="rank-4"):
        F.conv2d(paddle.to_tensor(np.zeros((4, 8, 8), "float32")), w_bad)


def test_embedding_dtype_and_weight_rank():
    w = paddle.to_tensor(np.zeros((10, 4), "float32"))
    with pytest.raises(InvalidArgumentError, match="integer"):
        F.embedding(paddle.to_tensor(np.zeros((2,), "float32")), w)
    with pytest.raises(InvalidArgumentError, match="2-D"):
        F.embedding(paddle.to_tensor(np.zeros((2,), "int64")),
                    paddle.to_tensor(np.zeros((10,), "float32")))


def test_dataloader_argument_checks():
    from paddle_tpu.io import DataLoader

    class DS:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            return np.zeros(2, "float32")

    with pytest.raises(InvalidArgumentError, match="batch_size"):
        DataLoader(DS(), batch_size=0)
    with pytest.raises(InvalidArgumentError, match="num_workers"):
        DataLoader(DS(), num_workers=-1)


def test_load_missing_artifact_is_not_found():
    with pytest.raises(NotFoundError, match="no artifact"):
        paddle.load("/tmp/definitely-not-a-real-checkpoint.pdparams")
    # NotFoundError doubles as FileNotFoundError for existing handlers
    with pytest.raises(FileNotFoundError):
        paddle.load("/tmp/definitely-not-a-real-checkpoint.pdparams")


def test_typed_errors_are_catchable_as_builtin():
    # the enforce contract: typed AND builtin-compatible
    x = paddle.to_tensor(np.zeros((2, 3), "float32"))
    with pytest.raises(ValueError):
        paddle.reshape(x, [7, 7])
    with pytest.raises(EnforceNotMet):
        paddle.reshape(x, [7, 7])


def test_grid_sample_mode_typed():
    x = paddle.to_tensor(np.zeros((1, 1, 2, 2), "float32"))
    g = paddle.to_tensor(np.zeros((1, 1, 1, 2), "float32"))
    with pytest.raises(InvalidArgumentError, match="grid_sample"):
        F.grid_sample(x, g, mode="bicubic")
