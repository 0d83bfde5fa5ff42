"""paddle_tpu.nn.functional (reference: python/paddle/nn/functional/)."""
from .activation import *  # noqa: F401,F403
from .sequence import (sequence_pad, sequence_unpad, sequence_pool,  # noqa: F401
                       sequence_softmax, sequence_reverse, sequence_concat,
                       sequence_enumerate, sequence_expand_as,
                       sequence_first_step, sequence_last_step)
from .attention import (scaled_dot_product_attention, sequence_mask,  # noqa: F401
                        set_flash_attention)
from .common import *  # noqa: F401,F403
from .conv import (conv1d, conv2d, conv3d, conv1d_transpose,  # noqa: F401
                   conv2d_transpose, conv3d_transpose)
from .loss import *  # noqa: F401,F403
from .norm import (batch_norm, fused_bn_act, fused_dual_bn_act,  # noqa: F401
                   layer_norm, instance_norm, group_norm,
                   local_response_norm, normalize, rms_norm)
from .pooling import *  # noqa: F401,F403
from .moe import moe_ffn, moe_ffn_held  # noqa: F401
from .vision import affine_grid, grid_sample, temporal_shift  # noqa: F401
from .crf import linear_chain_crf, crf_decoding, hsigmoid_loss  # noqa: F401


# ---------------------------------------------------------------------------
# fluid-1.x functional spellings (the reference's 2.0-rc functional
# namespace re-exported the fluid layers API wholesale; the working
# implementations live in their 2.0 homes — vision.ops for detection,
# interpolate for image_resize, the pooling/linear functionals, etc.)

def _vision_op(name):
    def fn(*args, **kwargs):
        from ...vision import ops as _vops
        return getattr(_vops, name)(*args, **kwargs)
    fn.__name__ = name
    fn.__doc__ = f"fluid spelling of paddle.vision.ops.{name}"
    return fn


yolo_box = _vision_op("yolo_box")
yolov3_loss = _vision_op("yolo_loss")
prior_box = _vision_op("prior_box")
anchor_generator = _vision_op("anchor_generator")
box_coder = _vision_op("box_coder")
box_clip = _vision_op("box_clip")
multiclass_nms = _vision_op("multiclass_nms")
distribute_fpn_proposals = _vision_op("distribute_fpn_proposals")
roi_align = _vision_op("roi_align")
roi_pool = _vision_op("roi_pool")
generate_proposals = _vision_op("generate_proposals")
deformable_conv = _vision_op("deform_conv2d")


def gather_tree(ids, parents):
    from ..decode import gather_tree as _gt
    return _gt(ids, parents)


def image_resize(input, out_shape=None, scale=None, name=None,  # noqa: A002
                 resample="BILINEAR", align_corners=True, **kw):
    # fluid defaults to align_corners=True (interpolate defaults False)
    return interpolate(input, size=out_shape, scale_factor=scale,
                       mode=resample.lower(), align_corners=align_corners)


def resize_bilinear(input, out_shape=None, scale=None,  # noqa: A002
                    align_corners=True, **kw):
    return interpolate(input, size=out_shape, scale_factor=scale,
                       mode="bilinear", align_corners=align_corners)


def resize_nearest(input, out_shape=None, scale=None,  # noqa: A002
                   align_corners=True, **kw):
    # nearest ignores corner alignment in interpolate; accepted for compat
    return interpolate(input, size=out_shape, scale_factor=scale,
                       mode="nearest")


def resize_trilinear(input, out_shape=None, scale=None,  # noqa: A002
                     align_corners=True, **kw):
    return interpolate(input, size=out_shape, scale_factor=scale,
                       mode="trilinear", align_corners=align_corners)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,  # noqa: A002
           pool_padding=0, global_pooling=False, **kw):
    if global_pooling:
        pool_size = input.shape[2:]
        pool_stride, pool_padding = pool_size, 0
    fn = max_pool2d if pool_type == "max" else avg_pool2d
    return fn(input, pool_size, stride=pool_stride, padding=pool_padding)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,  # noqa: A002
           pool_padding=0, global_pooling=False, **kw):
    if global_pooling:
        pool_size = input.shape[2:]
        pool_stride, pool_padding = pool_size, 0
    fn = max_pool3d if pool_type == "max" else avg_pool3d
    return fn(input, pool_size, stride=pool_stride, padding=pool_padding)


def fc(input, size, num_flatten_dims=1, weight=None, bias=None,  # noqa: A002
       **kw):
    """fluid.layers.fc functional form: flatten trailing dims + linear.
    Unlike the stateful original, weight/bias must be passed explicitly
    (layer state lives in nn.Linear here)."""
    from ...core.errors import InvalidArgumentError
    if weight is None:
        raise InvalidArgumentError(
            "functional fc needs an explicit weight — use nn.Linear for "
            "the stateful fluid.layers.fc behavior")
    b = input.shape[:num_flatten_dims]
    flat = input.reshape(list(b) + [-1])
    return linear(flat, weight, bias)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant",  # noqa: A002
          pad_value=0.0, data_format="NCHW", **kw):
    # fluid order [top, bottom, left, right] -> pad's [l, r, t, b]
    t, bm, l, r = paddings
    return pad(input, [l, r, t, bm],
               mode=mode.replace("edge", "replicate"),
               value=pad_value, data_format=data_format)


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=1.0):
    """fluid smooth_l1 (reference smooth_l1_loss_op): per-ROW summed
    huber with sigma^2 scaling and optional elementwise weights."""
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(xv, yv):
        s2 = float(sigma) ** 2
        d = xv - yv
        if inside_weight is not None:
            from ...core.tensor import unwrap as _u
            d = d * _u(inside_weight)
        ad = jnp.abs(d)
        loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
        if outside_weight is not None:
            from ...core.tensor import unwrap as _u
            loss = loss * _u(outside_weight)
        return loss.reshape(loss.shape[0], -1).sum(-1, keepdims=True)
    return _dispatch("smooth_l1", raw, x, y)


def dice_loss(input, label, epsilon=1e-5, name=None):  # noqa: A002
    """reference python/paddle/nn/functional/loss.py dice_loss."""
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(p, l):
        lab = jax.nn.one_hot(l[..., 0].astype(jnp.int32), p.shape[-1]) \
            if l.shape[-1] == 1 else l
        red = tuple(range(1, p.ndim))
        inter = jnp.sum(p * lab, axis=red)
        union = jnp.sum(p, axis=red) + jnp.sum(lab, axis=red)
        return jnp.mean(1.0 - (2 * inter + epsilon) / (union + epsilon))
    import jax
    return _dispatch("dice_loss", raw, input, label)


def bpr_loss(input, label, name=None):  # noqa: A002
    """Bayesian personalized ranking loss (reference bpr_loss_op)."""
    import jax
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(logits, lab):
        pos = jnp.take_along_axis(logits, lab.reshape(-1, 1), axis=1)
        diff = jax.nn.log_sigmoid(pos - logits)
        n = logits.shape[1]
        mask = jax.nn.one_hot(lab.reshape(-1), n) == 0
        return -(jnp.sum(jnp.where(mask, diff, 0.0), axis=1,
                         keepdims=True) / max(n - 1, 1))
    return _dispatch("bpr_loss", raw, input, label)


def soft_relu(x, threshold=40.0, name=None):
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch
    return _dispatch("soft_relu",
                     lambda v: jnp.log1p(jnp.exp(jnp.clip(
                         v, -threshold, threshold))), x)


def space_to_depth(x, blocksize, name=None):
    return pixel_unshuffle(x, blocksize)


def shuffle_channel(x, group, name=None):
    return channel_shuffle(x, group)
from ..legacy_layers import ctc_greedy_decoder, clip_by_norm, nce  # noqa: F401,E402


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):  # noqa: A002
    """Sinusoidal position encoding mix-in (reference: fluid
    add_position_encoding -> operators/add_position_encoding_op):
    out = alpha * x + beta * pe, pe the interleaved sin/cos table."""
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(x):
        b, t, c = x.shape
        half = (c + 1) // 2
        pos = jnp.arange(t, dtype=jnp.float32)[:, None]
        div = jnp.power(10000.0, jnp.arange(half, dtype=jnp.float32) / half)
        pe = jnp.concatenate([jnp.sin(pos / div), jnp.cos(pos / div)],
                             axis=1)[:, :c]  # odd C: drop the last cos col
        return alpha * x + beta * pe[None].astype(x.dtype)
    return _dispatch("add_position_encoding", raw, input)


def pad_constant_like(x, y, pad_value=0.0, name=None):
    """Pad y up to x's shape with pad_value (reference:
    operators/pad_constant_like_op)."""
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(xv, yv):
        pads = [(0, xv.shape[i] - yv.shape[i]) for i in range(yv.ndim)]
        return jnp.pad(yv, pads, constant_values=pad_value)
    return _dispatch("pad_constant_like", raw, x, y)


def fsp_matrix(x, y, name=None):
    """Flow-of-solution-procedure matrix for distillation (reference:
    operators/fsp_op): (B, Cx, Cy) = x·y^T over spatial dims / (H*W)."""
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(xv, yv):
        b, cx, h, w = xv.shape
        cy = yv.shape[1]
        xf = xv.reshape(b, cx, h * w)
        yf = yv.reshape(b, cy, h * w)
        return jnp.einsum("bim,bjm->bij", xf, yf) / (h * w)
    return _dispatch("fsp_matrix", raw, x, y)


def teacher_student_sigmoid_loss(input, label,  # noqa: A002
                                 soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    """CTR distillation loss (reference:
    operators/teacher_student_sigmoid_loss_op): teacher signal encoded in
    the label's fractional part."""
    import jax.numpy as jnp
    from ...core.op import dispatch as _dispatch

    def raw(z, lab):
        z = jnp.clip(z.astype(jnp.float32), soft_max_lower_bound,
                     soft_max_up_bound)
        lab = lab.astype(jnp.float32)
        hard = (lab > -1.0).astype(jnp.float32)
        soft = lab - jnp.floor(lab)
        log1pez = jnp.log1p(jnp.exp(-jnp.abs(z))) + jnp.maximum(z, 0)
        return (log1pez - hard * z) + (log1pez - soft * z)
    return _dispatch("teacher_student_sigmoid_loss", raw, input, label)


def ssd_loss(location, confidence, gt_box, gt_label, prior_box,
             prior_box_var=None, **kwargs):
    """paddle.nn.functional.ssd_loss (reference alias of
    fluid/layers/detection.py:1513) — implementation in vision.ops."""
    from ...vision.ops import ssd_loss as _impl
    return _impl(location, confidence, gt_box, gt_label, prior_box,
                 prior_box_var, **kwargs)


# era spellings surfaced under nn.functional (reference
# nn/functional/__init__.py:71 `from .common import assign` and :97
# `from .extension import diag_embed`)
from ...tensor.creation import assign  # noqa: F401,E402
from ...tensor.manipulation import diag_embed  # noqa: F401,E402
from . import extension  # noqa: F401,E402
