"""Int8 weight-only dequant-matmul for the serving decode path (pallas).

The serving decode step is weight-HBM-bound: every token reads every
weight once.  Holding the weights as int8 + per-output-channel fp32
scales halves the bytes per step (vs bf16; 4x vs f32) — the activation
stays floating point, so the MXU still computes in bf16/f32 and accuracy
is bounded by the ~1/127 per-channel weight quantization error alone
(`quantization.quantize_for_serving` builds the int8 buffers).

Two implementations behind one call:

- **pallas kernel** (TPU, or `_INTERPRET` for tests): grid over
  (M-blocks, N-blocks); each program DMAs an int8 weight block into VMEM,
  dequantizes it in-register against its scale slice, and feeds the MXU —
  the weight moves HBM->VMEM in int8, which is the entire point.  Design
  notes: /opt/skills/guides/pallas_guide.md (min int8 tile (32, 128):
  the gate below requires K % 32 == 0 and N % 128 == 0; M is padded to
  the sublane multiple).
- **jnp fallback** (CPU and unaligned shapes):
  ``x @ (w_int8.astype(x.dtype) * scale)`` — XLA fuses the dequant into
  the dot, so the fallback is one fused program too (the form the
  quantization package already relies on).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dequant_matmul"]

_INTERPRET = False  # tests flip this to run the kernel via the interpreter


def _available(m, k, n) -> bool:
    # int8 VMEM tiling: sublane multiple 32 on the contraction axis, lane
    # multiple 128 on the output axis; other shapes take the XLA form
    return ((_INTERPRET or jax.default_backend() == "tpu")
            and k % 32 == 0 and n % 128 == 0)


def _kernel(x_ref, w_ref, s_ref, o_ref):
    # dequantize the int8 weight block in VMEM and feed the MXU; the f32
    # accumulate keeps the quantization error the only error source
    w = w_ref[...].astype(jnp.float32) * s_ref[0][None, :]
    o_ref[...] = jax.lax.dot(
        x_ref[...].astype(jnp.float32), w,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pallas_matmul(x2, w_int8, scale_row):
    m, k = x2.shape
    n = w_int8.shape[1]
    blk_m = m if m <= 256 else 256        # caller pads m to blk_m multiple
    for blk_n in (512, 256, 128):
        if n % blk_n == 0:
            break
    n_m, n_n = m // blk_m, n // blk_n
    return pl.pallas_call(
        _kernel,
        grid=(n_m, n_n),
        in_specs=[
            pl.BlockSpec((blk_m, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, blk_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
        interpret=_INTERPRET,
    )(x2, w_int8, scale_row)


def dequant_matmul(x, w_int8, scale):
    """``x (..., K) @ dequant(w_int8 (K, N))`` with ``scale`` the
    per-output-channel multiplier of shape (1, N) (a (1, 1) per-tensor
    scale is broadcast).  Returns (..., N) in x's dtype.  Raw jax arrays
    in and out — Layer wrappers live in `paddle_tpu.quantization`."""
    k, n = w_int8.shape
    scale_row = jnp.broadcast_to(scale.astype(jnp.float32), (1, n))
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    if _available(m, k, n):
        # pad rows to the block multiple (sublane-aligned); the padded
        # rows are zeros and sliced back off
        blk_m = 256 if m > 256 else max(8, -(-m // 8) * 8)
        pad = (-m) % blk_m
        if pad:
            x2 = jnp.concatenate(
                [x2, jnp.zeros((pad, k), x2.dtype)], axis=0)
        out = _pallas_matmul(x2, w_int8, scale_row)[:m]
    else:
        out = jnp.dot(x2, w_int8.astype(x2.dtype)
                      * scale_row.astype(x2.dtype))
    return out.reshape(lead + (n,))
