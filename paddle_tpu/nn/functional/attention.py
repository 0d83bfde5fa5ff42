"""Attention functionals.

Reference: operators/fused/multihead_matmul_op.cu (fused QKV attention) and
fused_attention.  TPU-native: one jittable softmax(QK^T/sqrt(d))V whose hot
path swaps to the pallas flash-attention kernel (paddle_tpu/ops/flash_attention.py)
when shapes qualify; XLA otherwise fuses the naive form.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.op import dispatch
from ...observability.metrics import counter

_USE_FLASH = True

# which form each traced attention call took: the choice is made from shapes
# at trace time, so a compiled step's counts say what is inside it
_PATH_TAKEN = counter(
    "attention_path_total",
    "attention calls traced, by the form taken (pallas flash kernel or the "
    "O(S^2) XLA form)", ("path",))


def set_flash_attention(enabled: bool):
    global _USE_FLASH
    _USE_FLASH = bool(enabled)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """q/k/v: (batch, seq, heads, head_dim) — paddle layout."""
    from ...core import rng as _rng
    drop_key = _rng.next_key() if (dropout_p > 0.0 and training) else None

    def raw(q, k, v, mask):
        out = _sdpa_raw(q, k, v, mask, dropout_p if training else 0.0,
                        is_causal, drop_key)
        return out
    return dispatch("scaled_dot_product_attention", raw, query, key, value, attn_mask)


def _flash_kv_bias(mask, batch, sk):
    """Convert an attention mask to the flash kernel's (B, Sk) additive
    per-key bias, or raise ValueError when its shape can't be expressed."""
    if mask.ndim == 4:
        if mask.shape[1] != 1 or mask.shape[2] != 1:
            raise ValueError("per-head/per-query mask")
        mask = mask[:, 0, 0, :]
    if mask.ndim != 2 or mask.shape != (batch, sk):
        raise ValueError("unsupported mask shape")
    if mask.dtype == jnp.bool_:
        return jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    return mask.astype(jnp.float32)


def _sdpa_raw(q, k, v, mask, dropout_p, is_causal, drop_key):
    # pallas flash path: handles causal, (B,Sk) padding bias, and in-kernel
    # dropout; shapes and masks it cannot express take the XLA naive form
    if _USE_FLASH:
        from ...ops import flash_attention as fa
        try:
            bias = None if mask is None else _flash_kv_bias(
                mask, q.shape[0], k.shape[1])
        except ValueError:
            bias = False  # inexpressible mask: skip flash
        if bias is not False:
            seed = None
            if dropout_p > 0.0 and drop_key is not None:
                seed = jax.random.bits(
                    drop_key, (1,), dtype=jnp.uint32).astype(jnp.int32)
            out = fa.flash_attention_bshd(
                q, k, v, causal=is_causal, bias=bias,
                dropout_p=dropout_p if drop_key is not None else 0.0,
                dropout_seed=seed)
            if out is not None:
                _PATH_TAKEN.labels(path="flash").inc()
                return out
    _PATH_TAKEN.labels(path="xla").inc()
    scale = 1.0 / math.sqrt(q.shape[-1])
    # (b, s, h, d) -> (b, h, s, d)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if is_causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(causal, scores, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and drop_key is not None:
        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    """Reference: operators/sequence_ops/sequence_mask_op — the LoD-free way
    to express ragged sequences on TPU (mask + static shapes)."""
    from ...core import dtype as _dt
    from ...core.tensor import unwrap, Tensor
    lv = unwrap(lengths)
    m = int(maxlen) if maxlen is not None else int(jax.device_get(jnp.max(lv)))
    mask = jnp.arange(m) < lv[..., None]
    return Tensor(mask.astype(_dt.convert_dtype(dtype)))
