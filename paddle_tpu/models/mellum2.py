"""Mellum 2 decoder (the `mellum` family; JetBrains' Mellum2-12B-A2.5B
`config.json` keys), as it trains: a sequential pre-norm block (`x +=
Attn(RMSNorm(x))`, then `x += MoE(RMSNorm(x))`), grouped KV heads, window
layers (`sliding_attention`: a query sees its last `sliding_window` keys,
default rotary positions) beside full layers (causal over the whole prefix,
YaRN-scaled rotary positions), every layer's FFN routed (softmax over all
experts, top-k renormalised, SwiGLU experts, no shared expert), final
RMSNorm and an untied head.

Attention goes through the flash kernel's grouped form
(`ops.flash_attention.flash_attention_grouped`, forward and backward; a KV
head read where it lies for the query heads that share it, a window
layer's walk started at the window's far edge in both passes); where the
kernel refuses (not a TPU, a length that is no multiple of 128), the
chunked XLA form.  The routed layer is `nn.HeldExperts`, told which experts
this chip holds, in its trainable form (`F.moe_ffn_held(trainable=True)`:
the held picks in one pass a layer through the grouped-matmul kernels of
`ops.grouped_matmul`, forward and backward, nothing dropped).

`forward` returns, beside the logits, int32 counts summed over layers
(`train_count_names`), which `jit.TrainStep` returns beside the loss and
its `train_step` span carries.  Weights are held in `config.dtype`; under
autocast the products take its dtype, while norm statistics, the router,
softmax and every sum into the residual stream are float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import amp as _amp
from ..core.errors import InvalidArgumentError
from ..core.tensor import Tensor, unwrap
from ..nn import initializer as I
from ..nn.functional.attention import _PATH_TAKEN as _ATTENTION_PATH
from ..nn.functional.moe import moe_ffn_held
from ..nn.layer.container import LayerList
from ..nn.layer.moe import HeldExperts
from ..nn.layer_base import Layer
from ..ops.flash_attention import flash_attention_grouped
from .cohere_moe import attend_in_chunks

SLIDING, FULL = "sliding_attention", "full_attention"

_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}


class Mellum2Config:
    """The source's keys, plus `experts_held` (ids of the routed experts
    this chip holds; default all) and `dtype`."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 num_hidden_layers=28, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, layer_types=None,
                 sliding_window=1024, num_experts=64, num_experts_per_tok=8,
                 moe_intermediate_size=896, experts_held=None,
                 norm_topk_prob=True, rms_norm_eps=1e-6,
                 rope_parameters=None, initializer_range=0.02,
                 dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        if layer_types is None:   # every fourth layer full
            layer_types = [FULL if (i + 1) % 4 == 0 else SLIDING
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types[:num_hidden_layers])
        self.sliding_window = sliding_window
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.experts_held = tuple(range(num_experts) if experts_held is None
                                  else experts_held)
        if norm_topk_prob is not True:
            raise InvalidArgumentError(
                "the routed layer renormalises the top-k weights; got "
                f"norm_topk_prob={norm_topk_prob!r}")
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_parameters = dict(rope_parameters or _ROPE)
        self.initializer_range = initializer_range
        self.dtype = dtype


def rope_frequencies(params, head_dim):
    """(inv_freq (head_dim / 2,), the factor cos and sin are multiplied
    by) of one `rope_parameters` entry: "default", or "yarn" (inverse
    frequencies blended between the extrapolated `theta^(-2i/d)` and the
    interpolated ones `/ factor` by a ramp from `low` to `high`, the
    dimensions whose wavelengths fit `beta_fast` and `beta_slow` times in
    `original_max_position_embeddings`)."""
    theta = float(params["rope_theta"])
    extrap = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                       / head_dim)
    if params["rope_type"] == "default":
        return extrap, 1.0
    if params["rope_type"] != "yarn":
        raise InvalidArgumentError(f"rope_type {params['rope_type']!r}")
    low, high = yarn_range(params, head_dim)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    m = 1.0 - ramp                   # 1: extrapolated, 0: interpolated
    inv = extrap / params["factor"] * (1.0 - m) + extrap * m
    return inv, float(params["attention_factor"])


def yarn_range(params, head_dim):
    """YaRN's (low, high) dimensions: floor and ceil of the dimension whose
    wavelength fits `beta_fast` (`beta_slow`) times in the original
    context."""
    theta = float(params["rope_theta"])
    orig = params["original_max_position_embeddings"]

    def dim(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim(params["beta_fast"])), 0)
    high = min(math.ceil(dim(params["beta_slow"])), head_dim - 1)
    return low, high + (0.001 if low == high else 0)


def _rope(x, cos, sin):
    """x (B, S, heads, hd) rotated in halves-paired form (the first half of
    a head's numbers paired with the second) by cos, sin (S, hd / 2)."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    a, b = x32[..., :half], x32[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s],
                           axis=-1).astype(x.dtype)


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                               + eps) * g.astype(jnp.float32)


def _low():
    """The products' dtype: autocast's where it is on, else None."""
    return jnp.dtype(_amp._state.dtype) if _amp.is_amp_enabled() else None


def _mm(x, w, low):
    if low is not None:
        x, w = x.astype(low), w.astype(low)
    return jnp.matmul(x, w.astype(x.dtype))


class Mellum2Block(Layer):
    def __init__(self, cfg: Mellum2Config, kind: str):
        super().__init__()
        self.kind = kind
        self.cfg = cfg
        init = I.Normal(std=cfg.initializer_range)
        h, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads

        def mat(*shape):
            p = self.create_parameter(shape, dtype=dt,
                                      default_initializer=init)
            jax.block_until_ready(unwrap(p))   # `HeldExperts`' reason
            return p

        def ones():
            return self.create_parameter(
                (h,), dtype=dt, default_initializer=I.Constant(1.0))

        self.input_layernorm = ones()
        self.q_proj, self.k_proj = mat(h, nq * hd), mat(h, nkv * hd)
        self.v_proj, self.o_proj = mat(h, nkv * hd), mat(nq * hd, h)
        self.post_attention_layernorm = ones()
        self.experts = HeldExperts(
            h, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.experts_held, dtype=dt,
            std=cfg.initializer_range)

    def _attend(self, q, k, v):
        """q (B, S, Hq, hd), k, v (B, S, Hkv, hd) -> (B, S, Hq * hd)."""
        cfg = self.cfg
        window = cfg.sliding_window if self.kind == SLIDING else None
        b, s = q.shape[:2]
        with jax.named_scope("window_attention" if window
                             else "full_attention"):
            out = flash_attention_grouped(q, k, v, window=window)
            if out is not None:
                _ATTENTION_PATH.labels(path="flash").inc()
                return out.reshape(b, s, -1)
            _ATTENTION_PATH.labels(path="xla").inc()
            scale = 1.0 / math.sqrt(cfg.head_dim)
            qg = q.reshape(b, s, k.shape[2], -1, cfg.head_dim)
            return jnp.stack([attend_in_chunks(qg[i], k[i], v[i], scale,
                                               window).astype(q.dtype)
                              for i in range(b)])

    def forward(self, x, rope):
        """x (B, S, H) float32, rope {kind: (cos, sin)} -> (x', counts)."""
        cfg, low = self.cfg, _low()
        b, s, h = x.shape
        hd = cfg.head_dim
        a = _rms(x, unwrap(self.input_layernorm), cfg.rms_norm_eps)
        a = a.astype(low or x.dtype)
        q = _mm(a, unwrap(self.q_proj), low).reshape(b, s, -1, hd)
        k = _mm(a, unwrap(self.k_proj), low).reshape(b, s, -1, hd)
        v = _mm(a, unwrap(self.v_proj), low).reshape(b, s, -1, hd)
        cos, sin = rope[self.kind]
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        o = _mm(self._attend(q, k, v), unwrap(self.o_proj), low)
        x = x + o.astype(jnp.float32)
        m = _rms(x, unwrap(self.post_attention_layernorm), cfg.rms_norm_eps)
        m = m.astype(low or x.dtype).reshape(b * s, h)
        ex = self.experts
        cast = (lambda w: w) if low is None else (  # noqa: E731
            lambda w: w.astype(low))
        y, here, hit, products, rows = moe_ffn_held.raw(
            m, unwrap(ex.router), cast(unwrap(ex.gate)),
            cast(unwrap(ex.up)), cast(unwrap(ex.down)), ex.experts_held,
            ex.top_k, score="softmax", trainable=True)
        x = x + y.astype(jnp.float32).reshape(b, s, h)
        counts = jnp.stack([here, jnp.int32(b * s * ex.top_k), hit,
                            products, rows]).astype(jnp.int32)
        return x, counts


class Mellum2ForCausalLM(Layer):
    """Embedding, the blocks, the final norm and the untied head."""

    # what `forward`'s int32 counts are, in order, summed over layers: the
    # routed family's five, as the serving spans name them
    train_count_names = ("routed_here", "routed_all", "experts_hit",
                         "expert_products", "expert_rows")

    def __init__(self, cfg: Mellum2Config = None, **kw):
        super().__init__()
        self.config = cfg = cfg or Mellum2Config(**kw)
        init = I.Normal(std=cfg.initializer_range)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=init)
        self.layers = LayerList([Mellum2Block(cfg, kind)
                                 for kind in cfg.layer_types])
        self.norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), dtype=cfg.dtype,
            default_initializer=init)

    def _rope(self, s):
        """{kind: (cos, sin) (s, head_dim / 2)} at positions 0..s-1."""
        cfg, out = self.config, {}
        pos = jnp.arange(s, dtype=jnp.float32)[:, None]
        for kind in dict.fromkeys(cfg.layer_types):  # a set's order varies
            inv, factor = rope_frequencies(cfg.rope_parameters[kind],
                                           cfg.head_dim)
            ang = pos * inv[None, :]
            out[kind] = (jnp.cos(ang) * factor, jnp.sin(ang) * factor)
        return out

    def forward(self, input_ids):
        """input_ids (B, S) -> (logits (B, S, V), counts (5,) int32)."""
        cfg = self.config
        ids = unwrap(input_ids).astype(jnp.int32)
        x = unwrap(self.embed_tokens)[ids].astype(jnp.float32)
        rope = self._rope(ids.shape[1])
        counts = 0
        for blk in self.layers:
            x, c = blk.forward(x, rope)
            counts = counts + c
        low = _low()
        h = _rms(x, unwrap(self.norm), cfg.rms_norm_eps)
        logits = _mm(h.astype(low or h.dtype), unwrap(self.lm_head), low)
        return Tensor(logits), Tensor(counts)
