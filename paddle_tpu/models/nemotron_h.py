"""Nemotron-H decoder (the `nemotron_h` model type; Nemotron 3 Super's
`config.json` keys): a hybrid of three kinds of block chosen a layer by
`hybrid_override_pattern`, each `h = h + mixer(RMSNorm(h))`, then a final
RMSNorm and an untied head.

* `M`, a Mamba-2 mixer: `[z | xBC | dt] = in_proj(u)`; xBC through a causal
  depthwise conv1d of `conv_kernel` taps with a bias, then SiLU, split into
  x (`mamba_num_heads` x `mamba_head_dim`), B and C (`n_groups` x
  `ssm_state_size`, a group shared by `heads / n_groups` heads); `dt =
  softplus(dt + dt_bias)`, `A = -exp(A_log)`; per head `h_t = exp(dt A)
  h_{t-1} + dt x_t (x) B_t`, `y_t = C_t . h_t + D x_t`; then `y * silu(z)`
  through an RMSNorm over `n_groups` groups with a scale, and `out_proj`.
  A prompt runs the chunked (SSD) form, `chunk_size` rows a chunk, with dt
  masked to 0 past `prompt_len` so that a bucket's padding moves no state;
  a decode step runs the one-step recurrence.
* `*`, grouped-query attention with no positional encoding (the Mamba
  layers order the tokens): causal, 1/sqrt(head_dim).
* `E`, LatentMoE: sigmoid router over all experts, the top k of `score +
  e_score_correction_bias`, weights the scores over their sum times
  `routed_scaling_factor` (`noaux_tc`); the routed experts are relu^2 MLPs
  with no gate IN A LATENT (`fc1_latent_proj` down, the experts, then
  `fc2_latent_proj` up); beside them one shared relu^2 expert on the full
  width.  `nn.HeldExperts` holds the experts `experts_held` lists (one
  chip's share of an expert-parallel deployment).

Serving (`serving_batch_decode`): `gen_fixed_cache` gives every `M` layer
TWO LEAVES WITH NO ROW AXIS, the SSM state `(B, heads, head_dim, state)` in
float32 and the conv state `(B, conv_kernel - 1, conv_dim)` (the last xBC
rows before the conv) in `config.dtype`, written whole at a prompt's end and
rewritten in place every decode step; every `*` layer a `(k, v)` pair `(B,
rows, kv_heads, head_dim)`; an `E` layer holds nothing.  The programs' int32
counts are named (`serving_count_names`): the routed family's five, the
attention's rows, and the state's slot-layers.

Weights, cache and products are in `config.dtype`; the SSM state, the A,
D and dt leaves, norm statistics, the router, softmax and the scan's decays
are float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.errors import InvalidArgumentError
from ..core.tensor import Tensor, unwrap
from ..nn import initializer as I
from ..nn.functional.attention import _PATH_TAKEN as _ATTENTION_PATH
from ..nn.functional.moe import moe_ffn_held
from ..nn.layer.container import LayerList
from ..nn.layer.moe import HeldExperts
from ..nn.layer_base import Layer
from ..observability.metrics import counter
from ..ops.flash_attention import flash_attention_grouped
from .cohere_moe import _softmax_pv, attend_in_chunks

MAMBA, MOE, ATTENTION = "M", "E", "*"

# which form of the Mamba-2 scan each traced layer took: a prompt's chunked
# form or a decode step's recurrence, counted at trace time
_SCAN_FORM = counter(
    "ssm_scan_form_total",
    "Mamba-2 mixers traced, by the form of the scan (chunked: a prompt's; "
    "recurrent: a decode step's)", ("form",))


class NemotronHConfig:
    """The source's keys, plus `experts_held` (ids of the routed experts
    held here; default all) and `dtype`."""

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 num_hidden_layers=88,
                 hybrid_override_pattern="MEMEMEM*EME",
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, expand=2,
                 chunk_size=128, use_conv_bias=True, mamba_proj_bias=False,
                 n_routed_experts=512, num_experts_per_tok=22,
                 moe_intermediate_size=2688, moe_latent_size=1024,
                 moe_shared_expert_intermediate_size=5376,
                 n_shared_experts=1, routed_scaling_factor=5.0,
                 norm_topk_prob=True, n_group=1, topk_group=1,
                 mlp_hidden_act="relu2", attention_bias=False,
                 mlp_bias=False, layer_norm_epsilon=1e-5,
                 experts_held=None, initializer_range=0.02,
                 dtype="bfloat16"):
        pattern = hybrid_override_pattern[:num_hidden_layers]
        for key, got, only in (
                ("mlp_hidden_act", mlp_hidden_act, "relu2"),
                ("use_conv_bias", use_conv_bias, True),
                ("mamba_proj_bias", mamba_proj_bias, False),
                ("attention_bias", attention_bias, False),
                ("mlp_bias", mlp_bias, False),
                ("n_shared_experts", n_shared_experts, 1),
                ("norm_topk_prob", norm_topk_prob, True),
                ("n_group", n_group, 1), ("topk_group", topk_group, 1)):
            if got != only:
                raise InvalidArgumentError(
                    f"{key}={got!r}: this model has the form {key}={only!r} "
                    "alone (relu^2 experts, biases on the conv alone, one "
                    "shared expert, no group step in the router)")
        if (len(pattern) != num_hidden_layers
                or set(pattern) - {MAMBA, MOE, ATTENTION}):
            raise InvalidArgumentError(
                f"hybrid_override_pattern {hybrid_override_pattern!r} must "
                f"give {num_hidden_layers} layers of 'M', 'E' and '*'")
        if expand * hidden_size != mamba_num_heads * mamba_head_dim:
            raise InvalidArgumentError(
                f"expand x hidden_size ({expand} x {hidden_size}) must be "
                f"mamba_num_heads x mamba_head_dim ({mamba_num_heads} x "
                f"{mamba_head_dim})")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.pattern = pattern
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_latent_size = moe_latent_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.layer_norm_epsilon = layer_norm_epsilon
        self.experts_held = tuple(range(n_routed_experts)
                                  if experts_held is None else experts_held)
        self.initializer_range = initializer_range
        self.dtype = dtype

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size


def _rms(x, g, eps):
    """RMSNorm, float32 statistics; float32 out."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + eps)) * g.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, unwrap(w), preferred_element_type=jnp.float32)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def gated_group_norm(y, z, g, groups, eps):
    """`y * silu(z)` normalised by its RMS over each of `groups` equal
    groups of the last axis, times the scale g; float32."""
    a = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = a.shape
    a = a.reshape(shape[:-1] + (groups, -1))
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True) + eps)
    return a.reshape(shape) * g.astype(jnp.float32)


def real_rows(s, prompt_len):
    """(s,) bool: the rows of a bucket of s that hold the prompt; the
    padding after them moves no state."""
    return jnp.arange(s) < prompt_len


def conv_tail(rows, prompt_len, k):
    """The conv state a prompt hands to decode: the `k - 1` xBC rows before
    position `prompt_len` of `rows` (the prompt's xBC behind `k - 1` rows of
    zeros), (k - 1, conv_dim)."""
    return jax.lax.dynamic_slice_in_dim(rows, prompt_len, k - 1, axis=0)


def ssd_chunked(x, dt, a, b, c, chunk):
    """The Mamba-2 scan of one sequence in chunks (the SSD form): x (T, H,
    P), dt (T, H) (after softplus; 0 where a row must not move the state),
    a (H,) = -exp(A_log), b, c (T, G, N), T a multiple of `chunk`, H a
    multiple of G -> (y (T, H, P) without the D skip, the state after the
    last row (H, P, N)), float32.  Within a chunk the rows' outputs are one
    masked product of decays, C.B and dt x; across chunks each chunk's state
    is carried by a scan of nc steps."""
    t, nh, p = x.shape
    g, n = b.shape[1:]
    r, nc = nh // g, t // chunk
    f32 = jnp.float32
    la = (dt * a[None, :]).reshape(nc, chunk, g, r)            # log decays
    cum = jnp.cumsum(la, axis=1)                               # (nc, L, g, r)
    u = (dt[..., None] * x).reshape(nc, chunk, g, r, p)        # dt x
    bb = b.reshape(nc, chunk, g, n)
    cc = c.reshape(nc, chunk, g, n)
    # within a chunk: y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) u_j
    seg = cum[:, :, None] - cum[:, None, :]                    # (nc,i,j,g,r)
    keep = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    decay = jnp.exp(jnp.where(keep[None, :, :, None, None], seg, -jnp.inf))
    cb = jnp.einsum("cign,cjgn->cijg", cc, bb, preferred_element_type=f32)
    y = jnp.einsum("cijgr,cjgrp->cigrp", decay * cb[..., None], u,
                   preferred_element_type=f32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum)                        # (nc, L, g, r)
    own = jnp.einsum("cjgn,cjgrp->cgrpn", bb, to_end[..., None] * u,
                     preferred_element_type=f32)
    fade = jnp.exp(cum[:, -1])                                 # (nc, g, r)

    def carry(h, step):
        s, f = step
        return f[..., None, None] * h + s, h

    last, before = jax.lax.scan(carry, jnp.zeros((g, r, p, n), f32),
                                (own, fade))
    # the state entering each chunk, decayed to each of its rows
    y = y + jnp.einsum("cign,cgrpn->cigrp", cc, before,
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(t, nh, p), last.reshape(nh, p, n)


def ssm_step(state, x, dt, a, b, c):
    """One step of the recurrence for a batch: state (B, H, P, N) float32,
    x (B, H, P), dt (B, H), a (H,), b, c (B, G, N) -> (y (B, H, P) without
    the D skip, the new state), float32."""
    bsz, nh, p, n = state.shape
    g = b.shape[1]
    h = state.reshape(bsz, g, nh // g, p, n)
    fade = jnp.exp(dt * a[None, :]).reshape(bsz, g, -1)[..., None, None]
    u = (dt[..., None] * x).reshape(bsz, g, nh // g, p)
    h = fade * h + u[..., None] * b[:, :, None, None, :]
    y = jnp.einsum("bgrpn,bgn->bgrp", h, c, preferred_element_type=jnp.float32)
    return y.reshape(bsz, nh, p), h.reshape(state.shape)


class Mamba2Mixer(Layer):
    """The `M` mixer's leaves and its two forms (module docstring)."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(std=cfg.initializer_range)
        nh, h = cfg.mamba_num_heads, cfg.hidden_size
        mat = lambda *shape: self.create_parameter(  # noqa: E731
            shape, dtype=cfg.dtype, default_initializer=init)
        vec = lambda value: self.create_parameter(  # noqa: E731
            (nh,), dtype="float32", default_initializer=I.Constant(value))
        self.in_proj = mat(h, cfg.d_inner + cfg.conv_dim + nh)
        self.conv1d_weight = mat(cfg.conv_kernel, cfg.conv_dim)
        self.conv1d_bias = self.create_parameter(
            (cfg.conv_dim,), dtype=cfg.dtype,
            default_initializer=I.Constant(0.0))
        self.dt_bias = vec(0.0)
        self.A_log = vec(0.0)
        self.D = vec(1.0)
        self.norm = self.create_parameter(
            (cfg.d_inner,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.out_proj = mat(cfg.d_inner, h)

    def _split(self, h):
        """h (T, H) -> z (T, d_inner), xBC (T, conv_dim) in h's dtype (what
        the conv state holds), dt (T, heads), float32 where not said."""
        cfg = self.cfg
        zxbcdt = _mm(h, self.in_proj)
        z, xbc, dt = jnp.split(zxbcdt, [cfg.d_inner,
                                        cfg.d_inner + cfg.conv_dim], axis=-1)
        return z, xbc.astype(h.dtype), dt

    def _conv_out(self, windows):
        """windows (..., conv_kernel, conv_dim): each position's last
        `conv_kernel` xBC rows -> x (..., heads, head_dim), B, C (...,
        groups, state), float32."""
        cfg = self.cfg
        w = unwrap(self.conv1d_weight).astype(jnp.float32)
        a = jax.nn.silu(jnp.sum(windows.astype(jnp.float32) * w, axis=-2)
                        + unwrap(self.conv1d_bias).astype(jnp.float32))
        gn = cfg.n_groups * cfg.ssm_state_size
        x, b, c = jnp.split(a, [cfg.d_inner, cfg.d_inner + gn], axis=-1)
        lead = a.shape[:-1]
        return (x.reshape(lead + (cfg.mamba_num_heads, cfg.mamba_head_dim)),
                b.reshape(lead + (cfg.n_groups, cfg.ssm_state_size)),
                c.reshape(lead + (cfg.n_groups, cfg.ssm_state_size)))

    def _dt_a(self, dt):
        return (jax.nn.softplus(dt + unwrap(self.dt_bias)),
                -jnp.exp(unwrap(self.A_log)))

    def _out(self, y, x, z, dtype):
        """The D skip, the gated norm, `out_proj`; float32 out."""
        cfg = self.cfg
        y = y + unwrap(self.D)[:, None] * x
        y = gated_group_norm(y.reshape(y.shape[:-2] + (-1,)), z,
                             unwrap(self.norm), cfg.n_groups,
                             cfg.layer_norm_epsilon)
        return _mm(y.astype(dtype), self.out_proj)

    def forward_seq(self, h, prompt_len):
        """One sequence h (S, H), `prompt_len` of its rows real -> (the
        mixer's output (S, H) float32, (the SSM state at `prompt_len` (heads,
        head_dim, state) float32, the last `conv_kernel - 1` real xBC rows
        (conv_kernel - 1, conv_dim)))."""
        cfg, s = self.cfg, h.shape[0]
        k = cfg.conv_kernel
        _SCAN_FORM.labels(form="chunked").inc()
        with jax.named_scope("mamba_mixer"):
            z, xbc, dt = self._split(h)
            padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
            windows = jnp.stack([padded[i:i + s] for i in range(k)], axis=1)
            x, b, c = self._conv_out(windows)
            dt, a = self._dt_a(dt)
            dt = jnp.where(real_rows(s, prompt_len)[:, None], dt, 0.0)
            pad = (-s) % cfg.chunk_size
            grow = lambda v: jnp.pad(  # noqa: E731
                v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
            y, state = ssd_chunked(grow(x), grow(dt), a, grow(b), grow(c),
                                   cfg.chunk_size)
            out = self._out(y[:s], x, z, h.dtype)
            conv = conv_tail(padded, prompt_len, k)
        return out, (state, conv)

    def forward_decode(self, h, state, conv, active):
        """h (B, H), one token a slot; state (B, heads, head_dim, state)
        float32, conv (B, conv_kernel - 1, conv_dim); active (B,) -> (the
        mixer's output (B, H) float32, the two leaves rewritten; a slot
        that holds no request keeps its own)."""
        _SCAN_FORM.labels(form="recurrent").inc()
        with jax.named_scope("mamba_mixer"):
            z, xbc, dt = self._split(h)
            windows = jnp.concatenate([conv.astype(xbc.dtype), xbc[:, None]],
                                      axis=1)
            x, b, c = self._conv_out(windows)
            dt, a = self._dt_a(dt)
            y, new = ssm_step(state, x, dt, a, b, c)
            out = self._out(y, x, z, h.dtype)
            keep = active[:, None, None]
            state = jnp.where(keep[..., None], new, state)
            conv = jnp.where(keep, windows[:, 1:].astype(conv.dtype), conv)
        return out, state, conv


class GQAttention(Layer):
    """The `*` mixer: grouped-query attention, no positions."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(std=cfg.initializer_range)
        h, hd = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        mat = lambda *shape: self.create_parameter(  # noqa: E731
            shape, dtype=cfg.dtype, default_initializer=init)
        self.q_proj, self.k_proj = mat(h, nq * hd), mat(h, nkv * hd)
        self.v_proj, self.o_proj = mat(h, nkv * hd), mat(nq * hd, h)

    @property
    def _scale(self):
        return 1.0 / math.sqrt(self.cfg.head_dim)

    def _qkv(self, h):
        cfg, t = self.cfg, h.shape[0]
        qkv = [_mm(h, w).astype(h.dtype).reshape(t, -1, cfg.head_dim)
               for w in (self.q_proj, self.k_proj, self.v_proj)]
        return tuple(qkv)

    def _out(self, attn, dtype):
        return _mm(attn.reshape(attn.shape[0], -1).astype(dtype),
                   self.o_proj)

    def forward_seq(self, h):
        """One sequence against itself under the causal mask: h (S, H) ->
        (output (S, H) float32, (k, v) (S, kv_heads, head_dim)).  On the
        chip the flash kernel's grouped forward; where it refuses, the
        chunked XLA form."""
        q, k, v = self._qkv(h)
        with jax.named_scope("full_attention"):
            out = flash_attention_grouped(q[None], k[None], v[None])
            if out is not None:
                _ATTENTION_PATH.labels(path="flash").inc()
                attn = out[0]
            else:
                _ATTENTION_PATH.labels(path="xla").inc()
                attn = attend_in_chunks(
                    q.reshape(q.shape[0], k.shape[1], -1, q.shape[-1]),
                    k, v, self._scale)
        return self._out(attn, h.dtype), (k, v)

    def forward_decode(self, h, kbuf, vbuf, pos, active):
        """h (B, H) at positions pos (B,); kbuf / vbuf (B, rows, kv_heads,
        head_dim) -> (output, the buffers with the step's row written at
        pos).  Two products over the whole pool behind a mask."""
        cfg = self.cfg
        b, rows = h.shape[0], kbuf.shape[1]
        q, k, v = self._qkv(h)
        slot, at = jnp.arange(b), jnp.minimum(pos, rows - 1)
        kbuf = kbuf.at[slot, at].set(k.astype(kbuf.dtype))
        vbuf = vbuf.at[slot, at].set(v.astype(vbuf.dtype))
        keep = jnp.arange(rows)[None, :] <= pos[:, None]
        with jax.named_scope("cache_attention"):
            scores = jnp.einsum(
                "bgrd,bkgd->bgrk",
                q.reshape(b, kbuf.shape[2], -1, cfg.head_dim),
                kbuf.astype(q.dtype),
                preferred_element_type=jnp.float32) * self._scale
            attn = _softmax_pv(scores, keep[:, None, None, :],
                               vbuf.astype(q.dtype), "bgrk,bkgd->bgrd")
        return self._out(attn, h.dtype), kbuf, vbuf


class LatentMoE(Layer):
    """The `E` mixer: the held relu^2 experts in the latent, between the two
    latent projections, plus the shared relu^2 expert on the full width."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        init = I.Normal(std=cfg.initializer_range)
        h, lat = cfg.hidden_size, cfg.moe_latent_size
        mat = lambda *shape: self.create_parameter(  # noqa: E731
            shape, dtype=cfg.dtype, default_initializer=init)
        self.experts = HeldExperts(
            h, cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.experts_held, dtype=cfg.dtype,
            std=cfg.initializer_range, selection_bias=True,
            scale=cfg.routed_scaling_factor, gated=False, d_expert=lat)
        self.fc1_latent_proj = mat(h, lat)
        self.fc2_latent_proj = mat(lat, h)
        si = cfg.moe_shared_expert_intermediate_size
        self.shared_up = mat(h, si)
        self.shared_down = mat(si, h)

    def forward(self, h, valid):
        """h (T, H) -> (output (T, H) float32, counts [picks here, picks in
        all, held experts hit, grouped products made, rows they went
        over])."""
        ex = self.experts
        latent = _mm(h, self.fc1_latent_proj).astype(h.dtype)
        y, here, hit, products, rows = moe_ffn_held.raw(
            h, unwrap(ex.router), None, unwrap(ex.up), unwrap(ex.down),
            ex.experts_held, ex.top_k, valid=valid,
            select_bias=unwrap(ex.e_score_correction_bias), scale=ex.scale,
            expert_x=latent)
        routed = _mm(y.astype(h.dtype), self.fc2_latent_proj)
        shared = _mm(_relu2(_mm(h, self.shared_up)).astype(h.dtype),
                     self.shared_down)
        counts = jnp.stack([here, jnp.sum(valid, dtype=jnp.int32) * ex.top_k,
                            hit, products, rows]).astype(jnp.int32)
        return routed + shared, counts


class NemotronHBlock(Layer):
    """`h = h + mixer(RMSNorm(h))`; `kind` is the pattern's letter."""

    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        self.norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.mixer = {MAMBA: Mamba2Mixer, MOE: LatentMoE,
                      ATTENTION: GQAttention}[kind](cfg)

    def _normed(self, x):
        return _rms(x, unwrap(self.norm),
                    self.cfg.layer_norm_epsilon).astype(x.dtype)

    @staticmethod
    def _add(x, y):
        return (x.astype(jnp.float32) + y).astype(x.dtype)

    def forward_seq(self, x, prompt_len, valid):
        """x (S, H), one sequence -> (x', what a cache holds of it (None
        for `E`), routed counts or None)."""
        h = self._normed(x)
        if self.kind == MOE:
            y, counts = self.mixer.forward(h, valid)
            return self._add(x, y), None, counts
        if self.kind == MAMBA:
            y, leaves = self.mixer.forward_seq(h, prompt_len)
        else:
            y, leaves = self.mixer.forward_seq(h)
        return self._add(x, y), leaves, None

    def forward_decode(self, x, leaves, pos, active):
        """x (B, H) -> (x', the layer's leaves, routed counts or None)."""
        h = self._normed(x)
        if self.kind == MOE:
            y, counts = self.mixer.forward(h, active)
            return self._add(x, y), leaves, counts
        if self.kind == MAMBA:
            y, *leaves = self.mixer.forward_decode(h, *leaves, active)
        else:
            y, *leaves = self.mixer.forward_decode(h, *leaves, pos, active)
        return self._add(x, y), tuple(leaves), None


class NemotronHForCausalLM(Layer):
    """Embedding, the blocks, the final norm and the untied head."""

    serving_batch_decode = True
    # what the programs' int32 counts are, in order, summed over layers (and
    # a decode call's steps): the routed family's five, the attention's rows
    # (held by the call's requests, gone over), and the Mamba state's
    # slot-layers (advanced for a request, read and written)
    serving_count_names = ("routed_here", "routed_all", "experts_hit",
                           "expert_products", "expert_rows",
                           "kv_rows_live", "kv_rows_pool",
                           "ssm_state_live", "ssm_state_pool")

    def __init__(self, cfg: NemotronHConfig = None, **kw):
        super().__init__()
        self.config = cfg = cfg or NemotronHConfig(**kw)
        init = I.Normal(std=cfg.initializer_range)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=init)
        self.layers = LayerList([NemotronHBlock(cfg, kind)
                                 for kind in cfg.pattern])
        self.norm_f = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), dtype=cfg.dtype,
            default_initializer=init)

    def _count(self, kind):
        return sum(k == kind for k in self.config.pattern)

    def _head(self, x):
        h = _rms(x, unwrap(self.norm_f), self.config.layer_norm_epsilon)
        return _mm(h.astype(x.dtype), self.lm_head)

    def _seq(self, ids, prompt_len, valid):
        """ids (S,) -> hidden (S, H), the leaves of every layer that holds a
        cache, routed counts summed."""
        x = unwrap(self.embed_tokens)[ids]
        caches, routed = [], jnp.zeros((5,), jnp.int32)
        for blk in self.layers:
            x, leaves, counts = blk.forward_seq(x, prompt_len, valid)
            if leaves is not None:
                caches.append(leaves)
            if counts is not None:
                routed = routed + counts
        return x, caches, routed

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V), float32; no cache."""
        ids = unwrap(input_ids).astype(jnp.int32)
        every = jnp.ones(ids.shape[1:], bool)
        return Tensor(jnp.stack([
            self._head(self._seq(row, row.shape[0], every)[0])
            for row in ids]))

    # --- the serving protocol (paddle_tpu.serving.ServingEngine) ---
    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        """The leaves of every layer that holds a cache, in layer order: an
        `M` layer's SSM state (B, heads, head_dim, state) float32 and conv
        state (B, conv_kernel - 1, conv_dim), neither with a row axis; a
        `*` layer's keys and values (B, max_length, kv_heads, head_dim)."""
        cfg, dt = self.config, dtype or self.config.dtype
        out = []
        for kind in cfg.pattern:
            if kind == MAMBA:
                out.append((
                    jnp.zeros((batch_size, cfg.mamba_num_heads,
                               cfg.mamba_head_dim, cfg.ssm_state_size),
                              jnp.float32),
                    jnp.zeros((batch_size, cfg.conv_kernel - 1,
                               cfg.conv_dim), dt)))
            elif kind == ATTENTION:
                kv = (batch_size, max_length, cfg.num_key_value_heads,
                      cfg.head_dim)
                out.append((jnp.zeros(kv, dt), jnp.zeros(kv, dt)))
        return out

    def serving_rows_held(self, pos):
        """What a slot with `pos` rows written holds that grows with it:
        the attention layers' rows (`serving_kv_rows{kind="full"}`); the
        Mamba state is the same size at every position."""
        return {"full": int(pos) * self._count(ATTENTION)}

    def _counts(self, routed, live, went_over, state_live, state_pool):
        attn, mamba = self._count(ATTENTION), self._count(MAMBA)
        return jnp.concatenate([routed, jnp.stack([
            live * attn, went_over * attn, state_live * mamba,
            state_pool * mamba]).astype(jnp.int32)])

    def forward_prefill(self, input_ids, prompt_len):
        """One prompt right-padded to its bucket, input_ids (1, S) ->
        (logits at the prompt's last position (1, 1, V), the leaves of
        every layer that holds a cache, counts).  The padding is routed
        nowhere and moves no state."""
        ids = unwrap(input_ids).astype(jnp.int32)[0]
        plen = unwrap(prompt_len)
        x, caches, routed = self._seq(ids, plen,
                                      jnp.arange(ids.shape[0]) < plen)
        last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=0)
        return (self._head(last)[None],
                [tuple(leaf[None] for leaf in leaves) for leaves in caches],
                self._counts(routed, plen, ids.shape[0], 1, 1))

    def forward_decode(self, tokens, caches, pos, active):
        """tokens, pos, active (B,): every slot's last token at its own
        position -> (logits (B, V) float32, caches, counts).  Attention goes
        over the whole pool; every slot's state is read and written."""
        pos, active = unwrap(pos), unwrap(active)
        x = unwrap(self.embed_tokens)[unwrap(tokens)]
        new, routed, rows = [], jnp.zeros((5,), jnp.int32), 0
        for blk in self.layers:
            leaves = None if blk.kind == MOE else tuple(
                unwrap(leaf) for leaf in caches[len(new)])
            x, leaves, counts = blk.forward_decode(x, leaves, pos, active)
            if blk.kind == MOE:
                routed = routed + counts
                continue
            if blk.kind == ATTENTION:
                rows = leaves[0].shape[1]
            new.append(leaves)
        b = pos.shape[0]
        live = jnp.sum(jnp.where(active, pos + 1, 0))
        return self._head(x), new, self._counts(
            routed, live, b * rows, jnp.sum(active, dtype=jnp.int32), b)
