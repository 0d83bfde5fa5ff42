"""DeepSeek-V3-family decoder (the `deepseek_v3` model type; Moonlight's
`config.json` keys): pre-norm RMSNorm blocks, multi-head LATENT attention
(MLA: keys and values of every head expanded from one normalised latent of
`kv_lora_rank` numbers a token, beside `qk_rope_head_dim` rotated key
numbers that all heads share), a leading dense gated MLP
(`first_k_dense_replace`), then routed experts under `noaux_tc` (sigmoid
scores, a selection bias that moves the choice and not the weight, top-k
weights normalised and scaled by `routed_scaling_factor`) beside shared
experts that are SUMMED (one gated MLP of `n_shared_experts` widths),
untied output head.  Leaves are named as the source names them
(`layers.N.self_attn.kv_a_proj_with_mqa`, `layers.N.mlp.shared_experts.
up_proj`, ...), the routed experts as `nn.HeldExperts` names its stacks.

Two attention paths over ONE set of weights:

* a prompt (`forward`, `forward_prefill`) EXPANDS the latent: `kv_b_proj`
  gives every head its `k_nope | v`, the rotated key numbers are repeated a
  head, and the sequence attends to itself (the flash kernel's forward on
  the chip, heads padded to 256 lanes; the chunked XLA form elsewhere);
* a decode step (`forward_decode`) ABSORBS `kv_b_proj` into the query and
  the output: `q_lat[h] = q_nope[h] Wk[h]`, the score is `q_lat . c +
  q_pe . k_pe` over the cached rows as they lie, `o[h] = (p c) Wv[h]`.
  `Wk` and `Wv` are views of the one leaf; no second copy is held.  On
  the chip score, softmax and output are ONE kernel that walks each slot's
  rows up to its own position (`ops/latent_decode_attention.py`); where
  that refuses, two products over the whole pool behind a mask.

Serving: `gen_fixed_cache` gives a layer TWO leaves that are no `(k, v)`
pair: `(B, rows, kv_lora_rank)`, the normalised latent, and `(B, rows,
qk_rope_head_dim)`, the rotated key numbers, in `config.dtype` (1,152
bytes a row a layer at 512 + 64 in bfloat16, where 16 heads of 192 + 128
would take 10,240).  One leaf of 576 numbers was measured too: a decode
step's attention over 48 slots x 8192 rows took 4.41 ms a layer against
1.67 ms with the two (PERF.md, PR 34: 576 lanes are 4.5 tiles of 128, and
the compiler re-lays the buffer out for its two products), so two it is.
`forward_prefill` and `forward_decode` are `CohereMoEForCausalLM`'s
protocol (`serving_batch_decode`); their int32 counts are its five
routed ones `[picks on held experts, picks in all, held experts hit,
grouped products made, rows they went over]` and two of the cache: `[rows
the call's requests hold, rows its attention went over]`, summed over
layers.

Weights and cache are held in `config.dtype`; norm statistics (both
RMSNorms and `kv_a_layernorm`), the router (weight and bias), softmax and
every sum into the residual stream are float32.
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
from ..ops.flash_attention import flash_attention_grouped
from ..ops.latent_decode_attention import mla_decode_attention
from .cohere_moe import attend_in_chunks

_FLASH_LANES = 256      # the kernel's head width that holds 192 and 128


class DeepseekV3Config:
    """The source's keys, plus `experts_held` (ids of the routed experts
    held here; default all) and `dtype`."""

    def __init__(self, vocab_size=163840, hidden_size=2048,
                 intermediate_size=11264, moe_intermediate_size=1408,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 num_attention_heads=16, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                 q_lora_rank=None, n_routed_experts=64,
                 num_experts_per_tok=6, n_shared_experts=2,
                 experts_held=None, scoring_func="sigmoid",
                 topk_method="noaux_tc", n_group=1, topk_group=1,
                 norm_topk_prob=True, routed_scaling_factor=2.446,
                 rope_theta=50000.0, rms_norm_eps=1e-5,
                 initializer_range=0.02, dtype="bfloat16"):
        for key, got, only in (
                ("q_lora_rank", q_lora_rank, None),
                ("scoring_func", scoring_func, "sigmoid"),
                ("topk_method", topk_method, "noaux_tc"),
                ("n_group", n_group, 1), ("topk_group", topk_group, 1),
                ("norm_topk_prob", norm_topk_prob, True)):
            if got != only:
                raise InvalidArgumentError(
                    f"{key}={got!r}: this model has the form {key}={only!r} "
                    "alone (no query latent, no group step in the router, "
                    "sigmoid scores with the top-k weights normalised)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kv_lora_rank = kv_lora_rank
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.experts_held = tuple(range(n_routed_experts)
                                  if experts_held is None else experts_held)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype


def _rms(x, g, eps):
    """RMSNorm, float32 statistics; float32 out."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + eps)) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, heads, d) at positions pos (T,), float32 out: the pair (2i,
    2i + 1) turned by pos * theta ** (-2i / d) and written to places (i,
    d/2 + i), as the `deepseek_v3` modelling code lays the result out (it
    de-interleaves, then rotates halves).  Queries and keys go through the
    same, so a score is that of adjacent pairs."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mm(x, w):
    return jnp.matmul(x, unwrap(w), preferred_element_type=jnp.float32)


class GatedMLP(Layer):
    """`(silu(x Wg) * (x Wu)) Wd`; float32 out."""

    def __init__(self, cfg: DeepseekV3Config, width: int):
        super().__init__()
        init = I.Normal(std=cfg.initializer_range)
        mat = lambda *shape: self.create_parameter(  # noqa: E731
            shape, dtype=cfg.dtype, default_initializer=init)
        self.gate_proj = mat(cfg.hidden_size, width)
        self.up_proj = mat(cfg.hidden_size, width)
        self.down_proj = mat(width, cfg.hidden_size)

    def forward(self, h):
        a = (jax.nn.silu(_mm(h, self.gate_proj)) * _mm(h, self.up_proj))
        return _mm(a.astype(h.dtype), self.down_proj)


class RoutedMLP(Layer):
    """The routed experts (`nn.HeldExperts` with `noaux_tc`'s bias and
    scale) plus the shared ones, one gated MLP added once, unweighted."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.experts = HeldExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.experts_held, dtype=cfg.dtype,
            std=cfg.initializer_range, selection_bias=True,
            scale=cfg.routed_scaling_factor)
        self.shared_experts = GatedMLP(
            cfg, cfg.n_shared_experts * cfg.moe_intermediate_size)

    def forward(self, h, valid):
        ex = self.experts
        y, here, hit, products, rows = moe_ffn_held.raw(
            h, unwrap(ex.router), unwrap(ex.gate), unwrap(ex.up),
            unwrap(ex.down), ex.experts_held, ex.top_k, valid=valid,
            select_bias=unwrap(ex.e_score_correction_bias), scale=ex.scale)
        counts = jnp.stack([here, jnp.sum(valid, dtype=jnp.int32) * ex.top_k,
                            hit, products, rows]).astype(jnp.int32)
        return (y.astype(jnp.float32)
                + self.shared_experts.forward(h)), counts


def masked_latent_attention(q_lat, q_pe, cbuf, pbuf, pos, scale, dtype):
    """A decode step's absorbed attention as two products over the WHOLE
    pool behind a mask: q_lat (B, heads, latent), q_pe (B, heads, rope)
    float32, cbuf (B, rows, latent), pbuf (B, rows, rope), pos (B,) ->
    `softmax(scale (q_lat . c + q_pe . k_pe)) c` over rows 0..pos, (B, heads,
    latent) float32.  Operands in `dtype`, float32 sums and softmax.  What
    `ops/latent_decode_attention.py` is held to, and what runs where that
    kernel refuses."""
    rows = cbuf.shape[1]
    latent = cbuf.astype(dtype)
    scores = (jnp.einsum("bhc,brc->bhr", q_lat.astype(dtype), latent,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhc,brc->bhr", q_pe.astype(dtype),
                           pbuf.astype(dtype),
                           preferred_element_type=jnp.float32)
              ) * scale
    keep = jnp.arange(rows)[None, None, :] <= pos[:, None, None]
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    return jnp.einsum("bhr,brc->bhc", probs.astype(dtype), latent,
                      preferred_element_type=jnp.float32)


class LatentAttention(Layer):
    """MLA's five leaves and its two paths."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(std=cfg.initializer_range)
        mat = lambda *shape: self.create_parameter(  # noqa: E731
            shape, dtype=cfg.dtype, default_initializer=init)
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        self.q_proj = mat(h, nh * (cfg.qk_nope_head_dim
                                   + cfg.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = mat(
            h, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        self.kv_a_layernorm = self.create_parameter(
            (cfg.kv_lora_rank,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.kv_b_proj = mat(cfg.kv_lora_rank,
                             nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = mat(nh * cfg.v_head_dim, h)

    # ------------------------------------------------------------ pieces
    def _query_and_row(self, h, pos):
        """h (T, H) at positions pos (T,) -> q_nope (T, heads, nope) and
        the rotated q_pe (T, heads, rope), both float32, and what a cache
        holds of each token, in h's dtype: the normalised latent (T,
        latent) and the key numbers rotated once for all heads (T, rope)."""
        cfg, t = self.cfg, h.shape[0]
        q = _mm(h, self.q_proj).reshape(t, cfg.num_attention_heads, -1)
        q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        ckv = _mm(h, self.kv_a_proj_with_mqa)
        c = _rms(ckv[:, :cfg.kv_lora_rank], unwrap(self.kv_a_layernorm),
                 cfg.rms_norm_eps)
        k_pe = _rope(ckv[:, None, cfg.kv_lora_rank:], pos, cfg.rope_theta)
        return (q_nope, _rope(q_pe, pos, cfg.rope_theta), c.astype(h.dtype),
                k_pe[:, 0].astype(h.dtype))

    def _kv_b(self):
        """`kv_b_proj` as (latent, heads, nope + v)."""
        cfg = self.cfg
        return unwrap(self.kv_b_proj).reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads, -1)

    @property
    def _scale(self):
        cfg = self.cfg
        return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

    def _out(self, attn):
        return _mm(attn.reshape(attn.shape[0], -1), self.o_proj)

    # --------------------------------------------------------- the prompt
    def forward_seq(self, h):
        """One sequence against itself under the causal mask, EXPANDED: h
        (S, H) -> (attention's output (S, H) float32, what a cache would
        hold: (S, latent) and (S, rope))."""
        cfg, s = self.cfg, h.shape[0]
        nh, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
        with jax.named_scope("mla_expanded_attention"):
            q_nope, q_pe, c, k_pe = self._query_and_row(h, jnp.arange(s))
            kv = jnp.einsum("sc,chd->shd", c, self._kv_b(),
                            preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope].astype(h.dtype), jnp.broadcast_to(
                    k_pe[:, None], (s, nh, cfg.qk_rope_head_dim))], axis=-1)
            v = kv[..., nope:].astype(h.dtype)
            attn = self._attend_seq(q, k, v)
        return self._out(attn.astype(h.dtype)), (c, k_pe)

    def _attend_seq(self, q, k, v):
        """q (S, heads, 192) float32, k (S, heads, 192), v (S, heads, 128)
        -> (S, heads, 128).  On the chip the flash kernel's forward with
        every head padded to the 256 lanes it knows (zeros add nothing to
        a score or an output; its 1/sqrt(256) is made up for in the
        query before the query is rounded); where the kernel refuses (not
        a TPU, a length that is no multiple of 128) the chunked XLA form
        (`cohere_moe.attend_in_chunks`, a key head a query head)."""
        dv = v.shape[-1]
        if max(q.shape[-1], dv) <= _FLASH_LANES:
            pad = lambda a: jnp.pad(  # noqa: E731
                a, ((0, 0), (0, 0), (0, _FLASH_LANES - a.shape[-1])))[None]
            fix = self._scale * math.sqrt(_FLASH_LANES)
            out = flash_attention_grouped(pad((q * fix).astype(k.dtype)),
                                          pad(k), pad(v))
            if out is not None:
                _ATTENTION_PATH.labels(path="flash").inc()
                return out[0, :, :, :dv]
        _ATTENTION_PATH.labels(path="xla").inc()
        return attend_in_chunks(q.astype(k.dtype)[:, :, None], k, v,
                                self._scale).reshape(q.shape[0], -1, dv)

    # ------------------------------------------------------- a decode step
    def forward_decode(self, h, cbuf, pbuf, pos, active=None):
        """h (B, H): one token a slot at positions pos (B,); cbuf (B, rows,
        latent), pbuf (B, rows, rope); active (B,): the slots that hold a
        request (None: all) -> (attention's output (B, H) float32, the two
        buffers with the step's row written at pos, the rows of them the
        attention went over).  ABSORBED: nothing a head wide is made of a
        cached row.  On the chip one kernel walks each slot's rows up to
        its own `pos` and reads a row once for score and output
        (`ops/latent_decode_attention.py`); where it refuses (not a TPU,
        leaves that are not bfloat16, rows or widths that are no whole
        blocks and lanes) the products go over the whole pool behind a
        mask."""
        cfg = self.cfg
        b, rows = h.shape[0], cbuf.shape[1]
        nope = cfg.qk_nope_head_dim
        if active is None:
            active = jnp.ones((b,), bool)
        with jax.named_scope("mla_absorbed_attention"):
            q_nope, q_pe, c, k_pe = self._query_and_row(h, pos)
            w = self._kv_b()
            q_lat = jnp.einsum("bhd,chd->bhc", q_nope.astype(h.dtype),
                               w[..., :nope],
                               preferred_element_type=jnp.float32)
            slot, at = jnp.arange(b), jnp.minimum(pos, rows - 1)
            cbuf = cbuf.at[slot, at].set(c.astype(cbuf.dtype))
            pbuf = pbuf.at[slot, at].set(k_pe.astype(pbuf.dtype))
            walked = mla_decode_attention(q_lat * self._scale,
                                          q_pe * self._scale, cbuf, pbuf,
                                          pos, active)
            if walked is not None:
                _ATTENTION_PATH.labels(path="decode_kernel").inc()
                o_lat, went_over = walked
            else:
                _ATTENTION_PATH.labels(path="decode_xla").inc()
                o_lat, went_over = masked_latent_attention(
                    q_lat, q_pe, cbuf, pbuf, pos, self._scale,
                    h.dtype), jnp.int32(b * rows)
            attn = jnp.einsum("bhc,chd->bhd", o_lat.astype(h.dtype),
                              w[..., nope:],
                              preferred_element_type=jnp.float32)
        return self._out(attn.astype(h.dtype)), cbuf, pbuf, went_over


class DeepseekV3Block(Layer):
    """`h = x + attn(rms(x))`, `out = h + mlp(rms(h))`; `routed` says which
    MLP the layer has."""

    def __init__(self, cfg: DeepseekV3Config, routed: bool):
        super().__init__()
        self.cfg, self.routed = cfg, routed
        ones = lambda: self.create_parameter(  # noqa: E731
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.input_layernorm = ones()
        self.self_attn = LatentAttention(cfg)
        self.post_attention_layernorm = ones()
        self.mlp = (RoutedMLP(cfg) if routed
                    else GatedMLP(cfg, cfg.intermediate_size))

    def _normed(self, x, g):
        return _rms(x, unwrap(g), self.cfg.rms_norm_eps).astype(x.dtype)

    def _add(self, x, y):
        return (x.astype(jnp.float32) + y).astype(x.dtype)

    def _mlp(self, x, valid):
        h = self._normed(x, self.post_attention_layernorm)
        if not self.routed:
            return (self._add(x, self.mlp.forward(h)),
                    jnp.zeros((5,), jnp.int32))
        y, counts = self.mlp.forward(h, valid)
        return self._add(x, y), counts

    def forward_seq(self, x, valid):
        """x (S, H), one sequence, no cache -> (x', what a cache would hold
        (two leaves), the routed counts)."""
        attn, row = self.self_attn.forward_seq(
            self._normed(x, self.input_layernorm))
        x, counts = self._mlp(self._add(x, attn), valid)
        return x, row, counts

    def forward_decode(self, x, cbuf, pbuf, pos, active):
        """-> (x', the two buffers, the routed counts, the rows of the
        buffers the attention went over)."""
        attn, cbuf, pbuf, went_over = self.self_attn.forward_decode(
            self._normed(x, self.input_layernorm), cbuf, pbuf, pos, active)
        x, counts = self._mlp(self._add(x, attn), active)
        return x, cbuf, pbuf, counts, went_over


class DeepseekV3ForCausalLM(Layer):
    """Embedding, the blocks, the final norm and the untied head."""

    serving_batch_decode = True
    # what `serving_kv_rows{kind}` calls a layer's rows
    serving_cache_kind = "latent"

    def __init__(self, cfg: DeepseekV3Config = None, **kw):
        super().__init__()
        self.config = cfg = cfg or DeepseekV3Config(**kw)
        init = I.Normal(std=cfg.initializer_range)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=init)
        self.layers = LayerList([
            DeepseekV3Block(cfg, routed=i >= cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
        self.norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), dtype=cfg.dtype,
            default_initializer=init)

    def _head(self, x):
        h = _rms(x, unwrap(self.norm), self.config.rms_norm_eps)
        return _mm(h.astype(x.dtype), self.lm_head)

    def _seq(self, ids, valid=None):
        """ids (S,) -> hidden (S, H), [(latent, rope rows)] a layer, routed
        counts."""
        if valid is None:
            valid = jnp.ones(ids.shape, bool)
        x = unwrap(self.embed_tokens)[ids]
        rows, counts = [], 0
        for blk in self.layers:
            x, row, c = blk.forward_seq(x, valid)
            rows.append(row)
            counts = counts + c
        return x, rows, counts

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V), float32; no cache."""
        ids = unwrap(input_ids).astype(jnp.int32)
        return Tensor(jnp.stack([self._head(self._seq(row)[0])
                                 for row in ids]))

    # --- the serving protocol (paddle_tpu.serving.ServingEngine) ---
    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        """A layer's two leaves: the latent (B, max_length, kv_lora_rank)
        and the rotated key numbers (B, max_length, qk_rope_head_dim)."""
        cfg, dt = self.config, dtype or self.config.dtype
        at = (batch_size, max_length)
        return [(jnp.zeros(at + (cfg.kv_lora_rank,), dt),
                 jnp.zeros(at + (cfg.qk_rope_head_dim,), dt))
                for _ in range(cfg.num_hidden_layers)]

    def _cache_counts(self, live, went_over):
        return jnp.stack([live, went_over]).astype(jnp.int32) * len(
            self.layers)

    def forward_prefill(self, input_ids, prompt_len):
        """One prompt right-padded to its bucket, input_ids (1, S) ->
        (logits at the prompt's last position (1, 1, V), [((1, S, latent),
        (1, S, rope))] a layer, counts).  The padding is routed nowhere."""
        ids = unwrap(input_ids).astype(jnp.int32)[0]
        plen = unwrap(prompt_len)
        x, rows, counts = self._seq(ids, jnp.arange(ids.shape[0]) < plen)
        last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=0)
        counts = jnp.concatenate(
            [counts, self._cache_counts(plen, ids.shape[0])])
        return (self._head(last)[None],
                [(c[None], k_pe[None]) for c, k_pe in rows], counts)

    def forward_decode(self, tokens, caches, pos, active):
        """tokens, pos, active (B,): every slot's last token at its own
        position -> (logits (B, V) float32, caches, counts).  The rows
        attention went over are what each layer's form really read: the
        whole leaves behind a mask, or each slot's blocks up to `pos`."""
        pos, active = unwrap(pos), unwrap(active)
        x = unwrap(self.embed_tokens)[unwrap(tokens)]
        new, counts, went_over = [], 0, 0
        for blk, (cbuf, pbuf) in zip(self.layers, caches):
            x, cbuf, pbuf, c, rows = blk.forward_decode(
                x, unwrap(cbuf), unwrap(pbuf), pos, active)
            new.append((cbuf, pbuf))
            counts, went_over = counts + c, went_over + rows
        live = jnp.sum(jnp.where(active, pos + 1, 0)) * len(self.layers)
        counts = jnp.concatenate(
            [counts, jnp.stack([live, went_over]).astype(jnp.int32)])
        return self._head(x), new, counts
