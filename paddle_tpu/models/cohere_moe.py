"""Cohere2-MoE decoder (the `cohere2_moe` family; command-a-plus's
`config.json` keys): a parallel attention + FFN block under one scale-only
LayerNorm, grouped KV heads, window layers with rotary positions
(`rope_gptj`: adjacent pairs) beside full layers that rotate nothing,
sigmoid-routed experts (normalised top-k, nothing dropped) beside shared
experts that are averaged, tied output head.

Serving: `gen_fixed_cache` gives a window layer a RING of `min(window,
max_length)` rows, written at `pos % rows`, and a full layer `max_length`
rows; `forward_prefill` runs one padded prompt and `forward_decode` the
WHOLE batch of slots at once, a position a slot, so a layer routes once a
step and its experts see every slot's token in one grouped product
(`serving_batch_decode`: the engine asks for it).  A prompt of any bucket
goes through the routed layer once a layer too: `F.moe_ffn_held` walks the
picks held here in chunks and makes nothing as wide as a row for the rest.
Both return, beside their outputs, int32 counts `[picks on held experts,
picks in all, held experts hit, grouped products made, rows those products
went over]` summed over layers.

Weights and cache are held in `config.dtype`; norm statistics, the router,
softmax and every sum into the residual stream are float32.
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

SLIDING, FULL = "sliding_attention", "full_attention"
_QUERY_BLOCK = 256      # prefill attention: queries a block (scores fit)
_KEY_CHUNK = 2048       # and keys a pass of the running softmax


class CohereMoEConfig:
    """The source's keys, plus `experts_held` (ids of the routed experts
    this chip holds; default all) and `dtype`."""

    def __init__(self, vocab_size=262144, hidden_size=4096,
                 intermediate_size=4096, num_hidden_layers=32,
                 num_attention_heads=128, num_key_value_heads=8,
                 head_dim=128, layer_types=None, layer_switch=4,
                 sliding_window=4096, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=4,
                 experts_held=None, expert_selection_fn="sigmoid",
                 norm_topk_prob=True, rope_theta=50000.0,
                 layer_norm_eps=1e-5, logit_scale=1.0,
                 initializer_range=0.02, dtype="bfloat16"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        if layer_types is None:   # local_attn_first: every 4th layer full
            layer_types = [FULL if (i + 1) % layer_switch == 0 else SLIDING
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types[:num_hidden_layers])
        self.sliding_window = sliding_window
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.experts_held = tuple(range(num_experts) if experts_held is None
                                  else experts_held)
        if expert_selection_fn != "sigmoid" or norm_topk_prob is not True:
            raise InvalidArgumentError(
                "the routed layer (`F.moe_ffn_held`) has one form, sigmoid "
                "scores with the top-k weights normalised; got "
                f"expert_selection_fn={expert_selection_fn!r}, "
                f"norm_topk_prob={norm_topk_prob!r}")
        self.expert_selection_fn = expert_selection_fn
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = float(rope_theta)
        self.layer_norm_eps = layer_norm_eps
        self.logit_scale = float(logit_scale)
        self.initializer_range = initializer_range
        self.dtype = dtype


def _norm(x, g, eps):
    """Scale-only LayerNorm, float32 statistics; float32 out."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, heads, hd) rotated in adjacent pairs at positions pos (T,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _softmax_pv(scores, keep, v, spec):
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    return jnp.einsum(spec, probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def attend_in_chunks(q, k, v, scale, window=None):
    """Causal attention of one sequence in XLA, a block of queries at a
    time: q (S, Hkv, r, hd) (`r` query heads a KV head), k (S, Hkv, hd), v
    (S, Hkv, dv) -> (S, Hkv * r * dv) float32; with `window` a query sees
    its last `window` keys.  The float32 scores of 128 heads x 8192 x 8192
    do not fit whole.  Each group's keys are read once for its heads; a
    block reads only the keys its mask can keep, `_KEY_CHUNK` of them at a
    time under a running maximum and sum (one softmax over more is slow on
    the chip: PERF.md, PR 30)."""
    s = q.shape[0]
    per_query = lambda a: jnp.transpose(a, (2, 0, 1))[..., None]  # noqa
    out = []
    for i0 in range(0, s, _QUERY_BLOCK):
        i1 = min(i0 + _QUERY_BLOCK, s)
        lo = max(0, i0 - window + 1) if window else 0
        i = jnp.arange(i0, i1)[:, None]
        m = total = acc = None
        for c0 in range(lo, i1, _KEY_CHUNK):
            c1 = min(c0 + _KEY_CHUNK, i1)
            scores = jnp.einsum(
                "qgrd,kgd->grqk", q[i0:i1], k[c0:c1],
                preferred_element_type=jnp.float32) * scale
            j = jnp.arange(c0, c1)[None, :]
            keep = j <= i
            if window:
                keep = keep & (i - j < window)
            scores = jnp.where(keep, scores, -1e30)
            # a chunk that is all masked for a row leaves that row's
            # garbage under a maximum of -1e30, which the first real
            # maximum wipes (every row keeps j = i)
            top = jnp.max(scores, axis=-1)
            new_m = top if m is None else jnp.maximum(m, top)
            p = jnp.exp(scores - new_m[..., None])
            pv = jnp.einsum("grqk,kgd->qgrd", p.astype(v.dtype), v[c0:c1],
                            preferred_element_type=jnp.float32)
            if m is None:
                total, acc = jnp.sum(p, axis=-1), pv
            else:
                fade = jnp.exp(m - new_m)
                total = total * fade + jnp.sum(p, axis=-1)
                acc = acc * per_query(fade) + pv
            m = new_m
        out.append(acc / per_query(total))
    return jnp.concatenate(out, axis=0).reshape(s, -1)


class CohereMoEBlock(Layer):
    def __init__(self, cfg: CohereMoEConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.cfg = cfg
        init = I.Normal(std=cfg.initializer_range)
        h, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads

        def mat(*shape):
            p = self.create_parameter(shape, dtype=dt,
                                      default_initializer=init)
            # a leaf at a time, for `HeldExperts`' reason
            jax.block_until_ready(unwrap(p))
            return p

        self.norm_scale = self.create_parameter(
            (h,), dtype=dt, default_initializer=I.Constant(1.0))
        self.q_proj, self.k_proj = mat(h, nq * hd), mat(h, nkv * hd)
        self.v_proj, self.o_proj = mat(h, nkv * hd), mat(nq * hd, h)
        self.experts = HeldExperts(
            h, cfg.intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.experts_held, dtype=dt,
            std=cfg.initializer_range)
        s, i = cfg.num_shared_experts, cfg.intermediate_size
        self.shared_gate, self.shared_up = mat(s, h, i), mat(s, h, i)
        self.shared_down = mat(s, i, h)

    # ------------------------------------------------------------ pieces
    def _qkv(self, h, pos):
        """h (T, H) -> q (T, Hq, hd), k, v (T, Hkv, hd); window layers
        rotate q and k at `pos` (T,), full layers rotate nothing."""
        cfg, t = self.cfg, h.shape[0]
        q = (h @ unwrap(self.q_proj)).reshape(t, -1, cfg.head_dim)
        k = (h @ unwrap(self.k_proj)).reshape(t, -1, cfg.head_dim)
        v = (h @ unwrap(self.v_proj)).reshape(t, -1, cfg.head_dim)
        if self.kind == SLIDING:
            q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos,
                                                        cfg.rope_theta)
        return q, k, v

    def _ffn(self, h, valid):
        """Routed part of the held experts + the mean of the shared ones,
        float32; counts [picks here, picks in all, held experts hit,
        grouped products made, rows they went over]."""
        cfg = self.cfg
        ex = self.experts
        if valid is None:
            valid = jnp.ones((h.shape[0],), bool)
        y, here, hit, products, rows = moe_ffn_held.raw(
            h, unwrap(ex.router), unwrap(ex.gate), unwrap(ex.up),
            unwrap(ex.down), ex.experts_held, ex.top_k, valid=valid)
        g = jnp.einsum("th,shi->tsi", h, unwrap(self.shared_gate))
        u = jnp.einsum("th,shi->tsi", h, unwrap(self.shared_up))
        a = (jax.nn.silu(g.astype(jnp.float32)) * u).astype(h.dtype)
        shared = jnp.einsum("tsi,sih->th", a, unwrap(self.shared_down),
                            preferred_element_type=jnp.float32)
        counts = jnp.stack([here, jnp.sum(valid, dtype=jnp.int32) * ex.top_k,
                            hit, products, rows]).astype(jnp.int32)
        return (y.astype(jnp.float32)
                + shared / cfg.num_shared_experts), counts

    def _attend_seq(self, q, k, v):
        """One sequence against itself under the causal mask, a window
        layer's queries over their last `sliding_window` keys: q (S, Hq,
        hd), k, v (S, Hkv, hd) -> (S, Hq * hd).  On the chip the flash
        kernel's grouped forward (`ops/flash_attention.py`: a KV head read
        once where it lies for its 16 query heads, the walk started at the
        window's edge; a full layer at 8192 rows 20.2 ms, a window layer
        16.6, where the XLA form took 75 and 56: PERF.md, PR 33); where the
        kernel refuses (not a TPU, a length that is no multiple of 128)
        the chunked XLA form."""
        window = self.cfg.sliding_window if self.kind == SLIDING else None
        with jax.named_scope("window_attention" if window
                             else "full_attention"):
            out = flash_attention_grouped(q[None], k[None], v[None],
                                          window=window)
            if out is not None:
                _ATTENTION_PATH.labels(path="flash").inc()
                return out.reshape(q.shape[0], -1)
            _ATTENTION_PATH.labels(path="xla").inc()
            return self._attend_in_chunks(q, k, v, window)

    def _attend_in_chunks(self, q, k, v, window):
        cfg = self.cfg
        q = q.reshape(q.shape[0], k.shape[1], -1, cfg.head_dim)
        return attend_in_chunks(q, k, v, 1.0 / math.sqrt(cfg.head_dim),
                                window)

    def _out(self, x, attn, ffn):
        o = jnp.matmul(attn.astype(x.dtype), unwrap(self.o_proj),
                       preferred_element_type=jnp.float32)
        return (x.astype(jnp.float32) + o + ffn).astype(x.dtype)

    # ------------------------------------------------------------- entries
    def forward_seq(self, x, valid=None):
        """x (S, H), one sequence, no cache -> (x', k, v, counts); k and v
        (S, Hkv, hd) are what a cache would hold (keys rotated)."""
        h = _norm(x, unwrap(self.norm_scale), self.cfg.layer_norm_eps
                  ).astype(x.dtype)
        q, k, v = self._qkv(h, jnp.arange(x.shape[0]))
        ffn, counts = self._ffn(h, valid)
        return self._out(x, self._attend_seq(q, k, v), ffn), k, v, counts

    def forward_decode(self, x, kbuf, vbuf, pos, active):
        """x (B, H): one token a slot at positions pos (B,); kbuf / vbuf
        (B, rows, Hkv, hd).  A window layer's buffer is a ring written at
        pos % rows; rows whose slot is not `active` are routed nowhere."""
        cfg = self.cfg
        b, rows = x.shape[0], kbuf.shape[1]
        h = _norm(x, unwrap(self.norm_scale), cfg.layer_norm_eps
                  ).astype(x.dtype)
        q, k, v = self._qkv(h, pos)
        ring = self.kind == SLIDING
        at = pos % rows if ring else jnp.minimum(pos, rows - 1)
        slot = jnp.arange(b)
        kbuf = kbuf.at[slot, at].set(k.astype(kbuf.dtype))
        vbuf = vbuf.at[slot, at].set(v.astype(vbuf.dtype))
        r = jnp.arange(rows)[None, :]
        p = pos[:, None]
        # a ring's row r holds position p - ((p - r) mod rows), if any
        keep = ((p - (p - r) % rows) >= 0) if ring else (r <= p)
        with jax.named_scope("ring_attention" if ring
                             else "cache_attention"):
            scores = jnp.einsum(
                "bgrd,bkgd->bgrk",
                q.reshape(b, kbuf.shape[2], -1, cfg.head_dim),
                kbuf.astype(q.dtype), preferred_element_type=jnp.float32
            ) / math.sqrt(cfg.head_dim)
            attn = _softmax_pv(scores, keep[:, None, None, :],
                               vbuf.astype(q.dtype), "bgrk,bkgd->bgrd")
        ffn, counts = self._ffn(h, active)
        return self._out(x, attn.reshape(b, -1), ffn), kbuf, vbuf, counts


class CohereMoEForCausalLM(Layer):
    """Embedding, the blocks, the final norm and the tied head."""

    serving_batch_decode = True

    def __init__(self, cfg: CohereMoEConfig = None, **kw):
        super().__init__()
        self.config = cfg = cfg or CohereMoEConfig(**kw)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=I.Normal(std=cfg.initializer_range))
        self.layers = LayerList([CohereMoEBlock(cfg, kind)
                                 for kind in cfg.layer_types])
        self.final_norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(1.0))

    def _head(self, x):
        cfg = self.config
        h = _norm(x, unwrap(self.final_norm), cfg.layer_norm_eps
                  ).astype(x.dtype)
        return cfg.logit_scale * jnp.matmul(
            h, unwrap(self.embed_tokens).T,
            preferred_element_type=jnp.float32)

    def _seq(self, ids, valid=None):
        """ids (S,) -> hidden (S, H), [(k, v)] a layer, counts."""
        x = unwrap(self.embed_tokens)[ids]
        kv, counts = [], 0
        for blk in self.layers:
            x, k, v, c = blk.forward_seq(x, valid)
            kv.append((k, v))
            counts = counts + c
        return x, kv, counts

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V), float32; no cache."""
        ids = unwrap(input_ids).astype(jnp.int32)
        return Tensor(jnp.stack([self._head(self._seq(row)[0])
                                 for row in ids]))

    # --- the serving protocol (paddle_tpu.serving.ServingEngine) ---
    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        """[(k, v)] a layer, each (B, rows, Hkv, hd): a full layer holds
        `max_length` rows, a window layer a ring of min(window, that)."""
        cfg = self.config
        dt = dtype or cfg.dtype
        out = []
        for kind in cfg.layer_types:
            rows = (min(cfg.sliding_window, max_length) if kind == SLIDING
                    else max_length)
            shape = (batch_size, rows, cfg.num_key_value_heads, cfg.head_dim)
            out.append((jnp.zeros(shape, dt), jnp.zeros(shape, dt)))
        return out

    def forward_prefill(self, input_ids, prompt_len):
        """One prompt right-padded to its bucket, input_ids (1, S) ->
        (logits at the prompt's last position (1, 1, V), [(k, v)] a layer
        each (1, S, Hkv, hd), counts).  The padding is routed nowhere."""
        ids = unwrap(input_ids).astype(jnp.int32)[0]
        plen = unwrap(prompt_len)
        x, kv, counts = self._seq(ids, jnp.arange(ids.shape[0]) < plen)
        last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=0)
        return (self._head(last)[None], [(k[None], v[None]) for k, v in kv],
                counts)

    def forward_decode(self, tokens, caches, pos, active):
        """tokens, pos, active (B,): every slot's last token at its own
        position -> (logits (B, V) float32, caches, counts)."""
        pos, active = unwrap(pos), unwrap(active)
        x = unwrap(self.embed_tokens)[unwrap(tokens)]
        new, counts = [], 0
        for blk, (kbuf, vbuf) in zip(self.layers, caches):
            x, kbuf, vbuf, c = blk.forward_decode(x, unwrap(kbuf),
                                                  unwrap(vbuf), pos, active)
            new.append((kbuf, vbuf))
            counts = counts + c
        return self._head(x), new, counts
