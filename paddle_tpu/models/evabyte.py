"""EvaByte decoder (the `evabyte` model type, `attention_class: eva`; the
source's `config.json` keys): a byte-level pre-norm decoder, RMSNorm with a
unit offset (`x / rms(x) * (1 + g)`), gated MLP, untied head of
`num_pred_heads` x `vocab_size` columns, and EVA attention (Zheng et al.,
"Efficient Attention via Control Variates", ICLR 2023): a query at n sees,
under ONE softmax, the exact keys of its own `window_size`-aligned window
up to n, and of every EARLIER window one pooled summary a `chunk_size`
chunk.  A chunk's summary (head h, learned `adaptive_phi`, `adaptive_mu_k`
of head_dim numbers): `a = softmax_m(s * phi . k_m)` over the chunk's
rotated keys, `k_hat = sum a k + mu`, `v_hat = sum a v`.  Leaves are named
as the source names them (`layers.N.self_attn.adaptive_phi`, ...).

Two forms over one set of weights:

* a prompt (`forward`, `forward_prefill`): windows are independent given
  the summaries, so a bucket of W windows is W causal problems.  Window w's
  keys go in behind its `128 w` summaries and its queries are the last of
  those positions: under the causal mask with the diagonal `128 w` to the
  right a query then sees every summary and its window's keys up to
  itself, which is the flash kernel's forward as it is
  (`ops.flash_attention.flash_attention_grouped`; where it refuses, the
  chunked XLA form, `cohere_moe.attend_in_chunks`, with the queries behind
  `128 w` rows of zeros).  The summaries of all the prompt's chunks are
  computed once.
* a decode step (`forward_decode`): the token's key and value go into the
  ring at `n % window`, the query attends the ring's rows `r <= n %
  window` (rows beyond hold the window before) beside the summary rows `c
  < 128 (n // window)` (on the chip one kernel walks those rows alone,
  `ops.eva_decode_attention`; the masked products over the whole leaves,
  `masked_decode_attention`, where it refuses), and chunk `n // 16`'s
  summary is REWRITTEN EVERY STEP from the ring rows of the chunk so far
  (branch-free; the chunk's last step leaves the whole chunk's, and no
  query sees a summary of its own window, so an unfinished one is never
  read).

Serving: `gen_fixed_cache` gives a layer FOUR leaves of two lengths and two
clocks: the ring `K, V` (B, window, heads, head_dim), written every token,
and the summaries `K_hat, V_hat` (B, max_length / chunk, heads, head_dim),
written once a chunk at `n // chunk`.  A prompt's forward hands the ring
back in ring layout (the prompt's last window), never a bucket long.
`forward_prefill` / `forward_decode` are `CohereMoEForCausalLM`'s protocol
(`serving_batch_decode`); what their int32 counts are is said by
`serving_count_names`, which the engine carries into its spans unread, and
what a slot holds at a position by `serving_rows_held`.  A served byte's
logits are prediction head 0's (`lm_head`'s first `vocab_size` columns).

Weights, cache and products are `config.dtype`; sums into the residual
stream, norm statistics, both softmaxes (the pooling's and the attention's)
and logits are float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.errors import InvalidArgumentError
from ..core.tensor import Tensor, unwrap
from ..nn import initializer as I
from ..nn.functional.attention import _PATH_TAKEN as _ATTENTION_PATH
from ..nn.layer.container import LayerList
from ..nn.layer_base import Layer
from ..ops.eva_decode_attention import eva_decode_attention
from ..ops.flash_attention import flash_attention_grouped
from .cohere_moe import attend_in_chunks
from .deepseek_v3 import GatedMLP, _mm, _rms, _rope


class EvaByteConfig:
    """The source's keys, plus `initializer_range` and `dtype`."""

    def __init__(self, vocab_size=320, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=32,
                 attention_class="eva", window_size=2048, chunk_size=16,
                 num_pred_heads=8, max_position_embeddings=32768,
                 rope_theta=100000.0, rope_scaling=None, rms_norm_eps=1e-5,
                 norm_add_unit_offset=True, initializer_range=0.01275,
                 dtype="bfloat16"):
        for key, got, only in (
                ("attention_class", attention_class, "eva"),
                ("num_key_value_heads", num_key_value_heads,
                 num_attention_heads),
                ("rope_scaling", rope_scaling, None),
                ("norm_add_unit_offset", norm_add_unit_offset, True)):
            if got != only:
                raise InvalidArgumentError(
                    f"{key}={got!r}: this model has the form {key}={only!r} "
                    "alone (EVA attention, a key head a query head, plain "
                    "rotary frequencies, norms with a unit offset)")
        if window_size % chunk_size:
            raise InvalidArgumentError(
                f"window_size {window_size} is no multiple of chunk_size "
                f"{chunk_size}: a chunk would straddle the ring's end")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.window_size = window_size
        self.chunk_size = chunk_size
        self.num_pred_heads = num_pred_heads
        self.max_position_embeddings = max_position_embeddings
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype


def _rotate_halves(x, pos, theta):
    """x (T, heads, d) at pos (T,), float32 out: the pair (i, i + d/2)
    turned by pos * theta ** (-2i / d) and left where it lies.  The one
    rotary form the served models share turns ADJACENT pairs and writes
    them to halves (`deepseek_v3._rope`), so the halves are interleaved on
    the way in."""
    half = x.shape[-1] // 2
    return _rope(jnp.stack([x[..., :half], x[..., half:]],
                           axis=-1).reshape(x.shape), pos, theta)


def _summaries_seen(pos, window, chunk):
    """How many summary rows a query at `pos` sees: one a chunk of every
    EARLIER window, none of its own (a position, or an array of them)."""
    return pos // window * (window // chunk)


def _ring_keep(pos, rows):
    """(B, rows): the ring rows a query at pos (B,) sees, `r <= pos % rows`;
    the rows beyond hold the window before."""
    return jnp.arange(rows)[None] <= (pos % rows)[:, None]


def _unit_rms(x, g, eps):
    """RMSNorm with a unit offset: `x / rms(x) * (1 + g)`; float32 out."""
    return _rms(x, 1.0 + unwrap(g).astype(jnp.float32), eps)


def _pad_rows(a, multiple):
    """`a` with zero rows appended up to a multiple of `multiple`."""
    pad = -a.shape[0] % multiple
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a


class EvaAttention(Layer):
    """EVA's six leaves and its two forms."""

    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(std=cfg.initializer_range)
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        mat = lambda *shape: self.create_parameter(  # noqa: E731
            shape, dtype=cfg.dtype, default_initializer=init)
        self.q_proj, self.k_proj = mat(h, h), mat(h, h)
        self.v_proj, self.o_proj = mat(h, h), mat(h, h)
        self.adaptive_phi = mat(nh, h // nh)
        self.adaptive_mu_k = mat(nh, h // nh)

    # ------------------------------------------------------------ pieces
    @property
    def _scale(self):
        cfg = self.cfg
        return 1.0 / math.sqrt(cfg.hidden_size // cfg.num_attention_heads)

    def _qkv(self, h, pos):
        """h (T, H) at positions pos (T,) -> q, k, v (T, heads, head_dim)
        in h's dtype, q and k rotated."""
        t, nh = h.shape[0], self.cfg.num_attention_heads
        q, k, v = (_mm(h, w).reshape(t, nh, -1)
                   for w in (self.q_proj, self.k_proj, self.v_proj))
        theta = self.cfg.rope_theta
        return (_rotate_halves(q, pos, theta).astype(h.dtype),
                _rotate_halves(k, pos, theta).astype(h.dtype),
                v.astype(h.dtype))

    def _pool(self, k, v, keep=None):
        """k, v (N, chunk, heads, head_dim), N chunks of cached rows ->
        their summaries (N, heads, head_dim) twice, in k's dtype; `keep`
        (N, chunk) leaves rows out of a chunk."""
        scores = jnp.einsum("nchd,hd->nch", k, unwrap(self.adaptive_phi),
                            preferred_element_type=jnp.float32) * self._scale
        if keep is not None:
            scores = jnp.where(keep[..., None], scores, -1e30)
        a = jax.nn.softmax(scores, axis=1).astype(k.dtype)
        k_hat = jnp.einsum("nch,nchd->nhd", a, k,
                           preferred_element_type=jnp.float32)
        v_hat = jnp.einsum("nch,nchd->nhd", a, v,
                           preferred_element_type=jnp.float32)
        mu = unwrap(self.adaptive_mu_k).astype(jnp.float32)
        return (k_hat + mu).astype(k.dtype), v_hat.astype(k.dtype)

    def _out(self, attn):
        return _mm(attn.reshape(attn.shape[0], -1), self.o_proj)

    # --------------------------------------------------------- the prompt
    def forward_seq(self, h, prompt_len):
        """One sequence, h (S, H) of which `prompt_len` rows are the
        prompt's -> (attention's output (S, H) float32, what a cache holds:
        the ring's two leaves in ring layout (the window of the prompt's
        last position, at most `window_size` rows) and the summaries of
        every chunk, (ceil(S / chunk), heads, head_dim) twice)."""
        cfg, s = self.cfg, h.shape[0]
        win, chunk = cfg.window_size, cfg.chunk_size
        with jax.named_scope("eva_prefill_attention"):
            q, k, v = self._qkv(h, jnp.arange(s))
            # whole chunks, and past one window whole windows
            k, v = (_pad_rows(a, win if s > win else chunk) for a in (k, v))
            chunked = lambda a: a.reshape(  # noqa: E731
                (-1, chunk) + a.shape[1:])
            k_hat, v_hat = self._pool(chunked(k), chunked(v))
            out = []
            for w0 in range(0, s, win):
                w1 = min(w0 + win, s)
                seen = _summaries_seen(w0, win, chunk)
                # a batch of one where the rows are joined: the kernel's
                # zero rows at the tail then join them in the same pass
                behind = lambda a, b: jnp.concatenate(  # noqa: E731
                    [a[None, :seen], b[None, w0:w1]], axis=1)
                out.append(self._attend_window(
                    q[None, w0:w1], behind(k_hat, k), behind(v_hat, v)))
            attn = jnp.concatenate(out)
            rows = min(win, k.shape[0])
            at = (prompt_len - 1) // win * win
            ring = [jax.lax.dynamic_slice_in_dim(a, at, rows, axis=0)
                    for a in (k, v)]
        return self._out(attn.astype(h.dtype)), (*ring, k_hat, v_hat)

    def _attend_window(self, q, k, v):
        """A window's queries q (1, N, heads, head_dim), the last N of the
        positions of k, v (1, M, heads, head_dim), under the causal mask ->
        (N, heads, head_dim): query i sees the keys up to i + M - N.  The
        flash kernel's forward on the chip; where it refuses (not a TPU, a
        length no multiple of 128) the chunked XLA form, the queries behind
        M - N rows of zeros."""
        out = flash_attention_grouped(q, k, v)
        if out is not None:
            _ATTENTION_PATH.labels(path="flash").inc()
            return out[0]
        _ATTENTION_PATH.labels(path="xla").inc()
        q, k, v = q[0], k[0], v[0]
        front = k.shape[0] - q.shape[0]
        q = jnp.concatenate([jnp.zeros((front,) + q.shape[1:], q.dtype), q])
        return attend_in_chunks(q[:, :, None], k, v,
                                self._scale).reshape(q.shape)[front:]

    # ------------------------------------------------------- a decode step
    def forward_decode(self, h, ring_k, ring_v, sum_k, sum_v, pos,
                       active=None):
        """h (B, H): one token a slot at positions pos (B,); the ring (B,
        rows, heads, head_dim) twice and the summaries (B, chunks, heads,
        head_dim) twice; active (B,): the slots that hold a request (None:
        all) -> (attention's output (B, H) float32, the four leaves with the
        step's row and its chunk's summary written, the rows of them the
        attention went over).  On the chip one kernel walks each slot's
        live ring rows and summaries alone
        (`ops/eva_decode_attention.py`); where it refuses (not a TPU,
        leaves that are not bfloat16, rows or widths that are no whole
        blocks and tiles) the products go over the whole leaves behind a
        mask."""
        cfg, b = self.cfg, h.shape[0]
        chunk, rows = cfg.chunk_size, ring_k.shape[1]
        if active is None:
            active = jnp.ones((b,), bool)
        with jax.named_scope("eva_decode_attention"):
            q, k, v = self._qkv(h, pos)
            slot, at = jnp.arange(b), pos % rows
            ring_k = ring_k.at[slot, at].set(k.astype(ring_k.dtype))
            ring_v = ring_v.at[slot, at].set(v.astype(ring_v.dtype))
            seen = _summaries_seen(pos, cfg.window_size, chunk)
            walked = eva_decode_attention(q, ring_k, ring_v, sum_k, sum_v,
                                          at, seen - 1, active, self._scale)
            if walked is not None:
                _ATTENTION_PATH.labels(path="decode_kernel").inc()
                attn, went_over = walked
            else:
                _ATTENTION_PATH.labels(path="decode_xla").inc()
                attn = masked_decode_attention(
                    q, ring_k, ring_v, sum_k, sum_v, pos, seen, self._scale)
                went_over = jnp.int32(b * (rows + sum_k.shape[1]))
            # the chunk so far, out of the ring (16 divides it, so a chunk
            # is one row of the ring folded by chunks: a gather by slot and
            # chunk, which leaves the ring where it lies)
            so_far = lambda a: a.reshape(  # noqa: E731
                (b, rows // chunk, chunk) + a.shape[2:])[slot, at // chunk]
            k_hat, v_hat = self._pool(
                so_far(ring_k), so_far(ring_v),
                jnp.arange(chunk)[None] <= (at % chunk)[:, None])
            c = jnp.minimum(pos // chunk, sum_k.shape[1] - 1)
            sum_k = sum_k.at[slot, c].set(k_hat.astype(sum_k.dtype))
            sum_v = sum_v.at[slot, c].set(v_hat.astype(sum_v.dtype))
        return (self._out(attn.astype(h.dtype)),
                (ring_k, ring_v, sum_k, sum_v), went_over)


def masked_decode_attention(q, ring_k, ring_v, sum_k, sum_v, pos, seen,
                            scale):
    """The decode step's attention as products over the WHOLE leaves behind
    a mask: q (B, heads, head_dim) at pos (B,) against the ring's rows `r <=
    pos % rows` and the first `seen` (B,) summaries -> (B, heads, head_dim)
    float32; operands in q's dtype, scores, softmax and sums float32."""
    rows = ring_k.shape[1]
    score = lambda keys: jnp.einsum(  # noqa: E731
        "bhd,brhd->bhr", q, keys.astype(q.dtype),
        preferred_element_type=jnp.float32) * scale
    keep = jnp.concatenate(
        [_ring_keep(pos, rows),
         jnp.arange(sum_k.shape[1])[None] < seen[:, None]], axis=1)
    probs = jax.nn.softmax(jnp.where(
        keep[:, None], jnp.concatenate(
            [score(ring_k), score(sum_k)], axis=-1), -1e30), axis=-1)
    weigh = lambda p, vals: jnp.einsum(  # noqa: E731
        "bhr,brhd->bhd", p.astype(q.dtype), vals.astype(q.dtype),
        preferred_element_type=jnp.float32)
    return weigh(probs[..., :rows], ring_v) + weigh(probs[..., rows:], sum_v)


class EvaByteBlock(Layer):
    """`h = x + attn(rms(x))`, `out = h + mlp(rms(h))`, sums in float32."""

    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        zeros = lambda: self.create_parameter(  # noqa: E731
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(0.0))
        self.input_layernorm = zeros()
        self.self_attn = EvaAttention(cfg)
        self.post_attention_layernorm = zeros()
        self.mlp = GatedMLP(cfg, cfg.intermediate_size)

    def _normed(self, x, g):
        return _unit_rms(x, g, self.cfg.rms_norm_eps).astype(x.dtype)

    def _rest(self, x, attn):
        x = (x.astype(jnp.float32) + attn).astype(x.dtype)
        y = self.mlp.forward(self._normed(x, self.post_attention_layernorm))
        return (x.astype(jnp.float32) + y).astype(x.dtype)

    def forward_seq(self, x, prompt_len):
        attn, row = self.self_attn.forward_seq(
            self._normed(x, self.input_layernorm), prompt_len)
        return self._rest(x, attn), row

    def forward_decode(self, x, leaves, pos, active=None):
        attn, leaves, went_over = self.self_attn.forward_decode(
            self._normed(x, self.input_layernorm), *leaves, pos, active)
        return self._rest(x, attn), leaves, went_over


class EvaByteForCausalLM(Layer):
    """Embedding, the blocks, the final norm and the head of
    `num_pred_heads` x `vocab_size` columns."""

    serving_batch_decode = True
    # what the programs' int32 counts are, in order, summed over layers (and
    # a decode call's steps): ring rows and summaries the call's requests
    # hold, rows its attention went over, the summaries among the first
    serving_count_names = ("kv_rows_live", "kv_rows_pool", "kv_rows_summary")

    def __init__(self, cfg: EvaByteConfig = None, **kw):
        super().__init__()
        self.config = cfg = cfg or EvaByteConfig(**kw)
        init = I.Normal(std=cfg.initializer_range)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=init)
        self.layers = LayerList([EvaByteBlock(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.dtype,
            default_initializer=I.Constant(0.0))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.num_pred_heads * cfg.vocab_size),
            dtype=cfg.dtype, default_initializer=init)

    def _head(self, x, heads=1):
        """Logits of the first `heads` prediction heads, float32."""
        h = _unit_rms(x, self.norm, self.config.rms_norm_eps)
        return _mm(h.astype(x.dtype),
                   unwrap(self.lm_head)[:, :heads * self.config.vocab_size])

    def _seq(self, ids, prompt_len):
        x = unwrap(self.embed_tokens)[ids]
        rows = []
        for blk in self.layers:
            x, row = blk.forward_seq(x, prompt_len)
            rows.append(row)
        return x, rows

    def forward(self, input_ids):
        """input_ids (B, S) -> logits of every prediction head (B, S,
        num_pred_heads * V), float32, head j in columns [V j, V (j + 1));
        no cache."""
        ids = unwrap(input_ids).astype(jnp.int32)
        return Tensor(jnp.stack([
            self._head(self._seq(row, row.shape[0])[0],
                       self.config.num_pred_heads) for row in ids]))

    # --- the serving protocol (paddle_tpu.serving.ServingEngine) ---
    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        """A layer's four leaves: the ring's keys and values, `min(window,
        max_length)` rows, and the summaries', a row a chunk."""
        cfg, dt = self.config, dtype or self.config.dtype
        rest = (cfg.num_attention_heads,
                cfg.hidden_size // cfg.num_attention_heads)
        ring = (batch_size, min(cfg.window_size, max_length)) + rest
        chunks = (batch_size, -(-max_length // cfg.chunk_size)) + rest
        return [(jnp.zeros(ring, dt), jnp.zeros(ring, dt),
                 jnp.zeros(chunks, dt), jnp.zeros(chunks, dt))
                for _ in range(cfg.num_hidden_layers)]

    def _rows_at(self, n):
        """(live ring rows, summaries seen) of one layer with position n
        the last written."""
        cfg = self.config
        return (n % cfg.window_size + 1,
                _summaries_seen(n, cfg.window_size, cfg.chunk_size))

    def serving_rows_held(self, pos):
        """What a slot with `pos` rows written holds, by kind, over all
        layers: `serving_kv_rows{kind}`."""
        ring, summaries = self._rows_at(max(int(pos) - 1, 0))
        return {"window": ring * len(self.layers),
                "summary": summaries * len(self.layers)}

    def _counts(self, ring, summaries, went_over):
        return jnp.stack([ring + summaries, went_over,
                          summaries]).astype(jnp.int32) * len(self.layers)

    def forward_prefill(self, input_ids, prompt_len):
        """One prompt right-padded to its bucket, input_ids (1, S) ->
        (head 0's logits at the prompt's last position (1, 1, V), a layer
        its four leaves, counts).  The rows its attention went over are the
        bucket's and the summaries each of its windows read."""
        cfg = self.config
        ids = unwrap(input_ids).astype(jnp.int32)[0]
        plen = unwrap(prompt_len)
        x, rows = self._seq(ids, plen)
        last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=0)
        windows = -(-ids.shape[0] // cfg.window_size)
        read = (windows * (windows - 1) // 2
                * (cfg.window_size // cfg.chunk_size))
        return (self._head(last)[None],
                [tuple(leaf[None] for leaf in row) for row in rows],
                self._counts(*self._rows_at(plen - 1), ids.shape[0] + read))

    def forward_decode(self, tokens, caches, pos, active):
        """tokens, pos, active (B,): every slot's last token at its own
        position -> (head 0's logits (B, V) float32, caches, counts).  The
        rows attention went over: each slot's walked blocks of both leaves
        where the kernel ran, the whole leaves where the masked products
        did; every layer goes over the same."""
        pos, active = unwrap(pos), unwrap(active)
        x = unwrap(self.embed_tokens)[unwrap(tokens)]
        new = []
        for blk, leaves in zip(self.layers, caches):
            x, leaves, went_over = blk.forward_decode(
                x, tuple(unwrap(leaf) for leaf in leaves), pos, active)
            new.append(leaves)
        ring, summaries = (jnp.sum(jnp.where(active, n, 0))
                           for n in self._rows_at(pos))
        return self._head(x), new, self._counts(ring, summaries, went_over)
