"""evabyte (EvaByte/EvaByte, `config.json`; `attention_class: eva`): a
byte-level pre-norm decoder, `h = x + attn(rms(x))`, `out = h + mlp(rms(h))`,
`rms(x) = x / sqrt(mean(x^2) + eps) * (1 + g)` (`norm_add_unit_offset`),
`mlp(x) = (silu(x Wg) * (x Wu)) Wd`; final rms, then a head of 8 x 320
columns of which prediction head 0 (the first 320) is the next byte's.

EVA attention (Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", ICLR 2023), per head, `s = head_dim ** -0.5`:
  q, k, v = x Wq, x Wk, x Wv; q and k rotated at the token's position
  position m lies in chunk m // chunk and window m // window
  a chunk's summary, over its rotated keys: a_m = softmax_m(s * phi . k_m),
      k_hat = sum_m a_m k_m + mu,  v_hat = sum_m a_m v_m
  the query at n sees the exact rows {m : m // window = n // window, m <= n}
      and the summaries {c : c < (window / chunk) * (n // window)}: every
      chunk of every EARLIER window, none of its own, no exact row of an
      earlier one; scores s * q . k and s * q . k_hat under ONE softmax,
      o = sum p_m v_m + sum p_c v_hat_c;  attn = concat(o) Wo

Plain float32 `jax.numpy` at `highest`, no cache, one layer's weights at a
time (the caller hands `layer` one layer's leaves), attention a window at a
time so that 32,768 rows fit (32 heads x 2048 x 4096 scores = 1.07 GB).
Sizes `d` are `benchmark/arch/evabyte.py::dims`; the leaves are named there.
Imports nothing of the program.

Conventions the source's config does not fix, each also under `assumed` in
the configuration's file:
  * the rotation turns the pair (i, i + head_dim / 2) by pos * theta **
    (-2i / head_dim) and leaves it where it lies; no scaling of the
    frequencies (`rope_scaling` null);
  * keys are rotated BEFORE they are pooled, and the pooling's scores carry
    the factor s;
  * prediction head j is columns [320 j, 320 (j + 1)) of `lm_head`.
"""
import jax
import jax.numpy as jnp

from . import common as C


def rms(x, g, eps):
    """RMSNorm with a unit offset: x / sqrt(mean(x^2) + eps) * (1 + g)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + g)


def rope(x, pos, theta):
    """x (S, heads, r): pairs (i, i + r/2) turned by pos * theta ** (-2i /
    r), each left in its place."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]        # (S, r/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def summaries(k, v, phi, mu, d, precision):
    """k, v (S, heads, hd), S a multiple of the chunk -> k_hat, v_hat (S /
    chunk, heads, hd): a softmax-pooled row a chunk."""
    s, heads, hd = k.shape
    kc = k.reshape(s // d["chunk"], d["chunk"], heads, hd)
    vc = v.reshape(kc.shape)
    scores = C.mm(kc, phi, precision, "nchd,hd->nch").astype(
        jnp.float32) / jnp.sqrt(jnp.float32(hd))
    a = jax.nn.softmax(scores, axis=1)
    return (C.mm(a, kc, precision, "nch,nchd->nhd") + mu,
            C.mm(a, vc, precision, "nch,nchd->nhd"))


def eva(h, l, d, precision):
    """EVA attention of one sequence: h (S, H) -> (S, H)."""
    s0, heads = h.shape[0], d["heads"]
    win, chunk = d["window"], d["chunk"]
    # whole chunks, and past one window whole windows: rows behind the
    # sequence's end are seen by no row before it
    pad = -s0 % (win if s0 > win else chunk)
    h = jnp.pad(h, ((0, pad), (0, 0)))
    s = s0 + pad
    win = min(win, s)
    pos = jnp.arange(s)
    q, k, v = (C.mm(h, l[w], precision).reshape(s, heads, -1)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
    k_hat, v_hat = summaries(k, v, l["phi"], l["mu"], d, precision)
    scale = jnp.sqrt(jnp.float32(q.shape[-1]))
    in_window = jnp.tril(jnp.ones((win, win), bool))

    def one(w):
        """Window w's queries over its own rows and the summaries of the
        windows before it, under one softmax."""
        rows = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, w * win, win, 0)
        exact = C.mm(rows(q), rows(k), precision, "qhd,khd->hqk")
        pooled = C.mm(rows(q), k_hat, precision, "qhd,chd->hqc")
        earlier = jnp.arange(k_hat.shape[0]) < w * (win // chunk)
        scores = jnp.concatenate(
            [jnp.where(in_window, exact.astype(jnp.float32), -1e30),
             jnp.where(earlier, pooled.astype(jnp.float32), -1e30)],
            axis=-1) / scale
        p = jax.nn.softmax(scores, axis=-1)
        return (C.mm(p[..., :win], rows(v), precision, "hqk,khd->qhd")
                + C.mm(p[..., win:], v_hat, precision, "hqc,chd->qhd"))

    out = jax.lax.map(one, jnp.arange(s // win))
    return C.mm(out.reshape(s, -1)[:s0], l["wo"], precision)


def gated(h, wg, wu, wd, precision):
    """One gated MLP: (silu(h Wg) * (h Wu)) Wd."""
    a = jax.nn.silu(C.mm(h, wg, precision)) * C.mm(h, wu, precision)
    return C.mm(a, wd, precision)


def layer(x, l, kind, d, precision="float32"):
    """x (S, H) -> the block's output.  `kind` is "eva" for every layer."""
    x = x + eva(rms(x, l["ln1_g"], d["eps"]), l, d, precision)
    h = rms(x, l["ln2_g"], d["eps"])
    return x + gated(h, l["wg"], l["wu"], l["wd"], precision)


def embed(top, ids):
    return top["emb"][ids]


def head(top, x, d, precision="float32"):
    """The final norm, then prediction head 0: the next byte's V logits."""
    return C.mm(rms(x, top["lnf_g"], d["eps"]), top["head"][:, :d["V"]],
                precision).astype(jnp.float32)


def logits(top, layers, ids, d, precision="float32"):
    """ids (S,) -> (S, V); `layers` is a list of one layer's leaves each
    (the tests' sizes; the cell streams the layers, see the generator)."""
    x = embed(top, ids)
    for l in layers:
        x = layer(x, l, "eva", d, precision)
    return head(top, x, d, precision)
