"""cohere2_moe (CohereLabs command-a-plus-05-2026, `config.json`): a parallel
attention + FFN block under one scale-only norm, grouped KV heads, three
window layers (rotary positions, `rope_gptj` pairs) to one full layer (no
positions), sigmoid-routed experts with normalised top-k weights beside
averaged shared experts, tied output head.

Plain float32 `jax.numpy` at `highest`, one layer's weights at a time (the
caller hands `layer` one layer's leaves), attention in query blocks so that
8192 rows fit.  Sizes `d` are `benchmark/arch/cohere2_moe.py::dims`; the
leaves are named there.

Departures from the source, each also under `assumed` / `reduced_why` in the
configuration's file:
  * only the experts in `d["held"]` have weights here (this chip's share of
    a layer that eight chips divide); a token's picks that fall on other
    experts add nothing, in the program alike, and that partial sum is what
    goes on to the next layer.  The router keeps its published width and
    experts per token, and the top-k weights are normalised over all k.
  * "average" is read as the mean of the shared experts' outputs, added to
    the routed sum.
  * full-attention layers rotate nothing ("global NoPE").
  * the vocabulary is this chip's slice; logits are over the slice.
  * the vision tower is left out.
"""
import jax
import jax.numpy as jnp

from . import common as C

QUERY_BLOCK = 256


def norm(x, g, eps):
    """LayerNorm without bias: (x - mean) / sqrt(var + eps) * g."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g


def rope_gptj(x, pos, theta):
    """x (S, heads, hd) rotated in adjacent pairs (2i, 2i+1) by
    pos * theta ** (-2i / hd), over all hd numbers (`rotary_pct` 1)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, window, precision):
    """q (S, Hq, hd), k / v (S, Hkv, hd); query head i reads KV head
    i // (Hq / Hkv); position i sees j <= i, and with a window only
    i - j < window.  One block of queries at a time."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    q = q.reshape(s, hkv, hq // hkv, hd)
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    starts = jnp.arange(0, s + pad, block)
    j = jnp.arange(s)[None, :]

    def one(i0):
        qb = jax.lax.dynamic_slice_in_dim(qp, i0, block, 0)
        scores = C.mm(qb, k, precision, "qgrd,kgd->grqk").astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hd))
        i = i0 + jnp.arange(block)[:, None]
        keep = j <= i
        if window is not None:
            keep = keep & (i - j < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return C.mm(probs, v, precision, "grqk,kgd->qgrd")

    out = jax.lax.map(one, starts)                 # (nb, block, g, r, hd)
    return out.reshape(s + pad, hq * hd)[:s]


def gated(h, wg, wu, wd, precision):
    """One expert: (silu(h Wg) * (h Wu)) Wd."""
    a = jax.nn.silu(C.mm(h, wg, precision)) * C.mm(h, wu, precision)
    return C.mm(a, wd, precision)


def route(h, router, d, precision):
    """-> (S, E) weights: a token's k largest sigmoid scores over their
    sum (`norm_topk_prob`), 0 for every other expert."""
    s = jax.nn.sigmoid(C.mm(h, router, precision).astype(jnp.float32))
    top, idx = jax.lax.top_k(s, d["K"])
    top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def ffn(h, l, d, precision, held=None):
    """Routed sum over the held experts plus the mean of the shared ones."""
    held = d["held"] if held is None else held
    w = route(h, l["router"], d, precision)[:, jnp.asarray(held)]  # (S, n)

    def routed(acc, ew):
        wg, wu, wd, we = ew
        return acc + we[:, None] * gated(h, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(routed, jnp.zeros_like(h),
                        (l["eg"], l["eu"], l["ed"], w.T))

    def shared(acc, sw):
        return acc + gated(h, *sw, precision), None

    z, _ = jax.lax.scan(shared, jnp.zeros_like(h),
                        (l["sg"], l["su"], l["sd"]))
    return y + z / d["S"]


def layer(x, l, kind, d, precision="float32"):
    """x (S, H) -> x + attention(norm x) + ffn(norm x): the parallel block,
    one norm.  `kind` is the layer's entry of `layer_types`."""
    s = x.shape[0]
    h = norm(x, l["ln_g"], d["eps"])
    q = C.mm(h, l["wq"], precision).reshape(s, d["heads"], d["hd"])
    k = C.mm(h, l["wk"], precision).reshape(s, d["kv_heads"], d["hd"])
    v = C.mm(h, l["wv"], precision).reshape(s, d["kv_heads"], d["hd"])
    window = None
    if kind == "sliding_attention":
        pos = jnp.arange(s)
        q, k = rope_gptj(q, pos, d["theta"]), rope_gptj(k, pos, d["theta"])
        window = d["window"]
    a = attention(q, k, v, window, precision)
    return x + C.mm(a, l["wo"], precision) + ffn(h, l, d, precision)


def embed(top, ids):
    return top["emb"][ids]


def head(top, x, d, precision="float32"):
    """The same norm, then the tied head over this chip's rows."""
    h = norm(x, top["lnf_g"], d["eps"])
    return d["logit_scale"] * C.mm(h, top["emb"].T, precision).astype(
        jnp.float32)


def logits(top, layers, ids, d, precision="float32"):
    """ids (S,) -> (S, V); `layers` is a list of one layer's leaves each
    (the tests' sizes; the cell streams the layers, see the generator)."""
    x = embed(top, ids)
    for l, kind in zip(layers, d["kinds"]):
        x = layer(x, l, kind, d, precision)
    return head(top, x, d, precision)
