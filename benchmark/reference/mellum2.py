"""mellum (JetBrains Mellum2-12B-A2.5B-Instruct, `config.json`): a
sequential pre-norm block, `x += Attn(RMSNorm(x))` then `x +=
MoE(RMSNorm(x))`; grouped KV heads; three window layers (a query sees keys
j with i - j < 1024, default rotary positions) to one full layer (causal,
YaRN rotary positions); every layer's FFN routed: softmax over all experts,
top-8, the 8 weights renormalised, SwiGLU experts, no shared expert; final
RMSNorm and an untied head.  Its loss is the mean next-token cross entropy
over the vocabulary's slice.

Plain float32 `jax.numpy` at `highest`, for training: loss and gradients
of a batch, a row at a time; the layers in one `scan`, each recomputed
under `jax.checkpoint`; attention a block of queries at a time over all
keys under the layer's mask, and the held experts dense over blocks of
tokens, each block recomputed too, so that 32 x 8192 x 8192 scores or 8192
x 16 x 896 hidden rows are never held beside the float32 state.  (One
body for every layer compiles in a fraction of the time four would.)

The published constants (the window, top-8, the layer pattern, the rotary
parameters, the norm's epsilon) are this module's; `configure` sets them
from a configuration's file (the benchmark's architecture module calls it,
so that a cut test configuration runs the same code), and the rest is read
from the weights' shapes.  Departures from the source, each also under
`assumed` / `reduced_why` in the configuration's file:
  * only the experts 0..n-1 (n the leaves' count) have weights here, this
    chip's share of a layer that four chips divide; a token's picks that
    fall on other experts add nothing, in the program alike, and that
    partial sum goes on to the next layer.  The router keeps its published
    width and experts per token.
  * no q/k norm, halves-paired rotation, no router balance loss, no MTP.
  * the vocabulary is this chip's slice; logits and loss are over it.

`FAULT` plants one of the planted faults the limits are proven against:
"no_renorm" (the top-k weights not renormalised), "no_window" (window
layers attend the whole prefix), "no_yarn" (the full layer takes default
rotary positions), "router_cut" (the shares held constant in the
backward).
"""
import math

import jax
import jax.numpy as jnp

from . import common as C

SLIDING, FULL = "sliding_attention", "full_attention"
# the published constants (`config.json`); `configure` replaces them
CONST = {
    "window": 1024,
    "top_k": 8,
    "layer_types": (SLIDING, SLIDING, SLIDING, FULL),
    "eps": 1e-6,
    "rope": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000},
    },
}
FAULTS = ("no_renorm", "no_window", "no_yarn", "router_cut")
FAULT = None
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048


def configure(window, top_k, layer_types, eps, rope):
    CONST.update(window=window, top_k=top_k, layer_types=tuple(layer_types),
                 eps=eps, rope=rope)


def rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + CONST["eps"]) * g


def frequencies(p, hd):
    """(inv_freq (hd/2,), factor on cos and sin): default, or YaRN
    (`inv = inv_extrap / factor * (1 - m) + inv_extrap * m`, `m = 1 -
    clip((i - low) / (high - low), 0, 1)`, low and high the floor and ceil
    of the dimensions whose wavelengths fit beta_fast and beta_slow times
    in the original context)."""
    theta = float(p["rope_theta"])
    extrap = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if p["rope_type"] == "default" or FAULT == "no_yarn":
        return extrap, 1.0
    orig = p["original_max_position_embeddings"]

    def dim(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim(p["beta_fast"])), 0)
    high = min(math.ceil(dim(p["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(hd // 2, dtype=jnp.float32)
    m = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extrap / p["factor"] * (1.0 - m) + extrap * m, float(
        p["attention_factor"])


def rotary(kind, s, hd):
    """(cos, sin) (S, hd/2) of a layer of `kind` at positions 0..s-1."""
    inv, factor = frequencies(CONST["rope"][kind], hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """x (S, heads, hd): the first half of each head's numbers paired with
    the second, rotated by position."""
    hd = x.shape[-1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window, precision):
    """q (S, Hq, hd), k / v (S, Hkv, hd); query head i reads KV head
    i // (Hq / Hkv); position i sees j <= i and i - j < window (a traced
    number: the layers share one body).  A block of queries at a time
    (`lax.map`, each block recomputed in the backward)."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        s + pad, hkv, hq // hkv, hd)
    j = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
        scores = C.mm(qb, k, precision, "qgrd,kgd->grqk").astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hd))
        i = i0 + jnp.arange(block)[:, None]
        keep = (j <= i) & (i - j < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return C.mm(probs, v, precision, "grqk,kgd->qgrd")

    out = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return out.reshape(s + pad, hq * hd)[:s]


def shares(h, router, n_held, precision):
    """(T, n_held): each token's weight at each held expert, 0 where it did
    not pick it: softmax over all experts, top-k, renormalised."""
    probs = jax.nn.softmax(C.mm(h, router, precision).astype(jnp.float32),
                           axis=-1)
    top, idx = jax.lax.top_k(probs, CONST["top_k"])
    if FAULT != "no_renorm":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    if FAULT == "router_cut":
        top = jax.lax.stop_gradient(top)
    t = h.shape[0]
    dense = jnp.zeros((t, router.shape[-1]), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(top)
    return dense[:, :n_held]


def experts(h, weight, wg, wu, wd, precision):
    """Every token through every held expert, weighed: a block of tokens
    at a time (`lax.map`), each recomputed in the backward."""
    t = h.shape[0]
    block = min(TOKEN_BLOCK, t)
    pad = (-t) % block

    @jax.checkpoint
    def one(hs):
        hb, sb = hs
        g = C.mm(hb, wg, precision, "td,edi->eti")
        u = C.mm(hb, wu, precision, "td,edi->eti")
        a = jax.nn.silu(g) * u
        o = C.mm(a, wd, precision, "eti,eid->etd")
        return jnp.einsum("etd,te->td", o, sb,
                          precision=jax.lax.Precision.HIGHEST)

    blocks = tuple(jnp.pad(x, ((0, pad), (0, 0))).reshape(
        -1, block, x.shape[1]) for x in (h, weight))
    return jax.lax.map(one, blocks).reshape(t + pad, -1)[:t]


def hidden(w, ids, heads, precision):
    """ids (S,) -> final hidden states (S, H): the layers in one `scan`,
    each recomputed in the backward; a layer's window (a full layer's is
    the whole sequence) and rotary tables ride beside its weights."""
    s = ids.shape[0]
    x = w["embed"][ids]
    hd = w["layers"]["q"].shape[-1] // heads
    kinds = CONST["layer_types"]
    windows = jnp.asarray([CONST["window"] if kind == SLIDING
                           and FAULT != "no_window" else s + 1
                           for kind in kinds], jnp.int32)
    tables = {kind: rotary(kind, s, hd) for kind in set(kinds)}
    cos = jnp.stack([tables[kind][0] for kind in kinds])
    sin = jnp.stack([tables[kind][1] for kind in kinds])

    @jax.checkpoint
    def layer(x, per_layer):
        l, window, c, sn = per_layer
        a = rms(x, l["ln1"])
        q = C.mm(a, l["q"], precision).astype(jnp.float32).reshape(s, -1, hd)
        k = C.mm(a, l["k"], precision).astype(jnp.float32).reshape(s, -1, hd)
        v = C.mm(a, l["v"], precision).astype(jnp.float32).reshape(s, -1, hd)
        q, k = rotate(q, c, sn), rotate(k, c, sn)
        o = attention(q, k, v, window, precision)
        x = x + C.mm(o, l["o"], precision).astype(jnp.float32)
        m = rms(x, l["ln2"])
        weight = shares(m, l["router"], l["gate"].shape[0], precision)
        return x + experts(m, weight, l["gate"], l["up"], l["down"],
                           precision).astype(jnp.float32), None

    x, _ = jax.lax.scan(layer, x, (w["layers"], windows, cos, sin))
    return rms(x, w["norm"])


def logits(w, ids, heads, precision="float32"):
    """ids (S,) -> next-token logits over the slice (S, V), float32."""
    return C.mm(hidden(w, ids, heads, precision), w["head"],
                precision).astype(jnp.float32)


def row_loss(w, row, heads, precision, denom):
    ids, labels = row
    return jnp.sum(C.cross_entropy(logits(w, ids, heads, precision),
                                   labels)) / denom


def loss_and_grads(w, batch, heads, precision="float32"):
    """batch = (ids (B,S), labels (B,S)) -> mean loss, gradients."""
    ids, labels = batch
    denom = jnp.float32(ids.size)
    with jax.default_matmul_precision("highest"):
        return C.sum_over_rows(
            lambda ww, row: row_loss(ww, row, heads, precision, denom),
            w, (ids, labels))
