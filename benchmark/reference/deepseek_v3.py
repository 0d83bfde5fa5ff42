"""deepseek_v3 (moonshotai Moonlight-16B-A3B, `config.json`): pre-norm RMSNorm
blocks, `h = x + attn(rms(x))`, `out = h + ffn(rms(h))`; multi-head latent
attention in its EXPANDED form (no cache, nothing absorbed); a dense gated MLP
in the leading layer, then sigmoid-routed experts under `noaux_tc` (a
selection bias that moves the choice and not the weight, top-k weights
normalised and scaled) beside shared experts that are summed; final RMSNorm,
untied output head.

Plain float32 `jax.numpy` at `highest`, one layer's weights at a time (the
caller hands `layer` one layer's leaves), attention in query blocks so that
8192 rows fit.  Sizes `d` are `benchmark/arch/deepseek_v3.py::dims`; the
leaves are named there.  Imports nothing of the program.

The equations, per token x (H):
  q = x Wq -> heads of (nope + rope): q_nope | q_pe
  x Wkva -> c (latent) | k_pe (rope);  c = rms(c) * kva_g;  k_pe is rotated
      once and shared by every head
  c Wkvb -> heads of (nope + v): k_nope | v
  score = (q_nope . k_nope + rot(q_pe) . rot(k_pe)) / sqrt(nope + rope),
      causal softmax in float32, o = p v, attn = concat(o) Wo
  router: s = sigmoid(x Wr); the K experts are the top K of s + bias; their
      weights are s there (without the bias) over their sum, times `scale`
  ffn = sum_i w_i expert_i(x) + shared(x); expert(x) = (silu(x Wg) * (x Wu)) Wd

Departures from the source, each also under `assumed` in the configuration's
file:
  * the rotation turns the pair (2i, 2i + 1) by pos * theta ** (-2i / rope)
    and writes it to places (i, rope/2 + i), as the `deepseek_v3` modelling
    code does (de-interleave, then rotate halves); no scaling of the
    frequencies (the config has no `rope_scaling`).
  * `n_group` 1, `topk_group` 1: the router has no group step.
  * `e_score_correction_bias` is a trained buffer in the source; here it is a
    leaf drawn from the seed like every other.
"""
import jax
import jax.numpy as jnp

from . import common as C

QUERY_BLOCK = 256


def rms(x, g, eps):
    """RMSNorm: x / sqrt(mean(x^2) + eps) * g."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope(x, pos, theta):
    """x (S, heads, r): pairs (2i, 2i + 1) turned by pos * theta ** (-2i / r),
    the result laid out as [first numbers | second numbers]."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]        # (S, r/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, precision):
    """q, k (S, heads, dk), v (S, heads, dv): position i sees j <= i, the
    score over sqrt(dk).  One block of queries at a time -> (S, heads * dv)."""
    s, heads, dk = q.shape
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    j = jnp.arange(s)[None, :]

    def one(i0):
        qb = jax.lax.dynamic_slice_in_dim(qp, i0, block, 0)
        scores = C.mm(qb, k, precision, "qhd,khd->hqk").astype(
            jnp.float32) / jnp.sqrt(jnp.float32(dk))
        keep = j <= i0 + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return C.mm(probs, v, precision, "hqk,khd->qhd")

    out = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return out.reshape(s + pad, -1)[:s]


def mla(h, l, d, precision):
    """Latent attention of one sequence, expanded: h (S, H) -> (S, H)."""
    s = h.shape[0]
    pos = jnp.arange(s)
    q = C.mm(h, l["wq"], precision).reshape(s, d["heads"], -1)
    q_nope, q_pe = q[..., :d["nope"]], q[..., d["nope"]:]
    ckv = C.mm(h, l["wkva"], precision)
    c = rms(ckv[:, :d["latent"]], l["kva_g"], d["eps"])
    k_pe = rope(ckv[:, None, d["latent"]:], pos, d["theta"])     # (S, 1, r)
    kv = C.mm(c, l["wkvb"], precision).reshape(s, d["heads"], -1)
    k = jnp.concatenate(
        [kv[..., :d["nope"]],
         jnp.broadcast_to(k_pe, (s, d["heads"], d["rope"]))], axis=-1)
    q = jnp.concatenate([q_nope, rope(q_pe, pos, d["theta"])], axis=-1)
    a = attention(q, k, kv[..., d["nope"]:], precision)
    return C.mm(a, l["wo"], precision)


def gated(h, wg, wu, wd, precision):
    """One gated MLP: (silu(h Wg) * (h Wu)) Wd."""
    a = jax.nn.silu(C.mm(h, wg, precision)) * C.mm(h, wu, precision)
    return C.mm(a, wd, precision)


def route(h, router, bias, d, precision):
    """-> (S, E) weights: a token's K experts are the K largest of
    sigmoid(h Wr) + bias; each weighs its score WITHOUT the bias over the
    sum of the K (`norm_topk_prob`), times `scale`; 0 for every other."""
    s = jax.nn.sigmoid(C.mm(h, router, precision).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias, d["K"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    top = d["scale"] * top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def ffn(h, l, d, precision):
    """Routed sum over all experts plus the shared MLP, added once."""
    w = route(h, l["router"], l["bias"], d, precision)          # (S, E)

    def routed(acc, ew):
        wg, wu, wd, we = ew
        return acc + we[:, None] * gated(h, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(routed, jnp.zeros_like(h),
                        (l["eg"], l["eu"], l["ed"], w.T))
    return y + gated(h, l["sg"], l["su"], l["sd"], precision)


def layer(x, l, kind, d, precision="float32"):
    """x (S, H) -> the block's output.  `kind`: "dense" (one gated MLP of
    the dense width) or "moe" (router, experts, shared)."""
    x = x + mla(rms(x, l["ln1_g"], d["eps"]), l, d, precision)
    h = rms(x, l["ln2_g"], d["eps"])
    if kind == "dense":
        return x + gated(h, l["wg"], l["wu"], l["wd"], precision)
    return x + ffn(h, l, d, precision)


def embed(top, ids):
    return top["emb"][ids]


def head(top, x, d, precision="float32"):
    """The final RMSNorm, then the untied head."""
    return C.mm(rms(x, top["lnf_g"], d["eps"]), top["head"],
                precision).astype(jnp.float32)


def logits(top, layers, ids, d, precision="float32"):
    """ids (S,) -> (S, V); `layers` is a list of one layer's leaves each
    (the tests' sizes; the cell streams the layers, see the generator)."""
    x = embed(top, ids)
    for l, kind in zip(layers, d["kinds"]):
        x = layer(x, l, kind, d, precision)
    return head(top, x, d, precision)
