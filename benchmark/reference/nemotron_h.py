"""nemotron_h (NVIDIA Nemotron 3 Super, `config.json`): pre-norm RMSNorm
blocks, `x = x + mixer(rms(x))`, the mixer chosen a layer by
`hybrid_override_pattern`; final RMSNorm, untied output head.

Plain float32 `jax.numpy` at `highest`, one layer's weights at a time (the
caller hands `layer` one layer's leaves), one sequence at a time.  Sizes `d`
are `benchmark/arch/nemotron_h.py::dims`; the leaves are named there.
Imports nothing of the program.

The equations, per token u = rms(x) (H):
  mamba: [z | xBC | dt] = u Win;  xBC through a causal depthwise conv of
      `conv` taps (weight (conv, C), bias), then silu -> x (heads, P), B, C
      (groups, N), head h reading group h // (heads / groups);
      dt = softplus(dt + dt_bias), A = -exp(A_log);
      h_t = exp(dt A) h_{t-1} + dt x_t (x) B_t,  y_t = C_t . h_t + D x_t,
      STEP BY STEP over positions (no chunks);
      out = rms_groups(y * silu(z)) * g Wout, the RMS over each of
      `norm_groups` (= `n_groups`) equal groups of y's heads x head_dim
  attention: q = u Wq (32 heads), k = u Wk, v = u Wv (2 heads of 128, each
      shared by 16 query heads); causal softmax(q . k / sqrt(128)) v; no
      positional encoding; out = o Wo
  moe: s = sigmoid(u Wr); a token's K experts are the top K of s + bias,
      each weighing s over the sum of the K, times `scale`; the held
      experts' part: up(sum_i w_i relu(down(u) W1_i)^2 W2_i), with down
      H -> latent and up latent -> H; plus the shared relu(u S1)^2 S2

Departures from the source, each also under `assumed` in the configuration's
file: no rotation in attention; the router and the shared expert read the
full width, the routed experts the latent; `e_score_correction_bias` is a
leaf drawn from the seed.
"""
import jax
import jax.numpy as jnp

from . import common as C

QUERY_BLOCK = 256


def rms(x, g, eps):
    """RMSNorm: x / sqrt(mean(x^2) + eps) * g."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def mamba(u, l, d, precision):
    """The Mamba-2 mixer of one sequence: u (S, H) -> (S, H)."""
    s, di, c_dim = u.shape[0], d["d_inner"], d["conv_dim"]
    nh, p, g, n = d["m_heads"], d["m_hd"], d["groups"], d["state"]
    zxbcdt = C.mm(u, l["in_proj"], precision)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + c_dim], zxbcdt[:, -nh:]
    k = d["conv"]
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(pad[i:i + s] * l["conv_w"][i] for i in range(k))
    a = jax.nn.silu(conv + l["conv_b"])
    x = a[:, :di].reshape(s, nh, p)
    b = a[:, di:di + g * n].reshape(s, g, n)
    c = a[:, di + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + l["dt_bias"])
    decay_rate = -jnp.exp(l["A_log"])
    r = nh // g

    def step(h, row):
        x_t, dt_t, b_t, c_t = row
        bh, ch = jnp.repeat(b_t, r, axis=0), jnp.repeat(c_t, r, axis=0)
        h = (jnp.exp(dt_t * decay_rate)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :])
        return h, C.mm(h, ch, precision, "hpn,hn->hp")

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32),
                        (x, dt, b, c))
    y = (y + l["D"][:, None] * x).reshape(s, di)
    y = y * jax.nn.silu(z)
    y = rms(y.reshape(s, d["norm_groups"], -1), 1.0,
            d["eps"]).reshape(s, di) * l["mnorm"]
    return C.mm(y, l["out_proj"], precision)


def attention(u, l, d, precision):
    """Grouped-query causal attention of one sequence, no positions, a
    block of queries at a time: u (S, H) -> (S, H)."""
    s, hd = u.shape[0], d["hd"]
    q = C.mm(u, l["wq"], precision).reshape(s, d["heads"], hd)
    r = d["heads"] // d["kv_heads"]
    k = jnp.repeat(C.mm(u, l["wk"], precision).reshape(s, -1, hd), r, axis=1)
    v = jnp.repeat(C.mm(u, l["wv"], precision).reshape(s, -1, hd), r, axis=1)
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    j = jnp.arange(s)[None, :]

    def one(i0):
        qb = jax.lax.dynamic_slice_in_dim(qp, i0, block, 0)
        scores = C.mm(qb, k, precision, "qhd,khd->hqk") / jnp.sqrt(
            jnp.float32(hd))
        keep = j <= i0 + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return C.mm(probs, v, precision, "hqk,khd->qhd")

    o = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return C.mm(o.reshape(s + pad, -1)[:s], l["wo"], precision)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(u, router, bias, d, precision):
    """-> (S, E) weights: a token's K experts are the K largest of
    sigmoid(u Wr) + bias; each weighs its score WITHOUT the bias over the
    sum of the K, times `scale`; 0 for every other."""
    s = jax.nn.sigmoid(C.mm(u, router, precision))
    _, idx = jax.lax.top_k(s + bias, d["K"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    top = d["scale"] * top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def latent_moe(u, l, d, precision):
    """The held experts' part in the latent, brought back up, plus the
    shared expert: u (S, H) -> (S, H)."""
    w = route(u, l["router"], l["bias"], d, precision)[:, jnp.asarray(
        d["held"])]                                            # (S, held)
    lat = C.mm(u, l["fc1"], precision)

    def routed(acc, ew):
        w1, w2, we = ew
        return acc + we[:, None] * C.mm(relu2(C.mm(lat, w1, precision)), w2,
                                        precision), None

    y, _ = jax.lax.scan(routed, jnp.zeros_like(lat),
                        (l["e_up"], l["e_down"], w.T))
    return (C.mm(y, l["fc2"], precision)
            + C.mm(relu2(C.mm(u, l["s_up"], precision)), l["s_down"],
                   precision))


MIXERS = {"mamba": mamba, "attention": attention, "moe": latent_moe}


def layer(x, l, kind, d, precision="float32"):
    """x (S, H) -> the block's output; `kind` names the mixer."""
    return x + MIXERS[kind](rms(x, l["ln"], d["eps"]), l, d, precision)


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
