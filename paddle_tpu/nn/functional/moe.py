"""Mixture-of-Experts dispatch/combine (GShard-style, capacity-based).

Beyond-reference capability: the reference has no MoE (SURVEY.md §2.3
"Expert parallel: no").  TPU-native design: dense one-hot dispatch/combine
einsums with static shapes — under jit with the expert dim of the weights
sharded P("ep", ...) and tokens sharded P("dp"), GSPMD lowers the dispatch
einsum to the all-to-all the reference would have hand-written, and the
per-expert FFN einsum runs fully expert-parallel on the MXU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.op import defop

_ACTS = {
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
}


def _raw_moe_ffn(x, gate_w, w1, b1, w2, b2, top_k=2, capacity_factor=1.25,
                 activation="gelu"):
    """Returns (y, aux_loss).

    x: (..., d_model); gate_w: (d_model, E); w1: (E, d_model, d_hidden);
    b1: (E, d_hidden); w2: (E, d_hidden, d_model); b2: (E, d_model).
    Top-k routing with per-expert capacity C = ceil(k*T/E * factor); tokens
    over capacity are dropped (standard Switch/GShard semantics).  aux_loss
    is the Switch load-balance loss E * Σ_e fraction_e · prob_mass_e.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    E = gate_w.shape[-1]
    act = _ACTS[activation]

    logits = (xt @ gate_w.astype(xt.dtype)).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                        # (T, E)
    cap = max(1, int(math.ceil(top_k * T / E * capacity_factor)))

    # iterative top-k: argmax, mask out, repeat (k is tiny and static)
    rem = gates
    masks, probs = [], []
    for _ in range(top_k):
        idx = jnp.argmax(rem, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=gates.dtype)              # (T, E)
        masks.append(m)
        probs.append(jnp.sum(gates * m, axis=-1))                  # (T,)
        rem = rem * (1.0 - m)
    denom = sum(probs) + 1e-9

    # capacity assignment in token order; later k-choices queue behind all
    # earlier choices of the same expert
    combine = jnp.zeros((T, E, cap), gates.dtype)
    offset = jnp.zeros((E,), jnp.int32)
    for m, p in zip(masks, probs):
        mi = m.astype(jnp.int32)
        pos_in_e = jnp.cumsum(mi, axis=0) - mi + offset[None, :]   # (T, E)
        within = (pos_in_e < cap).astype(gates.dtype) * m
        pos = jnp.sum(pos_in_e * mi, axis=-1)                      # (T,)
        slot = jax.nn.one_hot(pos, cap, dtype=gates.dtype)         # (T, cap)
        combine = combine + ((p / denom)[:, None, None]
                             * within[:, :, None] * slot[:, None, :])
        offset = offset + jnp.sum(mi, axis=0)

    dispatch = (combine > 0).astype(xt.dtype)                      # (T,E,cap)
    ein = jnp.einsum("tec,td->ecd", dispatch, xt)
    h = act(jnp.einsum("ecd,edf->ecf", ein, w1.astype(ein.dtype))
            + b1[:, None, :].astype(ein.dtype))
    out_e = (jnp.einsum("ecf,efd->ecd", h, w2.astype(h.dtype))
             + b2[:, None, :].astype(h.dtype))
    y = jnp.einsum("tec,ecd->td", combine.astype(out_e.dtype), out_e)

    density = jnp.mean(masks[0], axis=0)          # fraction routed (top-1)
    density_proxy = jnp.mean(gates, axis=0)       # mean router prob
    aux = jnp.sum(density * density_proxy) * E
    return y.reshape(orig_shape), aux.astype(jnp.float32)


moe_ffn = defop("moe_ffn")(_raw_moe_ffn)


# grouped products one `moe_ffn_held` call makes (gate, up, down): what a
# serving span reports as `expert_products`, so that a reader of the device
# trace knows how many events of the product a program call holds
GROUPED_PRODUCTS = 3


def _raw_moe_ffn_held(x, router_w, w_gate, w_up, w_down, experts_held,
                      top_k=8, valid=None):
    """The part of a routed FFN that the experts HELD HERE give; nothing is
    dropped.  Returns (y, picks_here, experts_hit).

    x: (T, d_model); router_w: (d_model, E) over ALL E experts; w_gate,
    w_up: (n_held, d_model, d_hidden), w_down: (n_held, d_hidden, d_model):
    the weights of the experts whose ids `experts_held` lists (a tuple, in
    the leaves' order), gated form `(act(x Wg) * (x Wu)) Wd` with silu.
    Every token picks its `top_k` experts among all E by the sigmoid of the
    router's score (float32) and weighs them by it over the sum of the k
    (the one form a configuration and a reference ask for so far).  Picks
    that fall on an expert held elsewhere add nothing here (that chip adds
    them; on one chip the layer runs without its exchange).  The picks held
    here are sorted by expert and go through one grouped product a matrix
    (`jax.lax.ragged_dot`, rows past the last group untouched), so an
    expert costs what its tokens cost and an expert no token picked is not
    read.  `valid` (T,) bool: rows that are routed nowhere (an empty
    serving slot).  picks_here: int32, picks that fell on held experts;
    experts_hit: int32, held experts with at least one token.
    """
    t, _ = x.shape
    n_experts = router_w.shape[-1]
    n_held = len(experts_held)
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)        # (T, K)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    # expert id -> its place among the held leaves, n_held = held elsewhere
    lut = [n_held] * n_experts
    for place, e in enumerate(experts_held):
        lut[int(e)] = place
    place = jnp.asarray(lut, jnp.int32)[idx]
    if valid is not None:
        place = jnp.where(valid[:, None], place, n_held)
    place = place.reshape(-1)                                      # (T*K,)
    order = jnp.argsort(place, stable=True)
    sizes = jnp.sum(place[:, None] == jnp.arange(n_held)[None, :],
                    axis=0, dtype=jnp.int32)                       # (n_held,)
    here = jnp.sum(sizes)
    with jax.named_scope("moe_expert_product"):
        xs = x[order // top_k]
        live = (jnp.arange(t * top_k) < here)[:, None]
        dot = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
            a, w.astype(a.dtype), sizes,
            preferred_element_type=jnp.float32)
        h = jnp.where(live, jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up),
                      0.0).astype(x.dtype)
        out = jnp.where(live, dot(h, w_down), 0.0)                 # sorted
    # back to (token, pick) order: a gather, then the weighted sum over k
    back = jnp.zeros((t * top_k,), jnp.int32).at[order].set(
        jnp.arange(t * top_k, dtype=jnp.int32))
    y = jnp.einsum("tk,tkd->td", top, out[back].reshape(t, top_k, -1))
    return (y.astype(x.dtype), here.astype(jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32))


moe_ffn_held = defop("moe_ffn_held")(_raw_moe_ffn_held)
