"""Mixture-of-Experts dispatch/combine (GShard-style, capacity-based).

Beyond-reference capability: the reference has no MoE (SURVEY.md §2.3
"Expert parallel: no").  TPU-native design: dense one-hot dispatch/combine
einsums with static shapes — under jit with the expert dim of the weights
sharded P("ep", ...) and tokens sharded P("dp"), GSPMD lowers the dispatch
einsum to the all-to-all the reference would have hand-written, and the
per-expert FFN einsum runs fully expert-parallel on the MXU.

`moe_ffn_held` is the other form, for ONE chip's share of an
expert-parallel deployment: no capacity and nothing dropped; it sorts the
picks, walks those that fell on the experts held here in chunks through
grouped products, and says what that cost (picks here, experts hit,
grouped products made, rows they went over) as device values a serving
program returns with its tokens.  Where a few rows' picks cover the held
experts anyway (a decode step of 48 slots over 64 experts), it takes every
row through every held expert in one batched product a matrix instead
(`_batched_form`): the same bytes, no groups.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.op import defop
from ...observability.metrics import counter

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


# grouped products one chunk of `moe_ffn_held` makes (gate, up, down): a call
# returns how many it made, a serving span reports them as `expert_products`,
# and a reader of the device trace knows how many events of the product a
# program call holds
GROUPED_PRODUCTS = 3
# rows of sorted picks a chunk's grouped products go over: a call whose
# picks (tokens x top_k) fit makes one chunk, a longer one walks the picks
# held here in chunks of this many (chosen on the chip: PERF.md, PR 31)
_CHUNK_ROWS = 2048
# where the held experts' products are ONE batched product a matrix over
# every row and every held expert (`_batched_form`), both chosen on the chip
# (`probes/moe_decode_forms.py`, ms a layer; PERF.md, PR 35).  The share of
# the experts that the call's picks are expected to reach, at least: 16 of
# 128 experts of 4096 x 4096 take the batched form in 2.19 ms whatever the
# rows, the grouped one in 1.72 at an expected 0.63 (16 rows x 8 picks),
# 1.87 at 0.78 and 2.71 at 0.87; 64 experts of 2048 x 1408 are faster
# batched from 0.53 on (1.51 against 1.84, and 3.53 at 48 rows x 6 picks).
_BATCHED_COVER = 0.9
# The rows of the call, at most: up to here the batched product takes the
# time of the experts' bytes (1.51 ms at 48 rows, 1.52 at 128, 1.54 at 192,
# where 1.35 is the bytes' at 819 GB/s); at 256 rows it takes 1.95: rows x
# experts of operations, not bytes, bound it from about 240 rows on.
_BATCHED_ROWS = 192

# which form of the held experts' products each traced call took, chosen
# from the shapes at trace time like `flash_attention_form_total`
_FORM_TAKEN = counter(
    "moe_expert_form_total",
    "moe_ffn_held calls traced, by the form of the experts' products the "
    "shapes chose", ("form",))


def _batched_form(t, top_k, n_experts):
    """Whether `t` rows that each pick `top_k` of `n_experts` go through
    every held expert in one batched product a matrix: where the picks are
    expected to reach nearly every expert, so that the grouped product
    would read them all too, and the rows are few enough that the experts'
    bytes and not rows x experts of operations bound the product."""
    cover = 1.0 - (1.0 - 1.0 / n_experts) ** (t * top_k)
    return cover >= _BATCHED_COVER and t <= _BATCHED_ROWS


def _batched_products(x, w_gate, w_up, w_down, weight):
    """Every row of x (T, d_model) through every held expert, the expert
    axis the batch of each product and the weights read where they lie;
    `weight` (T, n_held) float32, a row's share at each held expert it
    picked and 0 elsewhere, weighs the float32 results.  -> (T, d_model)
    float32."""
    xe = jnp.broadcast_to(x, (w_gate.shape[0],) + x.shape)
    dot = lambda a, w: jax.lax.dot_general(  # noqa: E731
        a, w.astype(a.dtype), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    with jax.named_scope("moe_expert_product"):
        h = (jax.nn.silu(dot(xe, w_gate)) * dot(xe, w_up)).astype(x.dtype)
        return jnp.sum(dot(h, w_down) * weight.T[:, :, None], axis=0)


def _raw_moe_ffn_held(x, router_w, w_gate, w_up, w_down, experts_held,
                      top_k=8, valid=None, select_bias=None, scale=None):
    """The part of a routed FFN that the experts HELD HERE give; nothing is
    dropped.  Returns (y, picks_here, experts_hit, products, rows).

    x: (T, d_model); router_w: (d_model, E) over ALL E experts; w_gate,
    w_up: (n_held, d_model, d_hidden), w_down: (n_held, d_hidden, d_model):
    the weights of the experts whose ids `experts_held` lists (a tuple, in
    the leaves' order), gated form `(act(x Wg) * (x Wu)) Wd` with silu.
    Every token picks its `top_k` experts among all E by the sigmoid of the
    router's score (float32) and weighs them by it over the sum of the k.
    `select_bias` (E,), float32: added to the scores for the CHOICE alone
    (`noaux_tc`'s `e_score_correction_bias`); the weights stay the scores
    without it.  `scale`: every weight times this (`routed_scaling_factor`).
    With neither the trace is the one it was.  Picks
    that fall on an expert held elsewhere add nothing here (that chip adds
    them; on one chip the layer runs without its exchange), and nothing as
    wide as a row of `x` is made for them: a stable sort of the T x top_k
    picks by expert puts those held here first, and only that prefix is
    walked, `_CHUNK_ROWS` rows at a time and as many chunks as it takes (a
    trip count the device computes; no capacity, so no routing overflows).
    A chunk gathers its rows of `x`, takes them through one grouped product
    a matrix (`jax.lax.ragged_dot` with the chunk's part of each expert's
    group, rows past the last group zeroed), weighs each row by its pick's
    share in float32 and adds it to its token's row of `y`.  So an expert
    costs what its tokens cost, an expert no token picked is not read and
    one that straddles two chunks is read twice.  Where T x top_k fits one
    chunk (a decode step) there is no loop.  `valid` (T,) bool: rows that
    are routed nowhere (an empty serving slot, a prompt's padding).
    Where `_batched_form` says so (from T, top_k and E alone: a decode
    step whose picks reach nearly every expert), there is no sort, gather
    or grouped product: every row goes through every held expert in one
    batched product a matrix, weighed in float32 by its share there (0
    where it did not pick the expert); `products` and `rows` are then 0.
    int32 counts: picks_here, picks that fell on held experts;
    experts_hit, held experts with at least one token; products, grouped
    products made (`GROUPED_PRODUCTS` a chunk); rows, rows they went over
    (a chunk's rows x chunks).
    """
    t, d_model = x.shape
    n_experts = router_w.shape[-1]
    n_held = len(experts_held)
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    if select_bias is None:
        top, idx = jax.lax.top_k(scores, top_k)                    # (T, K)
    else:
        _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32),
                               top_k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    share = (top / jnp.sum(top, axis=-1, keepdims=True)).reshape(-1)
    if scale is not None:
        share = share * scale
    # expert id -> its place among the held leaves, n_held = held elsewhere
    lut = [n_held] * n_experts
    for place, e in enumerate(experts_held):
        lut[int(e)] = place
    place = jnp.asarray(lut, jnp.int32)[idx]
    if valid is not None:
        place = jnp.where(valid[:, None], place, n_held)
    place = place.reshape(-1)                                      # (T*K,)
    held_at = place[:, None] == jnp.arange(n_held)[None, :]   # (T*K, n_held)
    sizes = jnp.sum(held_at, axis=0, dtype=jnp.int32)              # (n_held,)
    batched = _batched_form(t, top_k, n_experts)
    _FORM_TAKEN.labels(form="batched" if batched else "grouped").inc()
    if batched:
        weight = jnp.sum(jnp.where(held_at, share[:, None], 0.0).reshape(
            t, top_k, n_held), axis=1)
        y = _batched_products(x, w_gate, w_up, w_down, weight)
        return (y.astype(x.dtype), jnp.sum(sizes),
                jnp.sum(sizes > 0, dtype=jnp.int32), jnp.int32(0),
                jnp.int32(0))
    ends = jnp.cumsum(sizes)
    here = ends[-1]
    rows = min(_CHUNK_ROWS, t * top_k)
    chunks = -(-(t * top_k) // rows)
    # the picks held here first, by expert; padded so that a chunk's slice
    # never runs off the end (rows past `here` add nothing)
    order = jnp.pad(jnp.argsort(place, stable=True).astype(jnp.int32),
                    (0, chunks * rows - t * top_k))

    def add_chunk(c, y):
        lo = c * rows
        picks = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        token = picks // top_k
        live = (lo + jnp.arange(rows) < here)[:, None]
        part = (jnp.clip(ends, lo, lo + rows)
                - jnp.clip(ends - sizes, lo, lo + rows))
        with jax.named_scope("moe_expert_product"):
            xs = x[token]
            dot = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
                a, w.astype(a.dtype), part,
                preferred_element_type=jnp.float32)
            h = jnp.where(live, jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up),
                          0.0).astype(x.dtype)
            out = jnp.where(live, dot(h, w_down) * share[picks][:, None], 0.0)
        return y.at[token].add(out)

    y = jnp.zeros((t, d_model), jnp.float32)
    if chunks == 1:
        y, walked = add_chunk(0, y), jnp.int32(1)
    else:
        walked = (here + rows - 1) // rows
        y = jax.lax.fori_loop(0, walked, add_chunk, y)
    return (y.astype(x.dtype), here.astype(jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32),
            (GROUPED_PRODUCTS * walked).astype(jnp.int32),
            (rows * walked).astype(jnp.int32))


moe_ffn_held = defop("moe_ffn_held")(_raw_moe_ffn_held)
