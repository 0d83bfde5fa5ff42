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
(`_batched_form`): the same bytes, no groups.  Its experts are gated
(`(silu(x Wg) * (x Wu)) Wd`) or, with no gate, relu squared (`relu(x W1)^2
W2`), and they may take another input than the router reads (a latent the
layer projects its tokens into).  Its router scores by the sigmoid or, as
Mellum's does, the softmax.  A layer that TRAINS takes its held picks in
one grouped pass with a backward (`trainable=True`: `_held_pass`, through
the kernels of `ops.grouped_matmul`, nothing dropped and no capacity), which
is what replaces `_raw_moe_ffn`'s capacity dispatch for a held share of the
experts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.op import defop
from ...observability.metrics import counter
from ...ops import grouped_matmul as gm

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
# program call holds.  Experts with no gate make one fewer.
GROUPED_PRODUCTS = 3
# rows of sorted picks a chunk's grouped products go over: a call whose
# picks (tokens x top_k) fit makes one chunk, a longer one walks the picks
# held here in chunks of this many (chosen on the chip: PERF.md, PR 31)
_CHUNK_ROWS = 2048
# rows a tile of the trainable form's grouped pass (`ops.grouped_matmul`):
# its kernels walk a layer's picks held here in tiles of this many, a tile
# that straddles two experts once for each, so that a layer's products go
# over about (picks here / tile + experts held) tiles.  At 16 of 64 experts
# of 2304 x 896 and ~16k picks held a layer: 256 goes over ~79 tiles where
# 512 goes over ~47 of twice the rows (a fifth more products), and a tile
# of 256 rows takes about as long on the MXU as its expert's matrices take
# to arrive, so that a change of expert stalls nothing.  Read on the chip,
# ms a step of 8192 tokens (PERF.md, PR 44): 256 195.0, 512 199.6 to 199.8
_TRAIN_CHUNK_ROWS = 256
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


def _hidden(dot, x, w_gate, w_up):
    """An expert's hidden row, float32: `silu(x Wg) * (x Wu)`, or with no
    gate `relu(x W1)^2`."""
    if w_gate is None:
        return jnp.square(jax.nn.relu(dot(x, w_up)))
    return jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)


def _batched_products(x, w_gate, w_up, w_down, weight):
    """Every row of x (T, d_model) through every held expert, the expert
    axis the batch of each product and the weights read where they lie;
    `weight` (T, n_held) float32, a row's share at each held expert it
    picked and 0 elsewhere, weighs the float32 results.  -> (T, d_model)
    float32."""
    xe = jnp.broadcast_to(x, (w_up.shape[0],) + x.shape)
    dot = lambda a, w: jax.lax.dot_general(  # noqa: E731
        a, w.astype(a.dtype), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    with jax.named_scope("moe_expert_product"):
        h = _hidden(dot, xe, w_gate, w_up).astype(x.dtype)
        return jnp.sum(dot(h, w_down) * weight.T[:, :, None], axis=0)


def _raw_moe_ffn_held(x, router_w, w_gate, w_up, w_down, experts_held,
                      top_k=8, valid=None, select_bias=None, scale=None,
                      expert_x=None, score="sigmoid", trainable=False):
    """The part of a routed FFN that the experts HELD HERE give; nothing is
    dropped.  Returns (y, picks_here, experts_hit, products, rows).

    x: (T, d_model); router_w: (d_model, E) over ALL E experts; w_gate,
    w_up: (n_held, d_model, d_hidden), w_down: (n_held, d_hidden, d_model):
    the weights of the experts whose ids `experts_held` lists (a tuple, in
    the leaves' order), gated form `(act(x Wg) * (x Wu)) Wd` with silu;
    `w_gate` None: the experts have no gate and take `relu(x Wu)^2 Wd`
    (two products a chunk, not three).  `expert_x` (T, d_expert): what the
    experts take where it is not what the router reads (a latent the
    caller projected x into; `y` is then d_expert wide).
    Every token picks its `top_k` experts among all E by the router's
    scores (float32) and weighs them by the score over the sum of the k:
    `score` "sigmoid", the sigmoid of each logit; "softmax", the softmax
    over all E (renormalised over the k, the full sum cancels).
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
    `trainable`: the held picks in ONE grouped pass that reverse mode can go
    through (`_held_pass`: the kernels of `ops.grouped_matmul` over tiles of
    `_TRAIN_CHUNK_ROWS` sorted picks, forward and, recomputed, backward; the
    router's gradient comes through the picks' shares), gated experts
    alone, never the batched form; counted as `grouped_kernel`.
    int32 counts: picks_here, picks that fell on held experts;
    experts_hit, held experts with at least one token; products, grouped
    products made (`GROUPED_PRODUCTS` a chunk; the trainable pass's forward
    kernel calls, `gm.FORWARD_CALLS`); rows, rows they went over (a chunk's
    rows x chunks; the trainable pass's, the distinct tiles' rows that hold
    the held picks).
    """
    t = x.shape[0]
    n_experts = router_w.shape[-1]
    n_held = len(experts_held)
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score {score!r}: 'sigmoid' or 'softmax'")
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
    if expert_x is not None:
        x = expert_x
    held_at = place[:, None] == jnp.arange(n_held)[None, :]   # (T*K, n_held)
    sizes = jnp.sum(held_at, axis=0, dtype=jnp.int32)              # (n_held,)
    batched = not trainable and _batched_form(t, top_k, n_experts)
    _FORM_TAKEN.labels(form="batched" if batched else "grouped_kernel"
                       if trainable else "grouped").inc()
    if batched:
        weight = jnp.sum(jnp.where(held_at, share[:, None], 0.0).reshape(
            t, top_k, n_held), axis=1)
        y = _batched_products(x, w_gate, w_up, w_down, weight)
        return (y.astype(x.dtype), jnp.sum(sizes),
                jnp.sum(sizes > 0, dtype=jnp.int32), jnp.int32(0),
                jnp.int32(0))
    ends = jnp.cumsum(sizes)
    here = ends[-1]
    # the picks held here first, by expert
    order = jnp.argsort(place, stable=True).astype(jnp.int32)
    if trainable:
        if w_gate is None:
            raise ValueError("the trainable form takes gated experts")
        # a tile a whole number of bfloat16 sublane tiles
        tile = min(_TRAIN_CHUNK_ROWS, -(-(t * top_k) // 16) * 16)
        y = _held_pass(x, share, w_gate, w_up, w_down, order, sizes, top_k,
                       tile)
        return (y.astype(x.dtype), here.astype(jnp.int32),
                jnp.sum(sizes > 0, dtype=jnp.int32),
                jnp.int32(gm.FORWARD_CALLS),
                (_walked(here, tile) * tile).astype(jnp.int32))
    rows = min(_CHUNK_ROWS, t * top_k)
    chunks = -(-(t * top_k) // rows)
    # padded so that a chunk's slice never runs off the end (rows past
    # `here` add nothing)
    order = jnp.pad(order, (0, chunks * rows - t * top_k))

    # products a chunk: gate, up and down, or up and down with no gate
    made = GROUPED_PRODUCTS if w_gate is not None else GROUPED_PRODUCTS - 1
    y, walked = _walk(x, share, w_gate, w_up, w_down, order, sizes, ends,
                      here, top_k, rows, chunks == 1)
    return (y.astype(x.dtype), here.astype(jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32),
            (made * walked).astype(jnp.int32),
            (rows * walked).astype(jnp.int32))


moe_ffn_held = defop("moe_ffn_held")(_raw_moe_ffn_held)


# the name the serving forms' products carry, by which a device trace finds
# their events
_SCOPE_FWD = "moe_expert_product"


def _chunk(order, ends, here, sizes, top_k, rows, c):
    """Chunk c of the picks sorted held-first (`order`; `ends`, the
    cumulative `sizes` of the held experts; `here`, their sum): (its
    picks, their tokens, which of its rows are held picks (rows, 1), each
    held expert's rows in it)."""
    lo = c * rows
    picks = jax.lax.dynamic_slice_in_dim(order, lo, rows)
    token = picks // top_k
    live = (lo + jnp.arange(rows) < here)[:, None]
    part = (jnp.clip(ends, lo, lo + rows)
            - jnp.clip(ends - sizes, lo, lo + rows))
    return picks, token, live, part


def _chunk_products(xs, w_gate, w_up, w_down, live, part, weigh):
    """A chunk's rows of x through its experts' grouped products, float32,
    each weighed by `weigh` (its pick's share).  A grouped product leaves
    the rows past its last group undefined on the chip, in either
    direction: rows that are not held picks give 0."""
    dot = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
        a, w.astype(a.dtype), part, preferred_element_type=jnp.float32)
    h = jnp.where(live, _hidden(dot, xs, w_gate, w_up), 0.0).astype(xs.dtype)
    return jnp.where(live, weigh(dot(h, w_down)), 0.0)


def _walked(here, rows):
    """Chunks the held picks fill."""
    return (here + rows - 1) // rows


def _walk(x, share, w_gate, w_up, w_down, order, sizes, ends, here, top_k,
          rows, one_chunk):
    """The held picks' part of the layer, (T, d) float32, and the chunks
    walked: a chunk at a time, as many as the held picks fill (a trip
    count the device computes), or the one chunk with no loop."""
    def add_chunk(c, y):
        picks, token, live, part = _chunk(order, ends, here, sizes, top_k,
                                          rows, c)
        with jax.named_scope(_SCOPE_FWD):
            out = _chunk_products(
                x[token], w_gate, w_up, w_down, live, part,
                lambda o: o * share[picks][:, None])
        return y.at[token].add(out)

    y = jnp.zeros((x.shape[0], x.shape[1]), jnp.float32)
    if one_chunk:
        return add_chunk(0, y), jnp.int32(1)
    walked = _walked(here, rows)
    return jax.lax.fori_loop(0, walked, add_chunk, y), walked


def _rows_of(order, share, sizes, top_k, tile):
    """The pass's rows, padded to whole tiles: the sorted picks' tokens,
    their place among the rows (the inverse of `order`), their shares as a
    (rows, 1) column, and the kernels' walk over them."""
    picks = order.shape[0]
    rows = -(-picks // tile) * tile
    token = jnp.pad(order // top_k, (0, rows - picks))
    place = jnp.zeros((picks,), jnp.int32).at[order].set(
        jnp.arange(picks, dtype=jnp.int32))
    weight = jnp.pad(share[order], (0, rows - picks))[:, None]
    return token, place, weight, gm.group_tiles(sizes, rows, tile)


def _to_tokens(rows, place, here, top_k):
    """Each token's sum of its held picks' rows, float32: `rows` (pass
    rows, w) in sorted order; a pick whose place is past `here` is held
    elsewhere and the row it would read is undefined."""
    picked = jnp.where((place < here)[:, None], rows[place], 0.0)
    return jnp.sum(picked.reshape(-1, top_k, rows.shape[1]), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held_pass(x, share, w_gate, w_up, w_down, order, sizes, top_k, tile):
    """The held picks' part of the layer, (T, d) float32, in one grouped
    pass: `order` the T * top_k picks sorted held-first by expert, `sizes`
    (n_held,) the picks of each held expert, `share` (T * top_k,) each
    pick's weight.  The picks' rows of x are gathered in that order, go
    through `gm.gate_up` and `gm.down` over tiles of `tile` rows, and each
    token sums its held picks' weighted rows."""
    return _held_fwd(x, share, w_gate, w_up, w_down, order, sizes, top_k,
                     tile)[0]


def _held_fwd(x, share, w_gate, w_up, w_down, order, sizes, top_k, tile):
    token, place, weight, groups = _rows_of(order, share, sizes, top_k, tile)
    h = gm.gate_up(groups, x[token], w_gate, w_up, tile)
    y = _to_tokens(gm.down(groups, h, weight, w_down, tile), place,
                   jnp.sum(sizes), top_k)
    return y, (x, share, w_gate, w_up, w_down, order, sizes)


def _held_bwd(top_k, tile, res, dy):
    """The same rows again: `gm.gate_up_bwd` gives each row's input's,
    share's and hidden products' gradients in one visit, `gm.tgmm` each
    stack's gradient over its expert's rows, and the rows' input gradients
    go back to their tokens as the forward's outputs did."""
    x, share, w_gate, w_up, w_down, order, sizes = res
    token, place, weight, groups = _rows_of(order, share, sizes, top_k, tile)
    here = jnp.sum(sizes)
    xs, dys = x[token], dy.astype(x.dtype)[token]
    dxs, dg, du, hw, dw = gm.gate_up_bwd(groups, xs, dys, weight, w_gate,
                                         w_up, w_down, tile)
    dwg, dwu = gm.tgmm(groups, xs, dg, tile), gm.tgmm(groups, xs, du, tile)
    dwd = gm.tgmm(groups, hw, dys, tile)
    dx = _to_tokens(dxs, place, here, top_k)
    dshare = jnp.where(place < here, dw[place, 0], 0.0)
    zero_int = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (dx.astype(x.dtype), dshare.astype(share.dtype),
            dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd.astype(w_down.dtype), zero_int(order), zero_int(sizes))


_held_pass.defvjp(_held_fwd, _held_bwd)
