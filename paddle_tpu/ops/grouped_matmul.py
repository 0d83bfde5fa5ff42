"""Grouped matrix products over rows sorted by group, as Pallas kernels: the
held experts' part of a routed layer that TRAINS (`nn.functional.moe`,
`moe_ffn_held(trainable=True)`), one pass a layer forward and backward.

The rows are a static bound (a layer's tokens x top_k picks, rounded up to
whole tiles), sorted so that group e's rows are `offsets[e]` to
`offsets[e + 1]` and the rows past the last group belong to none.  Every
kernel walks one flat grid of `tiles + groups - 1` steps, the most visits
that bound can need: a step is one (group, tile) pair, read from
scalar-prefetched lists (`group_tiles`) by the index maps, so that the
pipeline fetches the next step's tile and the next group's matrices while
the step's products run.  A tile that straddles two groups is visited once
by each, its rows masked to the group's; an empty group is visited once (the
weight gradient zeroes its block there).  Steps past the live visits do
nothing (`pl.when`), and their lists repeat the last live visit, so the
pipeline fetches nothing new for them.  The same lists serve a layer's
forward and backward calls.

- `gate_up`: `h = silu(x Wg) * (x Wu)` a row, in the operands' dtype.
- `down`: `(h Wd) * w` a row, float32: the pick's share `w` weighs it.
- `gate_up_bwd`: from a row's x and its output's gradient dy, everything
  the backward needs of it in one visit: the products of the forward again,
  `dh = (dy Wd^T) * w`, the share's gradient `h . (dy Wd^T)`, the gate's
  and the up's gradients `dg`, `du` (operands' dtype), the input's
  `dg Wg^T + du Wu^T` (float32), and `h * w` for the down weights'.
- `tgmm`: a group's `a^T b` over its rows, float32, each group's block
  written once: the three stacks' weight gradients.

A kernel call is traced once a signature (`jax.jit`): a model's layers call
it with one signature, so its body is traced, and lowered, once for all of
them (pallas traces a body at every call; `flash_attention._traced_once`
says what that cost a model's set-up).  Off a TPU the kernels run through
the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["group_tiles", "gate_up", "down", "gate_up_bwd", "tgmm",
           "FORWARD_CALLS"]

# kernel calls a layer's forward makes (`gate_up`, `down`); its backward
# makes twice as many (`gate_up_bwd`, then `tgmm` for each stack)
FORWARD_CALLS = 2

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


def group_tiles(sizes, rows, tile):
    """The walk's lists for rows sorted by group: `sizes` (groups,) int32,
    summing to at most `rows` (a multiple of `tile`) -> (offsets (groups +
    1,), group and tile of each step (tiles + groups - 1,), live visits
    (1,)), int32.  Group e's visits are the tiles from the one its first
    row lies in to the one its last row lies in, one tile if it is empty;
    the steps past the live ones repeat the last live visit."""
    n = sizes.shape[0]
    tiles = rows // tile
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    visits = jnp.where(sizes > 0, (ends + tile - 1) // tile - first, 1)
    done = jnp.cumsum(visits)
    live = done[-1]
    step = jnp.minimum(jnp.arange(tiles + n - 1, dtype=jnp.int32), live - 1)
    group = jnp.searchsorted(done, step, side="right").astype(jnp.int32)
    tile_of = first[group] + step - (done[group] - visits[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile_of.astype(jnp.int32), live[None]


def _rows(s, offsets, group, tile_of, shape, tile):
    """Which rows of step s's tile are its group's, a mask of `shape`."""
    g = group[s]
    row = tile_of[s] * tile + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _working(s, offsets, group, live):
    """Whether step s is a live visit of a group with rows."""
    g = group[s]
    return (s < live[0]) & (offsets[g + 1] > offsets[g])


def _merge(ref, value, s, offsets, group, tile_of, tile):
    """Step s's group's rows of `value` into the tile's block, the others
    kept: a straddled tile's block stays resident between its visits."""
    mask = _rows(s, offsets, group, tile_of, value.shape, tile)
    ref[...] = jnp.where(mask, value, ref[...]).astype(ref.dtype)


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _sigmoid(v):
    return 1.0 / (1.0 + jnp.exp(-v))


def _row_spec(tile, width):
    return pl.BlockSpec((tile, width), lambda s, off, grp, til, live: (
        til[s], 0))


def _group_spec(a, b):
    return pl.BlockSpec((None, a, b), lambda s, off, grp, til, live: (
        grp[s], 0, 0))


def _call(kernel, name, groups, out_shape, in_specs, out_specs, blocks,
          temps, interpret, *args):
    """One kernel over the walk's steps; `blocks`: bytes of a step's blocks,
    `temps`: of its float32 values."""
    steps = groups[1].shape[0]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(steps,), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        # every block twice over (the pipeline's two buffers), the float32
        # values, and room
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * blocks + temps + (16 << 20)),
        name=name,
        interpret=interpret,
    )(*groups, *args)


def _gate_up_kernel(off, grp, til, live, x_ref, wg_ref, wu_ref, h_ref, *,
                    tile):
    s = pl.program_id(0)

    @pl.when(_working(s, off, grp, live))
    def _():
        x = x_ref[...]
        g, u = _dot(x, wg_ref[...]), _dot(x, wu_ref[...])
        _merge(h_ref, g * _sigmoid(g) * u, s, off, grp, til, tile)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gate_up(groups, x, w_gate, w_up, tile, interpret):
    m, d = x.shape
    f = w_up.shape[-1]
    size = x.dtype.itemsize
    return _call(
        functools.partial(_gate_up_kernel, tile=tile), "moe_gmm_gate_up",
        groups, jax.ShapeDtypeStruct((m, f), x.dtype),
        [_row_spec(tile, d), _group_spec(d, f), _group_spec(d, f)],
        _row_spec(tile, f), (tile * d + 2 * d * f + tile * f) * size,
        3 * tile * f * 4, interpret, x, w_gate, w_up)


def _down_kernel(off, grp, til, live, h_ref, w_ref, wd_ref, o_ref, *, tile):
    s = pl.program_id(0)

    @pl.when(_working(s, off, grp, live))
    def _():
        _merge(o_ref, _dot(h_ref[...], wd_ref[...]) * w_ref[...], s, off,
               grp, til, tile)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _down(groups, h, weight, w_down, tile, interpret):
    m, f = h.shape
    d = w_down.shape[-1]
    return _call(
        functools.partial(_down_kernel, tile=tile), "moe_gmm_down", groups,
        jax.ShapeDtypeStruct((m, d), _F32),
        [_row_spec(tile, f), _row_spec(tile, 1), _group_spec(f, d)],
        _row_spec(tile, d),
        (tile * f + f * d) * h.dtype.itemsize + tile * (d + 128) * 4,
        2 * tile * d * 4, interpret, h, weight, w_down)


def _gate_up_bwd_kernel(off, grp, til, live, x_ref, dy_ref, w_ref, wg_ref,
                        wu_ref, wd_ref, dx_ref, dg_ref, du_ref, hw_ref,
                        dw_ref, *, tile):
    s = pl.program_id(0)

    @pl.when(_working(s, off, grp, live))
    def _():
        x, w = x_ref[...], w_ref[...]
        wg, wu = wg_ref[...], wu_ref[...]
        g, u = _dot(x, wg), _dot(x, wu)
        sg = _sigmoid(g)
        act = g * sg
        h = (act * u).astype(x.dtype).astype(_F32)   # as the forward kept it
        dhu = _dot(dy_ref[...], wd_ref[...], _NT)
        dh = dhu * w
        dg = (dh * u * sg * (1.0 + g * (1.0 - sg))).astype(x.dtype)
        du = (dh * act).astype(x.dtype)
        merge = functools.partial(_merge, s=s, offsets=off, group=grp,
                                  tile_of=til, tile=tile)
        merge(dx_ref, _dot(dg, wg, _NT) + _dot(du, wu, _NT))
        merge(dg_ref, dg)
        merge(du_ref, du)
        merge(hw_ref, h * w)
        merge(dw_ref, jnp.sum(h * dhu, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gate_up_bwd(groups, x, dy, weight, w_gate, w_up, w_down, tile,
                 interpret):
    m, d = x.shape
    f = w_up.shape[-1]
    rows_f = jax.ShapeDtypeStruct((m, f), x.dtype)
    return _call(
        functools.partial(_gate_up_bwd_kernel, tile=tile),
        "moe_gmm_gate_up_bwd", groups,
        [jax.ShapeDtypeStruct((m, d), _F32), rows_f, rows_f, rows_f,
         jax.ShapeDtypeStruct((m, 1), _F32)],
        [_row_spec(tile, d), _row_spec(tile, d), _row_spec(tile, 1),
         _group_spec(d, f), _group_spec(d, f), _group_spec(f, d)],
        [_row_spec(tile, d), _row_spec(tile, f), _row_spec(tile, f),
         _row_spec(tile, f), _row_spec(tile, 1)],
        (2 * tile * d + 3 * d * f + 3 * tile * f) * x.dtype.itemsize
        + tile * (d + 2 * 128) * 4, (9 * f + d) * tile * 4,
        interpret, x, dy, weight, w_gate, w_up, w_down)


def _tgmm_kernel(off, grp, til, live, a_ref, b_ref, out_ref, *, tile):
    s = pl.program_id(0)

    @pl.when((s == 0) | (grp[jnp.maximum(s - 1, 0)] != grp[s]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(_working(s, off, grp, live))
    def _():
        a, b = a_ref[...], b_ref[...]
        keep = functools.partial(_rows, s, off, grp, til, tile=tile)
        # rows of another group, or past the last, may hold anything
        a = jnp.where(keep(a.shape), a, jnp.zeros_like(a))
        b = jnp.where(keep(b.shape), b, jnp.zeros_like(b))
        out_ref[...] += _dot(a, b, _TN)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _tgmm(groups, a, b, tile, interpret):
    ka, kb = a.shape[1], b.shape[1]
    return _call(
        functools.partial(_tgmm_kernel, tile=tile), "moe_tgmm", groups,
        jax.ShapeDtypeStruct((groups[0].shape[0] - 1, ka, kb), _F32),
        [_row_spec(tile, ka), _row_spec(tile, kb)], _group_spec(ka, kb),
        tile * (ka + kb) * a.dtype.itemsize + ka * kb * 4,
        tile * (ka + kb) * a.dtype.itemsize + ka * kb * 4, interpret, a, b)


def gate_up(groups, x, w_gate, w_up, tile):
    """`silu(x Wg) * (x Wu)` of each grouped row of x (rows, d) through its
    group's (groups, d, f) matrices -> (rows, f) in x's dtype; rows of no
    group are left undefined."""
    return _gate_up(groups, x, w_gate.astype(x.dtype), w_up.astype(x.dtype),
                    tile, not _on_chip())


def down(groups, h, weight, w_down, tile):
    """`(h Wd) * weight` of each grouped row: h (rows, f), weight (rows, 1)
    float32, Wd (groups, f, d) -> (rows, d) float32; rows of no group are
    left undefined."""
    return _down(groups, h, weight, w_down.astype(h.dtype), tile,
                 not _on_chip())


def gate_up_bwd(groups, x, dy, weight, w_gate, w_up, w_down, tile):
    """What the backward needs of each grouped row, x (rows, d) and dy
    (rows, d) the gradient of its `down` output: (dx
    (rows, d) float32, dg, du, h * weight (rows, f) in x's dtype, the
    weight's gradient (rows, 1) float32); rows of no group are left
    undefined."""
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    return _gate_up_bwd(groups, x, dy.astype(x.dtype), weight, cast(w_gate),
                        cast(w_up), cast(w_down), tile, not _on_chip())


def tgmm(groups, a, b, tile):
    """Each group's `a^T b` over its rows: a (rows, ka), b (rows, kb) ->
    (groups, ka, kb) float32, 0 for an empty group."""
    return _tgmm(groups, a, b, tile, not _on_chip())
