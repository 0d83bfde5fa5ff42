"""The grouped-matmul kernels of the trainable held experts
(`ops/grouped_matmul.py`) through the interpreter, against dense einsums
over the same groups: the forward's two kernels, every gradient the
backward kernel gives, the weight gradients' `tgmm`, and the walk's lists.

Groups of rows sorted by group, in tiles of 16 rows over a bound of 64: an
empty group, a group that straddles two tiles, groups that end on a tile's
edge, no row in any group, every row in one.  Float32 throughout, so the
kernels' sums differ from the einsums' by the order of the terms alone:
2e-5 of the largest element.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import grouped_matmul as gm

TILE, ROWS, D, F = 16, 64, 32, 16
TOL = 2e-5

# rows of each of four groups, in order; the rows past their sum are in none
SIZES = {
    "straddles_and_empty": (10, 20, 0, 14),
    "ends_on_tile_edges": (16, 16, 8, 0),
    "empty_first_and_last": (0, 5, 30, 0),
    "no_row_held": (0, 0, 0, 0),
    "every_row_held": (20, 12, 16, 16),
    "one_group_takes_all": (0, 64, 0, 0),
}


def operands(seed):
    rng = np.random.RandomState(seed)
    mk = lambda *s, scale=1.0: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) * scale, jnp.float32)
    return dict(x=mk(ROWS, D), dy=mk(ROWS, D), weight=jnp.abs(mk(ROWS, 1)),
                wg=mk(4, D, F, scale=0.3), wu=mk(4, D, F, scale=0.3),
                wd=mk(4, F, D, scale=0.3))


def group_of(sizes):
    """(rows,) the group of each row, 4 for a row in none."""
    return np.repeat(np.arange(5), list(sizes) + [ROWS - sum(sizes)])


def dense(x, weight, wg, wu, wd, grp):
    """Each row through its group's matrices (a row in no group: 0)."""
    onehot = jnp.asarray(grp[:, None] == np.arange(4)[None, :], jnp.float32)
    pick = lambda w: jnp.einsum("re,eab->rab", onehot, w)  # noqa: E731
    hi = "highest"
    g = jnp.einsum("rd,rdf->rf", x, pick(wg), precision=hi)
    u = jnp.einsum("rd,rdf->rf", x, pick(wu), precision=hi)
    h = jax.nn.silu(g) * u
    return h, jnp.einsum("rf,rfd->rd", h, pick(wd), precision=hi) * weight


def close(got, want, live, name):
    got, want = np.asarray(got)[live], np.asarray(want)[live]
    scale = max(float(np.max(np.abs(want))), 1e-30) if want.size else 1.0
    np.testing.assert_allclose(got, want, atol=TOL * scale, err_msg=name)


def walk(sizes, rows=ROWS):
    return gm.group_tiles(jnp.asarray(sizes, jnp.int32), rows, TILE)


@pytest.mark.parametrize("case", list(SIZES))
def test_the_forward_kernels_are_the_dense_products(case):
    """`gate_up` then `down`: each grouped row's `silu(x Wg) * (x Wu)` and
    its `(h Wd) * weight`, as its group's matrices give them."""
    sizes, a = SIZES[case], operands(1)
    grp = group_of(sizes)
    groups = walk(sizes)
    h = gm.gate_up(groups, a["x"], a["wg"], a["wu"], TILE)
    out = gm.down(groups, h, a["weight"], a["wd"], TILE)
    want_h, want_out = dense(a["x"], a["weight"], a["wg"], a["wu"], a["wd"],
                             grp)
    live = grp < 4
    close(h, want_h, live, "h")
    close(out, want_out, live, "out")


@pytest.mark.parametrize("case", list(SIZES))
def test_the_backward_kernel_gives_every_gradient_of_the_dense_row(case):
    """`gate_up_bwd`: the input's and the weight's gradients of the dense
    row's output against `jax.vjp`, the gate's and the up's gradients and
    `h * weight` against their closed forms."""
    sizes, a = SIZES[case], operands(2)
    grp = group_of(sizes)
    groups = walk(sizes)
    dx, dg, du, hw, dw = gm.gate_up_bwd(groups, a["x"], a["dy"], a["weight"],
                                        a["wg"], a["wu"], a["wd"], TILE)
    _, vjp = jax.vjp(lambda x, w: dense(x, w, a["wg"], a["wu"], a["wd"],
                                        grp)[1], a["x"], a["weight"])
    want_dx, want_dw = vjp(a["dy"])
    onehot = jnp.asarray(grp[:, None] == np.arange(4)[None, :], jnp.float32)
    pick = lambda w: jnp.einsum("re,eab->rab", onehot, w)  # noqa: E731
    g = jnp.einsum("rd,rdf->rf", a["x"], pick(a["wg"]), precision="highest")
    u = jnp.einsum("rd,rdf->rf", a["x"], pick(a["wu"]), precision="highest")
    dh = jnp.einsum("rd,rfd->rf", a["dy"], pick(a["wd"]),
                    precision="highest") * a["weight"]
    sg = jax.nn.sigmoid(g)
    live = grp < 4
    close(dx, want_dx, live, "dx")
    close(dw, want_dw, live, "weight")
    close(dg, dh * u * sg * (1 + g * (1 - sg)), live, "dg")
    close(du, dh * jax.nn.silu(g), live, "du")
    close(hw, jax.nn.silu(g) * u * a["weight"], live, "h * weight")


@pytest.mark.parametrize("case", list(SIZES))
def test_tgmm_is_each_groups_sum_over_its_rows(case):
    """`tgmm`: group e's `a^T b` over its rows alone, 0 for an empty group;
    the rows past the groups hold NaN, which no group may read."""
    sizes, a = SIZES[case], operands(3)
    grp = group_of(sizes)
    live = jnp.asarray(grp < 4)[:, None]
    lhs = jnp.where(live, a["x"], jnp.nan)
    rhs = jnp.where(live, a["dy"][:, :F], jnp.nan)
    got = gm.tgmm(walk(sizes), lhs, rhs, TILE)
    onehot = jnp.asarray(grp[:, None] == np.arange(4)[None, :], jnp.float32)
    want = jnp.einsum("re,ra,rb->eab", onehot, a["x"], a["dy"][:, :F],
                      precision="highest")
    close(got, want, slice(None), "tgmm")


@pytest.mark.parametrize("case", ["straddles_and_empty", "no_row_held",
                                  "one_group_takes_all"])
def test_dead_tiles_change_nothing(case):
    """The walk over a bound of 64 rows, its steps past the live visits
    doing nothing, gives what the walk over the live tiles alone gives."""
    sizes, a = SIZES[case], operands(4)
    short = max(TILE, -(-sum(sizes) // TILE) * TILE)
    live = group_of(sizes)[:short] < 4
    for rows in (ROWS, short):
        cut = {k: (v[:rows] if v.shape[0] == ROWS else v)
               for k, v in a.items()}
        groups = walk(sizes, rows)
        h = gm.gate_up(groups, cut["x"], cut["wg"], cut["wu"], TILE)
        outs = (h, gm.down(groups, h, cut["weight"], cut["wd"], TILE),
                *gm.gate_up_bwd(groups, cut["x"], cut["dy"], cut["weight"],
                                cut["wg"], cut["wu"], cut["wd"], TILE))
        got = [np.asarray(o)[:short][live] for o in outs] + [np.asarray(
            gm.tgmm(groups, cut["x"], cut["dy"], TILE))]
        if rows == ROWS:
            whole = got
    for w, s in zip(whole, got):
        np.testing.assert_array_equal(w, s)


def test_the_walk_visits_each_groups_tiles_then_repeats_the_last():
    """(10, 20, 0, 14) over 4 tiles of 16: group 0 in tile 0, group 1 in
    tiles 0 and 1 (it straddles), the empty group 2 once in tile 1, group 3
    in tiles 1 and 2; 6 live visits of the 7 steps, the last repeated."""
    offsets, group, tile, live = (np.asarray(v) for v in walk(
        SIZES["straddles_and_empty"]))
    assert offsets.tolist() == [0, 10, 30, 30, 44]
    assert live.tolist() == [6]
    assert group.tolist() == [0, 1, 1, 2, 3, 3, 3]
    assert tile.tolist() == [0, 0, 1, 1, 1, 2, 2]
    # no group: each empty group once, in the first tile
    _, group, tile, live = (np.asarray(v) for v in walk(
        SIZES["no_row_held"]))
    assert live.tolist() == [4] and group.tolist()[:4] == [0, 1, 2, 3]
    assert set(tile.tolist()) == {0}
