"""Deduped gather / per-shard scatter numerics for giant embedding tables.

Reference lineage: the parameter-server sparse path —
operators/distributed/parameter_prefetch.cc (deduplicate lookup ids, pull
only the live rows), operators/math/selected_rows_functor.cc MergeAdd, and
adam_op.h lazy_mode.  TPU-native: every shape is static under jit, so the
dedup keeps full lookup-count buffers with out-of-range sentinels (the
`optimizer.sparse.merge_rows` convention) and the per-shard update reuses
`lazy_row_update` INSIDE a shard_map — each mesh shard touches only its own
rows, no densify, no all-gather of the table.

Bit-exactness contract (tests/test_embedding_shard.py): the deduped gather
returns exactly `w[ids]`, and the per-shard lazy update is bit-identical to
the single-device `lazy_row_update` — merge order per row id is preserved
because rebasing ids by the shard offset is monotone and jnp.argsort is
stable, so segment sums add the same values in the same order.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.selected_rows import RowSparseGrad
from ..core.tensor import Tensor, unwrap


def dedup_ids(flat_ids, height: int):
    """Static-shape dedup of (n,) lookup ids.

    Returns (uids, inv, n_unique): uids (n,) int32 holds the unique ids in
    the leading slots and the sentinel `height` in the rest; inv (n,) int32
    maps each lookup position to its unique slot (never a sentinel slot);
    n_unique is a traced scalar.  out = w[uids][inv] == w[flat_ids] exactly.
    """
    n = flat_ids.shape[0]
    ids = flat_ids.astype(jnp.int32)
    order = jnp.argsort(ids)  # stable: duplicate ids keep original order
    sr = ids[order]
    if n > 1:
        is_new = jnp.concatenate(
            [jnp.ones((1,), bool), sr[1:] != sr[:-1]])
    else:
        is_new = jnp.ones((n,), bool)
    seg = jnp.cumsum(is_new) - 1
    inv = jnp.zeros((n,), jnp.int32).at[order].set(seg.astype(jnp.int32))
    uids = jax.ops.segment_max(sr, seg, num_segments=n)
    n_unique = seg[-1] + 1
    uids = jnp.where(jnp.arange(n) < n_unique, uids, height)
    return uids.astype(jnp.int32), inv, n_unique


def dedup_gather(w, flat_ids):
    """Gather w[flat_ids] touching each live row once: dedup, gather the
    unique rows, re-expand.  Returns (out (n, width), uids, inv)."""
    height = w.shape[0]
    uids, inv, _ = dedup_ids(flat_ids, height)
    rows = jnp.take(w, jnp.clip(uids, 0, height - 1), axis=0)
    return jnp.take(rows, inv, axis=0), uids, inv


def psum_gather(w, uids, axis: str, mesh):
    """Row-sharded gather: each shard gathers the uids it owns, zeroes the
    rest, and a psum over `axis` assembles the (n, width) result — the
    cross-shard traffic is O(unique rows · width), never the table.

    Shards other than the owner contribute exact zeros, so the psum is
    bit-identical to a single-device gather."""
    from jax.sharding import PartitionSpec as P

    height = w.shape[0]
    local_h = height // mesh.shape[axis]

    def local(w_l, uids):
        start = jax.lax.axis_index(axis) * local_h
        lids = uids - start
        mine = (lids >= 0) & (lids < local_h)
        rows = jnp.take(w_l, jnp.clip(lids, 0, local_h - 1), axis=0)
        rows = jnp.where(mine[:, None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, axis)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(axis, None), P()), out_specs=P(),
                         check_vma=False)(w, uids)


def _state_specs(state, height: int, axis: str):
    """PartitionSpec tree for an optimizer-state dict: row leaves (leading
    dim == table height) shard with the table, scalars replicate."""
    from jax.sharding import PartitionSpec as P

    def spec(s):
        if (hasattr(s, "shape") and getattr(s, "ndim", 0) >= 1
                and s.shape[0] == height):
            return P(*(((axis,) + (None,) * (s.ndim - 1))))
        return P()
    return jax.tree_util.tree_map(spec, state)


def sharded_lazy_row_update(optimizer, p, grad: RowSparseGrad, state, lr,
                            step_no, axis: str, mesh,
                            decay_flag: bool = True, lr_mult: float = 1.0):
    """Per-shard lazy row update for a row-sharded table: each shard rebases
    the global lookup ids into its own row range (foreign ids become the
    local sentinel) and runs the SAME `lazy_row_update` on its local shard —
    O(lookups·width) work per shard, writes strictly local, moments of
    untouched rows untouched.  The distributed half of adam_op.h lazy_mode,
    with GSPMD placement instead of a parameter server."""
    from jax.sharding import PartitionSpec as P
    from ..optimizer.sparse import lazy_row_update

    height, width = p.shape
    nshards = mesh.shape[axis]
    local_h = height // nshards
    st_specs = _state_specs(state, height, axis)

    def local(p_l, state_l, rows, values, lr, step_no):
        start = jax.lax.axis_index(axis) * local_h
        lids = rows - start
        mine = (lids >= 0) & (lids < local_h)
        # foreign lookups get the local sentinel: merge_rows groups them
        # into segments whose scatter-back is dropped (mode="drop")
        lids = jnp.where(mine, lids, local_h).astype(jnp.int32)
        g = RowSparseGrad(lids, values, (local_h, width))
        return lazy_row_update(optimizer, p_l, g, state_l, lr, step_no,
                               decay_flag, lr_mult)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), st_specs, P(), P(), P(), P()),
        out_specs=(P(axis, None), st_specs),
        check_vma=False)(p, state, grad.rows, grad.values, lr, step_no)


# ---------------------------------------------------------------------------
# lookup entry point (eager + TrainStep sparse-grad channel)
# ---------------------------------------------------------------------------

def _note_lookup_stats(flat_ids):
    """Host-side dedup counters (concrete ids only — traced lookups are
    counted by the host-table pipeline instead)."""
    try:
        ids = np.asarray(flat_ids)
    except Exception:
        return
    from ..utils.monitor import stat_add
    stat_add("STAT_embedding_rows_gathered", int(ids.size))
    stat_add("STAT_embedding_rows_unique", int(np.unique(ids).size))


def ctx_sharded_lookup(ctx, x, weight, padding_idx=None):
    """ShardedEmbedding lookup inside a TrainStep trace: the deduped
    (optionally psum-sharded) gather runs under stop_gradient and the
    per-lookup gradient rides the zeros-cotangent channel, exactly like
    `selected_rows.ctx_embedding` — so the step's RowSparseGrad is
    bit-identical to the plain Embedding(sparse=True) path."""
    ids = unwrap(x).astype(jnp.int32)
    w = unwrap(weight)
    name = getattr(weight, "name", None) or "sharded_embedding"
    key = ctx.key_for(name)
    width = w.shape[1]
    height = w.shape[0]
    n = int(np.prod(ids.shape))

    if ctx.mode == "record":
        ctx.specs[key] = (n, width, w.dtype)
        out = jnp.take(w, ids, axis=0)
    else:
        z = ctx.zeros[key]
        flat = ids.reshape(-1)
        ctx.ids[key] = flat
        uids, inv, _ = dedup_ids(flat, height)
        axis = getattr(weight, "row_shard_axis", None)
        mesh = getattr(weight, "row_shard_mesh", None)
        wsg = jax.lax.stop_gradient(w)
        if axis is not None and mesh is not None and mesh.shape[axis] > 1:
            rows = psum_gather(wsg, jnp.clip(uids, 0, height - 1),
                               axis, mesh)
        else:
            rows = jnp.take(wsg, jnp.clip(uids, 0, height - 1), axis=0)
        out = (jnp.take(rows, inv, axis=0).reshape(ids.shape + (width,))
               + z.reshape(ids.shape + (width,)))
    if padding_idx is not None:
        out = jnp.where((ids == padding_idx)[..., None],
                        jnp.zeros((), out.dtype), out)
    return Tensor(out, stop_gradient=True)


def sharded_lookup(x, weight, padding_idx=None):
    """F.embedding analogue for ShardedEmbedding weights: routes through the
    TrainStep sparse-grad context when one is active, else the eager
    tape path (RowSparseGrad cotangent), else a plain deduped gather."""
    from ..core import selected_rows as sr
    from ..core.tensor import is_grad_enabled

    ctx = sr.current_ctx()
    name = getattr(weight, "name", None) or "sharded_embedding"
    if ctx is not None:
        if ctx.wants(name):
            return ctx_sharded_lookup(ctx, x, weight, padding_idx)
        # demoted (tied) weight: fall through to the dense differentiable
        # path via F.embedding below
        from ..nn import functional as F
        return F.embedding(x, weight, padding_idx=padding_idx, sparse=False)
    mesh = getattr(weight, "row_shard_mesh", None)
    if mesh is not None and not isinstance(unwrap(x), jax.core.Tracer):
        # eager on a mesh: ids must live on the table's device set before
        # mixing with the row-sharded weight (replicated — they're small)
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = Tensor(jax.device_put(unwrap(x), NamedSharding(mesh, P())))
    ids = unwrap(x)
    if not isinstance(ids, jax.core.Tracer):
        _note_lookup_stats(ids.reshape(-1))
    if (isinstance(weight, Tensor) and is_grad_enabled()
            and not weight.stop_gradient):
        return sr.eager_sparse_embedding(x, weight, padding_idx)
    out, _, _ = dedup_gather(unwrap(weight), ids.reshape(-1).astype(jnp.int32))
    out = out.reshape(tuple(ids.shape) + (weight.shape[1],))
    if padding_idx is not None:
        out = jnp.where((unwrap(x) == padding_idx)[..., None],
                        jnp.zeros((), out.dtype), out)
    return Tensor(out, stop_gradient=True)
