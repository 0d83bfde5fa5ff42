"""Paged attention: decode attention against a block-pooled KV cache.

The serving engine's paged KV pool (serving/kv_pool.py) stores KV as
``(num_blocks, block_size, heads, head_dim)`` per layer, with each slot
owning an indirection table of block ids.  This module is the device
side of that design:

- **gather / scatter / scrub primitives** — the three jnp operations the
  compiled serving decode/verify/prefill programs are built from:
  ``gather_block_rows`` materializes one slot's contiguous KV view from
  its block table (a single XLA gather), ``scatter_block_rows`` writes
  freshly produced KV rows back through the table (a single scatter;
  sentinel ids drop, so inactive slots and warmup write nothing), and
  ``scrub_blocks`` zeroes blocks the moment a slot first enters them
  (the scrub-on-recycle guarantee — a re-served block is erased in the
  same program that first writes it).  On CPU the gather fallback is
  also the engine's attention path: reconstructing the contiguous
  ``(T, heads, head_dim)`` view and running the model's own
  ``forward_fixed`` keeps paged streams BIT-IDENTICAL to the fixed-pool
  engine and to solo generate — the gathered array holds exactly the
  values the fixed row would, so every downstream float op is the same.
- **``paged_attention``** — the standalone op for one decode query
  against one slot's table: jnp gather fallback everywhere, and a
  pallas TPU kernel that never materializes the contiguous view — the
  block table rides in as a scalar-prefetch operand and the grid DMAs
  exactly the live blocks HBM->VMEM, accumulating flash-style online
  softmax across blocks (the vLLM PagedAttention structure; design
  notes /opt/skills/guides/pallas_guide.md).  The kernel is the TPU
  fast path: gather-free, O(live blocks) HBM traffic instead of
  O(max_len).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_block_rows", "scatter_block_rows", "scrub_blocks",
           "paged_attention"]

_INTERPRET = False  # tests flip this to run the kernel via the interpreter


# ---------------------------------------------------------------------------
# block-pool primitives (used inside the compiled serving programs)
# ---------------------------------------------------------------------------

def gather_block_rows(pool, table):
    """(num_blocks, block_size, *rest) pool + (nb,) block table ->
    (nb * block_size, *rest) contiguous rows, one XLA gather.  Sentinel
    (out-of-range) table entries clip to the last block — their rows are
    only ever read under the attention mask, never trusted."""
    blocks = jnp.take(pool, table, axis=0, mode="clip")
    return blocks.reshape((blocks.shape[0] * blocks.shape[1],)
                          + blocks.shape[2:])


def scatter_block_rows(pool, block_ids, offsets, rows):
    """Write rows[i] -> pool[block_ids[i], offsets[i]] in one scatter.
    Out-of-range block ids (the allocator's sentinel) are DROPPED — the
    engine routes inactive slots, finished-run tail iterations, and
    warmup through the sentinel so they write nothing.  Distinct live
    slots never collide: their tables are disjoint by construction."""
    return pool.at[block_ids, offsets].set(rows.astype(pool.dtype),
                                           mode="drop")


def scrub_blocks(pool, block_ids):
    """Zero whole blocks (sentinel ids dropped).  Issued by the decode/
    verify programs for every block a slot ENTERS (write offset 0) before
    the row write: a recycled block is erased by the same program that
    first reuses it, so no prior tenant's KV survives re-serving.  Safe
    by construction: a block's first row is the entering position, so
    every committed row of the entering slot lives in earlier blocks."""
    return pool.at[block_ids].set(0, mode="drop")


# ---------------------------------------------------------------------------
# standalone paged attention op
# ---------------------------------------------------------------------------

def _available() -> bool:
    return _INTERPRET or jax.default_backend() == "tpu"


def _fallback(q, kpool, vpool, table, pos):
    """jnp gather path: reconstruct the contiguous view, masked softmax.
    Bit-compatible with the fixed-pool engine's attention (same values in
    the gathered buffer -> same float ops)."""
    k = gather_block_rows(kpool, table).astype(jnp.float32)  # (T, H, D)
    v = gather_block_rows(vpool, table).astype(jnp.float32)
    d = q.shape[-1]
    s = jnp.einsum("hd,thd->ht", q.astype(jnp.float32), k) / jnp.sqrt(
        jnp.float32(d))
    t_idx = jnp.arange(k.shape[0])
    s = jnp.where((t_idx <= pos)[None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("ht,thd->hd", p, v).astype(q.dtype)


def _kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block_size):
    """One grid step = one block of one slot's table: online-softmax
    accumulate q against the DMA'd (block_size, H, D) KV block."""
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                    # (H, D)
    kb = k_ref[0].astype(jnp.float32)                     # (bs, H, D)
    vb = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]
    # a broadcast multiply and a sum, not an einsum: "hd,bhd->hb" is a
    # batched dot with no free left-hand dimension, which Mosaic refuses
    s = (q[None] * kb).sum(axis=-1).T / jnp.sqrt(jnp.float32(d))  # (H, bs)
    # rows of this block past the write position are dead
    row = i * block_size + jax.lax.broadcasted_iota(jnp.int32,
                                                    s.shape, 1)
    s = jnp.where(row <= pos_ref[0], s, -jnp.inf)

    m_prev = m_scr[...][:, 0]                             # (H,)
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # all-masked blocks keep m at -inf; exp(-inf - -inf) guards below
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_new[:, None]), 0.0)
    l_new = l_scr[...][:, 0] * alpha + p.sum(axis=1)
    acc = acc_scr[...] * alpha[:, None] + (p.T[:, :, None] * vb).sum(axis=0)
    m_scr[...] = m_new[:, None]
    l_scr[...] = l_new[:, None]
    acc_scr[...] = acc

    @pl.when(i == nb - 1)
    def _emit():
        denom = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[...] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def _pallas_paged_attention(q, kpool, vpool, table, pos):
    nb = table.shape[0]
    nb_pool = kpool.shape[0]
    bs = kpool.shape[1]
    h, d = q.shape

    def block_ix(i, table_ref, pos_ref):
        # same sentinel contract as the jnp fallback's mode="clip":
        # unallocated tail entries hold an out-of-range id — clamp the
        # DMA address into the pool (the position mask kills the rows)
        return (jnp.minimum(table_ref[i], nb_pool - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (table, pos) drive the block DMA
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((h, d), lambda i, table_ref, pos_ref: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs, h, d), block_ix),
            pl.BlockSpec((1, bs, h, d), block_ix),
        ],
        out_specs=pl.BlockSpec((h, d),
                               lambda i, table_ref, pos_ref: (0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # running denominator
            pltpu.VMEM((h, d), jnp.float32),   # weighted-V accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, d), q.dtype),
        interpret=_INTERPRET,
    )(table.astype(jnp.int32), jnp.asarray(pos, jnp.int32).reshape(1),
      q, kpool, vpool)


def paged_attention(q, kpool, vpool, table, pos):
    """Decode attention for ONE slot: query `q` (heads, head_dim) against
    the slot's paged KV — `kpool`/`vpool` (num_blocks, block_size, heads,
    head_dim), `table` (nb,) int32 block ids, `pos` the slot's current
    write position (rows > pos are masked; the row at `pos` must already
    be written).  vmap over slots for a batch.

    TPU (or `_INTERPRET`): the pallas kernel — block table as a
    scalar-prefetch operand, one (block_size, H, D) block DMA'd per grid
    step, flash-style online softmax across blocks; the contiguous KV
    view is never materialized.  Both paths accept the engine's real
    tables: out-of-range sentinel entries (unallocated tail blocks)
    clamp/clip into the pool and their rows die under the position
    mask.  Elsewhere: the jnp gather fallback (bit-compatible with the
    fixed-pool engine)."""
    if _available():
        return _pallas_paged_attention(q, kpool, vpool, table, pos)
    return _fallback(q, kpool, vpool, table, pos)
