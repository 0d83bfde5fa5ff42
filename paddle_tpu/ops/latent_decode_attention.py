"""Single-query latent attention (MLA's absorbed decode step) over a fixed
pool, walked to each slot's OWN live rows.

A decode step's query of a slot is `heads` rows against the slot's cached
rows: the score is `q_lat . c + q_pe . k_pe` over two leaves as they lie in
the pool, `cbuf` (B, rows, latent) and `pbuf` (B, rows, rope), and the
output is `softmax(score) c`: the SAME latent rows once more.  XLA's
batched products read both leaves whole and mask afterwards, and read
`cbuf` a second time for the output; with 48 slots x 8192 rows of which a
fifth are alive that is ten times the bytes a step needs.

The kernel's grid is the LIVE blocks alone, one after the other: slot 0's
blocks of `BLOCK_ROWS` rows from 0 to the one that holds `pos[0]`, then
slot 1's, ... (`live_rows.live_blocks`; an empty slot costs one block).
How many there are only the device knows, so the grid's bound is an
operand; which slot and block a step has comes from a work list made
beside it, prefetched with `pos` and `active`, and the index maps read it:
the pipeline copies step t + 1's block of both leaves while step t's is
multiplied, across slots too, and nothing is copied or stepped over for a
dead block.  A block in VMEM gives the scores (heads, block) on the MXU
with float32 sums, goes through a running softmax (max, sum, a (heads,
latent) float32 accumulator kept in scratch between a slot's steps) and
gives the output product from where it lies: each live row is read from
HBM once.  Rows past `pos` are masked; they can only lie in a slot's last
block.

Two other forms were weighed (PERF.md, PR 37).  A grid over EVERY block of
every slot whose index map repeats the last live block pays a grid step
for each of the 48 x 8192 / 512 = 768 blocks a layer, dead or alive: 0.242
ms a layer against 0.210 on the chip.  A grid over slots with the pool left in HBM and a loop of manual
copies to the slot's own block count cannot be built: Mosaic refuses a
copy of a SLICE of the 64-wide leaf (a kernel argument left in HBM is
padded to 128 lanes and the slice is then "not aligned to tiling").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _free_mesh_axes, _traced_once
from .live_rows import live_blocks, rows_walked

__all__ = ["mla_decode_attention", "BLOCK_ROWS"]

_INTERPRET = False  # tests flip this to run the kernel via the interpreter

# rows a block, from readings on the chip at 48 x 8192 rows with the cell's
# lengths (`probes/mla_probe.py --live`, ms a layer; PERF.md, PR 37): 256:
# 0.381, 512: 0.311, 1024: 0.298, where the masked products take 1.831.  512
# and not 1024: 4% of a layer is 0.7% of a decode step, and the walk goes
# over 14% dead rows where blocks of 1024 go over 24%
BLOCK_ROWS = 512

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _available() -> bool:
    return _INTERPRET or jax.default_backend() == "tpu"


def _kernel(slot_ref, block_ref, pos_ref, active_ref, q_lat_ref, q_pe_ref,
            c_ref, p_ref, o_ref, m_ref, l_ref, acc_ref, *, block, rows):
    """One grid step = one live block of one slot, the slots' blocks one
    after the other: the running softmax starts at a slot's block 0 and is
    written out at its last."""
    t = pl.program_id(0)
    slot, i = slot_ref[t], block_ref[t]
    n = live_blocks(pos_ref[slot], active_ref[slot] != 0, rows, block)
    # the last row the query sees: `pos`; an empty slot's, within its block
    top = jnp.minimum(jnp.clip(pos_ref[slot], 0, rows - 1), n * block - 1)

    @pl.when(i == 0)
    def _first():
        # row 0 is alive in every slot, so the running max is a score's
        # from the first block on and a masked row weighs exp(-1e30 - m) = 0
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[0]
    s = (jax.lax.dot_general(q_lat_ref[0], c, _NT,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(q_pe_ref[0], p_ref[0], _NT,
                               preferred_element_type=jnp.float32))
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(row <= top, s, _NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    e = jnp.exp(s - m_new)
    m_ref[...] = m_new
    l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        e.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when(i == n - 1)
    def _last():
        o_ref[0] = acc_ref[...] / l_ref[...]


@_traced_once(9)
def _walk(steps, slot_of, block_of, pos, active, q_lat, q_pe, cbuf, pbuf,
          block, interpret):
    b, heads, latent = q_lat.shape
    rows, rope = cbuf.shape[1], pbuf.shape[2]
    per_slot = lambda width: pl.BlockSpec(  # noqa: E731
        (1, heads, width), lambda t, slot, blk, *_: (slot[t], 0, 0))
    pool = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block, width), lambda t, slot, blk, *_: (slot[t], blk[t], 0))
    return pl.pallas_call(
        functools.partial(_kernel, block=block, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[per_slot(latent), per_slot(rope), pool(latent),
                      pool(rope)],
            out_specs=per_slot(latent),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),       # running max
                pltpu.VMEM((heads, 1), jnp.float32),       # running sum
                pltpu.VMEM((heads, latent), jnp.float32),  # weighted rows
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_decode_attention",
        interpret=interpret,
    )(slot_of, block_of, pos, active, q_lat, q_pe, cbuf, pbuf)


def _work_list(pos, active, rows, block):
    """How many steps the walk of all slots has, known on the device alone,
    and the (slot, block) of every step it can have, (B rows / block,)
    int32 each: slot 0's live blocks, then slot 1's, ...; the entries past
    the last live block are never read."""
    n = live_blocks(pos, active, rows, block).astype(jnp.int32)
    ends = jnp.cumsum(n)
    t = jnp.arange(pos.shape[0] * (rows // block), dtype=jnp.int32)
    # the slot of step t: how many slots' walks have ended by then
    slot = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), pos.shape[0] - 1)
    return ends[-1], slot, t - (ends - n)[slot]


def mla_decode_attention(q_lat, q_pe, cbuf, pbuf, pos, active):
    """`softmax(q_lat . c + q_pe . k_pe) c` of one query a slot over the
    slot's rows 0..pos: q_lat (B, heads, latent) and q_pe (B, heads, rope)
    ALREADY SCALED, cbuf (B, rows, latent) and pbuf (B, rows, rope) the
    pool's leaves as they lie (the step's row written), pos (B,) and active
    (B,) -> ((B, heads, latent) float32, the rows of a leaf the walk went
    over, int32); an inactive slot's output is that of its first block and
    means nothing.  Operands in the leaves' dtype, float32 sums and
    softmax.  None where the kernel does not apply: not a TPU, leaves that
    are not bfloat16, rows that are no whole blocks, widths that do not fill
    lanes (latent by 128, rope by 64) or sublanes (heads by 8), a
    multi-device program."""
    heads, latent = q_lat.shape[1:]
    rows, rope = cbuf.shape[1], pbuf.shape[2]
    if not _available():
        return None
    if cbuf.dtype != jnp.bfloat16 or pbuf.dtype != cbuf.dtype:
        return None
    if rows % BLOCK_ROWS or latent % 128 or rope % 64 or heads % 8:
        return None
    if any(n > 1 for n in _free_mesh_axes().values()):
        return None
    with jax.named_scope("mla_decode_attention"):
        out = _walk(*_work_list(pos, active, rows, BLOCK_ROWS),
                    pos.astype(jnp.int32), active.astype(jnp.int32),
                    q_lat.astype(cbuf.dtype), q_pe.astype(cbuf.dtype),
                    cbuf, pbuf, BLOCK_ROWS, _INTERPRET)
    return out, rows_walked(pos, active, rows, BLOCK_ROWS)
