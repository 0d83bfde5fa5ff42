"""The bound of a decode step's walk over a fixed pool: a slot's OWN live
rows.  A slot at position `pos` holds rows 0..pos (the step's own row is
written before the attention reads); a walk in blocks of `block` rows
reads the blocks up to the one that holds `pos` and no further, and an
empty slot costs one block.  The kernels that walk so and the counts that
say what they went over (`kv_rows_pool` of a `serving_decode` span) take
both from here, so a kernel and its count cannot drift apart.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["live_blocks", "rows_walked"]


def live_blocks(pos, active, rows, block):
    """Blocks of `block` rows a walk over `rows` reads for a slot at `pos`:
    those up to the block of the row at `pos` where the slot is `active`,
    one where it is not.  Scalars (inside a kernel, from its prefetched
    operands) or arrays a slot."""
    last = jnp.clip(pos, 0, rows - 1) // block
    return jnp.where(active, last + 1, 1)


def rows_walked(pos, active, rows, block):
    """Rows the walks of all slots went over, int32: what a count of the
    pool's rows read holds where such a kernel ran."""
    return jnp.sum(live_blocks(pos, active, rows, block),
                   dtype=jnp.int32) * block
