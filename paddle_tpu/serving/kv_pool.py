"""Paged KV-cache pool: block-granular KV allocation for the serving engine.

The PR-4 engine charges every resident request the full ``max_len`` of KV
HBM (`gen_fixed_cache(max_slots, max_len)` — one row per slot).  At
production scale that cap is the binding constraint: a 32-token request
and a 512-token request pay the same HBM, so the number of resident slots
is ``pool_bytes / max_len_bytes`` no matter what the traffic looks like.

vLLM's PagedAttention observation (Kwon et al., 2023) applied to this
engine: hold ONE device-resident block pool per layer —
``[num_blocks, block_size, heads, head_dim]`` — and give each slot an
indirection table of block ids covering exactly the rows it has actually
written.  Long and short requests then share HBM, and the resident-slot
count is bounded by *aggregate* live tokens, not ``slots * max_len``.

Split of responsibilities:

- **PagedKVPool** (here, host side): the block allocator — free-list,
  slot -> block-table indirection, alloc/append/free, capacity
  accounting (including the ``PDTPU_FAULT_KV_EXHAUST`` forced-exhaustion
  cap), and construction of the device pools from any model speaking the
  ``gen_fixed_cache`` protocol.  Pure host bookkeeping: nothing of it is
  ever traced.
- **FixedKVView / PagedKVView** (here): what the engine's three program
  bodies know of a layout.  Each has the same trace-time entries —
  `open` (pools + the call's tables -> the contiguous view the model
  runs against), `publish` (the rows a call wrote, back into the pools),
  `prompt_cache` / `write_prompt` (what a prompt's forward runs against,
  and where its rows go) — and the host-side inputs that go with them
  (`prompt_inputs`, `batch_inputs`; over no slot they are the sentinel
  forms warm-up uses).  The engine holds one and never asks which.
- **ops/paged_attention.py** (device side): the gather/scatter/scrub
  primitives the compiled serving programs use against the pool, plus
  the standalone paged-attention op (jnp gather fallback on CPU, pallas
  block-table kernel for TPU).
- **serving/engine.py**: `ServingEngine(kv="paged", block_size=...)`
  wires both into the unchanged engine contracts (compile bound,
  bit-identical streams, preempt/restore).

Scrub-on-recycle
----------------
Freed blocks return to the free-list and are re-served with a hard
no-stale-KV guarantee enforced INSIDE the compiled programs (zero extra
programs, zero idle HBM passes): a prefill overwrites every block it
claims end-to-end (prompt KV + zero padding to the block boundary), and
the decode/verify programs zero a block in full the moment a slot's
write position first enters it (``offset == 0``), before writing the new
row.  A block is only ever readable through a slot's table, tables only
cover rows the slot wrote, and the first write into a re-served block
erases all of it — so no request can observe another tenant's KV, and
the device state of a re-served block provably contains none
(tests/test_dist_serving.py::test_recycled_block_is_scrubbed).

Exhaustion is backpressure, not a crash: admission checks `free_blocks`
before claiming a slot, `ensure` returning False mid-decode triggers
preemption of the newest low-priority run (engine policy), and the typed
`KVPoolExhaustedError` is the terminal state for runs that can no longer
fit at all.  ``PDTPU_FAULT_KV_EXHAUST=N`` caps the live capacity to N
blocks to force every one of those paths on CPU.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError, ResourceExhaustedError
from ..utils import faults

__all__ = ["PagedKVPool", "KVPoolExhaustedError", "FixedKVView",
           "PagedKVView"]


class KVPoolExhaustedError(ResourceExhaustedError):
    """The paged KV block pool cannot hold this run: every block is in
    use (or the pool is capped by PDTPU_FAULT_KV_EXHAUST) and no
    lower-priority victim can be preempted to make room.  The request is
    terminal — resubmit when the pool drains, or raise num_blocks."""
    code = "ResourceExhausted"


_obs_handles: Dict[str, tuple] = {}


def _obs(pool: str):
    """(blocks_used_gauge, blocks_free_gauge) bound to this pool's
    `pool=` label — cached handles (registry.reset() zeroes values in
    place).  Labeling keeps two allocators in one process (a target and
    a draft pool, or two fleet replicas) from overwriting each other in
    /metrics."""
    handles = _obs_handles.get(pool)
    if handles is None:
        from ..observability import metrics as _m
        used = _m.gauge("serving_kv_blocks_used",
                        "paged KV pool blocks currently allocated",
                        labelnames=("pool",))
        free = _m.gauge("serving_kv_blocks_free",
                        "paged KV pool blocks free (after any fault cap)",
                        labelnames=("pool",))
        handles = _obs_handles[pool] = (used.labels(pool=pool),
                                        free.labels(pool=pool))
    return handles


class PagedKVPool:
    """Host-side block allocator over a device-resident block pool.

    ``build_pools(model, ...)`` constructs the per-layer device pools —
    each leaf of a layer's tuple in ``model.gen_fixed_cache(1, 1)`` becomes a
    ``(num_blocks, block_size, *leaf.shape[2:])`` zero pool — and the
    allocator hands out block ids: ``alloc``/``ensure`` grow a slot's
    table to cover a row count, ``free`` recycles the slot's blocks,
    ``table_array`` renders the table as the fixed-shape
    ``(max_blocks_per_slot,)`` int32 input the compiled programs take
    (unallocated entries hold the ``num_blocks`` sentinel: reads clip to
    masked rows, writes drop).

    All mutation happens on the engine loop thread; the lock only guards
    the metric snapshots other threads read."""

    def __init__(self, num_blocks: int, block_size: int, pool_len: int,
                 name: str = "target"):
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.pool_len = int(pool_len)
        self.name = str(name)
        if self.block_size < 1:
            raise InvalidArgumentError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks < 1:
            raise InvalidArgumentError(
                f"num_blocks must be >= 1, got {self.num_blocks}")
        # max blocks one slot can ever hold (its table's static width)
        self.max_blocks_per_slot = -(-self.pool_len // self.block_size)
        self._lock = threading.Lock()
        # LIFO free-list: the most recently freed block is re-served
        # first (deterministic recycling — the scrub proof relies on it)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._tables: Dict[int, List[int]] = {}
        # block id -> number of slot tables referencing it.  Without a
        # prefix cache every block has at most one reference and the
        # accounting reduces to the PR-8 free-list; with one, shared
        # prefix blocks carry ref > 1 and `free` only recycles a block
        # when its LAST reference drops.
        self._refs: Dict[int, int] = {}
        self._live = 0          # distinct blocks with ref > 0
        # blocks owned by the prefix cache: at ref 0 they stay RESIDENT
        # (evictable, not free-listed) until the cache evicts them
        self._cached: set = set()
        # prefix-cache hooks (engine loop thread only): reclaim(n) asks
        # the cache to evict >= n evictable blocks back to the free
        # list; unref(ids) tells it these cached blocks just hit ref 0
        self._on_reclaim = None
        self._on_cached_unref = None
        # debug/test aid: the most recent block ids handed out, in order —
        # the scrub-on-recycle proof reads which blocks were RE-served
        self.served_log: "deque[int]" = deque(maxlen=512)
        # bumped on every table mutation (growth or free): the engine
        # caches its device-side (tables, active) batch inputs against it
        # so unchanged ticks re-upload nothing
        self.version = 0

    def set_cache_hooks(self, reclaim, unref):
        """Attach a prefix cache (serving/prefix_cache.py)."""
        self._on_reclaim = reclaim
        self._on_cached_unref = unref

    # -- capacity ------------------------------------------------------------
    def capacity(self) -> int:
        """Usable blocks RIGHT NOW: num_blocks, unless
        PDTPU_FAULT_KV_EXHAUST caps it lower (consulted live)."""
        cap = faults.kv_exhaust_cap()
        return self.num_blocks if cap is None else min(self.num_blocks, cap)

    def used_blocks(self) -> int:
        """Distinct blocks with at least one table reference.  Cached
        refcount-0 blocks are NOT used: they are resident but evictable,
        so they count as free for admission (block-aware gate)."""
        with self._lock:
            return self._live

    def free_blocks(self) -> int:
        return max(0, self.capacity() - self.used_blocks())

    def block_ref(self, block: int) -> int:
        """Live reference count of one block (0 = unreferenced)."""
        with self._lock:
            return self._refs.get(block, 0)

    def cached_blocks(self) -> int:
        """Blocks currently owned by the prefix cache (any refcount)."""
        with self._lock:
            return len(self._cached)

    def blocks_for(self, rows: int) -> int:
        """Blocks needed to hold `rows` KV rows."""
        return -(-max(0, int(rows)) // self.block_size)

    def can_ever_fit(self, rows: int) -> bool:
        """Whether a run holding `rows` rows could occupy the pool even
        ALONE (under the live capacity) — False means the run can never
        resume and must fail typed instead of parking forever."""
        return self.blocks_for(rows) <= min(self.capacity(),
                                            self.max_blocks_per_slot)

    # -- alloc/free ----------------------------------------------------------
    def ensure(self, slot: int, rows: int) -> bool:
        """Grow slot's table to cover `rows` rows (clamped to the
        per-slot maximum).  Returns False — nothing allocated — when the
        capacity (after the fault cap) cannot supply the growth.  When
        the free list is short but a prefix cache holds evictable
        refcount-0 blocks, the cache is asked to evict (LRU order) —
        cached-but-unreferenced blocks are reclaimable capacity."""
        rows = min(int(rows), self.pool_len)
        with self._lock:
            table = self._tables.setdefault(slot, [])
            need = min(self.blocks_for(rows),
                       self.max_blocks_per_slot) - len(table)
            if need <= 0:
                return True
            if self._live + need > self.capacity():
                return False
        if need > len(self._free):
            self._reclaim(need - len(self._free))
        with self._lock:
            if need > len(self._free):
                return False
            for _ in range(need):
                b = self._free.pop()
                table.append(b)
                self._refs[b] = 1
                self._live += 1
                self.served_log.append(b)
            self.version += 1
        self._note_gauges()
        return True

    def _reclaim(self, shortfall: int):
        """Ask the prefix cache (if attached) to evict at least
        `shortfall` evictable blocks back to the free list.  Engine loop
        thread only; called outside the lock (the cache calls back into
        `release_cached`)."""
        if self._on_reclaim is not None and shortfall > 0:
            self._on_reclaim(shortfall)

    def alloc(self, slot: int, rows: int) -> bool:
        """Fresh allocation for a slot that must not already hold blocks
        (admission).  Same return contract as ensure."""
        with self._lock:
            if self._tables.get(slot):
                raise InvalidArgumentError(
                    f"slot {slot} already holds "
                    f"{len(self._tables[slot])} blocks")
        return self.ensure(slot, rows)

    # -- prefix-cache sharing ------------------------------------------------
    def adopt(self, slot: int, block_ids: List[int]) -> bool:
        """Map already-resident (cached) blocks into an EMPTY slot's
        table, bumping their refcounts — the warm-prefix admission path.
        Returns False (nothing mapped) when reviving the refcount-0
        blocks among them would exceed the live capacity cap."""
        with self._lock:
            if self._tables.get(slot):
                raise InvalidArgumentError(
                    f"slot {slot} already holds "
                    f"{len(self._tables[slot])} blocks")
            revive = sum(1 for b in block_ids if self._refs.get(b, 0) == 0)
            if self._live + revive > self.capacity():
                return False
            table = self._tables[slot] = []
            for b in block_ids:
                r = self._refs.get(b, 0)
                if r == 0:
                    self._live += 1
                self._refs[b] = r + 1
                table.append(b)
            if table:
                self.version += 1
        if block_ids:
            self._note_gauges()
        return True

    def cow_last(self, slot: int):
        """Copy-on-write divergence: replace the LAST block of the
        slot's table (a shared cached block about to be written) with a
        fresh private block.  Returns (src, dst) block ids — the caller
        must copy the device content src -> dst BEFORE any program
        writes through the table — or None when no block is available.
        Engine loop thread only: src's content stays intact until a
        later allocation re-serves it, so the copy is race-free."""
        with self._lock:
            table = self._tables.get(slot)
            if not table:
                raise InvalidArgumentError(f"slot {slot} holds no blocks")
            src = table[-1]
            short = self.capacity() < self._live + 1
        if short:
            return None
        if not self._free:
            self._reclaim(1)
        with self._lock:
            if not self._free:
                return None
            dst = self._free.pop()
            self._refs[dst] = 1
            self._live += 1
            self.served_log.append(dst)
            unref = []
            r = self._refs.get(src, 1) - 1
            if r > 0:
                self._refs[src] = r
            else:
                self._refs.pop(src, None)
                self._live -= 1
                if src in self._cached:
                    unref.append(src)
                else:
                    self._free.append(src)
            table[-1] = dst
            self.version += 1
        if unref and self._on_cached_unref is not None:
            self._on_cached_unref(unref)
        self._note_gauges()
        return src, dst

    def register_cached(self, block: int):
        """The prefix cache takes ownership of a block: at ref 0 it will
        stay resident (evictable) instead of returning to the free
        list."""
        with self._lock:
            self._cached.add(block)

    def release_cached(self, block_ids: List[int]):
        """The prefix cache evicted these blocks: recycle any that are
        unreferenced back to the free list (LIFO, so the scrub proof
        sees them re-served first)."""
        with self._lock:
            for b in block_ids:
                self._cached.discard(b)
                if self._refs.get(b, 0) == 0:
                    self._free.append(b)
        self._note_gauges()

    def free(self, slot: int) -> int:
        """Drop the slot's reference on every block it holds; returns
        how many table entries were released.  A block whose LAST
        reference drops is recycled to the free list — unless the
        prefix cache owns it, in which case it stays device-resident
        (evictable) and the cache is notified.  Shared blocks other
        slots still reference are never double-freed.  Block CONTENT is
        scrubbed at re-serve time inside the compiled programs (module
        docstring) — free itself is pure bookkeeping."""
        with self._lock:
            table = self._tables.pop(slot, [])
            n = len(table)
            unref = []
            for b in table:
                r = self._refs.get(b, 1) - 1
                if r > 0:
                    self._refs[b] = r
                    continue
                self._refs.pop(b, None)
                self._live -= 1
                if b in self._cached:
                    unref.append(b)
                else:
                    self._free.append(b)
            if n:
                self.version += 1
        if unref and self._on_cached_unref is not None:
            self._on_cached_unref(unref)
        if n:
            self._note_gauges()
        return n

    # -- views ---------------------------------------------------------------
    def rows_capacity(self, slot: int) -> int:
        with self._lock:
            return len(self._tables.get(slot, ())) * self.block_size

    def block_ids(self, slot: int) -> List[int]:
        with self._lock:
            return list(self._tables.get(slot, ()))

    def table_array(self, slot: int) -> np.ndarray:
        """(max_blocks_per_slot,) int32 program input; unallocated tail
        entries hold the `num_blocks` sentinel (reads clip into masked
        rows, writes drop)."""
        out = np.full((self.max_blocks_per_slot,), self.num_blocks,
                      np.int32)
        with self._lock:
            t = self._tables.get(slot, ())
            out[:len(t)] = t
        return out

    def sentinel_table(self) -> np.ndarray:
        """An all-sentinel table: every write through it is dropped —
        what engine warmup uses so precompiling writes nothing."""
        return np.full((self.max_blocks_per_slot,), self.num_blocks,
                       np.int32)

    def stats(self) -> Dict:
        used = self.used_blocks()
        with self._lock:
            shared = sum(1 for r in self._refs.values() if r > 1)
            cached = len(self._cached)
        return {"pool": self.name,
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "capacity": self.capacity(),
                "used_blocks": used,
                "free_blocks": self.free_blocks(),
                "shared_blocks": shared,
                "cached_blocks": cached,
                "max_blocks_per_slot": self.max_blocks_per_slot}

    def _note_gauges(self):
        used_g, free_g = _obs(self.name)
        used_g.set(self.used_blocks())
        free_g.set(self.free_blocks())

    # -- device pool construction -------------------------------------------
    @staticmethod
    def leaf_shapes(model, dtype=None):
        """Per layer, a tuple of (shape past the rows, dtype), one a leaf of
        the model's own fixed-cache protocol: `(k, v)` pairs of 4-D leaves,
        or whatever a layer holds (a latent layer: two 3-D leaves of
        different widths)."""
        template = model.gen_fixed_cache(1, 1, dtype)
        return [tuple((tuple(leaf.shape[2:]), leaf.dtype) for leaf in layer)
                for layer in template]

    def build_pools(self, model, dtype=None, put=None):
        """The device-resident block pool: for each model KV leaf of
        shape (B, T, *rest), one zero pool of shape
        (num_blocks, block_size, *rest).  `put` (optional) places each
        leaf — the mesh engine passes a heads-sharded device_put."""
        pools = []
        for layer in self.leaf_shapes(model, dtype):
            leaves = [jnp.zeros((self.num_blocks, self.block_size) + rest, dt)
                      for rest, dt in layer]
            if put is not None:
                leaves = [put(leaf) for leaf in leaves]
            pools.append(tuple(leaves))
        return pools

    def pool_bytes(self, pools) -> int:
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for layer in pools for leaf in layer))


class FixedKVView:
    """The fixed layout as the engine's programs see it.  A layer's cache
    is a TUPLE OF LEAVES, each `(slots, rows, *rest)` with its own `rows`,
    `rest` and dtype: `(k, v)` of `(…, heads, head_dim)` is one case, a
    latent layer's `(…, 512)` beside `(…, 64)` another, a ring's `(k, v)`
    beside a pair of pooled summaries, a row a chunk, a third.  A leaf
    holds a slot's rows `pool_len` long, or as a window's ring written at
    ``pos % rows``, or on whatever clock its model keeps, or is a state of
    fixed size with no row axis at all, `(slots, *state)` (`write_prompt`
    takes a prompt's leaf as the model hands it back: no longer than the
    pool's and already in the pool's layout, which a state's leaf always
    is, or longer and token-indexed, which is a ring's case).  The pool IS
    the contiguous view the model runs against, so `open` and `publish` are
    the identity; a prompt's rows go to its slot's row.  Nothing here knows
    how many leaves a layer has or their rank."""

    # -- host side: what a call's `inputs` say of the layout -----------------
    def prompt_inputs(self, slot: Optional[int] = None) -> Dict:
        """The slot a prefill writes (None: slot 0, whose warm-up junk dies
        at the slot's next prefill)."""
        return {"slot": jnp.int32(slot or 0)}

    def batch_inputs(self, slots) -> Dict:
        return {}

    # -- trace time ------------------------------------------------------------
    def open(self, pools, inputs):
        return pools

    def publish(self, pools, view, inputs, pos, n_rows):
        return view

    def prompt_cache(self, pools, inputs, scratch):
        """(the caches a prompt's forward runs against, its position)."""
        return scratch(), 0

    def write_prompt(self, pools, kv, inputs):
        slot, prompt_len = inputs["slot"], inputs["prompt_len"]
        new_pools = []
        for layer_pool, layer_kv in zip(pools, kv):
            # each leaf by its OWN length; a layer's leaves of one length
            # (a `(k, v)` pair, a ring's pair beside a summary pair) are
            # written together.  A state's leaf (no row axis) comes back in
            # the pool's own shape a slot and is written whole
            by_rows = {}
            for i, leaf in enumerate(layer_pool):
                by_rows.setdefault(leaf.shape[1], []).append(i)
            new = [None] * len(layer_pool)
            for rows, which in by_rows.items():
                written = self._write_rows(
                    [layer_pool[i] for i in which],
                    [layer_kv[i] for i in which], rows, slot, prompt_len)
                for i, leaf in zip(which, written):
                    new[i] = leaf
            new_pools.append(tuple(new))
        return new_pools

    @staticmethod
    def _write_rows(leaves, kv, rows, slot, prompt_len):
        """Pool leaves `rows` long with a prompt's rows in `slot`."""
        # full-range overwrite: the prompt's rows + zeros to the leaf's
        # own length (pool_len, a window layer's ring, a row a chunk), so
        # a recycled slot keeps no stale KV from its previous tenant
        if kv[0].shape[1] > rows:
            # a bucket longer than the ring leaves the prompt's
            # last `rows` positions in it: row r holds the one
            # position p in [plen - rows, plen) with p % rows == r
            first = prompt_len - rows
            p = first + (jnp.arange(rows) - first) % rows
            held = {}       # which rows hold a position, by rank
            for c in kv:
                if c.ndim not in held:
                    held[c.ndim] = (p >= 0)[
                        (None, slice(None)) + (None,) * (c.ndim - 2)]
            at = jnp.maximum(p, 0)
            row = [jnp.where(held[c.ndim], jnp.take(c, at, axis=1),
                             0).astype(leaf.dtype)
                   for leaf, c in zip(leaves, kv)]
        else:
            row = [jnp.zeros((1, rows) + leaf.shape[2:], leaf.dtype)
                   for leaf in leaves]
            row = [jax.lax.dynamic_update_slice(
                r, c.astype(r.dtype), (0,) * r.ndim)
                for r, c in zip(row, kv)]
        return [jax.lax.dynamic_update_slice(
            leaf, r, (slot,) + (0,) * (leaf.ndim - 1))
            for leaf, r in zip(leaves, row)]


class PagedKVView:
    """The paged layout as the engine's programs see it: one block pool
    per leaf and a block table per slot.  `open` gathers every slot's
    table into its contiguous view ONCE per call — value-identical to the
    fixed slot row, to the block boundary, so streams stay bit-identical
    and attention pays nothing extra — and `publish` scatters the rows
    the call wrote back through the tables in one pass, zeroing every
    block a slot ENTERS first (scrub-on-recycle, module docstring).  One
    gather and one scatter per call amortize the indirection over the
    call's rows.  A draft's pool pages through the SAME tables."""

    def __init__(self, pool: PagedKVPool, max_slots: int):
        self.pool = pool
        self.max_slots = int(max_slots)
        self._batch = None   # (allocator version, slots) -> its inputs

    # -- host side -------------------------------------------------------------
    def prompt_inputs(self, slot: Optional[int] = None) -> Dict:
        """The slot's block table (None: the all-sentinel table, through
        which warm-up writes nothing)."""
        return {"table": jnp.asarray(
            self.pool.sentinel_table() if slot is None
            else self.pool.table_array(slot))}

    def batch_inputs(self, slots) -> Dict:
        """Per-slot block tables (sentinel everywhere a slot is
        unoccupied, so its writes drop) + the occupancy mask.  Cached
        against the allocator's mutation version — tables only change
        when a slot crosses a block boundary or membership churns, so
        steady-state ticks re-upload nothing."""
        key = (self.pool.version, tuple(slots))
        if self._batch is None or self._batch[0] != key:
            tables = np.tile(self.pool.sentinel_table(),
                             (self.max_slots, 1))
            active = np.zeros((self.max_slots,), bool)
            for slot in slots:
                tables[slot] = self.pool.table_array(slot)
                active[slot] = True
            self._batch = (key, {"tables": jnp.asarray(tables),
                                 "active": jnp.asarray(active)})
        return self._batch[1]

    # -- trace time ------------------------------------------------------------
    def open(self, pools, inputs):
        """Batched `ops.paged_attention.gather_block_rows` (ONE
        implementation site for the clip/sentinel contract): (S, nb_max)
        tables over (num_blocks, block_size, ...) pools -> every slot's
        contiguous (T, ...) view."""
        from ..ops.paged_attention import gather_block_rows
        gather = jax.vmap(gather_block_rows, in_axes=(None, 0))
        tables = inputs["tables"]
        return [(gather(kp, tables), gather(vp, tables)) for kp, vp in pools]

    def publish(self, pools, view, inputs, pos, n_rows):
        """Scatter rows pos..pos+n_rows-1 of every slot's view back (near
        the view's end: `_window`)."""
        start = self._window(pos, n_rows, view)
        cut = jax.vmap(
            lambda c, p: jax.lax.dynamic_slice_in_dim(c, p, n_rows))
        return self._rows_back(
            pools, [(cut(kc, start), cut(vc, start)) for kc, vc in view],
            inputs["tables"], start, inputs["active"])

    def prompt_cache(self, pools, inputs, scratch):
        """A cold prompt runs against a bucket-sized scratch cache at 0.
        With a prefix cache (`cached_len` among the inputs) it runs at
        `cached_len` against the slot's own gathered view, whose rows
        [0, cached_len) the adopted blocks already hold: only the
        uncached SUFFIX is computed, and cached_len=0 IS the cold path."""
        if "cached_len" not in inputs:
            return scratch(), 0
        from ..ops.paged_attention import gather_block_rows
        table = inputs["table"]
        return ([(gather_block_rows(kp, table)[None],
                  gather_block_rows(vp, table)[None]) for kp, vp in pools],
                inputs["cached_len"])

    def write_prompt(self, pools, kv, inputs):
        bucket = inputs["ids"].shape[1]
        table = inputs["table"]
        if "cached_len" in inputs:
            # the suffix rows alone go back.  They start at cached_len — a
            # block boundary for non-COW admissions, so shared blocks are
            # never entered; any block the write scrubs lies entirely
            # inside the window (fully rewritten), so shared content is
            # preserved bit-exactly
            start = self._window(inputs["cached_len"], bucket, kv)
            rows = [(jax.lax.dynamic_slice_in_dim(kc[0], start, bucket)[None],
                     jax.lax.dynamic_slice_in_dim(vc[0], start, bucket)[None])
                    for kc, vc in kv]
            return self._rows_back(pools, rows, table[None], start[None],
                                   jnp.ones((1,), bool))
        # every block the table covers for the bucket is overwritten
        # END-TO-END (prompt KV + zeros to the block boundary): scrub-on-
        # recycle for prompt blocks is the overwrite itself.  Sentinel
        # entries (warm-up) drop the write.
        bs = self.pool.block_size
        nb = -(-bucket // bs)
        ids = table[:nb]

        def as_blocks(chunk, pool):
            rows = chunk[0].astype(pool.dtype)               # (bucket, ...)
            if nb * bs > bucket:
                rows = jnp.concatenate(
                    [rows, jnp.zeros((nb * bs - bucket,) + rows.shape[1:],
                                     pool.dtype)])
            return rows.reshape((nb, bs) + rows.shape[1:])

        return [(kp.at[ids].set(as_blocks(kc, kp), mode="drop"),
                 vp.at[ids].set(as_blocks(vc, vp), mode="drop"))
                for (kp, vp), (kc, vc) in zip(pools, kv)]

    @staticmethod
    def _window(pos, n_rows, view):
        """Where the `n_rows` rows a call wrote from `pos` on are cut out
        of a (*, T, ...) view: `pos` clamped so the window never runs off
        the view's end.  A clamped window re-writes up to (pos - start)
        rows BELOW pos with the values the gather read for them —
        idempotent by construction — instead of paying a permanently
        longer view just to keep dynamic_slice from clamping."""
        return jnp.maximum(0, jnp.minimum(pos, view[0][0].shape[1] - n_rows))

    def _rows_back(self, pools, rows, tables, start, active):
        """Per slot, the (S, R, ...) rows of positions start..start+R-1
        through the tables into the block pools.  Every block a slot
        ENTERS (write offset 0) is zeroed before the rows land; inactive
        slots and rows past pool_len route through the sentinel id and
        are dropped."""
        from ..ops.paged_attention import scatter_block_rows, scrub_blocks
        bs, sentinel = self.pool.block_size, self.pool.num_blocks
        n_rows = rows[0][0].shape[1]
        pvals = start[:, None] + jnp.arange(n_rows)[None, :]     # (S, R)
        bidx = jnp.clip(pvals // bs, 0, tables.shape[1] - 1)
        blk = jnp.take_along_axis(tables, bidx, axis=1)
        off = (pvals % bs).reshape(-1)
        ok = active[:, None] & (pvals < self.pool.pool_len)
        blk_w = jnp.where(ok, blk, sentinel).reshape(-1)
        # a block's first row IS the entering position, so every already
        # committed row of the entering slot lives in earlier blocks —
        # zeroing here can only erase recycled/stale speculative rows
        scrub = jnp.where(ok & (pvals % bs == 0), blk, sentinel).reshape(-1)
        new_pools = []
        for (kp, vp), (kr, vr) in zip(pools, rows):
            kr = kr.reshape((-1,) + kr.shape[2:])                # (S*R, ...)
            vr = vr.reshape((-1,) + vr.shape[2:])
            kp = scrub_blocks(kp, scrub)
            vp = scrub_blocks(vp, scrub)
            new_pools.append((scatter_block_rows(kp, blk_w, off, kr),
                              scatter_block_rows(vp, blk_w, off, vr)))
        return new_pools
