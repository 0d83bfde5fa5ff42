"""Flash attention (forward + backward) for TPU via pallas.

Replaces the reference's fused attention CUDA kernels
(paddle/fluid/operators/fused/multihead_matmul_op.cu, fused_attention) with an
online-softmax blocked kernel pair that never materializes the (seq, seq)
score matrix in HBM — the key to long-context MFU on TPU.

Design notes (see /opt/skills/guides/pallas_guide.md):
- q/k/v stay in their input dtype (bf16 under AMP) going into the MXU dots
  with `preferred_element_type=f32` accumulation; only the softmax state is
  kept in f32.
- **Natural layout**: the kernels read (B, S, H*D) blocks straight out of the
  model's (batch, seq, heads, head_dim) tensors — no (B,S,H,D)->(B*H,S,D)
  transpose through HBM on either side.  A grid step owns a GROUP of G heads
  (G*D lanes, 128 <= G*D <= 512) and loops over them in-register: per-head
  (s, d) matmuls at d=64 run at MXU row-rate, so amortizing every load/store
  across a head group is worth ~1.8x over a head-per-step grid (measured on
  v5e at BERT-large shapes).
- **Scores are computed transposed**, keys down the sublanes and queries
  along the lanes: the softmax's running max and sum are then (1, blk_q)
  rows, lane-dense, the reductions over keys are plain elementwise ops
  between vregs, and the output accumulator (d, blk_q) is rescaled at full
  width.  (With queries down the sublanes the state is a (blk_q, 1) column
  that costs half a score block's vector work per operation.)
- **One q block a grid step, the keys resident**: K and V of a head group
  stay in VMEM (their block index does not move with the q block, so they
  are loaded once per (batch, group)) and the kernel itself walks the k
  sub-blocks.  Under the causal mask the walk ends at the diagonal: blocks
  above it are neither loaded nor computed, and only the blocks the
  diagonal crosses pay for the mask (iota, compare, select).  Up to
  `_WRITTEN_OUT` rows the walk is written out, one branch a q block with
  static bounds, the whole blocks of a row taken as ONE tall block and the
  heads side by side, so that no chain of rescales and no loop boundary
  stands between a block's products and its neighbour's softmax; longer
  sequences are walked by `fori_loop`s.  A sequence of one block is the
  same code with nothing to walk and nothing to rescale.
- **Grouped KV heads and a window, forward only** (`flash_attention_grouped`:
  a prompt of a served model, causal).  With `r` query heads to a KV head a
  grid step owns `hg` query heads (`hg` divides `r`) that share ONE KV head:
  K's and V's block is that head's `d` lanes where it lies in the (B, S,
  Hkv*D) tensor, found from the step's index, and the `r / hg` steps of a KV
  head follow each other, so its K and V stay resident and nothing is
  repeated through HBM.  A `window` gives the walk a lower bound as the
  diagonal gives it the upper: key blocks wholly older than the window of
  the q block's first row are neither loaded into the products nor
  computed, the blocks the window's far edge crosses pay a second compare,
  the blocks between go unmasked.  A window that holds the whole sequence is
  the causal form, decided at trace time.  With `r` = 1 and no window every
  one of these branches is decided at trace time and the kernel is the
  training calls' kernel, equation for equation.  No backward is built for
  either form: a gradient raises.
- The backward is the FlashAttention-2 recompute scheme: the forward saves
  only O and the per-row logsumexp; one merged backward kernel owns a k
  block per grid step, holds Q, dO, lse and delta of the head group
  resident, walks the q sub-blocks from the diagonal down, and produces dK,
  dV and dQ in a single pass.  dQ accumulates in VMEM across the k blocks
  and leaves once, in the input dtype.  Its time goes with the area it
  computes, so under the causal mask it cuts the scores finer (256) than
  the forward, which at 256 query lanes takes 1.7 times as long a tile as
  at 512.
- Dropout is applied *inside* the kernel from the TPU hardware PRNG re-seeded
  per (head, tile of the scores), the tile being the backward's block, so
  the keep mask is bit-identical between forward and backward whatever
  blocks and order each walks in.  Under `interpret=True` (CPU CI) a
  murmur-style hash of absolute coordinates replaces the PRNG.
- Masking: `causal`, an additive per-key bias (B, Sk) covering padding masks,
  and q/kv segment ids (packed-sequence masking) are fused into the kernel.

What the chip reads (v5e, PERF.md section 6, PR 29) at GPT-2-medium's call,
(b4, s1024, 16 heads of 64) causal in bfloat16: forward 0.146 ms, backward
0.286 ms a call (0.407 and 0.384 before), 30% of the roofline the benchmark
counts (half of the full product's operations at 197 TFLOP/s).  A 128 x 128
tile of scores costs the forward 0.043-0.047 us and the backward 0.107-0.111
us, twice what its products take at peak (0.021 and 0.053): with d = 64 every
product has a 64-wide dimension and fills half of the 128-wide array, so the
bound that applies is the MXU at half rate, not the vector unit and not HBM.
What is left above it is area: the forward computes 3/4 and the backward 5/8
of the full product where the mask keeps 1/2 plus the diagonal.

At command-a-plus's prefill, (b1, s8192, 128 query heads on 8 KV heads of
128) in bfloat16 (PERF.md section 6, PR 33): a full layer 20.2 ms, a layer
with a window of 4096 16.6 ms (the XLA form in query blocks and key chunks
75 and 56), 55% of the 11.2 ms its causal products take at 197 TFLOP/s:
0.072 us a 128 x 128 tile of scores where the two products need 0.043, the
heads of 128 filling the array.  Blocks of 512 (256: 39.0 ms; 1024: 19.1),
2 query heads a step (1: 20.6; 4: 19.9).

`flash_attention_bshd` and `flash_attention_grouped` return None when the
kernel doesn't apply (wrong platform/shape, or a sequence too long for its
resident blocks); callers fall back to an XLA form.  The first refuses
grouped heads (the caller expands them, or takes the XLA form): it is the
trainable entry, and the backward knows one KV head a query head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import jaxpr_as_fun

from ..observability.metrics import counter

_INTERPRET = False  # tests flip this to run the kernels via the interpreter

_NEG_INF = -1e30

# what a kernel may hold in VMEM: the compiler's default scope is 16 MiB of
# the v5e's 128; a kernel whose resident blocks need more asks for it, and
# a sequence too long for that takes the XLA form
_VMEM_DEFAULT = 12 << 20
_VMEM_MOST = 96 << 20

# which form each traced kernel call took, chosen from the shapes at trace
# time like `attention_path_total`: `one_block` (the keys are one block: no
# loop, no rescale), `blocks` (a loop over all k sub-blocks), `causal_blocks`
# (the loop ends at the diagonal and only its blocks are masked); from
# `flash_attention_grouped`: `grouped` (query heads share a KV head, causal),
# `grouped_window` (and the walk starts at a window's far edge inside the
# sequence), `window_blocks` (a window, one KV head a query head)
_FORM_TAKEN = counter(
    "flash_attention_form_total",
    "flash kernel calls traced, by the form the shapes chose", ("form",))

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _available() -> bool:
    return _INTERPRET or jax.default_backend() == "tpu"


def _env_int(name):
    import os
    v = os.environ.get(name)
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        return None  # tuning knob: garbage falls back to the heuristic


# the longest sequence whose walk over the blocks is written out (its bounds
# static, the kernel one branch a grid block); longer ones are walked by
# `fori_loop`s whose bounds the kernel computes from its grid index
_WRITTEN_OUT = 1024


def _block(size: int, fine: bool = False) -> int:
    """Rows of a q or k sub-block: 512 where it divides, else 256 or 128
    (the forward read 0.080 us a 128 x 128 tile at 256 query lanes against
    0.047 at 512, the backward 0.11 at both).  `fine`: the causal backward,
    whose time goes with the area computed, is cut at 256 where its walk is
    written out: 10 of 16 blocks at S = 1024 where 512 gives 3 of 4."""
    cands = (256, 128) if fine and size <= _WRITTEN_OUT else (512, 256, 128)
    return next(b for b in cands if size % b == 0)


def _form(causal: bool, sk: int) -> str:
    if sk == _block(sk):
        return "one_block"
    return "causal_blocks" if causal else "blocks"


def _head_group(h: int, d: int):
    """Heads per grid step: largest divisor of h with 128 <= g*d <= 512,
    preferring g*d == 256 (the measured sweet spot on v5e).  Falls back to
    folding ALL heads into one group — a block whose lane dim equals the
    array's full last dim is exempt from the 128-divisibility rule."""
    # lane width g*d must be a multiple of 128 (or the array's full last
    # dim h*d, the one exemption Mosaic grants) — h=6,d=64 must pick g=2
    # (128 lanes), not g=3 (192 lanes, unlowerable)
    override = _env_int("PDTPU_FLASH_GROUP")
    if override and h % override == 0 and (override * d) % 128 == 0 \
            and 128 <= override * d <= 1024:
        # the override must still satisfy Mosaic's 128-lane constraint —
        # an unlowerable g would fail with an opaque kernel error
        return override
    cands = [g for g in range(1, h + 1)
             if h % g == 0 and 128 <= g * d <= 512 and (g * d) % 128 == 0]
    if not cands:
        return h  # full fold: block last dim == array last dim is allowed
    return min(cands, key=lambda g: (abs(g * d - 256), -g))


def _resident_bytes(sq, sk, gd, itemsize):
    """VMEM the larger of the two kernels holds for one head group: the
    backward's Q, dO and dQ of the whole sequence (double-buffered) and its
    float32 dQ accumulator; the forward's K and V."""
    bwd = sq * gd * (3 * 2 * itemsize + 4)
    fwd = sk * gd * 2 * 2 * itemsize
    return max(bwd, fwd)


def _forward_bytes(sq, sk, d, hg, itemsize):
    """VMEM of the forward kernel alone over ONE shared KV head: K and V of
    the whole sequence and the step's q and o blocks (double-buffered), and
    for each of its `hg` heads a step's float32 scores and probabilities
    (a written-out walk takes the whole blocks as one) and accumulator."""
    blk_q, blk_k = _block(sq), _block(sk)
    rows_k = sk if max(sq, sk) <= _WRITTEN_OUT else blk_k
    return (2 * 2 * sk * d * itemsize + 2 * 2 * blk_q * hg * d * itemsize
            + hg * blk_q * 4 * (2 * rows_k + d))


def _compiler_params(semantics, resident):
    limit = None if resident <= _VMEM_DEFAULT else resident + (16 << 20)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def flash_attention_bshd(q, k, v, causal=False, bias=None, q_segment_ids=None,
                         kv_segment_ids=None, dropout_p=0.0, dropout_seed=None):
    """q/k/v: (batch, seq, heads, head_dim). Returns same layout, or None.

    bias: additive f32 per-key bias (batch, seq_k) — the padding-mask case.
    q_segment_ids / kv_segment_ids: int32 (batch, seq) packed-sequence ids;
    positions attend only within equal ids.
    dropout_p with dropout_seed (int32 array shape (1,)): in-kernel attention
    probability dropout.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if not _available():
        return None
    if d not in (64, 128, 256):
        return None
    if sq % 128 != 0 or sk % 128 != 0:
        return None
    if k.shape[2] != h:  # grouped-query: caller expands kv heads first
        return None
    if dropout_p > 0.0 and dropout_seed is None:
        return None
    if (q_segment_ids is None) != (kv_segment_ids is None):
        return None
    if _resident_bytes(sq, sk, _head_group(h, d) * d,
                       q.dtype.itemsize) > _VMEM_MOST:
        return None
    if dropout_seed is None:
        dropout_seed = jnp.zeros((1,), jnp.int32)
    _FORM_TAKEN.labels(form=_form(bool(causal), sk)).inc()
    local = functools.partial(_flash_bshd, causal=bool(causal),
                              dropout_p=float(dropout_p))
    args = (q, k, v, bias, q_segment_ids, kv_segment_ids, dropout_seed)
    free = _free_mesh_axes()
    if all(n == 1 for n in free.values()):
        return local(*args)
    return _per_shard(local, free, *args)


def _free_mesh_axes():
    """{axis: size} of jax's own mesh context (`jax.set_mesh`) that are not
    yet manual: the ones GSPMD would partition this program over."""
    mesh = jax.sharding.get_abstract_mesh()
    return {a: mesh.shape[a] for a in mesh.axis_names
            if a not in mesh.manual_axes}


def flash_attention_grouped(q, k, v, window=None):
    """Causal self-attention of a prompt, FORWARD ONLY: q (batch, seq, Hq,
    head_dim) against k, v (batch, seq, Hkv, head_dim), Hq a multiple of
    Hkv, each KV head read where it lies for the query heads that share it.
    `window` (static): a query sees its own position and the `window - 1`
    before it; None, or a window that holds the whole sequence, is the
    causal form.  Returns q's layout, or None where the kernel does not
    apply (not a TPU, a length that is no multiple of 128, grouped heads
    narrower than 128 lanes, a multi-device program).  A gradient asked of
    it raises: the backward kernel knows neither form."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if not _available() or d not in (64, 128, 256):
        return None
    if sq % 128 != 0 or sk % 128 != 0 or h % hkv != 0:
        return None
    r = h // hkv
    if r > 1 and d % 128 != 0:    # a KV head's block is d lanes wide
        return None
    if any(n > 1 for n in _free_mesh_axes().values()):
        return None
    hg = _head_group(r, d) if r > 1 else _head_group(h, d)
    if window is not None and window >= sk:
        window = None
    most = (_forward_bytes(sq, sk, d, hg, q.dtype.itemsize) if r > 1
            else _resident_bytes(sq, sk, hg * d, q.dtype.itemsize))
    if most > _VMEM_MOST:
        return None
    if r > 1:
        form = "grouped" if window is None else "grouped_window"
    else:
        form = _form(True, sk) if window is None else "window_blocks"
    _FORM_TAKEN.labels(form=form).inc()
    out = _flash_forward_only(
        q.reshape(b, sq, h * d), k.reshape(b, sk, hkv * d),
        v.reshape(b, sk, hkv * d), h, hg, hkv, window)
    return out.reshape(b, sq, h, d)


def _flash_bshd(q, k, v, bias, q_segment_ids, kv_segment_ids, dropout_seed,
                causal, dropout_p):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    g = _head_group(h, d)
    # natural layout: (B, S, H, D) -> (B, S, H*D) is a free reshape
    qt = q.reshape(b, sq, h * d)
    kt = k.reshape(b, sk, h * d)
    vt = v.reshape(b, sk, h * d)
    # the mask inputs as the transposed scores want them: per-key vectors
    # ride the sublane axis as (B, Sk, 1), per-query ids the lane axis as
    # (B, 1, Sq)
    if bias is not None:
        bias = bias.astype(jnp.float32)[:, :, None]
    if q_segment_ids is not None:
        q_segment_ids = q_segment_ids.astype(jnp.int32)[:, None, :]
    if kv_segment_ids is not None:
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)[:, :, None]
    out = _flash(qt, kt, vt, bias, q_segment_ids, kv_segment_ids,
                 dropout_seed, causal, dropout_p, h, g)
    return out.reshape(b, sq, h, d)


def _per_shard(local, free, q, k, v, bias, qseg, kseg, seed):
    """The kernel inside a GSPMD program over the mesh axes `free` (name ->
    size): Mosaic kernels cannot be partitioned automatically, so each
    device runs the kernel on its own batch rows ("dp").  Over any other
    axis, and where the rows do not divide, every device sees the whole
    operands."""
    from jax.sharding import PartitionSpec as P
    dp = free.get("dp", 1)
    rows = "dp" if dp > 1 and q.shape[0] % dp == 0 else None
    qkv, per_row = P(rows, None, None, None), P(rows, None)

    def body(q, k, v, bias, qseg, kseg, seed):
        if rows is not None:
            # a shard must not repeat its neighbour's dropout mask
            seed = seed + jax.lax.axis_index(rows) * jnp.int32(7919)
        return local(q, k, v, bias, qseg, kseg, seed)

    return jax.shard_map(
        body, in_specs=(qkv, qkv, qkv, per_row, per_row, per_row, P()),
        out_specs=qkv, axis_names=frozenset(free),
        check_vma=False)(q, k, v, bias, qseg, kseg, seed)


# ---------------------------------------------------------------------------
# in-kernel dropout.
#
# On TPU: the hardware PRNG, re-seeded per (seed, bh) and tile of the scores
# (the backward's block), so the keep mask is identical wherever and in
# whatever blocks the scores are recomputed (the forward walks the k blocks
# of a q block, the backward the q blocks of a k block, in smaller blocks).
# Under interpret=True (CPU CI): a murmur3-style hash of absolute
# coordinates — the TPU PRNG primitives don't run in the interpreter.


# The compiled kernel draws its bits from the hardware PRNG: a primitive
# that does not lower on the chip is an error, never a switch to the hash.
# The chip-compile test sets this False to hold the hash form to the same
# compiler.
_HW_PRNG = True


def _keep_mask(seed_ref, bh, q0, k0, blk_q, blk_k, dropout_p, tile):
    """(blk_k, blk_q) keep mask of head bh's scores from key k0 and query
    q0 on, drawn in tiles of `tile` = (queries, keys)."""
    thresh = min(int(dropout_p * 4294967296.0), 4294967295)
    if _HW_PRNG and not _INTERPRET:
        # drawn tile by tile, each seeded by its own position: forward and
        # backward cut the scores into different blocks and must see one
        # mask, so the tile is the smaller, the backward's, block.
        # Hardware seeding takes at most 2 words: pack (seed, bh) and the
        # tile's (q, k) — tile coords are far below 2^15 so the pair is
        # unique.  -1640531615 == 0x9E3779B1 as int32
        tq, tk = tile

        def draw(i, j):
            pltpu.prng_seed(seed_ref[0] + bh * jnp.int32(-1640531615),
                            (q0 // tq + j) * jnp.int32(0x10001) + k0 // tk + i)
            return pltpu.prng_random_bits((tk, tq))
        bits = jnp.concatenate(
            [jnp.concatenate([draw(i, j) for j in range(blk_q // tq)], axis=1)
             for i in range(blk_k // tk)], axis=0)
        return bits.astype(jnp.uint32) >= jnp.uint32(thresh)
    kpos, qpos = _coords(q0, k0, blk_q, blk_k)
    x = (qpos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ kpos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    x = x ^ (seed_ref[0].astype(jnp.uint32)
             + bh.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x >= jnp.uint32(thresh)


def _coords(q0, k0, blk_q, blk_k):
    """Key and query positions of a transposed (blk_k, blk_q) block."""
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (blk_k, blk_q), 0)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (blk_k, blk_q), 1)
    return kpos, qpos


def _scores(k_hd, q_hd, bias, qseg, kseg, q0, k0, scale, diagonal,
            causal_off, edge=None):
    """One transposed (blk_k, blk_q) score block of one head (f32) with its
    masks.  bias, kseg: (blk_k, 1) or None; qseg: (1, blk_q) or None;
    `diagonal`: the causal diagonal may cross this block; `edge`: a window
    of that many keys, whose far edge may cross it."""
    s = jax.lax.dot_general(k_hd, q_hd, _NT,
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if diagonal or edge is not None:
        kpos, qpos = _coords(q0, k0, q_hd.shape[0], k_hd.shape[0])
    if diagonal:
        s = jnp.where(qpos + causal_off >= kpos, s, _NEG_INF)
    if edge is not None:
        s = jnp.where(qpos + causal_off - kpos < edge, s, _NEG_INF)
    if qseg is not None:
        s = jnp.where(kseg == qseg, s, _NEG_INF)
    return s


def _rows(i, blk, n=1):
    """Rows of sub-blocks [i, i+n) of a resident block; i a loop index (then
    n is 1) or an int."""
    if isinstance(i, int):
        return slice(i * blk, (i + n) * blk)
    return pl.ds(pl.multiple_of(i * blk, blk), blk)


def _clip(x, lo, hi):
    if all(isinstance(t, int) for t in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _diagonal_span(first, reach, blk, n):
    """Blocks of size `blk` (n of them) against a causal edge: those below
    index `lo` lie wholly on the kept side, [lo, hi) are crossed by the
    diagonal, those from `hi` on are wholly masked.  `first` is the last
    position the edge keeps for the nearest row, `reach` for the farthest."""
    hi = _clip(reach // blk + 1, 1, n)
    lo = _clip((first + 1) // blk, 0, hi)
    return lo, hi


def _window_span(first, reach, window, blk, n_whole):
    """The `n_whole` blocks under the diagonal against a window's far edge,
    the counterpart of `_diagonal_span`: those below index `lo` are wholly
    older than the window of the nearest row (`first` its position among
    the keys), [lo, hi) are crossed by the edge, those from `hi` on lie
    inside every row's window (`reach`: the farthest row's position)."""
    lo = _clip((first - window + 1) // blk, 0, n_whole)
    hi = _clip((reach - window + blk) // blk, lo, n_whole)
    return lo, hi


def _loop(lo, hi, body, carry, written_out):
    """`fori_loop`, or written out (the bounds are static then): the blocks
    of a short sequence lie in one stretch of straight-line code, and the
    scheduler starts a block's products under its neighbour's softmax where
    a loop's iterations would run one after the other."""
    if written_out:
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _per_block(index, n, written_out, fn):
    """fn(i) for the grid step's block `index` of `n`: where the walk is
    written out, one branch a block with i an int, so that the bounds of the
    causal walk, which depend on i, are static too."""
    if written_out:
        for i in range(n):
            pl.when(index == i)(functools.partial(fn, i))
    else:
        fn(index)


def _traced_once(n_arrays):
    """`impl(*arrays, *static)`, bound from a jaxpr traced once a signature.
    A model calls the kernels once a layer, all with one signature, and a
    step is traced more than once; pallas traces a kernel's body anew at
    every call, and the written-out walk is some fifty blocks of it a
    layer: 24 layers took the step's set-up from 14 s to 57 (chip's host,
    PR 29).  The equations are bound again where the call stands, so names
    and transformations see what they saw."""
    def wrap(impl):
        @functools.lru_cache(maxsize=32)
        def jaxpr_of(avals, static, flags):
            args = [a and jax.ShapeDtypeStruct(*a) for a in avals]
            return jax.make_jaxpr(lambda *xs: impl(*xs, *static),
                                  return_shape=True)(*args)

        @functools.wraps(impl)
        def bound(*args):
            arrays, static = args[:n_arrays], args[n_arrays:]
            if not jax.sharding.get_abstract_mesh().empty:
                return impl(*args)   # avals carry the mesh: trace in place
            closed, out = jaxpr_of(
                tuple(x if x is None else (x.shape, x.dtype) for x in arrays),
                static, (_INTERPRET, _HW_PRNG, _WRITTEN_OUT))
            flat = jaxpr_as_fun(closed)(*(x for x in arrays if x is not None))
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(out), flat)
        return bound
    return wrap


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(seed_ref, *refs, has_bias, has_seg, causal, dropout_p,
                drop_tile, written_out, blk_q, blk_k, n_q, n_k, scale,
                causal_off, heads, hg, shared_kv=False, window=None):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    o_ref, lse_ref = next(it), next(it)

    b, g = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1] // hg
    qseg = qseg_ref[0, 0] if has_seg else None

    def block(h, qi, ki, n, carry, diagonal, edge=None):
        """Online-softmax step of head h over the k sub-blocks [ki, ki+n);
        `carry` None: the first step, nothing to rescale."""
        sl = slice(h * d, (h + 1) * d)
        # grouped heads: the step's heads share the one KV head it was given
        kv = slice(0, d) if shared_kv else sl
        ks = _rows(ki, blk_k, n)
        s = _scores(k_ref[0, ks, kv], q_ref[0, :, sl],
                    bias_ref[0, ks, :] if has_bias else None,
                    qseg, kseg_ref[0, ks, :] if has_seg else None,
                    qi * blk_q, ki * blk_k, scale, diagonal, causal_off,
                    edge)
        m = jnp.max(s, axis=0, keepdims=True)          # (1, blk_q)
        if carry is not None:
            m_prev, l_prev, acc = carry
            m = jnp.maximum(m_prev, m)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=0, keepdims=True)
        if dropout_p > 0.0:
            bh = b * jnp.int32(heads) + g * jnp.int32(hg) + jnp.int32(h)
            keep = _keep_mask(seed_ref, bh, qi * blk_q, ki * blk_k, blk_q,
                              n * blk_k, dropout_p, drop_tile)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        pv = jax.lax.dot_general(                       # (d, blk_q)
            v_ref[0, ks, kv], p.astype(v_ref.dtype), _TN,
            preferred_element_type=jnp.float32)
        if carry is None:
            return m, l, pv
        alpha = jnp.exp(m_prev - m)
        return m, alpha * l_prev + l, alpha * acc + pv

    def q_block(qi):
        """q block qi (static or the grid's index) against its k blocks:
        those a window's far edge crosses (the older ones are skipped), the
        whole ones, then those the diagonal crosses.  The heads go side by
        side: their chains (product, softmax, product) are independent, so
        one's latency hides behind the others' work."""
        if causal:
            # the keys the block's first and last row see last
            first = qi * blk_q + causal_off
            reach = qi * blk_q + blk_q - 1 + causal_off
            n_whole, n_seen = _diagonal_span(first, reach, blk_k, n_k)
        else:
            n_whole = n_seen = n_k

        def blocks(ki, n, carry, diagonal, edge=None):
            return tuple(block(h, qi, ki, n, c, diagonal, edge)
                         for h, c in enumerate(carry))
        if written_out:
            carry = (None,) * hg
        else:
            carry = ((jnp.full((1, blk_q), _NEG_INF, jnp.float32),
                      jnp.zeros((1, blk_q), jnp.float32),
                      jnp.zeros((d, blk_q), jnp.float32)),) * hg
        start = 0
        if window is not None:
            # a row of an edge block may keep none of its keys: what that
            # leaves under a maximum of -1e30 the first real maximum wipes
            # (every row keeps its own position, in a later block)
            lo, start = _window_span(first, reach, window, blk_k, n_whole)
            carry = _loop(lo, start,
                          lambda ki, c: blocks(ki, 1, c, False, window),
                          carry, written_out)
        if written_out:
            # the whole blocks as ONE tall block: no chain of rescales
            if n_whole - start:
                carry = blocks(start, n_whole - start, carry, False)
        else:
            carry = jax.lax.fori_loop(
                start, n_whole, lambda ki, c: blocks(ki, 1, c, False), carry)
        # a window narrower than two blocks can cross a diagonal block too
        both = window if window is not None and window < blk_q + blk_k \
            else None
        carry = _loop(n_whole, n_seen,
                      lambda ki, c: blocks(ki, 1, c, True, both),
                      carry, written_out)
        outs, lses = [], []
        for m, l, acc in carry:
            l = jnp.maximum(l, 1e-30)
            outs.append(acc / l)
            lses.append(m + jnp.log(l))
        # (hg*d, blk_q) -> (blk_q, hg*d): one transpose a q block
        o_ref[0] = jnp.concatenate(outs, axis=0).T.astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.concatenate(lses, axis=0)

    if causal:
        _per_block(pl.program_id(2), n_q, written_out, q_block)
    else:
        q_block(pl.program_id(2))


def _mask_inputs(bias, qseg, kseg, blk_q, blk_k, q_index, k_index):
    """Inputs and BlockSpecs of the optional [bias, qseg, kseg].  Per-key
    inputs are (B, Sk, 1) in blocks of `blk_k` rows at `k_index(i)`, i the
    grid's last index; the q ids (B, 1, Sq) go in as (B, n_q, 1, blk_q),
    block `q_index(i)` of them, or all of them resident (`q_index` None)."""
    inputs, specs = [], []

    def per_key(x):
        inputs.append(x)
        specs.append(pl.BlockSpec(
            (1, blk_k, 1), lambda b, g, i, s: (b, k_index(i), 0)))

    if bias is not None:
        per_key(bias)
    if qseg is not None:
        n_q = qseg.shape[-1] // blk_q
        inputs.append(qseg.reshape(-1, n_q, 1, blk_q))
        if q_index is None:
            specs.append(pl.BlockSpec((1, n_q, 1, blk_q),
                                      lambda b, g, i, s: (b, 0, 0, 0)))
        else:
            specs.append(pl.BlockSpec(
                (1, 1, 1, blk_q), lambda b, g, i, s: (b, q_index(i), 0, 0)))
        per_key(kseg)
    return inputs, specs


@_traced_once(7)
def _fwd_impl(q, k, v, bias, qseg, kseg, seed, causal, dropout_p, heads, hg,
              kv_heads=None, window=None):
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // heads
    gd = hg * d
    n_hg = heads // hg
    blk_q, blk_k = _block(sq), _block(sk)
    n_q, n_k = sq // blk_q, sk // blk_k
    written_out = max(sq, sk) <= _WRITTEN_OUT

    # grid (b, head group, q block); K and V whole: their block does not
    # move with the q block, so they are fetched once per (b, head group).
    # Grouped heads (`kv_heads` < heads, hg dividing their ratio): the block
    # is the ONE KV head the step's query heads share, d lanes where it lies,
    # and the steps of one KV head follow each other, so it stays resident
    # for all of them and nothing is repeated through HBM
    shared_kv = kv_heads not in (None, heads)
    if shared_kv:
        steps = heads // kv_heads // hg      # grid steps a KV head
        kv_lanes, kv_at = d, lambda g: jax.lax.div(g, steps)
        resident = _forward_bytes(sq, sk, d, hg, q.dtype.itemsize)
    else:
        kv_lanes, kv_at = gd, lambda g: g
        resident = _resident_bytes(sq, sk, gd, q.dtype.itemsize)
    in_specs = [
        pl.BlockSpec((1, blk_q, gd), lambda b, g, i, s: (b, i, g)),
        pl.BlockSpec((1, sk, kv_lanes), lambda b, g, i, s: (b, 0, kv_at(g))),
        pl.BlockSpec((1, sk, kv_lanes), lambda b, g, i, s: (b, 0, kv_at(g))),
    ]
    extra, extra_specs = _mask_inputs(bias, qseg, kseg, blk_q, sk,
                                      q_index=lambda i: i,
                                      k_index=lambda i: 0)
    kernel = functools.partial(
        _fwd_kernel, has_bias=bias is not None, has_seg=qseg is not None,
        causal=causal, dropout_p=dropout_p,
        drop_tile=(_block(sq, fine=causal), _block(sk, fine=causal)),
        written_out=written_out, blk_q=blk_q, blk_k=blk_k, n_q=n_q, n_k=n_k,
        scale=1.0 / math.sqrt(d), causal_off=sk - sq, heads=heads, hg=hg,
        shared_kv=shared_kv, window=window)

    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_hg, n_q),
            in_specs=in_specs + extra_specs,
            out_specs=[
                pl.BlockSpec((1, blk_q, gd), lambda b, g, i, s: (b, i, g)),
                pl.BlockSpec((1, 1, hg, blk_q),
                             lambda b, g, i, s: (b, g, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            # per-row logsumexp, the rows along the lanes as the transposed
            # scores of both kernels want them
            jax.ShapeDtypeStruct((b, n_hg, hg, sq), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel"), resident),
        interpret=_INTERPRET,
    )(seed, q, k, v, *extra)
    return o, lse


# ---------------------------------------------------------------------------
# backward: ONE merged kernel (FlashAttention-2 recompute scheme).
#
# The score block s and p = exp(s - lse) are recomputed once per (k,q) block
# and feed dq, dk AND dv — half the exp/mask/dropout recompute of the classic
# two-kernel (dq grid / dkv grid) split.  A grid step owns one k block: dk/dv
# accumulate in VMEM over the loop of q sub-blocks, which under the causal
# mask starts at the diagonal.  dq accumulates, transposed like the scores,
# in a VMEM scratch that stays put while the k blocks of a (batch, head
# group) go by, and is written once after the last of them.


def _bwd_kernel(seed_ref, *refs, has_bias, has_seg, causal, dropout_p,
                written_out, blk_q, blk_k, n_q, n_k, scale, causal_off, heads,
                hg):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    dbias_ref = next(it) if has_bias else None
    dq_acc, dk_acc, dv_acc = next(it), next(it), next(it)
    db_acc = next(it) if has_bias else None

    b, g = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1] // hg
    bias = bias_ref[0] if has_bias else None
    kseg = kseg_ref[0] if has_seg else None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def block(h, qi, ki, diagonal):
        sl = slice(h * d, (h + 1) * d)
        hs = slice(h, h + 1)
        k_hd, v_hd = k_ref[0, :, sl], v_ref[0, :, sl]
        qs = _rows(qi, blk_q)
        q_hd, do_hd = q_ref[0, qs, sl], do_ref[0, qs, sl]
        s = _scores(k_hd, q_hd, bias, qseg_ref[0, qi] if has_seg else None,
                    kseg, qi * blk_q, ki * blk_k, scale, diagonal,
                    causal_off)
        p = jnp.exp(s - lse_ref[0, 0, qi, hs, :])           # (blk_k, blk_q)
        dpd = jax.lax.dot_general(v_hd, do_hd, _NT,
                                  preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            bh = b * jnp.int32(heads) + g * jnp.int32(hg) + jnp.int32(h)
            keep = _keep_mask(seed_ref, bh, qi * blk_q, ki * blk_k, blk_q,
                              blk_k, dropout_p, (blk_q, blk_k))
            inv = 1.0 / (1.0 - dropout_p)
            pd = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dpd * inv, 0.0)
        else:
            pd, dp = p, dpd
        dv_acc[:, sl] += jax.lax.dot(pd.astype(do_ref.dtype), do_hd,
                                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, qi, hs, :])
        if has_bias:  # d(bias_k) = sum over q rows of dS (heads summed)
            db_acc[...] += jnp.sum(ds, axis=1, keepdims=True)
        ds = ds.astype(q_ref.dtype)
        dk_acc[:, sl] += jax.lax.dot(
            ds, q_hd, preferred_element_type=jnp.float32) * scale
        dq_acc[qi, sl, :] += jax.lax.dot_general(           # (d, blk_q)
            k_hd, ds, _TN, preferred_element_type=jnp.float32) * scale

    def k_block(ki):
        """k block ki (static or the grid's index) against its q blocks,
        the heads side by side: first those the diagonal crosses, then the
        whole ones below it."""
        if causal:
            # seen from the keys' side: q blocks below `first_seen` are
            # wholly masked, those from `first_whole` on wholly kept
            edge = ki * blk_k - causal_off   # first row that sees the block
            first_seen = _clip(edge // blk_q, 0, n_q - 1)
            first_whole = _clip((edge + blk_k - 1 + blk_q - 1) // blk_q,
                                first_seen, n_q)
        else:
            first_seen = first_whole = 0
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if has_bias:
            db_acc[...] = jnp.zeros_like(db_acc)

        def blocks(qi, _, diagonal):
            for h in range(hg):
                block(h, qi, ki, diagonal)
        _loop(first_seen, first_whole, lambda qi, c: blocks(qi, c, True),
              None, written_out)
        _loop(first_whole, n_q, lambda qi, c: blocks(qi, c, False), None,
              written_out)
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        if has_bias:
            dbias_ref[0, 0] = db_acc[...]

    if causal:
        _per_block(pl.program_id(2), n_k, written_out, k_block)
    else:
        k_block(pl.program_id(2))

    @pl.when(pl.program_id(2) == n_k - 1)
    def _finish():
        for qi in range(n_q):
            dq_ref[0, qi * blk_q:(qi + 1) * blk_q, :] = dq_acc[qi].T.astype(
                dq_ref.dtype)


@_traced_once(10)
def _bwd_impl(q, k, v, bias, qseg, kseg, seed, o, lse, do,
              causal, dropout_p, heads, hg):
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // heads
    gd = hg * d
    n_hg = heads // hg
    blk_q, blk_k = _block(sq, fine=causal), _block(sk, fine=causal)
    n_q, n_k = sq // blk_q, sk // blk_k

    # lse and delta[b, s, h] = sum_d do*o as (B, n_hg, n_q, hg, blk_q): a
    # (hg, blk_q) tile a q sub-block, found by a leading index in the loop
    lse = lse.reshape(b, n_hg, hg, n_q, blk_q).transpose(0, 1, 3, 2, 4)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        b, n_q, blk_q, n_hg, hg, d).sum(-1).transpose(0, 3, 1, 4, 2)

    # grid (b, head group, k block); Q, dO, lse and delta whole
    whole = pl.BlockSpec((1, sq, gd), lambda b, g, j, s: (b, 0, g))
    k_block = pl.BlockSpec((1, blk_k, gd), lambda b, g, j, s: (b, j, g))
    rows = pl.BlockSpec((1, 1, n_q, hg, blk_q),
                        lambda b, g, j, s: (b, g, 0, 0, 0))
    extra, extra_specs = _mask_inputs(bias, qseg, kseg, blk_q, blk_k,
                                      q_index=None, k_index=lambda j: j)

    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, has_bias=bias is not None,
            has_seg=qseg is not None, causal=causal, dropout_p=dropout_p,
            written_out=max(sq, sk) <= _WRITTEN_OUT,
            blk_q=blk_q, blk_k=blk_k, n_q=n_q, n_k=n_k,
            scale=1.0 / math.sqrt(d), causal_off=sk - sq, heads=heads,
            hg=hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_hg, n_k),
            in_specs=[whole, k_block, k_block, whole, rows, rows]
            + extra_specs,
            out_specs=[whole, k_block, k_block]
            + ([pl.BlockSpec((1, 1, blk_k, 1),
                             lambda b, g, j, s: (b, g, j, 0))]
               if bias is not None else []),
            scratch_shapes=[
                pltpu.VMEM((n_q, gd, blk_q), jnp.float32),
                pltpu.VMEM((blk_k, gd), jnp.float32),
                pltpu.VMEM((blk_k, gd), jnp.float32),
            ] + ([pltpu.VMEM((blk_k, 1), jnp.float32)]
                 if bias is not None else []),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, sk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, sk, hd), v.dtype),
        ] + ([jax.ShapeDtypeStruct((b, n_hg, sk, 1), jnp.float32)]
             if bias is not None else []),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            _resident_bytes(sq, sk, gd, q.dtype.itemsize)),
        interpret=_INTERPRET,
    )(seed, q, k, v, do, lse, delta, *extra)
    dq, dk, dv = outs[:3]
    dbias = None
    if bias is not None:  # per-(batch, head-group) key sums -> (B, Sk, 1)
        dbias = outs[3].sum(axis=1)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# custom_vjp glue


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash(q, k, v, bias, qseg, kseg, seed, causal, dropout_p, heads, hg):
    o, _ = _fwd_impl(q, k, v, bias, qseg, kseg, seed, causal, dropout_p,
                     heads, hg)
    return o


def _flash_fwd(q, k, v, bias, qseg, kseg, seed, causal, dropout_p, heads, hg):
    o, lse = _fwd_impl(q, k, v, bias, qseg, kseg, seed, causal, dropout_p,
                       heads, hg)
    return o, (q, k, v, bias, qseg, kseg, seed, o, lse)


def _flash_bwd(causal, dropout_p, heads, hg, res, g):
    q, k, v, bias, qseg, kseg, seed, o, lse = res
    dq, dk, dv, dbias = _bwd_impl(q, k, v, bias, qseg, kseg, seed, o, lse, g,
                                  causal, dropout_p, heads, hg)
    dqseg = None if qseg is None else np.zeros(qseg.shape, jax.dtypes.float0)
    dkseg = None if kseg is None else np.zeros(kseg.shape, jax.dtypes.float0)
    dseed = np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, dbias, dqseg, dkseg, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


# the grouped and windowed forward: no backward is built for either form
# (training a routed layer: ROADMAP C3), and a gradient says so by name


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_forward_only(q, k, v, heads, hg, kv_heads, window):
    o, _ = _fwd_impl(q, k, v, None, None, None, jnp.zeros((1,), jnp.int32),
                     True, 0.0, heads, hg, kv_heads, window)
    return o


def _forward_only_fwd(q, k, v, heads, hg, kv_heads, window):
    raise NotImplementedError(
        "flash_attention_grouped is forward only: the flash backward kernel "
        "takes neither grouped KV heads nor a window (expand the KV heads "
        "and call flash_attention_bshd, or take the XLA form)")


_flash_forward_only.defvjp(_forward_only_fwd, lambda *_: None)
