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
- The backward is the FlashAttention-2 recompute scheme: the forward saves
  only O and the per-row logsumexp; one merged backward kernel recomputes the
  score blocks and produces dQ partials, dK and dV in a single pass.
- Dropout is applied *inside* the kernel from the TPU hardware PRNG re-seeded
  per (head, q-block, k-block), so the keep mask is bit-identical between
  forward and backward regardless of grid order.  Under `interpret=True`
  (CPU CI) a murmur-style hash of absolute coordinates replaces the PRNG.
- Masking: `causal`, an additive per-key bias (B, Sk) covering padding masks,
  and q/kv segment ids (packed-sequence masking) are fused into the kernel.

`flash_attention_bshd` returns None when the kernel doesn't apply (wrong
platform/shape); callers fall back to the XLA-fused naive path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False  # tests flip this to run the kernels via the interpreter

_NEG_INF = -1e30


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


def _block(size: int) -> int:
    override = _env_int("PDTPU_FLASH_BLOCK")
    if override in (128, 256, 512) and size % override == 0:
        return override
    return next(b for b in (512, 256, 128) if size % b == 0)


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
    if dropout_seed is None:
        dropout_seed = jnp.zeros((1,), jnp.int32)
    local = functools.partial(_flash_bshd, causal=bool(causal),
                              dropout_p=float(dropout_p))
    args = (q, k, v, bias, q_segment_ids, kv_segment_ids, dropout_seed)
    # jax's own mesh context (`jax.set_mesh`): the axes of it that are not
    # yet manual are the ones GSPMD would partition this program over
    mesh = jax.sharding.get_abstract_mesh()
    free = {a: mesh.shape[a] for a in mesh.axis_names
            if a not in mesh.manual_axes}
    if all(n == 1 for n in free.values()):
        return local(*args)
    return _per_shard(local, free, *args)


def _flash_bshd(q, k, v, bias, q_segment_ids, kv_segment_ids, dropout_seed,
                causal, dropout_p):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    g = _head_group(h, d)
    # natural layout: (B, S, H, D) -> (B, S, H*D) is a free reshape
    qt = q.reshape(b, sq, h * d)
    kt = k.reshape(b, sk, h * d)
    vt = v.reshape(b, sk, h * d)
    # reshape mask inputs so every pallas block satisfies the TPU tiling
    # rule (last two dims divisible by (8,128) or equal to the array's):
    # per-key vectors ride the lane axis as (B, 1, Sk), per-query ids the
    # sublane axis as (B, Sq, 1)
    if bias is not None:
        bias = bias.astype(jnp.float32)[:, None, :]
    if q_segment_ids is not None:
        q_segment_ids = q_segment_ids.astype(jnp.int32)[:, :, None]
    if kv_segment_ids is not None:
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)[:, None, :]
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
# On TPU: the hardware PRNG, re-seeded per (seed, bh, qi, ki) block so the
# keep mask is identical wherever the block is recomputed (fwd kernel and the
# merged bwd kernel iterate blocks in different grid orders).
# Under interpret=True (CPU CI): a murmur3-style hash of absolute
# coordinates — the TPU PRNG primitives don't run in the interpreter.


# The compiled kernel draws its bits from the hardware PRNG: a primitive
# that does not lower on the chip is an error, never a switch to the hash.
# The chip-compile test sets this False to hold the hash form to the same
# compiler.
_HW_PRNG = True


def _keep_mask(seed_ref, bh, qi, ki, blk_q, blk_k, dropout_p):
    thresh = min(int(dropout_p * 4294967296.0), 4294967295)
    if _HW_PRNG and not _INTERPRET:
        # hardware seeding takes at most 2 words: pack (seed, bh) and
        # (qi, ki) — grid coords are far below 2^15 so the pair is unique.
        # -1640531615 == 0x9E3779B1 as int32
        pltpu.prng_seed(seed_ref[0] + bh * jnp.int32(-1640531615),
                        qi * jnp.int32(0x10001) + ki)
        bits = pltpu.prng_random_bits((blk_q, blk_k))
        return bits.astype(jnp.uint32) >= jnp.uint32(thresh)
    rows, cols = _coords(qi, ki, blk_q, blk_k)
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    x = x ^ (seed_ref[0].astype(jnp.uint32)
             + bh.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x >= jnp.uint32(thresh)


def _coords(qi, ki, blk_q, blk_k):
    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return rows, cols


def _mask_specs(has_bias, has_seg, blk_q, blk_k, q_pos):
    """BlockSpecs for the optional [bias, qseg, kseg] inputs (in that order).
    `q_pos` says which of the two non-(batch/group) grid axes (0 or 1) walks
    the q blocks. Per-key inputs are (B, 1, Sk), per-query ones (B, Sq, 1)."""
    k_pos = 1 - q_pos

    def spec_k(pos):
        return pl.BlockSpec(
            (1, 1, blk_k),
            lambda b, g, a1, a2, s, _p=pos: (b, 0, (a1, a2)[_p]))

    def spec_q(pos):
        return pl.BlockSpec(
            (1, blk_q, 1),
            lambda b, g, a1, a2, s, _p=pos: (b, (a1, a2)[_p], 0))

    out = []
    if has_bias:
        out.append(spec_k(k_pos))
    if has_seg:
        out.append(spec_q(q_pos))
        out.append(spec_k(k_pos))
    return out


def _masked_scores(q_hd, k_hd, bias_ref, qseg_ref, kseg_ref, qi, ki,
                   blk_q, blk_k, scale, causal, causal_off):
    """One (blk_q, blk_k) score block for one head with all masks (f32)."""
    s = jax.lax.dot_general(q_hd, k_hd, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0]  # (1, blk_k) broadcast over rows
    if causal or qseg_ref is not None:
        rows, cols = _coords(qi, ki, blk_q, blk_k)
        if causal:
            s = jnp.where(rows + causal_off >= cols, s, _NEG_INF)
        if qseg_ref is not None:
            # (blk_q, 1) == (1, blk_k) -> (blk_q, blk_k)
            s = jnp.where(qseg_ref[0] == kseg_ref[0], s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(seed_ref, *refs, has_bias, has_seg, causal, dropout_p,
                blk_q, blk_k, n_k, scale, causal_off, heads, hg):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    o_ref, lse_ref = next(it), next(it)
    if n_k > 1:
        acc_ref, m_ref, l_ref = next(it), next(it), next(it)

    b, g, qi, ki = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    d = q_ref.shape[-1] // hg

    if n_k > 1:
        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    def _head(h):
        sl = slice(h * d, (h + 1) * d)
        s = _masked_scores(q_ref[0][:, sl], k_ref[0][:, sl], bias_ref,
                           qseg_ref, kseg_ref, qi, ki, blk_q, blk_k,
                           scale, causal, causal_off)
        bh = b * jnp.int32(heads) + g * jnp.int32(hg) + jnp.int32(h)
        if n_k == 1:
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
            if dropout_p > 0.0:
                keep = _keep_mask(seed_ref, bh, qi, ki, blk_q, blk_k,
                                  dropout_p)
                p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
            o = jax.lax.dot(p.astype(v_ref.dtype), v_ref[0][:, sl],
                            preferred_element_type=jnp.float32) / l
            return o.astype(o_ref.dtype), m + jnp.log(l)
        # online-softmax path (multiple k blocks)
        hsl = slice(h, h + 1)
        m_prev = m_ref[:, hsl]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[:, hsl] = l_ref[:, hsl] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
        m_ref[:, hsl] = m_cur
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, bh, qi, ki, blk_q, blk_k, dropout_p)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0][:, sl],
            preferred_element_type=jnp.float32)
        return None, None

    def _compute():
        if n_k == 1:
            outs, lses = [], []
            for h in range(hg):
                o, lse = _head(h)
                outs.append(o)
                lses.append(lse)
            o_ref[0] = jnp.concatenate(outs, axis=1)
            lse_ref[0, 0] = jnp.concatenate(lses, axis=1)
        else:
            for h in range(hg):
                _head(h)

    if causal and n_k > 1:
        @pl.when(qi * blk_q + blk_q - 1 + causal_off >= ki * blk_k)
        def _go():
            _compute()
    else:
        _compute()

    if n_k > 1:
        @pl.when(ki == n_k - 1)
        def _finish():
            l = jnp.maximum(l_ref[...], 1e-30)
            d_ = q_ref.shape[-1] // hg
            parts = [(acc_ref[:, h * d_:(h + 1) * d_] / l[:, h:h + 1])
                     for h in range(hg)]
            o_ref[0] = jnp.concatenate(parts, axis=1).astype(o_ref.dtype)
            lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _fwd_impl(q, k, v, bias, qseg, kseg, seed, causal, dropout_p, heads, hg):
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // heads
    gd = hg * d
    n_hg = heads // hg
    blk_q, blk_k = _block(sq), _block(sk)
    n_q, n_k = sq // blk_q, sk // blk_k
    scale = 1.0 / math.sqrt(d)

    in_specs = [
        pl.BlockSpec((1, blk_q, gd), lambda b, g, i, j, s: (b, i, g)),
        pl.BlockSpec((1, blk_k, gd), lambda b, g, i, j, s: (b, j, g)),
        pl.BlockSpec((1, blk_k, gd), lambda b, g, i, j, s: (b, j, g)),
    ]
    inputs = [q, k, v]
    in_specs += _mask_specs(bias is not None, qseg is not None,
                            blk_q, blk_k, q_pos=0)
    if bias is not None:
        inputs.append(bias)
    if qseg is not None:
        inputs.extend([qseg, kseg])

    kernel = functools.partial(
        _fwd_kernel, has_bias=bias is not None, has_seg=qseg is not None,
        causal=causal, dropout_p=dropout_p, blk_q=blk_q, blk_k=blk_k,
        n_k=n_k, scale=scale, causal_off=sk - sq, heads=heads, hg=hg)

    scratch = []
    if n_k > 1:
        scratch = [
            pltpu.VMEM((blk_q, gd), jnp.float32),
            pltpu.VMEM((blk_q, hg), jnp.float32),
            pltpu.VMEM((blk_q, hg), jnp.float32),
        ]

    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_hg, n_q, n_k),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, blk_q, gd), lambda b, g, i, j, s: (b, i, g)),
                pl.BlockSpec((1, 1, blk_q, hg),
                             lambda b, g, i, j, s: (b, g, i, 0)),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, n_hg, sq, hg), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_INTERPRET,
    )(seed, *inputs)
    return o, lse


# ---------------------------------------------------------------------------
# backward: ONE merged kernel (FlashAttention-2 recompute scheme).
#
# The score block s and p = exp(s - lse) are recomputed once per (k,q) block
# and feed dq, dk AND dv — half the exp/mask/dropout recompute of the classic
# two-kernel (dq grid / dkv grid) split.  dk/dv accumulate in VMEM across the
# inner q axis; dq cannot (its output block is revisited non-consecutively on
# TPU), so each grid step writes a per-k-block dq partial and XLA sums the
# n_k partials afterwards — free when n_k == 1, O(n_k · |dq|) HBM otherwise,
# still far cheaper than a second score recompute pass.


def _bwd_kernel(seed_ref, *refs, has_bias, has_seg, causal, dropout_p,
                blk_q, blk_k, n_q, scale, causal_off, heads, hg):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    dqp_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    dbias_ref = next(it) if has_bias else None
    dk_acc, dv_acc = next(it), next(it)
    db_acc = next(it) if has_bias else None

    b, g, ki, qi = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    d = q_ref.shape[-1] // hg

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if has_bias:
            db_acc[...] = jnp.zeros_like(db_acc)

    def _compute():
        dq_parts = []
        for h in range(hg):
            sl = slice(h * d, (h + 1) * d)
            s = _masked_scores(q_ref[0][:, sl], k_ref[0][:, sl], bias_ref,
                               qseg_ref, kseg_ref, qi, ki, blk_q, blk_k,
                               scale, causal, causal_off)
            p = jnp.exp(s - lse_ref[0, 0][:, h:h + 1])
            dpd = jax.lax.dot_general(
                do_ref[0][:, sl], v_ref[0][:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout_p > 0.0:
                bh = (b * jnp.int32(heads) + g * jnp.int32(hg)
                      + jnp.int32(h))
                keep = _keep_mask(seed_ref, bh, qi, ki, blk_q, blk_k,
                                  dropout_p)
                inv = 1.0 / (1.0 - dropout_p)
                pd = jnp.where(keep, p * inv, 0.0)
                dp = jnp.where(keep, dpd * inv, 0.0)
            else:
                pd, dp = p, dpd
            dv_acc[:, sl] += jax.lax.dot_general(
                pd.astype(do_ref.dtype), do_ref[0][:, sl],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0][:, h:h + 1])
            dk_acc[:, sl] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), q_ref[0][:, sl],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if has_bias:  # d(bias_k) = sum over q rows of dS (heads summed)
                db_acc[...] += jnp.sum(ds, axis=0, keepdims=True)
            dq_parts.append((jax.lax.dot(
                ds.astype(k_ref.dtype), k_ref[0][:, sl],
                preferred_element_type=jnp.float32) * scale))
        dqp_ref[0, 0] = jnp.concatenate(dq_parts, axis=1).astype(
            dqp_ref.dtype)

    if causal:
        cond = qi * blk_q + blk_q - 1 + causal_off >= ki * blk_k

        @pl.when(cond)
        def _go():
            _compute()

        @pl.when(jnp.logical_not(cond))
        def _zero():  # this (k,q) partial must still be defined
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])
    else:
        _compute()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        if has_bias:
            dbias_ref[0, 0] = db_acc[...]


def _bwd_impl(q, k, v, bias, qseg, kseg, seed, o, lse, do,
              causal, dropout_p, heads, hg):
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // heads
    gd = hg * d
    n_hg = heads // hg
    blk_q, blk_k = _block(sq), _block(sk)
    n_q, n_k = sq // blk_q, sk // blk_k
    scale = 1.0 / math.sqrt(d)
    causal_off = sk - sq

    # delta[b, s, h] = sum_d do*o, laid out (B, n_hg, Sq, hg) like lse
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        b, sq, heads, d).sum(-1).reshape(b, sq, n_hg, hg).transpose(
        0, 2, 1, 3)

    # grid (b, head group, k block, q block): dk/dv owned per outer k step,
    # dq written as per-k partials summed below
    kv_specs = [
        pl.BlockSpec((1, blk_q, gd), lambda b, g, j, i, s: (b, i, g)),  # q
        pl.BlockSpec((1, blk_k, gd), lambda b, g, j, i, s: (b, j, g)),  # k
        pl.BlockSpec((1, blk_k, gd), lambda b, g, j, i, s: (b, j, g)),  # v
        pl.BlockSpec((1, blk_q, gd), lambda b, g, j, i, s: (b, i, g)),  # do
        pl.BlockSpec((1, 1, blk_q, hg),
                     lambda b, g, j, i, s: (b, g, i, 0)),               # lse
        pl.BlockSpec((1, 1, blk_q, hg),
                     lambda b, g, j, i, s: (b, g, i, 0)),               # delta
    ]
    kv_extra = _mask_specs(bias is not None, qseg is not None,
                           blk_q, blk_k, q_pos=1)
    inputs = [q, k, v, do, lse, delta] + \
        ([] if bias is None else [bias]) + \
        ([] if qseg is None else [qseg, kseg])

    dqp_dtype = q.dtype if n_k == 1 else jnp.float32
    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, has_bias=bias is not None,
            has_seg=qseg is not None, causal=causal, dropout_p=dropout_p,
            blk_q=blk_q, blk_k=blk_k, n_q=n_q, scale=scale,
            causal_off=causal_off, heads=heads, hg=hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_hg, n_k, n_q),
            in_specs=kv_specs + kv_extra,
            out_specs=[
                pl.BlockSpec((1, 1, blk_q, gd),
                             lambda b, g, j, i, s: (j, b, i, g)),   # dq part
                pl.BlockSpec((1, blk_k, gd),
                             lambda b, g, j, i, s: (b, j, g)),
                pl.BlockSpec((1, blk_k, gd),
                             lambda b, g, j, i, s: (b, j, g)),
            ] + ([pl.BlockSpec((1, 1, 1, blk_k),
                               lambda b, g, j, i, s: (b, g, 0, j))]
                 if bias is not None else []),
            scratch_shapes=[
                pltpu.VMEM((blk_k, gd), jnp.float32),
                pltpu.VMEM((blk_k, gd), jnp.float32),
            ] + ([pltpu.VMEM((1, blk_k), jnp.float32)]
                 if bias is not None else []),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_k, b, sq, hd), dqp_dtype),
            jax.ShapeDtypeStruct((b, sk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, sk, hd), v.dtype),
        ] + ([jax.ShapeDtypeStruct((b, n_hg, 1, sk), jnp.float32)]
             if bias is not None else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_INTERPRET,
    )(seed, *inputs)
    dqp, dk, dv = outs[0], outs[1], outs[2]
    dq = dqp[0].astype(q.dtype) if n_k == 1 else \
        dqp.sum(axis=0).astype(q.dtype)
    dbias = None
    if bias is not None:  # per-(batch, head-group) key sums -> (B, 1, Sk)
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
