"""Fused training-BatchNorm + activation + residual-add for TPU via pallas.

The r5 ResNet-50 bench decomposition (bench.py RESNET notes) showed the
step is bound not by conv rate but by the BN/elementwise HBM traffic:
~8 passes over 5.7 GB of bf16 activations (conv write, stats read,
normalize+relu write, next-conv read, plus the backward re-reads)
~= 55 ms of a 118 ms step.  XLA cannot fuse a training-mode BN chain
below its reduce/elementwise granularity, so this module does it by
hand (design notes: /opt/skills/guides/pallas_guide.md):

- forward = 2 HBM passes: one single-pass stats kernel (sum and
  sum-of-squares accumulated together, f32, per channel) + one apply
  kernel computing `act(x * a + b [+ residual])` with the per-channel
  affine folded on the host side of the trace;
- backward = 2 passes with a `custom_vjp` that RECOMPUTES x_hat and the
  activation mask from the saved input instead of re-reading saved
  normalized/pre-activation tensors: one reduce kernel for the
  d_gamma/d_beta sums, one elementwise kernel producing dx (and the
  residual gradient) from three per-channel coefficients;
- all per-channel math ((C,)-sized) runs as plain traced jnp — it is
  nanoseconds and keeps the kernels pure elementwise/reduce.

Data is handled channels-last as a free (M, C) = (N*H*W, C) reshape —
the layout `core.layout` puts conv-net activations in anyway.  On
non-TPU backends (tier-1 CI runs `JAX_PLATFORMS=cpu`) `bn_act_train`
automatically selects a pure-jnp reference with identical semantics;
tests flip `_INTERPRET` to run the kernels through the pallas
interpreter and check parity against that reference.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False  # tests flip this to run the kernels via the interpreter


def _inside_checkpoint() -> bool:
    """Inside a jit.recompute_policy-wrapped subtree the custom_vjp /
    kernel paths would PIN their saved activations across the checkpoint
    boundary (jax cannot remat through a custom rule) — every public
    entry below falls back to its plain differentiable reference there
    and lets jax.checkpoint own the recompute."""
    from ..core import recompute as _rc
    return _rc.inside_checkpoint()

_ACTS = (None, "relu", "relu6")
# VMEM budget per (blk_m, C) block: keep each f32 buffer <= ~512 KB so the
# worst kernel (bwd dx: g, x, res in + dx, dres out) stays well under VMEM
_MAX_BLOCK_ELEMS = 1 << 17


def _available() -> bool:
    return _INTERPRET or jax.default_backend() == "tpu"


def _block_m(m: int, c: int):
    """Largest divisor of m that is a multiple of 8 and fits the VMEM
    budget; None when m has no usable divisor (jnp fallback)."""
    cap = min(m, max(8, _MAX_BLOCK_ELEMS // max(c, 1)))
    cap -= cap % 8
    for blk in range(cap, 7, -8):
        if m % blk == 0:
            return blk
    return None


def _act_apply(z, act):
    if act == "relu":
        return jnp.maximum(z, 0.0)
    if act == "relu6":
        return jnp.clip(z, 0.0, 6.0)
    return z


def _act_apply_ref(z, act):
    """Activation for the differentiable references, in select form:
    identical values to `_act_apply`, but the VJP of `where` saves only
    the bool predicate — `maximum`/`clip` save their f32 operand, which
    pins a full-resolution f32 tensor per BN site across the fwd->bwd
    gap (inside jax.checkpoint interiors too, which is exactly the
    liveness `jit.recompute_policy` exists to bound)."""
    if act == "relu":
        return jnp.where(z > 0.0, z, 0.0)
    if act == "relu6":
        return jnp.where(z > 0.0, jnp.where(z < 6.0, z, 6.0), 0.0)
    return z


def _act_mask(z, act):
    if act == "relu":
        return z > 0.0
    if act == "relu6":
        return jnp.logical_and(z > 0.0, z < 6.0)
    return None


# ---------------------------------------------------------------------------
# pallas kernels.  x is viewed as (M, C); the grid walks M in blk_m rows.
# Per-channel vectors ride in one (8, C) f32 `coef` input:
#   row 0 = a  (gamma * invstd)        row 1 = b  (beta - mean * a)
#   row 2 = mean                       row 3 = invstd
#   row 4 = A, row 5 = B, row 6 = Cc   (backward dx coefficients)


def _stats_kernel(x_ref, sum_ref, sq_ref, *, n_m):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    xb = x_ref[...].astype(jnp.float32)
    sum_ref[...] += jnp.sum(xb, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(xb * xb, axis=0, keepdims=True)


def _apply_kernel(*refs, act, has_res, dual=False):
    it = iter(refs)
    x_ref, coef_ref = next(it), next(it)
    res_ref = next(it) if has_res else None
    coefr_ref = next(it) if dual else None
    y_ref = next(it)
    z = x_ref[...].astype(jnp.float32) * coef_ref[0:1] + coef_ref[1:2]
    if has_res:
        rb = res_ref[...].astype(jnp.float32)
        z = z + (rb * coefr_ref[0:1] + coefr_ref[1:2] if dual else rb)
    y_ref[...] = _act_apply(z, act).astype(y_ref.dtype)


def _recompute_z(x_ref, coef_ref, res_ref, coefr_ref, has_res, dual):
    z = x_ref[...].astype(jnp.float32) * coef_ref[0:1] + coef_ref[1:2]
    if has_res:
        rb = res_ref[...].astype(jnp.float32)
        z = z + (rb * coefr_ref[0:1] + coefr_ref[1:2] if dual else rb)
    return z


def _bwd_reduce_kernel(*refs, act, has_res, dual=False):
    it = iter(refs)
    g_ref, x_ref, coef_ref = next(it), next(it), next(it)
    res_ref = next(it) if has_res else None
    coefr_ref = next(it) if dual else None
    sgz_ref, sgzx_ref = next(it), next(it)
    sgzr_ref = next(it) if dual else None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sgz_ref[...] = jnp.zeros_like(sgz_ref)
        sgzx_ref[...] = jnp.zeros_like(sgzx_ref)
        if dual:
            sgzr_ref[...] = jnp.zeros_like(sgzr_ref)

    xb = x_ref[...].astype(jnp.float32)
    gz = g_ref[...].astype(jnp.float32)
    if act is not None:
        z = _recompute_z(x_ref, coef_ref, res_ref, coefr_ref, has_res, dual)
        gz = jnp.where(_act_mask(z, act), gz, 0.0)
    xhat = (xb - coef_ref[2:3]) * coef_ref[3:4]
    sgz_ref[...] += jnp.sum(gz, axis=0, keepdims=True)
    sgzx_ref[...] += jnp.sum(gz * xhat, axis=0, keepdims=True)
    if dual:
        rhat = (res_ref[...].astype(jnp.float32) - coefr_ref[2:3]) \
            * coefr_ref[3:4]
        sgzr_ref[...] += jnp.sum(gz * rhat, axis=0, keepdims=True)


def _bwd_dx_kernel(*refs, act, has_res, dual=False):
    it = iter(refs)
    g_ref, x_ref, coef_ref = next(it), next(it), next(it)
    res_ref = next(it) if has_res else None
    coefr_ref = next(it) if dual else None
    dx_ref = next(it)
    dres_ref = next(it) if has_res else None
    xb = x_ref[...].astype(jnp.float32)
    gz = g_ref[...].astype(jnp.float32)
    if act is not None:
        z = _recompute_z(x_ref, coef_ref, res_ref, coefr_ref, has_res, dual)
        gz = jnp.where(_act_mask(z, act), gz, 0.0)
    dx = coef_ref[4:5] * gz + coef_ref[5:6] + coef_ref[6:7] * xb
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if has_res:
        if dual:
            rb = res_ref[...].astype(jnp.float32)
            dres = coefr_ref[4:5] * gz + coefr_ref[5:6] + coefr_ref[6:7] * rb
            dres_ref[...] = dres.astype(dres_ref.dtype)
        else:
            dres_ref[...] = gz.astype(dres_ref.dtype)


def _row_spec(blk_m, c):
    return pl.BlockSpec((blk_m, c), lambda i: (i, 0))


def _const_spec(rows, c):
    return pl.BlockSpec((rows, c), lambda i: (0, 0))


def _coef(mean, invstd, gamma, beta, A=None, B=None, Cc=None):
    c = mean.shape[0]
    a = gamma * invstd
    b = beta - mean * a
    zero = jnp.zeros((c,), jnp.float32)
    rows = [a, b, mean, invstd, A if A is not None else zero,
            B if B is not None else zero, Cc if Cc is not None else zero,
            zero]
    return jnp.stack([r.astype(jnp.float32) for r in rows])


def _run_stats(x2, blk_m):
    m, c = x2.shape
    n_m = m // blk_m
    return pl.pallas_call(
        functools.partial(_stats_kernel, n_m=n_m),
        grid=(n_m,),
        in_specs=[_row_spec(blk_m, c)],
        out_specs=[_const_spec(1, c), _const_spec(1, c)],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET,
    )(x2)


def _run_apply(x2, coef, res2, act, blk_m, coefr=None):
    m, c = x2.shape
    dual = coefr is not None
    inputs = [x2, coef] + ([res2] if res2 is not None else []) + \
        ([coefr] if dual else [])
    in_specs = [_row_spec(blk_m, c), _const_spec(8, c)] + \
        ([_row_spec(blk_m, c)] if res2 is not None else []) + \
        ([_const_spec(8, c)] if dual else [])
    return pl.pallas_call(
        functools.partial(_apply_kernel, act=act, has_res=res2 is not None,
                          dual=dual),
        grid=(m // blk_m,),
        in_specs=in_specs,
        out_specs=_row_spec(blk_m, c),
        out_shape=jax.ShapeDtypeStruct((m, c), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_INTERPRET,
    )(*inputs)


def _run_bwd_reduce(g2, x2, coef, res2, act, blk_m, coefr=None):
    m, c = x2.shape
    dual = coefr is not None
    inputs = [g2, x2, coef] + ([res2] if res2 is not None else []) + \
        ([coefr] if dual else [])
    in_specs = [_row_spec(blk_m, c), _row_spec(blk_m, c),
                _const_spec(8, c)] + \
        ([_row_spec(blk_m, c)] if res2 is not None else []) + \
        ([_const_spec(8, c)] if dual else [])
    n_out = 3 if dual else 2
    outs = pl.pallas_call(
        functools.partial(_bwd_reduce_kernel, act=act,
                          has_res=res2 is not None, dual=dual),
        grid=(m // blk_m,),
        in_specs=in_specs,
        out_specs=[_const_spec(1, c)] * n_out,
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET,
    )(*inputs)
    return outs if dual else (outs[0], outs[1])


def _run_bwd_dx(g2, x2, coef, res2, act, blk_m, coefr=None):
    m, c = x2.shape
    has_res = res2 is not None
    dual = coefr is not None
    inputs = [g2, x2, coef] + ([res2] if has_res else []) + \
        ([coefr] if dual else [])
    in_specs = [_row_spec(blk_m, c), _row_spec(blk_m, c),
                _const_spec(8, c)] + \
        ([_row_spec(blk_m, c)] if has_res else []) + \
        ([_const_spec(8, c)] if dual else [])
    out_specs = [_row_spec(blk_m, c)] + ([_row_spec(blk_m, c)] if has_res
                                         else [])
    out_shape = [jax.ShapeDtypeStruct((m, c), x2.dtype)] + \
        ([jax.ShapeDtypeStruct((m, c), res2.dtype)] if has_res else [])
    outs = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, act=act, has_res=has_res,
                          dual=dual),
        grid=(m // blk_m,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_INTERPRET,
    )(*inputs)
    return (outs[0], outs[1]) if has_res else (outs[0], None)


# ---------------------------------------------------------------------------
# custom_vjp over the (M, C) view


def _fwd_impl(x2, gamma, beta, res2, eps, act, blk_m):
    m = x2.shape[0]
    s, sq = _run_stats(x2, blk_m)
    mean = s[0] / m
    var = jnp.maximum(sq[0] / m - mean * mean, 0.0)
    invstd = jax.lax.rsqrt(var + eps)
    coef = _coef(mean, invstd, gamma, beta)
    y2 = _run_apply(x2, coef, res2, act, blk_m)
    return y2, mean, var, invstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bn_act_p(x2, gamma, beta, res2, eps, act, blk_m):
    y2, mean, var, _ = _fwd_impl(x2, gamma, beta, res2, eps, act, blk_m)
    return y2, mean, var


def _bn_act_fwd(x2, gamma, beta, res2, eps, act, blk_m):
    y2, mean, var, invstd = _fwd_impl(x2, gamma, beta, res2, eps, act, blk_m)
    return (y2, mean, var), (x2, gamma, beta, res2, mean, invstd)


def _bn_act_bwd(eps, act, blk_m, residuals, cts):
    x2, gamma, beta, res2, mean, invstd = residuals
    gy, gmean, gvar = cts
    m = x2.shape[0]
    gammaf = gamma.astype(jnp.float32)
    coef = _coef(mean, invstd, gammaf, beta)
    sgz, sgzx = _run_bwd_reduce(gy, x2, coef, res2, act, blk_m)
    sgz, sgzx = sgz[0], sgzx[0]
    # dx = c1*(gz - sgz/M - xhat*sgzx/M) + gmean/M + gvar*2*(x-mean)/M
    #    = A*gz + B + Cc*x   with the xhat/mean terms folded per channel
    c1 = gammaf * invstd
    k = -c1 * sgzx * invstd / m + 2.0 * gvar.astype(jnp.float32) / m
    A = c1
    B = -c1 * sgz / m + gmean.astype(jnp.float32) / m - k * mean
    Cc = k
    coef_dx = _coef(mean, invstd, gammaf, beta, A, B, Cc)
    dx2, dres2 = _run_bwd_dx(gy, x2, coef_dx, res2, act, blk_m)
    dgamma = sgzx.astype(gamma.dtype)
    dbeta = sgz.astype(beta.dtype)
    return dx2, dgamma, dbeta, dres2


_bn_act_p.defvjp(_bn_act_fwd, _bn_act_bwd)


# ---------------------------------------------------------------------------
# reference (pure jnp): same math, any channel axis, fully differentiable.
# Used on CPU / whenever the kernels don't apply, and as the test oracle.


def _ref_stats(x, axes):
    """Batch mean/var in f32 regardless of storage dtype.  The converts
    feed straight into reduces (single-consumer chains XLA input-fuses),
    so no full-tensor f32 copy materializes."""
    mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
    sq = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes)
    return mean, jnp.maximum(sq - mean * mean, 0.0)


def bn_act_reference(x, gamma, beta, eps=1e-5, act=None, residual=None,
                     channel_axis=-1):
    """Returns (y, batch_mean, batch_var) — f32 stats, biased variance.

    Every f32 upcast here is SINGLE-CONSUMER by construction (stats
    accumulate f32 inside the reduces via dtype=/square-chains; the
    normalize takes its own fresh upcast): a shared `xf` binding with
    three consumers materializes a full f32 copy of a bf16 activation in
    the optimized HLO — on the r50-b16 CPU step that convert churn alone
    was ~7 GB of XLA bytes accessed."""
    ch = channel_axis % x.ndim
    axes = tuple(i for i in range(x.ndim) if i != ch)
    mean, var = _ref_stats(x, axes)
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    a = (gamma.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).reshape(shape)
    b = beta.astype(jnp.float32).reshape(shape) - mean.reshape(shape) * a
    z = x.astype(jnp.float32) * a + b
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return _act_apply_ref(z, act).astype(x.dtype), mean, var


# ---------------------------------------------------------------------------
# public entry


def bn_act_train(x, gamma, beta, eps=1e-5, act=None, residual=None,
                 channel_last=True):
    """Fused training BatchNorm + optional residual-add + activation.

    x: (N, ..., C) when channel_last else (N, C, ...); gamma/beta: (C,);
    residual: same shape as x or None; act in {None, "relu", "relu6"}.
    Returns (y, batch_mean_f32, batch_var_f32).  Selects the pallas
    kernel pair on TPU (or under `_INTERPRET`), the jnp reference
    otherwise — callers never need to know which ran.
    """
    if act not in _ACTS:
        raise ValueError(f"bn_act_train: unsupported activation {act!r}")
    ch = -1 if channel_last else 1
    if _inside_checkpoint():
        return bn_act_reference(x, gamma, beta, eps, act, residual, ch)
    use_kernel = (channel_last and _available() and x.ndim >= 2
                  and x.dtype in (jnp.float32, jnp.bfloat16)
                  and (residual is None or residual.shape == x.shape))
    if use_kernel:
        c = x.shape[-1]
        m = int(x.size) // c
        blk_m = _block_m(m, c)
        if blk_m is not None:
            x2 = x.reshape(m, c)
            res2 = None if residual is None else \
                residual.astype(x.dtype).reshape(m, c)
            y2, mean, var = _bn_act_p(x2, gamma, beta, res2, float(eps),
                                      act, blk_m)
            return y2.reshape(x.shape), mean, var
    # fallback: same math, but through the recompute-backward wrapper so
    # the CPU/odd-shape path has the kernel's memory discipline too (only
    # x/res saved; z and the act mask recomputed in the backward)
    if residual is None:
        return _ref1_p(x, gamma, beta, float(eps), act, ch)
    return _ref1_res_p(x, gamma, beta, residual, float(eps), act, ch)


# ---------------------------------------------------------------------------
# recompute-backward wrappers over the jnp reference.  jax.checkpoint-style:
# forward saves only the primal inputs; the backward re-runs the (XLA-fused)
# reference and pulls gradients through jax.vjp — so the fallback paths stop
# materializing z / activation masks between forward and backward, which is
# where the unfused CPU legs were spending their bytes-accessed.


def _ref_vjp(fn, primals, cts):
    _, vjp = jax.vjp(fn, *primals)
    return vjp(cts)


# recompute-backward wrappers over the jnp reference (jax.checkpoint-style:
# forward saves only the primal inputs; the backward re-runs the XLA-fused
# reference under jax.vjp, so no z / activation-mask tensors are stored
# between forward and backward).  On CPU XLA CSEs the recomputation with
# the forward, so this costs no extra bytes accessed there.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ref1_p(x, gamma, beta, eps, act, ch):
    return bn_act_reference(x, gamma, beta, eps, act, None, ch)


def _ref1_fwd(x, gamma, beta, eps, act, ch):
    out = bn_act_reference(x, gamma, beta, eps, act, None, ch)
    return out, (x, gamma, beta)


def _ref1_bwd(eps, act, ch, res, cts):
    return _ref_vjp(lambda x, g, b: bn_act_reference(x, g, b, eps, act,
                                                     None, ch), res, cts)


_ref1_p.defvjp(_ref1_fwd, _ref1_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ref1_res_p(x, gamma, beta, residual, eps, act, ch):
    return bn_act_reference(x, gamma, beta, eps, act, residual, ch)


def _ref1_res_fwd(x, gamma, beta, residual, eps, act, ch):
    out = bn_act_reference(x, gamma, beta, eps, act, residual, ch)
    return out, (x, gamma, beta, residual)


def _ref1_res_bwd(eps, act, ch, res, cts):
    return _ref_vjp(lambda x, g, b, r: bn_act_reference(x, g, b, eps, act,
                                                        r, ch), res, cts)


_ref1_res_p.defvjp(_ref1_res_fwd, _ref1_res_bwd)


# ---------------------------------------------------------------------------
# pooled epilogue: BN + activation + max/avg pool as ONE op.  The pooled
# output is the only full-rank tensor that leaves the op — the normalized/
# activated full-resolution tensor never round-trips HBM (pallas writes the
# pooled block directly on TPU; the fallback recomputes it in the backward).


def _pool_norm(pool):
    """Normalize a pool spec to (kind, (kh,kw), (sh,sw), (ph,pw))."""
    kind, k, s, p = pool
    pair = lambda v: tuple(v) if isinstance(v, (tuple, list)) else \
        (int(v), int(v))
    if kind not in ("max", "avg"):
        raise ValueError(f"fused pool: unsupported kind {kind!r}")
    return (kind, pair(k), pair(s if s is not None else k), pair(p))


def fusable_pool_spec(layer, data_format="NCHW"):
    """(kind, kernel, stride, padding) when `layer` is a stock MaxPool2D
    the fused BN/act epilogue can express — exact type only (a subclass
    forward must run), no ceil_mode/return_mask, no registered hooks (the
    epilogue skips the layer's __call__, so hooks would silently stop
    firing), and no data_format disagreeing with the norm's
    (`data_format` is the layout the epilogue already runs in) — else
    None; the caller then runs the layer itself.  The one gate every
    conv-net block (ResNet stem, VGG runs) uses before folding its pool."""
    from ..nn.layer.pooling import MaxPool2D
    if type(layer) is not MaxPool2D:
        return None
    extra = dict(getattr(layer, "kw", {}))
    if extra.pop("data_format", data_format) != data_format:
        return None
    if any(extra.values()):
        return None
    if layer._forward_pre_hooks or layer._forward_post_hooks:
        return None
    return ("max", layer.kernel_size,
            layer.stride if layer.stride is not None else
            layer.kernel_size, layer.padding)


def _pool_windows(z, kind, k, s, p, channel_last):
    """Window-reduce z (rank 4) with static slice loops — runs identically
    inside pallas kernels (on a loaded block) and in the jnp reference."""
    kh, kw = k
    sh, sw = s
    ph, pw = p
    hax = 1 if channel_last else 2
    h, w = z.shape[hax], z.shape[hax + 1]
    ho = (h + 2 * ph - kh) // sh + 1
    wo = (w + 2 * pw - kw) // sw + 1
    pads = [(0, 0)] * z.ndim
    pads[hax], pads[hax + 1] = (ph, ph), (pw, pw)
    fill = -jnp.inf if kind == "max" else 0.0
    zp = jnp.pad(z, pads, constant_values=fill)
    cp = jnp.pad(jnp.ones_like(z), pads) if kind == "avg" else None

    def windows(a, di, dj):
        sl = [slice(None)] * z.ndim
        sl[hax] = slice(di, di + (ho - 1) * sh + 1, sh)
        sl[hax + 1] = slice(dj, dj + (wo - 1) * sw + 1, sw)
        return a[tuple(sl)]

    acc = cnt = None
    for di in range(kh):
        for dj in range(kw):
            wz = windows(zp, di, dj)
            if kind == "max":
                acc = wz if acc is None else jnp.maximum(acc, wz)
            else:
                acc = wz if acc is None else acc + wz
                wc = windows(cp, di, dj)
                cnt = wc if cnt is None else cnt + wc
    return acc if kind == "max" else acc / cnt


def _pool_reduce_window(y, kind, k, s, p, channel_last):
    """lax.reduce_window pooling (exclusive avg counting) — the XLA-native
    formulation the reference path uses; the pallas kernel body uses the
    static-slice `_pool_windows` form instead (reduce_window does not
    lower inside Mosaic kernels)."""
    kh, kw = k
    sh, sw = s
    ph, pw = p
    if channel_last:
        window, strides = (1, kh, kw, 1), (1, sh, sw, 1)
        pads = [(0, 0), (ph, ph), (pw, pw), (0, 0)]
    else:
        window, strides = (1, 1, kh, kw), (1, 1, sh, sw)
        pads = [(0, 0), (0, 0), (ph, ph), (pw, pw)]
    if kind == "max":
        return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, window,
                                     strides, pads)
    s_ = jax.lax.reduce_window(y, 0.0, jax.lax.add, window, strides, pads)
    cnt = jax.lax.reduce_window(jnp.ones_like(y), 0.0, jax.lax.add,
                                window, strides, pads)
    return s_ / cnt


def bn_act_pool_reference(x, gamma, beta, eps, act, pool, channel_axis=-1):
    """(pooled_y, batch_mean, batch_var) — pure jnp oracle, differentiable."""
    kind, k, s, p = _pool_norm(pool)
    y, mean, var = bn_act_reference(x, gamma, beta, eps, act, None,
                                    channel_axis)
    channel_last = channel_axis % x.ndim == x.ndim - 1
    # max pool is exact in the storage dtype; avg accumulates in f32
    pdt = jnp.float32 if (kind == "avg" or y.dtype == jnp.float32) \
        else y.dtype
    yp = _pool_reduce_window(y.astype(pdt), kind, k, s, p, channel_last)
    return yp.astype(x.dtype), mean, var


def _pool_apply_kernel(x_ref, coef_ref, y_ref, *, act, kind, k, s, p):
    zb = x_ref[0].astype(jnp.float32) * coef_ref[0] + coef_ref[1]
    zb = _act_apply(zb, act)
    y_ref[0] = _pool_windows(zb[None], kind, k, s, p,
                             channel_last=True)[0].astype(y_ref.dtype)


# per-image VMEM budget for the pooled kernel (f32 elements of the input
# block; the stem's (112,112,64) is ~0.8M)
_MAX_POOL_BLOCK_ELEMS = 1 << 20


def _run_pool_apply(x4, coef, act, kind, k, s, p):
    n, h, w, c = x4.shape
    ho = (h + 2 * p[0] - k[0]) // s[0] + 1
    wo = (w + 2 * p[1] - k[1]) // s[1] + 1
    return pl.pallas_call(
        functools.partial(_pool_apply_kernel, act=act, kind=kind,
                          k=k, s=s, p=p),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((8, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, ho, wo, c), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, c), x4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_INTERPRET,
    )(x4, coef)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _bn_pool_p(x, gamma, beta, eps, act, pool, ch):
    out, _ = _bn_pool_fwd(x, gamma, beta, eps, act, pool, ch)
    return out


def _bn_pool_fwd(x, gamma, beta, eps, act, pool, ch):
    kind, k, s, p = _pool_norm(pool)
    c = x.shape[-1]
    m = int(x.size) // c
    blk_m = _block_m(m, c)
    if blk_m is not None:
        sm, sq = _run_stats(x.reshape(m, c), blk_m)
        mean = sm[0] / m
        var = jnp.maximum(sq[0] / m - mean * mean, 0.0)
        invstd = jax.lax.rsqrt(var + eps)
        coef = _coef(mean, invstd, gamma.astype(jnp.float32),
                     beta.astype(jnp.float32))
        yp = _run_pool_apply(x, coef, act, kind, k, s, p)
        return (yp, mean, var), (x, gamma, beta)
    return (bn_act_pool_reference(x, gamma, beta, eps, act, pool, ch),
            (x, gamma, beta))


def _bn_pool_bwd(eps, act, pool, ch, res, cts):
    # recompute backward: re-run the (fused) reference from the saved
    # primals — no full-resolution activations were kept from the forward
    return _ref_vjp(lambda x, g, b: bn_act_pool_reference(
        x, g, b, eps, act, pool, ch), res, cts)


_bn_pool_p.defvjp(_bn_pool_fwd, _bn_pool_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ref_pool_p(x, gamma, beta, eps, act, pool, ch):
    return bn_act_pool_reference(x, gamma, beta, eps, act, pool, ch)


def _ref_pool_fwd(x, gamma, beta, eps, act, pool, ch):
    out = bn_act_pool_reference(x, gamma, beta, eps, act, pool, ch)
    return out, (x, gamma, beta)


def _ref_pool_bwd(eps, act, pool, ch, res, cts):
    return _ref_vjp(lambda x, g, b: bn_act_pool_reference(
        x, g, b, eps, act, pool, ch), res, cts)


_ref_pool_p.defvjp(_ref_pool_fwd, _ref_pool_bwd)


def bn_act_pool_train(x, gamma, beta, eps=1e-5, act=None,
                      pool=("max", 3, 2, 1), channel_last=True):
    """Fused training BatchNorm + activation + 2D max/avg pool.

    x: (N, H, W, C) when channel_last else (N, C, H, W); pool is
    (kind, kernel, stride, padding) with scalar-or-pair ints.  Returns
    (pooled_y, batch_mean_f32, batch_var_f32).

    On TPU (per-image block within VMEM budget) the pallas epilogue
    writes ONLY the pooled output — the normalized full-resolution tensor
    never reaches HBM — and the backward recomputes from the saved input.
    The CPU fallback keeps the same memory discipline through a
    recompute-backward custom_vjp over the reduce_window reference (only
    the primal input crosses the fwd->bwd gap; XLA CSEs the recompute
    with the forward, so bytes accessed do not grow).
    """
    if act not in _ACTS:
        raise ValueError(f"bn_act_pool_train: unsupported activation {act!r}")
    pool = _pool_norm(pool)
    ch = -1 if channel_last else 1
    if _inside_checkpoint():
        return bn_act_pool_reference(x, gamma, beta, eps, act, pool, ch)
    use_kernel = (channel_last and _available() and x.ndim == 4
                  and x.dtype in (jnp.float32, jnp.bfloat16)
                  and int(np.prod(x.shape[1:])) <= _MAX_POOL_BLOCK_ELEMS)
    if use_kernel:
        return _bn_pool_p(x, gamma, beta, float(eps), act, pool, ch)
    return _ref_pool_p(x, gamma, beta, float(eps), act, pool, ch)


# ---------------------------------------------------------------------------
# dual-BN residual: act(bn(x) + bn(res)) as ONE op — the downsample-shortcut
# pattern (ResNet stride blocks).  Both normalizations share the elementwise
# tile the residual add already pays for, so the normalized downsample
# tensor never round-trips HBM on its own.


def bn2_act_reference(x, gamma_x, beta_x, res, gamma_r, beta_r, eps=1e-5,
                      act=None, channel_axis=-1):
    """(y, mean_x, var_x, mean_r, var_r) — pure jnp oracle."""
    ch = channel_axis % x.ndim
    axes = tuple(i for i in range(x.ndim) if i != ch)
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    def affine(v, gamma, beta):
        # f32 stats via single-consumer converts — see bn_act_reference
        mean, var = _ref_stats(v, axes)
        a = (gamma.astype(jnp.float32)
             * jax.lax.rsqrt(var + eps)).reshape(shape)
        b = beta.astype(jnp.float32).reshape(shape) - mean.reshape(shape) * a
        return v.astype(jnp.float32) * a + b, mean, var

    zx, mean_x, var_x = affine(x, gamma_x, beta_x)
    zr, mean_r, var_r = affine(res, gamma_r, beta_r)
    y = _act_apply_ref(zx + zr, act).astype(x.dtype)
    return y, mean_x, var_x, mean_r, var_r


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _bn2_act_p(x2, gamma_x, beta_x, res2, gamma_r, beta_r, eps, act, blk_m):
    (y2, stats), _ = _bn2_fwd_impl(x2, gamma_x, beta_x, res2, gamma_r,
                                   beta_r, eps, act, blk_m)
    return (y2,) + stats


def _bn2_fwd_impl(x2, gamma_x, beta_x, res2, gamma_r, beta_r, eps, act,
                  blk_m):
    m = x2.shape[0]

    def stats_of(v2):
        sm, sq = _run_stats(v2, blk_m)
        mean = sm[0] / m
        var = jnp.maximum(sq[0] / m - mean * mean, 0.0)
        return mean, var, jax.lax.rsqrt(var + eps)

    mean_x, var_x, inv_x = stats_of(x2)
    mean_r, var_r, inv_r = stats_of(res2)
    coef_x = _coef(mean_x, inv_x, gamma_x.astype(jnp.float32),
                   beta_x.astype(jnp.float32))
    coef_r = _coef(mean_r, inv_r, gamma_r.astype(jnp.float32),
                   beta_r.astype(jnp.float32))
    y2 = _run_apply(x2, coef_x, res2, act, blk_m, coefr=coef_r)
    return ((y2, (mean_x, var_x, mean_r, var_r)),
            (mean_x, inv_x, mean_r, inv_r))


def _bn2_act_fwd(x2, gamma_x, beta_x, res2, gamma_r, beta_r, eps, act,
                 blk_m):
    (y2, stats), invs = _bn2_fwd_impl(x2, gamma_x, beta_x, res2, gamma_r,
                                      beta_r, eps, act, blk_m)
    return (y2,) + stats, (x2, gamma_x, beta_x, res2, gamma_r, beta_r, invs)


def _dx_coef(c1, sgz, sgzx, invstd, mean, gmean, gvar, m):
    k = -c1 * sgzx * invstd / m + 2.0 * gvar.astype(jnp.float32) / m
    A = c1
    B = -c1 * sgz / m + gmean.astype(jnp.float32) / m - k * mean
    return A, B, k


def _bn2_act_bwd(eps, act, blk_m, residuals, cts):
    x2, gamma_x, beta_x, res2, gamma_r, beta_r, invs = residuals
    mean_x, inv_x, mean_r, inv_r = invs
    gy, gmx, gvx, gmr, gvr = cts
    m = x2.shape[0]
    gxf = gamma_x.astype(jnp.float32)
    grf = gamma_r.astype(jnp.float32)
    coef_x = _coef(mean_x, inv_x, gxf, beta_x)
    coef_r = _coef(mean_r, inv_r, grf, beta_r)
    sgz, sgzx, sgzr = _run_bwd_reduce(gy, x2, coef_x, res2, act, blk_m,
                                      coefr=coef_r)
    sgz, sgzx, sgzr = sgz[0], sgzx[0], sgzr[0]
    Ax, Bx, Cx = _dx_coef(gxf * inv_x, sgz, sgzx, inv_x, mean_x, gmx, gvx, m)
    Ar, Br, Cr = _dx_coef(grf * inv_r, sgz, sgzr, inv_r, mean_r, gmr, gvr, m)
    coef_dx = _coef(mean_x, inv_x, gxf, beta_x, Ax, Bx, Cx)
    coef_dr = _coef(mean_r, inv_r, grf, beta_r, Ar, Br, Cr)
    dx2, dres2 = _run_bwd_dx(gy, x2, coef_dx, res2, act, blk_m,
                             coefr=coef_dr)
    return (dx2, sgzx.astype(gamma_x.dtype), sgz.astype(beta_x.dtype),
            dres2, sgzr.astype(gamma_r.dtype), sgz.astype(beta_r.dtype))


_bn2_act_p.defvjp(_bn2_act_fwd, _bn2_act_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ref2_p(x, gamma_x, beta_x, res, gamma_r, beta_r, eps, act, ch):
    return bn2_act_reference(x, gamma_x, beta_x, res, gamma_r, beta_r,
                             eps, act, ch)


def _ref2_fwd(x, gamma_x, beta_x, res, gamma_r, beta_r, eps, act, ch):
    out = bn2_act_reference(x, gamma_x, beta_x, res, gamma_r, beta_r,
                            eps, act, ch)
    return out, (x, gamma_x, beta_x, res, gamma_r, beta_r)


def _ref2_bwd(eps, act, ch, res, cts):
    return _ref_vjp(lambda x, gx, bx, r, gr, br: bn2_act_reference(
        x, gx, bx, r, gr, br, eps, act, ch), res, cts)


_ref2_p.defvjp(_ref2_fwd, _ref2_bwd)


def bn2_act_train(x, gamma_x, beta_x, res, gamma_r, beta_r, eps=1e-5,
                  act=None, channel_last=True):
    """Fused dual training-BN + add + activation: act(bn(x) + bn(res)).

    Both inputs share shape; each has its own (C,) gamma/beta and gets its
    own batch stats back.  Returns (y, mean_x, var_x, mean_r, var_r).
    pallas kernel pair on TPU, recompute-backward jnp reference elsewhere.
    """
    if act not in _ACTS:
        raise ValueError(f"bn2_act_train: unsupported activation {act!r}")
    if res.shape != x.shape:
        raise ValueError("bn2_act_train: residual shape must match x "
                         f"({res.shape} vs {x.shape})")
    ch = -1 if channel_last else 1
    if _inside_checkpoint():
        return bn2_act_reference(x, gamma_x, beta_x, res, gamma_r, beta_r,
                                 eps, act, ch)
    use_kernel = (channel_last and _available() and x.ndim >= 2
                  and x.dtype in (jnp.float32, jnp.bfloat16))
    if use_kernel:
        c = x.shape[-1]
        m = int(x.size) // c
        blk_m = _block_m(m, c)
        if blk_m is not None:
            y2, mean_x, var_x, mean_r, var_r = _bn2_act_p(
                x.reshape(m, c), gamma_x, beta_x,
                res.astype(x.dtype).reshape(m, c), gamma_r, beta_r,
                float(eps), act, blk_m)
            return y2.reshape(x.shape), mean_x, var_x, mean_r, var_r
    return _ref2_p(x, gamma_x, beta_x, res.astype(x.dtype), gamma_r,
                   beta_r, float(eps), act, ch)
