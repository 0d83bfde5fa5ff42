"""paddle_tpu.jit — the "static graph world" replacement.

Reference: @paddle.jit.to_static / ProgramTranslator
(python/paddle/fluid/dygraph/dygraph_to_static/program_translator.py:729) turn
dygraph Python into a ProgramDesc via AST rewriting; save_inference_model
serializes program + params; AnalysisPredictor serves it.

TPU-native: tracing *is* the program capture — `functional_call` runs a Layer's
forward with parameters injected as jax values and the tape off, so `jax.jit`
(+AOT `jax.export`) replaces ProgramDesc/Executor/AnalysisPredictor, buffer
donation replaces inplace/memory-optimize passes, and `TrainStep` fuses
forward+backward+optimizer into one compiled XLA program (what the reference
needs a whole SSA-graph ParallelExecutor for).
"""
from __future__ import annotations

import os
import pickle
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import buffer_updates as _bufup
from ..core import recompute as _recompute
from ..core.layout import layout_policy  # noqa: F401  (public: jit.layout_policy)
from ..core.recompute import recompute_policy  # noqa: F401  (public: jit.recompute_policy)
from ..core.tensor import Tensor, no_grad, unwrap
from ..nn.layer_base import Layer
from ..observability.tracer import defer_args as _defer_args
from ..observability.tracer import span as _span


# ---------------------------------------------------------------------------
# functional_call: run a Layer with params supplied as values
# ---------------------------------------------------------------------------

def state_arrays(layer: Layer) -> Dict[str, Any]:
    """Named param+buffer raw arrays (the layer's pytree leaves)."""
    return {k: v._data for k, v in layer.state_dict().items()}


def functional_call(layer: Layer, state: Dict[str, Any], *args,
                    training: Optional[bool] = None, method: str = None,
                    buffer_updates: Optional[Dict[str, Any]] = None,
                    **kwargs):
    """Run layer.forward with `state` (name -> raw array) swapped in.

    Works under jit tracing: swapping happens at trace time only.  Tape is
    disabled so the pure-functional jax.grad path is used for autodiff.
    `method` selects an alternative entry point (e.g. a fixed-cache decode
    forward) instead of __call__.  When `buffer_updates` (a dict) is
    passed, in-place buffer writes made during the forward (BatchNorm
    running stats) are captured FUNCTIONALLY instead of applied: the dict
    is filled with {state_key: new_raw_value} so a compiled train step can
    fold them into its next-state outputs (no host round-trip under jit).
    """
    sd = layer.state_dict()
    originals = {k: t._data for k, t in sd.items()}
    modes = None
    if training is not None:
        modes = [(l, l.training) for l in layer.sublayers(include_self=True)]
        for l, _ in modes:
            l.training = training
    try:
        for k, t in sd.items():
            if k in state:
                t._data = state[k]
        entry = getattr(layer, method) if method else layer
        with no_grad():
            if buffer_updates is not None:
                with _bufup.capture() as log:
                    out = entry(*_wrap_args(args), **kwargs)
                buffer_updates.update(_bufup.resolve(log, sd))
            else:
                out = entry(*_wrap_args(args), **kwargs)
        return _extract_raw(out)
    finally:
        for k, t in sd.items():
            t._data = originals[k]
        if modes is not None:
            for l, m in modes:
                l.training = m


def _wrap_args(args):
    return tuple(Tensor(a) if isinstance(a, (jax.Array, np.ndarray)) or _is_tracer(a)
                 else a for a in args)


def _extract_raw(out):
    """Tensor pytree -> raw arrays; a layout boundary: rank-4 tensors the
    layout policy left physically NHWC are transposed back to the logical
    NCHW the caller expects (loss functions, hapi metrics, predict)."""
    def leaf(x):
        if not isinstance(x, Tensor):
            return x
        if x._layout is not None and x._data.ndim == 4:
            return jnp.transpose(x._data, (0, 3, 1, 2))
        return x._data
    return jax.tree_util.tree_map(leaf, out,
                                  is_leaf=lambda x: isinstance(x, Tensor))


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# to_static
# ---------------------------------------------------------------------------

class InputSpec:
    """paddle.static.InputSpec equivalent."""

    def __init__(self, shape, dtype="float32", name=None):
        from ..core.dtype import convert_dtype
        self.shape = tuple(-1 if s is None else int(s) for s in shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    def to_shape_dtype(self, batch=1):
        shape = tuple(batch if s == -1 else s for s in self.shape)
        return jax.ShapeDtypeStruct(shape, self.dtype)


class StaticFunction:
    """Result of @to_static: compiled execution of a Layer/function."""

    def __init__(self, fn: Callable, layer: Optional[Layer] = None,
                 input_spec=None):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        self._compiled = None

    @property
    def _pure(self):
        if self._compiled is None:
            if self._layer is not None:
                layer, fn = self._layer, self._fn

                def pure(state, *args, **kwargs):
                    sd = layer.state_dict()
                    originals = {k: t._data for k, t in sd.items()}
                    try:
                        for k, t in sd.items():
                            if k in state:
                                t._data = state[k]
                        with no_grad():
                            out = fn(*_wrap_args(args), **kwargs)
                        return _extract_raw(out)
                    finally:
                        for k, t in sd.items():
                            t._data = originals[k]
            else:
                fn = self._fn

                def pure(state, *args, **kwargs):
                    with no_grad():
                        out = fn(*_wrap_args(args), **kwargs)
                    return _extract_raw(out)
            from ..observability import track
            label = (type(self._layer).__name__ if self._layer is not None
                     else getattr(self._fn, "__name__", "fn"))
            self._compiled = track(f"to_static:{label}", jax.jit(pure))
        return self._compiled

    def __call__(self, *args, **kwargs):
        state = state_arrays(self._layer) if self._layer is not None else {}
        raw_args = tuple(unwrap(a) for a in args)
        out = self._pure(state, *raw_args, **kwargs)
        return jax.tree_util.tree_map(lambda x: Tensor(x), out)

    def concrete_program(self, *args):
        return self

    @property
    def forward(self):
        return self


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """@paddle.jit.to_static — here: jit the forward (tape off, donation-ready)."""
    def decorate(fn):
        if isinstance(fn, Layer):
            return StaticFunction(fn.forward, layer=fn, input_spec=input_spec)
        # bound method of a Layer?
        self_obj = getattr(fn, "__self__", None)
        if isinstance(self_obj, Layer):
            return StaticFunction(fn, layer=self_obj, input_spec=input_spec)
        return StaticFunction(fn, input_spec=input_spec)
    if function is not None:
        return decorate(function)
    return decorate


declarative = to_static


def not_to_static(fn):
    return fn


# ---------------------------------------------------------------------------
# TrainStep: fused forward+backward+optimizer, fully jitted with donation
# ---------------------------------------------------------------------------

def forward_loss(model, loss_fn, state, batch, rng_key=None, amp_level=None,
                 amp_dtype="bfloat16", return_outputs=False,
                 return_buffer_updates=False):
    """Shared traced forward+loss used by TrainStep / ShardedTrainStep:
    functional_call with a per-step rng root (fresh dropout masks each step)
    and optional bf16 autocast.  With return_outputs, also returns the raw
    forward outputs (so hapi metrics reuse the training forward instead of
    paying a second one).  With return_buffer_updates, in-place buffer
    writes (BatchNorm running stats) are captured functionally and
    returned as a third element {state_key: new_raw} — the compiled step
    folds them into its next state instead of freezing them under jit."""
    import contextlib
    from .. import amp as amp_mod
    from ..core import rng as _rng

    def run():
        bufs = {} if return_buffer_updates else None
        out = functional_call(model, state, *batch[:-1], training=True,
                              buffer_updates=bufs)
        label = Tensor(batch[-1])
        outs = out if isinstance(out, tuple) else (out,)
        loss = loss_fn(*[Tensor(o) for o in outs], label)
        if return_buffer_updates:
            return unwrap(loss), outs if return_outputs else (), bufs
        if return_outputs:
            return unwrap(loss), outs
        return unwrap(loss)

    keyctx = (_rng.key_ctx(rng_key) if rng_key is not None
              else contextlib.nullcontext())
    with keyctx:
        if amp_level:
            with amp_mod.auto_cast(level=amp_level, dtype=amp_dtype):
                return run()
        return run()

_obs_step_hist = None


def _step_hist():
    """train_step_seconds histogram handle (created once; registry.reset()
    zeroes values in place so the cache stays valid)."""
    global _obs_step_hist
    if _obs_step_hist is None:
        from ..observability import metrics as _m
        _obs_step_hist = _m.histogram(
            "train_step_seconds",
            "host wall time per TrainStep/ShardedTrainStep call (dispatch "
            "+ any synchronous device wait)")
    return _obs_step_hist


def warm_step_program(compiled_fn, state, opt_state, optimizer, raw_batch):
    """Compile a train-step program for this signature WITHOUT executing
    it — the shared half of `TrainStep.warmup` / `ShardedTrainStep.warmup`
    (one place for the calling convention so the two step classes cannot
    drift).  Stand-ins for the per-call dynamic scalars are
    aval-identical to a real call's; the key is a CONSTANT (not
    `_rng.next_key()`: warming must not consume the stream a bit-exact
    resume depends on).  Returns whether a compile happened."""
    from ..core import rng as _rng
    args = (state, opt_state,
            jnp.asarray(optimizer._step_count + 1, jnp.int32),
            jnp.asarray(optimizer.get_lr(), jnp.float32),
            _rng.example_key(), raw_batch)
    if hasattr(compiled_fn, "warm"):              # TrackedJit
        return bool(compiled_fn.warm(*args))
    # PDTPU_OBS_PROGRAMS=0: compile without executing; the first call
    # retraces but hits the persistent cache
    try:
        compiled_fn.lower(*args).compile()
        return True
    except Exception:
        return False


def guard_select(params, opt_state, new_params, new_opt, loss, grads):
    """Device-side step guard, shared by TrainStep / ShardedTrainStep.

    Computes loss + global-grad-norm finiteness INSIDE the compiled step
    (no extra host sync: the scalars ride out as two more outputs the host
    reads together with the loss it was reading anyway) and selects the
    pre-update state when the step is bad — a NaN/Inf batch leaves params,
    optimizer moments, AND BatchNorm running stats untouched.  This is the
    skip half of GradScaler's skip-and-decay, applied even without AMP.

    Returns (guarded_params, guarded_opt, grad_norm, ok).
    """
    from ..core.selected_rows import RowSparseGrad
    leaves = [g.values if isinstance(g, RowSparseGrad) else g
              for g in grads.values()]
    if leaves:
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                             for l in leaves))
    else:
        gnorm = jnp.float32(0)
    ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)

    def sel(new, old):
        return jnp.where(ok, new, old)

    return (jax.tree_util.tree_map(sel, new_params, params),
            jax.tree_util.tree_map(sel, new_opt, opt_state),
            gnorm, ok)


class TrainStep:
    """One compiled training step (the perf path used by hapi/bench).

    step(params, opt_state, step_no, lr, *batch) -> (params', opt_state', loss)
    with `params`/`opt_state` donated — the XLA analogue of the reference's
    fused-allreduce + inplace-addto passes is simply donation + XLA fusion.

    guard=True compiles the finiteness guard into the step (see
    guard_select) and exposes per-step (grad_norm, ok) on `last_guard`;
    utils.guarded.GuardedTrainStep adds the host-side policy (spike window,
    quarantine records, rollback).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 amp_level: Optional[str] = None, amp_dtype="bfloat16",
                 mesh=None, batch_sharding=None, remat: bool = False,
                 with_outputs: bool = False, guard: bool = False,
                 accum_steps: int = 1):
        if mesh is not None or batch_sharding is not None:
            # both were accepted and ignored: the step ran on one device
            raise TypeError(
                "TrainStep compiles for one device and never used mesh= / "
                "batch_sharding=; for a step over a mesh use "
                "paddle_tpu.parallel.ShardedTrainStep(model, loss_fn, "
                "optimizer, mesh=mesh)")
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype
        # gradient accumulation: the step takes the FULL logical batch,
        # splits it into accum_steps micro-batches inside ONE compiled
        # program (lax.scan, f32 grad accumulators) and applies ONE
        # optimizer update — b>256-equivalent towers train in the
        # micro-batch activation envelope with the compile count unchanged
        self.accum_steps = int(accum_steps)
        if self.accum_steps < 1:
            raise ValueError("TrainStep: accum_steps must be >= 1")
        if self.accum_steps > 1 and with_outputs:
            raise ValueError(
                "TrainStep: with_outputs does not compose with "
                "accum_steps>1 (per-micro-batch forward outputs would "
                "have to be stacked across the scan; run the forward "
                "separately if metrics need it)")
        # with_outputs: the compiled step also returns the forward outputs
        # (hapi metric reuse) — on the sparse-grad path too (step_sparse
        # threads them through the aux channel)
        self._with_outputs = with_outputs
        self.last_outputs = None
        self._names = list(model.state_dict().keys())
        self._trainable = {k for k, v in model.state_dict().items()
                           if getattr(v, "trainable", False)}
        # params flagged by Embedding(sparse=True): their grads flow as
        # RowSparseGrad through the zeros-cotangent channel (selected_rows.py)
        self._sparse = {k for k, v in model.state_dict().items()
                        if getattr(v, "sparse_grad", False)}
        # row-sharded tables (embedding.ShardedEmbedding): their sparse
        # grads take the per-shard lazy update inside the compiled step
        self._row_shard = {
            k: (v.row_shard_axis, v.row_shard_mesh)
            for k, v in model.state_dict().items()
            if getattr(v, "row_shard_axis", None) is not None
            and getattr(v, "row_shard_mesh", None) is not None}
        if self.accum_steps > 1 and self._sparse:
            raise NotImplementedError(
                f"TrainStep(accum_steps={self.accum_steps}) does not "
                f"compose with sparse-grad embedding weights "
                f"{sorted(self._sparse)}: per-micro-batch RowSparseGrads "
                "would need a row-union merge inside the accumulation "
                "scan.  Rebuild the offending Embedding/ShardedEmbedding "
                "layers with sparse=False (dense grads accumulate fine) "
                "or run accum_steps=1")
        self._sig_cache = {}
        self._sparse_checked = False
        # param names demoted to DENSE grads (tied weights): sparse grads
        # would drop the other uses' gradients, so fall back instead of
        # erroring (the reference's lazy-mode Adam likewise densifies when
        # the lookup table is shared)
        self._sparse_deny = set()
        if self._sparse:
            by_obj = {}
            for k, v in model.state_dict().items():
                by_obj.setdefault(id(v), (v, []))[1].append(k)
            for v, names in by_obj.values():
                if len(names) > 1 and self._sparse.intersection(names):
                    import warnings
                    warnings.warn(
                        f"Embedding(sparse=True) weight registered under "
                        f"multiple names {names} (tied weight) — falling "
                        "back to a dense gradient for it so the other "
                        "uses' gradients are kept", UserWarning)
                    self._sparse_deny.add(
                        getattr(v, "name", None) or names[0])
        self._compiled = None
        self._compiled_multi = None
        self._opt_state = None
        self._remat = remat
        self._guard = bool(guard)
        # (grad_norm, ok) device scalars from the last guarded call; read
        # them together with the loss to avoid an extra host sync
        self.last_guard = None
        # a model whose forward returns, as its last output, an int32 vector
        # of counts names them (`train_count_names`): the step returns the
        # vector beside the loss, and the `train_step` span carries them,
        # read from the device only when the tracer's ring is read
        self._count_names = getattr(model, "train_count_names", None)
        if self._count_names is not None and self._sparse:
            raise NotImplementedError(
                "TrainStep does not carry train_count_names through the "
                "sparse-gradient step")
        self.last_counts = None

    def _forward_loss(self, state, batch, rng_key=None):
        return forward_loss(self.model, self.loss_fn, state, batch, rng_key,
                            self.amp_level, self.amp_dtype)

    def _sparse_setup(self, example_state, example_batch):
        """Shared sparse-grad preamble for the single- and multi-step
        builds: shape-probe each sparse lookup's (n, width, dtype), map ctx
        keys back to state keys, and run the dense-consumption guard once
        (its verdict is shape-independent).  A sparse weight the traced
        forward ALSO consumes densely (tied LM head) is demoted to dense
        grads with a one-time warning — erroring would reject the
        era-typical tied-embedding config."""
        from ..core import selected_rows as sr
        # ctx keys carry the param's unique .name; map back to state keys
        name_to_key = {getattr(v, "name", None) or k: k
                       for k, v in self.model.state_dict().items()}
        while True:
            rec = sr.SparseGradContext("record", deny=self._sparse_deny)
            with sr.use_ctx(rec):
                jax.eval_shape(
                    lambda s, b: self._forward_loss(
                        s, b, jax.random.PRNGKey(0)),
                    example_state, example_batch)
            sparse_specs = rec.specs
            sparse_names = {name_to_key[sr.param_name(k)]
                            for k in sparse_specs}
            if self._sparse_checked or not sparse_specs:
                break

            def probe(sparse_vals):
                zs = {k: jnp.zeros((n, w), dt)
                      for k, (n, w, dt) in sparse_specs.items()}
                full = dict(example_state)
                full.update(sparse_vals)
                ctx = sr.SparseGradContext("apply", zeros=zs,
                                           deny=self._sparse_deny)
                with sr.use_ctx(ctx):
                    return self._forward_loss(full, example_batch,
                                              jax.random.PRNGKey(0))
            bad = sr.dense_consumed_keys(
                probe, {k: example_state[k] for k in sparse_names})
            if not bad:
                break
            import warnings
            warnings.warn(
                f"Embedding(sparse=True) weights {sorted(bad)} are also "
                "consumed densely (tied head) — falling back to dense "
                "gradients for them so those uses' gradients are kept",
                UserWarning)
            key_to_name = {v: k for k, v in name_to_key.items()}
            self._sparse_deny.update(key_to_name[k] for k in bad)
        self._sparse_checked = True
        return sparse_specs, name_to_key, sparse_names

    @staticmethod
    def _merge_sparse_grads(grads, zgrads, ids, params, name_to_key):
        """Fold the zeros-cotangent channel into the dense grad dict as
        RowSparseGrads (shared by the single- and multi-step sparse
        builds)."""
        from ..core import selected_rows as sr
        grads = dict(grads)
        for zk, zg in zgrads.items():
            nm = name_to_key[sr.param_name(zk)]
            rsg = sr.RowSparseGrad(ids[zk], zg, params[nm].shape)
            grads[nm] = (grads[nm] + rsg) if nm in grads else rsg
        return grads

    def _build(self, example_state, example_opt, example_batch):
        from ..optimizer.functional import apply_updates, decay_flags
        opt = self.optimizer
        trainable = self._trainable
        # structured param names let AdamW's apply_decay_param_fun work here
        decay = decay_flags(opt, trainable)

        sparse_specs, sparse_names, name_to_key = {}, set(), {}
        if self._sparse:
            sparse_specs, name_to_key, sparse_names = self._sparse_setup(
                example_state, example_batch)

        with_outputs = self._with_outputs
        guard = self._guard
        accum = self.accum_steps
        counted = self._count_names is not None
        from ..utils import faults as _faults

        def accum_grads(params, step_no, lr, rng_key, batch):
            """K micro-batches through an in-program lax.scan: f32 grad
            accumulators, per-micro rng keys (fold_in), BatchNorm
            running-stat updates compounding sequentially through the
            carry.  Returns (mean loss, averaged grads, params with the
            final buffer state).  Only one micro-batch's activations are
            live at a time — the whole point."""
            for b in batch:
                if b.shape[0] % accum:
                    raise ValueError(
                        f"TrainStep(accum_steps={accum}): batch dim "
                        f"{b.shape[0]} is not divisible by accum_steps")
            split = tuple(
                b.reshape((accum, b.shape[0] // accum) + b.shape[1:])
                for b in batch)
            zero = {k: jnp.zeros(params[k].shape, jnp.float32)
                    for k in trainable}

            def micro(carry, xs):
                cur, acc = carry
                mb, i = xs
                key = jax.random.fold_in(rng_key, i)

                def loss_of(train_params):
                    full = dict(cur)
                    full.update(train_params)
                    loss, _outs, bufs = forward_loss(
                        self.model, self.loss_fn, full, mb, key,
                        self.amp_level, self.amp_dtype,
                        return_buffer_updates=True)
                    return loss, bufs

                lfn = _recompute.checkpoint(loss_of) if self._remat else loss_of
                (loss, bufs), g = jax.value_and_grad(lfn, has_aux=True)(
                    {k: cur[k] for k in trainable})
                acc = {k: acc[k] + g[k].astype(jnp.float32) for k in acc}
                nxt = dict(cur)
                nxt.update(bufs)
                return (nxt, acc), loss

            (cur, acc), losses = jax.lax.scan(
                micro, (dict(params), zero), (split, jnp.arange(accum)))
            grads = {k: (acc[k] / accum).astype(params[k].dtype)
                     for k in acc}
            return jnp.mean(losses), grads, cur

        def step(params, opt_state, step_no, lr, rng_key, batch):
            if accum > 1 and counted:
                raise NotImplementedError(
                    "TrainStep(accum_steps>1) does not carry a model's "
                    "train_count_names out of its micro-batches")
            if accum > 1:
                loss, grads, carried = accum_grads(
                    params, step_no, lr, rng_key, batch)
                outs, bufs = (), {k: v for k, v in carried.items()
                                  if k not in trainable}
            else:
                def loss_of(train_params):
                    full = dict(params)
                    full.update(train_params)
                    loss, outs, bufs = forward_loss(
                        self.model, self.loss_fn, full, batch, rng_key,
                        self.amp_level, self.amp_dtype,
                        return_outputs=with_outputs or counted,
                        return_buffer_updates=True)
                    return loss, (outs, bufs)

                train_params = {k: v for k, v in params.items()
                                if k in trainable}
                loss_fn = _recompute.checkpoint(loss_of) if self._remat else loss_of
                (loss, (outs, bufs)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(train_params)
                if counted:
                    # the model's counts, its last output, leave beside the
                    # loss; its other outputs only where asked for
                    counts = outs[-1]
                    outs = outs if with_outputs else ()
            # trace-time gated: identity (zero compiled ops) unless armed
            grads = _faults.poison_grads(grads, step_no)
            new_params, new_opt = apply_updates(
                opt, params, grads, opt_state, lr, step_no, decay)
            # running-stat (buffer) updates captured in the traced forward
            # ride the same compiled step — no eager _set_data round-trip
            new_params.update(bufs)
            tail = (counts,) if counted else ()
            if guard:
                new_params, new_opt, gnorm, ok = guard_select(
                    params, opt_state, new_params, new_opt, loss, grads)
                return (new_params, new_opt, loss, outs, gnorm, ok) + tail
            return (new_params, new_opt, loss, outs) + tail

        def step_sparse(params, opt_state, step_no, lr, rng_key, batch):
            from ..core import selected_rows as sr
            zeros = {k: jnp.zeros((n, w), dt)
                     for k, (n, w, dt) in sparse_specs.items()}

            def loss_of(train_params, zvals):
                full = dict(params)
                full.update(train_params)
                ctx = sr.SparseGradContext("apply", zeros=zvals,
                                           deny=self._sparse_deny)
                with sr.use_ctx(ctx):
                    loss, outs, bufs = forward_loss(
                        self.model, self.loss_fn, full, batch, rng_key,
                        self.amp_level, self.amp_dtype,
                        return_outputs=with_outputs,
                        return_buffer_updates=True)
                return loss, (ctx.ids, outs, bufs)

            train_params = {k: v for k, v in params.items()
                            if k in trainable and k not in sparse_names}
            loss_fn = _recompute.checkpoint(loss_of) if self._remat else loss_of
            (loss, (ids, outs, bufs)), (grads, zgrads) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(train_params, zeros)
            grads = self._merge_sparse_grads(grads, zgrads, ids, params,
                                             name_to_key)
            grads = _faults.poison_grads(grads, step_no)
            new_params, new_opt = apply_updates(
                opt, params, grads, opt_state, lr, step_no, decay,
                row_shard=self._row_shard)
            new_params.update(bufs)
            if guard:
                new_params, new_opt, gnorm, ok = guard_select(
                    params, opt_state, new_params, new_opt, loss, grads)
                return new_params, new_opt, loss, outs, gnorm, ok
            return new_params, new_opt, loss, outs

        from ..observability import track
        return track(f"train_step:{type(self.model).__name__}",
                     jax.jit(step_sparse if sparse_specs else step,
                             donate_argnums=(0, 1)))

    def init_opt_state(self, state):
        return {k: self.optimizer.init_state(v) for k, v in state.items()
                if k in self._trainable}

    def _build_multi(self):
        """K optimizer steps per compiled call via lax.scan over stacked
        batches (leaves shaped (K, ...)).

        The TPU-native analogue of the reference's dataset trainers running
        the train loop inside the C++ executor (train_from_dataset,
        framework/trainer.h): host round-trips per step become one dispatch
        per K steps.  lr is held constant within a call (schedulers advance
        between calls)."""
        from ..optimizer.functional import apply_updates, decay_flags
        opt = self.optimizer
        trainable = self._trainable
        decay = decay_flags(opt, trainable)

        def multi(params, opt_state, step_no0, lr, rng_key, stacked):
            def body(carry, xs):
                params, opt_state, i = carry
                key = jax.random.fold_in(rng_key, i)

                def loss_of(train_params):
                    full = dict(params)
                    full.update(train_params)
                    loss, _outs, bufs = forward_loss(
                        self.model, self.loss_fn, full, xs, key,
                        self.amp_level, self.amp_dtype,
                        return_buffer_updates=True)
                    return loss, bufs

                train_params = {k: v for k, v in params.items()
                                if k in trainable}
                loss_fn = (_recompute.checkpoint(loss_of) if self._remat
                           else loss_of)
                (loss, bufs), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(train_params)
                from ..utils import faults as _faults
                grads = _faults.poison_grads(grads, step_no0 + i)
                new_params, new_opt = apply_updates(
                    opt, params, grads, opt_state, lr, step_no0 + i, decay)
                new_params.update(bufs)
                return (new_params, new_opt, i + 1), loss

            (params, opt_state, _), losses = jax.lax.scan(
                body, (params, opt_state, jnp.int32(0)), stacked)
            return params, opt_state, losses

        from ..observability import track
        return track(f"train_step_multi:{type(self.model).__name__}",
                     jax.jit(multi, donate_argnums=(0, 1)))

    def _build_multi_sparse(self, example_state, example_batch_one):
        """K sparse-grad steps per compiled call: the same zeros-cotangent
        channel as the single-step sparse build, inside the lax.scan body —
        each step's RowSparseGrad feeds the lazy row-wise optimizer update,
        so the big-vocab path gets the same per-call amortization as dense
        (r3 weak #4: run_steps used to reject sparse)."""
        from ..optimizer.functional import apply_updates, decay_flags
        from ..core import selected_rows as sr
        opt = self.optimizer
        trainable = self._trainable
        decay = decay_flags(opt, trainable)

        sparse_specs, name_to_key, sparse_names = self._sparse_setup(
            example_state, example_batch_one)

        def multi(params, opt_state, step_no0, lr, rng_key, stacked):
            def body(carry, xs):
                params, opt_state, i = carry
                key = jax.random.fold_in(rng_key, i)
                zeros = {k: jnp.zeros((n, w), dt)
                         for k, (n, w, dt) in sparse_specs.items()}

                def loss_of(train_params, zvals):
                    full = dict(params)
                    full.update(train_params)
                    ctx = sr.SparseGradContext("apply", zeros=zvals,
                                               deny=self._sparse_deny)
                    with sr.use_ctx(ctx):
                        loss, _outs, bufs = forward_loss(
                            self.model, self.loss_fn, full, xs, key,
                            self.amp_level, self.amp_dtype,
                            return_buffer_updates=True)
                    return loss, (ctx.ids, bufs)

                train_params = {k: v for k, v in params.items()
                                if k in trainable and k not in sparse_names}
                loss_fn = (_recompute.checkpoint(loss_of) if self._remat
                           else loss_of)
                (loss, (ids, bufs)), (grads, zgrads) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True)(train_params,
                                                           zeros)
                grads = self._merge_sparse_grads(grads, zgrads, ids, params,
                                                 name_to_key)
                from ..utils import faults as _faults
                grads = _faults.poison_grads(grads, step_no0 + i)
                new_params, new_opt = apply_updates(
                    opt, params, grads, opt_state, lr, step_no0 + i, decay,
                    row_shard=self._row_shard)
                new_params.update(bufs)
                return (new_params, new_opt, i + 1), loss

            (params, opt_state, _), losses = jax.lax.scan(
                body, (params, opt_state, jnp.int32(0)), stacked)
            return params, opt_state, losses

        from ..observability import track
        return track(f"train_step_multi:{type(self.model).__name__}",
                     jax.jit(multi, donate_argnums=(0, 1)))

    def run_steps(self, *stacked_batch):
        """Run K train steps in ONE compiled call.

        Each arg is a stacked batch whose leading axis K is the step count
        (e.g. ids of shape (K, batch, seq)).  Returns the (K,) per-step loss
        array.  Works with Embedding(sparse=True): lookup counts are baked
        per batch-shape signature, so each signature compiles its own
        multi-step program."""
        if self._guard:
            raise NotImplementedError(
                "TrainStep(guard=True) does not support run_steps: the "
                "multi-step scan has no per-step skip/rollback point (a "
                "silent bypass would apply NaN updates the guard promised "
                "to block) — use per-call steps under the guard")
        if self.accum_steps > 1:
            raise NotImplementedError(
                "TrainStep(accum_steps>1) does not support run_steps: the "
                "accumulation window already scans in-program — stack "
                "whole windows as per-call batches instead")
        state = state_arrays(self.model)
        if self._opt_state is None:
            self._opt_state = self.init_opt_state(state)
        raw = tuple(unwrap(b) for b in stacked_batch)
        k_steps = raw[0].shape[0]
        if self._sparse:
            sig = ("multi",) + tuple(
                (tuple(b.shape), str(b.dtype)) for b in raw)
            self._compiled_multi = self._sig_cache.get(sig)
            if self._compiled_multi is None:
                one = tuple(b[0] for b in raw)
                self._compiled_multi = self._sig_cache[sig] = \
                    self._build_multi_sparse(state, one)
        if self._compiled_multi is None:
            self._compiled_multi = self._build_multi()
        state, self._opt_state, raw = self._place_for_row_shard(
            state, self._opt_state, raw)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_no0 = jnp.asarray(self.optimizer._step_count + 1, jnp.int32)
        from ..core import rng as _rng
        rng_key = _rng.next_key()
        new_state, self._opt_state, losses = self._compiled_multi(
            state, self._opt_state, step_no0, lr, rng_key, raw)
        self.optimizer._step_count += k_steps
        sd = self.model.state_dict()
        for k, v in new_state.items():
            sd[k]._set_data(v)
        return Tensor(losses)

    def _place_for_row_shard(self, state, opt_state, raw_batch):
        """With a mesh row-sharded table among the params, every input of
        the compiled step must live on the mesh's device set (the per-shard
        update is a shard_map): replicate anything not already there.  The
        sharded table (and, after the first step, its moments) keeps its
        row sharding — device_put is skipped for leaves already on the
        mesh."""
        if not self._row_shard:
            return state, opt_state, raw_batch
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = next(iter(self._row_shard.values()))[1]
        rep = NamedSharding(mesh, P())

        def place(x):
            s = getattr(x, "sharding", None)
            if s is not None and getattr(s, "device_set", None) \
                    == rep.device_set:
                return x
            return jax.device_put(x, rep)

        return (jax.tree_util.tree_map(place, state),
                jax.tree_util.tree_map(place, opt_state),
                jax.tree_util.tree_map(place, raw_batch))

    def _ensure_compiled(self, state, batch):
        """Resolve the compiled step for this batch signature (the sparse
        path keys per shape) — shared by __call__ and warmup()."""
        if self._sparse:
            # sparse lookup counts are baked into the compiled step, so
            # each batch-shape signature needs its own build (the dense
            # path just lets jax.jit retrace)
            sig = tuple((tuple(unwrap(b).shape), str(unwrap(b).dtype))
                        for b in batch)
            self._compiled = self._sig_cache.get(sig)
            if self._compiled is None:
                self._compiled = self._sig_cache[sig] = self._build(
                    state, self._opt_state, batch)
        if self._compiled is None:
            self._compiled = self._build(state, self._opt_state, batch)
        return self._compiled

    def warmup(self, *batch) -> dict:
        """AOT-compile the step for this sample batch WITHOUT applying an
        update: params, optimizer state, BN stats and the RNG stream are
        untouched — the training analogue of `ServingEngine.warmup()`,
        so a fleet worker (or a resumed preemption victim) pays its
        compile before the first real batch instead of inside it.  With
        the persistent program store enabled (PDTPU_PROGRAM_CACHE_DIR),
        warmup in one process makes every other process's first step a
        disk hit.  Returns {'seconds', 'compiled'} — compiled=False
        means the signature was already warm (or the build is not
        AOT-compilable; the first real call then compiles normally)."""
        import time as _time
        t0 = _time.perf_counter()
        state = state_arrays(self.model)
        if self._opt_state is None:
            self._opt_state = self.init_opt_state(state)
        compiled_fn = self._ensure_compiled(state, batch)
        raw_batch = tuple(unwrap(b) for b in batch)
        state, self._opt_state, raw_batch = self._place_for_row_shard(
            state, self._opt_state, raw_batch)
        did = warm_step_program(compiled_fn, state, self._opt_state,
                                self.optimizer, raw_batch)
        return {"seconds": _time.perf_counter() - t0, "compiled": did}

    def __call__(self, *batch):
        with _span("train_step",
                   args={"step": self.optimizer._step_count + 1}) as sp, \
                _step_hist().time():
            loss = self._call_inner(*batch)
            if self._count_names is not None:
                _defer_args(sp, self._count_names, self.last_counts)
            return loss

    def _call_inner(self, *batch):
        with _span("train_step_gather_state"):
            state = state_arrays(self.model)
            if self._opt_state is None:
                self._opt_state = self.init_opt_state(state)
        with _span("train_step_dispatch"):
            self._ensure_compiled(state, batch)
            self.optimizer._step_count += 1
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            step_no = jnp.asarray(self.optimizer._step_count, jnp.int32)
            from ..core import rng as _rng
            rng_key = _rng.next_key()  # fresh per step: dropout masks differ
            raw_batch = tuple(unwrap(b) for b in batch)
            # after the build, which probes with the state as the model
            # holds it; a no-op unless a table is row-sharded over a mesh
            state, self._opt_state, raw_batch = self._place_for_row_shard(
                state, self._opt_state, raw_batch)
            out = self._compiled(
                state, self._opt_state, step_no, lr, rng_key, raw_batch)
        with _span("train_step_write_back"):
            if self._count_names is not None:
                *out, self.last_counts = out
            if self._guard:
                new_state, self._opt_state, loss, outs, gnorm, ok = out
                self.last_guard = (gnorm, ok)
            else:
                new_state, self._opt_state, loss, outs = out
            self.last_outputs = (tuple(Tensor(o) for o in outs)
                                 if outs else None)
            sd = self.model.state_dict()
            for k, v in new_state.items():
                sd[k]._set_data(v)
        return Tensor(loss)

    # -- checkpointing (single-device variant of ShardedTrainStep's) ---------
    def save_checkpoint(self, directory, step=None, extra_meta=None,
                        scaler=None, data_cursor=None):
        from ..distributed import checkpoint as dck
        state = state_arrays(self.model)
        if self._opt_state is None:
            self._opt_state = self.init_opt_state(state)
        if self.accum_steps > 1:
            # record the window structure: a resumed run must feed the
            # same accum_steps for the rng fold_in stream to line up
            extra_meta = dict(extra_meta or {})
            extra_meta.setdefault("accum_steps", self.accum_steps)
        return dck.save_train_state(
            directory, state, self._opt_state,
            step if step is not None else self.optimizer._step_count,
            extra_meta, optimizer=self.optimizer, scaler=scaler,
            data_cursor=data_cursor)

    def restore_checkpoint(self, directory, scaler=None):
        from ..distributed import checkpoint as dck
        res = dck.restore_sharded(directory)
        if res is None:
            return None
        meta, restored_opt = dck.apply_train_state(
            self.model, self.optimizer, res, scaler=scaler)
        fresh = self.init_opt_state(state_arrays(self.model))
        self._opt_state = dck.merge_opt_state(fresh, restored_opt)
        return meta


# ---------------------------------------------------------------------------
# save / load (inference model): AOT export via jax.export + weights pickle
# ---------------------------------------------------------------------------

def _relevant_op_versions(layer):
    """Version entries for op families this layer tree actually exercises
    (reference: op_version_registry records versions per op IN the saved
    program; embedding the full registry would make unrelated version
    bumps reject artifacts that never use the bumped op)."""
    from ..utils import op_version
    relevant = {"exported_program"}
    for _, sub in getattr(layer, "named_sublayers", lambda: [])():
        name = type(sub).__name__
        if name in ("MultiHeadAttention", "TransformerEncoderLayer",
                    "TransformerDecoderLayer", "BertLayer", "GPTBlock",
                    "ErnieLayer"):
            relevant |= {"flash_attention", "scaled_dot_product_attention"}
        if name.startswith("Quanted") or name.startswith("Int8"):
            relevant.add("fake_quantize")
        if name.startswith("BatchNorm") or name == "SyncBatchNorm":
            # conv-net blocks route through the fused epilogue family
            relevant.add("fused_bn_act")
    snap = op_version.snapshot()
    return {k: v for k, v in snap.items() if k in relevant}


def save(layer, path, input_spec=None, **config):
    """paddle.jit.save — serialize compiled fn (StableHLO via jax.export) +
    weights (reference: save_inference_model, io.py:1198)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state = {k: np.asarray(v) for k, v in state_arrays(layer).items()}
    np.savez(path + ".pdiparams.npz", **state)
    from ..utils import op_version
    meta = {"class": type(layer).__name__, "input_spec": None,
            "op_versions": _relevant_op_versions(layer)}
    if input_spec is not None:
        layer.eval()
        from jax import export as jax_export

        # -1/None dims export as SYMBOLIC dimensions, named by AXIS
        # POSITION ("b" for dim 0, "d<j>" otherwise) and shared across
        # inputs — so (ids, mask) specs of (-1, -1) agree on batch AND
        # seq_len, the common paddle Program -1 pattern.  Inputs whose
        # same-position dynamic dims are genuinely independent would
        # over-constrain; pass concrete sizes for those.
        scope = jax_export.SymbolicScope()

        def to_sds(s):
            if not isinstance(s, InputSpec):
                return jax.ShapeDtypeStruct(tuple(s.shape), s.dtype)
            if all(d != -1 for d in s.shape):
                return s.to_shape_dtype()
            names = ",".join(
                ("b" if j == 0 else f"d{j}") if d == -1 else str(d)
                for j, d in enumerate(s.shape))
            sym = jax_export.symbolic_shape(names, scope=scope)
            return jax.ShapeDtypeStruct(sym, s.dtype)

        specs = [to_sds(s) for s in input_spec]

        def pure(state, *args):
            return functional_call(layer, state, *args, training=False)

        state_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in state.items()}
        try:
            try:
                exported = jax_export.export(jax.jit(pure))(state_sds, *specs)
            except Exception as sym_err:
                # model not shape-polymorphic: fall back to the concrete
                # export (every -1 becomes 1) rather than producing no
                # artifact — but say so, loudly and in the metadata
                import warnings
                warnings.warn(
                    "jit.save: symbolic-shape export failed "
                    f"({type(sym_err).__name__}); falling back to CONCRETE "
                    "shapes — the saved model only accepts the exact "
                    "fallback shapes (every -1 dim = 1)")
                meta["export_fallback"] = f"concrete: {sym_err}"[:500]
                specs = [s.to_shape_dtype() if isinstance(s, InputSpec)
                         else s for s in input_spec]
                exported = jax_export.export(jax.jit(pure))(state_sds, *specs)
            with open(path + ".pdmodel", "wb") as f:
                f.write(exported.serialize())
            meta["input_spec"] = [
                (tuple(int(d) if isinstance(d, int) else -1
                       for d in s.shape), str(np.dtype(s.dtype)))
                for s in specs]
        except Exception as e:  # export unsupported on some backends
            meta["export_error"] = str(e)
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer:
    """Loaded inference artifact (reference: TranslatedLayer / AnalysisPredictor)."""

    def __init__(self, exported, state, meta=None):
        self._exported = exported
        self._state = state
        self._meta = meta or {}

    def __call__(self, *args):
        raw = tuple(unwrap(a) for a in args)
        out = self._exported.call(self._state, *raw)
        return jax.tree_util.tree_map(lambda x: Tensor(x), out)

    def eval(self):
        return self

    def train(self):
        return self


def load(path, **config):
    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    from ..utils import op_version
    op_version.check_compat(meta.get("op_versions"),
                            strict=config.get("strict_op_versions", False))
    data = np.load(path + ".pdiparams.npz")
    state = {k: jnp.asarray(data[k]) for k in data.files}
    model_file = path + ".pdmodel"
    if os.path.exists(model_file):
        from jax import export as jax_export
        with open(model_file, "rb") as f:
            exported = jax_export.deserialize(f.read())
        return TranslatedLayer(exported, state, meta)
    raise FileNotFoundError(
        f"{model_file} not found — layer was saved without input_spec; "
        "load weights via paddle_tpu.load instead")


def enable_to_static(flag=True):
    pass


class ProgramTranslator:
    """API-compat shim for fluid's ProgramTranslator."""
    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, flag):
        pass


class TracedLayer:
    """Trace a dygraph Layer once into a compiled static callable
    (reference: fluid/dygraph/jit.py:1046 — a Program + Executor sharing
    the layer's parameters).  TPU-native: the "program" is the
    StaticFunction jit of the layer's forward; `save_inference_model`
    serializes the StableHLO artifact via `jit.save` with specs taken
    from the traced example inputs.

    Use `TracedLayer.trace(layer, inputs)`, not the constructor.
    """

    def __init__(self, layer, example_inputs, outputs):
        self._layer = layer
        self._static = StaticFunction(layer.forward, layer=layer)
        self._example = tuple(example_inputs)
        self._n_outputs = (len(outputs)
                           if isinstance(outputs, (list, tuple)) else 1)

    @staticmethod
    def trace(layer, inputs):
        """Returns (dygraph outputs, TracedLayer) like the reference."""
        ins = tuple(inputs)
        out = layer(*_wrap_args(ins))
        return out, TracedLayer(layer, ins, out)

    def __call__(self, inputs):
        """Run the compiled program on a LIST of inputs; returns the
        outputs as a list (the reference fetch-list convention)."""
        out = self._static(*inputs)
        return list(out) if isinstance(out, (list, tuple)) else [out]

    def set_strategy(self, build_strategy=None, exec_strategy=None):
        """No-op: XLA owns build/exec strategy (the reference attaches
        BuildStrategy/ExecutionStrategy to its CompiledProgram)."""

    def save_inference_model(self, path, feed=None, fetch=None, **config):
        specs = [InputSpec(tuple(unwrap(a).shape), str(unwrap(a).dtype))
                 for a in self._example]
        if feed is not None and sorted(feed) != list(range(len(specs))):
            raise NotImplementedError(
                "TracedLayer.save_inference_model: feed must cover all "
                "traced inputs (input subsetting would change the traced "
                "program)")
        if fetch is not None and sorted(fetch) != list(
                range(self._n_outputs)):
            raise NotImplementedError(
                "TracedLayer.save_inference_model: fetch must cover all "
                "traced outputs")
        save(self._layer, path, input_spec=specs, **config)


# dy2static debug logging (reference: fluid/dygraph/dygraph_to_static/
# logging_utils.py:182,221, re-exported from paddle.jit).  There is no
# source transform here (tracing IS program capture), so the knobs gate
# how loudly jit builds report: level >= 1 turns on jax compilation logs.
_VERBOSITY = 0
_CODE_LEVEL = -1
_PREV_JAX_LOG_LEVEL = None


def set_verbosity(level=0, also_to_stdout=False):
    global _VERBOSITY, _PREV_JAX_LOG_LEVEL
    import logging
    logger = logging.getLogger("jax")
    new = int(level)
    if new >= 1 and _VERBOSITY < 1:
        _PREV_JAX_LOG_LEVEL = logger.level  # restore on lowering
        logger.setLevel(logging.DEBUG)
    elif new < 1 and _VERBOSITY >= 1:
        # restore the exact saved level — 0 (NOTSET) is a valid level and
        # must round-trip, so test against None, not falsiness
        logger.setLevel(logging.WARNING if _PREV_JAX_LOG_LEVEL is None
                        else _PREV_JAX_LOG_LEVEL)
        _PREV_JAX_LOG_LEVEL = None
    _VERBOSITY = new


def get_verbosity():
    return _VERBOSITY


def set_code_level(level=100, also_to_stdout=False):
    global _CODE_LEVEL
    _CODE_LEVEL = int(level)


def get_code_level():
    return _CODE_LEVEL
