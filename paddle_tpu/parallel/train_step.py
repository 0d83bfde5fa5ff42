"""ShardedTrainStep — one compiled SPMD training step over a device mesh.

This single class replaces the reference's entire program-rewriting
parallelism stack (SURVEY.md §2.2 meta-optimizers):
- GraphExecutionOptimizer's inserted c_allreduce_sum per grad  → batch
  sharded P("dp"): XLA emits the gradient psum itself.
- ShardingOptimizer's param→rank broadcast/allreduce rewrite
  (sharding_optimizer.py:96-118)                               → FSDP
  PartitionSpecs on params/opt states; GSPMD inserts all-gather /
  reduce-scatter.
- Megatron-style TP (absent in reference, free on TPU)         → column/row
  PartitionSpecs from parallel.sharding.
- RecomputeOptimizer (backward.py:689)                         → jax.checkpoint.
- GradientMergeOptimizer (optimizer.py:4969)                   → lax.scan over
  microbatches accumulating grads.
- AMP meta-optimizer                                           → bf16 autocast
  inside the jitted step.
All of it is one jax.jit with in/out shardings + donation.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import recompute as _recompute
from ..core.tensor import Tensor, unwrap
from ..jit import functional_call, state_arrays
from ..nn.layer_base import Layer
from ..observability.tracer import span as _span
from . import sharding as shd
from .mesh import get_mesh
from .strategy import DistributedStrategy


class ShardedTrainStep:
    """step(*batch) -> loss; params/opt states live sharded on the mesh."""

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 strategy: Optional[DistributedStrategy] = None,
                 mesh: Optional[Mesh] = None,
                 batch_spec=None, guard: bool = False,
                 accum_steps: int = 1):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # compiled finiteness guard (see jit.guard_select): bad steps are
        # skipped on-device; (grad_norm, ok) ride out on last_guard
        self._guard = bool(guard)
        self.last_guard = None
        self.strategy = strategy or DistributedStrategy()
        self.mesh = mesh or get_mesh(create_default=True)
        st = self.strategy
        self._remat = st.recompute
        self._amp = st.amp
        self._amp_dtype = st.amp_configs.dtype
        self._k_steps = (st.gradient_merge_configs.k_steps
                         if st.gradient_merge else 1)
        # accum_steps: the TrainStep-shaped spelling of the gradient-merge
        # meta-optimizer — K microbatches scanned in-program with f32
        # accumulators and one update (same knob, friendlier name)
        if int(accum_steps) < 1:
            raise ValueError("ShardedTrainStep: accum_steps must be >= 1")
        if int(accum_steps) > 1:
            if st.gradient_merge and self._k_steps != int(accum_steps):
                raise ValueError(
                    "ShardedTrainStep: accum_steps and "
                    "strategy.gradient_merge_configs.k_steps disagree "
                    f"({accum_steps} vs {self._k_steps})")
            self._k_steps = int(accum_steps)
        self.accum_steps = self._k_steps
        sd = model.state_dict()
        self._trainable = {k for k, v in sd.items()
                           if getattr(v, "trainable", False)}
        fsdp = st.sharding and st.sharding_configs.stage >= 3
        self._zero12 = st.sharding and st.sharding_configs.stage in (1, 2)
        # bf16-compressed explicit gradient allreduce: pure-DP only (the
        # reference's fp16_allreduce likewise composes with collective DP,
        # not sharding/TP)
        self._fp16_allreduce = bool(st.fp16_allreduce)
        if self._fp16_allreduce and (fsdp or st.tensor_parallel
                                     or st.sequence_parallel or st.pipeline):
            raise ValueError(
                "fp16_allreduce composes with plain DP (optionally ZeRO-1/2)"
                " only — disable sharding stage 3 / tensor_parallel /"
                " sequence_parallel / pipeline")
        self.param_specs = shd.param_specs(
            {k: tuple(v.shape) for k, v in sd.items()}, self.mesh,
            tensor_parallel=st.tensor_parallel, fsdp=fsdp,
            custom_rule=st.sharding_rule,
            expert_parallel=st.expert_parallel)
        self.param_shardings = shd.shardings_of(self.param_specs, self.mesh)
        # batch elements shard over dp on axis 0 (+ sp on seq axis 1 when
        # sequence parallel)
        if batch_spec is None:
            batch_spec = (P("dp", "sp") if st.sequence_parallel else P("dp"))
        self._batch_sharding = NamedSharding(self.mesh, batch_spec)
        self._compiled = None
        self._opt_state = None
        self._placed = False

    # -- placement -----------------------------------------------------------
    def place_params(self):
        """Move model params onto the mesh with their shardings (the analogue
        of ParallelExecutor::BCastParamsToDevices, parallel_executor.cc:637)."""
        sd = self.model.state_dict()
        for k, t in sd.items():
            t._set_data(jax.device_put(t._data, self.param_shardings[k]))
        self._placed = True

    def _opt_shardings(self, opt_state):
        sd = self.model.state_dict()
        out = {}
        for k, st in opt_state.items():
            pshard = self.param_shardings[k]
            pshape = tuple(sd[k].shape)
            if self._zero12:
                # ZeRO-1/2: moments sharded over dp even though params
                # aren't (the ShardingOptimizer memory win)
                mesh = self.mesh
                spec = shd.apply_fsdp(self.param_specs[k], pshape, mesh)
                pshard = NamedSharding(mesh, spec if spec is not None else P())
            out[k] = {n: shd.state_sharding_like(pshape, pshard, leaf)
                      for n, leaf in st.items()}
        return out

    # -- compiled step -------------------------------------------------------
    def _forward_loss(self, state, batch, rng_key=None):
        # NOTE: no return_buffer_updates here — BatchNorm running stats
        # stay frozen under the SHARDED step (per-replica batch stats
        # would need a cross-replica mean, the SyncBatchNorm contract;
        # single-device TrainStep folds them functionally since ISSUE 1)
        from ..jit import forward_loss
        return forward_loss(self.model, self.loss_fn, state, batch, rng_key,
                            "O1" if self._amp else None, self._amp_dtype)

    def _build(self, opt_shardings):
        from ..optimizer.functional import apply_updates, decay_flags
        opt = self.optimizer
        trainable = self._trainable
        decay = decay_flags(opt, trainable)
        k_steps = self._k_steps
        avg = (self.strategy.gradient_merge_configs.avg
               if self.strategy.gradient_merge else True)

        def grads_of_implicit(params, batch, rng_key):
            def loss_of(tp):
                full = dict(params)
                full.update(tp)
                return self._forward_loss(full, batch, rng_key)
            train_params = {k: v for k, v in params.items() if k in trainable}
            fn = _recompute.checkpoint(loss_of) if self._remat else loss_of
            return jax.value_and_grad(fn)(train_params)

        def grads_of_explicit(params, batch, rng_key):
            """Per-replica local grads via shard_map, a dtype-compressed
            explicit pmean over dp (fp16_allreduce meta-optimizer,
            fp16_allreduce_optimizer.py:1; bf16 is the TPU wire format).

            DDP semantics like the reference's collective mode: gradients
            are AVERAGED across replicas.  For mean-reduced losses this is
            identical to the implicit global-loss gradient; a sum-reduced
            loss differs by a factor of dp (exactly as it would under the
            reference's scaled-loss + allreduce)."""

            def local(params, batch):
                key = jax.random.fold_in(rng_key,
                                         jax.lax.axis_index("dp"))

                def loss_of(tp):
                    full = dict(params)
                    full.update(tp)
                    return self._forward_loss(full, batch, key)
                tp0 = {k: v for k, v in params.items() if k in trainable}
                fn = _recompute.checkpoint(loss_of) if self._remat else loss_of
                loss, g = jax.value_and_grad(fn)(tp0)
                g = jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(
                        x.astype(jnp.bfloat16), "dp").astype(jnp.float32),
                    g)
                return jax.lax.pmean(loss, "dp"), g

            return jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), tuple(P("dp") for _ in batch)),
                out_specs=(P(), P()), check_vma=False)(params, batch)

        grads_of = (grads_of_explicit if self._fp16_allreduce
                    else grads_of_implicit)

        guard = self._guard
        from ..utils import faults as _faults

        def step(params, opt_state, step_no, lr, rng_key, batch):
            if k_steps > 1:
                # gradient merge: split batch into k microbatches, scan
                def micro(carry, mb_and_i):
                    mb, i = mb_and_i
                    acc, _ = carry
                    loss, g = grads_of(params, mb,
                                       jax.random.fold_in(rng_key, i))
                    acc = jax.tree_util.tree_map(jnp.add, acc, g)
                    return (acc, loss), None
                split = tuple(
                    b.reshape((k_steps, b.shape[0] // k_steps) + b.shape[1:])
                    for b in batch)
                zero = {k: jnp.zeros(params[k].shape, jnp.float32)
                        for k in trainable}
                (grads, loss), _ = jax.lax.scan(
                    micro, (zero, jnp.zeros((), jnp.float32)),
                    (split, jnp.arange(k_steps)))
                if avg:
                    grads = jax.tree_util.tree_map(
                        lambda g: g / k_steps, grads)
            else:
                loss, grads = grads_of(params, batch, rng_key)
            # trace-time gated fault injection: identity unless armed
            grads = _faults.poison_grads(grads, step_no)
            new_params, new_opt = apply_updates(
                opt, params, grads, opt_state, lr, step_no, decay)
            if guard:
                from ..jit import guard_select
                new_params, new_opt, gnorm, ok = guard_select(
                    params, opt_state, new_params, new_opt, loss, grads)
                return new_params, new_opt, loss, gnorm, ok
            return new_params, new_opt, loss

        n_batch = self._n_batch
        in_shardings = (self.param_shardings, opt_shardings, None, None, None,
                        (self._batch_sharding,) * n_batch)
        out_shardings = (self.param_shardings, opt_shardings, None)
        if guard:
            out_shardings += (None, None)
        from ..observability import track
        return track(f"sharded_train_step:{type(self.model).__name__}",
                     jax.jit(step, in_shardings=in_shardings,
                             out_shardings=out_shardings,
                             donate_argnums=(0, 1)))

    def init_opt_state(self, state):
        return {k: self.optimizer.init_state(v) for k, v in state.items()
                if k in self._trainable}

    def _ensure_opt_shardings(self):
        """Derive optimizer-state shardings from shapes only (eval_shape) —
        no throwaway device allocation on the restore path."""
        if getattr(self, "_opt_state_shardings", None) is None:
            state = state_arrays(self.model)
            shapes = jax.eval_shape(self.init_opt_state, state)
            self._opt_state_shardings = self._opt_shardings(shapes)
        return self._opt_state_shardings

    def warmup(self, *batch) -> dict:
        """AOT-compile the sharded step for this sample batch WITHOUT
        applying an update (mirrors `TrainStep.warmup`): params are
        placed on the mesh, the optimizer state is materialized, the
        step is built and compiled — but no gradients flow, no state
        changes, and the RNG stream is not consumed.  With the
        persistent program store enabled, one worker's warmup makes the
        whole fleet's first step a disk hit."""
        import time as _time
        t0 = _time.perf_counter()
        if not self._placed:
            self.place_params()
        state = state_arrays(self.model)
        if self._opt_state is None:
            raw = self.init_opt_state(state)
            shardings = self._ensure_opt_shardings()
            self._opt_state = jax.device_put(raw, shardings)
        if self._compiled is None:
            self._n_batch = len(batch)
            self._compiled = self._build(self._opt_state_shardings)
        raw_batch = tuple(jax.device_put(unwrap(b), self._batch_sharding)
                          for b in batch)
        from ..jit import warm_step_program
        with jax.set_mesh(self.mesh):
            did = warm_step_program(self._compiled, state, self._opt_state,
                                    self.optimizer, raw_batch)
        return {"seconds": _time.perf_counter() - t0, "compiled": did}

    def __call__(self, *batch):
        from ..jit import _step_hist
        # the phases carry TrainStep's names: one reduction and one set of
        # metrics read both steps (the program registry name stays)
        with _span("train_step",
                   args={"step": self.optimizer._step_count + 1}), \
                _step_hist().time():
            return self._call_inner(*batch)

    def _call_inner(self, *batch):
        with _span("train_step_gather_state"):
            if not self._placed:
                self.place_params()
            state = state_arrays(self.model)
            if self._opt_state is None:
                raw = self.init_opt_state(state)
                shardings = self._ensure_opt_shardings()
                self._opt_state = jax.device_put(raw, shardings)
        with _span("train_step_dispatch"):
            if self._compiled is None:
                self._n_batch = len(batch)
                self._compiled = self._build(self._opt_state_shardings)
            self.optimizer._step_count += 1
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            step_no = jnp.asarray(self.optimizer._step_count, jnp.int32)
            from ..core import rng as _rng
            rng_key = _rng.next_key()
            raw_batch = tuple(
                jax.device_put(unwrap(b), self._batch_sharding)
                for b in batch)
            # traced under jax's own mesh context: a pallas kernel in the
            # step reads it to run per shard (GSPMD cannot partition one)
            with jax.set_mesh(self.mesh):
                out = self._compiled(
                    state, self._opt_state, step_no, lr, rng_key, raw_batch)
        with _span("train_step_write_back"):
            if self._guard:
                new_state, self._opt_state, loss, gnorm, ok = out
                self.last_guard = (gnorm, ok)
            else:
                new_state, self._opt_state, loss = out
            sd = self.model.state_dict()
            for k, v in new_state.items():
                sd[k]._set_data(v)
        return Tensor(loss)

    # -- checkpointing -------------------------------------------------------
    def save_checkpoint(self, directory: str, step: Optional[int] = None,
                        extra_meta: Optional[dict] = None,
                        scaler=None, data_cursor=None) -> str:
        """Snapshot sharded params + optimizer state without host gather
        (each process writes only its own shards).  `scaler` adds the
        GradScaler loss-scaling state to the extras so an AMP resume does
        not restart dynamic loss scaling from init; `data_cursor` records
        the data-iterator position."""
        from ..distributed import checkpoint as dck
        if not self._placed:
            self.place_params()
        state = state_arrays(self.model)
        if self._opt_state is None:
            self._opt_state = jax.device_put(self.init_opt_state(state),
                                             self._ensure_opt_shardings())
        if self._k_steps > 1:
            extra_meta = dict(extra_meta or {})
            extra_meta.setdefault("accum_steps", self._k_steps)
        return dck.save_train_state(
            directory, state, self._opt_state,
            step if step is not None else self.optimizer._step_count,
            extra_meta, optimizer=self.optimizer, scaler=scaler,
            data_cursor=data_cursor)

    def restore_checkpoint(self, directory: str,
                           scaler=None) -> Optional[dict]:
        """Restore the newest checkpoint onto this step's shardings; resumes
        the optimizer step count + rng stream (+ GradScaler state when
        `scaler` is given). Returns meta or None."""
        from ..distributed import checkpoint as dck
        if not self._placed:
            self.place_params()
        res = dck.restore_sharded(
            directory, mesh=self.mesh,
            shardings={"params": self.param_shardings,
                       "opt": self._ensure_opt_shardings()})
        if res is None:
            return None
        meta, restored_opt = dck.apply_train_state(
            self.model, self.optimizer, res, scaler=scaler)
        fresh = jax.device_put(
            self.init_opt_state(state_arrays(self.model)),
            self._ensure_opt_shardings())
        self._opt_state = dck.merge_opt_state(fresh, restored_opt)
        return meta

    # -- introspection -------------------------------------------------------
    def describe_shardings(self) -> Dict[str, str]:
        return {k: str(v) for k, v in self.param_specs.items()}
