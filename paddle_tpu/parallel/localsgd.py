"""LocalSGD training step.

Reference: python/paddle/distributed/fleet/meta_optimizers/localsgd_optimizer.py:1
— every worker applies its LOCAL gradient for k_steps steps (no allreduce),
then workers synchronize by averaging parameters.  Cuts collective traffic by
k at the price of staleness; with SGD and k=1 it is mathematically identical
to synchronous data parallelism.

TPU-native design: each dp replica's divergent weights are one slice of a
leading replica axis — every param is stored stacked as (dp, *shape) sharded
P("dp"), so "a worker's copy" is just its device's shard.  The local step
runs inside shard_map (no implicit GSPMD gradient reduction can happen), and
the periodic sync is a single fused pmean over the stacked axis.  The
adaptive variant (begin syncing every step once k_steps decays) can be had
by passing k_steps=1.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor, unwrap
from ..jit import state_arrays, forward_loss
from .mesh import get_mesh


class LocalSGDTrainStep:
    """step(*batch) -> mean loss across replicas.

    Params live stacked (dp, *shape); `sync()` (called automatically every
    k_steps) averages them across replicas.  `model.state_dict()` is kept
    holding replica 0's view after every call so eval code sees one model.
    """

    def __init__(self, model, loss_fn: Callable, optimizer, k_steps: int = 4,
                 mesh: Optional[Mesh] = None, amp_level=None,
                 amp_dtype="bfloat16"):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.k_steps = int(k_steps)
        self.mesh = mesh or get_mesh(create_default=True)
        self.dp = self.mesh.shape["dp"]
        self._amp = amp_level
        self._amp_dtype = amp_dtype
        sd = model.state_dict()
        self._trainable = {k for k, v in sd.items()
                           if getattr(v, "trainable", False)}
        self._stack_sharding = NamedSharding(self.mesh, P("dp"))
        self._batch_sharding = NamedSharding(self.mesh, P("dp"))
        self._stacked = None     # name -> (dp, *shape)
        self._opt_state = None
        self._compiled = None
        self._since_sync = 0

    # -- placement -----------------------------------------------------------
    def _place(self):
        state = state_arrays(self.model)
        self._stacked = {
            k: jax.device_put(jnp.broadcast_to(v, (self.dp,) + v.shape),
                              self._stack_sharding)
            for k, v in state.items()}
        self._opt_state = {
            k: jax.tree_util.tree_map(
                lambda s: jax.device_put(
                    jnp.broadcast_to(s, (self.dp,) + s.shape),
                    self._stack_sharding),
                self.optimizer.init_state(state[k]))
            for k in self._trainable}

    # -- compiled local step -------------------------------------------------
    def _build(self, n_batch):
        from ..optimizer.functional import apply_updates, decay_flags
        opt = self.optimizer
        trainable = self._trainable
        decay = decay_flags(opt, trainable)
        mesh = self.mesh

        def local(params, opt_state, step_no, lr, rng_key, batch):
            # one replica's view: drop the stacked axis
            params = {k: v[0] for k, v in params.items()}
            opt_state = jax.tree_util.tree_map(lambda s: s[0], opt_state)
            key = jax.random.fold_in(rng_key, jax.lax.axis_index("dp"))

            def loss_of(tp):
                full = dict(params)
                full.update(tp)
                return forward_loss(self.model, self.loss_fn, full, batch,
                                    key, self._amp, self._amp_dtype)

            tp = {k: v for k, v in params.items() if k in trainable}
            loss, grads = jax.value_and_grad(loss_of)(tp)
            new_params, new_opt = apply_updates(
                opt, params, grads, opt_state, lr, step_no, decay)
            new_params = {k: v[None] for k, v in new_params.items()}
            new_opt = jax.tree_util.tree_map(lambda s: s[None], new_opt)
            return new_params, new_opt, jax.lax.pmean(loss, "dp")

        step = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P(), P(), P(),
                      tuple(P("dp") for _ in range(n_batch))),
            out_specs=(P("dp"), P("dp"), P()),
            check_vma=False)
        return jax.jit(step, donate_argnums=(0, 1))

    def _build_sync(self):
        def sync(stacked, opt_state):
            avg = {k: jnp.mean(v.astype(jnp.float32), axis=0,
                               keepdims=True).astype(v.dtype)
                   for k, v in stacked.items()}
            avg = {k: jnp.broadcast_to(v, stacked[k].shape)
                   for k, v in avg.items()}
            return avg, opt_state
        return jax.jit(sync, donate_argnums=(0,),
                       out_shardings=(self._stack_sharding, None))

    def sync(self):
        """Average parameters across replicas (the LocalSGD allreduce) and
        refresh the model's tensors with the synced weights."""
        if self._stacked is None:
            return
        if getattr(self, "_compiled_sync", None) is None:
            self._compiled_sync = self._build_sync()
        self._stacked, self._opt_state = self._compiled_sync(
            self._stacked, self._opt_state)
        self._since_sync = 0
        # eval view refreshed only at sync points: between syncs replicas
        # legitimately diverge and a per-step slice copy would be waste
        sd = self.model.state_dict()
        for k, v in self._stacked.items():
            sd[k]._set_data(v[0])

    def __call__(self, *batch):
        if self._stacked is None:
            self._place()
        if self._compiled is None:
            self._compiled = self._build(len(batch))
        self.optimizer._step_count += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_no = jnp.asarray(self.optimizer._step_count, jnp.int32)
        from ..core import rng as _rng
        key = _rng.next_key()
        raw = tuple(jax.device_put(unwrap(b), self._batch_sharding)
                    for b in batch)
        self._stacked, self._opt_state, loss = self._compiled(
            self._stacked, self._opt_state, step_no, lr, key, raw)
        self._since_sync += 1
        if self._since_sync >= self.k_steps:
            self.sync()
        return Tensor(loss)

    # -- checkpointing (same layout as ShardedTrainStep's) -------------------
    def save_checkpoint(self, directory: str, step=None, extra_meta=None):
        from ..distributed import checkpoint as dck
        if self._stacked is None:
            self._place()
        return dck.save_train_state(
            directory, self._stacked, self._opt_state,
            step if step is not None else self.optimizer._step_count,
            extra_meta, optimizer=self.optimizer)

    def restore_checkpoint(self, directory: str):
        from ..distributed import checkpoint as dck
        if self._stacked is None:
            self._place()
        shardings = {
            "params": {k: self._stack_sharding for k in self._stacked},
            "opt": jax.tree_util.tree_map(
                lambda _: self._stack_sharding, self._opt_state)}
        res = dck.restore_sharded(directory, mesh=self.mesh,
                                  shardings=shardings)
        if res is None:
            return None
        tree, step, extra = res
        self._stacked = tree["params"]
        self._opt_state = dck.merge_opt_state(self._opt_state,
                                              tree.get("opt", {}))
        meta = dck.restore_train_extras(self.optimizer, step, extra)
        sd = self.model.state_dict()
        for k, v in self._stacked.items():
            sd[k]._set_data(v[0])
        return meta
