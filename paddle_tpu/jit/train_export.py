"""Exportable TRAINING programs: save a fused train step as a serialized
XLA artifact a host process can drive without the Python model code.

Reference: paddle/fluid/train/demo/demo_trainer.cc:1 — Python saves a
ProgramDesc (train_program + startup), a standalone C++ binary loads it and
drives the executor per batch.  TPU-native: the whole fused
forward+backward+optimizer step (the TrainStep program) exports through
jax.export as StableHLO with its state pytree spec; `TrainSession` replays
it batch-by-batch, and the C ABI (native/src/capi.cc PD_CreateTrainer /
PD_TrainerStep) exposes the session to C/Go hosts (demo/train_demo.c).
"""
from __future__ import annotations

import os
import pickle
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["save_train_program", "TrainSession"]


def save_train_program(model, loss_fn, optimizer, path: str,
                       input_specs: Sequence, amp_level=None,
                       amp_dtype="bfloat16", remat=False, seed: int = 0):
    """Serialize one optimizer step (fwd+bwd+update, concrete shapes) plus
    the initial train state.

    input_specs: list of InputSpec/(shape, dtype) for the step's batch
    (inputs..., label).  Writes path.pdtrain (StableHLO), path.pdstate.npz
    (params + opt state leaves), path.pdtrainmeta (pytree specs).

    The exported program IS TrainStep's compiled step (same builder —
    sparse-grad probe, remat, AMP and all), so the artifact can never
    diverge from what the in-process step computes.
    """
    from . import InputSpec, TrainStep, state_arrays

    tstep = TrainStep(model, loss_fn, optimizer, amp_level=amp_level,
                      amp_dtype=amp_dtype, remat=remat)
    state = state_arrays(model)
    opt_state = tstep.init_opt_state(state)

    def to_sds(s):
        if isinstance(s, InputSpec):
            return s.to_shape_dtype()
        shape, dtype = s
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))

    batch_sds = tuple(to_sds(s) for s in input_specs)
    # the sparse-probe inside _build traces the forward, so hand it real
    # (zero) example arrays rather than abstract shapes
    example_batch = tuple(jnp.zeros(s.shape, s.dtype) for s in batch_sds)
    compiled = tstep._build(state, opt_state, example_batch)

    state_sds = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), state)
    opt_sds = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype),
        opt_state)

    from jax import export as jax_export
    # jax.export takes the jit itself, not the compile-tracking wrapper
    exported = jax_export.export(getattr(compiled, "_jitted", compiled))(
        state_sds, opt_sds,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        batch_sds)

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + ".pdtrain", "wb") as f:
        f.write(exported.serialize())
    sleaves, streedef = jax.tree_util.tree_flatten(state)
    oleaves, otreedef = jax.tree_util.tree_flatten(opt_state)
    np.savez(path + ".pdstate.npz",
             **{f"s{i}": np.asarray(v) for i, v in enumerate(sleaves)},
             **{f"o{i}": np.asarray(v) for i, v in enumerate(oleaves)})
    with open(path + ".pdtrainmeta", "wb") as f:
        pickle.dump({
            "state_treedef": streedef, "opt_treedef": otreedef,
            "n_state": len(sleaves), "n_opt": len(oleaves),
            "lr": float(optimizer.get_lr()), "seed": int(seed),
            "batch_specs": [(tuple(s.shape), str(np.dtype(s.dtype)))
                            for s in batch_sds],
        }, f)
    return path


class TrainSession:
    """Drive a saved train program: holds the state, steps per batch.
    The host-language twin lives behind PD_CreateTrainer in the C ABI."""

    def __init__(self, path: str):
        from jax import export as jax_export
        with open(path + ".pdtrain", "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        with open(path + ".pdtrainmeta", "rb") as f:
            meta = pickle.load(f)
        data = np.load(path + ".pdstate.npz")
        sleaves = [jnp.asarray(data[f"s{i}"])
                   for i in range(meta["n_state"])]
        oleaves = [jnp.asarray(data[f"o{i}"]) for i in range(meta["n_opt"])]
        self._state = jax.tree_util.tree_unflatten(meta["state_treedef"],
                                                   sleaves)
        self._opt_state = jax.tree_util.tree_unflatten(meta["opt_treedef"],
                                                       oleaves)
        self._meta = meta
        self._step_no = 0
        self._key = jax.random.PRNGKey(meta["seed"])
        self.lr = meta["lr"]

    @property
    def batch_specs(self):
        return list(self._meta["batch_specs"])

    def step(self, *batch) -> float:
        """One optimizer step on numpy/jax batch arrays; returns the loss."""
        self._step_no += 1
        key = jax.random.fold_in(self._key, self._step_no)
        args = tuple(jnp.asarray(b) for b in batch)
        self._state, self._opt_state, loss, _outs = self._exported.call(
            self._state, self._opt_state,
            jnp.int32(self._step_no), jnp.float32(self.lr),
            jax.random.key_data(key), args)
        return float(loss)

    def state_dict(self):
        return {k: np.asarray(v) for k, v in self._state.items()}
