"""Training's side of `correct`: the program's readings over its first three
steps, and the reference that follows them.

Program: each step's loss, the norm of every leaf of the first gradient as
the optimizer got it (Adam's first moment after one step is (1 - beta1) g),
and the norm of every leaf's change over the three steps.  Reference: the
same three steps on the same weights and rows in float32, row by row.
Leaves are named (reference leaf, layer index).
"""
import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .reference import common as C

STEPS = 3


QKV = ("q", "k", "v")


def _leaf_norms(tree):
    """{name or "layers/name": vector of norms (one per layer) or scalar}.
    A fused QKV leaf counts as three leaves: the key's bias has no gradient
    under softmax, and fused with the other two it would hide that."""
    out = {}
    for name, leaf in tree.items():
        if isinstance(leaf, dict):
            for sub, x in leaf.items():
                sq = jnp.square(x.astype(jnp.float32))
                if sub.startswith("qkv_"):
                    parts = sq.reshape(x.shape[0], -1, 3, x.shape[-1] // 3)
                    for j, part in enumerate(QKV):
                        out[f"{name}/{sub}.{part}"] = jnp.sqrt(
                            jnp.sum(parts[:, :, j], axis=(1, 2)))
                    continue
                out[f"{name}/{sub}"] = jnp.sqrt(jnp.sum(
                    sq, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(
                leaf.astype(jnp.float32))))
    return out


def _flatten(norms):
    """Device norms -> {"name" or "layers/name[i]": float}."""
    flat = {}
    for name, v in jax.device_get(norms).items():
        v = np.asarray(v)
        if v.ndim == 0:
            flat[name] = float(v)
        else:
            for i, x in enumerate(v):
                flat[f"{name}[{i}]"] = float(x)
    return flat


def _gather(arch, d, by_program_name):
    """The program's leaves regrouped into the reference's stacked layout."""
    top, layers = {}, {}
    for prog, ref, i in arch.program_names(d):
        if i is None:
            top[ref] = by_program_name[prog]
        else:
            layers.setdefault(ref, {})[i] = by_program_name[prog]
    top["layers"] = {ref: jnp.stack([rows[i] for i in range(d["L"])])
                     for ref, rows in layers.items()}
    return top


def load_weights(arch, d, model, w):
    """The benchmark's weights into the program's model, by name."""
    state = model.state_dict()
    names = arch.program_names(d)
    missing = set(state) - {p for p, _, _ in names}
    if missing:
        raise KeyError(f"program leaves with no weight: {sorted(missing)}")
    for prog, ref, i in names:
        leaf = w["layers"][ref][i] if i is not None else w[ref]
        if tuple(state[prog].shape) != tuple(leaf.shape):
            raise ValueError(f"{prog}: program {state[prog].shape}, "
                             f"benchmark {leaf.shape}")
        state[prog]._set_data(leaf)


def program_grad_norms(arch, d, step, beta1):
    """After the first step: the gradient the optimizer got, leaf by leaf."""
    m1 = {k: v["moment1"] for k, v in step._opt_state.items()}
    fn = jax.jit(lambda m: _leaf_norms(_gather(arch, d, m)))
    return {k: v / (1.0 - beta1) for k, v in _flatten(fn(m1)).items()}


def program_change_norms(arch, d, model, layout, seed):
    """After the third step: |p3 - p0| leaf by leaf, p0 made anew."""
    now = {k: v._data for k, v in model.state_dict().items()}
    w0 = W.make(layout, seed)

    def diff(now, w0):
        g = _gather(arch, d, now)
        return _leaf_norms(jax.tree_util.tree_map(jnp.subtract, g, w0))

    return _flatten(jax.jit(diff)(now, w0))


def reference_steps(arch, d, layout, seed, feeds, hyper, precision="float32",
                    fault=None):
    """Follows the first three steps.  `fault` plants one of the training
    faults in the reference put in the program's place: "half_batch" (the
    mean over the first half of the rows)."""
    ref = arch.reference
    w = W.make(layout, seed)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(w), zeros(w)

    def one(w, m, v, batch, step_no):
        loss, g = ref.loss_and_grads(w, batch, d["heads"], precision)
        w2, m2, v2 = C.adamw(w, g, m, v, step_no, hyper["lr"],
                             hyper["beta1"], hyper["beta2"], hyper["eps"],
                             hyper["weight_decay"])
        return loss, _leaf_norms(g), w2, m2, v2

    one = jax.jit(one, donate_argnums=(0, 1, 2))
    losses, grad_norms = [], None
    for i in range(STEPS):
        batch = tuple(jnp.asarray(x) for x in feeds[i])
        if fault == "half_batch":
            batch = tuple(x[: x.shape[0] // 2] for x in batch)
        loss, gn, w, m, v = one(w, m, v, batch, jnp.int32(i + 1))
        losses.append(float(loss))
        if i == 0:
            grad_norms = _flatten(gn)
    del m, v
    w0 = W.make(layout, seed)
    change = jax.jit(lambda a, b: _leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(w, w0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _flatten(change)}
