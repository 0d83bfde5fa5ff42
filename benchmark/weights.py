"""Weights from `--seed`, made on the device in one jitted call, in float32
(the type both kinds of cell hold them in).  The same call with the same
seed gives the same weights, which is how the reference gets its own copy
after the program has donated or dropped the first."""
import functools
import json

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from any whole number up to 2**62: the low 31 bits seed
    it and the rest are folded in, so seeds past 2**31 stay distinct."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _make(layout, key, counter):
    out = {}
    for name in sorted(layout):
        node = layout[name]
        if isinstance(node, dict):
            out[name] = _make(node, key, counter)
            continue
        shape, kind, std = node
        counter[0] += 1
        if kind == "normal":
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, counter[0]), tuple(shape),
                jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(tuple(shape), jnp.float32)
        else:
            raise ValueError(f"unknown init {kind!r}")
    return out


@functools.lru_cache(maxsize=None)
def _maker(layout_json):
    layout = json.loads(layout_json)
    return jax.jit(lambda key: _make(layout, key, [0]))


def make(layout, seed):
    """layout: nested dict name -> (shape, "normal" | "ones", std)."""
    return _maker(json.dumps(layout, sort_keys=True))(seed_key(seed))
