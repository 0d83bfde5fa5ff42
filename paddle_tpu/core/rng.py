"""RNG state management.

TPU-native replacement for the reference's per-device Generator
(reference: paddle/fluid/framework/generator.cc, python/paddle/fluid/generator.py).
JAX randomness is functional (explicit PRNG keys); for paddle-API parity we keep
a global generator that owns a key and splits a fresh subkey per draw.  The
functional training path should instead thread keys explicitly (see
`paddle_tpu.jit`): this global state is only touched at eager op dispatch, so it
never ends up baked into a compiled program.
"""
from __future__ import annotations

import threading

import jax


class Generator:
    """A stateful PRNG: owns a key, hands out fresh subkeys.

    The key is built from the seed on first use, not on construction: the
    package builds its default generator while it is imported, and
    `jax.random.key` initialises the default backend — a process that only
    imports the package (a launcher, a fleet manager) must leave the chip
    to its children."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        with self._lock:
            self._seed = int(seed)
            self._key_ = None
        return self

    seed = manual_seed

    def initial_seed(self) -> int:
        return self._seed

    @property
    def _key(self):
        if self._key_ is None:
            self._key_ = jax.random.key(self._seed)
        return self._key_

    @_key.setter
    def _key(self, key):
        self._key_ = key

    def next_key(self):
        """Split and return a fresh subkey (advances state)."""
        with self._lock:
            self._key, sub = jax.random.split(self._key)
            return sub

    def get_state(self):
        with self._lock:
            return jax.random.key_data(self._key)

    def set_state(self, state):
        with self._lock:
            self._key = jax.random.wrap_key_data(state)


_default_generator = Generator(0)

# host-side numpy samplers (e.g. the RCNN fg/bg assigners) register here so
# paddle.seed() also resets them — keeping the reproducibility contract
# without giving every call a fresh identical RandomState
_seed_listeners = []


def register_seed_listener(fn):
    _seed_listeners.append(fn)


def seed(s: int):
    """Set the global random seed (paddle.seed)."""
    _default_generator.manual_seed(s)
    for fn in _seed_listeners:
        fn(int(s))
    return _default_generator


def default_generator() -> Generator:
    return _default_generator


def next_key():
    ctx = _active_ctx()
    if ctx is not None:
        sub = jax.random.fold_in(ctx.key, ctx.count)
        ctx.count += 1
        return sub
    return _default_generator.next_key()


def example_key():
    """A constant key aval-identical to `next_key()`'s output WITHOUT
    advancing the stream — compile-only paths (TrainStep.warmup) need the
    signature but must not consume a key a bit-exact resume depends on."""
    ctx = _active_ctx()
    if ctx is not None:
        return jax.random.fold_in(ctx.key, 0)
    gen = _default_generator
    with gen._lock:
        return jax.random.fold_in(gen._key, 0)


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)


# ---------------------------------------------------------------------------
# traced-key context: randomness inside jitted programs
# ---------------------------------------------------------------------------
# Under `jax.jit`, calling next_key() at trace time would bake a *constant*
# key into the compiled program — every step would reuse identical dropout
# masks.  Compiled paths (jit.TrainStep, parallel.ShardedTrainStep) instead
# pass a fresh key argument per step and trace the forward inside key_ctx():
# next_key() then derives per-call-site subkeys from the traced key via
# fold_in, so masks differ every step while staying jit-pure.
import contextlib as _contextlib

_traced_ctx = threading.local()


class _KeyCtx:
    __slots__ = ("key", "count")

    def __init__(self, key):
        self.key = key
        self.count = 0


@_contextlib.contextmanager
def key_ctx(key):
    """Use `key` (possibly a tracer) as the randomness root for this trace."""
    prev = getattr(_traced_ctx, "ctx", None)
    _traced_ctx.ctx = _KeyCtx(key)
    try:
        yield
    finally:
        _traced_ctx.ctx = prev


def _active_ctx():
    return getattr(_traced_ctx, "ctx", None)
