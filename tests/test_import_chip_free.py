"""A chip belongs to one process: a parent that only imports the package
(a launcher, a fleet manager, the bench orchestrator) must not initialise a
JAX backend, or its children find the chip taken."""
import subprocess
import sys

import pytest

_CHECK = """
import importlib, sys
from jax._src import xla_bridge
importlib.import_module(sys.argv[1])
assert not xla_bridge.backends_are_initialized(), sys.argv[1]
import paddle_tpu
assert paddle_tpu.get_rng_state().shape == (2,)
assert xla_bridge.backends_are_initialized()
"""


@pytest.mark.parametrize("module", [
    "paddle_tpu", "paddle_tpu.serving.fleet", "paddle_tpu.serving.worker",
    "paddle_tpu.distributed.launch"])
def test_import_initialises_no_backend(module, cpu8_env):
    proc = subprocess.run([sys.executable, "-c", _CHECK, module],
                          capture_output=True, text=True, env=cpu8_env,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_generator_key_is_lazy_and_reproducible():
    from paddle_tpu.core.rng import Generator
    import jax
    g = Generator(7)
    assert g._key_ is None and g.initial_seed() == 7
    first = jax.random.key_data(g.next_key())
    g.manual_seed(7)
    assert g._key_ is None
    assert (jax.random.key_data(g.next_key()) == first).all()
    state = g.get_state()
    nxt = jax.random.key_data(g.next_key())
    g.set_state(state)
    assert (jax.random.key_data(g.next_key()) == nxt).all()
