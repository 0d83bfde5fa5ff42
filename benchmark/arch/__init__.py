"""One module per architecture: the sizes read from a configuration's file,
the layout of the weights the benchmark makes, their names in the program,
the feed of a training step, and which reference follows it.  This is the
only place where the benchmark names parts of the program's models."""
import importlib


def load(name):
    return importlib.import_module(f"benchmark.arch.{name}")


def build_program_model(cfg):
    """The program's model as the configuration's `program` section names
    it: `factory(**kwargs)` gives the program's own config object."""
    from ..harness import resolve
    p = cfg["program"]
    return resolve(p["model"])(resolve(p["factory"])(**p.get("kwargs", {})))
