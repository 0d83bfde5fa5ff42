"""`prove_streamed.py` for an `evabyte` cell: the same readings (the program,
the fp8-operand control, `compare.judge` under the cell's limits), with this
architecture's planted faults:

    python3 benchmark/prove_evabyte.py --workload <cell> --seed <n> \\
        [--seconds 12] [--control 1] [--fault <name>] [--out <file>]

`mu_k_left_out` and `pooling_by_the_mean` (`adaptive_phi` left out: every
row of a chunk then weighs 1/16) are planted as `prove_streamed.py` asks,
through what the benchmark hands the program: the weights it loads.
`summaries_left_out` and `ring_read_as_a_sliding_window` cannot be reached
so: no weight and no key of the configuration takes a set of keys out of a
softmax or a mask off a ring (a window as long as `max_len` would, with a
ring of 32,768 rows that does not fit).  Each replaces ONE module function
of the program's model by another of the same signature:
`_summaries_seen` by one under which a DECODE step sees none (its softmax
goes over the ring alone; a prompt's windows keep theirs: with none the
32768 bucket is another program, 16 equal windows a layer, and the chip's
compiler planned it past the device's memory, my chip run, PR 36; the
tokens judged are a decode step's), `_ring_keep` by one without the modulo
(`r <= pos`: past the first window every ring row is seen, those of the
window before too, which is what a sliding window of 2048 would read).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, prove_streamed  # noqa: E402
from benchmark.generators import flood_streamed as gen  # noqa: E402


def _leaf_zeroed(ref_name):
    """The benchmark's loader with one leaf of every layer zeroed after."""
    def plant():
        load = gen.load_streamed

        def zeroed(arch, d, model, seed):
            load(arch, d, model, seed)
            state = model.state_dict()
            for layer in range(d["L"]):
                p = state[arch.program_name(ref_name, layer)]
                p._set_data(p._data * 0)

        return gen, "load_streamed", zeroed
    return plant


def _summaries_left_out():
    from paddle_tpu.models import evabyte as model
    seen = model._summaries_seen
    # a decode step's positions are an array, a prompt's window's an int
    return model, "_summaries_seen", lambda pos, window, chunk: (
        pos * 0 if hasattr(pos, "shape") else seen(pos, window, chunk))


def _sliding_window():
    import jax.numpy as jnp
    from paddle_tpu.models import evabyte as model
    return model, "_ring_keep", lambda pos, rows: (
        jnp.arange(rows)[None] <= pos[:, None])


# name -> () -> (object, attribute, what to put there)
FAULTS = {"mu_k_left_out": _leaf_zeroed("mu"),
          "pooling_by_the_mean": _leaf_zeroed("phi"),
          "summaries_left_out": _summaries_left_out,
          "ring_read_as_a_sliding_window": _sliding_window}


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", default=None)
    known, rest = ap.parse_known_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cfg = files.config(files.cell(known.workload)["config"])
    if cfg["arch"] != "evabyte":
        raise SystemExit(f"{known.workload} is no evabyte cell")
    prove_streamed.FAULTS.update(FAULTS)
    try:
        rec = prove_streamed.main(rest + ["--workload", known.workload],
                                  need_tpu=need_tpu, spec_path=spec_path,
                                  data_dirs=data_dirs)
    finally:
        for name in FAULTS:
            prove_streamed.FAULTS.pop(name, None)
    if known.out:
        os.makedirs(os.path.dirname(known.out) or ".", exist_ok=True)
        with open(known.out, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    main()
