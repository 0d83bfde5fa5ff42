"""`prove_streamed.py` for a `nemotron_h` cell: the same readings (the
program, the fp8-operand control, `compare.judge` under the cell's limits),
with this architecture's planted faults, and several runs in ONE process
(each builds, serves and frees its own engine; set-up is paid once):

    python3 benchmark/prove_nemotron_h.py --workload <cell> \\
        --runs <seed>[:control|:<fault>],... [--seconds 12] [--out <file>]

Each fault replaces ONE function of the benchmark or of the program's model
module by another of the same signature, for its run alone:
  * `D_skip_left_out`: the benchmark's loader zeroes every Mamba layer's D
    after loading (no configuration key or module function holds the skip
    apart from its weight);
  * `norm_over_one_group`: `nemotron_h.gated_group_norm` normalises over
    one group of 8192 where the configuration says 8 of 1024;
  * `padding_advances_state`: `nemotron_h.real_rows` calls every row of a
    bucket real, so a prompt's padding moves the Mamba state it hands on;
  * `conv_state_not_carried`: `nemotron_h.conv_tail` hands decode zeros in
    place of the prompt's last xBC rows;
  * `routed_scaling_factor_left_out`: the program is built with
    `routed_scaling_factor` 1 where the reference keeps 5.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, prove_streamed  # noqa: E402
from benchmark.generators import flood_streamed as gen  # noqa: E402


def _d_left_out():
    load = gen.load_streamed

    def zeroed(arch, d, model, seed):
        load(arch, d, model, seed)
        state = model.state_dict()
        for layer, kind in enumerate(d["kinds"]):
            if kind == "mamba":
                p = state[arch.program_name("D", layer)]
                p._set_data(p._data * 0)

    return gen, "load_streamed", zeroed


def _one_group():
    from paddle_tpu.models import nemotron_h as model
    norm = model.gated_group_norm
    return model, "gated_group_norm", lambda y, z, g, groups, eps: norm(
        y, z, g, 1, eps)


def _padding_moves_state():
    import jax.numpy as jnp
    from paddle_tpu.models import nemotron_h as model
    return model, "real_rows", lambda s, prompt_len: jnp.ones((s,), bool)


def _conv_not_carried():
    import jax.numpy as jnp
    from paddle_tpu.models import nemotron_h as model
    tail = model.conv_tail
    return model, "conv_tail", lambda rows, prompt_len, k: jnp.zeros_like(
        tail(rows, prompt_len, k))


def _scale_left_out():
    build = gen.build_program_model

    def unscaled(cfg):
        program = dict(cfg["program"])
        program["kwargs"] = dict(program["kwargs"], routed_scaling_factor=1)
        return build({**cfg, "program": program})

    return gen, "build_program_model", unscaled


# name -> () -> (object, attribute, what to put there)
FAULTS = {"D_skip_left_out": _d_left_out,
          "norm_over_one_group": _one_group,
          "padding_advances_state": _padding_moves_state,
          "conv_state_not_carried": _conv_not_carried,
          "routed_scaling_factor_left_out": _scale_left_out}


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cfg = files.config(files.cell(args.workload)["config"])
    if cfg["arch"] != "nemotron_h":
        raise SystemExit(f"{args.workload} is no nemotron_h cell")
    import jax
    # `prove_streamed.main` sets the chip's generator for the run; the
    # process gets back the one it had
    prng = jax.config.jax_default_prng_impl
    out = []
    try:
        _runs(args, need_tpu, spec_path, data_dirs, out)
    finally:
        jax.config.update("jax_default_prng_impl", prng)
    return out


def _runs(args, need_tpu, spec_path, data_dirs, out):
    for entry in args.runs.split(","):
        seed, _, what = entry.partition(":")
        extra = (["--control", "1"] if what == "control"
                 else ["--fault", what] if what else [])
        if what and what != "control" and what not in FAULTS:
            raise SystemExit(f"unknown fault {what!r}: {sorted(FAULTS)}")
        saved = {}
        prove_streamed.FAULTS.update(FAULTS)
        if what in FAULTS:
            obj, attr, _ = FAULTS[what]()
            saved = {(obj, attr): getattr(obj, attr)}
        try:
            out.append(prove_streamed.main(
                ["--workload", args.workload, "--seed", seed,
                 "--seconds", str(args.seconds)] + extra,
                need_tpu=need_tpu, spec_path=spec_path, data_dirs=data_dirs))
        finally:
            for (obj, attr), value in saved.items():
                setattr(obj, attr, value)
            for name in FAULTS:
                prove_streamed.FAULTS.pop(name, None)
            gc.collect()
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
