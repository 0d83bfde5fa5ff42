"""The readings a data-parallel training cell's limits are set from, many
seeds in one process:

    python3 benchmark/prove_dp.py --workload <cell> --seeds 100,101,... \\
        [--control-seeds 1] [--program 0] [--out <file>]

`prove.py`'s training readings (the program against the reference, and on
the first `--control-seeds` seeds the fp8-operand control) with this cell's
planted fault in their place of the half batch: "the exchange between chips
left out", each chip applying the gradient of its own rows.  Every chip then
holds what the reference computes from ITS rows alone; chip 0's, the first
`batch / dp` rows of every step, are put in the program's place, as
`prove.py` puts the half batch.  Every set of numbers goes through
`compare.judge` with the cell's limits and carries the `correct` it got.
The control's and the fault's readings are the reference's alone; with
`--program 0` the program is not built and they are read on whatever
devices there are (one chip will do).
"""
import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, train_check  # noqa: E402
from benchmark.prove import CONTROL, _run  # noqa: E402


def judged(numbers, limits):
    rows, ok = compare.judge({k: v for k, v in numbers.items()
                              if k in limits}, limits)
    return dict(numbers, correct=ok)


def chip_rows(feeds, chips):
    """Each step's rows that the first of `chips` chips holds."""
    return [tuple(x[: x.shape[0] // chips] for x in feed) for feed in feeds]


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--program", type=int, default=1, choices=(0, 1))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"] if args.program else 1,
                                    need_tpu)
    import jax
    # the chip's generator for the readings, as `run.py` sets it; the
    # process gets back the one it had
    prng = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "rbg")
    try:
        return _readings(args, files, cell, devices)
    finally:
        jax.config.update("jax_default_prng_impl", prng)


def _readings(args, files, cell, devices):
    harness.place_cache()
    from benchmark.arch import load as load_arch
    from benchmark.generators import train_steps
    seeds = [int(s) for s in args.seeds.split(",")]
    run0 = _run(files, cell, seeds[0], 1, devices)
    mesh = run0.traffic.get("mesh")
    if not mesh:
        raise SystemExit(f"{args.workload} has no mesh")
    chips = math.prod(mesh.values())
    cfg, t = run0.config, run0.traffic
    arch = load_arch(cfg["arch"])
    d = arch.dims(cfg)
    layout, hyper = arch.layout(d), cfg["training"]["optimizer"]
    precision = cfg["training"]["precision"]
    progs = {}
    if args.program:
        system = train_steps.TrainSystem(run0)
        system.warmup()
        for seed in seeds:
            system.reseed(seed)
            progs[seed] = system.first_steps(seed)
        system.free()
    out = []
    for k, seed in enumerate(seeds):
        feeds = [b[1] for b in arch.train_batches(
            d, seed, t["batch"], t["seq"], t["batches"],
            cfg["training"]["vocab_used"])[:train_check.STEPS]]
        ref = train_check.reference_steps(arch, d, layout, seed, feeds, hyper)
        rec = {"seed": seed}
        if seed in progs:
            rec["program"] = judged(
                compare.train_numbers(progs[seed], ref)[0], run0.limits)
        if k < args.control_seeds:
            ctl = train_check.reference_steps(
                arch, d, layout, seed, feeds, hyper, CONTROL[precision])
            rec["control_" + CONTROL[precision]] = judged(
                compare.train_numbers(ctl, ref)[0], run0.limits)
        alone = train_check.reference_steps(
            arch, d, layout, seed, chip_rows(feeds, chips), hyper)
        rec["fault_exchange_left_out"] = judged(
            compare.train_numbers(alone, ref)[0], run0.limits)
        print(json.dumps(rec), flush=True)
        out.append(rec)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
