"""The readings a `mellum2` training cell's limits are set from, many seeds
in one process, judged against the limits the cell's file holds:

    python3 benchmark/prove_mellum2.py --workload mellum2-train-s8k \\
        --seeds 100,101,... [--control-seeds 2] [--fault-seeds 1] \\
        [--faults no_renorm,no_window,no_yarn,router_cut] \\
        [--out out/prove_mellum2.json]

For each seed the program's first three steps against the reference's (the
lower readings, `prove.py`'s); on the first `--control-seeds` of them the
control (the reference in fp8 operands put in the program's place) and the
half-batch fault; on the first `--fault-seeds` each planted fault of
`benchmark/reference/mellum2.py` (`FAULTS`) put in the program's place.
Every reading is judged by `compare.judge` against the cell's limits: the
program must pass, and the control and each fault must fail by at least
one limit.  The last line is a summary.  The benchmark's own runs never
run this; PERF.md records what it read on the chip.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, train_check  # noqa: E402
from benchmark.prove import CONTROL  # noqa: E402


def readings(files, cell, seeds, n_control, n_fault, faults, devices, log):
    from benchmark.generators import train_steps
    args = argparse.Namespace(seed=seeds[0], seconds=1, trace=0)
    run0 = harness.Run(files, cell, args, time.perf_counter(), devices)
    system = train_steps.TrainSystem(run0)
    system.warmup()
    precision = run0.config["training"]["precision"]
    progs = {}
    for seed in seeds:
        system.reseed(seed)
        progs[seed] = (system.first_steps(seed),
                       [system.batches[i][1] for i in range(train_check.STEPS)])
        log(f"program seed {seed}: losses {progs[seed][0]['losses']}")
    arch, d, layout, hyper = system.arch, system.d, system.layout, system.hyper
    system.free()
    ref_mod = arch.reference
    out = []
    for k, seed in enumerate(seeds):
        prog, feeds = progs[seed]
        ref = train_check.reference_steps(arch, d, layout, seed, feeds, hyper)
        rec = {"seed": seed}
        rec["program"], rec["program_where"] = compare.train_numbers(prog,
                                                                     ref)
        rec["losses"] = {"program": prog["losses"],
                         "reference": ref["losses"]}
        put = []
        if k < n_control:
            put.append(("control_" + CONTROL[precision],
                        dict(precision=CONTROL[precision])))
            put.append(("fault_half_batch", dict(fault="half_batch")))
        if k < n_fault:
            put += [("fault_" + f, {"planted": f}) for f in faults]
        for name, how in put:
            ref_mod.FAULT = how.pop("planted", None)
            try:
                other = train_check.reference_steps(
                    arch, d, layout, seed, feeds, hyper, **how)
            finally:
                ref_mod.FAULT = None
            rec[name], rec[name + "_where"] = compare.train_numbers(other,
                                                                    ref)
        log(json.dumps(rec))
        out.append(rec)
    return out


def judged(records, limits, not_compared):
    """{reading: [seeds it passed]} for the program and {reading: [seeds it
    passed]} for the rest, which must be empty."""
    passed = {}
    for rec in records:
        for name, numbers in rec.items():
            if not isinstance(numbers, dict) or name.endswith("_where") \
                    or name == "losses":
                continue
            numbers = dict(numbers, compiles_in_window=0.0)
            _, ok = compare.judge(numbers, limits, not_compared)
            passed.setdefault(name, [])
            if ok:
                passed[name].append(rec["seed"])
    program = passed.pop("program", [])
    return {"program_passed": program,
            "program_failed": [r["seed"] for r in records
                               if r["seed"] not in program],
            "others_passed": {k: v for k, v in passed.items() if v}}


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"], need_tpu)
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    harness.place_cache()
    cfg = files.config(cell["config"])
    from benchmark.arch import load as load_arch
    faults = (args.faults.split(",") if args.faults
              else list(load_arch(cfg["arch"]).reference.FAULTS))
    seeds = [int(s) for s in args.seeds.split(",")]
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    records = readings(files, cell, seeds, args.control_seeds,
                       args.fault_seeds, faults, devices, log)
    cell_file = files.data("cells", cell["name"])
    summary = judged(records, cell_file["limits"],
                     cell_file.get("not_compared", {}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"records": records, "summary": summary}, f, indent=1)
    print(json.dumps(summary), flush=True)
    return records, summary


if __name__ == "__main__":
    main()
