"""The readings the limits are set from, many seeds in one process:

    python3 benchmark/prove.py --workload <cell> --seeds 100,101,... \\
        [--control-seeds 3] [--seconds 12] [--out chiprun_out/prove.json]

For each seed the program's numbers against the reference (the lower
readings), and on the first `--control-seeds` of them the control's: the
reference put in the program's place in the nearest precision below the one
the configuration states (fp8 operands for bfloat16, bfloat16 for float32), and for a training cell the fault "half of the batch left out, the
mean taken over the rest" planted in the reference.  The benchmark's own runs
never run this; PERF.md records what it read on the chip.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, serve_check, train_check  # noqa: E402

CONTROL = {"bfloat16": "fp8", "float32": "bfloat16"}


def _run(files, cell, seed, seconds, devices):
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return harness.Run(files, cell, args, time.perf_counter(), devices)


def prove_train(files, cell, seeds, n_control, devices, log):
    from benchmark.generators import train_steps
    run0 = _run(files, cell, seeds[0], 1, devices)
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
    out = []
    for k, seed in enumerate(seeds):
        prog, feeds = progs[seed]
        ref = train_check.reference_steps(arch, d, layout, seed, feeds, hyper)
        numbers, where = compare.train_numbers(prog, ref)
        rec = {"seed": seed, "program": numbers, "worst_leaves": where,
               "losses": {"program": prog["losses"],
                          "reference": ref["losses"]}}
        if k < n_control:
            ctl = train_check.reference_steps(
                arch, d, layout, seed, feeds, hyper, CONTROL[precision])
            rec["control_" + CONTROL[precision]], rec["control_where"] = \
                compare.train_numbers(ctl, ref)
            half = train_check.reference_steps(
                arch, d, layout, seed, feeds, hyper, fault="half_batch")
            rec["fault_half_batch"], _ = compare.train_numbers(half, ref)
        log(json.dumps(rec))
        out.append(rec)
    return out


def prove_serve(files, cell, seeds, n_control, seconds, devices, log,
                kind="open_loop"):
    from benchmark.generators import open_loop
    serve = files.code("generators", kind).serve
    run0 = _run(files, cell, seeds[0], seconds, devices)
    system = open_loop.ServeSystem(run0)
    system.warmup()
    precision = run0.config["serving"]["precision"]
    served = {}
    for k, seed in enumerate(seeds):
        run = _run(files, cell, seed, seconds, devices)
        if k:
            system.reseed(seed)
        plan, done, t0, t_end = serve(run, system)
        for rec in done:
            rec.pop("resp", None)
        sample = serve_check.pick_sample(done, seed,
                                         run.traffic["check_requests"])
        served[seed] = (plan, sample, sum(r["failed"] for r in done),
                        len(done))
        log(f"served seed {seed}: {len(done)} requests, "
            f"{served[seed][2]} failed, drain {t_end - t0 - seconds:.2f}s")
    arch, d, layout = system.arch, system.d, system.layout
    max_len = run0.traffic["engine"]["max_len"]
    system.free()
    out = []
    for k, seed in enumerate(seeds):
        plan, sample, failed, total = served[seed]
        rec = {"seed": seed, "requests": total, "failed": failed,
               "program": serve_check.compare_sample(
                   arch, d, layout, seed, plan, sample, max_len)}
        if k < n_control:
            rec["control_" + CONTROL[precision]] = serve_check.compare_sample(
                arch, d, layout, seed, plan, sample, max_len,
                control=CONTROL[precision])
        log(json.dumps(rec))
        out.append(rec)
    return out


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"], need_tpu)
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    harness.place_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    kind = files.data("traffic", cell["traffic"])["kind"]
    if kind == "train_steps":
        out = prove_train(files, cell, seeds, args.control_seeds, devices,
                          log)
    else:
        out = prove_serve(files, cell, seeds, args.control_seeds,
                          args.seconds, devices, log, kind)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
