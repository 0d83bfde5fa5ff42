"""The readings a `flood_streamed` cell's limit is set from, one seed a
process (a second set of weights does not fit beside the first):

    python3 benchmark/prove_streamed.py --workload <cell> --seed <n> \\
        [--seconds 12] [--control 1] [--fault <name>] [--tie 2] [--out <file>]

The program's number against the reference (the lower reading); with
`--control 1` also the control's: the reference put in the program's place in
the nearest precision below the one the configuration states (fp8 operands
for bfloat16), judged at the same positions of the same prompts and tokens;
with `--fault` one of `FAULTS` planted before the program is built, so that
what is judged is what a faulty engine served.  A fault is planted through
what the BENCHMARK hands the program (its weights, its configuration), never
by reaching into the program's code.  Every set of numbers goes through
`compare.judge` with the cell's limits and carries the `correct` it got.

With `--tie N` the widest gaps of N requests are looked into with the reference alone
(`tie_diagnosis`): was it an expert flipped where two router scores tie?
The benchmark's own runs never run this; PERF.md records what it read on the
chip.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, serve_check  # noqa: E402
from benchmark import weights as W  # noqa: E402
from benchmark.generators import flood_streamed as gen  # noqa: E402
from benchmark.prove import CONTROL  # noqa: E402


def _shared_summed():
    """Each layer's shared down-projections loaded S times as large: the
    mean of the S outputs times S is their sum."""
    load = gen.load_streamed

    def summed(arch, d, model, seed):
        load(arch, d, model, seed)
        state = model.state_dict()
        for layer in range(d["L"]):
            p = state[arch.program_name("sd", layer)]
            p._set_data(p._data * d["S"])

    return gen, "load_streamed", summed


def _half_window():
    """The program built with half the configuration's window (mask and
    ring); the reference keeps the whole."""
    build = gen.build_program_model

    def halved(cfg):
        program = dict(cfg["program"])
        program["kwargs"] = dict(program["kwargs"])
        program["kwargs"]["sliding_window"] //= 2
        return build({**cfg, "program": program})

    return gen, "build_program_model", halved


# name -> () -> (object of the benchmark, attribute, what to put there)
FAULTS = {"shared_experts_summed_not_averaged": _shared_summed,
          "window_layers_see_half_the_window": _half_window}


def judged(run, numbers):
    """The compared numbers through `compare.judge` under the cell's
    limits, as `run.py` puts a run's: `correct` and each beside its limit."""
    rows, ok = compare.judge(
        {k: numbers[k] for k in ("token_logit_gap",
                                 "mismatched_token_share")},
        run.limits, run.not_compared)
    return dict(numbers, correct=ok, checks=compare.checks_json(rows))


def tie_diagnosis(arch, d, seed, plan, sample, max_len, count=1):
    """Where the served token lies furthest below the reference's best: is
    that an expert flipped at a tie?  The reference alone answers, for the
    `count` sampled requests with the widest gaps, each at its widest
    position.  There, a layer: the margin between its K-th and (K+1)-th
    router score (beside the median margin over the request's positions,
    and how far rounding the layer's input to bfloat16 moves those two
    scores), whether the flip would matter here (one of the two experts is
    held), and the gap at that position once the reference takes the
    runner-up in that layer there.  A routed layer works a position at
    a time, so the swap moves one row of that layer's output."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref, K, held = arch.reference, d["K"], list(d["held"])
    embed, layer, gaps_fn, _ = gen._reference_fns(
        arch.__name__.rsplit(".", 1)[-1], json.dumps(d, sort_keys=True))
    leaves = lambda i: dict(arch.make_leaves(W.make, d, seed, i))  # noqa
    ids, picks, mask = serve_check._rows(plan, sample, max_len)
    top = leaves(-1)
    xs = [embed(top, jnp.asarray(row)) for row in ids]
    for i, kind in enumerate(d["kinds"]):
        lw = leaves(i)
        xs = [layer(x, lw, kind, "float32") for x in xs]
        del lw
    gaps = np.stack([np.where(mask[k], np.asarray(gaps_fn(
        top, xs[k], jnp.asarray(picks[k]))), -np.inf)
        for k in range(len(sample))])
    del xs
    widest = sorted(range(len(sample)), key=lambda k: -gaps[k].max())
    # a request looked into: its row, position, the unswapped stream, the
    # streams swapped at one layer each, a note a layer
    looks = [{"k": k, "p": int(np.argmax(gaps[k])), "forced": {},
              "base": embed(top, jnp.asarray(ids[k])), "notes": []}
             for k in widest[:count]]
    scores = lambda h, lw: np.asarray(jax.nn.sigmoid(ref.C.mm(  # noqa: E731
        h, lw["router"], "float32")))
    for i, kind in enumerate(d["kinds"]):
        lw = leaves(i)
        for look in looks:
            k, p = look["k"], look["p"]
            length = int(mask[k].nonzero()[0][-1]) + 1
            h = ref.norm(look["base"], lw["ln_g"], d["eps"])
            s_all = scores(h, lw)
            s, hp = s_all[p], h[p:p + 1]
            s16 = scores(hp.astype(jnp.bfloat16).astype(jnp.float32), lw)[0]
            order = np.argsort(-s)
            last, runner_up = int(order[K - 1]), int(order[K])
            ranked = -np.sort(-s_all[:length], axis=1)

            def routed(chosen):
                y = jnp.zeros_like(hp)
                for e in chosen:
                    if int(e) in held:
                        at = held.index(int(e))
                        y = y + float(s[e] / s[chosen].sum()) * ref.gated(
                            hp, lw["eg"][at], lw["eu"][at], lw["ed"][at],
                            "float32")
                return y

            as_picked = routed(order[:K])
            delta = routed(np.append(order[:K - 1], runner_up)) - as_picked
            # `routed` against the reference's own layer, as a check of it
            shared = sum(ref.gated(hp, *w, "float32") for w in zip(
                lw["sg"], lw["su"], lw["sd"])) / d["S"]
            off = float(jnp.max(jnp.abs(
                as_picked + shared - ref.ffn(hp, lw, d, "float32"))))
            look["forced"] = {j: layer(x, lw, kind, "float32")
                              for j, x in look["forced"].items()}
            look["base"] = layer(look["base"], lw, kind, "float32")
            look["forced"][i] = look["base"].at[p].add(delta[0])
            look["notes"].append({
                "layer": i, "margin": float(s[last] - s[runner_up]),
                "margin_median_over_positions": float(np.median(
                    ranked[:, K - 1] - ranked[:, K])),
                "bfloat16_input_moves_the_two_scores_by": float(max(
                    abs(s16[last] - s[last]),
                    abs(s16[runner_up] - s[runner_up]))),
                "last_pick_held": last in held,
                "runner_up_held": runner_up in held,
                "ffn_recomputed_off_by": off})
        del lw
    out = []
    for look in looks:
        k, p = look["k"], look["p"]
        gap_at = lambda x: float(np.asarray(gaps_fn(  # noqa: E731
            top, x, jnp.asarray(picks[k])))[p])
        for i, note in enumerate(look["notes"]):
            note["gap_with_runner_up_taken"] = gap_at(look["forced"][i])
        out.append({"request": int(sample[k]["i"]), "position": p,
                    "prompt_length": int(sample[k]["plen"]),
                    "gap": float(gaps[k, p]),
                    "gap_recomputed": gap_at(look["base"]),
                    "layers": look["notes"]})
    return {"requests_looked_over": len(sample),
            "gap_by_request": [float(gaps[k].max()) for k in widest],
            "widest": out}


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control", type=int, default=0, choices=(0, 1))
    ap.add_argument("--fault", default=None, choices=sorted(FAULTS))
    ap.add_argument("--tie", type=int, default=0,
                    help="look into the widest gaps of this many requests")
    ap.add_argument("--tie-requests", type=int, default=None,
                    help="sampled requests to look over (the cell's count)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"], need_tpu)
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    harness.place_cache()
    if args.fault:
        setattr(*FAULTS[args.fault]())
    run = harness.Run(files, cell, argparse.Namespace(
        seed=args.seed, seconds=args.seconds, trace=0), time.perf_counter(),
        devices)
    if run.traffic["kind"] != "flood_streamed":
        raise SystemExit(f"{args.workload} is no flood_streamed cell")
    system = gen.StreamedServeSystem(run)
    system.warmup()
    plan, done, t0, t_end = gen.flood.serve(run, system)
    arch, d = gen.close(run, system, done)
    sample = serve_check.pick_sample(done, args.seed,
                                     run.traffic["check_requests"])
    max_len = run.traffic["engine"]["max_len"]
    rec = {"seed": args.seed, "fault": args.fault, "requests": len(done),
           "failed": sum(r["failed"] for r in done),
           "memory_peak_bytes": run.extra["memory_peak_bytes"],
           "program": gen.compare_streamed(arch, d, args.seed, plan, sample,
                                           max_len)}
    if args.control:
        control = CONTROL[run.config["serving"]["precision"]]
        rec["control_" + control] = gen.compare_streamed(
            arch, d, args.seed, plan, sample, max_len, control=control)
    for key, numbers in list(rec.items()):
        if isinstance(numbers, dict):
            numbers["mismatched_token_share"] = gen.mismatched_share(
                numbers["checked_tokens"], numbers["mismatched_tokens"])
            rec[key] = judged(run, numbers)
    if args.tie:
        # a larger draw holds the cell's own sample: `pick_sample` takes the
        # longest and then the head of one permutation
        over = serve_check.pick_sample(
            done, args.seed,
            args.tie_requests or run.traffic["check_requests"])
        rec["tie"] = tie_diagnosis(arch, d, args.seed, plan, over, max_len,
                                   args.tie)
    print(json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    main()
