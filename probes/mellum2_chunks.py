"""Milliseconds a `mellum2-train-s8k` step takes with the held experts'
trainable form's grouped pass walking its picks in tiles of each given
size, on the cell's own weights and batches (one model, a step compiled a
size):

    python3 probes/mellum2_chunks.py --tiles 256,512 \\
        --seeds 2600004177,3147483901 [--steps 20] \\
        [--workload mellum2-train-s8k]

Per size and seed one JSON line: ms a step over `--steps` steps enqueued
two in flight after three unmeasured ones, the share of the step's picks
that fell on the held experts (`routed_here` / `routed_all`), the rows of
the tiles the grouped pass went over, and the seconds the first steps took
(the step traced, lowered and compiled).  Needs the chip.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mellum2-train-s8k")
    ap.add_argument("--tiles", default="256,512")
    ap.add_argument("--seeds", default="2600004177,3147483901")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args(argv)
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"], need_tpu)
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    harness.place_cache()
    from benchmark.generators import train_steps
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nn.functional import moe
    seeds = [int(s) for s in args.seeds.split(",")]
    run = harness.Run(files, cell, argparse.Namespace(
        seed=seeds[0], seconds=1, trace=0), time.perf_counter(), devices)
    system = train_steps.TrainSystem(run)
    amp = run.config["training"]["amp"]
    names = system.model.train_count_names
    for tile in (int(c) for c in args.tiles.split(",")):
        moe._TRAIN_CHUNK_ROWS = tile
        system.step = None
        gc.collect()
        system.step = TrainStep(system.model, system.arch.train_loss_fn(),
                                system.opt, amp_level=amp["level"],
                                amp_dtype=amp["dtype"])
        for k, seed in enumerate(seeds):
            system.reseed(seed)
            t0 = time.perf_counter()
            if k == 0:
                system.warmup()
            compile_s = time.perf_counter() - t0
            for i in range(3):
                loss = system.call(i)
            float(loss)
            losses = []
            t0 = time.perf_counter()
            for i in range(args.steps):
                if len(losses) >= 2:
                    float(losses[-2])
                losses.append(system.call(3 + i))
            float(losses[-1])
            ms = (time.perf_counter() - t0) * 1e3 / args.steps
            counts = dict(zip(names, (int(v) for v in jax.device_get(
                system.step.last_counts))))
            print(json.dumps({
                "tile": tile, "seed": seed, "ms_per_step": ms,
                "tokens_per_s": system.tokens_per_step * 1e3 / ms,
                "picks_here_pct": 100.0 * counts["routed_here"]
                / counts["routed_all"],
                "expert_rows": counts["expert_rows"],
                "expert_products": counts["expert_products"],
                "compile_s": compile_s}), flush=True)


if __name__ == "__main__":
    main()
