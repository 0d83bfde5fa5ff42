"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and its configuration, traffic mix, limits
and metrics by name under benchmark/, runs the mix's generator on the chips
the cell asks for, and prints one JSON object as the last line of standard
output.  With no TPU, or fewer chips than the cell asks for, it exits with a
non-zero code and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, trace_reduce  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    return ap.parse_args(argv)


def run_cell(files, cell, args, devices, t_start):
    """Everything after the look for the chip: set-up, window, comparison,
    metrics.  Returns the result object."""
    run = harness.Run(files, cell, args, t_start, devices)
    generator = files.code("generators", run.traffic["kind"])
    out = generator.run(run)
    rows, ok = compare.judge(out["numbers"], run.limits, run.not_compared)
    run.extra["not_compared"] = {k: out["numbers"][k]
                                 for k in run.not_compared
                                 if k in out["numbers"]}
    metrics = {}
    units = {m["name"]: m["unit"]
             for m in files.spec["end_to_end"] + files.spec["per_layer"]}
    end_to_end = dict(out["end_to_end"], setup_s=run.setup_s)
    if not run.trace:
        for name in files.metrics_of(cell["name"], "end_to_end"):
            metrics[name] = {"value": end_to_end[name], "unit": units[name]}
    else:
        run.peaks = harness.peaks_for(devices[0].device_kind) \
            if devices[0].platform == "tpu" else None
        if run.traced is not None and run.peaks is not None:
            run.trace_summary = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(run.trace_dir)))
        for name in files.metrics_of(cell["name"], "per_layer"):
            meta = files.data("metrics", name)
            value = files.code("readers", meta["reader"]).read(
                run, meta.get("params", {}))
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    result = {
        "correct": ok, "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics,
        "device": harness.device_block(devices, run),
    }
    if run.trace_summary is not None:
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    if run.trace:
        result["end_to_end_of_traced_run"] = end_to_end
    result["notes"] = {k: v for k, v in run.extra.items()
                       if k != "memory_peak_bytes"}
    result["counters"] = run.counters
    result["checks"] = compare.checks_json(rows)
    compare.print_checks(rows)
    return result


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None,
         cache_root=None):
    args = parse(argv)
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"], need_tpu)
    import jax
    # dropout and weight masks from the chip's own generator, as
    # chip_smoke.py and bench.py set it (listed under `assumed`)
    before = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "rbg")
    try:
        harness.place_cache(cache_root or harness.ROOT)
        result = run_cell(files, cell, args, devices, T_START)
    finally:
        jax.config.update("jax_default_prng_impl", before)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
