"""What the Mellum 2 training cell adds to the benchmark, at the test-only
sizes of `tiny/` (`spec_mellum2.json`, `tiny-mellum2`: 4 layers SSSF, 8
experts of which 4 held, 2 x 64 tokens): the `train_steps` kind's CPU
rehearsal over a routed model, `prove_mellum2.py`'s control and planted
faults failing the tiny cell's limits, the architecture module's counts
against hand-worked numbers at the PUBLISHED sizes, and the new readers'
arithmetic on a made-up trace summary and ring.  Nothing timed here is a
device number.
"""
import argparse
import json
import os
import shutil
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, prove_mellum2  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.arch import mellum2 as A  # noqa: E402
from benchmark.readers import (expert_product_train_roofline,  # noqa: E402
                               flash_train_roofline, mfu_train_arch)

CELL, REAL = "tiny-mellum2-train", "mellum2-train-s8k"
CONFIG, MIX = "mellum2-12b-a2.5b-1of4-4of28", "train_b1_s8192"
SPEC = os.path.join(TINY, "spec_mellum2.json")
CATALOG = os.environ.get("MODEL_CATALOG_JSONL", "")  # architectures.jsonl
SFX = ".train_s8k"
NEW = {"mfu" + SFX, "window_flash_train_roofline" + SFX,
       "moe_expert_product_roofline" + SFX, "moe_picks_here_pct" + SFX}
APPENDED = {"train_host_ms_per_step", "attn_flash_calls_pct",
            "device_idle_pct.train", "train_state_walk_ms_per_step",
            "train_dispatch_ms_per_step"}


def rehearse(seconds=1.0, trace=1, seed=2147483659):
    files = harness.Files(SPEC, [TINY, BENCH])
    import jax
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    try:
        return bench_run.run_cell(files, files.cell(CELL), args,
                                  jax.devices()[:1], time.perf_counter())
    finally:        # the profiler's files: megabytes that nothing reads
        shutil.rmtree(os.path.join(TINY, ".bench_trace", CELL),
                      ignore_errors=True)


@pytest.fixture(scope="module")
def real():
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    return spec, cfg, A.dims(cfg)


# ----------------------------------------------------------- the rehearsal

def test_traced_rehearsal_is_correct_and_reports_what_a_cpu_can_give():
    res = rehearse()
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 3
    # the device's metrics find no trace summary on the CPU and stay
    # silent; the host's spans and the counters are read
    assert set(res["metrics"]) == {
        "moe_picks_here_pct" + SFX, "train_host_ms_per_step",
        "attn_flash_calls_pct", "train_state_walk_ms_per_step",
        "train_dispatch_ms_per_step"}
    # 4 of 8 experts held, 2 picks a token: about half the picks here
    assert 20 < res["metrics"]["moe_picks_here_pct" + SFX]["value"] < 80
    # the CPU takes the XLA form: every call counted, none by the kernel
    paths = res["counters"]["attention_paths"]
    assert paths["xla"] == 4 and not paths.get("flash")
    assert res["metrics"]["attn_flash_calls_pct"]["value"] == 0.0
    assert res["checks"]["compiles_in_window"]["value"] == 0
    for name, check in res["checks"].items():
        assert check["value"] <= check["limit"], name


def test_prove_fails_the_control_and_every_planted_fault():
    """The program passes the tiny cell's limits on two seeds; the fp8
    control, the half-batch fault and each planted fault of the reference
    put in the program's place each fail by at least one of them."""
    records, summary = prove_mellum2.main(
        ["--workload", CELL, "--seeds", "2147483659,11",
         "--control-seeds", "1", "--fault-seeds", "1"],
        need_tpu=False, spec_path=SPEC, data_dirs=[TINY, BENCH])
    assert summary == {"program_passed": [2147483659, 11],
                       "program_failed": [], "others_passed": {}}
    read = set(records[0]) - {"seed", "losses"}
    assert {n for n in read if not n.endswith("_where")} == {
        "program", "control_fp8", "fault_half_batch", "fault_no_renorm",
        "fault_no_window", "fault_no_yarn", "fault_router_cut"}
    # the planted faults leave the reference as it was
    assert A.reference.FAULT is None


# ------------------------------------------------ the entries and the files

def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_entries_are_there_once_and_the_configuration_keeps_the_catalog(
        real):
    spec, cfg, d = real
    cell = _named(spec["workloads"], REAL)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": REAL, "config": CONFIG, "traffic": MIX, "chips": 1}
    entry = _named(spec["configs"], CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts_held",
                                "vocab_size"]
    for text in (cell["why"], entry["why"]):
        assert 0 < len(text) <= 200
    for name in NEW:
        metric = _named(spec["per_layer"], name)
        assert metric["workloads"] == [REAL], name
        assert metric["moves"] == "train_tokens_per_s", name
        meta = harness.load_json(os.path.join(BENCH, "metrics",
                                              name + ".json"))
        assert (meta["unit"], meta["layer"]) == (metric["unit"],
                                                 metric["layer"]), name
    for name in APPENDED:
        assert REAL in _named(spec["per_layer"], name)["workloads"], name
    for name in ("train_step_host_ms", "flash_attn_roofline", "mfu.train"):
        assert REAL not in _named(spec["per_layer"], name)["workloads"]
    assert REAL in _named(spec["end_to_end"],
                          "train_tokens_per_s")["workloads"]
    # the published widths; the chip's share of experts and vocabulary
    assert (d["H"], d["heads"], d["kv_heads"], d["hd"], d["E"], d["K"],
            d["I"], d["window"]) == (2304, 32, 4, 128, 64, 8, 896, 1024)
    assert d["held"] == list(range(16)) and d["V"] == 24576 and d["L"] == 4
    assert d["kinds"] == ["sliding_attention"] * 3 + ["full_attention"]
    kw = cfg["program"]["kwargs"]
    for key, value in kw.items():
        if key in cfg:
            assert cfg[key] == value, key
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert cfg["published"] == {
        "num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304,
        "params": cfg["published"]["params"]}
    assert "stage 0" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    for key in ("qk_norm", "rotation", "yarn", "router", "mtp", "weights",
                "lr", "prng_impl"):
        assert key in cfg["assumed"], key
    train = cfg["training"]
    assert (train["precision"], train["amp"]["level"], train["vocab_used"],
            train["optimizer"]["name"]) == ("bfloat16", "O1", 24576, "AdamW")
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             MIX + ".json"))
    assert traffic == {"kind": "train_steps", "batch": 1, "seq": 8192,
                       "batches": 8, "in_flight": 2, "trace_seconds": 2.0}
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Mellum2-12B-A2.5B-Instruct"' in line][0]
    assert entry["source"] == cfg["_source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "vocab_size"}


def test_counts_against_hand_worked_numbers(real):
    """At the published widths: a layer outside its experts 21.39M
    parameters, an expert 6.193M, the embedding and the head 56.62M each,
    595.2M held in all; a step's operations at 1 x 8192 tokens."""
    _, _, d = real
    layer = 2304 * (2 * 4096 + 2 * 512 + 64) + 2 * 2304
    assert layer == 21_385_728
    assert A.expert_params(d) == 6_193_152
    held = 4 * (layer + 16 * A.expert_params(d)) + 2 * 24576 * 2304 + 2304
    assert round(held / 1e6, 1) == 595.2
    # window layers keep 1024 x 1025 / 2 + 7168 x 1024 pairs of 8192 x 8193
    # / 2; the full layer all of them
    assert A.pairs(8192, 1024) == 1024 * 1025 / 2 + 7168 * 1024
    assert A.pairs(8192, None) == 8192 * 8193 / 2
    assert 1 - A.pairs(8192, 1024) / A.pairs(8192, None) == pytest.approx(
        0.7656, abs=1e-3)
    picks = 8192 * 4 * 8 * 16 / 64
    flops = A.train_flops(1, 8192, d, picks)
    dense = 6 * (4 * 2304 * (2 * 4096 + 2 * 512 + 64) + 2304 * 24576) * 8192
    attn = 12 * 32 * 128 * (3 * A.pairs(8192, 1024) + A.pairs(8192, None))
    assert flops == pytest.approx(dense + attn + 6 * picks * 6_193_152)
    assert A.train_flops(1, 8192, d) == pytest.approx(flops)
    ops, nbytes = A.flash_call_cost(1, 8192, d, 1024, backward=True)
    assert ops == 8 * 32 * 128 * A.pairs(8192, 1024)
    assert nbytes == 4 * 8192 * 4096 * 2 + 4 * 8192 * 512 * 2
    ops, nbytes = A.expert_product_train_cost(1000, 16, d)
    assert (ops, nbytes) == (6 * 1000 * 6_193_152, 16 * 6_193_152 * 8)


# ---------------------------------------------------------------- readers

def _run(window_s=2.0, steps=4):
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             MIX + ".json"))
    return types.SimpleNamespace(
        config=cfg, traffic=traffic, devices=[0],
        peaks=harness.peaks_for("TPU v5 lite"), window=(10.0, 20.0),
        traced={"t0": 17.0, "t1": 19.0, "steps": steps},
        trace_summary={"window_s": window_s, "events": [], "modules": []})


def _params(name):
    return harness.load_json(os.path.join(BENCH, "metrics",
                                          name + ".json"))["params"]


def test_the_new_readers_on_a_made_up_trace_and_ring(real):
    from benchmark import flops
    from paddle_tpu.observability import get_tracer
    _, _, d = real
    tracer = get_tracer()
    tracer.clear()
    run = _run()
    # the parent's ring: steps without the counts; nothing is reported
    for t0 in (17.1, 17.5, 18.0, 18.5):
        tracer.record("train_step", t0, t0 + 0.01, args={"step": 1})
    assert mfu_train_arch.read(run, _params("mfu" + SFX)) is None
    assert expert_product_train_roofline.read(
        run, _params("moe_expert_product_roofline" + SFX)) is None
    tracer.clear()
    counts = {"routed_here": 70000, "routed_all": 262144,
              "experts_hit": 64, "expert_products": 108,
              "expert_rows": 73728}
    for t0 in (12.0, 17.1, 17.5, 18.0, 18.5):
        tracer.record("train_step", t0, t0 + 0.01,
                      args=dict(counts, step=1))
    ops = 4 * A.train_flops(1, 8192, d, 70000)
    assert mfu_train_arch.read(run, _params("mfu" + SFX)) == pytest.approx(
        100 * ops / (2.0 * 197e12))
    # the products' events: four steps of 108 x 4
    run.trace_summary["events"] = [("%ragged-dot-none.3 = f32", 17.2,
                                    17.2 + 1e-3)] * (4 * 108 * 4)
    least = 4 * flops.least_seconds(*A.expert_product_train_cost(
        70000, 64, d), run.peaks)[0]
    assert expert_product_train_roofline.read(
        run, _params("moe_expert_product_roofline" + SFX)) == pytest.approx(
            100 * least / (4 * 108 * 4 * 1e-3))
    # events the spans cannot account for: nothing
    run.trace_summary["events"] *= 3
    assert expert_product_train_roofline.read(
        run, _params("moe_expert_product_roofline" + SFX)) is None
    # the kernel's two names, one call a layer a step each
    p = _params("window_flash_train_roofline" + SFX)
    run.trace_summary["events"] = (
        [("%grouped_flash_fwd.1 = (bf16[1,8192,4096]) custom-call(", 17.3,
          17.302)] * 16
        + [("%grouped_flash_bwd.1 = (bf16[1,8192,4096]) custom-call(", 17.4,
            17.405)] * 16)
    least = 4 * sum(
        flops.least_seconds(*A.flash_call_cost(1, 8192, d, w, b),
                            run.peaks)[0]
        for w in (1024, 1024, 1024, None) for b in (False, True))
    assert flash_train_roofline.read(run, p) == pytest.approx(
        100 * least / (16 * 0.002 + 16 * 0.005))
    run.trace_summary["events"] = []
    assert flash_train_roofline.read(run, p) is None
    tracer.clear()
