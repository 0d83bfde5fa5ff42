"""What PR 34 adds to the benchmark, at the test-only sizes of `tiny/`
(`spec_moonlight.json`, `tiny-moonlight`): the `flood_streamed` kind's CPU
rehearsal over a `deepseek_v3` model, the architecture module's counts
against hand-worked numbers at the PUBLISHED sizes, the new reader's
arithmetic on a made-up ring, and `correct` coming out false for each
planted fault.  Nothing timed here is a device number.
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

from benchmark import harness, prove_deepseek_v3, run as bench_run  # noqa: E402
from benchmark.arch import deepseek_v3 as A  # noqa: E402
from benchmark.readers import (decode_roofline_arch,  # noqa: E402
                               experts_hit_share, mfu_serve_arch,
                               span_arg_share)

CELL, REAL = "tiny-moonlight-flood", "moonlight-serve-flood-longgen"
CONFIG = "moonlight-16b-a3b-7of27"
SPEC = os.path.join(TINY, "spec_moonlight.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SFX = ".serve_flood_longgen"
SPAN_METRICS = {"moe_experts_hit_pct" + SFX, "serve_step_host_ms" + SFX,
                "sched_batch_slots_pct" + SFX,
                "serve_slot_occupancy_pct" + SFX,
                "serve_prefill_mean_ms" + SFX, "kv_rows_live_pct" + SFX}
DEVICE_METRICS = {"mfu" + SFX, "device_idle_pct" + SFX,
                  "decode_flood_longgen_roofline",
                  "moe_expert_product_roofline" + SFX}


def rehearse(seconds=0.6, trace=1, seed=2147483659):
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
    assert res["attempted"] > 10 and res["notes"]["checked_tokens"] > 0
    # device metrics find no trace summary on the CPU and stay silent
    assert set(res["metrics"]) == SPAN_METRICS
    m = {k[:-len(SFX)]: v["value"] for k, v in res["metrics"].items()}
    # 4 slots x 3 picks over 8 experts, 2 routed layers of 3: most are hit
    assert 30 < m["moe_experts_hit_pct"] <= 100
    assert 0 < m["serve_slot_occupancy_pct"] <= m[
        "sched_batch_slots_pct"] <= 100
    assert m["serve_step_host_ms"] > 0 and m["serve_prefill_mean_ms"] > 0
    # requests of 7 to 50 rows in a pool of 4 slots x 64
    assert 5 < m["kv_rows_live_pct"] < 80
    assert res["end_to_end_of_traced_run"]["serve_tokens_per_s"] > 0
    assert res["checks"]["token_logit_gap"]["value"] < 1e-4
    assert res["checks"]["mismatched_token_share"]["value"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(prove_deepseek_v3.faults({
    "kv_lora_rank": 24})))
def test_run_is_not_correct_with_a_fault_planted_in_the_program(
        monkeypatch, fault):
    cfg = harness.load_json(os.path.join(TINY, "configs",
                                         "tiny-moonlight.json"))
    monkeypatch.setattr(*prove_deepseek_v3.faults(cfg)[fault]())
    res = rehearse(seconds=0.3, trace=0)
    assert res["correct"] is False
    gap = res["checks"]["token_logit_gap"]
    assert gap["value"] > 100 * gap["limit"]
    share = res["checks"]["mismatched_token_share"]
    assert share["value"] > 10 * share["limit"]


def test_prove_reads_the_program_the_control_and_a_fault(tmp_path):
    out = tmp_path / "prove.json"
    rec = prove_deepseek_v3.main(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.3", "--control",
         "1", "--fault", "selection_bias_left_out", "--flips", "1", "--out",
         str(out)], need_tpu=False, spec_path=SPEC, data_dirs=[TINY, BENCH])
    assert rec == json.loads(out.read_text())
    # the reference alone: rounding a routed layer's input to bfloat16
    # changes a pick now and then, in each of the two routed layers
    flips = rec["flips"]
    assert [l["layer"] for l in flips["layers"]] == [1, 2]
    assert flips["positions"] == rec["program"]["checked_tokens"]
    near = flips["best_two_logits_margin"]
    assert 0 <= near["share_under_0.01"] <= near["share_under_0.1"] <= 1
    assert near["median"] > 0
    assert 0 <= max(l["picks_changed_share"] for l in flips["layers"]) <= \
        flips["picks_changed_in_some_layer_share"] < 0.5
    assert all(l["margin_median"] > l["bfloat16_input_moves_a_score_by_median"]
               > 0 for l in flips["layers"])
    assert rec["fault"] == "selection_bias_left_out" and rec["failed"] == 0
    assert rec["program"]["correct"] is False
    assert rec["program"]["checked_tokens"] == rec["control_bfloat16"][
        "checked_tokens"] > 0
    # the faults are this call's alone
    from benchmark import prove_streamed
    assert "selection_bias_left_out" not in prove_streamed.FAULTS
    with pytest.raises(SystemExit, match="no deepseek_v3 cell"):
        prove_deepseek_v3.main(
            ["--workload", "tiny-cmdap-flood", "--seed", "5"], need_tpu=False,
            spec_path=os.path.join(TINY, "spec_cmdap.json"),
            data_dirs=[TINY, BENCH])


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
        "name": REAL, "config": CONFIG, "traffic": "flood_longgen_8k",
        "chips": 1}
    entry = _named(spec["configs"], CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    for name in SPAN_METRICS | DEVICE_METRICS:
        metric = _named(spec["per_layer"], name)
        assert metric["workloads"] == [REAL], name
        assert metric["moves"] == "serve_tokens_per_s", name
        meta = harness.load_json(os.path.join(BENCH, "metrics",
                                              name + ".json"))
        assert (meta["unit"], meta["layer"]) == (metric["unit"],
                                                 metric["layer"]), name
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           meta["reader"] + ".py")), name
    assert _named(spec["end_to_end"], "serve_tokens_per_s")["workloads"][
        -1] == REAL
    # the twins read the new cell with their `.serve_flood_mixed` parameters
    for twin, of in (("mfu", "mfu.serve_flood_mixed"),
                     ("device_idle_pct", "device_idle_pct.serve_flood_mixed"),
                     ("serve_step_host_ms",
                      "serve_step_host_ms.serve_flood_mixed"),
                     ("sched_batch_slots_pct",
                      "sched_batch_slots_pct.serve_flood_mixed"),
                     ("serve_slot_occupancy_pct",
                      "serve_slot_occupancy_pct.serve_flood_mixed"),
                     ("serve_prefill_mean_ms",
                      "serve_prefill_mean_ms.serve_flood_mixed"),
                     ("moe_expert_product_roofline",
                      "moe_expert_product_roofline")):
        assert harness.load_json(os.path.join(
            BENCH, "metrics", twin + SFX + ".json")) == harness.load_json(
                os.path.join(BENCH, "metrics", of + ".json")), twin
    # the published widths; every expert, head and row of the vocabulary
    assert (d["H"], d["heads"], d["nope"], d["rope"], d["vd"],
            d["latent"]) == (2048, 16, 128, 64, 128, 512)
    assert (d["E"], d["I"], d["K"], d["S"], d["I_dense"], d["V"]) == (
        64, 1408, 6, 2, 11264, 163840)
    assert d["held"] == list(range(64)) and d["scale"] == 2.446
    assert d["kinds"] == ["dense"] + ["moe"] * 6
    assert d["window"] == 8192          # rows_window == rows_full
    kw = cfg["program"]["kwargs"]
    for key, value in kw.items():
        if key in cfg:
            assert cfg[key] == value, key
    assert kw["dtype"] == cfg["serving"]["weights_dtype"] == "bfloat16"
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    for key in ("rope_pairing", "e_score_correction_bias_std",
                "kv_a_proj_with_mqa_std",
                "head_on_stage_0", "initializer_range", "prng_impl"):
        assert key in cfg["assumed"], key
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "flood_longgen_8k.json"))
    assert traffic["kind"] == "flood_streamed"
    assert traffic["prompt"]["max"] + traffic["output"]["max"] <= traffic[
        "engine"]["max_len"] == max(traffic["engine"]["prefill_buckets"])
    assert traffic["backlog"] <= traffic["engine"]["max_queue_depth"]
    assert traffic["engine"]["max_slots"] in (48, 32)
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Moonlight-16B-A3B"' in line][0]
    assert entry["source"] == cfg["_source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differ == {"num_hidden_layers"}


def test_counts_against_hand_worked_numbers(real):
    _, cfg, d = real
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    expert = 3 * 2048 * 1408
    assert (A.attn_params(d), A.expert_params(d)) == (attn, expert) == (
        13762560, 8650752)
    # 584.9M a routed layer, 82.97M the dense one (norm scales, bias too)
    routed = attn + 66 * expert + 2048 * 64 + 64 + 2 * 2048 + 512
    dense = attn + 3 * 2048 * 11264 + 2 * 2048 + 512
    assert A.layer_params(d, "moe") == routed == 584847936
    assert A.layer_params(d, "dense") == dense == 82973184
    whole = 2 * 163840 * 2048 + 2048 + dense + 6 * routed
    assert A.param_count(d) == whole
    assert round(whole * 2 / 1e9, 2) == 8.53            # GB in bfloat16
    # the published model: 26 routed layers
    assert round((whole + 20 * routed) / 1e9, 2) == 15.96
    # 1,152 bytes a row a layer; the cell's pool
    assert A.kv_row_bytes(d) == 1152
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "flood_longgen_8k.json"))
    pool = (traffic["engine"]["max_slots"] * traffic["engine"]["max_len"]
            * 7 * 1152)
    assert round(pool / 1e9, 2) in (3.17, 2.11)
    # one token over 5000 rows, absorbed: a row costs a head 576 + 512
    token = (7 * attn + 3 * 2048 * 11264
             + 6 * (8 * expert + 2048 * 64))
    assert A.decode_token_flops(5000, d) == (
        2 * token + 7 * 2 * 16 * 5000 * (576 + 512) + 2 * 163840 * 2048)
    assert A.decode_token_flops(5000, d, rows_window=3000) == \
        A.decode_token_flops(5000, d)
    # a prompt of 6000, expanded: a pair costs a head 192 + 128
    assert A.prefill_flops(6000, d) == pytest.approx(
        2 * 6000 * token + 7 * 2 * 16 * 320 * (6000 * 6001 / 2)
        + 2 * 163840 * 2048)
    assert round(A.prefill_flops(1, d) / 1e9, 2) == 1.83   # 1.16 + the head
    # a decode call of 4 steps, 1500 experts hit in all, 48 requests of
    # 2000 rows
    rows = 48 * 2000
    step = (7 * attn * 2 + 3 * 2048 * 11264 * 2
            + 6 * (2 * expert * 2 + 2049 * 64 * 4) + 163840 * 2048 * 2
            + 1152 * rows * 7)
    assert A.decode_call_bytes(d, 4, 1500, rows, rows) == (
        4 * step + 1500 * expert * 2)
    # outside the experts and the rows: 1.2 GB a step
    assert round((step - 1152 * rows * 7) / 1e9, 1) == 1.2
    assert A.expert_product_cost(10, 7, d) == (2 * 10 * expert,
                                               7 * expert * 2)
    seeds = {A.leaf_seed(s, l, k) for s in (0, 1, 2 ** 31 + 11)
             for l in range(-1, 7) for k in range(15)}
    assert len(seeds) == 3 * 8 * 15 and max(seeds) < 2 ** 62
    # a layer's leaves by kind, each with the program's name
    assert set(A.layer_layout(d, "dense")) - set(A.layer_layout(d, "moe")) \
        == {"wg", "wu", "wd"}
    assert A.program_name("bias", 3) == \
        "layers.3.mlp.experts.e_score_correction_bias"
    assert A.program_name("head", -1) == "lm_head"
    assert cfg["e_score_correction_bias_std"] == d["bias_std"] == 0.03
    assert cfg["kv_a_proj_with_mqa_std"] == d["kva_std"] == 0.05
    assert A.layer_layout(d, "moe")["wkva"][2] == 0.05 != d["std"]


# ------------------------------------------------------------- the readers

def _run(steps, window_s=3.0):
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "flood_longgen_8k.json"))
    return types.SimpleNamespace(
        config=cfg, traffic=traffic, engine_steps=steps, devices=[0],
        peaks=harness.peaks_for("TPU v5 lite"), window=(10.0, 20.0),
        traced={"t0": 17.0, "t1": 20.0},
        trace_summary={"window_s": window_s, "events": [], "modules": []})


def test_the_accepted_readers_count_this_architecture(real):
    _, _, d = real
    step = {"traced": True, "admitted_plens": [1000], "admitted": 1,
            "tokens": 193, "running": 48, "rows_full": 48 * 2000,
            "rows_window": 48 * 2000, "experts_hit": 1500}
    run = _run([step, dict(step, traced=False)])
    ops = A.prefill_flops(1000, d) + 192 * A.decode_token_flops(2000, d)
    assert mfu_serve_arch.read(run, {}) == pytest.approx(
        100 * ops / (3.0 * 197e12))
    run.trace_summary["modules"] = [("jit_decode(123)", 17.5, 17.6)]
    run.engine_steps = [step]
    least = A.decode_call_bytes(d, 4, 1500, 48 * 2000, 48 * 2000) / 819e9
    assert decode_roofline_arch.read(
        run, {"program": r"^jit_decode\("}) == pytest.approx(
            100 * least / 0.1)


def test_the_new_readers_on_a_hand_made_ring(real):
    from paddle_tpu.observability import get_tracer
    _, _, d = real
    tracer = get_tracer()
    tracer.clear()
    run = _run([])
    hits, live = (harness.load_json(os.path.join(
        BENCH, "metrics", name + SFX + ".json"))["params"] for name in (
            "moe_experts_hit_pct", "kv_rows_live_pct"))
    # the parent's ring: the spans without the counts
    tracer.record("serving_decode", 18.0, 18.1, args={"active": 48})
    assert experts_hit_share.read(run, hits) is None
    assert span_arg_share.read(run, live) is None
    tracer.clear()
    pool = 4 * 7 * 48 * 8192
    for t0, rows, hit in ((12.0, 2000000, 1400), (18.0, 2400000, 1500),
                          (18.5, 2600000, 1510), (25.0, 9, 9)):
        tracer.record("serving_decode", t0, t0 + 0.06, args={
            "active": 48, "experts_hit": hit, "kv_rows_live": rows,
            "kv_rows_pool": pool})
    # of 64 experts x 6 ROUTED layers (not 7) x 4 steps a call
    assert experts_hit_share.read(run, hits) == pytest.approx(
        100 * (1400 + 1500 + 1510) / (3 * 64 * 6 * 4))
    assert experts_hit_share.share([], d, ["moe"], 4) is None
    assert span_arg_share.read(run, live) == pytest.approx(
        100 * (2000000 + 2400000 + 2600000) / (3 * pool))
    tracer.clear()
