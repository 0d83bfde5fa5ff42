"""What the Nemotron-H cell and the four-chip GPT-2 training cell add to the
benchmark, at the test-only sizes of `tiny/` (`spec_nemotron.json`,
`tiny-nemotron`: 5 layers MEM*E, 8 experts of which 4 held, 160 positions;
`spec.json`'s `tiny-gpt2-train-dp4` on four virtual CPU devices): the
`flood_streamed` kind's CPU rehearsal over a `nemotron_h` model, the
architecture module's counts against hand-worked numbers at the PUBLISHED
sizes, the new reader's arithmetic on a made-up trace summary and ring, and
`correct` coming out false for each planted fault.  Nothing timed here is a
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

from benchmark import harness, prove_dp, prove_nemotron_h  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.arch import nemotron_h as A  # noqa: E402
from benchmark.readers import decode_roofline_state  # noqa: E402

CELL, REAL = "tiny-nemotron-flood", "nemotron3-serve-flood-reason"
CONFIG, MIX = "nemotron3-super-1of4-11of88", "flood_reason_8k"
DP4, DP4_MIX = "gpt2m-train-dp4", "train_b16_s1024_dp4"
SPEC = os.path.join(TINY, "spec_nemotron.json")
CATALOG = os.environ.get("MODEL_CATALOG_JSONL", "")  # architectures.jsonl
SFX = ".serve_flood_reason"
SPAN_METRICS = {"ssm_state_live_pct" + SFX, "kv_rows_live_pct" + SFX,
                "moe_experts_hit_pct" + SFX, "serve_step_host_ms" + SFX,
                "sched_batch_slots_pct" + SFX,
                "serve_slot_occupancy_pct" + SFX,
                "serve_prefill_mean_ms" + SFX}
DEVICE_METRICS = {"mfu" + SFX, "device_idle_pct" + SFX,
                  "decode_flood_reason_roofline",
                  "moe_expert_product_roofline" + SFX}
SHARED = {"slot_held_by_prefill_pct", "chip_wait_on_host_pct",
          "python_gc_pct"}


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
    assert res["attempted"] > 8 and res["notes"]["checked_tokens"] > 0
    # device metrics find no trace summary on the CPU and stay silent; the
    # host's hold and the collector's pauses read the program's spans
    assert set(res["metrics"]) - SHARED == SPAN_METRICS
    m = {k[:-len(SFX)]: v["value"] for k, v in res["metrics"].items()
         if k.endswith(SFX)}
    # 4 slots x 160 rows, requests of 10 to 130 rows: some rows alive
    assert 5 < m["kv_rows_live_pct"] < 95
    # a decode call's state: every slot's read and written, the seated
    # ones advanced
    assert 0 < m["ssm_state_live_pct"] == pytest.approx(
        m["sched_batch_slots_pct"])
    assert 0 < m["moe_experts_hit_pct"] <= 100
    assert m["serve_step_host_ms"] > 0 and m["serve_prefill_mean_ms"] > 0
    assert res["checks"]["token_logit_gap"]["value"] < 2e-4
    assert res["checks"]["mismatched_token_share"]["value"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(prove_nemotron_h.FAULTS))
def test_run_is_not_correct_with_a_fault_planted_in_the_program(
        monkeypatch, fault):
    monkeypatch.setattr(*prove_nemotron_h.FAULTS[fault]())
    res = rehearse(seconds=0.3, trace=0)
    assert res["correct"] is False
    gap = res["checks"]["token_logit_gap"]
    assert gap["value"] > 100 * gap["limit"]


def test_prove_reads_the_program_the_control_and_faults_in_one_process(
        tmp_path):
    out = tmp_path / "prove.json"
    rec = prove_nemotron_h.main(
        ["--workload", CELL, "--runs", "5:control,6:norm_over_one_group",
         "--seconds", "0.3", "--out", str(out)],
        need_tpu=False, spec_path=SPEC, data_dirs=[TINY, BENCH])
    assert rec == json.loads(out.read_text())
    assert [r["fault"] for r in rec] == [None, "norm_over_one_group"]
    assert rec[0]["program"]["correct"] is True
    assert rec[0]["control_bfloat16"]["correct"] is False
    assert rec[1]["program"]["correct"] is False
    # the faults are each run's alone
    from benchmark import prove_streamed
    from paddle_tpu.models import nemotron_h
    assert "norm_over_one_group" not in prove_streamed.FAULTS
    assert nemotron_h.gated_group_norm.__name__ == "gated_group_norm"
    with pytest.raises(SystemExit, match="no nemotron_h cell"):
        prove_nemotron_h.main(
            ["--workload", "tiny-evabyte-flood", "--runs", "5"],
            need_tpu=False, spec_path=os.path.join(TINY, "spec_evabyte.json"),
            data_dirs=[TINY, BENCH])


# ------------------------------------------------ the four-chip train cell

def test_the_data_parallel_cell_is_correct_and_its_exchange_fault_is_not():
    """`tiny-gpt2-train-dp4` over four virtual CPU devices: the sharded
    step's readings pass the cell's limits, the fp8 control's do not, and
    neither do chip 0's alone (each chip applying its own rows' gradient)."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices (conftest asks for eight)")
    rec = prove_dp.main(["--workload", "tiny-gpt2-train-dp4", "--seeds",
                         "7,2147483659"],
                        need_tpu=False,
                        spec_path=os.path.join(TINY, "spec.json"),
                        data_dirs=[TINY, BENCH])
    assert [r["program"]["correct"] for r in rec] == [True, True]
    assert rec[0]["control_fp8"]["correct"] is False
    assert [r["fault_exchange_left_out"]["correct"] for r in rec] == [
        False, False]
    # a quarter of the rows moves the first loss and every gradient
    fault = rec[0]["fault_exchange_left_out"]
    assert fault["grad_whole_norm_gap"] > 10 * rec[0]["program"][
        "grad_whole_norm_gap"]
    # the reference's readings alone, on one device, are the same numbers
    alone = prove_dp.main(["--workload", "tiny-gpt2-train-dp4", "--seeds",
                           "7", "--program", "0"],
                          need_tpu=False,
                          spec_path=os.path.join(TINY, "spec.json"),
                          data_dirs=[TINY, BENCH])
    assert "program" not in alone[0]
    assert alone[0]["fault_exchange_left_out"] == fault


def test_chip_rows_are_the_first_chips_share_of_every_step():
    import numpy as np
    feeds = [(np.arange(16).reshape(16, 1), np.ones((16, 3)))] * 3
    rows = prove_dp.chip_rows(feeds, 4)
    assert [r[0].ravel().tolist() for r in rows] == [[0, 1, 2, 3]] * 3
    assert rows[0][1].shape == (4, 3)


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
                                "vocab_size", "num_nextn_predict_layers"]
    for text in (cell["why"], entry["why"]):
        assert 0 < len(text) <= 200
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
    assert REAL in _named(spec["end_to_end"],
                          "serve_tokens_per_s")["workloads"]
    # the twins read the new cell with their `.serve_flood_longgen`
    # parameters
    for twin in ("mfu", "device_idle_pct", "serve_step_host_ms",
                 "sched_batch_slots_pct", "serve_slot_occupancy_pct",
                 "serve_prefill_mean_ms", "kv_rows_live_pct",
                 "moe_experts_hit_pct", "moe_expert_product_roofline"):
        assert harness.load_json(os.path.join(
            BENCH, "metrics", twin + SFX + ".json")) == harness.load_json(
                os.path.join(BENCH, "metrics",
                             twin + ".serve_flood_longgen.json")), twin
    # the published widths; the chip's share of experts and vocabulary
    assert (d["H"], d["heads"], d["kv_heads"], d["hd"], d["m_heads"],
            d["m_hd"], d["groups"], d["state"], d["conv"], d["chunk"],
            d["E"], d["K"], d["I"], d["latent"], d["SI"]) == (
                4096, 32, 2, 128, 128, 64, 8, 128, 4, 128, 512, 22, 2688,
                1024, 5376)
    assert d["held"] == list(range(128)) and d["V"] == 32768
    assert "".join({"mamba": "M", "moe": "E", "attention": "*"}[k]
                   for k in d["kinds"]) == "MEMEMEM*EME"
    kw = cfg["program"]["kwargs"]
    for key, value in kw.items():
        if key in cfg:
            assert cfg[key] == value, key
    assert kw["dtype"] == cfg["serving"]["weights_dtype"] == "bfloat16"
    assert kw["layer_norm_epsilon"] == cfg["norm_eps"]
    assert cfg["published"]["num_hidden_layers"] == 88
    assert "stage 0" in cfg["deployment"]
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    for key in ("no_rotation", "latent", "ssm_state_dtype", "mamba_init",
                "conv_init_std", "e_score_correction_bias_std",
                "head_on_stage_0", "initializer_range", "prng_impl"):
        assert key in cfg["assumed"], key
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             MIX + ".json"))
    assert traffic["kind"] == "flood_streamed"
    assert traffic["prompt"]["max"] + traffic["output"]["max"] <= traffic[
        "engine"]["max_len"] == max(traffic["engine"]["prefill_buckets"])
    assert traffic["backlog"] <= traffic["engine"]["max_queue_depth"]
    assert traffic["engine"]["max_slots"] in (96, 64)
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line][0]
    assert entry["source"] == cfg["_source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "vocab_size",
                      "num_nextn_predict_layers"}


def test_the_four_chip_cell_is_the_one_chip_cells_configuration(real):
    spec = real[0]
    cell = _named(spec["workloads"], DP4)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": DP4, "config": "gpt2-medium", "traffic": DP4_MIX,
        "chips": 4}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 4)
    assert DP4 in _named(spec["end_to_end"],
                         "train_tokens_per_s")["workloads"]
    one = harness.load_json(os.path.join(BENCH, "traffic",
                                         "train_b4_s1024.json"))
    four = harness.load_json(os.path.join(BENCH, "traffic",
                                          DP4_MIX + ".json"))
    assert four.pop("mesh") == {"dp": 4}
    # four chips of the one-chip cell's rows each
    assert four.pop("batch") == 4 * one.pop("batch")
    assert four == one
    limits = harness.load_json(os.path.join(BENCH, "cells", DP4 + ".json"))
    assert set(limits["limits"]) >= {"loss_gap", "grad_whole_norm_gap",
                                     "change_norm_gap", "compiles_in_window"}


def test_counts_against_hand_worked_numbers(real):
    _, cfg, d = real
    mamba = (4096 * 18560 + 10240 * 4 + 10240 + 3 * 128 + 8192
             + 8192 * 4096)
    assert A.mamba_params(d) == mamba
    assert round(mamba / 1e6, 2) == 109.64
    assert A.attn_params(d) == 2 * 4096 * 128 * 34
    assert round(A.attn_params(d) / 1e6, 2) == 35.65
    assert A.expert_params(d) == 2 * 1024 * 2688
    assert round(A.expert_params(d) / 1e6, 3) == 5.505
    outside = 4097 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert round(outside / 1e6, 2) == 54.53
    assert A.layer_params(d, "moe") == outside + 128 * 2 * 1024 * 2688 + 4096
    assert round(A.layer_params(d, "moe") / 1e6, 2) == 759.17
    # the chip's 4.648B = 9.30 GB in bfloat16
    assert round(A.param_count(d) / 1e9, 3) == 4.648
    # a Mamba slot-layer's state: f32 128 x 64 x 128 and bf16 3 x 10240
    assert A.ssm_state_bytes(d) == 128 * 64 * 128 * 4 + 3 * 10240 * 2
    assert round(A.ssm_state_bytes(d) / 1e6, 2) == 4.26
    assert A.kv_row_bytes(d) == 1024
    assert A.expert_bytes(d) == 11010048
    # the cell's cache: 96 x 5 slot-layers of state, 96 x 8192 rows
    e = harness.load_json(os.path.join(BENCH, "traffic",
                                       MIX + ".json"))["engine"]
    cache = (e["max_slots"] * 5 * A.ssm_state_bytes(d)
             + e["max_slots"] * e["max_len"] * A.kv_row_bytes(d))
    assert round(cache / 1e9, 2) == 2.85
    # a decode call's least bytes, by hand
    got = A.decode_call_cost(d, 4, 10, 7, 1000)
    assert got == 4 * A.decode_weight_bytes(d) + 10 * 11010048 + 2 * 7 * (
        A.ssm_state_bytes(d)) + 1000 * 1024
    assert 2.0e9 < A.decode_weight_bytes(d) < 2.1e9
    # a token's products: 5 M, 1 attention, 5 E layers with 22 x 128 / 512
    # of its picks here
    token = (5 * 4096 * (2 * 8192 + 10240 + 128) + 2 * 4096 * 128 * 34
             + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
                    + 22 * 128 / 512 * 2 * 1024 * 2688))
    scan = 5 * 4 * 8192 * 128
    assert A.decode_token_flops(100, d) == pytest.approx(
        2 * token + scan + 4 * 32 * 128 * 100 + 2 * 32768 * 4096)
    assert A.prefill_flops(1000, d) == pytest.approx(
        2000 * token + 1000 * scan + 4 * 32 * 128 * 1000 * 1001 / 2
        + 2 * 32768 * 4096)
    seeds = {A.leaf_seed(s, l, k) for s in (0, 1, 2 ** 31 + 11)
             for l in range(-1, 11) for k in range(12)}
    assert len(seeds) == 3 * 12 * 12 and max(seeds) < 2 ** 62
    assert A.program_name("e_up", 1) == "layers.1.mixer.experts.up"
    assert A.program_name("lnf_g", -1) == "norm_f"


def test_the_mamba_leaves_are_drawn_as_the_source_initialises_them(real):
    import numpy as np
    from benchmark import weights as W
    _, cfg, d = real
    small = dict(d, H=8, m_heads=128, d_inner=16, conv_dim=24)
    leaves = dict(A.make_leaves(W.make, small, 4000000017, 0))
    np.testing.assert_allclose(np.exp(np.asarray(leaves["A_log"])),
                               np.arange(1, 129), rtol=1e-5)
    assert np.all(np.asarray(leaves["D"]) == 1)
    dt = np.log1p(np.exp(np.asarray(leaves["dt_bias"])))     # softplus
    assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.std(np.asarray(leaves["conv_w"])) == pytest.approx(0.2887,
                                                                 rel=0.2)


# ------------------------------------------------------------- the reader

def _run(window_s=3.0):
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             MIX + ".json"))
    return types.SimpleNamespace(
        config=cfg, traffic=traffic, engine_steps=[], devices=[0],
        peaks=harness.peaks_for("TPU v5 lite"), window=(10.0, 20.0),
        traced={"t0": 17.0, "t1": 20.0},
        trace_summary={"window_s": window_s, "events": [], "modules": []})


def test_the_state_roofline_reader_on_a_made_up_trace_and_ring(real):
    from paddle_tpu.observability import get_tracer
    _, _, d = real
    tracer = get_tracer()
    tracer.clear()
    run = _run()
    params = harness.load_json(os.path.join(
        BENCH, "metrics", "decode_flood_reason_roofline.json"))["params"]
    run.trace_summary["modules"] = [("jit_decode(7)", 17.5, 17.55),
                                    ("jit_decode(7)", 18.5, 18.55),
                                    ("jit_prefill(3)", 19.0, 19.4)]
    # the parent's ring: the spans without the counts
    tracer.record("serving_decode", 18.0, 18.1, args={"active": 16})
    assert decode_roofline_state.read(run, params) is None
    tracer.clear()
    for t0, hit, live in ((12.0, 9, 9), (17.5, 2400, 300000),
                          (18.5, 2500, 320000), (25.0, 9, 9)):
        tracer.record("serving_decode", t0, t0 + 0.05, args={
            "active": 96, "experts_hit": hit, "ssm_state_pool": 96 * 5 * 4,
            "kv_rows_live": live})
    least = (2 * 4 * A.decode_weight_bytes(d) + 4900 * A.expert_bytes(d)
             + 2 * 2 * 96 * 5 * 4 * A.ssm_state_bytes(d)
             + 620000 * 1024) / 819e9
    assert decode_roofline_state.read(run, params) == pytest.approx(
        100 * least / 0.1)
    tracer.clear()
