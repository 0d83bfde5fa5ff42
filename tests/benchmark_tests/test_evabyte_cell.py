"""What PR 36 adds to the benchmark, at the test-only sizes of `tiny/`
(`spec_evabyte.json`, `tiny-evabyte`: window 32, chunk 4, 160 positions):
the `flood_streamed` kind's CPU rehearsal over an `evabyte` model, the
architecture module's counts against hand-worked numbers at the PUBLISHED
sizes, the new readers' arithmetic on a made-up trace summary and ring, and
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

from benchmark import harness, prove_evabyte, run as bench_run  # noqa: E402
from benchmark.arch import evabyte as A  # noqa: E402
from benchmark.readers import (decode_roofline_rows,  # noqa: E402
                               mfu_serve_arch, prefill_attention_roofline,
                               span_arg_share)

CELL, REAL = "tiny-evabyte-flood", "evabyte-serve-flood-longctx"
CONFIG, MIX = "evabyte-6.5b-8of32", "flood_longctx_32k"
SPEC = os.path.join(TINY, "spec_evabyte.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SFX = ".serve_flood_longctx"
SPAN_METRICS = {"kv_rows_live_pct" + SFX, "kv_summary_rows_pct" + SFX,
                "serve_step_host_ms" + SFX, "sched_batch_slots_pct" + SFX,
                "serve_slot_occupancy_pct" + SFX,
                "serve_prefill_mean_ms" + SFX}
DEVICE_METRICS = {"mfu" + SFX, "device_idle_pct" + SFX,
                  "decode_flood_longctx_roofline",
                  "eva_prefill_attention_roofline" + SFX}


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
    # device metrics find no trace summary on the CPU and stay silent
    assert set(res["metrics"]) == SPAN_METRICS
    m = {k[:-len(SFX)]: v["value"] for k, v in res["metrics"].items()}
    # requests of 10 to 130 rows in 4 slots x (32 + 40) rows a layer: a
    # ring of 32 is mostly alive, and most requests have passed a window
    assert 10 < m["kv_rows_live_pct"] < 90
    assert 5 < m["kv_summary_rows_pct"] < 80
    assert 0 < m["serve_slot_occupancy_pct"] <= m[
        "sched_batch_slots_pct"] <= 100
    assert m["serve_step_host_ms"] > 0 and m["serve_prefill_mean_ms"] > 0
    assert res["end_to_end_of_traced_run"]["serve_tokens_per_s"] > 0
    assert res["checks"]["token_logit_gap"]["value"] < 2e-4
    assert res["checks"]["mismatched_token_share"]["value"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(prove_evabyte.FAULTS))
def test_run_is_not_correct_with_a_fault_planted_in_the_program(
        monkeypatch, fault):
    monkeypatch.setattr(*prove_evabyte.FAULTS[fault]())
    res = rehearse(seconds=0.3, trace=0)
    assert res["correct"] is False
    gap = res["checks"]["token_logit_gap"]
    assert gap["value"] > 100 * gap["limit"]
    share = res["checks"]["mismatched_token_share"]
    assert share["value"] > 5 * share["limit"]


def test_prove_reads_the_program_the_control_and_a_fault(tmp_path):
    out = tmp_path / "prove.json"
    rec = prove_evabyte.main(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.3", "--control",
         "1", "--fault", "summaries_left_out", "--out", str(out)],
        need_tpu=False, spec_path=SPEC, data_dirs=[TINY, BENCH])
    assert rec == json.loads(out.read_text())
    assert rec["fault"] == "summaries_left_out" and rec["failed"] == 0
    assert rec["program"]["correct"] is False
    assert rec["program"]["checked_tokens"] == rec["control_bfloat16"][
        "checked_tokens"] > 0
    # the faults are this call's alone
    from benchmark import prove_streamed
    assert "summaries_left_out" not in prove_streamed.FAULTS
    with pytest.raises(SystemExit, match="no evabyte cell"):
        prove_evabyte.main(
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
        "name": REAL, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert len(cell["why"]) <= 200
    entry = _named(spec["configs"], CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert len(entry["why"]) <= 200
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
    # `in`, not last: the next cell is appended behind this one
    assert REAL in _named(spec["end_to_end"],
                          "serve_tokens_per_s")["workloads"]
    # the twins read the new cell with their `.serve_flood_longgen`
    # parameters
    for twin in ("mfu", "device_idle_pct", "serve_step_host_ms",
                 "sched_batch_slots_pct", "serve_slot_occupancy_pct",
                 "serve_prefill_mean_ms", "kv_rows_live_pct"):
        assert harness.load_json(os.path.join(
            BENCH, "metrics", twin + SFX + ".json")) == harness.load_json(
                os.path.join(BENCH, "metrics",
                             twin + ".serve_flood_longgen.json")), twin
    # the published widths; every head and row of the vocabulary
    assert (d["H"], d["heads"], d["hd"], d["I"], d["window"], d["chunk"],
            d["pred_heads"], d["V"], d["L"]) == (
                4096, 32, 128, 11008, 2048, 16, 8, 320, 8)
    assert d["kinds"] == ["eva"] * 8 and d["theta"] == 100000.0
    kw = cfg["program"]["kwargs"]
    for key, value in kw.items():
        if key in cfg:
            assert cfg[key] == value, key
    assert kw["dtype"] == cfg["serving"]["weights_dtype"] == "bfloat16"
    assert cfg["published"]["num_hidden_layers"] == 32
    assert "stage 0" in cfg["deployment"]
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    for key in ("rope_pairing", "keys_rotated_before_pooling",
                "pooling_scale", "pred_heads_layout", "head_on_stage_0",
                "initializer_range", "qk_proj_std", "adaptive_phi_std",
                "adaptive_mu_k_std", "summary_rewritten_every_step",
                "prng_impl"):
        assert key in cfg["assumed"], key
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             MIX + ".json"))
    assert traffic["kind"] == "flood_streamed"
    assert traffic["prompt"]["max"] + traffic["output"]["max"] <= traffic[
        "engine"]["max_len"] == max(traffic["engine"]["prefill_buckets"])
    assert traffic["backlog"] <= traffic["engine"]["max_queue_depth"]
    assert traffic["engine"]["max_slots"] in (16, 12)
    assert all(b % d["window"] == 0
               for b in traffic["engine"]["prefill_buckets"])
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"EvaByte"' in line][0]
    assert entry["source"] == cfg["_source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differ == {"num_hidden_layers"}


def test_counts_against_hand_worked_numbers(real):
    _, cfg, d = real
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert A.layer_params(d) == layer == 202391552
    whole = 8 * layer + 320 * 4096 + 4096 + 4096 * 2560
    assert A.param_count(d) == whole
    assert round(whole * 2 / 1e9, 2) == 3.26            # GB in bfloat16
    # the published model: 32 layers
    assert round((whole + 24 * layer) / 1e9, 2) == 6.49
    # 16,384 bytes a row a layer, a ring's or a summary's; the cell's pool
    assert A.kv_row_bytes(d) == 16384
    e = harness.load_json(os.path.join(BENCH, "traffic",
                                       MIX + ".json"))["engine"]
    pool = e["max_slots"] * 8 * (2048 + e["max_len"] // 16) * 16384
    assert round(pool / 1e9, 2) in (8.59, 6.44)
    # what a step reads of the weights: the layers and head 0
    assert A.decode_weight_bytes(d) == (8 * layer + 4096 + 320 * 4096) * 2
    assert round(A.decode_weight_bytes(d) / 1e9, 2) == 3.24
    # rows alive and summaries seen at a position
    assert (A.ring_rows(0, d), A.summary_rows(0, d)) == (1, 0)
    assert (A.ring_rows(2047, d), A.summary_rows(2047, d)) == (2048, 0)
    assert (A.ring_rows(2048, d), A.summary_rows(2048, d)) == (1, 128)
    assert (A.ring_rows(32767, d), A.summary_rows(32767, d)) == (2048, 1920)
    # one token at position 5000: 905 ring rows and 256 summaries, a row a
    # head 2 x 128 for the score and as much for the output; the chunk's 16
    # rows pooled
    token = 8 * (4 * 4096 ** 2 + 3 * 4096 * 11008)
    assert A.decode_token_flops(5000, d) == (
        2 * token + 8 * 4 * 4096 * (905 + 256) + 8 * 6 * 4096 * 16
        + 2 * 320 * 4096)
    assert A.decode_token_flops(5000.7, d, rows_window=2048) == \
        A.decode_token_flops(5000, d)
    # a prompt of 6000: two whole windows and 1904 rows of a third
    pairs = (2 * 2048 * 2049 / 2 + 1904 * 1905 / 2
             + 2048 * 128 + 1904 * 256)
    assert A.prefill_flops(6000, d) == pytest.approx(
        2 * 6000 * token + 8 * 4 * 4096 * pairs + 8 * 6 * 4096 * 6000
        + 2 * 320 * 4096)
    # a whole bucket of 32768: ISSUE 36's sum over windows
    ops, nbytes = A.prefill_attention_cost(32768, d)
    assert ops == 4 * 4096 * (16 * 2048 * 2049 / 2 + 2048 * 128 * 120)
    assert nbytes == 8192 * (4 * 32768 + 2 * 128 * 120)
    # attention is a few percent of a prompt's dense products at any length
    for n in (2048, 8192, 32768):
        assert 8 * A.prefill_attention_cost(n, d)[0] < 0.1 * 2 * n * token
    seeds = {A.leaf_seed(s, l, k) for s in (0, 1, 2 ** 31 + 11)
             for l in range(-1, 8) for k in range(11)}
    assert len(seeds) == 3 * 9 * 11 and max(seeds) < 2 ** 62
    assert A.program_name("phi", 3) == "layers.3.self_attn.adaptive_phi"
    assert A.program_name("head", -1) == "lm_head"
    layout = A.layer_layout(d)
    assert layout["wq"][2] == layout["wk"][2] == cfg["qk_proj_std"] == 0.024
    assert layout["wv"][2] == cfg["initializer_range"] == 0.01275
    assert (layout["phi"][2], layout["mu"][2]) == (1.0, 1.0)
    assert layout["ln1_g"][2] == 0.0        # 1 + g = 1


# ------------------------------------------------------------- the readers

def _run(steps, window_s=3.0):
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             MIX + ".json"))
    return types.SimpleNamespace(
        config=cfg, traffic=traffic, engine_steps=steps, devices=[0],
        peaks=harness.peaks_for("TPU v5 lite"), window=(10.0, 20.0),
        traced={"t0": 17.0, "t1": 20.0},
        trace_summary={"window_s": window_s, "events": [], "modules": []})


def test_the_accepted_mfu_reader_counts_this_architecture(real):
    _, _, d = real
    step = {"traced": True, "admitted_plens": [9000], "admitted": 1,
            "tokens": 65, "running": 16, "rows_full": 16 * 7000,
            "rows_window": 16 * 2048}
    run = _run([step, dict(step, traced=False)])
    ops = A.prefill_flops(9000, d) + 64 * A.decode_token_flops(7000, d)
    assert mfu_serve_arch.read(run, {}) == pytest.approx(
        100 * ops / (3.0 * 197e12))


def test_the_new_readers_on_a_made_up_trace_summary_and_ring(real):
    from paddle_tpu.observability import get_tracer
    _, _, d = real
    tracer = get_tracer()
    tracer.clear()
    run = _run([])
    meta = lambda name: harness.load_json(os.path.join(  # noqa: E731
        BENCH, "metrics", name + ".json"))["params"]
    decode = meta("decode_flood_longctx_roofline")
    kernel = meta("eva_prefill_attention_roofline" + SFX)
    summ = meta("kv_summary_rows_pct" + SFX)
    run.trace_summary["modules"] = [("jit_decode(7)", 17.5, 17.6),
                                    ("jit_decode(7)", 18.5, 18.6),
                                    ("jit_prefill(3)", 19.0, 19.4)]
    # the parent's ring: the spans without the counts
    tracer.record("serving_decode", 18.0, 18.1, args={"active": 16})
    tracer.record("serving_admit", 18.0, 18.1, args={"plen": 5})
    assert decode_roofline_rows.read(run, decode) is None
    assert prefill_attention_roofline.read(run, kernel) is None
    assert span_arg_share.read(run, summ) is None
    tracer.clear()
    pool = 4 * 8 * 16 * 4096
    for t0, live, summaries in ((12.0, 900000, 300000),
                                (17.5, 1000000, 400000),
                                (18.5, 1200000, 500000), (25.0, 9, 9)):
        tracer.record("serving_decode", t0, t0 + 0.09, args={
            "active": 16, "kv_rows_live": live, "kv_rows_pool": pool,
            "kv_rows_summary": summaries})
    # two traced calls of four steps: the weights four times each and the
    # rows the spans say were alive, at 819 GB/s, over 0.2 s of the program
    least = (2 * 4 * A.decode_weight_bytes(d)
             + 2200000 * 16384) / 819e9
    assert decode_roofline_rows.read(run, decode) == pytest.approx(
        100 * least / 0.2)
    assert least < 0.2
    # in the window (10 to 20 s): three spans
    assert span_arg_share.read(run, summ) == pytest.approx(
        100 * 1200000 / 3100000)
    # one prompt in the 4096 bucket: 2 windows x 8 layers of events
    tracer.record("serving_admit", 19.0, 19.4, args={
        "plen": 3000, "bucket": 4096})
    run.trace_summary["events"] = [
        (f"%eva_prefill_attention.{i} = (bf16[1,2048,4096]) custom-call(",
         19.0 + i * 0.001, 19.0 + i * 0.001 + 0.0005) for i in range(16)
    ] + [("%fusion.3 = bf16[2048]", 19.2, 19.3)]
    ops, nbytes = A.prefill_attention_cost(4096, d)
    assert ops / 197e12 > nbytes / 819e9        # bound by operations
    assert prefill_attention_roofline.read(run, kernel) == pytest.approx(
        100 * 8 * (ops / 197e12) / (16 * 0.0005))
    # events of more than an edge call beyond the spans: nothing
    run.trace_summary["events"] = run.trace_summary["events"] * 4
    assert prefill_attention_roofline.read(run, kernel) is None
    tracer.clear()
