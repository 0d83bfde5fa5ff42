"""What PR 30 adds to the benchmark, at the test-only sizes of `tiny/`
(`spec_cmdap.json`, `tiny-cmdap`): the `flood_streamed` kind's CPU
rehearsal, the architecture module's counts against hand-worked numbers,
the new readers' arithmetic, and `correct` coming out false for a planted
fault.  Nothing timed here is a device number.
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

from benchmark import harness, prove_streamed, run as bench_run  # noqa: E402
from benchmark.arch import cohere2_moe as A  # noqa: E402
from benchmark.readers import (decode_roofline_arch,  # noqa: E402
                               expert_product_roofline, mfu_serve_arch,
                               span_arg_share)

CELL, REAL = "tiny-cmdap-flood", "cmdap-serve-flood-mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SPAN_METRICS = {"moe_picks_here_pct", "moe_experts_hit_pct",
                "serve_step_host_ms.serve_flood_mixed",
                "sched_batch_slots_pct.serve_flood_mixed",
                "serve_slot_occupancy_pct.serve_flood_mixed"}
DEVICE_METRICS = {"mfu.serve_flood_mixed", "device_idle_pct.serve_flood_mixed",
                  "decode_flood_mixed_roofline",
                  "moe_expert_product_roofline"}


def rehearse(seconds=0.6, trace=1, seed=2147483659):
    files = harness.Files(os.path.join(TINY, "spec_cmdap.json"),
                          [TINY, BENCH])
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
    cfg = harness.load_json(os.path.join(
        BENCH, "configs", "command-a-plus-1of8.json"))
    return spec, cfg, A.dims(cfg)


# ----------------------------------------------------------- the rehearsal

def test_traced_rehearsal_is_correct_and_reports_what_a_cpu_can_give():
    res = rehearse()
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10 and res["notes"]["checked_tokens"] > 0
    # device metrics find no trace summary on the CPU and stay silent
    assert set(res["metrics"]) == SPAN_METRICS
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # 2 of 8 experts held: even routing sends a quarter of the picks here
    assert 10 < m["moe_picks_here_pct"] < 45
    assert 0 < m["moe_experts_hit_pct"] <= 100
    assert 0 < m["sched_batch_slots_pct.serve_flood_mixed"] <= 100
    # slots seated BEFORE a step: never more than the decode call's batch
    assert 0 < m["serve_slot_occupancy_pct.serve_flood_mixed"] <= m[
        "sched_batch_slots_pct.serve_flood_mixed"]
    assert m["serve_step_host_ms.serve_flood_mixed"] > 0
    assert res["end_to_end_of_traced_run"]["serve_tokens_per_s"] > 0
    assert res["checks"]["token_logit_gap"]["value"] < 1e-4
    assert res["checks"]["mismatched_token_share"]["value"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(prove_streamed.FAULTS))
def test_run_is_not_correct_with_a_fault_planted_in_the_program(
        monkeypatch, fault):
    monkeypatch.setattr(*prove_streamed.FAULTS[fault]())
    res = rehearse(seconds=0.3, trace=0)
    assert res["correct"] is False
    gap = res["checks"]["token_logit_gap"]
    assert gap["value"] > 100 * gap["limit"]
    # what the real cell compares (its gap is not: PERF.md section 2)
    share = res["checks"]["mismatched_token_share"]
    assert share["value"] > 10 * share["limit"]


# ------------------------------------------------ the entries and the files

def _named(entries, name):
    """The one entry of that name, wherever it stands in its list."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_entries_are_there_once_and_the_configuration_keeps_the_catalog(
        real):
    spec, cfg, d = real
    cell = _named(spec["workloads"], REAL)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": REAL, "config": "command-a-plus-1of8",
        "traffic": "flood_mixed_8k", "chips": 1}
    entry = _named(spec["configs"], "command-a-plus-1of8")
    assert entry["file"] == "benchmark/configs/command-a-plus-1of8.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts_held",
                                "vocab_size", "vision_tower"]
    for name in SPAN_METRICS | DEVICE_METRICS:
        metric = _named(spec["per_layer"], name)
        assert REAL in metric["workloads"], name
        assert metric["moves"] == "serve_tokens_per_s", name
    assert REAL in _named(spec["end_to_end"],
                          "serve_tokens_per_s")["workloads"]
    # the published widths, the chip's share, one whole period
    assert (d["H"], d["heads"], d["kv_heads"], d["hd"], d["I"]) == (
        4096, 128, 8, 128, 4096)
    assert (d["E"], d["K"], d["S"], d["window"]) == (128, 8, 4, 4096)
    assert d["held"] == list(range(16)) and d["V"] == 32768
    assert d["kinds"] == ["sliding_attention"] * 3 + ["full_attention"]
    kw = cfg["program"]["kwargs"]
    for key, value in kw.items():
        if key in cfg and key != "experts_held":
            assert cfg[key] == value, key
    assert kw["experts_held"] == cfg["experts_held"] and kw[
        "dtype"] == cfg["serving"]["weights_dtype"] == "bfloat16"
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "flood_mixed_8k.json"))
    assert traffic["kind"] == "flood_streamed"
    assert traffic["prompt"]["max"] + traffic["output"]["max"] <= traffic[
        "engine"]["max_len"] == max(traffic["engine"]["prefill_buckets"])
    assert traffic["backlog"] <= traffic["engine"]["max_queue_depth"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"command-a-plus-05-2026"' in line][0]
    assert entry["source"] == cfg["_source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "vocab_size"}


def test_counts_against_hand_worked_numbers(real):
    _, _, d = real
    attn = 2 * 4096 * 128 * 128 + 2 * 4096 * 8 * 128      # 142.6M
    expert = 3 * 4096 * 4096                               # 50.33M
    assert A.expert_params(d) == expert == 50331648
    dense = attn + 4 * expert + 4096 * 128                 # 344.4M a layer
    assert (attn, dense) == (142606336, 344457216)
    routed = 8 * 16 / 128 * expert                         # 1 expert's worth
    # one token at 5000 rows: window layers see 4096, the full layer 5000
    assert A.decode_token_flops(5000, d) == (
        2 * 4 * (dense + routed) + 4 * 128 * 128 * (3 * 4096 + 5000)
        + 2 * 32768 * 4096)
    assert A.decode_token_flops(5000, d, rows_window=3000) == (
        A.decode_token_flops(5000, d) - 4 * 128 * 128 * 3 * 1096)
    # a prompt of 6000: pairs under the mask; the head at one position
    full = 6000 * 6001 / 2
    window = 4096 * 4097 / 2 + (6000 - 4096) * 4096
    assert A.prefill_flops(6000, d) == pytest.approx(
        2 * 6000 * 4 * (dense + routed)
        + 4 * 128 * 128 * (3 * window + full) + 2 * 32768 * 4096)
    assert A.prefill_flops(100, d) < A.prefill_flops(200, d)
    # a decode call of 4 steps, 40 experts hit in all, 16 requests of 5000
    # rows: each capped at the window A REQUEST
    rows_full, rows_window = 16 * 5000, 16 * 4096
    step = (4 * ((attn + 4 * expert) * 2 + 4096 * 128 * 4)
            + 32768 * 4096 * 2 + 4096 * (3 * rows_window + rows_full))
    assert A.kv_row_bytes(d) == 4096
    assert A.decode_call_bytes(d, 4, 40, rows_full, rows_window) == (
        4 * step + 40 * expert * 2)
    assert A.expert_product_cost(10, 7, d) == (2 * 10 * expert,
                                               7 * expert * 2)
    assert not hasattr(A, "MOE_BLOCK")     # the program reports its blocks
    # a leaf's seed: distinct over seeds past 2**31, layers and leaves
    seeds = {A.leaf_seed(s, l, k) for s in (0, 1, 2 ** 31 + 11)
             for l in range(-1, 4) for k in range(12)}
    assert len(seeds) == 3 * 5 * 12 and max(seeds) < 2 ** 62


# ------------------------------------------------------------- the readers

def _run(steps, window_s=3.0):
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = harness.load_json(os.path.join(
        BENCH, "configs", "command-a-plus-1of8.json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "flood_mixed_8k.json"))
    assert spec
    return types.SimpleNamespace(
        config=cfg, traffic=traffic, engine_steps=steps, devices=[0],
        peaks=harness.peaks_for("TPU v5 lite"), window=(10.0, 20.0),
        traced={"t0": 17.0, "t1": 20.0},
        trace_summary={"window_s": window_s, "events": [], "modules": []})


def test_the_whole_steps_share_and_the_decode_programs_roofline(real):
    _, _, d = real
    step = {"traced": True, "admitted_plens": [1000], "admitted": 1,
            "tokens": 65, "running": 16, "live_rows": 16 * 5000,
            "rows_full": 16 * 5000, "rows_window": 16 * 4096,
            "experts_hit": 160, "routed_here": 512, "routed_all": 4096}
    run = _run([step, dict(step, traced=False)])
    ops = A.prefill_flops(1000, d) + 64 * A.decode_token_flops(
        5000, d, 4096)
    assert mfu_serve_arch.read(run, {}) == pytest.approx(
        100 * ops / (3.0 * 197e12))
    # the decode program ran 0.1 s for one call; a second step at the
    # window's edge has no call: the bytes are scaled to the calls seen
    run.trace_summary["modules"] = [("jit_decode(123)", 17.5, 17.6)]
    run.engine_steps = [step, dict(step)]
    least = A.decode_call_bytes(d, 4, 160, 16 * 5000, 16 * 4096) / 819e9
    assert decode_roofline_arch.read(
        run, {"program": r"^jit_decode\("}) == pytest.approx(
            100 * least / 0.1)
    # a program whose spans carry no routed counts: nothing to read
    bare = {k: v for k, v in step.items() if k not in (
        "experts_hit", "rows_full", "rows_window")}
    run.engine_steps = [bare]
    assert decode_roofline_arch.read(run, {"program": r"^jit_decode\("}) \
        is None
    run.trace_summary = None
    assert mfu_serve_arch.read(run, {}) is None


def test_the_span_readers_on_a_hand_made_ring(real):
    from paddle_tpu.observability import get_tracer
    _, _, d = real
    tracer = get_tracer()
    tracer.clear()
    run = _run([])
    picks = harness.load_json(os.path.join(
        BENCH, "metrics", "moe_picks_here_pct.json"))["params"]
    hits = harness.load_json(os.path.join(
        BENCH, "metrics", "moe_experts_hit_pct.json"))["params"]
    roof = harness.load_json(os.path.join(
        BENCH, "metrics", "moe_expert_product_roofline.json"))["params"]
    # the parent's ring: the spans without the counts
    tracer.record("serving_decode", 18.0, 18.1, args={"active": 16})
    assert span_arg_share.read(run, picks) is None
    assert span_arg_share.read(run, hits) is None
    run.trace_summary["events"] = [("%ragged-dot-none.1 = f32[]", 18.0,
                                    18.004)] * 48
    assert expert_product_roofline.read(run, roof) is None
    # a program that carries the routed counts but not how many grouped
    # products it made (this PR's first form): nothing to hold the trace to
    tracer.record("serving_decode", 18.0, 18.1, args={
        "active": 16, "routed_here": 524, "routed_all": 4096,
        "experts_hit": 170})
    assert expert_product_roofline.read(run, roof) is None
    tracer.clear()
    # decode calls in the window (four of them in the traced stretch), one
    # outside it, an admission; each says how many grouped products it made
    # (3 a layer a step; a prompt of two blocks 3 a layer a block)
    for t0, here, hit in ((12.0, 500, 150), (18.0, 524, 170),
                          (18.4, 524, 170), (18.6, 524, 170),
                          (18.8, 524, 170), (25.0, 9999, 9999)):
        tracer.record("serving_decode", t0, t0 + 0.06, args={
            "active": 16, "routed_here": here, "routed_all": 4096,
            "experts_hit": hit, "expert_products": 48})
    tracer.record("serving_admit", 18.2, 18.3, args={
        "plen": 3000, "bucket": 4096, "routed_here": 12000,
        "routed_all": 96000, "experts_hit": 128, "expert_products": 24})
    assert span_arg_share.read(run, picks) == pytest.approx(
        100 * (500 + 4 * 524) / (5 * 4096))
    # of 16 held experts x 4 layers x 4 steps a call
    assert span_arg_share.read(run, hits) == pytest.approx(
        100 * (150 + 4 * 170) / (5 * 256))
    # the traced stretch (17 to 20) holds four decode calls and the
    # admission: 4 x 48 + 3 x 4 x 2 events of the product are expected
    events = [("%ragged-dot-none.1 = f32[]", 18.0, 18.004)] * 216
    events.append(("%ragged-dot-metadata.1 = s32[]", 18.0, 18.5))
    run.trace_summary["events"] = events
    expert = 3 * 4096 * 4096
    least = (4 * max(2 * 524 * expert / 197e12, 170 * expert * 2 / 819e9)
             + max(2 * 12000 * expert / 197e12, 128 * expert * 2 / 819e9))
    assert expert_product_roofline.read(run, roof) == pytest.approx(
        100 * least / (216 * 0.004))
    # the call at an edge fell half outside the traced stretch: scaled to
    # the events that are there
    run.trace_summary["events"] = events[:192]
    assert expert_product_roofline.read(run, roof) == pytest.approx(
        100 * least * (192 / 216) / (192 * 0.004))
    # half of the events the program says it made: its count and the trace
    # disagree (a fused or renamed product), so no share and not a low one
    run.trace_summary["events"] = events[:108]
    assert expert_product_roofline.read(run, roof) is None
    tracer.clear()


def test_prove_streamed_reads_the_program_and_the_control(tmp_path):
    """The script the limit's readings come from, at the tiny size: the
    program reads no gap; the control (bfloat16, since the tiny
    configuration states float32) is judged at the same positions (over 128
    tokens its choice seldom moves: the chip's readings are in PERF.md)."""
    out = tmp_path / "prove.json"
    rec = prove_streamed.main(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.3",
         "--control", "1", "--tie", "2", "--tie-requests", "5", "--out",
         str(out)], need_tpu=False,
        spec_path=os.path.join(TINY, "spec_cmdap.json"),
        data_dirs=[TINY, BENCH])
    assert rec == json.loads(out.read_text())
    assert rec["failed"] == 0 and rec["requests"] > 5
    assert rec["program"]["token_logit_gap"] < 1e-4
    assert rec["program"]["mismatched_token_share"] == 0
    assert rec["program"]["checked_tokens"] == rec["control_bfloat16"][
        "checked_tokens"] > 0
    assert rec["control_bfloat16"]["token_logit_gap"] >= rec["program"][
        "token_logit_gap"]
    # each set of numbers went through `compare.judge` under the cell's limits
    assert rec["program"]["correct"] is True
    assert set(rec["program"]["checks"]) == {"token_logit_gap",
                                             "mismatched_token_share"}
    assert rec["control_bfloat16"]["correct"] is (
        rec["control_bfloat16"]["token_logit_gap"] <= 1e-4)
    # the widest gap looked into (at float32 nothing was flipped)
    assert rec["tie"]["requests_looked_over"] == 5
    assert rec["tie"]["gap_by_request"] == sorted(
        rec["tie"]["gap_by_request"], reverse=True)
    first, second = rec["tie"]["widest"]
    assert first["request"] != second["request"]
    # the cell's own sample lies in the larger draw
    assert first["gap"] >= rec["program"]["token_logit_gap"] >= 0
    for tie in (first, second):
        assert tie["gap_recomputed"] == pytest.approx(tie["gap"], abs=1e-6)
        assert [n["layer"] for n in tie["layers"]] == [0, 1, 2, 3]
    for note in first["layers"] + second["layers"]:
        assert note["margin"] >= 0 and note[
            "margin_median_over_positions"] > 0
        assert note["gap_with_runner_up_taken"] >= 0
        # the row it swaps is the reference's own FFN where nothing is swapped
        assert note["ffn_recomputed_off_by"] < 1e-5
