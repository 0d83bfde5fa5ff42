"""Where a seat's time and the chip's time go over the whole window (PR 38):
the readers `seat_seconds`, `host_hold` and `pure_step_stat` on hand-made
rings with known answers, and a CPU rehearsal of tiny chat, flood and
training cells through `tiny/spec_seats.json` that reports all six metrics.
Nothing timed here is a device number.
"""
import argparse
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

from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.readers import (host_hold, pure_step_stat,  # noqa: E402
                               seat_seconds)

FLOODS = ["gpt2m-serve-flood", "cmdap-serve-flood-mixed",
          "moonlight-serve-flood-longgen", "evabyte-serve-flood-longctx"]
NEW = {   # name -> (workloads, moves)
    "slot_held_by_prefill_pct": (FLOODS, "serve_tokens_per_s"),
    "slot_held_by_prefill_pct.serve_chat": (["gpt2m-serve-chat"],
                                            "serve_itl_p95_ms"),
    "chip_wait_on_host_pct": (FLOODS, "serve_tokens_per_s"),
    "python_gc_pct": (FLOODS, "serve_tokens_per_s"),
    "serve_decode_step_ms.serve_chat": (["gpt2m-serve-chat"],
                                        "serve_itl_p95_ms"),
    "train_step_host_ms": (["gpt2m-train", "bertl-train"],
                           "train_tokens_per_s"),
}
SEAT = {"admit": "serving_admit",
        "decode": ["serving_decode", "serving_verify"]}


def _metric(name):
    return harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))


def _ev(name, t0, dur, sid=None, parent=None, args=None):
    return (name, t0, dur, 1, sid, parent, args)


def test_seat_seconds_weigh_each_span_by_its_runs_clipped_to_the_window():
    ring = [
        _ev("serving_admit", 1.0, 0.1, 1, None, {"seated": 3}),
        _ev("serving_admit", 2.0, 0.05, 2, None, {"seated": 0}),
        _ev("serving_admit", 2.95, 0.1, 3, None, {"seated": 2}),   # half in
        _ev("serving_decode", 1.1, 0.2, 4, None, {"active": 4}),
        _ev("serving_verify", 2.1, 0.1, 5, None, {"active": 2}),
        _ev("serving_decode", 0.5, 0.6, 6, None, {"active": 1}),   # 0.1 in
        _ev("serving_decode", 5.0, 1.0, 7, None, {"active": 9}),   # after
        _ev("serving_deliver", 1.2, 0.01, 8, 4, {"tokens": 4}),
    ]
    held = 0.1 * 3 + 0.05 * 2
    moving = 0.2 * 4 + 0.1 * 2 + 0.1 * 1
    assert seat_seconds.share(ring, (1.0, 3.0), SEAT) == pytest.approx(
        100.0 * held / (held + moving))
    # the parent's admissions carry no `seated`: nothing to read
    parent = [ev[:6] + ({"request": 1},) if ev[0] == "serving_admit" else ev
              for ev in ring]
    assert seat_seconds.share(parent, (1.0, 3.0), SEAT) is None
    assert seat_seconds.share([], (1.0, 3.0), SEAT) is None


def _run(spans, traced_t0, window=(0.0, 10.0)):
    return types.SimpleNamespace(
        window=window, spans=spans,
        traced=None if traced_t0 is None else {"t0": traced_t0})


def test_host_hold_leaves_the_profilers_start_out_of_the_window():
    run = _run([("generate_load", 5.0, 5.1), ("collect_tokens", 6.0, 6.9),
                ("generate_load", 7.5, 7.6)], traced_t0=7.4)
    stall = host_hold.profiler_start(run)
    assert stall == pytest.approx((6.9, 7.4))
    ring = [
        _ev("serving_prefill_wait", 1.0, 1.0, 1),
        _ev("serving_token_pull", 5.0, 2.0, 2),     # 0.1 s in the stall
        _ev("serving_token_pull", 9.5, 1.0, 3),     # half after the window
        _ev("serving_decode", 1.0, 5.0, 4, None, {"active": 2}),
        _ev("python_gc", 7.0, 0.2, 5, None, {"generation": 2}),  # stall
        _ev("python_gc", 2.0, 0.095, 6, None, {"generation": 1}),
    ]
    waits = _metric("chip_wait_on_host_pct")["params"]
    w = 10.0 - 0.5
    assert host_hold.share(ring, run.window, stall, waits) == \
        pytest.approx(100.0 * (w - (1.0 + 1.9 + 0.5)) / w)
    gcs = _metric("python_gc_pct")["params"]
    assert host_hold.share(ring, run.window, stall, gcs) == \
        pytest.approx(100.0 * 0.095 / w)
    # no collection recorded reads 0; no wait on the chip reads nothing
    assert host_hold.share(ring[:4], run.window, stall, gcs) == 0.0
    assert host_hold.share(ring[4:], run.window, stall, waits) is None
    # an untraced run, or one whose profiler started before any span
    assert host_hold.profiler_start(_run(run.spans, None)) is None
    assert host_hold.profiler_start(_run([], 0.25)) == pytest.approx(
        (0.0, 0.25))
    assert host_hold.share(ring, run.window, None, waits) == \
        pytest.approx(100.0 * (10.0 - 3.5) / 10.0)


def test_pure_step_stat_skips_steps_that_admitted_or_did_not_decode():
    ring = [
        _ev("serving_step", 1.0, 0.1, 1),              # admitted
        _ev("serving_admit", 1.0, 0.05, 2, 1),
        _ev("serving_decode", 1.05, 0.05, 3, 1),
        _ev("serving_step", 2.0, 0.08, 4),             # decode alone
        _ev("serving_decode", 2.0, 0.08, 5, 4),
        _ev("serving_token_pull", 2.01, 0.05, 6, 5),
        _ev("serving_step", 2.2, 0.05, 7),             # an admission deeper
        _ev("serving_decode", 2.2, 0.02, 8, 7),
        _ev("serving_sweep", 2.22, 0.03, 9, 7),
        _ev("serving_admit", 2.22, 0.02, 10, 9),
        _ev("serving_step", 2.4, 0.03, 11),            # no decode call
        _ev("serving_sweep", 2.4, 0.03, 12, 11),
        _ev("serving_step", 2.5, 0.06, 13),            # a verify tick
        _ev("serving_verify", 2.5, 0.06, 14, 13),
        _ev("serving_step", 2.98, 0.1, 15),            # ends after
        _ev("serving_decode", 2.98, 0.1, 16, 15),
        _ev("python_gc", 2.0, 0.001, 17),
    ]
    params = _metric("serve_decode_step_ms.serve_chat")["params"]
    assert sorted(pure_step_stat.values(ring, (1.0, 3.0), params)) == \
        pytest.approx([0.06, 0.08])
    assert pure_step_stat.values(ring, (5.0, 6.0), params) == []


def test_the_readers_read_the_tracers_ring_and_the_parents_reads_none():
    import gc
    from paddle_tpu.observability import get_tracer
    tracer = get_tracer()
    gc.disable()                  # no collection lands in the ring here
    tracer.clear()
    run = _run([("collect_tokens", 0.5, 1.0)], traced_t0=1.5,
               window=(0.0, 4.0))
    try:
        # the parent's spans: admissions without `seated`
        tracer.record("serving_step", 2.0, 2.5)
        sid = tracer.record("serving_step", 3.0, 3.2)
        tracer.record("serving_admit", 2.0, 2.2, args={"request": 1})
        tracer.record("serving_decode", 2.2, 2.5, args={"active": 1})
        tracer.record("serving_decode", 3.0, 3.2, args={"active": 2})
        tracer.record("serving_token_pull", 3.1, 3.2)
        tracer.record("serving_decode", 3.0, 3.2, args={"active": 2},
                      parent=types.SimpleNamespace(span_id=sid))
        read = {n: harness.Files().code(
            "readers", _metric(n)["reader"]).read(run, _metric(n)["params"])
            for n in NEW if n != "train_step_host_ms"}
    finally:
        tracer.clear()
        gc.enable()
    assert read["slot_held_by_prefill_pct"] is None
    assert read["chip_wait_on_host_pct"] == pytest.approx(
        100.0 * (3.5 - 0.1) / 3.5)
    assert read["python_gc_pct"] == 0.0
    assert read["serve_decode_step_ms.serve_chat"] == pytest.approx(200.0)


def _rehearse(cell, seconds):
    files = harness.Files(os.path.join(TINY, "spec_seats.json"),
                          [TINY, BENCH])
    import jax
    args = argparse.Namespace(seed=2147483671, seconds=seconds, trace=1)
    try:
        return bench_run.run_cell(files, files.cell(cell), args,
                                  jax.devices()[:1], time.perf_counter())
    finally:        # the profiler's files: megabytes that nothing reads
        shutil.rmtree(os.path.join(TINY, ".bench_trace", cell),
                      ignore_errors=True)


def test_rehearsal_of_chat_flood_and_training_reports_all_six():
    chat = _rehearse("tiny-gpt2-chat", 0.6)
    flood = _rehearse("tiny-gpt2-flood", 0.6)
    train = _rehearse("tiny-gpt2-train", 0.4)
    assert chat["correct"] and flood["correct"] and train["correct"]
    assert set(chat["metrics"]) == {"slot_held_by_prefill_pct.serve_chat",
                                    "serve_decode_step_ms.serve_chat"}
    assert set(flood["metrics"]) == {"slot_held_by_prefill_pct",
                                     "chip_wait_on_host_pct",
                                     "python_gc_pct"}
    assert set(train["metrics"]) == {"train_step_host_ms"}
    m = dict(chat["metrics"], **flood["metrics"], **train["metrics"])
    assert set(m) == set(NEW)
    for name in ("slot_held_by_prefill_pct", "chip_wait_on_host_pct",
                 "python_gc_pct", "slot_held_by_prefill_pct.serve_chat"):
        assert 0.0 <= m[name]["value"] <= 100.0, name
    # a flood admits into a seated batch; the host holds the chip somewhat
    assert m["slot_held_by_prefill_pct"]["value"] > 0.0
    assert m["chip_wait_on_host_pct"]["value"] > 0.0
    # the engine's own decode step is no longer than the harness's longest
    assert 0.0 < m["serve_decode_step_ms.serve_chat"]["value"] <= \
        chat["notes"]["longest_engine_step"]["ms"]
    assert m["train_step_host_ms"]["value"] > 0.0


def test_the_six_are_appended_with_their_cells_and_files():
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index("slot_held_by_prefill_pct")
    assert first == 53      # later PRs append after these
    for m in spec["per_layer"][first:first + 6]:
        workloads, moves = NEW[m["name"]]
        assert (m["workloads"], m["moves"]) == (workloads, moves)
        assert (m["source"], m["better"]) == ("program_span", "lower")
        meta = _metric(m["name"])
        assert (meta["unit"], meta["layer"], meta["moves"]) == (
            m["unit"], m["layer"], m["moves"])
