"""The per-layer metrics that read the program's own spans (`source`:
`program_span`): the reader's arithmetic on a hand-made ring, and a CPU
rehearsal of one tiny serving and one tiny training cell through
`tiny/spec_spans.json` that reports all eight.  Nothing timed here is a
device number.
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
from benchmark.readers import program_span_stat  # noqa: E402

NEW = {
    "serve_prefill_p95_ms", "serve_step_host_ms.serve_chat",
    "serve_step_host_ms.serve_flood", "serve_deliver_ms",
    "sched_queue_wait_p95_ms", "sched_batch_slots_pct",
    "train_state_walk_ms_per_step", "train_dispatch_ms_per_step"}


def _ring():
    """Two steps inside the window (1.0 to 3.0) and one that ends after
    it.  (name, t0, dur, tid, id, parent, args)"""
    ev = []

    def add(name, t0, dur, sid, parent=None, args=None):
        ev.append((name, t0, dur, 1, sid, parent, args))

    # step 1: 100 ms, of which 30 wait on the chip two levels down
    add("serving_step", 1.0, 0.100, 1, None, {"step": 1})
    add("serving_admit", 1.0, 0.050, 2, 1, {"request": 7})
    add("serving_prefill_wait", 1.02, 0.020, 3, 2)
    add("serving_decode", 1.05, 0.050, 4, 1, {"active": 3})
    add("serving_token_pull", 1.06, 0.010, 5, 4)
    add("serving_deliver", 1.07, 0.004, 6, 4, {"tokens": 6})
    # step 2: 40 ms, 10 of them in the pull
    add("serving_step", 2.0, 0.040, 7, None, {"step": 2})
    add("serving_decode", 2.0, 0.040, 8, 7, {"active": 1})
    add("serving_token_pull", 2.01, 0.010, 9, 8)
    add("serving_deliver", 2.03, 0.002, 10, 8, {"tokens": 2})
    # step 3 ends after the window closes: not counted
    add("serving_step", 2.99, 0.500, 11, None, {"step": 3})
    add("serving_decode", 2.99, 0.500, 12, 11, {"active": 4})
    add("serving_token_pull", 3.0, 0.400, 13, 12)
    # a light span (no id) and a span of another tracer user
    ev.append(("matmul", 1.5, 0.001, 1, None, None, None))
    add("checkpoint_publish", 1.6, 0.2, 14, None, {"step": 3})
    return ev


WINDOW = (1.0, 3.0)


def test_duration_self_time_children_and_args_on_a_hand_made_ring():
    vals = lambda **p: sorted(program_span_stat.values(  # noqa: E731
        _ring(), WINDOW, p))
    # a span's own duration, clipped to the window
    assert vals(span="serving_step") == pytest.approx([0.040, 0.100])
    assert vals(span="serving_deliver") == pytest.approx([0.002, 0.004])
    # less the named spans below it, at any depth
    assert vals(span="serving_step", less=[
        "serving_prefill_wait", "serving_token_pull"]) == pytest.approx(
            [0.030, 0.070])
    assert vals(span="serving_step", less=["no_such_span"]) == pytest.approx(
        [0.040, 0.100])
    # the named spans below it, summed; a step with none gives no value
    assert vals(span="serving_step", children=[
        "serving_prefill_wait", "serving_token_pull"]) == pytest.approx(
            [0.010, 0.030])
    assert vals(span="serving_step", children=["serving_prefill_wait"]) \
        == pytest.approx([0.020])
    assert vals(span="serving_step", children=["no_such_span"]) == []
    # one of its args
    assert vals(span="serving_decode", arg="active") == [1, 3]
    assert vals(span="serving_decode", arg="no_such_key") == []
    # another window
    assert vals(span="serving_step") != sorted(program_span_stat.values(
        _ring(), (0.0, 10.0), {"span": "serving_step"}))
    assert program_span_stat.values(_ring(), (5.0, 6.0),
                                    {"span": "serving_step"}) == []


def test_read_takes_the_tracers_ring_and_is_silent_where_it_is_empty():
    from paddle_tpu.observability import get_tracer
    tracer = get_tracer()
    tracer.clear()
    run = types.SimpleNamespace(window=WINDOW, extra={"max_slots": 4})
    every = [harness.load_json(os.path.join(BENCH, "metrics", n + ".json"))
             for n in sorted(NEW)]
    assert all(m["reader"] == "program_span_stat" for m in every)
    # a program that records none of the spans: nothing to read, no error
    assert [program_span_stat.read(run, m["params"]) for m in every] == [
        None] * len(every)
    # the parent's ring: `train_step` alone, with nothing below it
    tracer.record("train_step", 1.1, 1.2)
    walk = harness.load_json(os.path.join(
        BENCH, "metrics", "train_state_walk_ms_per_step.json"))["params"]
    assert program_span_stat.read(run, walk) is None
    for name, t0, dur, _, _, _, args in _ring():
        if name == "serving_decode":
            tracer.record(name, t0, t0 + dur, args=args)
    slots = harness.load_json(os.path.join(
        BENCH, "metrics", "sched_batch_slots_pct.json"))["params"]
    assert program_span_stat.read(run, slots) == pytest.approx(
        100.0 * (3 + 1) / 2 / 4)
    tracer.clear()


def _rehearse(cell, seconds):
    files = harness.Files(os.path.join(TINY, "spec_spans.json"),
                          [TINY, BENCH])
    import jax
    args = argparse.Namespace(seed=2147483659, seconds=seconds, trace=1)
    try:
        return bench_run.run_cell(files, files.cell(cell), args,
                                  jax.devices()[:1], time.perf_counter())
    finally:        # the profiler's files: megabytes that nothing reads
        shutil.rmtree(os.path.join(TINY, ".bench_trace", cell),
                      ignore_errors=True)


def test_rehearsal_of_a_serving_and_a_training_cell_reports_all_eight():
    serve = _rehearse("tiny-gpt2-flood", 0.6)
    train = _rehearse("tiny-gpt2-train", 0.4)
    assert serve["correct"] and train["correct"]
    assert set(serve["metrics"]) | set(train["metrics"]) == NEW
    assert set(train["metrics"]) == {"train_state_walk_ms_per_step",
                                     "train_dispatch_ms_per_step"}
    for res in (serve, train):
        for name, m in res["metrics"].items():
            assert m["value"] > 0, name
    m = serve["metrics"]
    assert 0 < m["sched_batch_slots_pct"]["value"] <= 100
    # the host's part of a step is no longer than the whole of it, and a
    # prefill no longer than the longest step
    assert m["serve_deliver_ms"]["value"] <= \
        m["serve_step_host_ms.serve_flood"]["value"]
    assert m["serve_step_host_ms.serve_chat"]["value"] == \
        m["serve_step_host_ms.serve_flood"]["value"]
    assert m["serve_prefill_p95_ms"]["value"] <= \
        serve["notes"]["longest_engine_step"]["ms"]
    assert train["end_to_end_of_traced_run"]["train_tokens_per_s"] > 0


def test_the_new_entries_name_their_source_and_only_follow_the_old():
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in spec["per_layer"]]
    assert set(names[14:22]) == NEW     # later PRs append after these
    for m in spec["per_layer"][14:22]:
        assert m["source"] == "program_span"
    assert [m["source"] for m in spec["per_layer"][:14]].count(
        "program_span") == 0
