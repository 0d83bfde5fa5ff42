"""The benchmark's own tests: the harness finds everything by name, the
yardstick's arithmetic is right, the references agree with the program's
models, and `correct` comes out false when the timed path is broken.

Everything here runs on the CPU at the test-only sizes of `tiny/`; nothing
compiles for a described chip, sleeps or opens a socket, and the three
child processes each have a timeout.
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (compare, flops, harness, run as bench_run,  # noqa: E402
                       serve_check, stats, trace_reduce, train_check,
                       weights as W)
from benchmark.arch import load as load_arch  # noqa: E402
from benchmark.generators import open_loop  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = os.path.join(ROOT, "benchmark")


def tiny_files():
    return harness.Files(os.path.join(TINY, "spec.json"), [TINY, BENCH])


def tiny_run(cell, seed=7, seconds=0.2, trace=0):
    files = tiny_files()
    import jax
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return bench_run.run_cell(files, files.cell(cell), args,
                              jax.devices()[:1], time.perf_counter())


# ---------------------------------------------------------------- the spec

@pytest.fixture(scope="module")
def spec():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_spec_names_units_and_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in spec[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
    assert "setup_s" in [m["name"] for m in spec["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_every_cell_finds_its_files_and_metrics(spec):
    files = harness.Files()
    e2e = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        cfg = files.config(w["config"])
        arch = load_arch(cfg["arch"])
        assert arch.dims(cfg)["H"] % arch.dims(cfg)["heads"] == 0
        traffic = files.data("traffic", w["traffic"])
        assert hasattr(files.code("generators", traffic["kind"]), "run")
        limits = files.data("cells", w["name"])["limits"]
        assert limits and all(v >= 0 for v in limits.values())
        own_e2e = files.metrics_of(w["name"], "end_to_end")
        assert "setup_s" in own_e2e and len(own_e2e) >= 2
        own_layers = files.metrics_of(w["name"], "per_layer")
        assert own_layers
        for name in own_layers:
            meta = files.data("metrics", name)
            assert hasattr(files.code("readers", meta["reader"]), "read")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        meta = files.data("metrics", m["name"])
        assert (meta["unit"], meta["layer"], meta["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        # each of its cells reports the end-to-end metric it moves
        for cell in m.get("workloads", [w["name"] for w in
                                        spec["workloads"]]):
            assert m["moves"] in files.metrics_of(cell, "end_to_end"), (
                m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in spec["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced_why"])


def test_file_names_under_paths_use_allowed_characters(spec):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in spec["paths"]:
        for base, dirs, names in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), ROOT)
                assert ok.match(rel), rel


def test_a_cell_a_mix_a_kind_and_a_metric_are_added_as_files_only(tmp_path):
    """A later PR adds files and entries, and edits nothing that is there:
    a new cell with a new kind of traffic and a new metric, all in a
    directory of their own, runs through the unchanged harness."""
    d = tmp_path
    for sub in ("configs", "traffic", "cells", "metrics", "generators",
                "readers"):
        (d / sub).mkdir()
    (d / "configs" / "toy.json").write_text(json.dumps({"arch": "gpt2"}))
    (d / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"kind": "toy_kind", "answer": 42}))
    (d / "cells" / "toy-cell.json").write_text(json.dumps(
        {"limits": {"toy_gap": 0.5}}))
    (d / "generators" / "toy_kind.py").write_text(
        "import time\n"
        "def run(run):\n"
        "    run.setup_s = 1.5\n"
        "    run.window = (0.0, 1.0)\n"
        "    run.counters['answer'] = run.traffic['answer']\n"
        "    return {'attempted': 3, 'failed': 0, 'numbers': {'toy_gap': "
        "0.25}, 'end_to_end': {'toy_rate': 10.0}}\n")
    (d / "readers" / "toy_reader.py").write_text(
        "def read(run, params):\n"
        "    return run.counters['answer'] * params['times']\n")
    (d / "metrics" / "toy_metric.json").write_text(json.dumps(
        {"unit": "1", "layer": "toy", "moves": "toy_rate",
         "reader": "toy_reader", "params": {"times": 2}}))
    (d / "metrics" / "toy_silent.json").write_text(json.dumps(
        {"unit": "%", "layer": "toy", "moves": "toy_rate",
         "reader": "device_idle"}))
    spec = {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "none",
                     "file": str(d / "configs" / "toy.json"),
                     "reduced": [], "why": "toy"}],
        "workloads": [{"name": "toy-cell", "config": "toy",
                       "traffic": "toy_mix", "chips": 1, "why": "toy"}],
        "end_to_end": [
            {"name": "toy_rate", "unit": "1/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "toy_metric", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "toy_rate"},
            {"name": "toy_silent", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "toy", "moves": "toy_rate"}]}
    (d / "BENCHMARK.json").write_text(json.dumps(spec))
    files = harness.Files(str(d / "BENCHMARK.json"), [str(d), BENCH])
    import jax
    for trace in (0, 1):
        args = argparse.Namespace(seed=1, seconds=1, trace=trace)
        res = bench_run.run_cell(files, files.cell("toy-cell"), args,
                                 jax.devices()[:1], time.perf_counter())
        assert res["correct"] and res["attempted"] == 3
        if trace:
            # the reader that finds nothing to read is left out, never 0
            assert res["metrics"] == {"toy_metric": {"value": 84,
                                                     "unit": "1"}}
        else:
            assert res["metrics"] == {
                "toy_rate": {"value": 10.0, "unit": "1/s"},
                "setup_s": {"value": 1.5, "unit": "s"}}
        assert list(res)[-1] == "checks"
        assert res["checks"] == {"toy_gap": {"value": 0.25, "limit": 0.5}}


def test_a_compared_number_without_a_limit_is_an_error():
    with pytest.raises(KeyError):
        compare.judge({"new_number": 0.0}, {"loss_gap": 1.0})
    rows, ok = compare.judge({"a": float("nan"), "b": 0.1},
                             {"a": 1.0, "b": 0.2})
    assert not ok and [r["ok"] for r in rows] == [False, True]


# ------------------------------------------------------ traffic and clocks

def test_open_loop_schedule_same_seed_same_traffic_and_same_work():
    mix = harness.load_json(os.path.join(BENCH, "traffic", "chat.json"))
    a = open_loop.schedule(mix, 3000000019, 20, 50257)
    b = open_loop.schedule(mix, 3000000019, 20, 50257)
    c = open_loop.schedule(mix, 5, 20, 50257)
    assert len(a) == len(c) == round(mix["rate_per_s"] * 20)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["out"] == y["out"]
        assert np.array_equal(x["prompt"], y["prompt"])
    # another seed: the same arrivals and lengths in the same order (a tail
    # depends on which bursts meet which long prompts), other token ids
    assert [(x["due"], len(x["prompt"]), x["out"]) for x in a] == [
        (x["due"], len(x["prompt"]), x["out"]) for x in c]
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])
    # another mix_seed: another order of the same lengths
    other = open_loop.schedule(dict(mix, mix_seed=1), 5, 20, 50257)
    assert sorted(len(x["prompt"]) for x in a) == sorted(
        len(x["prompt"]) for x in other)
    assert [len(x["prompt"]) for x in a] != [len(x["prompt"])
                                             for x in other]
    assert abs(a[-1]["due"] - c[-1]["due"]) < 1e-6
    dues = [x["due"] for x in a]
    assert dues == sorted(dues) and dues[0] > 0.0 and 19.0 < dues[-1] < 20.0
    lens = [len(x["prompt"]) for x in a]
    assert min(lens) >= mix["prompt"]["min"]
    assert max(lens) <= mix["prompt"]["max"]
    assert all(len(x["prompt"]) + x["out"] <= mix["engine"]["max_len"]
               for x in a)
    assert max(int(x["prompt"].max()) for x in a) < 50257


def _log(n=100, stall_at=None):
    """n requests, one due every 10 ms, each answered 5 ms after it is due
    and streaming 11 tokens over 100 ms; a stall delays those due in it."""
    out = []
    for i in range(n):
        due = 0.01 * i
        first = due + 0.005
        if stall_at is not None and stall_at <= due < stall_at + 0.2:
            first = stall_at + 0.2 + 0.005      # served when the stall ends
        out.append({"due": due, "first": first, "last": first + 0.1,
                    "tokens": 11, "done": True, "failed": False})
    return out


def test_percentiles_and_rates_on_a_hand_made_log_and_a_stall_moves_them():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 95) == 95
    assert stats.percentile([], 95) is None
    calm = stats.serve_end_to_end(_log(), 1.0, worst_ms=9999.0)
    assert calm["serve_ttft_p95_ms"] == pytest.approx(5.0)
    assert calm["serve_itl_p95_ms"] == pytest.approx(10.0)
    assert calm["serve_tokens_per_s"] == pytest.approx(1100.0)
    # timed from when each request was DUE: a 200 ms stall makes the 20
    # requests due in it wait, and the tail shows it
    stalled = stats.serve_end_to_end(_log(stall_at=0.3), 1.0, 9999.0)
    assert stalled["serve_ttft_p95_ms"] > 150.0
    # a failed or unfinished request counts as the worst
    log = _log()
    for r in log[:10]:
        r["failed"] = True
    assert stats.serve_end_to_end(log, 1.0, 9999.0)[
        "serve_ttft_p95_ms"] == 9999.0


# ----------------------------------------------------- the trace reduction

FIXTURE = os.path.join(BENCH, "testdata", "train_trace_events.json")


def test_trace_reduce_on_a_recorded_trace():
    events = trace_reduce.load(FIXTURE)
    s = trace_reduce.reduce(events)
    meta = harness.load_json(FIXTURE)["recorded"]
    assert s["window_s"] == pytest.approx(meta["window_s"], rel=1e-6)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(meta["busy_s"], rel=1e-6)
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert s["device_ops"][0][1] >= s["device_ops"][-1][1]
    gap_total = sum(t for _, t in s["idle_gaps"])
    assert gap_total <= s["window_s"] - s["busy_s"] + 1e-9
    fwd = harness.load_json(os.path.join(
        BENCH, "metrics", "flash_attn_roofline.json"))["params"]
    for key in ("forward", "backward"):
        seconds, calls = trace_reduce.pattern_seconds(s, fwd[key])
        assert calls == meta["kernel_calls"][key] and seconds > 0


def test_trace_reduce_arithmetic_on_hand_made_events():
    ev = {"device": {0: [("a", 1.0, 2.0), ("b", 1.5, 2.5), ("a", 4.0, 5.0)],
                     1: [("a", 1.0, 1.5)]},
          "modules": {0: [("jit_step(1)", 1.0, 2.5)]},
          "host": [(trace_reduce.WINDOW_MARK, 0.0, 6.0),
                   ("train_step_call", 2.4, 3.2), ("bench_x", 3.2, 4.5)]}
    s = trace_reduce.reduce(ev)
    assert s["window_s"] == 6.0 and s["busiest_chip"] == 0
    assert s["busy_by_chip"] == {0: 2.5, 1: 0.5}
    assert s["busy_s"] == 1.5
    assert dict(s["device_ops"]) == {"a": 2.0, "b": 1.0}
    # the gap 2.5-4.0 is named by the span open at its middle
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"no_span_open": 2.0, "bench_x": 1.5})
    assert trace_reduce.op_kind("%fusion.1894 = (f32[1024]{0}) fusion(") \
        == "fusion"
    assert trace_reduce.op_kind("%transpose_jvp___.46 = (f32[2,4]") \
        == "transpose_jvp"
    assert trace_reduce.op_kind("%multiply_subtract_fusion = f32[8]") \
        == "multiply_subtract_fusion"
    assert trace_reduce.pattern_seconds(s, "^a$") == (2.0, 2)
    assert trace_reduce.pattern_seconds(s, "jit_step", "modules") == (1.5, 1)
    # no device operation traced: nothing to read, not a zero
    assert trace_reduce.reduce({"device": {}, "host": []}) is None


# ------------------------------------------------------------ flops, peaks

def test_flops_against_hand_worked_counts():
    g = load_arch("gpt2").dims(harness.load_json(
        os.path.join(BENCH, "configs", "gpt2-medium.json")))
    b = load_arch("bert").dims(harness.load_json(
        os.path.join(BENCH, "configs", "bert-large.json")))
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + vocab x 1024
    assert flops.dense_params(g) == 24 * 12582912 + 50304 * 1024 == 353501184
    assert flops.dense_params(b) == 24 * 12582912 + 30528 * 1024
    # 6 x dense x 4096 tokens + 6 x 24 x 4 x 1024^2 x 1024 (causal half)
    assert flops.gpt_train_flops(4, 1024, g) == (
        6 * 353501184 * 4096 + 6 * 24 * 4 * 1024 * 1024 * 1024)
    assert flops.bert_train_flops(8, 512, b) == (
        6 * flops.dense_params(b) * 4096 + 12 * 24 * 8 * 512 * 512 * 1024)
    # flash forward, causal, b4 h16 s1024 d64: 2 products of 2*b*h*s*s*d/2
    ops, nbytes = flops.flash_attention_cost(4, 16, 1024, 1024, 64, True,
                                             False)
    assert ops == 2 * 2 * 4 * 16 * 1024 * 1024 * 64 / 2
    assert nbytes == 4 * (4 * 16 * 1024 * 64 * 2)
    ops_b, _ = flops.flash_attention_cost(4, 16, 1024, 1024, 64, True, True)
    assert ops_b == 2 * ops
    peaks = harness.peaks_for("TPU v5 lite")
    t, bound = flops.least_seconds(ops, nbytes, peaks)
    assert bound == "operations" and t == pytest.approx(ops / 197e12)
    # one decode call: 4 dependent steps, each the weights once and the
    # live rows; a row is keys and values of 24 layers x 1024 x 4 bytes
    assert flops.kv_row_bytes(g) == 2 * 24 * 1024 * 4 == 196608
    assert flops.decode_call_bytes(g, 1000, 4) == 4 * (
        353501184 * 4 + 1000 * 196608)
    assert flops.prefill_flops(100, g) == (
        2 * 353501184 * 100 + 2 * 24 * 100 * 100 * 1024)


def test_an_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


# ------------------------------------------- references against the models

def _tiny(name):
    cfg = harness.load_json(os.path.join(TINY, "configs", name + ".json"))
    arch = load_arch(cfg["arch"])
    d = arch.dims(cfg)
    return cfg, arch, d, arch.layout(d)


# ------------------------- `correct`: sound runs, the control, the faults

@pytest.fixture(scope="module")
def train_system():
    """One compiled tiny step for the training tests below."""
    from benchmark.generators import train_steps
    files = tiny_files()
    import jax
    args = argparse.Namespace(seed=7, seconds=0.2, trace=0)
    r = harness.Run(files, files.cell("tiny-gpt2-train"), args,
                    time.perf_counter(), jax.devices()[:1])
    return r, train_steps.TrainSystem(r)


def _train_readings(system, seed, precision=None, fault=None):
    system.reseed(seed)
    prog = system.first_steps(seed)
    feeds = [system.batches[i][1] for i in range(train_check.STEPS)]
    ref = train_check.reference_steps(system.arch, system.d, system.layout,
                                      seed, feeds, system.hyper)
    if precision or fault:
        prog = train_check.reference_steps(
            system.arch, system.d, system.layout, seed, feeds, system.hyper,
            precision or "float32", fault)
    return compare.train_numbers(prog, ref)[0]


def test_gpt2_reference_agrees_with_the_models_logits(train_system):
    """The plain reference against the program's own forward pass, in
    float32, on the benchmark's weights (a seed past 2**31).  The BERT
    reference is held to the model by the child run of `tiny-bert-train`
    below, whose limits are a hundredth of what a wrong layer reads."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import functional_call
    _, system = train_system
    arch, d = system.arch, system.d
    w = W.make(system.layout, 2147483659)
    assert not np.array_equal(np.asarray(w["wte"]), np.asarray(
        W.make(system.layout, 11)["wte"]))
    train_check.load_weights(arch, d, system.model, w)
    state = {k: v._data for k, v in system.model.state_dict().items()}
    ids = np.random.RandomState(0).randint(0, 1000, (1, 24)).astype(np.int32)
    got = jax.jit(lambda s, x: functional_call(
        system.model, s, x, training=False))(state, jnp.asarray(ids))
    got = np.asarray(getattr(got, "_data", got))
    want = np.asarray(arch.reference.logits(w, jnp.asarray(ids[0]),
                                            d["heads"]))
    assert np.max(np.abs(got[0] - want)) < 2e-5


def test_training_sound_run_passes_and_the_fp8_control_fails(train_system):
    r, system = train_system
    sound = _train_readings(system, 7)
    rows, ok = compare.judge(sound, {k: v for k, v in r.limits.items()
                                     if k in sound})
    assert ok, rows
    # the reference with fp8 operands, put in the program's place
    control = _train_readings(system, 7, precision="fp8")
    assert control["first_loss_gap"] > 3 * sound["first_loss_gap"]
    assert control["grad_norm_median_gap"] > 3 * sound["grad_norm_median_gap"]
    rows, ok = compare.judge(control, {k: v for k, v in r.limits.items()
                                       if k in control})
    assert not ok, rows


def test_run_fails_when_the_step_returns_its_state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.jit import TrainStep
    inner = TrainStep._call_inner

    def unchanged(self, *batch):
        sd = self.model.state_dict()
        before = {k: jnp.copy(v._data) for k, v in sd.items()}
        opt_before = self._opt_state
        if opt_before is not None:
            import jax
            opt_before = jax.tree_util.tree_map(jnp.copy, opt_before)
        loss = inner(self, *batch)
        for k, v in before.items():
            sd[k]._set_data(v)
        if opt_before is not None:
            self._opt_state = opt_before
        return loss

    monkeypatch.setattr(TrainStep, "_call_inner", unchanged)
    res = tiny_run("tiny-gpt2-train")
    assert res["correct"] is False
    assert res["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_run_fails_when_half_of_the_batch_is_left_out(monkeypatch):
    from benchmark.generators import train_steps
    feed = train_steps.TrainSystem.feed

    def half(self, i):
        return tuple(x[: x.shape[0] // 2] for x in feed(self, i))

    monkeypatch.setattr(train_steps.TrainSystem, "feed", half)
    res = tiny_run("tiny-gpt2-train")
    assert res["correct"] is False
    bad = [k for k, v in res["checks"].items() if v["value"] > v["limit"]]
    assert "grad_norm_gap" in bad


def test_run_fails_when_a_served_token_is_altered(monkeypatch):
    from paddle_tpu.serving.engine import ServingEngine
    emit = ServingEngine._emit
    count = [0]

    def altered(self, run, tok, logp):
        count[0] += 1
        if count[0] % 5 == 0:
            tok = (int(tok) + 1) % 1000
        return emit(self, run, tok, logp)

    monkeypatch.setattr(ServingEngine, "_emit", altered)
    # through the flood kind (the open loop runs in a child below)
    res = tiny_run("tiny-gpt2-flood", seconds=0.5)
    assert res["correct"] is False
    assert (res["checks"]["token_logit_gap"]["value"]
            > res["checks"]["token_logit_gap"]["limit"])
    assert res["notes"]["mismatched_tokens"] > 0


def test_serving_control_in_bfloat16_reads_a_gap_where_float32_reads_none():
    """The reference in bfloat16 put in the program's place, at the same
    positions of the same prompts and tokens: the token it puts first lies
    below the float32 reference's best somewhere; the reference's own
    choice never does."""
    import jax.numpy as jnp
    cfg, arch, d, layout = _tiny("tiny-gpt2")
    w = W.make(layout, 3)
    rng = np.random.RandomState(3)
    plan = [{"prompt": rng.randint(0, 1000, 30).astype(np.int32)}
            for _ in range(6)]
    _, choice = serve_check.make_fns(arch, d)
    sample = []
    for i, p in enumerate(plan):   # "served" tokens: any, teacher-forced
        sample.append({"i": i, "tokens_list": rng.randint(
            0, 1000, 30).tolist()})
    own = serve_check.compare_sample(arch, d, layout, 3, plan, sample, 64,
                                     control="float32", w=w)
    assert own["token_logit_gap"] == 0.0 and own["checked_tokens"] == 180
    low = serve_check.compare_sample(arch, d, layout, 3, plan, sample, 64,
                                     control="bfloat16", w=w)
    assert low["token_logit_gap"] > 0.0 and low["mismatched_tokens"] > 0
    # random "served" tokens are far below the best: the number sees them
    served = serve_check.compare_sample(arch, d, layout, 3, plan, sample,
                                        64, w=w)
    assert served["token_logit_gap"] > 100 * low["token_logit_gap"]


# ------------------------------------------------------- child processes

_STARTED = {}


def _start(cell, env, trace, cache_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.run import main\n"
        "main(['--workload', %r, '--seed', '2147483659', '--seconds', '0.4',"
        " '--trace', %r], need_tpu=False, spec_path=%r, data_dirs=[%r, %r],"
        " cache_root=%r)\n" % (ROOT, cell, str(trace), os.path.join(
            TINY, "spec.json"), TINY, BENCH, str(cache_root)))
    return subprocess.Popen([sys.executable, "-c", code], env=env, cwd=ROOT,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def _finish(proc):
    try:
        out, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise
    assert proc.returncode == 0, err[-2000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    # the numbers compared, each beside its limit: the last lines on stderr
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail), tail
    return res


def test_child_train_cell_prints_one_result_line(cpu8_env, tmp_path):
    # both children start here and run side by side; the next test waits
    # for the second
    train = _start("tiny-bert-train", cpu8_env, 0, tmp_path / "train")
    _STARTED["serve"] = _start("tiny-gpt2-chat", cpu8_env, 1,
                               tmp_path / "serve")
    res = _finish(train)
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["attempted"] >= 1


def test_child_serve_cell_traced_run_reports_per_layer_metrics(cpu8_env,
                                                               tmp_path):
    proc = _STARTED.pop("serve", None) or _start(
        "tiny-gpt2-chat", cpu8_env, 1, tmp_path)
    res = _finish(proc)
    # host-clock metrics are read; device metrics find nothing on the CPU
    assert set(res["metrics"]) == {"serve_queue_wait_p95_ms",
                                   "serve_engine_step_ms"}
    assert res["notes"]["checked_tokens"] > 0


def test_no_chip_no_result_line_and_a_non_zero_exit(cpu8_env):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gpt2m-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=cpu8_env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
