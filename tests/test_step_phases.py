"""The phases `engine.step()` and `TrainStep.__call__` trace of themselves
(PERF.md section 3 lists the names: they are a contract with the
benchmark's reduction and its `program_span_stat` metrics).

One tracer, always on; every span is also a `jax.profiler.TraceAnnotation`,
so a profiler session that anyone starts holds the phases in its host plane.
"""
import collections
import glob
import json
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models, observability as obs, parallel
from paddle_tpu.nn.layer_base import Layer
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

pytestmark = [pytest.mark.serving, pytest.mark.observability]

NAME, T0, DUR, TID, ID, PARENT, ARGS = range(7)


def tiny_gpt():
    cfg = models.GPTConfig(vocab_size=13, hidden_size=16,
                           num_hidden_layers=2, num_attention_heads=2,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=64)
    paddle.seed(7)
    m = models.GPTForPretraining(cfg)
    m.eval()
    return m


ENGINES = {
    "fixed": dict(),
    "paged": dict(kv="paged", block_size=4),
    "prefix": dict(kv="paged", block_size=4, prefix_cache=True),
    "speculative": dict(spec_tokens=2),   # + draft_model, built in the test
}
PER_REQUEST = ("serving_queue_wait", "serving_admit",
               "serving_prefill_dispatch", "serving_prefill_wait",
               "serving_request")


def _engine(kind):
    kw = dict(ENGINES[kind])
    if kind == "speculative":
        kw["draft_model"] = tiny_gpt()
    return ServingEngine(tiny_gpt(), max_slots=2, max_len=32,
                         prefill_buckets=(8,), decode_chunk=2, **kw)


def _drive(eng, tracer):
    """Three requests through two slots; -> the responses and, per
    `step()`, the ring's new events."""
    resps = [eng.submit([1, 2, 3], 11), eng.submit([4, 5, 6, 7], 3),
             eng.submit([2, 2], 2)]
    steps = []
    while eng.has_work():
        before = len(tracer)
        eng.step()
        steps.append(tracer.events()[before:])
    return resps, steps


# what the little schedule leaves: the plain decode call emits 2 tokens a
# slot, the speculative tick up to 3, so it needs a step less
EXPECTED = {
    "decode": dict(serving_step=5, serving_sweep=5, serving_decode=5,
                   serving_batch_rebuild=3, serving_decode_dispatch=5,
                   serving_token_pull=5, serving_deliver=5,
                   **{n: 3 for n in PER_REQUEST}),
    "verify": dict(serving_step=4, serving_sweep=4, serving_verify=4,
                   serving_batch_rebuild=3, serving_decode_dispatch=4,
                   serving_token_pull=4, serving_deliver=4,
                   **{n: 3 for n in PER_REQUEST}),
}


@pytest.mark.parametrize("kind", list(ENGINES))
def test_engine_steps_leave_the_phase_spans_and_nothing_per_token(kind):
    tracer = obs.get_tracer()
    eng = _engine(kind)
    try:
        tracer.clear()
        resps, steps = _drive(eng, tracer)
        assert eng.step() is False and len(tracer) == sum(map(len, steps))
    finally:
        eng.close()
    assert [len(r.tokens()) for r in resps] == [11, 3, 2]
    events = [ev for step in steps for ev in step]
    names = collections.Counter(ev[NAME] for ev in events)
    assert names == EXPECTED["verify" if kind == "speculative" else "decode"]

    # a request's timeline shares its identifier, on one clock
    for r in resps:
        mine = {ev[NAME]: ev for ev in events
                if ev[ARGS] and ev[ARGS].get("request") == r.request.id}
        assert set(mine) == {"serving_queue_wait", "serving_admit",
                             "serving_request"}
        wait, admit, whole = (mine["serving_queue_wait"],
                              mine["serving_admit"], mine["serving_request"])
        assert wait[T0] == whole[T0] == r.submitted_at
        assert wait[T0] + wait[DUR] == r.admitted_at <= admit[T0]
        assert admit[T0] <= r.first_token_at <= admit[T0] + admit[DUR]
        assert whole[T0] + whole[DUR] == r.finished_at
        assert whole[ARGS]["tokens"] == len(r.tokens())
        assert whole[ARGS]["finish"] == "length"
        assert admit[ARGS]["bucket"] == 8
        assert admit[ARGS]["plen"] == whole[ARGS]["prompt"]

    # children lie inside their parents; the two spans that start in the
    # past (`Tracer.record`) have none
    by_id = {ev[ID]: ev for ev in events}
    tree = {"serving_sweep": "serving_step", "serving_admit": "serving_step",
            "serving_decode": "serving_step", "serving_verify": "serving_step",
            "serving_prefill_dispatch": "serving_admit",
            "serving_prefill_wait": "serving_admit"}
    call = "serving_verify" if kind == "speculative" else "serving_decode"
    for child in ("serving_batch_rebuild", "serving_decode_dispatch",
                  "serving_token_pull", "serving_deliver"):
        tree[child] = call
    for ev in events:
        if ev[NAME] in ("serving_queue_wait", "serving_request",
                        "serving_step"):
            assert ev[PARENT] is None
            continue
        parent = by_id[ev[PARENT]]
        assert parent[NAME] == tree[ev[NAME]], ev[NAME]
        assert parent[T0] <= ev[T0]
        assert ev[T0] + ev[DUR] <= parent[T0] + parent[DUR]

    # counts ride on the spans; at most 7 spans in a step that admits
    # nothing and ends nothing, 4 more a request admitted, 1 a request ended
    quiet = 0
    for i, step in enumerate(steps, 1):
        n = collections.Counter(ev[NAME] for ev in step)
        assert len(step) <= 7 + 4 * n["serving_admit"] + n["serving_request"]
        quiet += not (n["serving_admit"] or n["serving_request"])
        top = [ev for ev in step if ev[NAME] == "serving_step"]
        assert [ev[ARGS]["step"] for ev in top] == [i]
        dec = [ev for ev in step if ev[NAME] == call][0]
        deliver = [ev for ev in step if ev[NAME] == "serving_deliver"][0]
        assert 1 <= dec[ARGS]["active"] <= 2 and dec[ARGS]["calls"] == i
        assert deliver[ARGS]["finished"] == sum(
            1 for ev in step if ev[NAME] == "serving_request")
        assert deliver[ARGS]["tokens"] >= 1
    assert quiet >= 1
    assert sum(ev[ARGS]["tokens"] for ev in events
               if ev[NAME] == "serving_deliver") == 16 - 3  # less the firsts


def test_a_failed_request_ends_its_timeline_too():
    tracer = obs.get_tracer()
    eng = _engine("fixed")
    try:
        tracer.clear()
        r = eng.submit([1, 2, 3], 4)
        r.cancel()
        eng.step()
    finally:
        eng.close()
    whole = [ev for ev in tracer.events() if ev[NAME] == "serving_request"]
    assert len(whole) == 1 and whole[0][ARGS] == {
        "request": r.request.id, "prompt": 3, "tokens": 0,
        "finish": "error", "error": "RequestCancelled"}
    assert r.admitted_at is None


# ------------------------------------------------------------- training

class _MLP(Layer):
    def __init__(self):
        super().__init__()
        self.a = paddle.nn.Linear(8, 16)
        self.b = paddle.nn.Linear(16, 1)

    def forward(self, x):
        return self.b(paddle.nn.functional.relu(self.a(x)))


def _mse(out, y):
    return paddle.nn.functional.mse_loss(out, y)


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["TrainStep", "ShardedTrainStep"])
def test_three_train_steps_leave_four_names_numbered_1_2_3(sharded):
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    model = _MLP()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    if sharded:
        step = parallel.ShardedTrainStep(
            model, _mse, opt, mesh=parallel.create_mesh({"dp": 8}))
    else:
        step = TrainStep(model, _mse, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 1).astype("float32"))
    tracer = obs.get_tracer()
    tracer.clear()
    for _ in range(3):
        step(x, y)
    events = [ev for ev in tracer.events()
              if ev[NAME].startswith("train_step")]
    assert collections.Counter(ev[NAME] for ev in events) == {
        "train_step": 3, "train_step_gather_state": 3,
        "train_step_dispatch": 3, "train_step_write_back": 3}
    tops = [ev for ev in events if ev[NAME] == "train_step"]
    assert [ev[ARGS]["step"] for ev in tops] == [1, 2, 3]
    by_id = {ev[ID]: ev for ev in tops}
    for ev in events:
        if ev[NAME] != "train_step":
            parent = by_id[ev[PARENT]]
            assert parent[T0] <= ev[T0]
            assert ev[T0] + ev[DUR] <= parent[T0] + parent[DUR]


# --------------------------------------- in a profiler session, and reduced

def test_a_profiler_session_holds_the_phases_and_the_reduction_names_a_gap(
        tmp_path):
    """Nobody told the engine about the session: the spans are annotations
    by themselves.  A CPU session has no `/device:TPU` plane, so the
    device's events are hand-made around the host's."""
    import jax
    from benchmark import trace_reduce
    eng = _engine("fixed")
    try:
        eng.warmup()
        eng.submit([1, 2, 3], 6)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            eng.step()
            eng.step()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    path = trace_reduce.find_xplane(str(tmp_path))
    data = jax.profiler.ProfileData.from_file(path)
    in_host_plane = {ev.name for plane in data.planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events}
    want = {"serving_step", "serving_sweep", "serving_admit",
            "serving_prefill_dispatch", "serving_prefill_wait",
            "serving_decode", "serving_batch_rebuild",
            "serving_decode_dispatch", "serving_token_pull",
            "serving_deliver"}
    assert want <= in_host_plane
    events = trace_reduce.load(path)
    assert events["device"] == {}
    host = events["host"]
    assert want <= {name for name, _, _ in host}
    assert sum(name == "serving_step" for name, _, _ in host) == 2

    # the device busy from the first step's start to its end, but for a
    # stretch in the middle of the enqueue of the decode call
    step = min((ev for ev in host if ev[0] == "serving_step"),
               key=lambda ev: ev[1])
    inner = min((ev for ev in host if ev[0] == "serving_decode_dispatch"),
                key=lambda ev: ev[1])
    assert step[1] <= inner[1] and inner[2] <= step[2]
    third = (inner[2] - inner[1]) / 3
    gap = (inner[1] + third, inner[2] - third)
    events["device"] = {0: [("%fusion.1 = f32[8]", step[1], gap[0]),
                            ("%fusion.2 = f32[8]", gap[1], step[2])]}
    events["modules"] = {}
    summary = trace_reduce.reduce(events)
    assert [name for name, _ in summary["idle_gaps"]] == [
        "serving_decode_dispatch"]
    assert summary["idle_gaps"][0][1] == pytest.approx(third)


# ------------------------------- the names the benchmark's patterns match

def _module_name(fn, args):
    text = fn.lower(*args).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def _tiny_routed():
    m = models.CohereMoEForCausalLM(models.CohereMoEConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, sliding_window=8, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=2, dtype="float32", experts_held=(1, 4, 6)))
    m.eval()
    return m


def _engine_of(kind):
    """The five kinds of engine the three program bodies are built for:
    three of `ENGINES`, one with adapters, one over a batched model."""
    if kind in ENGINES:
        return _engine(kind)
    from paddle_tpu.lora import LoRAConfig
    model, extra = ((_tiny_routed(), dict()) if kind == "batched" else
                    (tiny_gpt(), dict(lora=LoRAConfig(
                        rank=4, max_adapters=3, targets=("qkv",)))))
    return ServingEngine(model, max_slots=2, max_len=32,
                         prefill_buckets=(8,), decode_chunk=2, **extra)


@pytest.mark.parametrize("kind", ["fixed", "paged", "adapters",
                                  "speculative", "batched"])
def test_every_kind_of_engine_lowers_the_three_named_programs(kind):
    """One body each for prefill, decode and verify, whatever the engine
    is configured with: the jitted functions keep the names the trace's
    "XLA Modules" line shows, and every program takes the one signature
    `(weights, pools, inputs)` with the pools donated."""
    eng = _engine_of(kind)
    try:
        family = eng._program_family()
        names = {name: _module_name(fn, args) for name, fn, args, _ in family}
        for _, _, args, donate in family:
            weights, pools, inputs = args
            assert donate == (1,)
            assert set(weights) == {"model"} | (
                {"draft"} if kind == "speculative" else set()) | (
                {"lora"} if kind == "adapters" else set())
            assert set(pools) == set(weights) - {"lora"}
            assert isinstance(inputs, dict)
    finally:
        eng.close()
    assert names == {"prefill_b8": "jit_prefill",
                     "decode": "jit_verify" if kind == "speculative"
                     else "jit_decode"}


def test_program_patterns_in_the_metrics_match_the_programs_they_name():
    """`decode_*_roofline` finds the decode program's executions in the
    trace's "XLA Modules" line by `^jit_decode\\(`: a refactor that renames
    `decode` must fail here, not read `null` on the chip."""
    from paddle_tpu.jit import TrainStep
    eng = _engine("fixed")
    try:
        family = {name: _module_name(fn, args)
                  for name, fn, args, _ in eng._program_family()}
    finally:
        eng.close()
    model = _MLP()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = TrainStep(model, _mse, opt)
    x = paddle.to_tensor(np.zeros((8, 8), "float32"))
    y = paddle.to_tensor(np.zeros((8, 1), "float32"))
    step.warmup(x, y)
    from paddle_tpu.jit import state_arrays
    from paddle_tpu.core.tensor import unwrap
    import jax.numpy as jnp
    state = state_arrays(model)
    family["train_step"] = _module_name(step._compiled, (
        state, step._opt_state, jnp.int32(1), jnp.float32(0.1),
        paddle.core.rng.next_key(), (unwrap(x), unwrap(y))))
    assert family == {"prefill_b8": "jit_prefill", "decode": "jit_decode",
                      "train_step": "jit_step"}
    # a module's event in the trace is its name and its program id
    as_traced = {k: v + "(12345)" for k, v in family.items()}
    patterns = {}
    for path in glob.glob(os.path.join(ROOT, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            params = json.load(f).get("params", {})
        if "program" in params:
            patterns[os.path.basename(path)] = params["program"]
    assert {"decode_chat_roofline.json", "decode_flood_roofline.json"} <= set(
        patterns)
    for metric, pattern in patterns.items():
        hits = [k for k, v in as_traced.items() if re.search(pattern, v)]
        assert hits == ["decode"], (metric, pattern, as_traced)
