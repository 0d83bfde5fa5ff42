"""Multi-tenant serving gateway (paddle_tpu.serving.gateway + slo).

Covers the ISSUE-6 contracts: SLO-aware admission (token buckets,
weighted fairness, shed policy), priority preemption with slot KV
save/restore resuming bit-identical, terminal Response states for EVERY
admission outcome (no consumer ever hangs), mid-decode deadline
enforcement against a chunk longer than the budget, and the OpenAI-shaped
port-free HTTP handler."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.core.errors import UnavailableError
from paddle_tpu.nn.layer_base import Layer
from paddle_tpu.nn.layer.common import Embedding
from paddle_tpu.serving import (ServingEngine, ServingGateway, TenantConfig,
                                TokenBucket, ShedPolicy, Signals,
                                RateLimitedError, SheddedError,
                                RequestCancelled, DeadlineExceededError,
                                PRIORITY_HIGH, PRIORITY_LOW)
from paddle_tpu.utils import faults

pytestmark = pytest.mark.gateway

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StubModel(Layer):
    """Minimal gen_fixed_cache/forward_fixed protocol model (cheap to
    compile; KV marks written positions so save/restore is visible)."""

    def __init__(self, vocab=24):
        super().__init__()
        self.emb = Embedding(vocab, vocab)

    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        import jax.numpy as jnp
        dt = dtype or jnp.float32
        return [(jnp.zeros((batch_size, max_length, 1, 2), dt),
                 jnp.zeros((batch_size, max_length, 1, 2), dt))]

    def forward_fixed(self, input_ids, caches, pos):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import unwrap
        ids = unwrap(input_ids)
        p = unwrap(pos)
        b, s = ids.shape
        logits = unwrap(self.emb(input_ids)).astype(jnp.float32)
        k, v = caches[0]
        chunk = jnp.ones((b, s, 1, 2), k.dtype)
        k = jax.lax.dynamic_update_slice(k, chunk, (0, p, 0, 0))
        v = jax.lax.dynamic_update_slice(v, chunk, (0, p, 0, 0))
        return logits, [(k, v)]


def stub_gateway(slots=1, max_len=32, chunk=2, **gw_kw):
    paddle.seed(3)
    m = StubModel()
    m.eval()
    eng = ServingEngine(m, max_slots=slots, max_len=max_len,
                        prefill_buckets=(8,), decode_chunk=chunk)
    eng.warmup()
    return ServingGateway(eng, **gw_kw)


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


def solo(model, prompt, max_new):
    out, _ = model.generate(paddle.to_tensor(
        np.asarray(prompt, np.int32)[None]), max_new_tokens=max_new)
    return np.asarray(out.numpy())[0].tolist()


# ---------------------------------------------------------------------------
# slo.py policy objects (no engine)
# ---------------------------------------------------------------------------

def test_token_bucket_rate_and_burst():
    t = [0.0]
    b = TokenBucket(rate=10.0, burst=2.0, _clock=lambda: t[0])
    assert b.try_take() and b.try_take()
    assert not b.try_take(), "burst exhausted"
    t[0] += 0.1  # refills one token at 10/s
    assert b.try_take()
    assert not b.try_take()
    assert TokenBucket(rate=float("inf")).try_take()


def test_shed_policy_rules():
    p = ShedPolicy(max_lane_depth=4, max_est_wait=1.0, ttft_slo=0.5,
                   shed_priority_below=1)
    ok = Signals(lane_depth=0, est_wait=0.1, ttft_p99_hi=0.1)
    assert p.decide(ok, 0) is None
    assert p.decide(Signals(lane_depth=4), 0) == "queue_depth"
    assert p.decide(Signals(lane_depth=4), 1) == "queue_depth", \
        "the hard lane cap applies to every priority"
    assert p.decide(Signals(est_wait=2.0), 0) == "est_wait"
    assert p.decide(Signals(est_wait=2.0), 1) is None, \
        "high priority is exempt from soft shedding"
    assert p.decide(Signals(ttft_p99_hi=0.9), 0) == "slo_pressure"
    # unknown signals (no completions yet) never shed
    assert p.decide(Signals(), 0) is None


def test_tenant_config_validation():
    with pytest.raises(ValueError):
        TenantConfig(weight=0.0)


def test_slo_tracker_ttft_window_decays_with_age():
    """A burst's over-SLO p99 must expire once the samples age out —
    otherwise slo_pressure would shed an idle system forever."""
    from paddle_tpu.serving import SLOTracker
    t = [0.0]
    tr = SLOTracker(max_age=10.0, _clock=lambda: t[0])
    for _ in range(20):
        tr.note_ttft("hi", 2.0)     # way over any SLO
    assert tr.ttft_p99("hi") == 2.0
    t[0] += 11.0                    # burst ages out, nothing new arrives
    assert tr.ttft_p99("hi") is None
    tr.note_ttft("hi", 0.1)
    assert tr.ttft_p99("hi") == 0.1


# ---------------------------------------------------------------------------
# admission outcomes are terminal responses (satellite: no consumer hangs)
# ---------------------------------------------------------------------------

def test_rate_limited_terminal_response():
    gw = stub_gateway(tenants={"t": TenantConfig(rate=0.0, burst=1.0)})
    try:
        ok = gw.submit(np.arange(4), 3, tenant="t")
        limited = gw.submit(np.arange(4), 3, tenant="t")
        assert limited.done(), "rejection must be terminal immediately"
        with pytest.raises(RateLimitedError):
            limited.tokens(timeout=1)
        gw.run_until_drained(timeout=60)
        assert ok.tokens(timeout=5) and ok.error is None
        assert gw.metrics()["rate_limited"] == 1
    finally:
        gw.close()


def test_shed_terminal_response_and_reason():
    gw = stub_gateway(shed=ShedPolicy(max_lane_depth=1))
    try:
        first = gw.submit(np.arange(4), 3)   # occupies the lane
        shedded = gw.submit(np.arange(4), 3)
        assert shedded.done()
        with pytest.raises(SheddedError) as ei:
            shedded.tokens(timeout=1)
        assert ei.value.reason == "queue_depth"
        gw.run_until_drained(timeout=60)
        assert first.error is None
        assert gw.metrics()["shed"] == 1
    finally:
        gw.close()


def test_invalid_request_terminal_response():
    gw = stub_gateway()
    try:
        r = gw.submit(np.arange(20), 3)  # > largest bucket: invalid
        assert r.done() and r.error is not None
        with pytest.raises(Exception):
            r.tokens(timeout=1)
        empty = gw.submit([], 3)  # Request ctor rejects before a rid
        assert empty.done() and empty.error is not None
    finally:
        gw.close()


def test_every_rejection_path_terminates():
    """Shed, rate-limited, deadline-expired-in-lane, preempted-then-
    cancelled, and gateway-close: every consumer gets a terminal state
    within a bounded wait (extends PR 4's loop-death/close-hang
    regressions to the gateway)."""
    gw = stub_gateway(
        slots=1,
        tenants={"limited": TenantConfig(rate=0.0, burst=1.0)},
        shed=ShedPolicy(max_lane_depth=2))
    outcomes = {}
    try:
        blocker = gw.submit(np.arange(4), 25)     # holds the only slot
        gw._tick()
        assert gw.engine.scheduler.occupancy() == 1
        outcomes["deadline"] = gw.submit(np.arange(4), 3, deadline=0.01)
        outcomes["queued"] = gw.submit(np.arange(4), 3)
        outcomes["shed"] = gw.submit(np.arange(4), 3)      # lane full
        # the limited tenant submits into the (empty) high lane: the shed
        # policy passes, so the empty token bucket is what rejects — shed
        # traffic must not reach the bucket, but bucket-limited traffic
        # still 429s
        gw.submit(np.arange(4), 2, tenant="limited",
                  priority=PRIORITY_HIGH)                  # takes burst
        outcomes["rate_limited"] = gw.submit(np.arange(4), 2,
                                             tenant="limited",
                                             priority=PRIORITY_HIGH)
        # preempt the blocker, then cancel it while paused
        hi = gw.submit(np.arange(4), 25, priority=PRIORITY_HIGH)
        time.sleep(0.03)   # deadline entry expires in the lane
        gw._tick()
        assert blocker.request.preempts >= 1
        blocker.cancel()
        gw._tick()
        outcomes["preempted_then_cancelled"] = blocker
        outcomes["close_while_queued"] = gw.submit(np.arange(4), 3)
        hi.cancel()
    finally:
        gw.close()
    expect = {
        "deadline": DeadlineExceededError,
        "queued": (RequestCancelled, Exception),
        "shed": SheddedError,
        "rate_limited": RateLimitedError,
        "preempted_then_cancelled": RequestCancelled,
        "close_while_queued": RequestCancelled,
    }
    for name, resp in outcomes.items():
        assert resp._done.wait(timeout=5), f"{name} consumer would hang"
        with pytest.raises(expect[name]):
            resp.tokens(timeout=1)
    # after close the gateway refuses new work terminally, not silently
    late = gw.submit(np.arange(4), 2)
    assert late.done()
    with pytest.raises(UnavailableError):
        late.tokens(timeout=1)


def test_gateway_loop_death_fails_everything():
    gw = stub_gateway(slots=1)

    def boom(*a, **k):
        raise RuntimeError("injected tick crash")

    gw.engine._decode_fn = boom
    gw.start()
    r = gw.submit(np.arange(4), 9)
    with pytest.raises(UnavailableError, match="injected tick crash"):
        r.tokens(timeout=10)
    late = gw.submit(np.arange(4), 2)
    with pytest.raises(UnavailableError, match="died"):
        late.tokens(timeout=1)
    gw.close()


# ---------------------------------------------------------------------------
# preemption: KV save/restore, bit-identical resume, zero new programs
# ---------------------------------------------------------------------------

def test_preempt_restore_bit_identical_gpt():
    model = tiny_gpt()
    eng = ServingEngine(model, max_slots=1, max_len=48,
                        prefill_buckets=(8,), decode_chunk=2)
    eng.warmup()
    compiles_before = eng.compile_counts()["total"]
    gw = ServingGateway(eng)
    try:
        low = gw.submit([1, 2, 3], 20)
        for _ in range(3):
            gw._tick()
        assert 1 <= len(low.tokens_so_far()) < 20
        hi = gw.submit([4, 5], 5, priority=PRIORITY_HIGH)
        gw.run_until_drained(timeout=120)
        assert low.request.preempts >= 1
        assert low.request.resumes >= 1
        assert hi.tokens(timeout=5) == solo(model, [4, 5], 5)
        # the victim's full stream is bit-identical to an uninterrupted
        # run: saved KV rows + RNG/position state restored exactly
        assert low.tokens(timeout=5) == solo(model, [1, 2, 3], 20)
        assert eng.compile_counts()["total"] == compiles_before, \
            "preempt/restore must add no compiled programs"
        assert gw.metrics()["preempted"] >= 1
        assert gw.metrics()["resumed"] >= 1
    finally:
        gw.close()


def test_preempt_snapshot_contents_and_slot_accounting():
    gw = stub_gateway(slots=1, chunk=2)
    eng = gw.engine
    try:
        r = gw.submit(np.arange(4), 20)
        gw._tick()
        (slot, run), = eng._slots.items()
        pos = run.pos
        paused = eng.preempt_slot(slot)
        assert eng.scheduler.free_slot_count() == 1
        assert paused.pos == pos and paused.produced == run.produced
        k_rows, v_rows = paused.kv_rows[0]
        assert k_rows.shape[0] == pos
        # the stub writes ones at every occupied position
        assert np.all(k_rows == 1) and np.all(v_rows == 1)
        assert not r.done(), "preemption must keep the stream open"
        assert eng.restore_run(paused)
        assert eng.scheduler.free_slot_count() == 0
        gw.run_until_drained(timeout=60)
        assert r.error is None and len(r.tokens(timeout=5)) == 20
    finally:
        gw.close()


def test_no_preemption_when_disabled():
    gw = stub_gateway(slots=1, preempt=False)
    try:
        low = gw.submit(np.arange(4), 10)
        gw._tick()
        hi = gw.submit(np.arange(4), 3, priority=PRIORITY_HIGH)
        gw.run_until_drained(timeout=60)
        assert gw.metrics()["preempted"] == 0
        assert low.request.preempts == 0
        assert low.error is None and hi.error is None
        # high still completes — after the low finishes
        assert hi.first_token_at > low.finished_at
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# weighted fairness + priority lanes
# ---------------------------------------------------------------------------

def test_weighted_fair_admission_order():
    gw = stub_gateway(
        slots=1,
        tenants={"heavy": TenantConfig(weight=2.0),
                 "light": TenantConfig(weight=1.0)})
    try:
        for _ in range(6):
            gw.submit(np.arange(4), 2, tenant="heavy")
            gw.submit(np.arange(4), 2, tenant="light")
        order = []
        for _ in range(9):
            entry, _tenant, _prev = gw._pop_lane(PRIORITY_LOW)
            order.append(entry.req.tenant)
        # stride scheduling: weight-2 tenant admitted ~2x as often
        assert order.count("heavy") == 6 and order.count("light") == 3, order
    finally:
        gw.close()


def test_priority_lane_admitted_first():
    gw = stub_gateway(slots=1)
    try:
        blocker = gw.submit(np.arange(4), 6)
        gw._tick()
        lows = [gw.submit(np.arange(4), 2) for _ in range(3)]
        hi = gw.submit(np.arange(4), 2, priority=PRIORITY_HIGH)
        gw.run_until_drained(timeout=60)
        assert hi.first_token_at < min(l.first_token_at for l in lows)
        assert blocker.error is None
    finally:
        gw.close()


def test_tenant_max_priority_clamped():
    gw = stub_gateway(
        slots=1, tenants={"free": TenantConfig(max_priority=0)})
    try:
        r = gw.submit(np.arange(4), 2, tenant="free",
                      priority=PRIORITY_HIGH)
        assert r.request.priority == PRIORITY_LOW, \
            "priority is a tenant entitlement, not caller-chosen"
        gw.run_until_drained(timeout=60)
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# mid-decode deadline enforcement (satellite: shorter than one chunk)
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_deadline_shorter_than_one_decode_chunk():
    """A deadline that expires INSIDE one compiled decode chunk must stop
    the stream on that very tick — no post-expiry tokens delivered, slot
    recycled — using the PDTPU_FAULT_SLOW_DECODE injection to make the
    chunk reliably slower than the budget."""
    paddle.seed(3)
    m = StubModel()
    m.eval()
    eng = ServingEngine(m, max_slots=1, max_len=32, prefill_buckets=(8,),
                        decode_chunk=4)
    eng.warmup()
    faults.enable("slow_decode", "80")  # every decode call sleeps 80ms
    try:
        r = eng.submit(np.arange(4), max_new_tokens=20, deadline=0.04)
        eng.step()  # prefill (fast) + one 80ms decode chunk
        with pytest.raises(DeadlineExceededError):
            r.tokens(timeout=5)
        assert len(r.tokens_so_far()) == 1, \
            "no chunk tokens may be delivered after expiry (prefill's " \
            "first token only)"
        assert eng.scheduler.free_slot_count() == eng.max_slots
    finally:
        faults.reset()
        eng.close()


@pytest.mark.faults
def test_slow_decode_stride_config():
    faults.enable("slow_decode", "5:3")
    try:
        assert faults.slow_decode_config() == (5.0, 3)
        assert faults.maybe_slow_decode(1) == 0.0
        assert faults.maybe_slow_decode(3) == 0.005
    finally:
        faults.reset()
    assert faults.slow_decode_config() is None
    assert faults.maybe_slow_decode(0) == 0.0


# ---------------------------------------------------------------------------
# tier-1 smoke: OpenAI-shaped port-free handler, tiny GPT, <= 3 requests
# ---------------------------------------------------------------------------

def test_gateway_openai_handler_smoke():
    model = tiny_gpt()
    eng = ServingEngine(model, max_slots=2, max_len=48,
                        prefill_buckets=(8,), decode_chunk=2)
    eng.warmup()
    gw = ServingGateway(eng, model_name="tiny-gpt")
    gw.start()
    try:
        # 1: non-stream completion, high priority
        status, ctype, body = gw.handle(
            "POST", "/v1/completions",
            json.dumps({"prompt": [1, 2, 3], "max_tokens": 5,
                        "priority": "high", "user": "gold"}).encode())
        assert status == 200 and ctype == "application/json"
        out = json.loads(body)
        assert out["object"] == "text_completion"
        assert out["choices"][0]["token_ids"] == solo(model, [1, 2, 3], 5)
        assert out["choices"][0]["finish_reason"] == "length"
        assert out["usage"]["total_tokens"] == 8
        # 2: streaming completion (SSE chunk iterator, no socket)
        status, ctype, chunks = gw.handle(
            "POST", "/v1/completions",
            json.dumps({"prompt": "4 5", "max_tokens": 4,
                        "stream": True}).encode())
        assert status == 200 and ctype == "text/event-stream"
        events = [c.decode() for c in chunks]
        assert events[-1] == "data: [DONE]\n\n"
        toks = []
        for e in events[:-1]:
            payload = json.loads(e[len("data: "):])
            toks += payload["choices"][0]["token_ids"]
        assert toks == solo(model, [4, 5], 4)
        finals = json.loads(events[-2][len("data: "):])
        assert finals["choices"][0]["finish_reason"] == "length"
        # 3: sampling via the OpenAI temperature knob
        status, _, body = gw.handle(
            "POST", "/v1/completions",
            json.dumps({"prompt": [2, 2], "max_tokens": 3,
                        "temperature": 0.8, "seed": 5}).encode())
        assert status == 200
        assert len(json.loads(body)["choices"][0]["token_ids"]) == 3
    finally:
        gw.close()


@pytest.mark.faults
def test_sse_abandoned_stream_cancels_request():
    """A streaming client that disconnects (generator closed) must cancel
    its request — an abandoned stream must not leave a KV slot decoding
    for nobody."""
    faults.enable("slow_decode", "20")  # keep the victim decoding
    gw = stub_gateway(slots=1)
    gw.start()
    try:
        status, ctype, chunks = gw.handle(
            "POST", "/v1/completions",
            json.dumps({"prompt": [1, 2], "max_tokens": 30,
                        "stream": True}).encode())
        assert status == 200
        next(chunks)     # client reads one event...
        chunks.close()   # ...then disconnects
        deadline = time.monotonic() + 5
        while (time.monotonic() < deadline
               and gw.engine.scheduler.occupancy()):
            time.sleep(0.01)
        assert gw.engine.scheduler.occupancy() == 0, \
            "abandoned stream still holds its slot"
    finally:
        faults.reset()
        gw.close()


def test_gateway_handler_error_statuses():
    gw = stub_gateway(
        tenants={"limited": TenantConfig(rate=0.0, burst=0.0)},
        shed=ShedPolicy(max_lane_depth=1))
    try:
        # empty-bucket tenant -> 429 (shed policy passes at depth 0)
        status, _, body = gw.handle(
            "POST", "/v1/completions",
            json.dumps({"prompt": [1], "max_tokens": 2,
                        "user": "limited"}).encode())
        assert status == 429
        assert json.loads(body)["error"]["type"] == "RateLimitedError"
        # fill the lane (queued, gateway not ticking), then the next
        # arrival sheds -> 503
        filler = gw.submit(np.arange(4), 2)
        status, _, body = gw.handle(
            "POST", "/v1/completions",
            json.dumps({"prompt": [1], "max_tokens": 2}).encode())
        assert status == 503
        assert json.loads(body)["error"]["type"] == "SheddedError"
        assert not filler.done(), "queued filler unaffected by the shed"
        # malformed body -> 400; unknown route -> 404; bad method -> 405
        assert gw.handle("POST", "/v1/completions", b"{nope")[0] == 400
        assert gw.handle("POST", "/v1/completions",
                         json.dumps({"prompt": []}).encode())[0] == 400
        assert gw.handle("GET", "/nope")[0] == 404
        assert gw.handle("PUT", "/v1/completions", b"{}")[0] == 405
        # models + health + metrics passthrough
        status, _, body = gw.handle("GET", "/v1/models")
        assert status == 200 and json.loads(body)["data"][0]["id"]
        status, _, body = gw.handle("GET", "/healthz")
        assert status == 200 and json.loads(body)["ok"] is True
        status, ctype, _ = gw.handle("GET", "/metrics")
        assert status == 200 and "version=0.0.4" in ctype
    finally:
        gw.close()
    status, _, body = gw.handle("GET", "/healthz")
    assert status == 503 and json.loads(body)["ok"] is False


# ---------------------------------------------------------------------------
# inference.Config wiring
# ---------------------------------------------------------------------------

def test_enable_serving_gateway_wiring():
    from paddle_tpu.inference import Config, create_predictor
    model = tiny_gpt()
    cfg = Config()
    cfg.enable_serving(
        model=model, max_slots=2, max_len=48, prefill_buckets=(8,),
        decode_chunk=2, start=False,
        gateway={"tenants": {"gold": TenantConfig(weight=2.0)},
                 "model_name": "wired"})
    pred = create_predictor(cfg)
    try:
        assert pred.gateway is not None
        r = pred.submit([1, 2, 3], max_new_tokens=4, tenant="gold",
                        priority=PRIORITY_HIGH)
        pred.gateway.run_until_drained(timeout=120)
        assert r.tokens(timeout=5) == solo(model, [1, 2, 3], 4)
        rep = pred.profile_report()
        assert rep["gateway"]["admitted"] >= 1
        assert "engine" not in rep["gateway"]
        met = pred.metrics()
        assert met["tenants"]["gold"]["weight"] == 2.0
        # observability.report() carries the gateway section
        from paddle_tpu import observability
        assert observability.report()["gateway"]["admitted"] >= 1
    finally:
        pred.close()


def test_gateway_refuses_started_engine():
    from paddle_tpu.core.errors import InvalidArgumentError
    paddle.seed(3)
    m = StubModel()
    m.eval()
    eng = ServingEngine(m, max_slots=1, max_len=32, prefill_buckets=(8,))
    eng.start()
    try:
        with pytest.raises(InvalidArgumentError, match="gateway drives"):
            ServingGateway(eng)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# probe smoke (fresh interpreter: slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gateway_probe_smoke():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "probes", "gateway_probe.py"),
         "--steps", "3"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert proc.returncode == 0, (proc.stderr or proc.stdout)[-800:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("GATE")]
    assert lines, proc.stdout[-400:]
    out = json.loads(lines[-1][len("GATE"):])
    assert out["smoke"] is True
    assert "failures" not in out, out.get("failures")
    assert out["completed"] == 3
    assert out["compile_counts"]["total"] <= out["compile_counts"]["bound"]
