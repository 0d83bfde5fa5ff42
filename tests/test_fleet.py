"""Multi-replica serving fleet (paddle_tpu.serving.fleet + transfer).

Covers the ISSUE-12 contracts: least-loaded/session-affine routing,
fence-on-crash with resubmission failover (streams bit-identical to the
uninterrupted oracle), the non-migratable -> typed-terminal matrix,
drain-then-rollout with zero dropped requests, the replica-portable run
transfer codec (bytes round-trip + loud incompatibility), brownout
fencing from step-time health, concurrent double-close idempotency, and
the gateway /healthz fleet aggregation."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.core.errors import InvalidArgumentError, UnavailableError
from paddle_tpu.nn.layer_base import Layer
from paddle_tpu.nn.layer.common import Embedding
from paddle_tpu.serving import (FleetRouter, ReplicaLostError,
                                RequestCancelled, RunTransferError,
                                ServingEngine, ServingGateway,
                                TenantConfig, decode_run, encode_run,
                                run_from_bytes, run_to_bytes)
from paddle_tpu.utils import faults

pytestmark = pytest.mark.fleet

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_model_cache = {}


class StubModel(Layer):
    """Minimal gen_fixed_cache/forward_fixed protocol model — cheap to
    compile, for routing/lifecycle tests that never check token
    values."""

    def __init__(self, vocab=24, dim=2):
        super().__init__()
        self.emb = Embedding(vocab, vocab)
        self.dim = dim

    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        import jax.numpy as jnp
        dt = dtype or jnp.float32
        return [(jnp.zeros((batch_size, max_length, 1, self.dim), dt),
                 jnp.zeros((batch_size, max_length, 1, self.dim), dt))]

    def forward_fixed(self, input_ids, caches, pos):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import unwrap
        ids = unwrap(input_ids)
        p = unwrap(pos)
        b, s = ids.shape
        logits = unwrap(self.emb(input_ids)).astype(jnp.float32)
        k, v = caches[0]
        chunk = jnp.ones((b, s, 1, self.dim), k.dtype)
        k = jax.lax.dynamic_update_slice(k, chunk, (0, p, 0, 0))
        v = jax.lax.dynamic_update_slice(v, chunk, (0, p, 0, 0))
        return logits, [(k, v)]


def tiny_gpt():
    m = _model_cache.get("gpt")
    if m is None:
        cfg = models.GPTConfig(vocab_size=13, hidden_size=16,
                               num_hidden_layers=2, num_attention_heads=2,
                               hidden_dropout_prob=0.0,
                               attention_probs_dropout_prob=0.0,
                               max_position_embeddings=64)
        paddle.seed(7)
        m = models.GPTForPretraining(cfg)
        m.eval()
        _model_cache["gpt"] = m
    return m


def gpt_engine(slots=2, max_len=48, chunk=2, **kw):
    return ServingEngine(tiny_gpt(), max_slots=slots, max_len=max_len,
                         prefill_buckets=(8,), decode_chunk=chunk, **kw)


def stub_engine(slots=2, **kw):
    m = _model_cache.get("stub")
    if m is None:
        paddle.seed(3)
        m = StubModel()
        m.eval()
        _model_cache["stub"] = m
    return ServingEngine(m, max_slots=slots, max_len=32,
                         prefill_buckets=(8,), decode_chunk=2, **kw)


def gpt_fleet(n=2, slots=2, **kw):
    fleet = FleetRouter([gpt_engine(slots=slots) for _ in range(n)], **kw)
    fleet.warmup()
    return fleet


def stub_fleet(n=2, slots=2, **kw):
    fleet = FleetRouter([stub_engine(slots=slots) for _ in range(n)], **kw)
    fleet.warmup()
    return fleet


def solo(prompt, max_new):
    out, _ = tiny_gpt().generate(
        paddle.to_tensor(np.asarray(prompt, np.int32)[None]),
        max_new_tokens=max_new)
    return np.asarray(out.numpy())[0].tolist()


def prompts(n, seed=0, plen=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 13, (plen,)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# fault-knob parsing + request fields
# ---------------------------------------------------------------------------

def test_replica_fault_configs_parse():
    faults.enable("replica_crash", "2:17")
    assert faults.replica_crash_config() == (2, 17)
    faults.enable("replica_slow", "25")
    assert faults.replica_slow_config() == (25.0, 1, None)
    faults.enable("replica_slow", "25:4")
    assert faults.replica_slow_config() == (25.0, 4, None)
    faults.enable("replica_slow", "25:4:1")
    assert faults.replica_slow_config() == (25.0, 4, 1)
    # targeted: wrong replica never sleeps
    assert faults.maybe_slow_replica(0, 0) == 0.0
    assert faults.maybe_slow_replica(1, 0) > 0.0
    assert faults.maybe_slow_replica(1, 1) == 0.0  # off-stride
    faults.reset()
    assert faults.replica_crash_config() is None
    assert faults.replica_slow_config() is None


def test_resubmit_requires_greedy_and_fields_ride():
    eng = stub_engine()
    with pytest.raises(InvalidArgumentError):
        eng.make_request([1, 2, 3], 4, decode_strategy="sampling",
                         resubmit=True)
    req, _ = eng.make_request([1, 2, 3], 4, session="u1", resubmit=True)
    assert req.session == "u1" and req.resubmit and req.migrations == 0
    eng.close()


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_routing_least_loaded_spreads():
    fleet = stub_fleet(n=3)
    for _ in range(3):
        fleet.submit([1, 2, 3], 4)
    loads = [r.engine.scheduler.queue_depth()
             for r in fleet.manager.replicas()]
    assert loads == [1, 1, 1], loads
    fleet.close()


def test_session_affinity_sticky_then_rehomes():
    fleet = stub_fleet(n=3)
    fleet.submit([1, 2, 3], 4, session="s")
    fleet.submit([1, 2, 3], 4, session="s")
    loads = {r.id: r.engine.scheduler.queue_depth()
             for r in fleet.manager.replicas()}
    pinned = [rid for rid, n in loads.items() if n == 2]
    assert len(pinned) == 1, loads
    # fence the pinned replica: the session re-homes to a survivor
    fleet.drain(pinned[0])
    fleet.submit([1, 2, 3], 4, session="s")
    loads2 = {r.id: r.engine.scheduler.queue_depth()
              for r in fleet.manager.replicas()}
    assert loads2[pinned[0]] == 0, "drained replica must get nothing"
    assert sum(loads2.values()) == 1 + loads[pinned[0]]
    fleet.run_until_drained(timeout=30)
    fleet.close()


def test_unwarm_replica_never_routed():
    warm = stub_engine()
    cold = stub_engine()
    fleet = FleetRouter([warm])
    fleet.warmup()
    rid_cold = fleet.add_replica(cold)  # never warmed: stays booting
    for _ in range(3):
        fleet.submit([1, 2, 3], 4)
    assert cold.scheduler.queue_depth() == 0
    assert fleet.manager.get(rid_cold).state == "booting"
    assert not fleet.manager.get(rid_cold).routable()
    fleet.close()


# ---------------------------------------------------------------------------
# parity + crash failover
# ---------------------------------------------------------------------------

def test_fleet_streams_bit_identical_to_solo():
    fleet = gpt_fleet(n=2)
    ps = prompts(4)
    resps = [fleet.submit(p, 12, session=f"u{i % 2}")
             for i, p in enumerate(ps)]
    fleet.run_until_drained(timeout=60)
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=5) == solo(p, 12)
    fleet.close()


def test_crash_failover_resubmit_bit_identical():
    fleet = gpt_fleet(n=2)
    ps = prompts(4)
    resps = [fleet.submit(p, 12, resubmit=True) for p in ps]
    for _ in range(3):
        fleet.step()
    assert all(len(r.tokens_so_far()) > 0 for r in resps), \
        "crash must land mid-decode"
    rep = fleet.manager.get(1)
    faults.enable("replica_crash", f"1:{rep.steps}")
    fleet.run_until_drained(timeout=60)
    faults.reset()
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=5) == solo(p, 12), \
            "resubmitted stream must be bit-identical end to end"
    c = fleet.manager.counters()
    assert c["failovers"] == 1 and c["resubmits"] >= 1 and c["lost"] == 0
    assert fleet.manager.get(1).state == "crashed"
    assert all(r.error is None for r in resps), \
        "every opted-in stream completes despite the crash"
    fleet.close()


def test_crash_terminal_matrix():
    """Non-migratable outcomes: resident without resubmit -> typed
    ReplicaLostError; queued-but-never-prefilled -> re-routed and served
    in full; nothing hangs."""
    fleet = gpt_fleet(n=2, slots=1)
    ps = prompts(4, seed=3)
    # two residents (one per replica), two queued behind them
    resps = [fleet.submit(p, 12) for p in ps]
    for _ in range(3):
        fleet.step()
    rep = fleet.manager.get(0)
    assert rep.engine.scheduler.occupancy() == 1
    faults.enable("replica_crash", f"0:{rep.steps}")
    fleet.run_until_drained(timeout=60)
    faults.reset()
    lost = done = 0
    for p, r in zip(ps, resps):
        assert r.done(), "every consumer must reach a terminal state"
        if r.error is None:
            assert r.tokens(timeout=5) == solo(p, 12)
            done += 1
        else:
            assert isinstance(r.error, ReplicaLostError)
            lost += 1
    assert lost == 1, "exactly the crashed replica's resident is lost"
    assert done == 3, "queued work re-routes and completes"
    fleet.close()


def test_crash_resubmit_without_capacity_is_typed():
    fleet = gpt_fleet(n=1)
    ps = prompts(1, seed=5)
    r = fleet.submit(ps[0], 12, resubmit=True)
    for _ in range(3):
        fleet.step()
    rep = fleet.manager.get(0)
    faults.enable("replica_crash", f"0:{rep.steps}")
    fleet.step()
    faults.reset()
    with pytest.raises(ReplicaLostError):
        r.tokens(timeout=5)
    fleet.close()


# ---------------------------------------------------------------------------
# drain + migration + rollout
# ---------------------------------------------------------------------------

def test_drain_migrates_mid_decode_bit_identical():
    fleet = gpt_fleet(n=2)
    ps = prompts(2, seed=1)
    resps = [fleet.submit(p, 16, session="pin") for p in ps]
    for _ in range(3):
        fleet.step()
    assert fleet.manager.get(0).engine.scheduler.occupancy() == 2
    assert all(len(r.tokens_so_far()) > 0 for r in resps)
    fleet.drain(0)
    fleet.run_until_drained(timeout=60)
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=5) == solo(p, 16), \
            "migrated stream must be bit-identical"
    assert all(r.request.migrations >= 1 for r in resps)
    c = fleet.manager.counters()
    assert c["migrated"] >= 2 and c["lost"] == 0
    assert fleet.manager.get(0).state == "closed"
    fleet.close()


def test_drain_full_fleet_finishes_in_place():
    """No spare capacity anywhere: draining must NOT drop or hang the
    residents — they finish on the draining replica, then it closes."""
    fleet = gpt_fleet(n=2)
    ps = prompts(4, seed=2)
    resps = [fleet.submit(p, 12) for p in ps]
    for _ in range(3):
        fleet.step()  # both replicas full (2 slots each)
    fleet.drain(0)
    fleet.run_until_drained(timeout=60)
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=5) == solo(p, 12)
    assert fleet.manager.get(0).state == "closed"
    fleet.close()


def test_rollout_under_traffic_zero_drops():
    fleet = gpt_fleet(n=2)
    fleet.start()
    ps = prompts(10, seed=4)
    oracle = {p.tobytes(): solo(p, 10) for p in ps}
    resps = []

    def submitter():
        for i, p in enumerate(ps):
            resps.append((p, fleet.submit(p, 10, session=f"u{i % 3}")))
            time.sleep(0.03)

    t = threading.Thread(target=submitter)
    t.start()
    time.sleep(0.1)
    new_ids = fleet.rollout(gpt_engine)
    t.join()
    deadline = time.time() + 60
    for p, r in resps:
        got = r.tokens(timeout=max(0.1, deadline - time.time()))
        assert got == oracle[p.tobytes()]
    assert len(resps) == len(ps), "zero dropped requests"
    assert sorted(r.id for r in fleet.manager.replicas()) == new_ids
    # post-rollout traffic compiles nothing
    r2 = fleet.submit(ps[0], 10)
    assert r2.tokens(timeout=30) == oracle[ps[0].tobytes()]
    assert fleet.post_warmup_compiles() == 0
    fleet.close()


def test_brownout_fences_migrates_then_recovers():
    fleet = gpt_fleet(n=2, slow_threshold_ms=20)
    ps = prompts(2, seed=6)
    resps = [fleet.submit(p, 20, session="pin") for p in ps]
    for _ in range(3):
        fleet.step()
    assert fleet.manager.get(0).engine.scheduler.occupancy() == 2
    faults.enable("replica_slow", "60:1:0")  # 60ms/step, replica 0 only
    fleet.run_until_drained(timeout=120)
    faults.reset()
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=5) == solo(p, 20), \
            "browned-out replica's streams migrate bit-identical"
    c = fleet.manager.counters()
    assert c["migrated"] >= 1 and c["failovers"] >= 1
    assert fleet.manager.get(0).state == "degraded"
    # disarmed: probation sampling returns the replica to rotation
    for _ in range(400):
        fleet.step()
    assert fleet.manager.get(0).state == "healthy"
    fleet.close()


def test_drain_without_peer_queue_space_serves_in_place():
    """Zero-drop under queue pressure: a single-replica fleet (no peer
    exists at all) drains with queued work — the queued requests are
    served by the draining replica before it closes, never failed."""
    fleet = gpt_fleet(n=1, slots=1)
    ps = prompts(3, seed=11)
    resps = [fleet.submit(p, 8) for p in ps]
    fleet.drain(0)
    fleet.run_until_drained(timeout=60)
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=5) == solo(p, 8)
    assert fleet.manager.counters()["lost"] == 0
    assert fleet.manager.get(0).state == "closed"
    fleet.close()


def test_affinity_map_is_lru_bounded():
    fleet = stub_fleet(n=2, max_sessions=4)
    for i in range(10):
        fleet.submit([1, 2, 3], 2, session=f"s{i}")
    assert len(fleet._affinity) == 4
    assert set(fleet._affinity) == {"s6", "s7", "s8", "s9"}
    fleet.run_until_drained(timeout=30)
    fleet.close()


def test_crash_releases_scheduler_bookkeeping():
    fleet = gpt_fleet(n=2)
    ps = prompts(4, seed=12)
    resps = [fleet.submit(p, 12, resubmit=True) for p in ps]
    for _ in range(3):
        fleet.step()
    rep = fleet.manager.get(0)
    assert rep.engine.scheduler.occupancy() == 2
    faults.enable("replica_crash", f"0:{rep.steps}")
    fleet.run_until_drained(timeout=60)
    faults.reset()
    assert rep.engine.scheduler.occupancy() == 0, \
        "a crashed replica must not pin slot bookkeeping forever"
    for r in resps:
        r.tokens(timeout=5)
    fleet.close()


# ---------------------------------------------------------------------------
# run transfer codec
# ---------------------------------------------------------------------------

def test_codec_bytes_roundtrip_cross_engine_bit_identical():
    ea, eb = gpt_engine(), gpt_engine()
    ea.warmup()
    eb.warmup()
    p = prompts(1, seed=7)[0]
    r = ea.submit(p, 16)
    for _ in range(4):
        ea.step()
    produced = len(r.tokens_so_far())
    assert produced > 0
    slot = next(iter(ea._slots))
    paused = ea.preempt_slot(slot)
    blob = run_from_bytes(run_to_bytes(encode_run(paused)))
    assert blob["produced"] == produced
    assert blob["req"]["seed"] == paused.req.seed
    snap = decode_run(blob, req=paused.req, resp=paused.resp,
                      engine=eb)
    assert eb.restore_run(snap)
    eb.run_until_drained(timeout=30)
    assert r.tokens(timeout=5) == solo(p, 16)
    ea.close()
    eb.close()


def test_codec_incompatibility_is_typed():
    eng = gpt_engine()
    eng.warmup()
    p = prompts(1, seed=8)[0]
    eng.submit(p, 12)
    for _ in range(3):
        eng.step()
    blob = encode_run(eng.preempt_slot(next(iter(eng._slots))))
    # wrong model width
    other = stub_engine()
    with pytest.raises(RunTransferError):
        decode_run(blob, engine=other)
    # wrong codec version
    bad = dict(blob, version=99)
    with pytest.raises(RunTransferError):
        decode_run(bad, engine=eng)
    # subprocess path: request rebuilt from the blob alone
    snap = decode_run(blob)
    assert snap.req.id == blob["req"]["id"]
    assert list(snap.req.prompt) == list(p)
    eng.close()
    other.close()


def test_codec_carries_remaining_deadline():
    eng = gpt_engine()
    eng.warmup()
    p = prompts(1, seed=13)[0]
    eng.submit(p, 12, deadline=30.0)
    for _ in range(2):
        eng.step()
    blob = run_from_bytes(run_to_bytes(
        encode_run(eng.preempt_slot(next(iter(eng._slots))))))
    rem = blob["req"]["deadline_remaining_s"]
    assert rem is not None and 0 < rem <= 30.0
    snap = decode_run(blob)  # subprocess path: Request rebuilt
    assert snap.req.deadline is not None
    assert snap.req.deadline.remaining() <= rem + 0.001, \
        "a migrated run keeps counting down, it never gets a fresh budget"
    eng.close()


# ---------------------------------------------------------------------------
# double-close idempotency (satellite regression)
# ---------------------------------------------------------------------------

def test_concurrent_double_close_engine_gateway_fleet():
    eng = stub_engine()
    gw = ServingGateway(eng)
    gw.start()
    r = gw.submit([1, 2, 3], 64, tenant="t")
    fleet = stub_fleet(n=2)
    errs = []

    def hammer(obj, n=4):
        for _ in range(n):
            try:
                obj.close()
            except BaseException as e:  # noqa: BLE001 — test collects
                errs.append(e)

    threads = ([threading.Thread(target=hammer, args=(gw,))
                for _ in range(4)]
               + [threading.Thread(target=hammer, args=(fleet,))
                  for _ in range(4)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert r.done(), "in-flight request reaches terminal on close"
    # closed objects refuse new work, typed
    with pytest.raises(UnavailableError):
        fleet.submit([1], 2)
    resp = gw.submit([1], 2, tenant="t")
    assert isinstance(resp.error, UnavailableError)


def test_fleet_close_fails_outstanding_terminal():
    fleet = stub_fleet(n=2)
    resps = [fleet.submit([1, 2, 3], 8) for _ in range(4)]
    fleet.close()  # never stepped: queued work must still terminate
    for r in resps:
        assert r.done() and isinstance(r.error, RequestCancelled)


# ---------------------------------------------------------------------------
# gateway integration + observability
# ---------------------------------------------------------------------------

def test_gateway_over_fleet_serves_and_healthz_aggregates():
    fleet = gpt_fleet(n=2)
    gw = ServingGateway(fleet,
                        tenants={"gold": TenantConfig(max_priority=1)})
    gw.start()
    ps = prompts(4, seed=9)
    resps = [gw.submit(p, 10, tenant="gold", priority=i % 2,
                       session=f"u{i}") for i, p in enumerate(ps)]
    for p, r in zip(ps, resps):
        assert r.tokens(timeout=60) == solo(p, 10)
    status, _, body = gw.handle("GET", "/healthz")
    h = json.loads(body)
    assert status == 200 and h["warm"] is True
    fl = h["fleet"]
    assert fl["routable"] == 2 and fl["total"] == 2
    assert set(fl["replicas"]) == {"0", "1"}
    for rep in fl["replicas"].values():
        assert rep["state"] == "healthy" and rep["warm"]
        assert rep["post_warmup_compiles"] == 0
    gw.close()
    # a gateway whose fleet has nothing routable reports 503
    status2, _, body2 = gw.handle("GET", "/healthz")
    assert status2 == 503


def test_fleet_observability_report_and_gauges():
    from paddle_tpu import observability
    from paddle_tpu.observability import metrics as obs_m
    fleet = gpt_fleet(n=2)
    ps = prompts(2, seed=10)
    resps = [fleet.submit(p, 10, resubmit=True) for p in ps]
    for _ in range(3):
        fleet.step()
    faults.enable("replica_crash", f"0:{fleet.manager.get(0).steps}")
    fleet.run_until_drained(timeout=60)
    faults.reset()
    for r in resps:
        r.tokens(timeout=5)
    rep = observability.report()["fleet"]
    assert rep["failovers"] >= 1 and rep["resubmits"] >= 1
    up = dict(obs_m.get_registry().get("serving_replica_up").samples())
    assert up[("0",)] == 0 and up[("1",)] == 1
    m = fleet.metrics()
    assert m["routable"] == 1 and m["fleet_failovers"] >= 1
    assert "0" in m["replicas"] and m["replicas"]["0"]["state"] == "crashed"
    fleet.close()


@pytest.mark.slow
def test_fleet_probe_smoke():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "probes", "fleet_probe.py"),
         "--steps", "3"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("FLEET")]
    assert line, proc.stdout[-2000:] + proc.stderr[-2000:]
    rec = json.loads(line[0][len("FLEET"):])
    assert proc.returncode == 0, rec.get("failures")
    assert rec["smoke"] and not rec.get("failures")
