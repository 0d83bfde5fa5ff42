"""Train->serve loop (ISSUE 18): continuous weight refresh with a
canary gate and rollback-safe convergence (serving/refresh.py), plus
SLO-driven elastic membership (serving/autoscaler.py).

Tier-1 keeps the fleet tests small (tiny GPT, <= 2 worker processes)
under a hard SIGALRM per-test timeout; the diurnal replay and the full
chaos matrix live in probes/elastic_probe.py (bench `detail.elastic`).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.jit import state_arrays
from paddle_tpu.serving import (Autoscaler, FleetRouter, FleetRefresher,
                                ServingEngine, ServingGateway,
                                WeightPublisher, latest_publish)
from paddle_tpu.serving.fleet import (DRAINING, HEALTHY, ReplicaManager)
from paddle_tpu.serving.transfer import file_sha256
from paddle_tpu.utils import faults

pytestmark = pytest.mark.autoscale

GPT_KW = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=2, hidden_dropout_prob=0.0,
              attention_probs_dropout_prob=0.0,
              max_position_embeddings=128)
ENGINE_KW = dict(max_slots=2, max_len=64, prefill_buckets=(8,),
                 decode_chunk=2)
SEED_OLD, SEED_NEW, SEED_BAD, SEED_DIV = 11, 99, 13, 77


def worker_spec(**engine_overrides):
    ekw = dict(ENGINE_KW, **engine_overrides)
    ekw["prefill_buckets"] = list(ekw["prefill_buckets"])
    return {"model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                      "kwargs": dict(GPT_KW, seed=SEED_OLD)},
            "engine": ekw}


_model_cache = {}


def tiny_model(seed=SEED_OLD):
    """One model instance per seed: engines sharing it share compiled
    programs (the test_fleet _model_cache pattern), which keeps this
    file inside the tier-1 time budget."""
    m = _model_cache.get(seed)
    if m is None:
        paddle.seed(seed)
        m = models.GPTForPretraining(models.GPTConfig(**GPT_KW))
        m.eval()
        _model_cache[seed] = m
    return m


def tiny_engine(seed=SEED_OLD, **overrides):
    return ServingEngine(tiny_model(seed), **dict(ENGINE_KW, **overrides))


def oracle(model, prompt, max_new):
    out, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new)
    return np.asarray(out.numpy())[0].tolist()


@pytest.fixture
def hard_timeout():
    """Tier-1 wedge guard: SIGALRM aborts the test outright if a flip
    or worker hang ever leaks past the in-test timeouts."""
    def handler(signum, frame):
        raise TimeoutError("autoscale hard per-test timeout (a flip or "
                           "worker hang leaked past in-test timeouts)")
    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(150)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture
def guard():
    """Closes every registered fleet/engine/refresher at teardown and
    disarms faults — a failing test leaves no orphans behind."""
    items = []
    yield items.append
    for item in reversed(items):
        try:
            item.close()
        except Exception:
            pass
    faults.reset()


@pytest.fixture
def remote_worker():
    """Standalone `--listen` worker on an ephemeral loopback port."""
    procs = []

    def spawn(index=0):
        env = dict(os.environ)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = (root + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else root)
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.worker",
             "--listen", "127.0.0.1:0", "--index", str(index)],
            stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, env=env,
            start_new_session=True)
        procs.append(proc)
        while True:  # SIGALRM guards the wait
            line = proc.stdout.readline()
            if not line:
                raise AssertionError("remote worker exited early")
            if "worker listening on" in line:
                addr = line.strip().rsplit(" ", 1)[-1]
                break
        threading.Thread(target=lambda: proc.stdout.read(),
                         daemon=True).start()
        return addr, proc

    yield spawn
    for p in procs:
        try:
            p.kill()
            p.wait(timeout=10)
        except Exception:
            pass


def wait_for(pred, timeout, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out after {timeout}s waiting for {what}")


# ---------------------------------------------------------------------------
# publisher: atomic publishes + the corrupt-publish chaos knob
# ---------------------------------------------------------------------------

def test_publisher_atomic_latest_and_corrupt_knob(tmp_path, guard):
    d = str(tmp_path / "pub")
    pub = WeightPublisher(d)
    state = {"w/a": np.arange(8, dtype=np.float32),
             "w/b": np.ones((2, 3), dtype=np.float32)}
    assert latest_publish(d) is None
    p0 = pub.publish(state=state)
    assert p0["step"] == 0
    got = latest_publish(d)
    assert got is not None and got["sha256"] == p0["sha256"]
    # the manifest sha matches the visible bytes (no fault armed)
    assert file_sha256(got["path"]) == got["sha256"]
    # round-trips with keys intact
    with np.load(got["path"], allow_pickle=False) as z:
        assert sorted(z.files) == sorted(state)
    # auto-incrementing steps; LATEST follows
    p1 = pub.publish(state=state)
    assert p1["step"] == 1
    assert latest_publish(d)["step"] == 1
    # numbering resumes past what's on disk
    assert WeightPublisher(d).publish(state=state)["step"] == 2
    # a publisher crash mid-write leaves only an invisible tmp dir:
    # nothing but push-* dirs are ever considered
    os.makedirs(os.path.join(d, ".push-000000099.tmp-1"))
    assert latest_publish(d)["step"] == 2

    # PDTPU_FAULT_PUBLISH_CORRUPT bit-rots the artifact AFTER the
    # rename, so the manifest still carries the good-bytes sha and the
    # mismatch is detectable — corruption can never ride in silently
    faults.enable("publish_corrupt", "1")
    p3 = pub.publish(state=state)
    assert file_sha256(p3["path"]) != p3["sha256"]
    faults.disable("publish_corrupt")
    p4 = pub.publish(state=state)  # knob names ONE publish, not all
    assert file_sha256(p4["path"]) == p4["sha256"]


def test_publisher_rejects_ambiguous_args(tmp_path):
    pub = WeightPublisher(str(tmp_path))
    with pytest.raises(InvalidArgumentError):
        pub.publish()
    with pytest.raises(InvalidArgumentError):
        pub.publish(model=object(), state={})


# ---------------------------------------------------------------------------
# engine.swap_weights: the zero-recompile primitive
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_swap_weights_bit_identity_and_zero_recompiles(guard):
    eng = tiny_engine(SEED_OLD)
    guard(eng)
    eng.warmup()
    prompt = [1, 2, 3]
    resp = eng.submit(prompt, max_new_tokens=10)
    eng.run_until_drained(timeout=60)
    assert resp.tokens() == oracle(tiny_model(SEED_OLD), prompt, 10)
    assert eng.weights_sha is None and eng.refresh_epoch == 0

    new_state = {k: np.asarray(v)
                 for k, v in state_arrays(tiny_model(SEED_NEW)).items()}
    eng.swap_weights(new_state, "shaNEW")
    assert eng.weights_sha == "shaNEW" and eng.refresh_epoch == 1
    resp2 = eng.submit(prompt, max_new_tokens=10)
    eng.run_until_drained(timeout=60)
    assert resp2.tokens() == oracle(tiny_model(SEED_NEW), prompt, 10)
    # the flip reused every compiled program
    assert eng.post_warmup_compiles() == 0

    # a state dict that does not fit the model is rejected ATOMICALLY:
    # typed error, old weights keep serving
    bad = dict(new_state)
    missing_key = sorted(bad)[0]
    del bad[missing_key]
    with pytest.raises(InvalidArgumentError):
        eng.swap_weights(bad, "shaBAD")
    wrong = dict(new_state)
    wrong[missing_key] = np.zeros((3, 3), dtype=np.float32)
    with pytest.raises(InvalidArgumentError):
        eng.swap_weights(wrong, "shaBAD")
    assert eng.weights_sha == "shaNEW" and eng.refresh_epoch == 1
    resp3 = eng.submit(prompt, max_new_tokens=10)
    eng.run_until_drained(timeout=60)
    assert resp3.tokens() == resp2.tokens()


# ---------------------------------------------------------------------------
# ISSUE-18 satellite: remove() of a mid-drain replica is idempotent
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_remove_mid_drain_idempotent_hammer(guard):
    mgr = ReplicaManager()
    guard(type("_Closer", (), {"close": staticmethod(mgr.close_all)})())
    r0 = mgr.add(tiny_engine(SEED_OLD))
    r1 = mgr.add(tiny_engine(SEED_OLD))
    mgr.warm_all()
    # park a long-running stream on r0 so the drain cannot finish
    # instantly
    req, resp = r0.engine.make_request([1, 2, 3], 24)
    r0.engine.scheduler.submit(req, resp)
    mgr.drain(r0.id)
    assert r0.state == DRAINING
    # the hammer: remove() during the drain must neither raise, nor
    # yank the replica out from under its residents, nor double-close
    for _ in range(25):
        mgr.remove(r0.id)
    assert mgr.get(r0.id) is r0       # still draining, removal deferred
    assert r0.remove_after_drain
    t0 = time.monotonic()
    while ((mgr.get(r0.id) is not None or not resp.done())
           and time.monotonic() - t0 < 90):
        mgr.tick()
        time.sleep(0.002)
    assert mgr.get(r0.id) is None      # drained, THEN removed
    # the stream survived (finished in place or migrated to r1)
    assert resp.done() and resp.error is None
    assert len(resp.tokens()) == 24
    # removing an already-removed replica stays a no-op
    mgr.remove(r0.id)
    assert [r.id for r in mgr.replicas()] == [r1.id]


# ---------------------------------------------------------------------------
# the full refresh loop on an in-process fleet
# ---------------------------------------------------------------------------

# Engine-level tests in this file are full-tier only: each pays 7-10s of
# warmup compile and the repo-wide tier-1 run is already near its wall-time
# budget.  Tier-1 keeps the sub-second unit tests (publisher contract,
# autoscaler hysteresis on a fake fleet) plus the healthz gate below.
@pytest.mark.slow
def test_fleet_refresh_flip_and_rollback_inprocess(
        hard_timeout, guard, tmp_path):
    prompt = [1, 2, 3]
    want_new = oracle(tiny_model(SEED_NEW), prompt, 10)

    # oracle warms first: its compiles land before the fleet's marks
    orc = tiny_engine(SEED_OLD)
    guard(orc)
    orc.warmup()
    fleet = FleetRouter([tiny_engine(SEED_OLD), tiny_engine(SEED_OLD)])
    guard(fleet)
    fleet.warmup()
    fleet.start()
    pubdir = str(tmp_path / "push")
    refresher = FleetRefresher(fleet, pubdir, orc,
                               canary_prompts=(prompt,),
                               canary_max_new_tokens=10)
    guard(refresher)
    publisher = WeightPublisher(pubdir)

    def shas():
        return [getattr(r.engine, "weights_sha", None)
                for r in fleet.manager.replicas((HEALTHY,))]

    # admitted BEFORE the publish: finishes on the old weights
    resp_pre = fleet.submit(prompt, 24)
    pub = publisher.publish(state=state_arrays(tiny_model(SEED_NEW)))

    def converged(sha):
        refresher.poll()
        s = shas()
        return len(s) == 2 and all(x == sha for x in s)

    wait_for(lambda: converged(pub["sha256"]), 90,
             "both replicas on the published weights")
    assert resp_pre.tokens(timeout=60) == oracle(tiny_model(SEED_OLD),
                                                 prompt, 24)
    for rep in fleet.manager.replicas((HEALTHY,)):
        req, resp = rep.engine.make_request(prompt, 10)
        rep.engine.scheduler.submit(req, resp)
        fleet._work.set()
        assert resp.tokens(timeout=60) == want_new
    assert fleet.post_warmup_compiles() == 0

    # corrupt publish: quarantined at the artifact gate, nothing flips
    faults.enable("publish_corrupt", "1")
    bad = publisher.publish(state=state_arrays(tiny_model(SEED_BAD)))
    faults.disable("publish_corrupt")
    refresher.poll()
    assert bad["sha256"] in refresher.status()["quarantined"]
    assert all(x == pub["sha256"] for x in shas())

    # diverging canary: rolls back + reconverges on verified weights
    faults.enable("canary_diverge")
    div = publisher.publish(state=state_arrays(tiny_model(SEED_DIV)))
    refresher.poll()
    faults.disable("canary_diverge")
    assert div["sha256"] in refresher.status()["quarantined"]
    wait_for(lambda: converged(pub["sha256"]), 90,
             "rollback convergence onto the last verified weights")
    assert fleet.manager.counters()["rollbacks"] >= 2
    assert fleet.post_warmup_compiles() == 0
    assert fleet.health()["routable_verified"] == 2


# ---------------------------------------------------------------------------
# the full loop on a MIXED fleet: in-process + subprocess + remote
# (two worker-process boots: full-tier only, the in-process tier-1 test
# above covers the same choreography inside the time budget)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mixed_fleet_refresh_rollback_and_bit_identity(
        hard_timeout, guard, remote_worker, tmp_path):
    prompt = [1, 2, 3]
    want_old = oracle(tiny_model(SEED_OLD), prompt, 24)
    want_new = oracle(tiny_model(SEED_NEW), prompt, 10)

    # the oracle warms FIRST: its compiles land in the global registry
    # before the fleet takes its warmup marks, so the zero-post-warmup
    # assertion below measures only the flips
    orc = tiny_engine(SEED_OLD)
    guard(orc)
    orc.warmup()

    fleet = FleetRouter([tiny_engine(SEED_OLD)], heartbeat_timeout_s=30.0)
    guard(fleet)
    fleet.add_worker(worker_spec(), boot_timeout_s=180.0)
    addr, _proc = remote_worker()
    fleet.add_worker(worker_spec(), address=addr, boot_timeout_s=180.0,
                     manager_silence_s=30.0, ack_timeout_s=30.0)
    fleet.warmup()
    fleet.start()
    pubdir = str(tmp_path / "push")
    refresher = FleetRefresher(fleet, pubdir, orc,
                               canary_prompts=(prompt,),
                               canary_max_new_tokens=10,
                               flip_timeout_s=90.0)
    guard(refresher)
    publisher = WeightPublisher(pubdir)

    def shas():
        return [getattr(r.engine, "weights_sha", None)
                for r in fleet.manager.replicas((HEALTHY,))]

    # a stream admitted BEFORE the publish finishes on the old weights —
    # the flip fences admissions but never a resident run
    resp_pre = fleet.submit(prompt, 24)

    pub = publisher.publish(state=state_arrays(tiny_model(SEED_NEW)))
    refresher.poll()
    assert refresher.status()["current_sha"] == pub["sha256"]

    def converged(sha):
        refresher.poll()  # convergence sweep for stragglers
        s = shas()
        return len(s) == 3 and all(x == sha for x in s)

    wait_for(lambda: converged(pub["sha256"]), 120,
             "every replica on the published weights")
    assert resp_pre.tokens(timeout=60) == want_old  # pre-flip stream
    # post-flip: every replica serves streams bit-identical to the
    # new-weights oracle, with zero post-warmup compiles fleet-wide
    for rep in fleet.manager.replicas((HEALTHY,)):
        req, resp = rep.engine.make_request(prompt, 10)
        rep.engine.scheduler.submit(req, resp)
        fleet._work.set()
        assert resp.tokens(timeout=90) == want_new
    assert fleet.post_warmup_compiles() == 0
    health = fleet.health()
    assert health["routable_verified"] == 3
    assert health["refresh"]["current_sha"] == pub["sha256"]

    # -- corrupt publish: quarantined at the artifact gate, nothing
    # flips, the fleet keeps serving the verified weights
    faults.enable("publish_corrupt", "1")
    bad = publisher.publish(state=state_arrays(tiny_model(SEED_BAD)))
    faults.disable("publish_corrupt")
    refresher.poll()
    st = refresher.status()
    assert bad["sha256"] in st["quarantined"]
    assert st["current_sha"] == pub["sha256"]
    assert all(x == pub["sha256"] for x in shas())

    # -- canary-diverging publish: flips ONE canary, the forced
    # mismatch rolls it back, and the fleet converges onto the last
    # verified weights on every replica
    faults.enable("canary_diverge")
    div = publisher.publish(state=state_arrays(tiny_model(SEED_DIV)))
    refresher.poll()
    faults.disable("canary_diverge")
    st = refresher.status()
    assert div["sha256"] in st["quarantined"]
    wait_for(lambda: converged(pub["sha256"]), 120,
             "rollback convergence onto the last verified weights")
    for rep in fleet.manager.replicas((HEALTHY,)):
        req, resp = rep.engine.make_request(prompt, 10)
        rep.engine.scheduler.submit(req, resp)
        fleet._work.set()
        assert resp.tokens(timeout=90) == want_new
    assert fleet.manager.counters()["rollbacks"] >= 2
    assert fleet.manager.counters()["weight_refreshes"] >= 3
    assert fleet.post_warmup_compiles() == 0
    assert fleet.health()["routable_verified"] == 3


# ---------------------------------------------------------------------------
# autoscaler decision unit (injected clock, fake fleet)
# ---------------------------------------------------------------------------

class _FakeRep:
    def __init__(self, rid):
        self.id = rid
        self.state = HEALTHY
        self.flipping = False
        self._load = 0

    def load(self):
        return self._load


class _FakeManager:
    def __init__(self):
        self.reps = {}
        self.scales = []
        self.target = None

    def replicas(self, states=None):
        return [r for r in self.reps.values()
                if states is None or r.state in states]

    def note_scale(self, up):
        self.scales.append("up" if up else "down")

    def set_target_replicas(self, n):
        self.target = n


class _FakeFleet:
    def __init__(self, n=1):
        self.manager = _FakeManager()
        self._next = 0
        self.removed = []
        for _ in range(n):
            self.spawn()

    def spawn(self):
        rid = self._next
        self._next += 1
        self.manager.reps[rid] = _FakeRep(rid)
        return rid

    def drain(self, rid):
        self.manager.reps[rid].state = DRAINING

    def remove(self, rid):
        # deferred remove-after-drain, like the real manager
        self.removed.append(rid)
        self.manager.reps.pop(rid, None)


def test_autoscaler_hysteresis_cooldown_and_bounds():
    clock = {"t": 0.0}
    sig = {"est_wait_s": 0.0, "queue_depth": 0, "shed_total": 0}
    fleet = _FakeFleet(n=1)
    asc = Autoscaler(fleet, lambda: dict(sig), fleet.spawn,
                     min_replicas=1, max_replicas=3,
                     scale_up_est_wait_s=0.5, breach_ticks=3,
                     idle_ticks=4, cooldown_s=10.0,
                     _clock=lambda: clock["t"])

    def live():
        return len([r for r in fleet.manager.reps.values()
                    if r.state != DRAINING])

    # hysteresis: two breached ticks move nothing, the third spawns
    sig["est_wait_s"] = 2.0
    assert asc.tick() is None and asc.tick() is None
    assert asc.tick() == "up" and live() == 2
    assert fleet.manager.scales == ["up"]
    # cooldown: sustained breach cannot spawn again until it elapses
    for _ in range(6):
        clock["t"] += 1.0
        assert asc.tick() is None
    # breach sustained THROUGH the cooldown: acts the moment it elapses
    clock["t"] += 10.0
    assert asc.tick() == "up" and live() == 3
    # bounds: at max_replicas, breach forever, no further spawns
    clock["t"] += 100.0
    for _ in range(8):
        clock["t"] += 1.0
        assert asc.tick() is None
    assert live() == 3

    # a calm tick resets the breach streak
    clock["t"] += 100.0
    sig["est_wait_s"] = 0.0
    asc.tick()
    assert asc.status()["breach_streak"] == 0
    # shed counters breach even with a low est-wait
    sig["shed_total"] = 5
    asc.tick()
    assert asc.status()["breach_streak"] == 1
    # a shed-free tick with an empty queue is idle — the opposing
    # streak resets (scale-down racing scale-up can never interleave)
    asc.tick()
    assert asc.status()["breach_streak"] == 0

    # idle ticks retire one replica per cooldown, draining — never
    # below min_replicas
    clock["t"] += 100.0
    downs = 0
    for _ in range(60):
        clock["t"] += 1.0
        if asc.tick() == "down":
            downs += 1
    assert downs == 2 and live() == 1
    assert fleet.manager.scales == ["up", "up", "down", "down"]
    assert fleet.manager.target == 1
    # drain-then-remove, never a kill: every retired replica went
    # through DRAINING before the deferred remove
    assert sorted(fleet.removed) == sorted(
        r for r in range(3) if r not in fleet.manager.reps)

    # a mid-flip replica is never picked as the victim
    fleet2 = _FakeFleet(n=2)
    for r in fleet2.manager.reps.values():
        r.flipping = True
    asc2 = Autoscaler(fleet2, lambda: dict(sig), fleet2.spawn,
                      min_replicas=1, max_replicas=3, idle_ticks=1,
                      cooldown_s=0.0, _clock=lambda: clock["t"])
    sig["est_wait_s"] = 0.0
    sig["shed_total"] = 0  # no fresh sheds for the new scaler
    for _ in range(5):
        clock["t"] += 1.0
        assert asc2.tick() is None  # wants down, but everyone is mid-flip
    assert len(fleet2.manager.reps) == 2


def test_autoscaler_rejects_bad_bounds():
    with pytest.raises(InvalidArgumentError):
        Autoscaler(_FakeFleet(), lambda: {}, lambda: None,
                   min_replicas=0, max_replicas=2)
    with pytest.raises(InvalidArgumentError):
        Autoscaler(_FakeFleet(), lambda: {}, lambda: None,
                   min_replicas=3, max_replicas=2)


# ---------------------------------------------------------------------------
# elastic membership against a REAL fleet (drain semantics end-to-end)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_autoscaler_scales_real_fleet_up_and_down(hard_timeout, guard):
    fleet = FleetRouter([tiny_engine(SEED_OLD)])
    guard(fleet)
    fleet.warmup()
    fleet.start()
    sig = {"est_wait_s": 0.0, "queue_depth": 0, "shed_total": 0}

    def spawn():
        eng = tiny_engine(SEED_OLD)
        eng.warmup()
        return fleet.add_replica(eng)

    asc = Autoscaler(fleet, lambda: dict(sig), spawn,
                     min_replicas=1, max_replicas=2,
                     scale_up_est_wait_s=0.5, breach_ticks=2,
                     idle_ticks=2, cooldown_s=0.0)
    sig["est_wait_s"] = 3.0
    asc.tick()
    assert asc.tick() == "up"
    wait_for(lambda: len(fleet.manager.routable()) == 2, 60,
             "spawned replica routable")
    # the new replica serves — and the gateway-visible counters moved
    resp = fleet.submit([1, 2, 3], 8)
    assert resp.tokens(timeout=60) == oracle(tiny_model(SEED_OLD),
                                             [1, 2, 3], 8)
    sig["est_wait_s"] = 0.0
    asc.tick()
    assert asc.tick() == "down"
    wait_for(lambda: len(fleet.manager.replicas((HEALTHY,))) == 1, 60,
             "drained replica reaped")
    c = fleet.manager.counters()
    assert c["scale_up"] == 1 and c["scale_down"] == 1
    # retirement was a drain: the fleet still serves
    resp2 = fleet.submit([1, 2, 3], 8)
    assert resp2.tokens(timeout=60) == oracle(tiny_model(SEED_OLD),
                                              [1, 2, 3], 8)


# ---------------------------------------------------------------------------
# gateway /healthz: 503 when no routable replica serves verified weights
# ---------------------------------------------------------------------------

class _FakeRefresher:
    def __init__(self):
        self.ok = True

    def sha_ok(self, sha):
        return self.ok

    def status(self):
        return {"current_sha": "deadbeef", "verified": 1,
                "quarantined": {}, "last_error": None}


def test_healthz_503_when_no_verified_replica(guard):
    fleet = FleetRouter([tiny_engine(SEED_OLD)])
    guard(fleet)
    fleet.warmup()
    gw = ServingGateway(fleet)
    guard(gw)
    fr = _FakeRefresher()
    fleet.attach_refresher(fr)
    status, _, body = gw.handle("GET", "/healthz")
    doc = json.loads(body)
    assert status == 200
    assert doc["fleet"]["routable_verified"] == 1
    assert doc["fleet"]["refresh"]["current_sha"] == "deadbeef"
    # replicas up, but NONE serving canary-passed weights: readiness
    # must fail — routing exists, verified capacity does not
    fr.ok = False
    status, _, body = gw.handle("GET", "/healthz")
    assert status == 503
    assert json.loads(body)["fleet"]["routable_verified"] == 0
    # scale signals the autoscaler polls are cheap and complete
    sig = gw.scale_signals()
    for key in ("est_wait_s", "queue_depth", "shed_total",
                "admitted_total"):
        assert key in sig
