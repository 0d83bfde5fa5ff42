"""Network-transparent fleet (ISSUE 15): standalone remote TCP workers
(`--listen`), sha256-verified weight shipping over the attach handshake,
beat-frame wedge fencing with no heartbeat file, epoch-fenced reconnect,
submit dedup under ack loss, and the PDTPU_FAULT_NET_* chaos knobs.

Tier-1 keeps every test to <= 2 workers on the tiny GPT over loopback
TCP with a hard SIGALRM per-test timeout (a hung or partitioned worker
can never wedge the suite); the partition/chaos matrix runs under
`slow`.
"""
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, models
from paddle_tpu.serving import (FleetRouter, RestartBackoff, ServingEngine,
                                WireFormatError, WorkerDiedError)
from paddle_tpu.serving.fleet import RemoteReplica
from paddle_tpu.serving.worker import (RemoteWorkerClient, StaleEpochError,
                                       _FrameConn, _WorkerServer)
from paddle_tpu.utils import faults

pytestmark = pytest.mark.remote_fleet

GPT_KW = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=2, hidden_dropout_prob=0.0,
              attention_probs_dropout_prob=0.0,
              max_position_embeddings=128)
ENGINE_KW = dict(max_slots=2, max_len=64, prefill_buckets=(8,),
                 decode_chunk=2)

# the spec's FACTORY seed deliberately differs from the shipped-weight
# seed: bit-identical output against the seed-99 oracle proves the
# worker serves the SHIPPED artifact, not a seeded rebuild
FACTORY_SEED, WEIGHT_SEED = 11, 99


def remote_spec(weights=None, **engine_overrides):
    ekw = dict(ENGINE_KW, **engine_overrides)
    ekw["prefill_buckets"] = list(ekw["prefill_buckets"])
    spec = {"model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                      "kwargs": dict(GPT_KW, seed=FACTORY_SEED)},
            "engine": ekw}
    if weights is not None:
        spec["weights"] = weights
    return spec


def tiny_model(seed=WEIGHT_SEED):
    paddle.seed(seed)
    m = models.GPTForPretraining(models.GPTConfig(**GPT_KW))
    m.eval()
    return m


def oracle(model, prompt, max_new):
    out, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new)
    return np.asarray(out.numpy())[0].tolist()


@pytest.fixture
def shipped_weights(tmp_path):
    """A real jit.save weight artifact for the seed-99 model."""
    m = tiny_model(WEIGHT_SEED)
    jit.save(m, str(tmp_path / "m"))
    path = str(tmp_path / "m.pdiparams.npz")
    assert os.path.exists(path)
    return m, path


@pytest.fixture
def hard_timeout():
    """Tier-1 wedge guard: SIGALRM aborts the test outright if a remote
    hang ever leaks past the in-test timeouts."""
    def handler(signum, frame):
        raise TimeoutError("remote_fleet hard per-test timeout (a remote "
                           "worker hang leaked past the in-test timeouts)")
    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(150)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture
def fleet_guard():
    """Closes every registered fleet/client at teardown — a failing test
    leaves no orphan connection behind."""
    items = []
    yield items.append
    for item in items:
        try:
            item.close()
        except Exception:
            pass
    faults.reset()


@pytest.fixture
def remote_worker():
    """Factory spawning standalone `--listen` workers on an ephemeral
    loopback port; yields (address, proc) and reaps at teardown."""
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
                raise AssertionError(
                    "remote worker exited before listening")
            if "worker listening on" in line:
                addr = line.strip().rsplit(" ", 1)[-1]
                break
        # keep draining stdout so the worker can never block on a full
        # pipe mid-test
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


def drive(fleet, pred, timeout, what):
    """Tick the fleet from THIS thread (the driving-thread contract)
    until `pred` holds."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        fleet.step()
        if pred():
            return
        time.sleep(0.002)
    raise AssertionError(f"timed out after {timeout}s waiting for {what}")


def drive_client(client, pred, timeout, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            client.step()
        except (WorkerDiedError, WireFormatError):
            pass  # session torn down under us — pred decides
        if pred():
            return
        time.sleep(0.002)
    raise AssertionError(f"timed out after {timeout}s waiting for {what}")


# ---------------------------------------------------------------------------
# pure wire units: no subprocess, no model
# ---------------------------------------------------------------------------

def test_frameconn_assembly_deadline_and_send_stall():
    """ISSUE-15 satellite: a peer holding ONE frame open forever (the
    slowloris PDTPU_FAULT_NET_DELAY models) trips the typed assembly
    deadline instead of occupying recv_frames; a peer not draining its
    socket trips the bounded-send WorkerDiedError; and an honestly slow
    multi-part send still assembles fine."""
    # 1) partial frame stuck past the assembly deadline -> typed
    a, b = socket.socketpair()
    rx = _FrameConn(b, frame_deadline=0.25)
    a.sendall((1000).to_bytes(8, "big") + b"x" * 10)  # 10/1000 bytes
    t0 = time.monotonic()
    with pytest.raises(WireFormatError, match="assembly deadline"):
        while True:
            rx.recv_frames(0.02)
            assert time.monotonic() - t0 < 5.0, "deadline never tripped"
    a.close()
    rx.close()
    # 2) a frame split across writes with pauses assembles (progress
    #    resets the deadline clock; only a STUCK frame is typed)
    a, b = socket.socketpair()
    rx = _FrameConn(b, frame_deadline=5.0)
    from paddle_tpu.serving.worker import pack_frame
    frame = pack_frame("ping", {"k": 1})
    a.sendall(frame[:9])
    assert rx.recv_frames(0.01) == []
    time.sleep(0.05)
    a.sendall(frame[9:])
    frames = rx.recv_frames(0.2)
    assert len(frames) == 1 and frames[0][0] == "ping"
    a.close()
    rx.close()
    # 3) peer not draining: bounded send raises typed, never hangs.
    #    (partial writes under the deadline are tolerated — the frame is
    #    far larger than the socket buffers, so the send MUST go short
    #    repeatedly before the deadline verdict)
    a, b = socket.socketpair()
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    tx = _FrameConn(a, send_timeout=0.3)
    with pytest.raises(WorkerDiedError, match="stalled"):
        tx.send("blob", {}, {"data": np.zeros(1 << 21, np.uint8)})
    tx.close()
    b.close()


def test_manager_silence_self_abort_and_abort_epoch(hard_timeout):
    """ISSUE-15 satellite: under an injected clock, a remote session
    whose manager went silent past `manager_silence_s` aborts every
    resident/queued run typed (StaleEpochError) and detaches; the
    `abort_epoch` verb does the same but ONLY for its own epoch."""
    engine = ServingEngine(tiny_model(FACTORY_SEED), **ENGINE_KW)
    s_mgr, s_wrk = socket.socketpair()
    conn = _FrameConn(s_wrk)
    now = {"t": 100.0}
    try:
        server = _WorkerServer(engine, conn, None, 0, epoch=3,
                               manager_silence_s=2.0,
                               _clock=lambda: now["t"])
        # a wrong-epoch abort_epoch is a stale manager talking to the
        # wrong session: ignored entirely
        server._handle("abort_epoch", {"epoch": 2}, {})
        assert server.detach is None
        resp = engine.submit(np.arange(1, 5, dtype=np.int32), 4)
        # inside the budget: nothing aborts
        now["t"] = 101.9
        assert not server._check_manager_silence()
        assert resp.error is None
        # past the budget: typed self-abort + detach
        now["t"] = 102.1
        assert server._check_manager_silence()
        assert server.detach == "manager-silence"
        assert isinstance(resp.error, StaleEpochError)
        assert "manager silent" in str(resp.error)
        # matching-epoch abort_epoch on a fresh server also aborts typed
        server2 = _WorkerServer(engine, conn, None, 0, epoch=3,
                                manager_silence_s=None,
                                _clock=lambda: now["t"])
        assert not server2._check_manager_silence()  # no budget, no fence
        resp2 = engine.submit(np.arange(1, 5, dtype=np.int32), 4)
        server2._handle("abort_epoch", {"epoch": 3}, {})
        assert server2.detach == "abort_epoch"
        assert isinstance(resp2.error, StaleEpochError)
        assert "epoch superseded" in str(resp2.error)
    finally:
        conn.close()
        s_mgr.close()
        engine.close()


# ---------------------------------------------------------------------------
# tier-1 remote smoke: <= 2 workers, tiny GPT over loopback TCP
# ---------------------------------------------------------------------------

def test_remote_attach_ships_weights_dedups_and_reattaches(
        hard_timeout, fleet_guard, remote_worker, shipped_weights):
    """The tier-1 remote smoke: a standalone `--listen` worker attached
    by address boots from the SHIPPED sha256-verified weight artifact
    (bit-identical to the weight-seed oracle, which the factory seed
    cannot produce), liveness rides beat frames (no heartbeat file), a
    retried submit after a forced ack loss admits exactly once, and a
    manager re-attach after detach ships zero bytes onto the cached
    engine under a fresh epoch — with the net_delay trickle armed."""
    model, wpath = shipped_weights
    addr, proc = remote_worker(index=0)
    fleet = FleetRouter([], heartbeat_timeout_s=5.0)
    fleet_guard(fleet)
    rid = fleet.add_worker(remote_spec(weights=wpath), address=addr,
                           ack_timeout_s=30.0)
    rep = fleet.manager.get(rid)
    assert isinstance(rep, RemoteReplica) and rep.kind == "remote"
    drive(fleet, lambda: rep.state == "healthy", 120, "remote boot")
    client = rep.engine
    assert client.heartbeat_path is None  # liveness is beat FRAMES
    assert client.epoch == 1 and client.weights_sha is not None
    assert client.bytes_shipped > 0
    assert client.pid > 0 and client.pid == proc.pid
    snap = rep.snapshot()
    assert snap["kind"] == "remote" and snap["address"] == addr
    assert snap["weights_sha"] == client.weights_sha
    assert snap["epoch"] == 1 and snap["bytes_shipped"] > 0
    assert fleet.health()["remote_workers"] == 1
    prompt = np.arange(1, 6, dtype=np.int32)
    want = oracle(model, prompt, 12)
    # mild slowloris on every 5th manager frame: streams still complete
    faults.enable("net_delay", "2:5")
    # -- exactly-once admission under injected ack loss: ship, then
    # force the ack-timeout resend path twice; the worker's wid dedup
    # re-acks without double-admitting, so the stream is bit-identical
    # (a double admission would push duplicate chunks into the run)
    req, resp = client.make_request(prompt, 12, resubmit=False)
    client._ship(req, resp)
    wid = next(iter(client._await_ack))
    for _ in range(2):
        client._await_ack[wid][0] = 0.0  # ack "lost": deadline now
        client._pump_acks()
    assert client._await_ack[wid][1] == client.submit_retries - 2
    drive(fleet, resp.done, 60, "deduped stream completion")
    assert resp.tokens() == want
    assert not client._await_ack
    assert client.post_warmup_compiles() == 0
    drive(fleet, lambda: (client.heartbeat_age() is not None
                          and client.heartbeat_steps() is not None),
          30, "beat frames")
    assert client.heartbeat_age() < 5.0
    faults.disable("net_delay")
    # -- detach: the manager does NOT own the process
    fleet.close()
    time.sleep(0.3)
    assert proc.poll() is None, "standalone worker died on manager close"
    # -- re-attach: cached engine, zero bytes re-shipped, fresh epoch
    fleet2 = FleetRouter([], heartbeat_timeout_s=5.0)
    fleet_guard(fleet2)
    rid2 = fleet2.add_worker(remote_spec(weights=wpath), address=addr)
    rep2 = fleet2.manager.get(rid2)
    drive(fleet2, lambda: rep2.state == "healthy", 60, "re-attach")
    assert rep2.engine.bytes_shipped == 0
    assert rep2.engine.weights_sha == client.weights_sha
    assert rep2.engine.post_warmup_compiles() == 0
    req2, resp2 = rep2.engine.make_request(prompt, 12)
    rep2.engine.scheduler.submit(req2, resp2)
    drive(fleet2, resp2.done, 60, "post-re-attach stream")
    assert resp2.tokens() == want


def test_stale_epoch_reject_and_higher_epoch_takeover(
        hard_timeout, fleet_guard, remote_worker):
    """Split-brain fencing on the worker's listener: an attach with an
    EQUAL epoch is refused with a typed StaleEpochError fatal; a HIGHER
    epoch supersedes the live session — its residents abort typed
    (StaleEpochError reaches the old manager's consumers) and the new
    session serves.  No token is ever double-served."""
    addr, _ = remote_worker(index=0)
    spec = remote_spec()
    prompt = np.arange(1, 6, dtype=np.int32)
    want = oracle(tiny_model(FACTORY_SEED), prompt, 24)
    cl_a = RemoteWorkerClient(spec, addr, index=0, epoch=5,
                              manager_silence_s=30.0)
    fleet_guard(cl_a)
    cl_a.warmup()
    # keep A's stream resident: slow the worker's decode
    cl_a.set_fault("replica_slow", "60:1:0")
    req_a, resp_a = cl_a.make_request(prompt, 24, resubmit=False)
    cl_a._ship(req_a, resp_a)
    drive_client(cl_a, lambda: len(resp_a.tokens_so_far()) >= 1, 60,
                 "stream resident on the remote worker")
    # -- equal epoch: refused typed before any session damage
    cl_stale = RemoteWorkerClient(spec, addr, index=0, epoch=5,
                                  boot_timeout_s=30.0)
    fleet_guard(cl_stale)
    with pytest.raises(WorkerDiedError, match="StaleEpochError"):
        t0 = time.monotonic()
        while True:
            try:
                cl_a.step()  # the worker polls its listener per step
            except (WorkerDiedError, WireFormatError):
                pass
            if cl_stale.poll_ready():
                raise AssertionError("stale epoch was admitted")
            assert time.monotonic() - t0 < 60
            time.sleep(0.01)
    # A's session is untouched by the refused stale attach
    assert resp_a.error is None and not resp_a.done()
    # -- higher epoch: takeover.  A's resident aborts typed; the worker
    # reuses its cached engine for B (same spec, no weights)
    cl_b = RemoteWorkerClient(spec, addr, index=0, epoch=6)
    fleet_guard(cl_b)
    drive_client(cl_a, resp_a.done, 60, "old-epoch resident aborted")
    assert isinstance(resp_a.error, StaleEpochError)
    assert "superseded by attach epoch 6" in str(resp_a.error)
    cl_b.warmup()
    assert cl_b.epoch == 6
    cl_b.set_fault("replica_slow", None)
    req_b, resp_b = cl_b.make_request(prompt, 24)
    cl_b._ship(req_b, resp_b)
    drive_client(cl_b, resp_b.done, 60, "new-epoch stream")
    assert resp_b.tokens() == want


def test_corrupt_weight_chunk_typed_reject_then_supervised_reattach(
        hard_timeout, fleet_guard, remote_worker, shipped_weights,
        monkeypatch):
    """ISSUE-15 satellite: a corrupted weight chunk is refused typed by
    the worker's per-chunk sha256 check (never assembled into garbage
    weights), the boot failure burns one restart-budget attempt, and the
    supervisor's re-attach (epoch+1) ships clean and serves the shipped
    weights bit-identical."""
    import paddle_tpu.serving.transfer as transfer
    model, wpath = shipped_weights
    real_iter = transfer.iter_artifact_chunks
    calls = {"n": 0}

    def corrupting(path, *a, **kw):
        calls["n"] += 1
        poison = calls["n"] == 1
        for seq, data in real_iter(path, *a, **kw):
            if poison and seq == 0:
                data = b"\x00" * len(data)
            yield seq, data

    monkeypatch.setattr(transfer, "iter_artifact_chunks", corrupting)
    addr, _ = remote_worker(index=0)
    fleet = FleetRouter(
        [], heartbeat_timeout_s=5.0,
        restart_backoff=RestartBackoff(max_restarts=1, base_delay=0.05,
                                       max_delay=0.2))
    fleet_guard(fleet)
    rid = fleet.add_worker(remote_spec(weights=wpath), address=addr)
    rep = fleet.manager.get(rid)

    def healthy_remote():
        return next((r for r in fleet.manager.replicas()
                     if isinstance(r, RemoteReplica)
                     and r.state == "healthy"), None)

    drive(fleet, lambda: healthy_remote() is not None, 120,
          "supervised re-attach after the poisoned ship")
    # the first attach died TYPED on the sha mismatch
    assert rep.state == "crashed"
    assert "WeightShipError" in rep.fence_reason
    assert "sha256 mismatch" in rep.fence_reason
    new_rep = healthy_remote()
    assert new_rep.id != rid
    assert new_rep.lineage["restarts"] == 1
    assert new_rep.lineage["epoch"] == 2 and new_rep.engine.epoch == 2
    assert calls["n"] == 2  # clean re-ship, not a cached skip
    assert new_rep.engine.bytes_shipped > 0
    prompt = np.arange(1, 6, dtype=np.int32)
    req, resp = new_rep.engine.make_request(prompt, 12)
    new_rep.engine.scheduler.submit(req, resp)
    drive(fleet, resp.done, 60, "post-retry stream")
    assert resp.tokens() == oracle(model, prompt, 12)
    assert fleet.manager.counters()["worker_restarts"] == 1


def test_remote_wedge_fences_on_beat_age_without_heartbeat_file(
        hard_timeout, fleet_guard, remote_worker):
    """PDTPU_FAULT_REPLICA_WEDGE on a REMOTE worker: no heartbeat file
    exists (heartbeat_path is None) — ONLY the beat-frame arrival age
    fences it, the resubmit opt-in stream fails over bit-identical onto
    the in-process survivor, and the zero-budget lineage is removed."""
    model = tiny_model(FACTORY_SEED)
    fleet = FleetRouter(
        [ServingEngine(model, **ENGINE_KW)],
        heartbeat_timeout_s=0.8, kill_grace_s=0.2,
        restart_backoff=RestartBackoff(max_restarts=0))
    fleet_guard(fleet)
    # in-process survivor took replica id 0; align the worker's fault
    # index with the lineage index the fleet will assign (1)
    addr, proc = remote_worker(index=1)
    rid = fleet.add_worker(remote_spec(), address=addr)
    rep = fleet.manager.get(rid)
    assert rep.lineage["index"] == 1
    fleet.warmup()
    fleet.start()
    wait_for(lambda: rep.state == "healthy", 120, "remote boot")
    assert rep.engine.heartbeat_path is None
    prompt = np.arange(1, 6, dtype=np.int32)
    want = oracle(model, prompt, 24)
    rep.engine.set_fault("replica_slow", "60:1:1")
    req, resp = rep.engine.make_request(prompt, 24, resubmit=True)
    rep.engine.scheduler.submit(req, resp)
    wait_for(lambda: len(resp.tokens_so_far()) >= 1, 60,
             "stream resident on the remote worker")
    rep.engine.set_fault("replica_wedge", "1:0")
    t_arm = time.monotonic()
    # beat frames stop; the fence is driven purely by their arrival age
    assert resp.tokens(timeout=60) == want
    detect_s = time.monotonic() - t_arm
    assert rep.state == "wedged"
    assert "heartbeat age" in rep.fence_reason
    assert detect_s < 5.0
    # zero budget: lineage exhausted, replica removed — and the manager
    # does NOT kill a process it never owned
    wait_for(lambda: fleet.manager.get(rid) is None, 30,
             "exhausted remote lineage removed")
    assert rep.lineage["exhausted"]
    c = fleet.manager.counters()
    assert c["wedges"] == 1 and c["worker_restarts"] == 0
    assert proc.poll() is None  # wedged REMOTE process is not ours to kill


# ---------------------------------------------------------------------------
# chaos matrix (slow): mid-frame cuts and hard partitions
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_net_drop_midframe_typed_failover_and_reattach(
        hard_timeout, fleet_guard, remote_worker, shipped_weights):
    """PDTPU_FAULT_NET_DROP on the manager side: a frame cut mid-send
    kills the session typed — the resubmit opt-in streams complete
    bit-identical on the in-process survivor and the supervisor
    re-attaches the SAME standalone worker (epoch+1), which serves
    again."""
    model, wpath = shipped_weights
    fleet = FleetRouter(
        [ServingEngine(tiny_model(WEIGHT_SEED), **ENGINE_KW)],
        heartbeat_timeout_s=5.0,
        restart_backoff=RestartBackoff(max_restarts=2, base_delay=0.05,
                                       max_delay=0.2))
    fleet_guard(fleet)
    addr, proc = remote_worker(index=1)
    rid = fleet.add_worker(remote_spec(weights=wpath), address=addr)
    rep = fleet.manager.get(rid)
    fleet.warmup()
    fleet.start()
    wait_for(lambda: rep.state == "healthy", 120, "remote boot")
    prompt = np.arange(1, 6, dtype=np.int32)
    want = oracle(model, prompt, 24)
    rep.engine.set_fault("replica_slow", "60:1:1")
    r1, resp1 = rep.engine.make_request(prompt, 24, resubmit=True)
    rep.engine.scheduler.submit(r1, resp1)
    wait_for(lambda: len(resp1.tokens_so_far()) >= 1, 60,
             "stream resident on the remote worker")
    # the very next manager frame is cut mid-send: the submit below
    faults.enable("net_drop", "1")
    r2, resp2 = rep.engine.make_request(prompt, 24, resubmit=True)
    rep.engine.scheduler.submit(r2, resp2)
    # both streams fail over to the survivor, bit-identical
    assert resp1.tokens(timeout=90) == want
    assert resp2.tokens(timeout=90) == want
    faults.disable("net_drop")
    # the worker survived its manager's torn stream and re-attaches
    wait_for(lambda: any(isinstance(r, RemoteReplica)
                         and r.state == "healthy"
                         for r in fleet.manager.replicas()), 120,
             "supervised re-attach after the mid-frame cut")
    new_rep = next(r for r in fleet.manager.replicas()
                   if isinstance(r, RemoteReplica)
                   and r.state == "healthy")
    assert new_rep.lineage["epoch"] >= 2
    assert proc.poll() is None
    new_rep.engine.set_fault("replica_slow", None)
    r3, resp3 = new_rep.engine.make_request(prompt, 24)
    new_rep.engine.scheduler.submit(r3, resp3)
    assert resp3.tokens(timeout=90) == want
    assert fleet.manager.counters()["worker_restarts"] >= 1


@pytest.mark.slow
def test_net_partition_fences_self_aborts_and_heals(
        hard_timeout, fleet_guard, remote_worker, shipped_weights):
    """PDTPU_FAULT_NET_PARTITION: both directions blackholed with every
    process alive.  The manager fences on beat-frame age within 2x the
    threshold and resubmits onto the survivor (bit-identical); the
    isolated worker self-aborts its residents after manager-silence and
    returns to listening; after the window heals, the supervisor's
    re-attach under a HIGHER epoch is accepted and serves — zero
    double-served tokens, zero hung consumers."""
    model, wpath = shipped_weights
    hb_timeout = 0.8
    fleet = FleetRouter(
        [ServingEngine(tiny_model(WEIGHT_SEED), **ENGINE_KW)],
        heartbeat_timeout_s=hb_timeout, kill_grace_s=0.2,
        # first re-attach lands AFTER the 2.5s partition window heals: a
        # mid-partition attach would just time out and burn budget
        restart_backoff=RestartBackoff(max_restarts=3, base_delay=2.0,
                                       max_delay=3.0))
    fleet_guard(fleet)
    addr, proc = remote_worker(index=1)
    rid = fleet.add_worker(remote_spec(weights=wpath), address=addr,
                           boot_timeout_s=8.0, manager_silence_s=1.5)
    rep = fleet.manager.get(rid)
    fleet.warmup()
    fleet.start()
    wait_for(lambda: rep.state == "healthy", 120, "remote boot")
    prompt = np.arange(1, 6, dtype=np.int32)
    want = oracle(model, prompt, 24)
    rep.engine.set_fault("replica_slow", "60:1:1")
    req, resp = rep.engine.make_request(prompt, 24, resubmit=True)
    rep.engine.scheduler.submit(req, resp)
    wait_for(lambda: len(resp.tokens_so_far()) >= 1, 60,
             "stream resident on the remote worker")
    # arm the WORKER side first (the RPC frame must still get through),
    # then this side: both directions blackholed, every process alive
    rep.engine.set_fault("net_partition", "1:2.5")
    faults.enable("net_partition", "1:2.5")
    t_arm = time.monotonic()
    # the opted-in stream fails over on beat-arrival age alone
    assert resp.tokens(timeout=90) == want
    detect_s = time.monotonic() - t_arm
    assert rep.state == "wedged"
    assert "heartbeat age" in rep.fence_reason
    assert detect_s < 2 * hb_timeout + 2.0
    assert proc.poll() is None  # partitioned, not dead
    # heal: the supervisor re-attaches under a fresh epoch; the worker
    # (which self-aborted on manager silence and went back to
    # listening) accepts it and serves bit-identical again
    wait_for(lambda: any(isinstance(r, RemoteReplica)
                         and r.state == "healthy"
                         for r in fleet.manager.replicas()), 120,
             "healed re-attach after the partition window")
    new_rep = next(r for r in fleet.manager.replicas()
                   if isinstance(r, RemoteReplica)
                   and r.state == "healthy")
    assert new_rep.lineage["epoch"] >= 2
    assert new_rep.engine.epoch == new_rep.lineage["epoch"]
    new_rep.engine.set_fault("replica_slow", None)
    r2, resp2 = new_rep.engine.make_request(prompt, 24)
    new_rep.engine.scheduler.submit(r2, resp2)
    assert resp2.tokens(timeout=90) == want
    c = fleet.manager.counters()
    assert c["wedges"] >= 1 and c["worker_restarts"] >= 1
    assert c["resubmits"] >= 1
