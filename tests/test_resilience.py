"""Resilient-training-runtime tests (ISSUE 3).

Every recovery claim is exercised by an actual failure: a SIGKILL mid-save,
a NaN-poisoned gradient, a hard-killed dataloader worker, a real SIGTERM.
The injection points live in paddle_tpu.utils.faults; the `faults` marker
selects this suite (it is fast and runs in tier-1).
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import jit as pjit
from paddle_tpu.distributed import checkpoint as dck
from paddle_tpu.utils import faults
from paddle_tpu.utils.retry import RetriesExhausted, RetryPolicy

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# utils.retry
# ---------------------------------------------------------------------------

def test_retry_succeeds_after_transient_failures():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    policy = RetryPolicy(retries=5, base_delay=0.1, jitter=0.5,
                         retry_on=(OSError,), sleep=sleeps.append)
    assert policy.call(flaky) == "ok"
    assert calls["n"] == 3
    # exponential with full jitter: d in [base*2^i, 1.5*base*2^i]
    assert 0.1 <= sleeps[0] <= 0.15 and 0.2 <= sleeps[1] <= 0.3


def test_retry_exhaustion_chains_last_error():
    def always():
        raise ValueError("nope")

    policy = RetryPolicy(retries=2, base_delay=0.0, jitter=0.0,
                         sleep=lambda d: None)
    with pytest.raises(RetriesExhausted) as ei:
        policy.call(always)
    assert ei.value.attempts == 3
    assert isinstance(ei.value.last, ValueError)


def test_retry_giveup_and_deadline():
    with pytest.raises(KeyError):  # giveup_on beats retry_on
        RetryPolicy(retries=5, retry_on=(Exception,), giveup_on=(KeyError,),
                    sleep=lambda d: None).call(
                        lambda: (_ for _ in ()).throw(KeyError("x")))

    def fail():
        raise OSError("x")

    with pytest.raises(RetriesExhausted, match="deadline"):
        RetryPolicy(retries=50, base_delay=10.0, jitter=0.0, deadline=0.5,
                    sleep=lambda d: None).call(fail)


# ---------------------------------------------------------------------------
# async checkpointing
# ---------------------------------------------------------------------------

def test_async_manager_writes_retention_and_restore(tmp_path):
    import jax.numpy as jnp
    mgr = dck.AsyncCheckpointManager(str(tmp_path), max_to_keep=2,
                                     keep_every_k_steps=10)
    for s in (5, 10, 15, 20, 25):
        mgr.save({"w": jnp.full((16, 4), float(s)),
                  "nested": {"b": jnp.arange(8.0)}}, s,
                 extra_meta={"tag": s})
    assert mgr.wait_until_finished(timeout=60)
    # keep-last-2 (20, 25) plus keep-every-10 milestones (10, 20)
    assert mgr.all_steps() == [10, 20, 25]
    tree, step, extra = mgr.restore_latest()
    assert step == 25 and extra["tag"] == 25
    np.testing.assert_allclose(np.asarray(tree["w"]),
                               np.full((16, 4), 25.0))
    mgr.close()


def test_async_manager_surfaces_background_write_errors(tmp_path):
    import jax
    import jax.numpy as jnp
    mgr = dck.AsyncCheckpointManager(str(tmp_path))
    mgr.save({"w": jnp.zeros((4,))}, 1)
    assert mgr.wait_until_finished(timeout=60)
    # break the next write: a regular FILE squats on the step-2 tmp dir
    # path, so the background writer's makedirs fails — and that failure
    # must surface on the training thread, not vanish
    squatter = os.path.join(
        str(tmp_path), f"step-{2:09d}.tmp-p{jax.process_index():05d}")
    open(squatter, "w").close()
    try:
        mgr.save({"w": jnp.zeros((4,))}, 2)
        with pytest.raises(Exception, match="async checkpoint write failed"):
            mgr.wait_until_finished(timeout=60)
            mgr.save({"w": jnp.zeros((4,))}, 3)  # or on the next save
    finally:
        os.unlink(squatter)
        mgr.close()


def test_async_manager_bounded_queue_applies_backpressure(tmp_path):
    """max_in_flight bounds host-RAM copies: a third save blocks until an
    earlier write drains, rather than buffering without limit."""
    import jax.numpy as jnp
    mgr = dck.AsyncCheckpointManager(str(tmp_path), max_to_keep=10,
                                     max_in_flight=1)
    for s in range(1, 6):
        mgr.save({"w": jnp.full((256, 256), float(s))}, s)
    assert mgr.wait_until_finished(timeout=60)
    assert mgr.all_steps() == [1, 2, 3, 4, 5]
    mgr.close()


_KILL_MID_SAVE_SCRIPT = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax.numpy as jnp
from paddle_tpu.distributed import checkpoint as dck
d = sys.argv[1]
dck.save_sharded({{"w": jnp.arange(8.0)}}, d, step=1)          # clean save
os.environ["PDTPU_FAULT_KILL_MID_SAVE"] = "1"                 # arm: next save
dck.save_sharded({{"w": jnp.full((8,), 999.0)}}, d, step=2)    # SIGKILLed
print("UNREACHABLE")
"""


def test_sigkill_mid_save_preserves_previous_checkpoint(tmp_path):
    """The atomicity claim, exercised by an actual kill: a save SIGKILLed
    after its files are written but before the atomic rename leaves the
    previous checkpoint fully restorable (and the debris does not confuse
    the manager)."""
    d = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_MID_SAVE_SCRIPT.format(repo=REPO), d],
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode, proc.stderr)
    assert "UNREACHABLE" not in proc.stdout
    # step-2 tmp debris exists, step-2 was never published
    assert any(".tmp-p" in f for f in os.listdir(d))
    out = dck.restore_sharded(d)
    assert out is not None
    tree, step, _ = out
    assert step == 1
    np.testing.assert_allclose(np.asarray(tree["w"]), np.arange(8.0))
    # manager init clears the debris and training continues
    mgr = dck.CheckpointManager(d, save_interval_steps=1)
    assert not any(".tmp-p" in f for f in os.listdir(d))
    assert mgr.all_steps() == [1]


def test_latest_pointer_recovery(tmp_path):
    """A missing/dangling/garbage `latest` pointer falls back to the newest
    step dir with a valid manifest; manifest-less dirs are skipped."""
    import jax.numpy as jnp
    d = str(tmp_path)
    for s in (1, 2):
        dck.save_sharded({"w": jnp.full((4,), float(s))}, d, step=s)
    ptr = os.path.join(d, "latest")

    with open(ptr, "w") as f:  # dangling: names a deleted dir
        f.write("step-000000099")
    tree, step, _ = dck.restore_sharded(d)
    assert step == 2

    os.unlink(ptr)  # missing entirely
    tree, step, _ = dck.restore_sharded(d)
    assert step == 2

    # newest dir is incomplete (no manifest): fall through to step 2
    os.makedirs(os.path.join(d, "step-000000007"))
    assert dck.latest_step_dir(d).endswith("step-000000002")

    # corrupt manifest in the newest complete-looking dir: also skipped
    os.makedirs(os.path.join(d, "step-000000005"))
    with open(os.path.join(d, "step-000000005", "manifest.json"), "w") as f:
        f.write("{not json")
    assert dck.latest_step_dir(d).endswith("step-000000002")


# ---------------------------------------------------------------------------
# guarded steps
# ---------------------------------------------------------------------------

class _MLP(paddle.nn.Layer):
    def __init__(self, din=8, h=16):
        super().__init__()
        self.l1 = paddle.nn.Linear(din, h)
        self.l2 = paddle.nn.Linear(h, 1)

    def forward(self, x):
        return self.l2(F.relu(self.l1(x)))


def _guarded(tmpdir=None, scaler=None, max_bad_steps=10 ** 9):
    from paddle_tpu.utils.guarded import GuardedTrainStep
    paddle.seed(0)
    model = _MLP()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = pjit.TrainStep(model, lambda o, y: F.mse_loss(o, y), opt,
                          guard=True)
    g = GuardedTrainStep(step, checkpoint_dir=tmpdir, scaler=scaler,
                         max_bad_steps=max_bad_steps)
    return model, g


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(4, 8).astype("float32"),
             rng.randn(4, 1).astype("float32")) for _ in range(n)]


def test_guarded_step_skips_nonfinite_on_device(tmp_path):
    """NaN-poisoned grads at step 3: params, optimizer state and streak
    behave as a skip; a quarantine record lands on disk."""
    faults.enable("nan_grads", 3)
    model, g = _guarded(tmpdir=str(tmp_path))
    for i, (x, y) in enumerate(_batches(5), start=1):
        before = {k: np.asarray(v._data).copy()
                  for k, v in model.state_dict().items()}
        g(x, y)
        changed = any(
            np.abs(np.asarray(v._data) - before[k]).max() > 0
            for k, v in model.state_dict().items())
        if i == 3:
            assert g.last_skipped and not changed
        else:
            assert not g.last_skipped and changed
    assert [r["reason"] for r in g.quarantine] == ["nonfinite"]
    with open(os.path.join(str(tmp_path), "quarantine.jsonl")) as f:
        recs = [json.loads(l) for l in f]
    assert recs[0]["step"] == 3 and recs[0]["skipped_on_device"]


def test_guarded_step_feeds_scaler_skip_and_decay():
    """Without AMP, a nonfinite step still drives the attached GradScaler's
    decay half (decr_every_n_nan_or_inf=1 halves the scale)."""
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                   decr_every_n_nan_or_inf=1)
    faults.enable("nan_grads", 2)
    model, g = _guarded(scaler=scaler)
    for x, y in _batches(3):
        g(x, y)
    assert scaler.get_init_loss_scaling() == 512.0


def test_guarded_rollback_after_consecutive_bad_steps(tmp_path):
    """nan window [3, 5): two consecutive bad steps with max_bad_steps=2
    roll back to the step-2 checkpoint and record it."""
    faults.enable("nan_grads", "3:5")
    model, g = _guarded(tmpdir=str(tmp_path), max_bad_steps=2)
    batches = _batches(6)
    for x, y in batches[:2]:
        g(x, y)
    g.save_checkpoint()  # step 2
    snap = {k: np.asarray(v._data).copy()
            for k, v in model.state_dict().items()}
    g(*batches[2])  # bad (streak 1)
    assert g.bad_streak == 1 and g.quarantine[-1].get("rolled_back_to") is None
    g(*batches[3])  # bad (streak 2) -> rollback
    assert g.quarantine[-1]["rolled_back_to"] == 2
    assert g.step.optimizer._step_count == 2
    assert g.bad_streak == 0  # streak resets with the rollback
    for k, v in model.state_dict().items():
        np.testing.assert_array_equal(np.asarray(v._data), snap[k])


def test_guarded_run_steps_rejected():
    """guard=True + run_steps must fail loudly, not silently bypass the
    compiled finiteness guard inside the scan."""
    model, g = _guarded()
    x = np.zeros((2, 4, 8), "float32")
    y = np.zeros((2, 4, 1), "float32")
    with pytest.raises(NotImplementedError, match="guard"):
        g.step.run_steps(x, y)


def test_guarded_spike_detection():
    model, g = _guarded()
    g.min_window = 4
    g.spike_factor = 10.0
    for x, y in _batches(6, seed=1):
        g(x, y)
    # fake a filled window then force a spike via a huge-label batch
    x = np.zeros((4, 8), "float32")
    y = np.full((4, 1), 1e6, "float32")
    g(x, y)
    assert g.last_reason == "loss_spike"
    assert g.quarantine[-1]["reason"] == "loss_spike"


def test_sharded_step_guard_and_scaler_extras(tmp_path):
    """ShardedTrainStep: the same on-device guard skips a poisoned step,
    and GradScaler state rides the checkpoint extras (AMP resumes don't
    restart loss scaling from init)."""
    from paddle_tpu import parallel
    paddle.seed(0)
    model = _MLP()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    mesh = parallel.create_mesh({"dp": 8})
    step = parallel.ShardedTrainStep(model, lambda o, y: F.mse_loss(o, y),
                                     opt, mesh=mesh, guard=True)
    faults.enable("nan_grads", 2)
    rng = np.random.RandomState(0)  # batch divisible by the dp=8 mesh
    batches = [(rng.randn(8, 8).astype("float32"),
                rng.randn(8, 1).astype("float32")) for _ in range(3)]
    step(*batches[0])
    before = {k: np.asarray(v._data).copy()
              for k, v in model.state_dict().items()}
    step(*batches[1])  # poisoned -> on-device skip
    _, ok = step.last_guard
    assert not bool(np.asarray(ok))
    for k, v in model.state_dict().items():
        np.testing.assert_array_equal(np.asarray(v._data), before[k])
    faults.reset()

    scaler = paddle.amp.GradScaler(init_loss_scaling=4096.0)
    scaler._scale = 123.0
    scaler._good_steps = 7
    step.save_checkpoint(str(tmp_path), scaler=scaler)
    fresh = paddle.amp.GradScaler(init_loss_scaling=4096.0)
    meta = step.restore_checkpoint(str(tmp_path), scaler=fresh)
    assert meta is not None
    assert fresh.get_init_loss_scaling() == 123.0
    assert fresh._good_steps == 7


# ---------------------------------------------------------------------------
# dataloader: worker crash respawn + iterator shutdown
# ---------------------------------------------------------------------------

class _DetDataset:
    """Deterministic, module-level (picklable for forkserver workers)."""

    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return (np.full((8,), float(i), "float32"),
                np.asarray([i], "int64"))


@pytest.fixture
def hard_timeout():
    """The fleet tests' wedge guard (`tests/test_autoscale.py`): SIGALRM
    fails this one test if it hangs (now and then a killed `DataLoader`
    worker leaves the producer thread polling: the probe's run, and the
    in-process worker-crash tests here), where the hang used to cost a
    whole run its time limit."""
    def handler(signum, frame):
        raise TimeoutError("hard per-test timeout (120 s)")
    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(120)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def test_worker_crash_respawns_and_epoch_completes(tmp_path, hard_timeout):
    """A worker hard-killed (os._exit) mid-epoch is respawned and its lost
    batch redelivered: the epoch yields every batch, in order."""
    from paddle_tpu.io import DataLoader
    from paddle_tpu.utils.monitor import stat_get, stat_reset
    stat_reset("STAT_dataloader_worker_respawns")
    once = str(tmp_path / "once")
    faults.enable("worker_crash", f"kill:2:{once}")
    dl = DataLoader(_DetDataset(32), batch_size=4, num_workers=2)
    seen = []
    for xb, yb in dl:
        seen.extend(np.asarray(yb.numpy()).reshape(-1).tolist())
    assert seen == list(range(32))
    assert stat_get("STAT_dataloader_worker_respawns") >= 1
    assert os.path.exists(once)  # the fault actually fired


def test_worker_crash_budget_exhausted_raises(tmp_path, hard_timeout):
    """A poison task that kills every worker that touches it (no `once`
    sentinel) exhausts the respawn budget and surfaces UnavailableError."""
    from paddle_tpu.core.errors import UnavailableError
    from paddle_tpu.io import DataLoader
    faults.enable("worker_crash", "kill:1")  # fires every delivery
    dl = DataLoader(_DetDataset(16), batch_size=4, num_workers=2)
    with pytest.raises(UnavailableError, match="respawn budget"):
        for _ in dl:
            pass


def test_abandoned_iterator_releases_worker_pool():
    """Breaking out mid-epoch shuts the owned pool down promptly (the
    leak fix: producer thread + workers must not linger until loader
    __del__)."""
    from paddle_tpu.io import DataLoader
    dl = DataLoader(_DetDataset(64), batch_size=2, num_workers=2)
    it = iter(dl)
    next(it)
    it.close()  # explicit generator close (same path as break / GC)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with dl._pool_lock:
            n = len(dl._owned_pools)
        if n == 0:
            break
        time.sleep(0.1)
    assert n == 0
    dl.close()  # idempotent


def test_resumable_loader_cursor_fast_forwards():
    from paddle_tpu.io import DataLoader
    from paddle_tpu.io.dataloader import ResumableLoader
    dl = DataLoader(_DetDataset(24), batch_size=4, shuffle=False)
    cur = ResumableLoader(dl)
    got = []
    for xb, yb in cur:
        got.append(int(np.asarray(yb.numpy())[0, 0]))
        if cur.index == 3:
            break
    assert got == [0, 4, 8]
    state = cur.state_dict()
    assert state == {"epoch": 0, "index": 3}

    cur2 = ResumableLoader(DataLoader(_DetDataset(24), batch_size=4,
                                      shuffle=False))
    cur2.load_state_dict(state)
    rest = [int(np.asarray(yb.numpy())[0, 0]) for _, yb in cur2]
    assert rest == [12, 16, 20]
    assert cur2.epoch == 1 and cur2.index == 0

    # a broken-off epoch restarts the cursor: a fresh iteration replays
    # from batch 0 and index tracks the true position, not a stale count
    for i, _ in enumerate(cur):
        if i == 1:
            break
    first = []
    for _, yb in cur:
        first.append(int(np.asarray(yb.numpy())[0, 0]))
        if cur.index == 2:
            break
    assert first == [0, 4]
    assert cur.state_dict() == {"epoch": 0, "index": 2}


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preemption_checkpoint_and_exit_then_resume(tmp_path):
    """A real SIGTERM mid-loop sets the flag; the loop checkpoints (with
    the data cursor) and exits; the resumed run reproduces the
    uninterrupted trajectory exactly."""
    from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
        PreemptionHandler)
    batches = _batches(6, seed=7)

    def fresh():
        paddle.seed(0)
        model = _MLP()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        return model, pjit.TrainStep(model, lambda o, y: F.mse_loss(o, y),
                                     opt)

    model, step = fresh()
    straight = [float(step(x, y)) for x, y in batches]

    ckpt = str(tmp_path / "ck")
    model1, step1 = fresh()
    part1 = []
    with PreemptionHandler() as pre:
        for i, (x, y) in enumerate(batches):
            part1.append(float(step1(x, y)))
            if i == 2:
                os.kill(os.getpid(), signal.SIGTERM)
            if pre.preempted():
                step1.save_checkpoint(ckpt,
                                      data_cursor={"epoch": 0,
                                                   "index": i + 1})
                break
    assert len(part1) == 3
    # handler uninstalled on exit; a later SIGTERM would again be fatal
    assert signal.getsignal(signal.SIGTERM) != pre._on_signal

    model2, step2 = fresh()
    meta = step2.restore_checkpoint(ckpt)
    assert meta["step"] == 3
    assert meta["data_cursor"] == {"epoch": 0, "index": 3}
    part2 = [float(step2(x, y)) for x, y in batches[3:]]
    np.testing.assert_allclose(part1 + part2, straight, rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# the full probe, smoke mode
# ---------------------------------------------------------------------------

def test_resilience_probe_smoke(hard_timeout):
    """End-to-end acceptance: NaN-injected + worker-killed + SIGTERM-
    preempted run resumes to the baseline's exact final loss, and async
    saves stall the loop less than sync saves."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "probes",
                                      "resilience_probe.py"), "--smoke"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=REPO, start_new_session=True)
    try:
        out, err = proc.communicate()
    finally:
        if proc.poll() is None:     # the alarm fired: leave nothing behind
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    line = [l for l in out.splitlines() if l.startswith("RESIL")]
    assert line, (out, err)
    rec = json.loads(line[0][len("RESIL"):])
    parity = rec["chaos_parity"]
    assert parity["ok"], parity
    assert parity["max_param_diff"] < 1e-6
    assert parity["nan_skipped_steps"] >= 1
    assert parity["worker_respawns"] >= 1
    assert rec["async_save_stall_ms"] > 0
    # the >=2x stall bar is asserted on the bench host; here just sanity
    assert rec["sync_save_stall_ms"] > rec["async_save_stall_ms"]
