#!/usr/bin/env python
"""Resilient-training probe (ISSUE-3 acceptance artifact).

Two legs, one RESIL{json} line:

1. **Save-stall leg** (in-process): a compiled train-step loop checkpoints
   every k steps, once with the synchronous CheckpointManager (serialize +
   atomic rename on the training thread) and once with
   AsyncCheckpointManager (device->host snapshot + enqueue on the training
   thread; npz/rename/fsync on the background writer).  Headline:
   `stall_ratio` = mean sync save stall / mean async save stall — the
   acceptance bar is >= 2x.

2. **Chaos-parity leg** (subprocesses): a deterministic SGD MLP run is
   trained three ways —
     baseline: M steps uninterrupted;
     chaos:    NaN-injected grads at step k (guarded step skips on-device,
               the runner retries the batch), a DataLoader worker
               hard-killed mid-epoch (pool respawns + redelivers), then a
               real SIGTERM after P batches (PreemptionHandler ->
               checkpoint with rng + GradScaler + data cursor -> clean
               exit);
     resume:   restores the checkpoint + cursor and finishes.
   Parity: chaos-resumed final loss and params must equal the baseline's.

Runs on CPU (JAX_PLATFORMS=cpu) so the numbers reproduce in tier-1's
environment.  `--smoke` shrinks both legs for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

BATCH = 8
IN_DIM = 32


class ChaosDataset:
    """Deterministic map-style dataset: sample i is a fixed function of i,
    so worker-parallel, single-process, and resumed runs all see identical
    batches (module-level: picklable for forkserver workers)."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.RandomState(1000 + i)
        x = rng.randn(IN_DIM).astype("float32")
        y = np.asarray([np.sin(i * 0.1)], "float32")
        return x, y


def build(hidden=64, lr=0.05, guard=False):
    import paddle_tpu as paddle
    from paddle_tpu import jit as pjit

    class MLP(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = paddle.nn.Linear(IN_DIM, hidden)
            self.l2 = paddle.nn.Linear(hidden, hidden)
            self.l3 = paddle.nn.Linear(hidden, 1)

        def forward(self, x):
            import paddle_tpu.nn.functional as F
            return self.l3(F.relu(self.l2(F.relu(self.l1(x)))))

    paddle.seed(0)
    model = MLP()
    opt = paddle.optimizer.SGD(learning_rate=lr,
                               parameters=model.parameters())
    import paddle_tpu.nn.functional as F
    step = pjit.TrainStep(model, lambda out, y: F.mse_loss(out, y), opt,
                          guard=guard)
    return model, opt, step


# ---------------------------------------------------------------------------
# leg 1: save stall
# ---------------------------------------------------------------------------

def measure_save_stall(steps, save_every, hidden):
    from paddle_tpu.distributed.checkpoint import (AsyncCheckpointManager,
                                                   CheckpointManager)
    from paddle_tpu.jit import state_arrays

    def leg(use_async, workdir):
        model, opt, step = build(hidden=hidden)
        rng = np.random.RandomState(0)
        xs = rng.randn(steps, BATCH, IN_DIM).astype("float32")
        ys = rng.randn(steps, BATCH, 1).astype("float32")
        mgr_cls = AsyncCheckpointManager if use_async else CheckpointManager
        mgr = mgr_cls(workdir, max_to_keep=2, save_interval_steps=save_every)
        stalls = []
        step(xs[0], ys[0])  # compile outside the timed region
        for i in range(1, steps):
            step(xs[i], ys[i])
            if i % save_every == 0:
                state = {"params": state_arrays(model),
                         "opt": step._opt_state}
                t0 = time.perf_counter()
                mgr.save(state, i)
                stalls.append(time.perf_counter() - t0)
        if use_async:
            mgr.wait_until_finished()
            mgr.close()
        assert mgr.all_steps(), "no checkpoint landed"
        return 1e3 * sum(stalls) / max(1, len(stalls))

    with tempfile.TemporaryDirectory() as d:
        sync_ms = leg(False, os.path.join(d, "sync"))
    with tempfile.TemporaryDirectory() as d:
        async_ms = leg(True, os.path.join(d, "async"))
    return {"sync_save_stall_ms": round(sync_ms, 3),
            "async_save_stall_ms": round(async_ms, 3),
            "stall_ratio": round(sync_ms / max(async_ms, 1e-9), 2),
            "async_ge_2x": bool(sync_ms >= 2.0 * async_ms)}


# ---------------------------------------------------------------------------
# leg 2: chaos parity (subprocess roles)
# ---------------------------------------------------------------------------

def _loader(n_batches, num_workers):
    from paddle_tpu.io import DataLoader
    return DataLoader(ChaosDataset(n_batches * BATCH), batch_size=BATCH,
                      shuffle=False, num_workers=num_workers)


def run_baseline(args):
    """Uninterrupted reference run: M steps, single-process loader."""
    model, opt, step = build()
    losses = []
    for i, (x, y) in enumerate(_loader(args.steps, 0)):
        losses.append(float(step(x, y)))
    np.savez(args.params_out,
             **{k: np.asarray(v._data) for k, v in
                model.state_dict().items()})
    print("CHAOS" + json.dumps({"final_loss": losses[-1],
                                "steps": len(losses)}), flush=True)


def run_chaos(args):
    """Faulted run: guarded step + worker pool + preemption handler.
    Faults are armed by the parent via env.  Exits 3 after the preemption
    checkpoint; run again with --role resume to finish."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
        PreemptionHandler)
    from paddle_tpu.io.dataloader import ResumableLoader
    from paddle_tpu.utils.guarded import GuardedTrainStep
    from paddle_tpu.utils.monitor import stat_get

    model, opt, step = build(guard=True)
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0)
    gstep = GuardedTrainStep(step, checkpoint_dir=args.ckpt, scaler=scaler,
                             max_bad_steps=10**9)  # skip, never roll back
    cursor = ResumableLoader(_loader(args.steps, args.workers))
    resumed_meta = None
    if args.role == "resume":
        resumed_meta = gstep.restore_checkpoint(args.ckpt)
        assert resumed_meta is not None, "resume role found no checkpoint"
        if "data_cursor" in resumed_meta:
            cursor.load_state_dict(resumed_meta["data_cursor"])
    preempt_at = int(os.environ.get("PDTPU_PROBE_PREEMPT_AT") or "0")
    skipped = 0
    losses = []
    with PreemptionHandler() as pre:
        for x, y in cursor:
            while True:  # retry the batch if the guard skipped its update
                loss = float(gstep(x, y))
                if not gstep.last_skipped:
                    break
                skipped += 1
            losses.append(loss)
            if preempt_at and cursor.index == preempt_at:
                os.kill(os.getpid(), signal.SIGTERM)  # the real signal
                time.sleep(0.1)
            if pre.preempted():
                gstep.save_checkpoint(data_cursor=cursor.state_dict())
                print("CHAOS" + json.dumps(
                    {"preempted_at": cursor.index,
                     "nan_skipped_steps": skipped,
                     "worker_respawns":
                         stat_get("STAT_dataloader_worker_respawns")}),
                    flush=True)
                raise SystemExit(3)
    np.savez(args.params_out,
             **{k: np.asarray(v._data) for k, v in
                model.state_dict().items()})
    print("CHAOS" + json.dumps(
        {"final_loss": losses[-1], "steps_this_run": len(losses),
         "resumed_from": None if resumed_meta is None
         else resumed_meta["step"],
         "nan_skipped_steps": skipped,
         "worker_respawns": stat_get("STAT_dataloader_worker_respawns")}),
        flush=True)


def _sub(role, args, extra_env, params_out=None, ckpt=None):
    env = dict(os.environ)
    env.update(extra_env)
    cmd = [sys.executable, os.path.abspath(__file__), "--role", role,
           "--steps", str(args.steps), "--workers", str(args.workers)]
    if params_out:
        cmd += ["--params-out", params_out]
    if ckpt:
        cmd += ["--ckpt", ckpt]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env=env)
    rec = None
    for line in proc.stdout.splitlines():
        if line.startswith("CHAOS"):
            rec = json.loads(line[len("CHAOS"):])
    if rec is None:
        raise RuntimeError(
            f"{role} subprocess produced no CHAOS line (rc={proc.returncode})"
            f": {(proc.stderr or proc.stdout)[-800:]}")
    return proc.returncode, rec


def measure_chaos_parity(args):
    with tempfile.TemporaryDirectory() as d:
        base_npz = os.path.join(d, "baseline.npz")
        chaos_npz = os.path.join(d, "chaos.npz")
        ckpt = os.path.join(d, "ckpt")
        once = os.path.join(d, "worker_kill_once")
        nan_step = max(2, args.steps // 3)
        kill_seq = 1
        preempt_at = max(3, 2 * args.steps // 3)

        rc, base = _sub("baseline", args, {}, params_out=base_npz)
        assert rc == 0, f"baseline failed rc={rc}"

        chaos_env = {
            "PDTPU_FAULT_NAN_GRADS": str(nan_step),
            "PDTPU_FAULT_WORKER_CRASH": f"kill:{kill_seq}:{once}",
            "PDTPU_PROBE_PREEMPT_AT": str(preempt_at),
        }
        rc, mid = _sub("chaos", args, chaos_env, params_out=chaos_npz,
                       ckpt=ckpt)
        assert rc == 3, f"chaos run should exit 3 (preempted), got {rc}"

        clean_env = {"PDTPU_FAULT_NAN_GRADS": "", "PDTPU_PROBE_PREEMPT_AT":
                     "", "PDTPU_FAULT_WORKER_CRASH": ""}
        rc, fin = _sub("resume", args, clean_env, params_out=chaos_npz,
                       ckpt=ckpt)
        assert rc == 0, f"resume failed rc={rc}"

        a, b = np.load(base_npz), np.load(chaos_npz)
        max_diff = max(float(np.abs(a[k] - b[k]).max()) for k in a.files)
        loss_diff = abs(base["final_loss"] - fin["final_loss"])
        return {
            "baseline_final_loss": round(base["final_loss"], 8),
            "chaos_final_loss": round(fin["final_loss"], 8),
            "final_loss_diff": loss_diff,
            "max_param_diff": max_diff,
            "nan_injected_at_step": nan_step,
            "nan_skipped_steps": mid.get("nan_skipped_steps"),
            "worker_killed_at_seq": kill_seq,
            "worker_respawns": mid.get("worker_respawns"),
            "preempted_at_batch": mid.get("preempted_at"),
            "resumed_from_step": fin.get("resumed_from"),
            "ok": bool(loss_diff < 1e-6 and max_diff < 1e-6
                       and mid.get("nan_skipped_steps", 0) >= 1
                       and mid.get("worker_respawns", 0) >= 1),
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", default="orchestrate",
                    choices=["orchestrate", "baseline", "chaos", "resume"])
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--stall-steps", type=int, default=None)
    ap.add_argument("--hidden", type=int, default=None)
    ap.add_argument("--params-out", default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny shapes, few steps")
    args = ap.parse_args()
    if args.steps is None:
        args.steps = 9 if args.smoke else 15
    if args.stall_steps is None:
        args.stall_steps = 9 if args.smoke else 33
    if args.hidden is None:
        args.hidden = 256 if args.smoke else 1024

    if args.role == "baseline":
        return run_baseline(args)
    if args.role in ("chaos", "resume"):
        return run_chaos(args)

    out = {}
    try:
        out.update(measure_save_stall(args.stall_steps, save_every=4,
                                      hidden=args.hidden))
    except Exception as e:
        out["stall_error"] = f"{type(e).__name__}: {e}"[:300]
    try:
        out["chaos_parity"] = measure_chaos_parity(args)
    except Exception as e:
        out["chaos_parity"] = {"ok": False,
                               "error": f"{type(e).__name__}: {e}"[:500]}
    print("RESIL" + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
