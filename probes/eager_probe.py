#!/usr/bin/env python
"""Eager dispatch ops/sec microbench (ISSUE-2 acceptance artifact).

Measures the imperative-runtime hot path — `core.op.dispatch` — with the
signature-keyed jitted forward+vjp cache ON vs OFF on two legs:

- per-op microbench: a fixed 5-op grad-enabled chain
  (matmul -> add -> relu -> multiply -> sum) + backward each step; the
  headline `eager_ops_per_sec` counts forward dispatches / wall second.
- small-MLP leg: 3-layer MLP (Linear+relu) fwd+bwd+SGD step, eager.

The uncached leg is exactly the `PADDLE_TPU_DISPATCH_CACHE=0` path: the env
knob sets the same flag this probe toggles in-process via
`core.op.set_dispatch_cache_enabled` (run with the env var set and `--env`
to skip the in-process toggle and measure only the ambient configuration).

Runs on CPU by default (JAX_PLATFORMS=cpu) so the
number reproduces in tier-1's environment.  Prints one `EAGER{json}` line;
`--steps 3` is the CI smoke mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200,
                    help="timed iterations of the per-op chain")
    ap.add_argument("--mlp-steps", type=int, default=None,
                    help="timed MLP train steps (default: steps//4, min 2)")
    ap.add_argument("--backend", default="cpu",
                    help="'cpu' (default, reproducible) or 'native' to keep "
                         "the ambient jax backend")
    ap.add_argument("--env", action="store_true",
                    help="do not toggle the cache in-process; measure only "
                         "the ambient PADDLE_TPU_DISPATCH_CACHE setting")
    args = ap.parse_args()

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core import op as core_op

    paddle.seed(0)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 64).astype("float32"))
    w = paddle.to_tensor(rng.randn(64, 64).astype("float32"))
    b = paddle.to_tensor(rng.randn(64).astype("float32"))
    for t in (x, w, b):
        t.stop_gradient = False

    def one_chain():
        y = paddle.matmul(x, w)
        y = paddle.add(y, b)
        y = F.relu(y)
        z = paddle.multiply(y, y)
        loss = paddle.sum(z)
        loss.backward()
        x.clear_grad(); w.clear_grad(); b.clear_grad()
        return loss

    def per_op_leg(steps):
        warm = min(5, max(1, steps // 2))
        for _ in range(warm):
            one_chain()
        n0 = core_op.dispatch_count()
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = one_chain()
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        return (core_op.dispatch_count() - n0) / dt, float(loss)

    mlp_steps = args.mlp_steps if args.mlp_steps is not None else max(
        2, args.steps // 4)
    # drawn ONCE so both legs train on identical data (the parity check
    # below compares final losses across legs)
    mlp_x = rng.randn(32, 64).astype("float32")
    mlp_y = rng.randint(0, 10, (32,)).astype("int64")

    def mlp_leg(steps):
        paddle.seed(0)
        import paddle_tpu.nn as nn
        model = nn.Sequential(
            nn.Linear(64, 128), nn.ReLU(),
            nn.Linear(128, 128), nn.ReLU(),
            nn.Linear(128, 10))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        xb = paddle.to_tensor(mlp_x)
        yb = paddle.to_tensor(mlp_y)

        def step():
            loss = F.cross_entropy(model(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        for _ in range(min(3, steps)):
            step()
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step()
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        return steps / dt, float(loss)

    legs = {}
    env_cached = core_op.dispatch_cache_stats()["enabled"]
    modes = [("ambient", env_cached)] if args.env else [
        ("uncached", False), ("cached", True)]
    for tag, enable in modes:
        if not args.env:
            core_op.set_dispatch_cache_enabled(enable)
            core_op.dispatch_cache_clear()
        ops_s, loss = per_op_leg(args.steps)
        mlp_s, mlp_loss = mlp_leg(mlp_steps)
        legs[tag] = {"ops_per_sec": round(ops_s, 1),
                     "mlp_steps_per_sec": round(mlp_s, 2),
                     "loss": loss, "mlp_loss": mlp_loss}

    cached = legs.get("cached", legs.get("ambient"))
    out = {
        "eager_ops_per_sec": cached["ops_per_sec"],
        "eager_mlp_steps_per_sec": cached["mlp_steps_per_sec"],
        "legs": legs,
        "cache": core_op.dispatch_cache_stats(),
        "backend": args.backend,
        "steps": args.steps, "mlp_steps": mlp_steps,
        "config": "per-op: 5-op grad chain 64x64 + backward; mlp: "
                  "64-128-128-10 b32 SGD, all eager",
    }
    if "uncached" in legs and legs["uncached"]["ops_per_sec"]:
        out["speedup_vs_uncached"] = round(
            cached["ops_per_sec"] / legs["uncached"]["ops_per_sec"], 2)
        out["mlp_speedup_vs_uncached"] = round(
            cached["mlp_steps_per_sec"] / legs["uncached"]["mlp_steps_per_sec"],
            2)
        # grad-parity assertion rides in the probe: identical losses on the
        # two legs (same seed, same data) or the number is meaningless
        for k in ("loss", "mlp_loss"):
            a, bve = legs["cached"][k], legs["uncached"][k]
            if not np.allclose(a, bve, rtol=1e-4, atol=1e-5):
                out["parity_error"] = f"{k}: cached {a} vs uncached {bve}"
    print("EAGER" + json.dumps(out), flush=True)
    # parity failure means the speedup number is meaningless: fail the
    # probe so CI and the bench leg cannot publish it as a headline
    return 1 if "parity_error" in out else 0


if __name__ == "__main__":
    raise SystemExit(main())
