"""ResNet-50 TPU component profile (VERDICT r3 item #1).

Each mode runs in its OWN process (two big models in one TPU process
cross-contaminate HBM and inflate wall clocks — the r3 39ms-probe vs
50.45ms-bench discrepancy).  Drive with probes/run_resnet_probes.sh or:

    python probes/resnet_probe.py <mode> [batch]

Modes: baseline fwd fwdbwd nobn o2 f32 convtower convtower_nhwc stem
Prints one line:  PROBE <mode> <batch> <ms_per_step> <detail...>
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9


def _sync(x):
    import jax
    jax.block_until_ready(x)
    return float(np.asarray(x).reshape(-1)[0])


def timed_calls(fn, warmup=2, iters=4):
    """bench-style timing: queue all calls, sync ONCE at the end — per-call
    dispatch latency (~150-200ms for the ~270-leaf ResNet state on round
    5's remotely attached chip) otherwise dominates and overlapped dispatch
    is the real deployment shape.  The per-call list holds UN-synced dispatch times."""
    for _ in range(warmup):
        out = fn()
    _sync(out)
    t0 = time.perf_counter()
    per = []
    for _ in range(iters):
        t1 = time.perf_counter()
        out = fn()
        per.append(time.perf_counter() - t1)
    _sync(out)
    dt = (time.perf_counter() - t0) / iters
    return dt, per


def strip_bn(model):
    from paddle_tpu import nn
    for layer in model.sublayers(include_self=True):
        for name, sub in list(layer._sub_layers.items()):
            if sub is not None and "BatchNorm" in type(sub).__name__:
                layer._sub_layers[name] = nn.Identity()
    return model


def build(batch, nobn=False, data_format="NCHW"):
    import paddle_tpu as paddle
    from paddle_tpu.vision import models as vmodels
    paddle.seed(0)
    model = vmodels.resnet50(data_format=data_format)
    if nobn:
        strip_bn(model)
    rng = np.random.RandomState(0)
    shape = ((batch, 3, 224, 224) if data_format == "NCHW"
             else (batch, 224, 224, 3))
    x = rng.randn(*shape).astype("float32")
    y = rng.randint(0, 1000, (batch,)).astype("int64")
    return paddle, model, x, y


def mode_trainstep(batch, amp="O1", nobn=False, k=None,
                   data_format="NCHW"):
    if k is None:
        k = int(os.environ.get("PROBE_K", "10"))
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep
    paddle, model, x, y = build(batch, nobn=nobn, data_format=data_format)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    step = TrainStep(model, lambda logits, label: F.cross_entropy(
        logits, label), opt, amp_level=amp, amp_dtype="bfloat16")
    xs = paddle.to_tensor(np.broadcast_to(x, (k,) + x.shape).copy())
    ys = paddle.to_tensor(np.broadcast_to(y, (k,) + y.shape).copy())

    def call():
        return step.run_steps(xs, ys)._data
    dt, per = timed_calls(call, warmup=2, iters=3)
    return dt / k, [p / k for p in per]


def mode_fwd(batch, with_bwd=False):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import amp as amp_mod
    from paddle_tpu.jit import forward_loss, state_arrays
    import paddle_tpu.nn.functional as F
    paddle, model, x, y = build(batch)
    state = state_arrays(model)

    trainable = {k for k, v in model.state_dict().items()
                 if getattr(v, "trainable", False)}
    train_params = {k: v for k, v in state.items() if k in trainable}
    frozen = {k: v for k, v in state.items() if k not in trainable}

    def loss_of(tp, xb, yb):
        full = dict(frozen)
        full.update(tp)
        return forward_loss(model, lambda logits, label: F.cross_entropy(
            logits, label), full, (xb, yb), rng_key=jax.random.PRNGKey(0),
            amp_level="O1")

    if with_bwd:
        def _loss_plus_gradsum(tp, xb, yb):
            # fold every grad leaf into the output so XLA can't DCE the bwd
            loss, grads = jax.value_and_grad(loss_of)(tp, xb, yb)
            return loss + sum(jnp.sum(g.astype(jnp.float32)) * 1e-30
                              for g in jax.tree_util.tree_leaves(grads))
        fn = jax.jit(_loss_plus_gradsum)
    else:
        fn = jax.jit(loss_of)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    dt, per = timed_calls(lambda: fn(train_params, xj, yj), warmup=2,
                          iters=6)
    return dt, per


def _conv_list():
    """(cin, cout, k, stride, hw_in) for every conv in ResNet-50 (stride on
    the 3x3, paddle/torchvision convention)."""
    convs = [(3, 64, 7, 2, 224)]  # stem; maxpool/2 follows -> 56
    spec = [(64, 3, 1, 56), (128, 4, 2, 56), (256, 6, 2, 28), (512, 3, 2, 14)]
    inplanes = 64
    for planes, blocks, stride, hw_in in spec:
        out = planes * 4
        hw_out = hw_in // stride
        for b in range(blocks):
            s = stride if b == 0 else 1
            hw = hw_in if b == 0 else hw_out
            convs.append((inplanes, planes, 1, 1, hw))
            convs.append((planes, planes, 3, s, hw))
            convs.append((planes, out, 1, 1, hw_out))
            if b == 0 and (s != 1 or inplanes != out):
                convs.append((inplanes, out, 1, s, hw))
            inplanes = out
    return convs


def mode_convtower(batch, layout="NCHW", with_bwd=True):
    """Pure conv chain at ResNet-50 shapes: the achievable conv ceiling."""
    import jax
    import jax.numpy as jnp
    convs = _conv_list()
    dn = ("NCHW", "OIHW", "NCHW") if layout == "NCHW" else \
         ("NHWC", "HWIO", "NHWC")
    rng = np.random.RandomState(0)
    weights = []
    flops = 0.0
    for cin, cout, kk, s, hw in convs:
        if layout == "NCHW":
            w = rng.randn(cout, cin, kk, kk).astype(np.float32) * 0.05
        else:
            w = rng.randn(kk, kk, cin, cout).astype(np.float32) * 0.05
        weights.append(jnp.asarray(w, jnp.bfloat16))
        hw_out = hw // s
        flops += 2.0 * batch * hw_out * hw_out * cin * cout * kk * kk

    def run(ws, inputs):
        acc = jnp.float32(0)
        for (cin, cout, kk, s, hw), w, x in zip(convs, ws, inputs):
            pad = [(kk // 2, kk // 2)] * 2
            o = jax.lax.conv_general_dilated(
                x, w, window_strides=(s, s), padding=pad,
                dimension_numbers=dn)
            acc = acc + jnp.sum(o.astype(jnp.float32)) * 1e-12
        return acc

    inputs = []
    for cin, cout, kk, s, hw in convs:
        shp = (batch, cin, hw, hw) if layout == "NCHW" else (batch, hw, hw, cin)
        inputs.append(jnp.asarray(rng.randn(*shp) * 0.05, jnp.bfloat16))

    if with_bwd:
        g = jax.jit(lambda ws, xs: jax.grad(
            lambda ws2: run(ws2, xs))(ws)[0].astype(jnp.float32).sum())
        fn = lambda: g(weights, inputs)
        mult = 2.0  # fwd + grad_w only (inputs are leaves, no grad_x chain)
    else:
        j = jax.jit(run)
        fn = lambda: j(weights, inputs)
        mult = 1.0
    dt, per = timed_calls(fn, warmup=2, iters=6)
    tfs = flops * mult / dt / 1e12
    return dt, tfs, flops * mult


def mode_convtower_grouped(batch, layout="NCHW", n_groups=8):
    """Conv ceiling at the REAL operating batch (VERDICT r5 #3): the r4
    monolithic tower OOM'd at b256 (5.5 GB inputs + 5.7 GB outputs + grad
    stash > 16 GB HBM — why probes/resnet_probe_results2.txt's b256
    sections are empty).  This version (a) splits the 53 convs into
    contiguous groups so only one group's arrays are resident, (b) makes
    inputs ON DEVICE (jax.random, no host transfer), and (c) times each
    group by the k-difference form (2 vs 10 queued iters, one sync each)
    so the fixed sync round trip cancels per group."""
    import jax
    import jax.numpy as jnp
    convs = _conv_list()
    dn = ("NCHW", "OIHW", "NCHW") if layout == "NCHW" else \
         ("NHWC", "HWIO", "NHWC")
    per = (len(convs) + n_groups - 1) // n_groups
    total_flops, total_dt, rows = 0.0, 0.0, []
    key = jax.random.key(0)
    for gi in range(0, len(convs), per):
        sub = convs[gi:gi + per]
        ws, xs, flops = [], [], 0.0
        for cin, cout, kk, s, hw in sub:
            key, k1, k2 = jax.random.split(key, 3)
            wshape = ((cout, cin, kk, kk) if layout == "NCHW"
                      else (kk, kk, cin, cout))
            xshape = ((batch, cin, hw, hw) if layout == "NCHW"
                      else (batch, hw, hw, cin))
            ws.append(jax.random.normal(k1, wshape, jnp.bfloat16) * 0.05)
            xs.append(jax.random.normal(k2, xshape, jnp.bfloat16) * 0.05)
            flops += 2.0 * batch * (hw // s) ** 2 * cin * cout * kk * kk

        def run(ws, xs, sub=sub):
            # sum of SQUARES: a loss linear in the conv outputs has an
            # all-ones cotangent and XLA strength-reduces both the
            # backward convs AND the forward (group rates > peak were the
            # tell); o^2 makes every cotangent data-dependent
            acc = jnp.float32(0)
            for (cin, cout, kk, s, hw), w, x in zip(sub, ws, xs):
                pad = [(kk // 2, kk // 2)] * 2
                o = jax.lax.conv_general_dilated(
                    x, w, window_strides=(s, s), padding=pad,
                    dimension_numbers=dn)
                # square in the conv dtype, accumulate f32 IN the reduce:
                # .astype(f32)**2 materialized multi-GB f32 copies of the
                # big early activations (stem alone: 3.2 GB at b256) and
                # HBM-thrashed the probe to ~5 TF/s
                acc = acc + jnp.sum(o * o, dtype=jnp.float32) * 1e-12
            return acc

        # grad wrt ALL weights AND inputs, summed over every leaf — taking
        # [0] lets XLA dead-code-eliminate every other conv entirely (the
        # r4 tower numbers had exactly that bug: 26-30 "TF/s" was one conv
        # per group, not the tower)
        def g_all(ws, xs, run=run):
            gws, gxs = jax.grad(
                lambda a, b: run(a, b), argnums=(0, 1))(ws, xs)
            tot = jnp.float32(0)
            for t in list(gws) + list(gxs):
                tot = tot + jnp.sum(t.astype(jnp.float32))
            return tot

        g = jax.jit(g_all)

        def timed_n(n):
            t0 = time.perf_counter()
            for _ in range(n):
                out = g(ws, xs)
            _sync(out)
            return time.perf_counter() - t0

        _sync(g(ws, xs))  # compile + warm
        t2, t18 = timed_n(2), timed_n(18)
        net = (t18 - t2) / 16
        mult = 3.0  # fwd + grad_w + grad_x (the train-step accounting)
        rows.append((gi, len(sub), net * 1e3,
                     flops * mult / net / 1e12))
        total_flops += flops * mult
        total_dt += net
        del ws, xs
    for gi, n, ms, tfs in rows:
        print(f"  group@{gi} ({n} convs): {ms:.1f} ms  {tfs:.1f} TF/s",
              flush=True)
    return total_dt, total_flops / total_dt / 1e12, total_flops


def main():
    mode = sys.argv[1]
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    if mode == "baseline":
        dt, per = mode_trainstep(batch)
    elif mode == "nhwc":
        dt, per = mode_trainstep(batch, data_format="NHWC")
    elif mode == "nhwc_o2":
        dt, per = mode_trainstep(batch, amp="O2", data_format="NHWC")
    elif mode == "o2":
        dt, per = mode_trainstep(batch, amp="O2")
    elif mode == "f32":
        dt, per = mode_trainstep(batch, amp=None)
    elif mode == "nobn":
        dt, per = mode_trainstep(batch, nobn=True)
    elif mode == "fwd":
        dt, per = mode_fwd(batch, with_bwd=False)
    elif mode == "fwdbwd":
        dt, per = mode_fwd(batch, with_bwd=True)
    elif mode in ("convtower", "convtower_nhwc"):
        layout = "NHWC" if mode.endswith("nhwc") else "NCHW"
        dt, tfs, fl = mode_convtower(batch, layout=layout)
        print(f"PROBE {mode} {batch} {dt*1e3:.2f} tf_s={tfs:.1f} "
              f"flops={fl:.3e}", flush=True)
        return
    elif mode in ("convfwd", "convfwd_nhwc"):
        layout = "NHWC" if mode.endswith("nhwc") else "NCHW"
        dt, tfs, fl = mode_convtower(batch, layout=layout, with_bwd=False)
        print(f"PROBE {mode} {batch} {dt*1e3:.2f} tf_s={tfs:.1f} "
              f"flops={fl:.3e}", flush=True)
        return
    elif mode in ("convtower2", "convtower2_nhwc"):
        layout = "NHWC" if mode.endswith("nhwc") else "NCHW"
        dt, tfs, fl = mode_convtower_grouped(batch, layout=layout)
        print(f"PROBE {mode} {batch} {dt*1e3:.2f} tf_s={tfs:.1f} "
              f"flops={fl:.3e}", flush=True)
        return
    else:
        raise SystemExit(f"unknown mode {mode}")
    sps = batch / dt
    mfu = RESNET50_TRAIN_FLOPS_PER_IMG * sps / 197e12 * 100
    # per-call times are UN-synced dispatch latencies (sync happens once at
    # the end) — label them as such, not as per-step spread
    per_s = ",".join(f"{p*1e3:.1f}" for p in per)
    print(f"PROBE {mode} {batch} {dt*1e3:.2f} sps={sps:.0f} mfu={mfu:.1f} "
          f"dispatch_ms_per_call={per_s}", flush=True)


if __name__ == "__main__":
    main()
