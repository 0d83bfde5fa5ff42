"""The Mellum 2 decoder as it trains (`models/mellum2.py`: window and full
attention layers through the flash kernel's grouped form, forward and
backward; routed experts held a share at a time through the trainable form
of `F.moe_ffn_held`) against the benchmark's plain reference
(`benchmark/reference/mellum2.py`), at a tiny size on the CPU in float32
with seeded weights; the kernel's new backward and the held experts' new
backward alone, its grouped kernels traced once for all layers; YaRN's
frequencies; the shares of the experts.

Tolerances, each with its reason:
  * 2e-5 relative on the loss and 2e-4 relative (of a leaf's largest
    element) on every leaf's gradient through `TrainStep` in float32: the
    same arithmetic in another order (the kernel's blocks, the held picks in
    tiles, the reference dense over experts); gradients are of size 1e-3
    to 1e-1 and their float32 sums over a few thousand terms differ by
    ~1e-7 of the largest.
  * 2e-5 of the largest element between the kernel's gradients and
    `jax.grad` of plain attention, and between the held experts' backward
    and `jax.grad` of the dense layer: one algorithm in another order,
    float32 sums of a few hundred terms.
"""
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import mellum2 as M
from paddle_tpu.nn.functional import moe
from paddle_tpu.observability import get_tracer
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import grouped_matmul as gm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import train_check, weights as W  # noqa: E402
from benchmark.arch import mellum2 as A  # noqa: E402

LOSS_TOL, GRAD_TOL, OP_TOL = 2e-5, 2e-4, 2e-5
PUBLISHED_ROPE = M._ROPE


def tiny_cfg(hd=16, heads=4, kv_heads=2, hidden=64, window=16):
    return dict(
        vocab_size=128, hidden_size=hidden, num_hidden_layers=4,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        head_dim=hd, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, num_experts_held=4, experts_held=[0, 1, 2, 3],
        sliding_window=window, rms_norm_eps=1e-6, initializer_range=0.1,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        rope_parameters=PUBLISHED_ROPE)


def program(cfg):
    kw = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "experts_held", "sliding_window", "rms_norm_eps",
        "initializer_range", "layer_types", "rope_parameters")}
    return models.Mellum2ForCausalLM(models.Mellum2Config(**kw))


# (head_dim, heads, kv heads, hidden, window, seq): the XLA form at a tiny
# width; the flash kernel's grouped form through the interpreter (r = 2,
# heads of 128, a window under one of the kernel's 256-row blocks)
PATHS = {"xla": (16, 4, 2, 64, 16, 64), "flash": (128, 2, 1, 64, 100, 512)}


@pytest.mark.parametrize("path", list(PATHS))
def test_train_step_loss_and_every_leafs_gradient_follow_the_reference(
        monkeypatch, path):
    """One float32 step through `jit.TrainStep` (no autocast): the loss,
    and every leaf's gradient as the optimizer got it (Adam's first moment
    after one step is (1 - beta1) g), against the reference's on the same
    weights and ids; the attention path is the one asked for."""
    hd, heads, kv, hidden, window, seq = PATHS[path]
    if path == "flash":
        monkeypatch.setattr(fa, "_INTERPRET", True)
    cfg = tiny_cfg(hd, heads, kv, hidden, window)
    d = A.dims(cfg)
    layout = A.layout(d)
    model = program(cfg)
    train_check.load_weights(A, d, model, W.make(layout, 5))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, beta1=0.9,
                                 parameters=model.parameters())
    step = TrainStep(model, A.train_loss_fn(), opt)
    ids = np.random.RandomState(3).randint(0, 128, (2, seq + 1)).astype(
        np.int32)
    feed = (ids[:, :-1], ids[:, 1:])
    from benchmark.generators.train_steps import attention_paths
    before = attention_paths()
    loss = float(step(*(paddle.to_tensor(x) for x in feed)))
    moved = {k: v - before.get(k, 0) for k, v in attention_paths().items()}
    assert moved.get(path) == 4 and sum(moved.values()) == 4
    ref_loss, ref_grads = A.reference.loss_and_grads(
        W.make(layout, 5), tuple(jnp.asarray(x) for x in feed), heads)
    assert abs(loss - float(ref_loss)) <= LOSS_TOL * abs(float(ref_loss))
    grads = {k: v["moment1"] / 0.1 for k, v in step._opt_state.items()}
    by_ref = train_check._gather(A, d, grads)
    for name in ("embed", "norm", "head"):
        np.testing.assert_allclose(
            by_ref[name], ref_grads[name],
            atol=GRAD_TOL * float(jnp.max(jnp.abs(ref_grads[name]))))
    for name, leaf in ref_grads["layers"].items():
        np.testing.assert_allclose(
            by_ref["layers"][name], leaf,
            atol=GRAD_TOL * float(jnp.max(jnp.abs(leaf))), err_msg=name)
    # the router's gradient comes through the picks' shares alone
    assert float(jnp.max(jnp.abs(ref_grads["layers"]["router"]))) > 0


def test_the_counts_ride_on_the_train_step_span_unread_until_the_ring_is():
    """`train_count_names`: the step's routed counts come back beside the
    loss as one device vector, and the `train_step` span holds them as
    numbers once the tracer's ring is read; a model without the names
    leaves its span as it was."""
    cfg = tiny_cfg()
    model = program(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, A.train_loss_fn(), opt)
    ids = np.random.RandomState(0).randint(0, 128, (2, 33)).astype(np.int32)
    step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    assert isinstance(step.last_counts, jax.Array)
    args = [ev[6] for ev in get_tracer().events() if ev[0] == "train_step"]
    last = args[-1]
    assert set(last) == {"step"} | set(model.train_count_names)
    assert last["routed_all"] == 2 * 32 * 2 * 4     # tokens x k x layers
    assert 0 < last["routed_here"] <= last["routed_all"]
    assert 0 < last["experts_hit"] <= 4 * 4
    # the grouped pass's two forward kernels a layer, over the whole tiles
    # that hold each layer's held picks (one tile of 128 rows here)
    assert last["expert_products"] == gm.FORWARD_CALLS * 4
    assert last["routed_here"] <= last["expert_rows"] <= 4 * 128
    assert all(isinstance(v, int) for v in last.values())

    gpt = models.GPTForPretraining(models.GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=32))
    crit = models.GPTPretrainingCriterion()
    gstep = TrainStep(gpt, lambda lg, lab: crit(lg, lab),
                      paddle.optimizer.AdamW(learning_rate=1e-3,
                                             parameters=gpt.parameters()))
    gstep(paddle.to_tensor(ids[:, :16] % 64), paddle.to_tensor(
        ids[:, 1:17] % 64))
    args = [ev[6] for ev in get_tracer().events() if ev[0] == "train_step"]
    assert set(args[-1]) == {"step"} and gstep.last_counts is None


# ------------------------------------------------- the kernel's backward

def plain(q, k, v, window):
    """Float32 softmax over each query's kept keys (the queries the last of
    the keys' positions), the KV heads repeated."""
    r = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, r, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / math.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None] + k.shape[1] - q.shape[1]
    j = jnp.arange(k.shape[1])[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


# (sq, sk, r, window, written out): blocks of 256 written out at 512 rows,
# of 512 walked by `fori_loop`s past `_WRITTEN_OUT`; a window under one
# block, over several, one that skips whole blocks (2048 rows, 300), and
# queries that are the last rows of longer keys
BACKWARD = {
    "r1_causal": (512, 512, 1, None, True),
    "r8_causal": (512, 512, 8, None, True),
    "r1_window_under_a_block": (512, 512, 1, 100, True),
    "r8_window_under_a_block": (512, 512, 8, 100, True),
    "r8_window_of_blocks": (512, 512, 8, 300, True),
    "r8_loop_window_of_blocks": (1536, 1536, 8, 600, False),
    "r1_loop_skips_blocks": (2048, 2048, 1, 300, False),
    "r8_queries_shorter": (384, 640, 8, 200, True),
    "r1_queries_shorter_loop": (512, 1024, 1, None, False),
}


@pytest.mark.parametrize("case", list(BACKWARD))
def test_grouped_windowed_backward_is_the_gradient_of_plain_attention(
        monkeypatch, case):
    """`flash_attention_grouped`'s backward through the interpreter: dQ,
    and dK and dV summed over the query heads of a KV head, against
    `jax.grad` of the plain softmax, for one KV head a query head and for
    eight, with and without a window's far edge bounding the walk."""
    sq, sk, r, window, written_out = BACKWARD[case]
    monkeypatch.setattr(fa, "_INTERPRET", True)
    if not written_out:
        monkeypatch.setattr(fa, "_WRITTEN_OUT", 0)
    hkv = 1 if r == 8 else 2
    rng = np.random.RandomState(sq + r)
    q, k, v = (jnp.asarray(rng.standard_normal((1, n, h, 128)), jnp.float32)
               for n, h in ((sq, hkv * r), (sk, hkv), (sk, hkv)))
    g = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    got = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention_grouped(
        q, k, v, window=window) * g), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(plain(q, k, v, window) * g),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=OP_TOL * float(jnp.max(jnp.abs(b))), err_msg=name)


def test_the_backward_kernel_refuses_what_it_cannot_hold(monkeypatch):
    """Queries of a head group over more rows than VMEM holds: the
    gradient asked of it fails by name, before anything is compiled."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_VMEM_MOST", 1 << 20)
    monkeypatch.setattr(fa, "_forward_bytes", lambda *a: 0)
    q = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="backward holds"):
        jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention_grouped(
            q, k, v).astype(jnp.float32).sum()), q, kv, kv)


# ------------------------------------------------ the held experts' backward

def dense_held(x, router, wg, wu, wd, held, top_k):
    """The held experts' part of the layer, every token through every held
    expert, weighed by its renormalised softmax share there (0 where it did
    not pick the expert)."""
    p = jax.nn.softmax(jnp.matmul(x, router, precision="highest"), axis=-1)
    top, idx = jax.lax.top_k(p, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    t, e = p.shape
    w = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], idx].set(top)
    w = w[:, jnp.asarray(held)]
    h = jax.nn.silu(jnp.einsum("td,edi->eti", x, wg, precision="highest")) \
        * jnp.einsum("td,edi->eti", x, wu, precision="highest")
    o = jnp.einsum("eti,eid->etd", h, wd, precision="highest")
    return jnp.einsum("etd,te->td", o, w, precision="highest")


def experts(seed, t=40, d=16, i=8, e=8, n=4):
    rng = np.random.RandomState(seed)
    mk = lambda *s, scale=1.0: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) * scale, jnp.float32)
    return (mk(t, d), mk(d, e), mk(n, d, i, scale=0.3), mk(n, d, i, scale=0.3),
            mk(n, i, d, scale=0.3), mk(t, d))


@pytest.mark.parametrize("routing", ["spread", "all_to_expert_0"])
@pytest.mark.parametrize("chunk", [16, 2048])
def test_held_experts_backward_is_the_gradient_of_the_dense_layer(
        monkeypatch, routing, chunk):
    """`moe_ffn_held(trainable=True)`: the output and the gradients of x,
    the router and the three stacks against `jax.grad` of the dense held
    layer, its grouped pass over tiles of 16 picks (several, experts
    straddling them) and over one; with every token's first pick on
    expert 0 (its router column raised), so that one expert takes every
    token and nothing is dropped."""
    monkeypatch.setattr(moe, "_TRAIN_CHUNK_ROWS", chunk)
    held, k = (0, 2, 3, 5), 3
    x, router, wg, wu, wd, g = experts(7)
    if routing == "all_to_expert_0":
        router = router.at[:, 0].add(50.0)

    def prog(*a):
        return jnp.sum(moe.moe_ffn_held.raw(
            *a, held, top_k=k, score="softmax", trainable=True)[0] * g)

    def want(*a):
        return jnp.sum(dense_held(*a, held, k) * g)
    args = (x, router, wg, wu, wd)
    np.testing.assert_allclose(prog(*args), want(*args), rtol=1e-5)
    got = jax.grad(prog, argnums=range(5))(*args)
    ref = jax.grad(want, argnums=range(5))(*args)
    for a, b, name in zip(got, ref, ("x", "router", "gate", "up", "down")):
        np.testing.assert_allclose(
            a, b, atol=OP_TOL * float(jnp.max(jnp.abs(b))), err_msg=name)
    here = moe.moe_ffn_held.raw(*args, held, top_k=k, score="softmax",
                                trainable=True)[1]
    if routing == "all_to_expert_0":
        assert int(here) >= x.shape[0]      # every token's pick on 0 counted


def test_the_trainable_form_is_counted_by_its_own_label():
    from paddle_tpu.observability.metrics import get_registry
    x, router, wg, wu, wd, _ = experts(1, t=4)
    m = get_registry().get("moe_expert_form_total")
    before = {k[0]: v for k, v in m.samples()}
    jax.eval_shape(lambda *a: moe.moe_ffn_held.raw(
        *a, (0, 1, 2, 3), top_k=2, score="softmax", trainable=True),
        x, router, wg, wu, wd)
    after = {k[0]: v for k, v in m.samples()}
    assert after["grouped_kernel"] - before.get("grouped_kernel", 0) == 1
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if k != "grouped_kernel"} == {k: 0 for k in after
                                          if k != "grouped_kernel"}


KERNELS = ("_gate_up_kernel", "_down_kernel", "_gate_up_bwd_kernel",
           "_tgmm_kernel")


def test_each_grouped_kernels_body_is_traced_and_lowered_once_for_all_layers(
        monkeypatch):
    """A two-layer model's train step calls the grouped pass's kernels in
    every layer, forward and backward, with one signature a kernel: each
    body is traced once for all the layers (`tgmm` once for each of its
    two signatures, the gate's and up's gradients and the down's), as is
    the pass of two layers alone, whose lowered text holds each kernel as
    ONE function that both layers call."""
    traced = {name: 0 for name in KERNELS}
    for name in KERNELS:
        body = getattr(gm, name)
        monkeypatch.setattr(gm, name, lambda *a, _n=name, _b=body, **kw: (
            traced.__setitem__(_n, traced[_n] + 1), _b(*a, **kw))[1])
    # widths no other test traces the kernels at
    cfg = dict(tiny_cfg(hidden=96), num_hidden_layers=2,
               layer_types=["sliding_attention", "full_attention"])
    model = program(cfg)
    step = TrainStep(model, A.train_loss_fn(), paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters()))
    ids = np.random.RandomState(2).randint(0, 128, (2, 33)).astype(np.int32)
    step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    assert traced == {"_gate_up_kernel": 1, "_down_kernel": 1,
                      "_gate_up_bwd_kernel": 1, "_tgmm_kernel": 2}

    x, router, wg, wu, wd, g = experts(5, d=80)

    def two_layers(x, *w):
        for _ in range(2):
            x = x + moe.moe_ffn_held.raw(x, *w, (0, 2, 3, 5), top_k=3,
                                         score="softmax", trainable=True)[0]
        return jnp.sum(x * g)
    text = jax.jit(jax.value_and_grad(two_layers, argnums=range(5))).lower(
        x, router, wg, wu, wd).as_text()
    for fn in ("_gate_up", "_down", "_gate_up_bwd", "_tgmm"):
        assert len(re.findall(rf"func\.func private @{fn}(_\d+)?\(",
                              text)) == (2 if fn == "_tgmm" else 1), fn
        assert len(re.findall(rf"call @{fn}(_\d+)?\(", text)) == (
            6 if fn == "_tgmm" else 2), fn


# lowers a tiny model's forward in a fresh process and prints its text's hash
_LOWER_FORWARD = """
import hashlib, json, sys
import jax, jax.numpy as jnp
from paddle_tpu import models
cfg = models.Mellum2Config(**json.loads(sys.argv[1]))
model = models.Mellum2ForCausalLM(cfg)
text = jax.jit(lambda ids: model.forward(ids)[0]).lower(
    jnp.zeros((1, 8), jnp.int32)).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_the_forward_lowers_to_one_text_whatever_the_hash_seed():
    """The rotary tables are made in the order the layer kinds first
    appear: a set of them is ordered by string hashes, which differ from
    process to process, and the step's text, the compile cache's key,
    would then differ with them (a warm run compiles the step again)."""
    import json
    import subprocess
    kw = {k: v for k, v in tiny_cfg().items()
          if k not in ("num_experts_held", "rope_parameters")}
    texts = set()
    for seed in ("0", "1", "2", "3"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", _LOWER_FORWARD, json.dumps(kw)], env=env,
            cwd=ROOT, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        texts.add(out.stdout.split()[-1])
    assert len(texts) == 1


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their parts of the layer, and of the
    input's gradient, sum to the layer that holds all eight."""
    x, router, wg, wu, wd, g = experts(11, n=8)
    k = 3

    def part(held, x):
        sel = jnp.asarray(held)
        return moe.moe_ffn_held.raw(x, router, wg[sel], wu[sel], wd[sel],
                                    held, top_k=k, score="softmax",
                                    trainable=True)[0]
    shares = [(0, 1), (2, 3), (4, 5), (6, 7)]
    whole = part(tuple(range(8)), x)
    np.testing.assert_allclose(sum(part(s, x) for s in shares), whole,
                               atol=1e-5 * float(jnp.max(jnp.abs(whole))))
    grad = lambda held: jax.grad(  # noqa: E731
        lambda x: jnp.sum(part(held, x) * g))(x)
    gw = grad(tuple(range(8)))
    np.testing.assert_allclose(sum(grad(s) for s in shares), gw,
                               atol=1e-5 * float(jnp.max(jnp.abs(gw))))


# ------------------------------------------------------------------ YaRN

def test_yarn_frequencies_are_the_closed_form():
    """At the published numbers (theta 500000, factor 16, 8192 positions,
    beta 32 and 1, heads of 128): low 18, high 35, and inv_freq blends
    theta^(-2i/128) with a sixteenth of it by the ramp between them; the
    window layers' default frequencies are the extrapolated ones alone."""
    full = PUBLISHED_ROPE[M.FULL]
    assert M.yarn_range(full, 128) == (18, 35)
    inv, factor = M.rope_frequencies(full, 128)
    i = np.arange(64)
    extrap = 500000.0 ** (-2.0 * i / 128)
    m = 1.0 - np.clip((i - 18) / 17.0, 0.0, 1.0)
    np.testing.assert_allclose(inv, extrap / 16 * (1 - m) + extrap * m,
                               rtol=1e-5)
    assert factor == 1.2772588722239782
    np.testing.assert_allclose(inv[:18], extrap[:18], rtol=1e-5)
    np.testing.assert_allclose(inv[35:], extrap[35:] / 16, rtol=1e-5)
    inv_w, factor_w = M.rope_frequencies(PUBLISHED_ROPE[M.SLIDING], 128)
    np.testing.assert_allclose(inv_w, extrap, rtol=1e-5)
    assert factor_w == 1.0
    ref_inv, ref_factor = A.reference.frequencies(full, 128)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_factor == factor


def test_a_form_the_model_has_not_is_refused():
    from paddle_tpu.core.errors import InvalidArgumentError
    with pytest.raises(InvalidArgumentError, match="renormalises"):
        models.Mellum2Config(norm_topk_prob=False)
