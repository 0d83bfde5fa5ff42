"""Flash-attention kernel tests (pallas interpret mode on CPU).

Covers SURVEY.md §2.1 "Operators: fused" (the reference's
fused/multihead_matmul_op.cu): forward parity vs the naive softmax(QK^T)V,
backward parity vs jax.grad of the naive form, mask/causal/segment handling,
and in-kernel dropout (statistics, determinism, fwd/bwd consistency).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret():
    fa._INTERPRET = True
    yield
    fa._INTERPRET = False


@pytest.fixture(params=["written_out", "loop"])
def walk(request, monkeypatch):
    """Both walks over the blocks at test sizes: the written-out one short
    sequences take, and the `fori_loop` of sequences past `_WRITTEN_OUT`."""
    if request.param == "loop":
        monkeypatch.setattr(fa, "_WRITTEN_OUT", 0)
    return request.param


def naive(q, k, v, causal=False, bias=None, qseg=None, kseg=None):
    scale = 1.0 / np.sqrt(q.shape[-1])
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    if qseg is not None:
        ok = qseg[:, None, :, None] == kseg[:, None, None, :]
        s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


def rand_qkv(b=2, sq=256, sk=256, h=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(
        rng.standard_normal((b, s, h, d)).astype(np.float32)).astype(dtype)
    return mk(sq), mk(sq if sq == sk else sk), mk(sq if sq == sk else sk)


def counter_moves(name, trace):
    """{label: by how much} the registry's counter `name` moved over the
    call `trace()`, and what the call returned."""
    from paddle_tpu.observability.metrics import get_registry

    def counts():
        return {k[0]: v for k, v in get_registry().get(name).samples()}
    before = counts()
    out = trace()
    return {k: v - before.get(k, 0) for k, v in counts().items()
            if v != before.get(k, 0)}, out


def test_fwd_matches_naive():
    q, k, v = rand_qkv()
    out = fa.flash_attention_bshd(q, k, v)
    assert out is not None
    np.testing.assert_allclose(out, naive(q, k, v), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,h", [(384, 2), (1024, 4)])
def test_fwd_causal_multiblock(walk, s, h):
    # 384 forces 128-blocks (3 per axis) so the online-softmax carry is real;
    # 1024 with a head group of 4 is gpt2m-train's call in small
    q, k, v = rand_qkv(b=1, sq=s, sk=s, h=h)
    out = fa.flash_attention_bshd(q, k, v, causal=True)
    assert out is not None
    np.testing.assert_allclose(out, naive(q, k, v, causal=True),
                               rtol=2e-5, atol=2e-5)


def test_fwd_rectangular_causal(walk):
    # kv-cache decode shape: sq < sk with causal offset
    q, k, v = rand_qkv(sq=128, sk=384)
    out = fa.flash_attention_bshd(q, k, v, causal=True)
    assert out is not None
    np.testing.assert_allclose(out, naive(q, k, v, causal=True),
                               rtol=2e-5, atol=2e-5)


def test_fwd_padding_bias():
    q, k, v = rand_qkv()
    lengths = np.array([200, 120])
    bias = jnp.asarray(np.where(np.arange(256)[None, :] < lengths[:, None],
                                0.0, -1e30).astype(np.float32))
    out = fa.flash_attention_bshd(q, k, v, bias=bias)
    assert out is not None
    np.testing.assert_allclose(out, naive(q, k, v, bias=bias),
                               rtol=2e-5, atol=2e-5)


def test_fwd_segment_ids():
    q, k, v = rand_qkv()
    seg = jnp.asarray((np.arange(256)[None, :] // 64 +
                       np.array([[0], [10]])).astype(np.int32))
    out = fa.flash_attention_bshd(q, k, v, q_segment_ids=seg,
                                  kv_segment_ids=seg)
    assert out is not None
    np.testing.assert_allclose(out, naive(q, k, v, qseg=seg, kseg=seg),
                               rtol=2e-5, atol=2e-5)


def test_bf16_fwd():
    q, k, v = rand_qkv(dtype=jnp.bfloat16)
    out = fa.flash_attention_bshd(q, k, v, causal=True)
    assert out is not None and out.dtype == jnp.bfloat16
    ref = naive(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal,sq,sk,h", [
    (False, 256, 256, 2), (True, 256, 256, 2),
    (True, 1024, 1024, 4),      # gpt2m-train's call in small
    (False, 1024, 1024, 4),     # every block of several
    (True, 256, 640, 2),        # rectangular: the diagonal starts at 384
], ids=["full", "causal", "causal_1024", "full_1024", "causal_rect"])
def test_grad_matches_naive(walk, causal, sq, sk, h):
    q, k, v = rand_qkv(b=1, sq=sq, sk=sk, h=h)
    co = jnp.asarray(np.random.RandomState(1).standard_normal(
        (1, sq, h, 64)).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention_bshd(q, k, v, causal=causal) * co)

    def loss_naive(q, k, v):
        return jnp.sum(naive(q, k, v, causal=causal) * co)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_n = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal,s", [(False, 256), (True, 1024)])
def test_grad_with_bias_and_segments(walk, causal, s):
    q, k, v = rand_qkv(sq=s, sk=s)
    lengths = np.array([s, s * 5 // 8])
    bias = jnp.asarray(np.where(np.arange(s)[None, :] < lengths[:, None],
                                0.0, -1e30).astype(np.float32))
    seg = jnp.asarray((np.arange(s)[None, :] // (s // 2)).astype(np.int32)
                      * np.ones((2, 1), np.int32))
    co = jnp.asarray(np.random.RandomState(1).standard_normal(
        (2, s, 2, 64)).astype(np.float32))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * co)

    flash = loss(lambda q, k, v: fa.flash_attention_bshd(
        q, k, v, causal=causal, bias=bias, q_segment_ids=seg,
        kv_segment_ids=seg))
    ref = loss(lambda q, k, v: naive(q, k, v, causal=causal, bias=bias,
                                     qseg=seg, kseg=seg))
    g_f = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_n = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_bias_gradient():
    """A differentiable additive bias gets a real gradient through the flash
    path (not silent zeros)."""
    q, k, v = rand_qkv()
    bias = jnp.asarray(np.random.RandomState(3).standard_normal(
        (2, 256)).astype(np.float32))
    co = jnp.asarray(np.random.RandomState(1).standard_normal(
        (2, 256, 2, 64)).astype(np.float32))

    g_f = jax.grad(lambda b: jnp.sum(
        fa.flash_attention_bshd(q, k, v, bias=b) * co))(bias)
    g_n = jax.grad(lambda b: jnp.sum(naive(q, k, v, bias=b) * co))(bias)
    np.testing.assert_allclose(g_f, g_n, rtol=1e-4, atol=1e-4)


def test_segment_ids_must_be_paired():
    q, k, v = rand_qkv()
    seg = jnp.zeros((2, 256), jnp.int32)
    assert fa.flash_attention_bshd(q, k, v, kv_segment_ids=seg) is None
    assert fa.flash_attention_bshd(q, k, v, q_segment_ids=seg) is None


def test_dropout_statistics_and_determinism():
    q, k, v = rand_qkv()
    seed = jnp.asarray([1234], jnp.int32)
    out1 = fa.flash_attention_bshd(q, k, v, dropout_p=0.3, dropout_seed=seed)
    out2 = fa.flash_attention_bshd(q, k, v, dropout_p=0.3, dropout_seed=seed)
    out3 = fa.flash_attention_bshd(q, k, v, dropout_p=0.3,
                                   dropout_seed=jnp.asarray([99], jnp.int32))
    assert out1 is not None
    np.testing.assert_array_equal(out1, out2)  # same seed -> same mask
    assert float(jnp.max(jnp.abs(out1 - out3))) > 1e-4  # seed matters
    # dropout is unbiased: mean over seeds approaches the no-dropout output
    acc = jnp.zeros_like(out1)
    n = 24
    for s in range(n):
        acc = acc + fa.flash_attention_bshd(
            q, k, v, dropout_p=0.3, dropout_seed=jnp.asarray([s], jnp.int32))
    base = naive(q, k, v)
    err = float(jnp.mean(jnp.abs(acc / n - base)))
    scale = float(jnp.mean(jnp.abs(base)))
    assert err < 0.25 * scale


@pytest.mark.parametrize("causal,s", [(False, 128), (True, 1024)])
def test_dropout_grad_consistency(walk, causal, s):
    """vjp of the dropout kernel matches the directional numeric derivative,
    i.e. forward and backward regenerate the identical keep mask (at 1024
    under the causal mask they cut the scores into different blocks)."""
    q, k, v = rand_qkv(b=1, sq=s, sk=s, h=1)
    seed = jnp.asarray([7], jnp.int32)
    co = jnp.asarray(np.random.RandomState(1).standard_normal(
        (1, s, 1, 64)).astype(np.float32))
    tang = jnp.asarray(np.random.RandomState(2).standard_normal(
        q.shape).astype(np.float32))

    def f(q):
        return jnp.sum(fa.flash_attention_bshd(
            q, k, v, causal=causal, dropout_p=0.25, dropout_seed=seed) * co)

    g = jax.grad(f)(q)
    eps = 1e-3
    num = (f(q + eps * tang) - f(q - eps * tang)) / (2 * eps)
    ana = jnp.sum(g * tang)
    np.testing.assert_allclose(float(ana), float(num), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("s,causal,form", [
    (512, False, "one_block"),      # bertl-train's call
    (1024, False, "blocks"),
    (1024, True, "causal_blocks"),  # gpt2m-train's call
])
def test_form_counter_says_which_form_the_shapes_chose(s, causal, form):
    """`flash_attention_form_total{form}` is counted where the wrapper
    chooses, at trace time: tracing alone moves it, by one, for one form."""
    qkv = jax.ShapeDtypeStruct((4, s, 16, 64), jnp.bfloat16)
    moved, out = counter_moves(
        "flash_attention_form_total",
        lambda: jax.eval_shape(lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, causal=causal), qkv, qkv, qkv))
    assert out.shape == qkv.shape
    assert moved == {form: 1}


def plain_grouped(q, k, v, window=None):
    """Float32 softmax over each query's kept keys, the KV heads repeated:
    the queries are the last of the keys' positions, and a key is kept at
    or before the query's position and, under a window, less than `window`
    positions before it."""
    r = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x.astype(jnp.float32), r, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                   precision="highest") / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None] + k.shape[1] - q.shape[1]
    j = jnp.arange(k.shape[1])[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def grouped_qkv(s, r, hkv=1, d=128, dtype=jnp.float32, seed=0, sk=None):
    """q (1, s, hkv r, d) and k, v (1, sk, hkv, d); sk None: s."""
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, n, h, d)), dtype)
                 for n, h in ((s, hkv * r), (sk or s, hkv), (sk or s, hkv)))


# S = 128 is one block; 384 is three blocks of 128, walked written out as
# `_WRITTEN_OUT` stands and by `fori_loop`s with it patched under S
REGIMES = {"one_block": (128, None), "written_out": (384, None),
           "fori_loop": (384, 0)}
# a window absent, no multiple of a block, under, equal to and over S
WINDOWS = {"none": lambda s: None, "ragged": lambda s: 100,
           "under": lambda s: max(s - 128, 128) if s > 128 else 64,
           "equal": lambda s: s, "over": lambda s: s + 640}


@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("r", [1, 4, 16])
def test_grouped_windowed_forward_is_the_plain_softmax(monkeypatch, r,
                                                        regime, window):
    """`flash_attention_grouped`: `r` query heads share each of 2 KV heads
    (one for r = 16), read where they lie; under a window the walk starts
    at its far edge.  Against a float32 softmax over the kept keys."""
    s, written_out = REGIMES[regime]
    if written_out is not None:
        monkeypatch.setattr(fa, "_WRITTEN_OUT", written_out)
    w = WINDOWS[window](s)
    q, k, v = grouped_qkv(s, r, hkv=1 if r == 16 else 2, seed=r)
    out = fa.flash_attention_grouped(q, k, v, window=w)
    assert out is not None and out.shape == q.shape
    np.testing.assert_allclose(out, plain_grouped(q, k, v, w),
                               rtol=2e-5, atol=2e-5)


def test_grouped_forward_in_bfloat16_keeps_float32_softmax_state():
    q, k, v = grouped_qkv(384, 16, dtype=jnp.bfloat16)
    out = fa.flash_attention_grouped(q, k, v, window=200)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32),
                               plain_grouped(q, k, v, 200),
                               rtol=2e-2, atol=2e-2)


# (sq, sk, window): the queries are the last sq of sk positions.  512 rows
# or a multiple of 512 are walked as they are; a length past 512 that 512
# does not divide takes zero rows at its tail up to the next multiple
OFFSET_CASES = {
    "q512_k640": (512, 640, None),       # keys padded to 1024, diagonal 128
    "q512_k896": (512, 896, None),       # keys padded to 1024, diagonal 384
    "q1024_k1152": (1024, 1152, None),   # a window of EvaByte's in small
    "q640_k640": (640, 640, None),       # both padded, the diagonal at 0
    "q384_k640": (384, 640, None),       # 128-row q blocks, 512-row k blocks
    "q512_k1024": (512, 1024, None),     # no padding: the offset alone
    "q512_k896_window": (512, 896, 300),
}


@pytest.mark.parametrize("r", [1, 4])
@pytest.mark.parametrize("case", list(OFFSET_CASES))
def test_queries_at_the_end_of_longer_keys_are_the_plain_softmax(
        walk, case, r):
    """`flash_attention_grouped` with fewer queries than keys: query i sees
    the keys up to i + sk - sq, whatever rows the wrapper padded at the
    tail, on both walks.  Against a float32 softmax over the kept keys."""
    sq, sk, window = OFFSET_CASES[case]
    q, k, v = grouped_qkv(sq, r, hkv=1 if r == 1 else 2, seed=sk + r,
                          sk=sk)
    out = fa.flash_attention_grouped(q, k, v, window=window)
    assert out is not None and out.shape == q.shape
    np.testing.assert_allclose(out, plain_grouped(q, k, v, window),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,r,form", [
    (2048, 2176, 1, "padded_blocks"),    # EvaByte's window 1
    (2048, 2304, 1, "padded_blocks"),    # window 2
    (2048, 2560, 1, "causal_blocks"),    # window 4: 512 divides
    (640, 640, 1, "padded_blocks"),
    (640, 640, 16, "padded_blocks"),
    (512, 512, 1, "one_block"),
    (256, 256, 16, "grouped"),           # the smallest prompt buckets
    (2048, 2048, 16, "grouped"),
    (8192, 8192, 16, "grouped"),
])
def test_form_counter_says_where_the_tail_padding_engages(sq, sk, r, form):
    """A call whose lengths took rows at their tail counts `padded_blocks`
    and nothing else; lengths that 512 divides, and 512 or under, count
    their old forms alone."""
    hkv = 32 if r == 1 else 8
    q = jax.ShapeDtypeStruct((1, sq, hkv * r, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, sk, hkv, 128), jnp.bfloat16)
    moved, out = counter_moves(
        "flash_attention_form_total",
        lambda: jax.eval_shape(fa.flash_attention_grouped, q, kv, kv))
    assert out.shape == q.shape
    assert moved == {form: 1}


@pytest.mark.parametrize("r,s,window,form", [
    (16, 8192, None, "grouped"),            # a full layer, any bucket
    (16, 4096, 4096, "grouped"),            # the window holds the bucket
    (16, 8192, 4096, "grouped_window"),     # the 8192 bucket's window layers
    (1, 1024, 256, "window_blocks"),
    (1, 1024, None, "causal_blocks"),
])
def test_form_counter_names_the_grouped_and_windowed_forms(r, s, window,
                                                           form):
    """The routed model's prefill shapes (128 / 8 heads of 128) and what a
    window does to the form, counted at trace time."""
    q = jax.ShapeDtypeStruct((1, s, 8 * r, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, 8, 128), jnp.bfloat16)
    moved, out = counter_moves(
        "flash_attention_form_total",
        lambda: jax.eval_shape(lambda q, k, v: fa.flash_attention_grouped(
            q, k, v, window=window), q, kv, kv))
    assert out.shape == q.shape
    assert moved == {form: 1}


def test_grouped_forward_refuses_what_it_cannot_do():
    """Not a multiple of 128, query heads that do not divide, grouped heads
    narrower than a lane tile: None, so the caller keeps its XLA form.  A
    gradient asked of a call it takes is its backward's (tests/
    test_mellum2.py holds it to `jax.grad` of the plain softmax)."""
    q, k, v = grouped_qkv(128, 4, hkv=2)
    assert fa.flash_attention_grouped(q[:, :100], k[:, :100],
                                      v[:, :100]) is None
    assert fa.flash_attention_grouped(q[:, :, :3], k, v) is None
    assert fa.flash_attention_grouped(q[..., :64], k[..., :64],
                                      v[..., :64]) is None
    dq = jax.grad(lambda q: fa.flash_attention_grouped(q, k, v).sum())(q)
    assert dq.shape == q.shape and bool(jnp.all(jnp.isfinite(dq)))


def test_sdpa_keeps_refusing_grouped_heads_and_differentiates():
    """`F.scaled_dot_product_attention` is the trainable call: with fewer
    KV heads than query heads it takes the XLA form as before (the grouped
    form is `flash_attention_grouped`'s, causal only), and its gradient
    flows."""
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    q, k, v = (paddle.to_tensor(np.asarray(x), stop_gradient=False)
               for x in grouped_qkv(128, 4, hkv=1))
    moved, out = counter_moves(
        "attention_path_total",
        lambda: F.scaled_dot_product_attention(q, k, v, is_causal=True))
    assert moved == {"xla": 1}
    np.testing.assert_allclose(
        out.numpy(), plain_grouped(*(jnp.asarray(t.numpy())
                                     for t in (q, k, v))),
        rtol=2e-5, atol=2e-5)
    out.sum().backward()
    assert q.grad.shape == [1, 128, 4, 128]
    assert k.grad.shape == [1, 128, 1, 128]
    assert float(np.abs(k.grad.numpy()).sum()) > 0


def test_sdpa_routes_through_flash():
    """F.scaled_dot_product_attention with dropout and a padding mask must
    hit the flash kernel (the r1 gap: dropout/mask used to disqualify it)."""
    import paddle_tpu  # noqa: F401  (registers tensor type)
    from paddle_tpu.nn import functional as F
    from paddle_tpu.core.tensor import Tensor

    calls = {"n": 0}
    orig = fa.flash_attention_bshd

    def spy(*a, **kw):
        out = orig(*a, **kw)
        if out is not None:
            calls["n"] += 1
        return out

    fa.flash_attention_bshd, saved = spy, orig
    try:
        q = Tensor(rand_qkv()[0])
        mask = Tensor(jnp.ones((2, 1, 1, 256), jnp.float32) * 0.0)
        out = F.scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                             dropout_p=0.1, training=True)
        assert calls["n"] == 1
        assert out.shape == [2, 256, 2, 64]
    finally:
        fa.flash_attention_bshd = saved


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "tp": 2},
                                  {"dp": 3}, {"sp": 2}])
def test_kernel_under_a_mesh_matches_one_device(axes):
    """Inside a GSPMD program a Mosaic kernel cannot be partitioned by XLA:
    traced under jax's mesh context (`jax.set_mesh`) the kernel wraps
    itself in a shard_map (batch rows over dp; rows that do not divide, and
    any other axis, see the whole operands).  Forward and gradients must
    equal the unpartitioned kernel, padding bias and segments included."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(axes)
    q, k, v = rand_qkv(b=4, h=2)
    bias = jnp.where(jnp.arange(256)[None, :] < jnp.asarray(
        [[256], [200], [131], [256]]), 0.0, -1e30).astype(jnp.float32)
    seg = jnp.asarray(np.repeat(np.arange(4), 64)[None].repeat(4, 0),
                      jnp.int32)

    def loss(q, k, v, bias):
        return (fa.flash_attention_bshd(
            q, k, v, causal=True, bias=bias, q_segment_ids=seg,
            kv_segment_ids=seg) ** 2).sum()

    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
        q, k, v, bias)
    rows = NamedSharding(mesh, P("dp") if axes.get("dp", 1) in (2, 4)
                         else P())
    with jax.set_mesh(mesh):
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
        args = [jax.device_put(x, rows) for x in (q, k, v, bias)]
        assert "shard_map" in str(jax.make_jaxpr(step)(*args))
        got = step(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_kernel_inside_a_manual_shard_map_is_not_wrapped_again():
    """`ShardedTrainStep(fp16_allreduce)` runs the model inside its own
    shard_map: every mesh axis is manual there, the kernel sees its local
    rows and must run as it is."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.mesh import create_mesh
    q, k, v = rand_qkv(b=4, h=2)

    def attend(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)
    want = jax.jit(attend)(q, k, v)
    with jax.set_mesh(create_mesh({"dp": 4})):
        local = jax.jit(jax.shard_map(attend, in_specs=P("dp"),
                                      out_specs=P("dp"), check_vma=False))
        assert str(jax.make_jaxpr(local)(q, k, v)).count("shard_map") == 1
        got = local(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_partitioned_dropout_masks_differ_between_shards():
    """Each shard folds its mesh position into the seed: two devices that
    hold identical rows must not draw the same keep mask."""
    from paddle_tpu.parallel.mesh import create_mesh
    q0 = jnp.zeros((2, 256, 2, 64), jnp.float32)
    v1 = jnp.ones((2, 256, 2, 64), jnp.float32)
    seed = jnp.asarray([7], jnp.int32)

    def run(q, v):
        return fa.flash_attention_bshd(q, q, v, dropout_p=0.5,
                                       dropout_seed=seed)
    with jax.set_mesh(create_mesh({"dp": 2})):
        out = np.asarray(jax.jit(run)(q0, v1))
    assert abs(out.mean() - 1.0) < 0.05
    assert not np.array_equal(out[0], out[1])


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="hardware PRNG dropout path needs a real TPU")
@pytest.mark.parametrize("S,causal", [(256, False), (1024, True)])
def test_hw_prng_dropout_fwd_bwd_consistency_on_tpu(monkeypatch, S, causal):
    """On-device validation of the hardware bit-source (compiled, not the
    interpreter's hash): determinism, keep fraction, and fwd/bwd mask
    agreement.  With q = k = 0 and v = 1 in float32 every kept probability
    reaches both sum_q o[q] and sum_k dv[k], so the two are equal to
    rounding iff the passes drew one mask (at 1024 under the causal mask
    they cut the scores into different blocks); two masks put them ~1e-3
    apart."""
    monkeypatch.setattr(fa, "_INTERPRET", False)
    B, Hh, D = 2, 4, 64
    q0 = jnp.zeros((B, S, Hh, D), jnp.float32)
    v1 = jnp.ones((B, S, Hh, D), jnp.float32)
    seed = jnp.asarray([7], jnp.int32)

    def f(v):
        return fa.flash_attention_bshd(q0, q0, v, causal=causal,
                                       dropout_p=0.5, dropout_seed=seed)
    o1, o2 = f(v1), f(v1)
    assert bool(jnp.all(o1 == o2))
    assert abs(float(jnp.mean(o1)) / 2.0 - 0.5) < 0.01
    dv = jax.grad(lambda v: f(v).sum())(v1)
    so = np.asarray(o1, np.float64).sum(axis=1)
    sdv = np.asarray(dv, np.float64).sum(axis=1)
    assert np.abs(so - sdv).max() / so.max() < 2e-4
