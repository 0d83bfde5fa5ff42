"""The DeepSeek-V3-family decoder (`models/deepseek_v3.py`: latent attention
expanded for a prompt and absorbed for a decode step, a dense leading layer,
`noaux_tc`-routed experts beside a summed shared MLP) and the serving
engine's cache of two unequal leaves a layer, against the benchmark's plain reference
(`benchmark/reference/deepseek_v3.py`), at a tiny size on the CPU in float32
with seeded weights.

No share test: every expert of a layer is held here (`experts_held` is all of
them), so there are no parts held elsewhere to add up.

Tolerances, each with its reason:
  * 2e-5 on logits between the float32 program and the float32 reference:
    the same products in another order (logits are of size 1; float32 sums
    over 64 to 128 terms differ by a few 1e-6).  The absorbed step multiplies
    `kv_b_proj` into the query before the rows instead of into the rows, a
    re-association of the same float32 products, inside the same 2e-5.
  * a bfloat16 program reads 1e-2 or more on the same comparison (asserted
    above 50 x the tolerance): computing below the stated precision fails.
  * the decode kernel (`ops/latent_decode_attention.py`, through the
    interpreter) against the masked products it replaces, both with
    bfloat16 operands and float32 sums: the query is scaled before it is
    rounded where the products scale the score, and the kernel rounds a
    row's weight before the sum of weights divides, so an output of size 1
    differs by bfloat16's step, 2 ** -8: 2e-2 on the attention's output
    (readings to 5e-3), 0.15 on logits three dense layers later, each of
    which rounds the residual stream again (readings 0.05 to 0.09).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models, observability as obs
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights as W  # noqa: E402
from benchmark.arch import deepseek_v3 as A  # noqa: E402

pytestmark = [pytest.mark.serving]

REF = A.reference
TOL = 2e-5
TINY = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
            kv_lora_rank=24, n_routed_experts=8, num_experts_per_tok=3,
            n_shared_experts=2, routed_scaling_factor=2.446,
            rope_theta=50000, rms_norm_eps=1e-5)
MAX_LEN = 40
ROWS_MOST = 192     # `nn/functional/moe.py::_BATCHED_ROWS`
NAME, T0, DUR, TID, ID, PARENT, ARGS = range(7)


def dims(**over):
    return A.dims(dict(TINY, max_position_embeddings=MAX_LEN,
                       initializer_range=0.125,
                       e_score_correction_bias_std=0.2,
                       kv_a_proj_with_mqa_std=0.5, n_group=1,
                       topk_group=1, **over))


# the least widths the decode kernel takes: lanes of 128 and 64, 8 heads;
# every layer dense, because two bfloat16 programs that differ by rounding
# flip a routed layer's pick at a near tie and then differ by 0.4 of a logit
LANES = dict(num_attention_heads=8, kv_lora_rank=128, qk_rope_head_dim=64,
             first_k_dense_replace=3)
BLOCK = 16          # rows a block of the kernel in these tests
LANES_LEN = 48      # three blocks a slot
KERNEL_TOL, KERNEL_LOGIT_TOL = 2e-2, 0.15


def build(dtype="float32", seed=2147483659, **over):
    d = dims(**over)
    model = models.DeepseekV3ForCausalLM(models.DeepseekV3Config(
        **dict(TINY, **over), dtype=dtype))
    model.eval()
    top = dict(A.make_leaves(W.make, d, seed, -1))
    layers = [dict(A.make_leaves(W.make, d, seed, i)) for i in range(d["L"])]
    state = model.state_dict()
    for i, leaves in enumerate([top] + layers):
        for name, leaf in leaves.items():
            p = state[A.program_name(name, i - 1)]
            assert tuple(p.shape) == tuple(leaf.shape), name
            p._set_data(leaf.astype(p._data.dtype))
    return model, d, top, layers


@pytest.fixture(scope="module")
def tiny():
    return build()


def reference_logits(tiny, d=None):
    """The reference's logits over MAX_LEN positions, compiled once a set of
    sizes: under the causal mask what follows a position does not move it."""
    _, d0, top, layers = tiny
    d = d or d0
    fn = jax.jit(lambda ids: REF.logits(top, layers, ids, d))

    def padded(ids):
        row = np.zeros((MAX_LEN,), np.int32)
        row[:len(ids)] = np.asarray(ids, np.int32)
        return np.asarray(fn(jnp.asarray(row)))[:len(ids)]

    return padded


@pytest.fixture(scope="module")
def ref_logits(tiny):
    return reference_logits(tiny)


@pytest.fixture(scope="module")
def eng(tiny):
    e = ServingEngine(tiny[0], max_slots=3, max_len=MAX_LEN,
                      prefill_buckets=(4, 16), decode_chunk=4,
                      max_queue_depth=16)
    e.warmup()
    yield e
    e.close()


IDS = np.random.RandomState(0).randint(0, 128, (24,)).astype(np.int32)


# ------------------------------------------------------------------ model

def test_full_forward_logits_agree_with_the_reference(tiny, ref_logits):
    model, d, _, _ = tiny
    assert d["kinds"] == ["dense", "moe", "moe"]
    assert [blk.routed for blk in model.layers] == [False, True, True]
    got = np.asarray(model(paddle.to_tensor(IDS[None])).numpy())[0]
    assert got.dtype == np.float32
    assert np.max(np.abs(got - ref_logits(IDS))) < TOL
    names = set(model.state_dict())
    # leaves as the source names them
    assert {"embed_tokens", "norm", "lm_head",
            "layers.0.self_attn.kv_a_proj_with_mqa",
            "layers.0.self_attn.kv_a_layernorm",
            "layers.0.self_attn.kv_b_proj", "layers.0.mlp.down_proj",
            "layers.1.mlp.shared_experts.gate_proj",
            "layers.1.mlp.experts.e_score_correction_bias",
            "layers.2.post_attention_layernorm"} <= names
    assert "layers.0.mlp.experts.router" not in names


# what the reference reads when one detail of the layer is left out: each
# must move its logits by far more than the tolerance, so that the agreement
# above shows the program has the detail
DETAILS = {
    "selection_bias": lambda d, layers: (d, [
        dict(l, bias=jnp.zeros_like(l["bias"])) if "bias" in l else l
        for l in layers]),
    "routed_scaling_factor": lambda d, layers: (dict(d, scale=1.0), layers),
    "shared_expert_summed": lambda d, layers: (d, [
        dict(l, sd=l["sd"] / 2) if "sd" in l else l for l in layers]),
    "dense_first_layer": lambda d, layers: (d, [
        dict(l, wd=jnp.zeros_like(l["wd"])) if "wd" in l else l
        for l in layers]),
    "kv_a_layernorm": lambda d, layers: (d, [
        dict(l, kva_g=3.0 * l["kva_g"]) for l in layers]),
    "k_pe_rotated_once_for_all_heads": lambda d, layers: (
        dict(d, theta=1e30), layers),
}


@pytest.mark.parametrize("detail", sorted(DETAILS))
def test_each_detail_of_the_layer_moves_the_reference(tiny, ref_logits,
                                                      detail):
    _, d, top, layers = tiny
    d2, layers2 = DETAILS[detail](d, layers)
    moved = np.asarray(jax.jit(lambda ids: REF.logits(
        top, layers2, ids, d2))(jnp.asarray(IDS)))
    assert np.max(np.abs(moved - ref_logits(IDS))) > 100 * TOL


def test_the_bias_moves_a_pick_and_not_its_weight():
    """`F.moe_ffn_held` with a selection bias: the experts are the top k of
    score + bias, their weights the scores WITHOUT it over their sum, times
    the scale; `norm_topk_prob`: a token's weights sum to the scale."""
    from paddle_tpu.nn.functional import moe
    rng = np.random.RandomState(3)
    t, h, i, e, k = 6, 16, 8, 5, 2
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    router = jnp.asarray(rng.randn(h, e), jnp.float32)
    bias = jnp.asarray([0.0, 5.0, 0.0, 0.0, -5.0], jnp.float32)
    # experts whose output is their own id in every place: y reads the
    # weights straight off
    ones = jnp.ones((e, h, i), jnp.float32)
    down = jnp.ones((e, i, h), jnp.float32) * jnp.arange(
        1, e + 1, dtype=jnp.float32)[:, None, None]
    gate = ones * 1e3                   # silu(1e3 * sum x) ~ 1e3 * sum x
    held = tuple(range(e))

    def weights_of(**kw):
        """(T, E) weights read back from the layer's output."""
        out = []
        for only in range(e):
            mask = jnp.zeros((e, 1, 1)).at[only].set(1.0)
            y = moe.moe_ffn_held.raw(jnp.abs(x), router, gate * mask + 1e-9,
                                     ones * mask, down * mask, held, k,
                                     **kw)[0]
            u = jnp.abs(x).sum(-1)
            out.append(np.asarray(y[:, 0]) / np.asarray(
                1e3 * u * u * i * (only + 1)))
        return np.stack(out, axis=1)

    sa = np.asarray(jax.nn.sigmoid(jnp.abs(x) @ router))
    plain = weights_of()
    biased = weights_of(select_bias=bias, scale=2.5)
    for row in range(t):
        want = np.argsort(-(sa[row] + np.asarray(bias)))[:k]
        assert set(np.nonzero(biased[row] > 1e-6)[0]) == set(want)
        assert 1 in want and 4 not in want          # the bias chose
        np.testing.assert_allclose(
            biased[row][want], 2.5 * sa[row][want] / sa[row][want].sum(),
            rtol=2e-3)
        np.testing.assert_allclose(biased[row].sum(), 2.5, rtol=2e-3)
        np.testing.assert_allclose(plain[row].sum(), 1.0, rtol=2e-3)


def test_with_neither_bias_nor_scale_the_routed_call_traces_as_before():
    """`command-a-plus-1of8`'s call passes neither: its jaxpr holds no
    addition of a bias and no second multiplication of the shares."""
    from paddle_tpu.nn.functional import moe
    x = jnp.ones((4, 8), jnp.float32)
    r = jnp.ones((8, 4), jnp.float32)
    w = jnp.ones((2, 8, 4), jnp.float32)
    wd = jnp.ones((2, 4, 8), jnp.float32)
    plain = str(jax.make_jaxpr(lambda *a: moe.moe_ffn_held.raw(
        *a, (0, 1), 2))(x, r, w, w, wd))
    again = str(jax.make_jaxpr(lambda *a: moe.moe_ffn_held.raw(
        *a, (0, 1), 2, select_bias=None, scale=None))(x, r, w, w, wd))
    both = str(jax.make_jaxpr(lambda *a: moe.moe_ffn_held.raw(
        *a[:5], (0, 1), 2, select_bias=a[5], scale=2.0))(
            x, r, w, w, wd, jnp.zeros((4,))))
    assert plain == again != both


# of 8 experts, 3 a token: those held, whether a bias and a scale are given,
# valid rows of the 12 (None: all), an expert the bias keeps every token
# from (None: none)
FORM_CASES = {
    "all_held_with_bias_and_scale": (tuple(range(8)), True, None, None),
    "a_held_subset": ((6, 1, 4), False, None, None),
    "rows_masked": (tuple(range(8)), True, 7, None),
    "an_expert_no_token_picked": ((0, 2, 5, 7), True, None, 5),
}


@pytest.mark.parametrize("case", list(FORM_CASES))
def test_the_batched_form_is_the_grouped_form(monkeypatch, case):
    """Every row through every held expert, weighed by its share where it
    picked the expert and by 0 elsewhere, against the picks sorted into
    grouped products: the same `y` within the file's tolerance (the same
    float32 products summed in another order), the same picks here and
    experts hit, and no grouped product or row in the batched one."""
    from paddle_tpu.nn.functional import moe
    held, biased, n_valid, shunned = FORM_CASES[case]
    rng = np.random.RandomState(len(case))
    t, e, k, h, i = 12, 8, 3, 16, 8
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    router = jnp.asarray(rng.randn(h, e) * 0.5, jnp.float32)
    gate, up, down = (jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
                      for shape in ((len(held), h, i), (len(held), h, i),
                                    (len(held), i, h)))
    kw = {}
    if biased:
        bias = rng.randn(e) * 0.2
        if shunned is not None:
            bias[shunned] = -5.0
        kw = dict(select_bias=jnp.asarray(bias, jnp.float32), scale=2.446)
    if n_valid is not None:
        kw["valid"] = jnp.arange(t) < n_valid
    got = {}
    for form in ("grouped", "batched"):
        monkeypatch.setattr(moe, "_batched_form",
                            lambda *a, f=form: f == "batched")
        y, *counts = moe.moe_ffn_held.raw(x, router, gate, up, down, held, k,
                                          **kw)
        got[form] = np.asarray(y), [int(c) for c in counts]
    (y_g, (here_g, hit_g, products_g, rows_g)) = got["grouped"]
    (y_b, (here_b, hit_b, products_b, rows_b)) = got["batched"]
    assert np.max(np.abs(y_g)) > 0.1 and np.max(np.abs(y_b - y_g)) < TOL
    assert (here_b, hit_b) == (here_g, hit_g) and here_g > 0
    assert (products_g, rows_g) == (moe.GROUPED_PRODUCTS, t * k)
    assert (products_b, rows_b) == (0, 0)
    if len(held) == e:
        assert here_g == (t if n_valid is None else n_valid) * k
    else:                       # picks held elsewhere add nothing here
        assert here_g < t * k
    if n_valid is not None:
        assert not np.any(y_b[n_valid:]) and not np.any(y_g[n_valid:])
    if shunned is not None:
        assert hit_g == len(held) - 1


@pytest.mark.parametrize("t, top_k, n_experts, batched", [
    (48, 6, 64, True),        # Moonlight's decode step
    (16, 8, 128, False),      # command-a-plus's decode step
    (256, 6, 64, False),      # Moonlight's smallest prompt bucket
    (256, 8, 128, False),     # command-a-plus's smallest prompt bucket
    (ROWS_MOST, 6, 64, True), (ROWS_MOST + 1, 6, 64, False),
    # 64 experts are covered to 0.9 by 147 picks and not by 146
    (49, 3, 64, True), (73, 2, 64, False)])
def test_the_rule_that_chooses_the_form(t, top_k, n_experts, batched):
    """`_batched_form` at the shapes of the two routed cells' programs and
    at the edge of each of its two constants."""
    from paddle_tpu.nn.functional import moe
    assert (moe._BATCHED_COVER, moe._BATCHED_ROWS) == (0.9, ROWS_MOST)
    assert moe._batched_form(t, top_k, n_experts) is batched


def test_the_form_counts_itself_once_a_traced_call():
    from paddle_tpu.nn.functional import moe
    taken = obs.metrics.get_registry().get("moe_expert_form_total")
    before = {f: taken.value(form=f) for f in ("grouped", "batched")}
    x, r = jnp.ones((4, 8), jnp.float32), jnp.ones((8, 4), jnp.float32)
    w, wd = jnp.ones((2, 8, 4), jnp.float32), jnp.ones((2, 4, 8), jnp.float32)
    call = jax.jit(lambda x: moe.moe_ffn_held.raw(x, r, w, w, wd, (0, 1), 2))
    assert not moe._batched_form(4, 2, 4) and moe._batched_form(8, 2, 4)
    for _ in range(3):          # traced once, run three times
        call(x)
    call(jnp.ones((8, 8), jnp.float32))
    assert {f: taken.value(form=f) - before[f] for f in before} == {
        "grouped": 1, "batched": 1}


def test_absorbed_attention_equals_expanded(tiny):
    """One layer's attention: a sequence through the expanded path, then the
    same positions one at a time through the absorbed step against the rows
    the expanded path would cache."""
    model, d, _, _ = tiny
    attn = model.layers[1].self_attn
    rng = np.random.RandomState(5)
    s = 12
    h = jnp.asarray(rng.randn(s, d["H"]), jnp.float32)
    want, rows = attn.forward_seq(h)
    assert [r.shape for r in rows] == [(s, d["latent"]), (s, d["rope"])]
    bufs = [jnp.zeros((2, MAX_LEN, r.shape[1]), jnp.float32) for r in rows]
    for p in range(s):
        # slot 0 walks the sequence; slot 1 stays at position 0
        pos = jnp.asarray([p, 0], jnp.int32)
        got, *bufs, went_over = attn.forward_decode(
            jnp.stack([h[p], h[0]]), *bufs, pos)
        assert int(went_over) == 2 * MAX_LEN    # the masked products: all
        assert np.max(np.abs(np.asarray(got[0] - want[p]))) < TOL
        assert np.max(np.abs(np.asarray(got[1] - want[0]))) < TOL
    for buf, r in zip(bufs, rows):
        assert np.max(np.abs(np.asarray(buf[0, :s] - r))) < 1e-6
        assert not np.any(np.asarray(buf[0, s:]))


def test_prompt_attention_in_blocks_and_chunks_is_the_references(
        tiny, monkeypatch):
    """Off the chip the kernel refuses and `_attend_seq` takes the XLA form
    it shares with the Cohere model, here with a value narrower than a key
    and blocks and chunks that do not divide the length."""
    from paddle_tpu.models import cohere_moe
    monkeypatch.setattr(cohere_moe, "_QUERY_BLOCK", 8)
    monkeypatch.setattr(cohere_moe, "_KEY_CHUNK", 5)
    attn = tiny[0].layers[0].self_attn
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(21, 4, 24), jnp.float32)
    k = jnp.asarray(rng.randn(21, 4, 24), jnp.float32)
    v = jnp.asarray(rng.randn(21, 4, 12), jnp.float32)
    got = attn._attend_seq(q, k, v).reshape(21, -1)
    want = REF.attention(q, k, v, "float32")
    assert np.max(np.abs(np.asarray(got - want))) < TOL


def test_a_bfloat16_program_fails_the_float32_tolerance(ref_logits):
    model = build(dtype="bfloat16")[0]
    got = np.asarray(model(paddle.to_tensor(IDS[None])).numpy())[0]
    assert np.max(np.abs(got - ref_logits(IDS))) > 50 * TOL


def test_a_form_the_model_has_not_is_refused():
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("topk_method", "greedy"), ("norm_topk_prob", False)):
        with pytest.raises(InvalidArgumentError, match=key):
            models.DeepseekV3Config(**dict(TINY, **{key: value}))


# ------------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def lanes():
    """A bfloat16 model at the least widths the decode kernel takes."""
    return build("bfloat16", **LANES)


@pytest.fixture()
def kernel(monkeypatch):
    """The decode kernel through the interpreter, in blocks of BLOCK rows."""
    from paddle_tpu.ops import latent_decode_attention as K
    monkeypatch.setattr(K, "_INTERPRET", True)
    monkeypatch.setattr(K, "BLOCK_ROWS", BLOCK)
    return K


def decode_paths():
    taken = obs.metrics.get_registry().get("attention_path_total")
    return {p: taken.value(path="decode_" + p) for p in ("kernel", "xla")}


def by_hand(model, prompt, seq, max_len):
    """The model's two entries by hand over the cache protocol: the logits
    at the prompt's last position and at every later one of `seq`, and the
    counts of the prefill and of the last decode step."""
    padded = np.zeros((1, 16), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, rows, first = model.forward_prefill(
        paddle.to_tensor(padded), paddle.to_tensor(np.int32(len(prompt))))
    out = [np.asarray(logits, np.float32)[0, 0]]
    cache = [tuple(jnp.zeros((1, max_len, r.shape[2]), r.dtype).at[
        :, :16].set(jnp.where(jnp.arange(16)[None, :, None] < len(prompt),
                              r, 0)) for r in layer) for layer in rows]
    for p in range(len(prompt), len(seq)):
        logits, cache, counts = model.forward_decode(
            jnp.asarray([seq[p]]), cache, jnp.asarray([p], jnp.int32),
            jnp.asarray([True]))
        out.append(np.asarray(logits, np.float32)[0])
    return np.stack(out), np.asarray(first), np.asarray(counts)


REQUESTS = ((IDS[:11], 9), (IDS[5:8], 14), (IDS[2:18], 6))


def serve(engine):
    served = {tuple(prompt): engine.submit(list(prompt), n)
              for prompt, n in REQUESTS}
    while engine.has_work():
        engine.step()
    return {prompt: list(resp.tokens(5)) for prompt, resp in served.items()}


@pytest.mark.parametrize("path", ["masked_products", "kernel"])
def test_prefill_then_decode_through_the_cache_agrees_at_every_position(
        path, request):
    """Logits, not tokens: the prompt's last position from the prefill
    program, every later one from the absorbed decode step over the cache
    the engine holds.  `masked_products`: the float32 model, whose decode
    step the kernel refuses, against the reference's full forward.
    `kernel`: a bfloat16 model at lane-whole widths with the kernel run by
    the interpreter, against the SAME model with the kernel refused."""
    prompt = IDS[:11]
    if path == "masked_products":
        model = request.getfixturevalue("tiny")[0]
        ref_logits = request.getfixturevalue("ref_logits")
        cache = model.gen_fixed_cache(2, MAX_LEN)
        assert [[leaf.shape for leaf in layer] for layer in cache] == [
            [(2, MAX_LEN, 24), (2, MAX_LEN, 8)]] * 3
        before = decode_paths()
        served = serve(request.getfixturevalue("eng"))
        for p, toks in served.items():
            want = ref_logits(list(p) + toks)
            # the served tokens are the reference's own choices...
            assert toks == list(np.argmax(want[len(p) - 1:-1], axis=-1))
        # ...and the logits agree position by position
        seq = list(prompt) + served[tuple(prompt)]
        got, first, counts = by_hand(model, prompt, seq, MAX_LEN)
        assert np.max(np.abs(got - ref_logits(seq)[10:])) < TOL
        # 11 tokens x 3 picks x 2 routed layers; the cache's two counts:
        # the prompt's rows and the bucket's, a layer
        assert first.shape == (7,) and first[1] == 11 * 3 * 2
        assert first[0] == first[1] and list(first[5:]) == [11 * 3, 16 * 3]
        # a decode step's: the rows the slot holds, and the WHOLE pool
        assert list(counts[5:]) == [len(seq) * 3, MAX_LEN * 3]
        took = decode_paths()
        assert took["kernel"] == before["kernel"]
        assert took["xla"] > before["xla"]
        return
    model = request.getfixturevalue("lanes")[0]
    K = request.getfixturevalue("kernel")
    before = decode_paths()
    engine = ServingEngine(model, max_slots=3, max_len=LANES_LEN,
                           prefill_buckets=(4, 16), decode_chunk=4,
                           max_queue_depth=16)
    try:
        served = serve(engine)
    finally:
        engine.close()
    took = decode_paths()
    assert took["kernel"] - before["kernel"] == 3      # a layer, traced once
    assert took["xla"] == before["xla"]
    for p, toks in served.items():
        seq = list(p) + toks
        got, _, counts = by_hand(model, p, seq, LANES_LEN)
        K._INTERPRET = False         # the kernel refuses: masked products
        want, _, pool = by_hand(model, p, seq, LANES_LEN)
        K._INTERPRET = True
        assert np.max(np.abs(got - want)) < KERNEL_LOGIT_TOL
        # a served token is the masked products' choice, or a near tie
        assert all(want[i, t] > want[i].max() - KERNEL_LOGIT_TOL
                   for i, t in enumerate(toks))
        # what the last step went over: the slot's blocks up to its row on
        # the one path, the pool on the other; the rows it holds on both
        walked = -(-len(seq) // BLOCK) * BLOCK
        assert list(counts[5:]) == [len(seq) * 3, walked * 3]
        assert list(pool[5:]) == [len(seq) * 3, LANES_LEN * 3]


# ------------------------------------------------- the decode kernel alone

ROWS = 4 * BLOCK
RAGGED = {      # (pos, active) a slot
    "first_row": ([0] * 4, [True] * 4),
    "a_blocks_last_row": ([BLOCK - 1] * 4, [True] * 4),
    "a_blocks_first_row": ([BLOCK, 2 * BLOCK, 3 * BLOCK, BLOCK], [True] * 4),
    "the_pools_last_row": ([ROWS - 1] * 4, [True] * 4),
    "an_inactive_slot": ([2 * BLOCK + 3, ROWS - 1, 5, 7],
                         [True, False, True, False]),
    "every_slot_in_another_block": ([3, BLOCK + 5, 2 * BLOCK + 9,
                                     3 * BLOCK + 2], [True] * 4),
}


def masked_products(q_lat, q_pe, cbuf, pbuf, pos):
    """The model's own masked products over queries already scaled."""
    from paddle_tpu.models.deepseek_v3 import masked_latent_attention
    return masked_latent_attention(q_lat, q_pe, cbuf, pbuf, pos, 1.0,
                                   cbuf.dtype)


def kernel_inputs(rows=ROWS, heads=8, latent=128, rope=64,
                  dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    return (jax.random.normal(ks[0], (4, heads, latent), jnp.float32) * 0.3,
            jax.random.normal(ks[1], (4, heads, rope), jnp.float32) * 0.3,
            jax.random.normal(ks[2], (4, rows, latent), dtype),
            jax.random.normal(ks[3], (4, rows, rope), dtype))


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_the_decode_kernel_is_the_masked_products_over_ragged_positions(
        kernel, case):
    """Scores sharp enough that a row left out, a row too many or a block
    skipped would show (the weights are far from flat), each slot's walk
    ending in its own block."""
    pos, active = (jnp.asarray(x) for x in RAGGED[case])
    q_lat, q_pe, cbuf, pbuf = kernel_inputs()
    got, went_over = kernel.mla_decode_attention(q_lat, q_pe, cbuf, pbuf,
                                                 pos, active)
    want = masked_products(q_lat, q_pe, cbuf, pbuf, pos)
    live = np.asarray(active)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    assert np.max(np.abs(np.asarray(got - want))[live]) < KERNEL_TOL
    assert np.all(np.isfinite(np.asarray(got)))
    # an inactive slot costs one block, an active one its blocks up to pos
    blocks = np.where(live, np.asarray(pos) // BLOCK + 1, 1)
    assert int(went_over) == int(blocks.sum()) * BLOCK
    # a row past `pos` weighs nothing: garbage there changes no output
    dirty = jnp.where(jnp.arange(ROWS)[None, :, None] > pos[:, None, None],
                      jnp.asarray(1e4, cbuf.dtype), cbuf)
    again, _ = kernel.mla_decode_attention(q_lat, q_pe, dirty, pbuf, pos,
                                           active)
    assert np.array_equal(np.asarray(again)[live], np.asarray(got)[live])


REFUSED = {
    "float32_leaves": dict(dtype=jnp.float32),
    "rows_that_are_no_whole_blocks": dict(rows=ROWS + 8),
    "a_latent_that_fills_no_lanes": dict(latent=96),
    "a_rope_that_fills_no_half_lane": dict(rope=24),
    "heads_that_fill_no_sublanes": dict(heads=4),
}


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_a_shape_the_decode_kernel_refuses_takes_the_masked_products(
        kernel, why):
    pos, active = jnp.asarray([3, 9, 20, 40]), jnp.ones((4,), bool)
    assert kernel.mla_decode_attention(
        *kernel_inputs(**REFUSED[why]), pos, active) is None
    # and not on a TPU with no interpreter: whatever the shape
    kernel._INTERPRET = False
    assert kernel.mla_decode_attention(*kernel_inputs(), pos, active) is None


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_the_layer_counts_the_decode_form_it_took_and_what_it_went_over(
        lanes, kernel, path):
    """One layer's `forward_decode` at lane-whole widths: with the kernel,
    `attention_path_total{path="decode_kernel"}` and the walked blocks;
    over a pool of rows that are no whole blocks, `decode_xla` and the
    pool; the same output either way."""
    attn = lanes[0].layers[1].self_attn
    rows = ROWS if path == "kernel" else ROWS + 8
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(4, 64), jnp.bfloat16)
    bufs = [jnp.asarray(rng.randn(4, rows, w), jnp.bfloat16)
            for w in (128, 64)]
    pos = jnp.asarray([3, BLOCK + 5, 2 * BLOCK + 9, 3 * BLOCK + 2])
    active = jnp.asarray([True, True, False, True])
    before = decode_paths()
    got, _, _, went_over = attn.forward_decode(h, *bufs, pos, active)
    took = decode_paths()
    assert {p: took[p] - before[p] for p in took} == {
        "kernel": int(path == "kernel"), "xla": int(path == "xla")}
    assert int(went_over) == ((1 + 2 + 1 + 4) * BLOCK if path == "kernel"
                              else 4 * rows)
    kernel._INTERPRET = False
    want = attn.forward_decode(h, *bufs, pos, active)[0]
    live = np.asarray(active)
    assert np.max(np.abs(np.asarray(got - want))[live]) < KERNEL_TOL


def test_the_decode_kernels_body_is_traced_once_for_all_layers(
        lanes, kernel, monkeypatch):
    """Three layers call the kernel with one signature: its body is traced
    for the first and bound again for the others (pallas traces a body at
    every call; `flash_attention._traced_once` says what that cost)."""
    traced, body = [], kernel._kernel
    monkeypatch.setattr(kernel, "_kernel", lambda *a, **kw: (
        traced.append(1), body(*a, **kw))[1])
    model, slots, rows = lanes[0], 5, 5 * BLOCK   # a signature of its own
    jax.make_jaxpr(lambda tok, cache, pos, act: model.forward_decode(
        tok, cache, pos, act))(
        jnp.zeros((slots,), jnp.int32), model.gen_fixed_cache(slots, rows),
        jnp.arange(slots, dtype=jnp.int32) * 7, jnp.ones((slots,), bool))
    assert len(traced) == 1


def test_the_engine_gauges_latent_rows_and_records_the_cache_counts(
        tiny, eng):
    obs.get_tracer().clear()
    resp = eng.submit(list(IDS[:9]), 7)
    while eng.has_work():
        eng.step()
    assert len(resp.tokens(5)) == 7
    events = obs.get_tracer().events()
    admit = [ev[ARGS] for ev in events if ev[NAME] == "serving_admit"][-1]
    assert (admit["kv_rows_live"], admit["kv_rows_pool"]) == (9 * 3, 16 * 3)
    decodes = [ev[ARGS] for ev in events if ev[NAME] == "serving_decode"]
    assert decodes and all(
        a["kv_rows_pool"] == 4 * 3 * 3 * MAX_LEN for a in decodes)
    # the first call: rows 10, 11, 12, 13 seen by its four steps, 3 layers
    assert decodes[0]["kv_rows_live"] == 3 * (10 + 11 + 12 + 13)
    assert decodes[0]["routed_all"] == 4 * 3 * 2
    # the last decode call read its one request's rows, three layers of them
    rows = obs.metrics.get_registry().get("serving_kv_rows")
    assert 3 * 9 < rows.value(kind="latent") <= 3 * 16
    assert eng._leaf_kinds == [("latent", "latent")] * 3


def test_a_call_that_made_no_grouped_product_leaves_its_counts_out(
        tiny, eng):
    """Twelve slots x 3 picks cover the 8 experts, as a prompt's 16 rows
    do: that engine's programs take the batched form, its spans carry the
    routed and the cache counts but neither `expert_products` nor
    `expert_rows`, and `moe_expert_rows_total` stands still; the module's
    engine of three slots makes grouped products and says so."""
    from paddle_tpu.nn.functional import moe
    assert moe._batched_form(12, 3, 8) and not moe._batched_form(3, 3, 8)
    always = {"routed_here", "routed_all", "experts_hit", "kv_rows_live",
              "kv_rows_pool"}
    grouped_only = {"expert_products", "expert_rows"}
    rows = obs.metrics.get_registry().get("moe_expert_rows_total")

    def spans_of(engine):
        obs.get_tracer().clear()
        resp = engine.submit(list(IDS[:9]), 6)
        while engine.has_work():
            engine.step()
        assert len(resp.tokens(5)) == 6
        events = obs.get_tracer().events()
        return [[set(ev[ARGS]) for ev in events if ev[NAME] == name]
                for name in ("serving_admit", "serving_decode")]

    wide = ServingEngine(tiny[0], max_slots=12, max_len=MAX_LEN,
                         prefill_buckets=(16,), decode_chunk=4,
                         max_queue_depth=4)
    try:
        wide.warmup()
        before = rows.value()
        admits, decodes = spans_of(wide)
        assert rows.value() == before
    finally:
        wide.close()
    assert admits and decodes
    for args in admits + decodes:
        assert always <= args and not grouped_only & args
    before = rows.value()
    admits, decodes = spans_of(eng)
    assert rows.value() > before
    # the prompt of 9 rows fills the bucket of 16 (batched); a decode step
    # of three slots is grouped
    assert all(always <= a and not grouped_only & a for a in admits)
    assert decodes and all(always | grouped_only <= a for a in decodes)


@pytest.mark.parametrize("what, kw", [
    ("kv='paged'", dict(kv="paged")),
    ("draft_model=", dict(draft_model=object())),
    ("mesh=", dict(mesh=object())),
    ("lora=", dict(lora=object()))])
def test_what_is_not_built_for_a_batched_model_keeps_raising(tiny, what, kw):
    with pytest.raises(InvalidArgumentError) as err:
        ServingEngine(tiny[0], max_slots=2, max_len=MAX_LEN, **kw)
    assert what.split("=")[0] in str(err.value)


def test_snapshots_refuse_naming_what_is_no_pair(eng):
    for call in (lambda: eng.preempt_slot(0), lambda: eng.restore_run(None)):
        with pytest.raises(InvalidArgumentError, match="no `.k, v.` pair"):
            call()
