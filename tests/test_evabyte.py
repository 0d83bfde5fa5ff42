"""The EvaByte decoder (`models/evabyte.py`: EVA attention, one softmax over
a window's exact keys and one pooled summary a chunk of every earlier window)
and the serving engine's cache of FOUR leaves of two lengths and two clocks a
layer, against the benchmark's plain reference
(`benchmark/reference/evabyte.py`), at a tiny size on the CPU in float32 with
seeded weights: window 32, chunk 4, `max_len` 160, so the ring is 32 rows and
a summary leaf 40.

No share test: nothing of a layer is held elsewhere (no expert, head or row
of the vocabulary is cut), so there are no parts to add up.

Tolerances, each with its reason:
  * 5e-5 on logits between the float32 program and the float32 reference:
    the same products in another order (logits are of size 1; float32 sums
    over 64 to 160 terms differ by a few 1e-6, and the decode step pools a
    chunk from cached rows where the reference pools it from the sequence).
  * a bfloat16 program reads 1e-2 or more on the same comparison (asserted
    above 50 x the tolerance): computing below the stated precision fails.
  * a mechanism left out of the reference moves its logits, or one layer's
    attention, by more than 100 x the tolerance.
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
from paddle_tpu.serving.kv_pool import FixedKVView

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights as W  # noqa: E402
from benchmark.arch import evabyte as A  # noqa: E402

pytestmark = [pytest.mark.serving]

REF = A.reference
TOL = 5e-5
WINDOW, CHUNK, MAX_LEN = 32, 4, 160
TINY = dict(vocab_size=48, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, window_size=WINDOW, chunk_size=CHUNK,
            num_pred_heads=3, rope_theta=100000, rms_norm_eps=1e-5)
NAME, T0, DUR, TID, ID, PARENT, ARGS = range(7)


def dims():
    return A.dims(dict(TINY, initializer_range=0.125, qk_proj_std=0.3,
                       adaptive_phi_std=1.0, adaptive_mu_k_std=1.0))


def build(dtype="float32", seed=2147483659):
    d = dims()
    model = models.EvaByteForCausalLM(models.EvaByteConfig(
        **TINY, max_position_embeddings=MAX_LEN, dtype=dtype))
    model.eval()
    top = dict(A.make_leaves(W.make, d, seed, -1))
    layers = [dict(A.make_leaves(W.make, d, seed, i)) for i in range(d["L"])]
    # the benchmark draws the norms' offsets as 0; here they are not, so
    # that the unit offset shows
    rng = np.random.RandomState(7)
    for leaves in [top] + layers:
        for name in leaves:
            if name.endswith("_g"):
                leaves[name] = jnp.asarray(
                    0.3 * rng.randn(*leaves[name].shape), jnp.float32)
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


@pytest.fixture(scope="module")
def ref_logits(tiny):
    """The reference's logits over MAX_LEN positions, compiled once: under
    EVA's mask too, what follows a position does not move it."""
    _, d, top, layers = tiny
    fn = jax.jit(lambda ids: REF.logits(top, layers, ids, d))

    def padded(ids):
        row = np.zeros((MAX_LEN,), np.int32)
        row[:len(ids)] = np.asarray(ids, np.int32)
        return np.asarray(fn(jnp.asarray(row)))[:len(ids)]

    return padded


@pytest.fixture(scope="module")
def eng(tiny):
    e = ServingEngine(tiny[0], max_slots=3, max_len=MAX_LEN,
                      prefill_buckets=(8, 40, 96), decode_chunk=4,
                      max_queue_depth=16)
    e.warmup()
    yield e
    e.close()


IDS = np.random.RandomState(0).randint(0, 48, (MAX_LEN,)).astype(np.int32)


# ------------------------------------------------------------------ model

def test_full_forward_logits_agree_with_the_reference(tiny, ref_logits):
    model, d, _, _ = tiny
    ids = IDS[:110]             # three windows and 14 rows of a fourth
    got = np.asarray(model(paddle.to_tensor(ids[None])).numpy())[0]
    assert got.dtype == np.float32 and got.shape == (110, 3 * 48)
    # a served byte's logits are prediction head 0's: the first V columns
    assert np.max(np.abs(got[:, :48] - ref_logits(ids))) < TOL
    names = set(model.state_dict())
    # leaves as the source names them
    assert {"embed_tokens", "norm", "lm_head",
            "layers.0.self_attn.q_proj", "layers.0.self_attn.adaptive_phi",
            "layers.1.self_attn.adaptive_mu_k", "layers.0.mlp.gate_proj",
            "layers.1.post_attention_layernorm"} <= names
    assert model.state_dict()["lm_head"].shape == [64, 3 * 48]


# what the reference reads when one mechanism is left out of its leaves:
# each must move its logits by far more than the tolerance, so that the
# agreement above shows the program has the mechanism
LEFT_OUT = {
    "mu_k": lambda top, layers: (top, [
        dict(l, mu=jnp.zeros_like(l["mu"])) for l in layers]),
    # phi = 0: every row of a chunk weighs 1/4, the pooling is the mean
    "phi_pooling_is_not_the_mean": lambda top, layers: (top, [
        dict(l, phi=jnp.zeros_like(l["phi"])) for l in layers]),
    # x / rms(x) * g: the reference's 1 + g with g one less
    "norm_unit_offset": lambda top, layers: (
        dict(top, lnf_g=top["lnf_g"] - 1.0),
        [dict(l, ln1_g=l["ln1_g"] - 1.0, ln2_g=l["ln2_g"] - 1.0)
         for l in layers]),
}


@pytest.mark.parametrize("mechanism", sorted(LEFT_OUT))
def test_each_mechanism_left_out_moves_the_reference(tiny, ref_logits,
                                                     mechanism):
    _, d, top, layers = tiny
    top2, layers2 = LEFT_OUT[mechanism](top, layers)
    ids = IDS[:110]
    moved = np.asarray(jax.jit(lambda ids: REF.logits(
        top2, layers2, ids, d))(jnp.asarray(ids)))
    assert np.max(np.abs(moved - ref_logits(ids))) > 100 * TOL


def sets_attention(h, l, d, own_window_summaries=False,
                   earlier_exact_rows=False):
    """One layer's EVA attention with the query's two key sets written out
    a query at a time in float64: the definition, and with a flag set one
    of the two things it rules out."""
    s, heads, hd = h.shape[0], d["heads"], d["hd"]
    win, chunk = d["window"], d["chunk"]
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    q, k, v = (f(h) @ f(l[w]) for w in ("wq", "wk", "wv"))
    pos = jnp.arange(s)
    q, k = (f(REF.rope(jnp.asarray(a.reshape(s, heads, hd), jnp.float32),
                       pos, d["theta"])) for a in (q, k))
    v = v.reshape(s, heads, hd)
    scale = hd ** -0.5
    out = np.zeros((s, heads, hd))
    for head in range(heads):
        k_hat, v_hat = [], []
        for c0 in range(0, s - s % chunk, chunk):
            rows = slice(c0, c0 + chunk)
            a = np.exp(scale * k[rows, head] @ f(l["phi"])[head])
            a /= a.sum()
            k_hat.append(a @ k[rows, head] + f(l["mu"])[head])
            v_hat.append(a @ v[rows, head])
        for n in range(s):
            first = n // win * win
            exact = list(range(0 if earlier_exact_rows else first, n + 1))
            upto = (n // chunk if own_window_summaries else first // chunk)
            keys = np.stack([k[m, head] for m in exact] + k_hat[:upto])
            vals = np.stack([v[m, head] for m in exact] + v_hat[:upto])
            p = np.exp(scale * keys @ q[n, head])
            out[n, head] = p / p.sum() @ vals
    return out.reshape(s, -1) @ f(l["wo"])


def test_the_references_two_key_sets_are_the_definitions(tiny):
    """The reference's windowed form against the sets written out: one
    softmax over the window's exact rows and the summaries of every earlier
    window.  Seeing a summary of the query's own window, or an exact row of
    an earlier one, reads far off."""
    _, d, _, layers = tiny
    h = jnp.asarray(np.random.RandomState(5).randn(3 * WINDOW + 6, d["H"]),
                    jnp.float32)
    got = np.asarray(REF.eva(h, layers[0], d, "float32"))
    assert np.max(np.abs(got - sets_attention(h, layers[0], d))) < TOL
    for flag in ("own_window_summaries", "earlier_exact_rows"):
        off = sets_attention(h, layers[0], d, **{flag: True})
        assert np.max(np.abs(off - got)) > 100 * TOL
    # the first window has no earlier one to see exact rows of
    assert np.max(np.abs(off[:WINDOW] - got[:WINDOW])) < TOL


def test_below_one_window_the_layer_is_causal_softmax_attention(tiny):
    model, d, _, layers = tiny
    attn = model.layers[0].self_attn
    s = WINDOW - 3              # ends inside a chunk
    h = jnp.asarray(np.random.RandomState(6).randn(s, d["H"]), jnp.float32)
    l = layers[0]
    q, k, v = (np.asarray(h @ l[w]).reshape(s, d["heads"], -1)
               for w in ("wq", "wk", "wv"))
    q, k = (np.asarray(REF.rope(jnp.asarray(a), jnp.arange(s), d["theta"]))
            for a in (q, k))
    scores = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d["hd"])
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                     v).reshape(s, -1) @ np.asarray(l["wo"])
    got, rows = attn.forward_seq(h, s)
    assert np.max(np.abs(np.asarray(got) - want)) < TOL
    assert np.max(np.abs(np.asarray(REF.eva(h, l, d, "float32")) - want)
                  ) < TOL
    # what a cache would hold: the ring's rows as they lie, a row a chunk
    assert [r.shape[0] for r in rows] == [32, 32, 8, 8]


def test_a_decode_step_equals_the_prompts_form(tiny):
    """One layer's attention: a sequence of three windows and a half
    through the windowed form, then the same positions one at a time
    through the decode step against the ring and the summaries it writes."""
    model, d, _, _ = tiny
    attn = model.layers[1].self_attn
    s = 3 * WINDOW + 14
    h = jnp.asarray(np.random.RandomState(8).randn(s, d["H"]), jnp.float32)
    want, rows = attn.forward_seq(h, s)
    leaves = [jnp.zeros((2, n, d["heads"], d["hd"]), jnp.float32)
              for n in (WINDOW, WINDOW, MAX_LEN // CHUNK, MAX_LEN // CHUNK)]
    for p in range(s):
        # slot 0 walks the sequence; slot 1 stays at position 0
        got, leaves = attn.forward_decode(
            jnp.stack([h[p], h[0]]), *leaves, jnp.asarray([p, 0], jnp.int32))
        assert np.max(np.abs(np.asarray(got[0] - want[p]))) < TOL, p
        assert np.max(np.abs(np.asarray(got[1] - want[0]))) < TOL
    # the ring holds the last window's rows where the prompt's form lays
    # them, the summaries every finished chunk's
    for ring, r in zip(leaves[:2], rows[:2]):
        assert np.max(np.abs(np.asarray(ring[0, :14] - r[:14]))) < 1e-6
    done = s // CHUNK
    for summ, r in zip(leaves[2:], rows[2:]):
        assert np.max(np.abs(np.asarray(summ[0, :done] - r[:done]))) < TOL
        assert not np.any(np.asarray(summ[0, done + 1:]))


def test_a_bfloat16_program_fails_the_float32_tolerance(ref_logits):
    model = build(dtype="bfloat16")[0]
    ids = IDS[:70]
    got = np.asarray(model(paddle.to_tensor(ids[None])).numpy())[0, :, :48]
    assert np.max(np.abs(got - ref_logits(ids))) > 50 * TOL


def test_a_form_the_model_has_not_is_refused():
    for key, value in (("attention_class", "softmax"),
                       ("num_key_value_heads", 2),
                       ("rope_scaling", {"type": "linear"}),
                       ("norm_add_unit_offset", False)):
        with pytest.raises(InvalidArgumentError, match=key):
            models.EvaByteConfig(**dict(TINY, **{key: value}))
    with pytest.raises(InvalidArgumentError, match="straddle"):
        models.EvaByteConfig(**dict(TINY, chunk_size=5))


# ------------------------------------------------------------- the engine

# prompts that end inside a chunk (37), at a chunk's end (36: position 35
# closes chunk 8) and at a window's end (64), each decoded across at least
# two window boundaries
REQUESTS = ((37, 62), (36, 64), (64, 70))


@pytest.fixture(scope="module")
def served(eng):
    obs.get_tracer().clear()
    resps = [eng.submit(list(IDS[i:i + plen]), n)
             for i, (plen, n) in enumerate(REQUESTS)]
    while eng.has_work():
        eng.step()
    return resps, obs.get_tracer().events()


def test_the_cache_has_two_lengths_a_layer(tiny, eng):
    cache = tiny[0].gen_fixed_cache(2, MAX_LEN)
    rest = (4, 16)
    assert [[leaf.shape for leaf in layer] for layer in cache] == [
        [(2, 32) + rest, (2, 32) + rest, (2, 40) + rest, (2, 40) + rest]] * 2
    assert eng._leaf_rows == [(32, 32, 40, 40)] * 2
    assert eng.compile_counts()["total"] == eng.compile_counts()["bound"] == 4


def test_served_tokens_are_the_references_choices(served, ref_logits, eng):
    resps, _ = served
    assert eng.post_warmup_compiles() == 0
    for i, ((plen, n), resp) in enumerate(zip(REQUESTS, resps)):
        toks = list(resp.tokens(5))
        assert len(toks) == n
        want = ref_logits(list(IDS[i:i + plen]) + toks)
        assert toks == list(np.argmax(want[plen - 1:-1], axis=-1)), i


@pytest.mark.parametrize("which", range(len(REQUESTS)))
def test_prefill_then_decode_through_the_cache_agrees_at_every_position(
        tiny, served, ref_logits, which):
    """Logits, not tokens: the prompt's last position from the model's
    prefill entry, its leaves through the engine's own `write_prompt` into
    a pool, and every later position from the decode step over that pool,
    against the reference's full forward of the served sequence."""
    model = tiny[0]
    plen, n = REQUESTS[which]
    seq = list(IDS[which:which + plen]) + list(served[0][which].tokens(5))
    want = ref_logits(seq)
    bucket = 40 if plen <= 40 else 96
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = seq[:plen]
    logits, rows, counts = model.forward_prefill(
        paddle.to_tensor(padded), paddle.to_tensor(np.int32(plen)))
    assert np.max(np.abs(np.asarray(logits)[0, 0] - want[plen - 1])) < TOL
    # leaves no longer than the pool's: the ring in ring layout
    assert all(r.shape[1] <= leaf.shape[1] for layer, pool in zip(
        rows, model.gen_fixed_cache(1, MAX_LEN)) for r, leaf in zip(
            layer, pool))
    ring, summ = (plen - 1) % WINDOW + 1, (plen - 1) // WINDOW * 8
    windows = -(-bucket // WINDOW)
    assert list(np.asarray(counts)) == [
        2 * (ring + summ), 2 * (bucket + 8 * windows * (windows - 1) // 2),
        2 * summ]
    pools = model.gen_fixed_cache(2, MAX_LEN, "float32")
    pools = [tuple(leaf + 9.0 for leaf in layer) for layer in pools]
    cache = FixedKVView().write_prompt(pools, rows, {
        "slot": jnp.int32(1), "prompt_len": jnp.int32(plen)})
    step = jax.jit(lambda tok, cache, pos: model.forward_decode(
        tok, cache, pos, jnp.asarray([False, True])))
    for p in range(plen, len(seq)):
        logits, cache, counts = step(
            jnp.asarray([0, seq[p]]), cache, jnp.asarray([0, p], jnp.int32))
        assert np.max(np.abs(np.asarray(logits)[1] - want[p])) < TOL, p
    last = len(seq) - 1
    assert list(np.asarray(counts)) == [
        2 * (last % WINDOW + 1 + last // WINDOW * 8), 2 * 2 * (32 + 40),
        2 * (last // WINDOW * 8)]


def test_the_spans_carry_the_models_counts_under_its_names(served):
    _, events = served
    admits = [ev[ARGS] for ev in events if ev[NAME] == "serving_admit"]
    decodes = [ev[ARGS] for ev in events if ev[NAME] == "serving_decode"]
    assert len(admits) == 3 and decodes
    names = {"kv_rows_live", "kv_rows_pool", "kv_rows_summary"}
    for args in admits + decodes:
        assert names <= set(args)
        # no routed layer: nothing of the routed family's
        assert not {"routed_here", "routed_all", "experts_hit",
                    "expert_products", "expert_rows"} & set(args)
    by_plen = {a["plen"]: a for a in admits}
    # 37 rows: ring rows 0..4 of window 1 and window 0's 8 summaries, two
    # layers; the bucket of 96 is three windows: 96 rows + (0 + 8 + 16)
    assert (by_plen[37]["kv_rows_live"], by_plen[37]["kv_rows_summary"],
            by_plen[37]["kv_rows_pool"]) == (2 * (5 + 8), 2 * 8,
                                             2 * (40 + 8))
    assert by_plen[64]["kv_rows_live"] == 2 * (32 + 8)
    assert by_plen[64]["kv_rows_pool"] == 2 * (96 + 24)
    # a decode call's four steps go over the whole pool: 3 slots x (32 +
    # 40) rows x 2 layers
    assert all(a["kv_rows_pool"] == 4 * 3 * 72 * 2 for a in decodes)
    assert all(0 < a["kv_rows_summary"] < a["kv_rows_live"]
               < a["kv_rows_pool"] for a in decodes)
    # nothing a token, slot, layer or chunk
    assert {ev[NAME] for ev in events} <= {
        "serving_step", "serving_sweep", "serving_admit",
        "serving_prefill_dispatch", "serving_prefill_wait",
        "serving_decode", "serving_batch_rebuild", "serving_decode_dispatch",
        "serving_token_pull", "serving_deliver", "serving_queue_wait",
        "serving_request"}


def test_the_gauge_reads_what_the_model_says_a_slot_holds(tiny, eng):
    model = tiny[0]
    assert model.serving_rows_held(37) == {"window": 2 * 5, "summary": 2 * 8}
    assert model.serving_rows_held(64) == {"window": 2 * 32,
                                           "summary": 2 * 8}
    assert model.serving_rows_held(65) == {"window": 2 * 1,
                                           "summary": 2 * 16}
    resp = eng.submit(list(IDS[:70]), 9)
    while eng.has_work():
        eng.step()
    assert len(resp.tokens(5)) == 9
    rows = obs.metrics.get_registry().get("serving_kv_rows")
    # the last decode call began with 74 to 77 rows written: window 2
    assert 2 * (74 - 64) <= rows.value(kind="window") <= 2 * (78 - 64)
    assert rows.value(kind="summary") == 2 * 16


def test_the_prompts_attention_counts_its_path(tiny):
    taken = obs.metrics.get_registry().get("attention_path_total")
    before = taken.value(path="xla")
    tiny[0](paddle.to_tensor(IDS[None, :70]))
    # off the chip the kernel refuses: 3 windows x 2 layers
    assert taken.value(path="xla") - before == 6


def test_a_slot_recycled_from_a_longer_request_to_a_shorter(tiny,
                                                            ref_logits):
    """One slot: a tenant that leaves three windows of summaries and a full
    ring behind, then a short one that must see none of it."""
    e = ServingEngine(tiny[0], max_slots=1, max_len=MAX_LEN,
                      prefill_buckets=(8, 96), decode_chunk=4,
                      max_queue_depth=4)
    try:
        long_one = e.submit(list(IDS[:90]), 30)
        short = e.submit(list(IDS[100:106]), 40)
        while e.has_work():
            e.step()
        assert len(long_one.tokens(5)) == 30
        toks = list(short.tokens(5))
        want = ref_logits(list(IDS[100:106]) + toks)
        assert toks == list(np.argmax(want[5:-1], axis=-1))
        # 6 + 40 rows: window 1's ring rows 0..13 over window 0's, chunks
        # 0..11 written, nothing of the long tenant's 30 chunks beyond
        for layer in e._pools:
            assert not np.any(np.asarray(layer[2])[0, 12:])
            assert not np.any(np.asarray(layer[3])[0, 12:])
    finally:
        e.close()


@pytest.mark.parametrize("what, kw", [
    ("kv='paged'", dict(kv="paged")),
    ("prefix_cache", dict(prefix_cache=True)),
    ("draft_model=", dict(draft_model=object())),
    ("mesh=", dict(mesh=object())),
    ("lora=", dict(lora=object()))])
def test_what_is_not_built_for_a_batched_model_keeps_raising(tiny, what, kw):
    with pytest.raises(InvalidArgumentError) as err:
        ServingEngine(tiny[0], max_slots=2, max_len=MAX_LEN, **kw)
    assert what.split("=")[0] in str(err.value)
    if what in ("kv='paged'", "prefix_cache"):
        assert "summary" in str(err.value)


def test_snapshots_refuse_naming_a_summary_leaf(eng):
    for call in (lambda: eng.preempt_slot(0), lambda: eng.restore_run(None)):
        with pytest.raises(InvalidArgumentError, match="summary leaf"):
            call()
