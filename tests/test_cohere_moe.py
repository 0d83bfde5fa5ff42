"""The Cohere2-MoE decoder (`models/cohere_moe.py`), the expert layer that is
told which experts it holds (`nn.HeldExperts`, `F.moe_ffn_held`) and the
serving engine's batched decode over a pool whose leaves differ by layer,
against the benchmark's plain reference (`benchmark/reference/
cohere2_moe.py`), at a tiny size on the CPU in float32 with seeded weights.
"""
import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models, nn, observability as obs
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import moe
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights as W  # noqa: E402
from benchmark.arch import cohere2_moe as A  # noqa: E402

pytestmark = [pytest.mark.serving]

REF = A.reference
WINDOW = 8
TINY = dict(vocab_size=128, hidden_size=64, intermediate_size=32,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, sliding_window=WINDOW,
            num_experts=8, num_experts_per_tok=2, num_shared_experts=2,
            dtype="float32")
NAME, T0, DUR, TID, ID, PARENT, ARGS = range(7)


def dims(held, tiny=None):
    cfg = dict(tiny or TINY, layer_types=["sliding_attention"] * 3
               + ["full_attention"], rope_theta=50000, layer_norm_eps=1e-5,
               logit_scale=1, initializer_range=0.125,
               experts_held=list(held))
    return A.dims(cfg)


def weights(d, seed):
    """(top, [one layer's leaves]) made the way the benchmark makes them."""
    return (dict(A.make_leaves(W.make, d, seed, -1)),
            [dict(A.make_leaves(W.make, d, seed, i)) for i in range(d["L"])])


def build(held=(1, 4, 6), seed=2147483659, tiny=None):
    d = dims(held, tiny)
    model = models.CohereMoEForCausalLM(models.CohereMoEConfig(
        **(tiny or TINY), experts_held=held))
    model.eval()
    top, layers = weights(d, seed)
    state = model.state_dict()
    for i, leaves in enumerate([top] + layers):
        for name, leaf in leaves.items():
            state[A.program_name(name, i - 1)]._set_data(leaf)
    return model, d, top, layers


MAX_LEN = 40


@pytest.fixture(scope="module")
def tiny():
    return build()


@pytest.fixture(scope="module")
def ref_logits(tiny):
    """The reference's logits over MAX_LEN positions, compiled once: under
    the causal mask what follows a position does not move it, so every
    comparison pads its ids to that length."""
    _, d, top, layers = tiny
    fn = jax.jit(lambda ids: REF.logits(top, layers, ids, d))

    def padded(ids):
        ids = np.asarray(ids, np.int32)
        row = np.zeros((MAX_LEN,), np.int32)
        row[:len(ids)] = ids
        return np.asarray(fn(jnp.asarray(row)))[:len(ids)]

    return padded


@pytest.fixture(scope="module")
def eng(tiny):
    """One warmed engine for the module: three slots, buckets of 4 and 16
    (the second longer than the window of 8)."""
    e = ServingEngine(tiny[0], max_slots=3, max_len=MAX_LEN,
                      prefill_buckets=(4, 16), decode_chunk=4,
                      max_queue_depth=16)
    e.warmup()
    yield e
    e.close()


# ------------------------------------------------------------------ model

@pytest.mark.parametrize("chunk_rows", [0, None, 16],
                         ids=["batched", "one_chunk", "chunks_of_16"])
def test_logits_agree_with_the_reference(tiny, ref_logits, monkeypatch,
                                         chunk_rows):
    """24 tokens x 2 picks a layer cover the 8 experts, so the rule takes
    the batched form; with the grouped form in its place: one chunk as
    `_CHUNK_ROWS` stands, and the walk in chunks (a trip count the device
    computes) with it patched under the picks."""
    assert moe._batched_form(24, 2, 8)
    if chunk_rows != 0:
        monkeypatch.setattr(moe, "_batched_form", lambda *a: False)
    if chunk_rows:
        monkeypatch.setattr(moe, "_CHUNK_ROWS", chunk_rows)
    model, d, top, layers = tiny
    assert model.config.layer_types == d["kinds"]
    ids = np.random.RandomState(0).randint(0, 128, (1, 24)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids)).numpy())[0]
    want = ref_logits(ids[0])
    assert np.max(np.abs(got - want)) < 2e-5
    # 24 positions pass the window of 8, and window layers rotate where
    # full layers do not: the reference reads both
    for other in (dict(d, window=64), dict(d, kinds=["full_attention"] * 4)):
        moved = np.asarray(jax.jit(lambda ids: REF.logits(
            top, layers, ids, other))(jnp.asarray(ids[0])))
        assert np.max(np.abs(moved - want)) > 1e-3


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_prefill_attention_in_blocks_and_chunks_is_the_references(
        tiny, monkeypatch, kind):
    """Queries a block, keys a chunk under a running maximum and sum, with
    blocks and chunks that divide neither the length nor the window."""
    from paddle_tpu.models import cohere_moe
    monkeypatch.setattr(cohere_moe, "_QUERY_BLOCK", 8)
    monkeypatch.setattr(cohere_moe, "_KEY_CHUNK", 5)
    blk = [b for b in tiny[0].layers if b.kind == kind][0]
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(37, heads, 16), jnp.float32)
               for heads in (8, 2, 2))
    want = REF.attention(q, k, v, WINDOW if kind == "sliding_attention"
                         else None, "float32")
    np.testing.assert_allclose(jax.jit(blk._attend_seq)(q, k, v), want,
                               atol=2e-6)


def test_a_kernel_sized_prompt_goes_through_the_flash_kernel(monkeypatch):
    """Heads of 128 lanes, 4 query heads on 2 KV heads, a prompt bucket of
    128 rows with a window of 48 inside it: every layer's prefill attention
    takes `flash_attention_grouped` (interpreter), the logits are the
    reference's, and the engine's prefill then decode follow the reference
    at every position."""
    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_INTERPRET", True)

    def counts(name):
        return {k[0]: v for k, v in get_registry().get(name).samples()}
    wide = dict(TINY, num_attention_heads=4, head_dim=128, sliding_window=48)
    model, d, top, layers = build(tiny=wide)
    bucket, max_len, plen, out = 128, 144, 120, 12
    ref = jax.jit(lambda ids: REF.logits(top, layers, ids, d))
    rng = np.random.RandomState(7)

    paths, forms = counts("attention_path_total"), counts(
        "flash_attention_form_total")
    ids = rng.randint(0, 128, (1, bucket)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids)).numpy())[0]
    assert counts("attention_path_total").get("flash", 0) \
        - paths.get("flash", 0) == 4
    assert counts("attention_path_total").get("xla", 0) == paths.get("xla", 0)
    moved = {k: v - forms.get(k, 0)
             for k, v in counts("flash_attention_form_total").items()}
    assert moved.get("grouped_window") == 3 and moved.get("grouped") == 1
    assert np.max(np.abs(got - np.asarray(ref(jnp.asarray(ids[0]))))) < 2e-5

    e = ServingEngine(model, max_slots=2, max_len=max_len,
                      prefill_buckets=(bucket,), decode_chunk=4,
                      max_queue_depth=4)
    try:
        e.warmup()
        prompt = rng.randint(0, 128, plen).astype(np.int32)
        resp = e.submit(prompt, out)
        e.run_until_drained(timeout=120)
        toks = resp.tokens()
        assert len(toks) == out and e.post_warmup_compiles() == 0
    finally:
        e.close()
    row = np.zeros((max_len,), np.int32)
    row[:plen + out] = np.concatenate([prompt, toks])
    want = np.asarray(jax.nn.log_softmax(
        ref(jnp.asarray(row)), axis=-1))[plen - 1:plen + out - 1]
    assert [int(np.argmax(r)) for r in want] == list(toks)
    assert abs(resp.logprob - want[np.arange(out), toks].sum()) < 1e-4


def test_rope_turns_adjacent_pairs_and_leaves_position_0():
    q = jnp.asarray(np.random.RandomState(1).randn(5, 2, 16), jnp.float32)
    rot = REF.rope_gptj(q, jnp.arange(5), 50000.0)
    pair = lambda x: np.asarray(x).reshape(5, 2, 8, 2)  # noqa: E731
    np.testing.assert_allclose(np.linalg.norm(pair(rot), axis=-1),
                               np.linalg.norm(pair(q), axis=-1), rtol=1e-5)
    np.testing.assert_allclose(rot[0], q[0], rtol=1e-6)
    # pair i of position 1 turns by theta ** (-2i / 16)
    ang = 50000.0 ** (-np.arange(8) / 8.0)
    a, b = pair(q)[1, 0, :, 0], pair(q)[1, 0, :, 1]
    np.testing.assert_allclose(pair(rot)[1, 0, :, 0],
                               a * np.cos(ang) - b * np.sin(ang), atol=1e-6)


# ------------------------------------------------------- the expert layer

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """The guide's share test: the routed parts of all 4 shares of 2
    experts, plus the shared experts once, are the uncut layer's FFN."""
    d_all = dims(range(8))
    _, layers = weights(d_all, 11)
    l = layers[0]
    h = jnp.asarray(np.random.RandomState(2).randn(40, 64), jnp.float32)
    want = np.asarray(REF.ffn(h, l, d_all, "float32"))
    routed, here, hit = [], 0, 0
    for share in ((0, 1), (2, 3), (4, 5), (6, 7)):
        take = jnp.asarray(share)
        y, n_here, n_hit, _, _ = F.moe_ffn_held(
            h, l["router"], l["eg"][take], l["eu"][take], l["ed"][take],
            share, top_k=2)
        routed.append(np.asarray(y))
        here, hit = here + int(n_here), hit + int(n_hit)
        # each share alone is the reference's share
        cut = {**l, "eg": l["eg"][take], "eu": l["eu"][take],
               "ed": l["ed"][take]}
        shared_too = np.asarray(REF.ffn(h, cut, dims(share), "float32"))
        if share == (0, 1):
            shared = shared_too - routed[-1]
        np.testing.assert_allclose(routed[-1] + shared, shared_too,
                                   atol=2e-6)
    # every pick fell on exactly one share, and nothing was dropped
    assert here == 40 * 2 and 0 < hit <= 8
    np.testing.assert_allclose(sum(routed) + shared, want, atol=3e-6)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """The capacity-and-drop layer loses tokens here; this one must not."""
    rng = np.random.RandomState(3)
    t, h, i = 48, 16, 8
    x = jnp.asarray(np.abs(rng.randn(t, h)), jnp.float32)
    router = np.zeros((h, 6), np.float32)
    router[:, 3], router[:, 5] = 1.0, 0.5          # every token: 3, then 5
    gate, up, down = (jnp.asarray(rng.randn(*s), jnp.float32) * 0.3
                      for s in ((2, h, i), (2, h, i), (2, i, h)))
    layer = nn.HeldExperts(h, i, 6, 2, experts_held=(5, 3), dtype="float32")
    for p, v in zip((layer.router, layer.gate, layer.up, layer.down),
                    (jnp.asarray(router), gate, up, down)):
        p._set_data(v)
    y, here, hit, products, rows = layer(paddle.to_tensor(x))
    assert (int(here.numpy()), int(hit.numpy())) == (t * 2, 2)
    # 96 picks are expected to cover 6 experts: the batched form, whose
    # products are not grouped ones
    assert moe._batched_form(t, 2, 6)
    assert (int(products.numpy()), int(rows.numpy())) == (0, 0)
    s = jax.nn.sigmoid(x @ router)
    w = s[:, [5, 3]] / jnp.sum(s[:, [5, 3]], -1, keepdims=True)
    want = sum(w[:, k:k + 1] * ((jax.nn.silu(x @ gate[k]) * (x @ up[k]))
                                @ down[k]) for k in range(2))
    np.testing.assert_allclose(np.asarray(y.numpy()), np.asarray(want),
                               atol=1e-5)
    assert np.all(np.abs(np.asarray(y.numpy())).sum(-1) > 0)
    # rows marked not valid are routed nowhere
    valid = jnp.arange(t) < 10
    y2, here2, *_ = F.moe_ffn_held(x, jnp.asarray(router), gate, up, down,
                                   (5, 3), top_k=2, valid=valid)
    assert int(here2) == 20 and not np.any(np.asarray(y2)[10:])
    with pytest.raises(ValueError):
        nn.HeldExperts(h, i, 6, 2, experts_held=(5, 5))


@pytest.mark.parametrize("key, value", [("expert_selection_fn", "softmax"),
                                        ("norm_topk_prob", False)])
def test_the_one_form_of_routing_that_is_built_is_the_one_accepted(key,
                                                                    value):
    """The source's keys stay in the config; a value the routed layer and
    the reference do not compute raises and is not silently ignored."""
    cfg = models.CohereMoEConfig(**TINY)
    assert (cfg.expert_selection_fn, cfg.norm_topk_prob) == ("sigmoid", True)
    with pytest.raises(InvalidArgumentError, match=key):
        models.CohereMoEConfig(**TINY, **{key: value})


def plain_held(x, router, gate, up, down, held, top_k, valid):
    """A loop over the tokens and over each token's picks that fell on a
    held expert, in float64: (y, picks here, held experts hit)."""
    x, router, gate, up, down = (np.asarray(a, np.float64)
                                 for a in (x, router, gate, up, down))
    y, here, hit = np.zeros_like(x), 0, set()
    for t in np.flatnonzero(valid):
        score = 1.0 / (1.0 + np.exp(-(x[t] @ router)))
        picks = np.argsort(-score, kind="stable")[:top_k]
        for e in picks:
            if e not in held:
                continue
            k = held.index(e)
            g = x[t] @ gate[k]
            y[t] += (score[e] / score[picks].sum()) * (
                (g / (1.0 + np.exp(-g)) * (x[t] @ up[k])) @ down[k])
            here += 1
            hit.add(e)
    return y, here, len(hit)


# tokens, held experts (of 6, two a token), rows a chunk, experts every
# token is steered to (None: wherever the router's draw sends it), tokens
# that are valid (None: all)
HELD_CASES = {
    "picks_below_a_chunk": (6, (1, 4), 16, None, None),
    "one_chunk_exactly_full": (8, (3,), 8, (3, 5), None),
    "an_expert_straddles_two_chunks": (12, (5, 3), 8, (3, 5), None),
    "every_pick_held_here": (10, (0, 1, 2, 3, 4, 5), 8, None, None),
    "no_pick_held_here": (9, (0, 2), 8, (3, 5), None),
    "a_padded_tail": (12, (0, 1, 2, 3, 4, 5), 4, None, 5),
    "a_tail_and_a_part_filled_chunk": (16, (1, 4, 5), 8, None, 11),
}


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_the_picks_held_here_in_chunks_are_a_loop_over_each_tokens_picks(
        monkeypatch, case):
    """The compacted form (sort, walk the held prefix a chunk at a time,
    add rows to their tokens) against the plain loop, with what it says of
    itself: `GROUPED_PRODUCTS` a chunk walked and a chunk's rows a chunk,
    so rows that are held elsewhere or padding cost no row.  (The grouped
    form in the rule's place: 8 tokens' picks already cover 6 experts.)"""
    t, held, chunk_rows, steer, n_valid = HELD_CASES[case]
    monkeypatch.setattr(moe, "_batched_form", lambda *a: False)
    monkeypatch.setattr(moe, "_CHUNK_ROWS", chunk_rows)
    rng = np.random.RandomState(len(case))
    h, i, top_k = 16, 8, 2
    x = np.abs(rng.randn(t, h)).astype(np.float32)
    router = (rng.randn(h, 6) * 0.3).astype(np.float32)
    if steer:           # positive x: these two columns win for every token
        router[:, steer[0]], router[:, steer[1]] = 1.0, 0.5
    gate, up, down = ((rng.randn(*s) * 0.3).astype(np.float32) for s in (
        (len(held), h, i), (len(held), h, i), (len(held), i, h)))
    valid = np.arange(t) < (t if n_valid is None else n_valid)
    y, here, hit, products, rows = F.moe_ffn_held(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(gate),
        jnp.asarray(up), jnp.asarray(down), held, top_k=top_k,
        valid=jnp.asarray(valid))
    want, want_here, want_hit = plain_held(x, router, gate, up, down, held,
                                           top_k, valid)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert (int(here), int(hit)) == (want_here, want_hit)
    if t * top_k <= chunk_rows:       # one chunk, no loop: all the picks
        assert (int(products), int(rows)) == (moe.GROUPED_PRODUCTS,
                                              t * top_k)
    else:
        chunks = -(-want_here // chunk_rows)
        assert (int(products), int(rows)) == (
            moe.GROUPED_PRODUCTS * chunks, chunk_rows * chunks)
    if steer:
        assert want_here == t * len(set(steer) & set(held))
    if n_valid is not None:           # the tail adds no row and gets none
        assert int(rows) < t * top_k and not np.any(np.asarray(y)[n_valid:])


# ------------------------------------------------------------- the engine

REQUESTS = ((3, 20), (12, 18), (5, 6), (14, 10), (2, 25), (9, 12))


def serve(eng, reqs, seed=0):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, 128, plen).astype(np.int32)
               for plen, _ in reqs]
    resps = [eng.submit(p, out) for p, (_, out) in zip(prompts, reqs)]
    eng.run_until_drained(timeout=120)
    return prompts, resps


@pytest.fixture(scope="module")
def served(eng):
    """Six requests through three slots: prompts shorter (3, 5, 2) and
    longer (12, 14, 9) than the window of 8, a bucket of 16 that leaves its
    last 8 rows in the ring, rings that wrap during decode, and slots
    reused after a longer tenant; the ring of spans it left."""
    tracer = obs.get_tracer()
    tracer.clear()
    prompts, resps = serve(eng, REQUESTS)
    return {"prompts": prompts, "resps": resps, "events": tracer.events()}


def test_prefill_then_decode_follow_the_reference_at_every_position(
        eng, served, ref_logits):
    assert eng._leaf_rows == [(WINDOW, WINDOW)] * 3 + [(MAX_LEN, MAX_LEN)]
    assert eng.post_warmup_compiles() == 0
    assert eng.compile_counts()["total"] == eng.compile_counts()["bound"] == 3
    for prompt, resp, (_, out) in zip(served["prompts"], served["resps"],
                                      REQUESTS):
        toks = resp.tokens()
        assert len(toks) == out and resp.finish_reason == "length"
        full = np.concatenate([prompt, toks])
        want = np.asarray(jax.nn.log_softmax(
            ref_logits(full[:-1]), axis=-1))[len(prompt) - 1:]
        # the served token is the reference's choice, at its probability
        assert [int(np.argmax(row)) for row in want] == list(toks)
        assert abs(resp.logprob - want[np.arange(out), toks].sum()) < 1e-4


def test_a_batch_of_slots_at_different_positions_is_each_slot_alone(
        eng, served):
    for k in (1, 4):
        resp = eng.submit(served["prompts"][k], REQUESTS[k][1])
        eng.run_until_drained(timeout=120)
        assert resp.tokens() == served["resps"][k].tokens()
        assert abs(resp.logprob - served["resps"][k].logprob) < 1e-5


def test_what_is_not_built_for_such_a_model_refuses(tiny, eng):
    for kw, what in ((dict(kv="paged"), "kv='paged'"),
                     (dict(prefix_cache=True), "prefix_cache"),
                     (dict(draft_model=tiny[0]), "draft_model"),
                     (dict(mesh=object()), "mesh=")):
        with pytest.raises(InvalidArgumentError, match=what):
            ServingEngine(tiny[0], max_slots=2, max_len=16, **kw)
    resp = eng.submit([1, 2, 3], 20)
    eng.step()
    with pytest.raises(InvalidArgumentError, match="ring"):
        eng.preempt_slot(0)
    with pytest.raises(InvalidArgumentError, match="ring"):
        eng.restore_run(None)
    eng.run_until_drained(timeout=60)
    assert len(resp.tokens()) == 20


# ------------------------------------------------------ spans and counters

def test_the_spans_a_step_are_unchanged_and_carry_the_routed_counts(served):
    events = served["events"]
    names = collections.Counter(ev[NAME] for ev in events)
    steps = names["serving_step"]
    # what `tests/test_step_phases.py` finds of a GPT-2 engine: one of each
    # phase a step, five a request, nothing a token, a slot or a layer
    assert names == dict(
        serving_step=steps, serving_sweep=steps, serving_decode=steps,
        serving_decode_dispatch=steps, serving_token_pull=steps,
        serving_deliver=steps,
        serving_batch_rebuild=names["serving_batch_rebuild"],
        **{n: len(REQUESTS) for n in (
            "serving_queue_wait", "serving_admit", "serving_request",
            "serving_prefill_dispatch", "serving_prefill_wait")})
    layers, k, chunk = 4, 2, 4
    for ev in events:
        if ev[NAME] == "serving_admit":
            args = ev[ARGS]
            assert args["routed_all"] == args["plen"] * k * layers
        elif ev[NAME] == "serving_decode":
            args = ev[ARGS]
            assert args["routed_all"] == args["active"] * k * layers * chunk
        else:
            assert not (ev[ARGS] and "routed_here" in ev[ARGS])
            continue
        assert 0 <= args["routed_here"] <= args["routed_all"]
        # three experts held: each hit counts once a layer a step
        assert 0 <= args["experts_hit"] <= min(
            args["routed_here"], 3 * layers * chunk)
        if args.get("bucket") == 16:
            # 32 picks cover the 8 experts: the batched form, no grouped
            # product and so neither count
            assert not {"expert_products", "expert_rows"} & set(args)
            continue
        # three grouped products a layer a step over all the picks of the
        # batch of 3 slots or of the prompt's bucket: one chunk each here
        steps = chunk if ev[NAME] == "serving_decode" else 1
        assert args["expert_products"] == 3 * layers * steps
        assert args["expert_rows"] == k * layers * steps * (
            3 if ev[NAME] == "serving_decode" else args["bucket"])


def test_the_counters_add_up(eng):
    reg = obs.metrics.get_registry()
    picks, hit, rows_through = (reg.get("moe_routed_picks_total"), reg.get(
        "moe_experts_hit_total"), reg.get("moe_expert_rows_total"))
    before = (picks.value(where="here"), picks.value(where="elsewhere"),
              hit.value(), rows_through.value())
    tracer = obs.get_tracer()
    tracer.clear()
    serve(eng, REQUESTS[:3], seed=5)
    events = tracer.events()
    last = [ev[ARGS] for ev in events if ev[NAME] == "serving_decode"][-1]
    # the last decode call read its requests' rows (all past the window by
    # then): three rings and one full layer a request
    rows = reg.get("serving_kv_rows")
    assert rows.value(kind="window") == 3 * WINDOW * last["active"]
    assert (WINDOW * last["active"] < rows.value(kind="full")
            <= MAX_LEN * last["active"])
    spans = [ev[ARGS] for ev in events
             if ev[ARGS] and "routed_here" in ev[ARGS]]
    here = sum(a["routed_here"] for a in spans)
    total = sum(a["routed_all"] for a in spans)
    assert picks.value(where="here") - before[0] == here > 0
    assert picks.value(where="elsewhere") - before[1] == total - here > 0
    assert hit.value() - before[2] == sum(a["experts_hit"] for a in spans)
    # a bucket of 16 takes the batched form: its spans carry no rows
    grouped = [a for a in spans if "expert_rows" in a]
    assert 0 < len(grouped) < len(spans)
    assert rows_through.value() - before[3] == sum(
        a["expert_rows"] for a in grouped) >= sum(
            a["routed_here"] for a in grouped)
