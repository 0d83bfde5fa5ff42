"""The Nemotron-H decoder (`models/nemotron_h.py`: Mamba-2 mixers whose state
has no row axis, LatentMoE relu^2 experts held a share at a time, one GQA
layer with no positions) and the serving engine's cache of state leaves
beside `(k, v)` rows, against the benchmark's plain reference
(`benchmark/reference/nemotron_h.py`, the Mamba recurrence step by step), at
a tiny size on the CPU in float32 with seeded weights.

Tolerances, each with its reason:
  * 2e-4 on logits between the float32 program and the float32 reference:
    the same arithmetic in another order, the scan in chunks where the
    reference steps; logits are of size 1 to 3 and their float32 sums over
    a few hundred terms of decays and products differ by a few 1e-5.
  * 1e-4 relative between the chunked scan and the recurrence on the same
    inputs (outputs and states of size 1e-2 to 1, float32).
  * a bfloat16 program reads 1e-2 or more on the first comparison (asserted
    above 50 x the tolerance): computing below the stated precision fails.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import models, observability as obs
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.models import nemotron_h as NH
from paddle_tpu.nn.functional import moe
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights as W  # noqa: E402
from benchmark.arch import nemotron_h as A  # noqa: E402

pytestmark = [pytest.mark.serving]

REF = A.reference
TOL, SCAN_TOL = 2e-4, 1e-4
MAX_LEN = 512
HELD = [0, 1, 2, 5]
TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E", num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=8, conv_kernel=4,
            expand=2, chunk_size=128, n_routed_experts=8,
            num_experts_per_tok=3, moe_intermediate_size=24,
            moe_latent_size=16, moe_shared_expert_intermediate_size=40,
            routed_scaling_factor=5.0)
NAME, T0, DUR, TID, ID, PARENT, ARGS = range(7)


def dims(held=HELD):
    return A.dims(dict(
        TINY, norm_eps=1e-5, max_position_embeddings=MAX_LEN,
        initializer_range=0.2, e_score_correction_bias_std=0.2,
        conv_init_std=0.2887, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=1e-4, experts_held=held, num_experts_held=len(held)))


def build(dtype="float32", seed=2147483659, held=HELD):
    d = dims(held)
    model = models.NemotronHForCausalLM(models.NemotronHConfig(
        **TINY, layer_norm_epsilon=1e-5, experts_held=held,
        initializer_range=0.2, dtype=dtype))
    model.eval()
    top = dict(A.make_leaves(W.make, d, seed, -1))
    layers = [dict(A.make_leaves(W.make, d, seed, i)) for i in range(d["L"])]
    state = model.state_dict()
    loaded = set()
    for i, leaves in enumerate([top] + layers):
        for name, leaf in leaves.items():
            p = state[A.program_name(name, i - 1)]
            assert tuple(p.shape) == tuple(leaf.shape), name
            p._set_data(leaf.astype(p._data.dtype))
            loaded.add(A.program_name(name, i - 1))
    assert loaded == set(state)
    return model, d, top, layers


@pytest.fixture(scope="module")
def tiny():
    return build()


@pytest.fixture(scope="module")
def ref_logits(tiny):
    """The reference's logits over MAX_LEN positions, compiled once: what
    follows a position does not move it."""
    _, d, top, layers = tiny
    fn = jax.jit(lambda ids: REF.logits(top, layers, ids, d))

    def padded(ids):
        row = np.zeros((MAX_LEN,), np.int32)
        row[:len(ids)] = np.asarray(ids, np.int32)
        return np.asarray(fn(jnp.asarray(row)))[:len(ids)]

    return padded


@pytest.fixture(scope="module")
def eng(tiny):
    """One engine for the prompt cases: its programs compile once."""
    e = engine(tiny[0])
    yield e
    e.close()


def engine(model, slots=3):
    return ServingEngine(model, max_slots=slots, max_len=MAX_LEN,
                         prefill_buckets=(128, 256, 512), decode_chunk=4,
                         max_queue_depth=16)


def prompt_of(n, seed=0):
    return list(np.random.RandomState(seed + n).randint(1, 96, size=n))


def by_hand(model, prompt, seq, bucket):
    """The model's two entries by hand over the cache protocol: logits at
    the prompt's last position and at every later one of `seq`."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    prefill = jax.jit(lambda ids, n: model.forward_prefill(ids, n)[:2])
    decode = jax.jit(lambda *a: model.forward_decode(*a)[:2])
    logits, leaves = prefill(jnp.asarray(padded), jnp.int32(len(prompt)))
    out = [np.asarray(logits, np.float32)[0, 0]]
    pool = model.gen_fixed_cache(1, MAX_LEN)
    cache = [tuple(
        got if got.shape == want.shape else
        want.at[:, :bucket].set(jnp.where(
            (jnp.arange(bucket) < len(prompt))[None, :, None, None], got, 0))
        for got, want in zip(layer, empty))
        for layer, empty in zip(leaves, pool)]
    for p in range(len(prompt), len(seq)):
        logits, cache = decode(
            jnp.asarray([seq[p]]), cache, jnp.asarray([p], jnp.int32),
            jnp.asarray([True]))
        out.append(np.asarray(logits, np.float32)[0])
    return np.stack(out)


# -------------------------------------------- the engine against the reference

@pytest.mark.parametrize("plen", [127, 128, 129, 300])
def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(
        tiny, ref_logits, eng, plen):
    """Across a chunk's edge and with a bucket's padding behind the prompt:
    the served tokens are the reference's own choices, and the logits agree
    at the prompt's last position and every decoded one."""
    model = tiny[0]
    prompt = prompt_of(plen)
    resp = eng.submit(prompt, 9)
    while eng.has_work():
        eng.step()
    toks = list(resp.tokens(5))
    want = ref_logits(prompt + toks)
    assert toks == list(np.argmax(want[plen - 1:-1], axis=-1))
    bucket = next(b for b in (128, 256, 512) if b >= plen)
    got = by_hand(model, prompt, prompt + toks, bucket)
    assert np.max(np.abs(got - want[plen - 1:])) < TOL


def test_the_cache_is_state_leaves_beside_rows(tiny):
    model, d = tiny[:2]
    cache = model.gen_fixed_cache(3, MAX_LEN)
    state = (3, 8, 8, 8)
    conv = (3, 3, d["conv_dim"])
    rows = (3, MAX_LEN, 2, 8)
    assert [[leaf.shape for leaf in layer] for layer in cache] == [
        [state, conv], [state, conv], [rows, rows]]
    assert cache[0][0].dtype == jnp.float32
    assert model.serving_rows_held(40) == {"full": 40}


def test_a_bfloat16_program_fails_the_float32_tolerance(ref_logits):
    model = build("bfloat16")[0]
    prompt = prompt_of(129)
    got = by_hand(model, prompt, prompt + [7, 9, 11], 256)
    assert np.max(np.abs(got - ref_logits(prompt + [7, 9, 11])[128:])) > \
        50 * TOL


# ------------------------------------------------------------ the scan

@pytest.mark.parametrize("t, real", [(64, 64), (128, 100), (384, 300)])
def test_the_chunked_scan_is_the_recurrence(t, real):
    """`ssd_chunked` in chunks of 32 against `ssm_step` row by row, padding
    rows (dt 0) moving nothing: the same outputs on the real rows and the
    state at the last real row."""
    rng = np.random.RandomState(t)
    nh, p, g, n = 8, 4, 2, 6
    x = jnp.asarray(rng.randn(t, nh, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (t, nh)), jnp.float32)
    dt = jnp.where((jnp.arange(t) < real)[:, None], dt, 0.0)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, nh), jnp.float32)
    b = jnp.asarray(rng.randn(t, g, n), jnp.float32)
    c = jnp.asarray(rng.randn(t, g, n), jnp.float32)
    y, last = NH.ssd_chunked(x, dt, a, b, c, 32)
    h = jnp.zeros((1, nh, p, n), jnp.float32)
    want = []
    for i in range(real):
        yi, h = NH.ssm_step(h, x[i][None], dt[i][None], a, b[i][None],
                            c[i][None])
        want.append(yi[0])
    want = np.stack(want)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(np.asarray(y[:real]) - want)) < SCAN_TOL * scale
    assert np.max(np.abs(np.asarray(last) - np.asarray(h[0]))) < \
        SCAN_TOL * np.max(np.abs(np.asarray(h)))


def test_a_reused_slot_carries_none_of_the_last_requests_state(tiny):
    """One slot, two requests in turn: the second's tokens are what a
    fresh engine serves it, so neither the SSM state, nor the conv state,
    nor the rows of the first survive into it."""
    model = tiny[0]
    first, second = prompt_of(200, 1), prompt_of(60, 2)
    eng = engine(model, slots=1)
    a = eng.submit(first, 12)
    b = eng.submit(second, 12)
    while eng.has_work():
        eng.step()
    reused = list(b.tokens(5))
    assert len(list(a.tokens(5))) == 12
    fresh = engine(model, slots=1)
    c = fresh.submit(second, 12)
    while fresh.has_work():
        fresh.step()
    assert reused == list(c.tokens(5))
    eng.close()
    fresh.close()


# ------------------------------------------------------ the relu^2 experts

@pytest.mark.parametrize("rows", [4, 40])
def test_the_relu2_experts_grouped_and_batched_forms_agree(monkeypatch, rows):
    """The held relu^2 experts in the latent through grouped products and
    through one batched product a matrix, the same float32 result, and the
    counts the form says: two products a chunk for experts with no gate."""
    rng = np.random.RandomState(rows)
    h, lat, i, e, k = 24, 16, 12, 8, 3
    x = jnp.asarray(rng.randn(rows, h), jnp.float32)
    latent = jnp.asarray(rng.randn(rows, lat), jnp.float32)
    router = jnp.asarray(rng.randn(h, e), jnp.float32)
    up = jnp.asarray(rng.randn(4, lat, i) * 0.3, jnp.float32)
    down = jnp.asarray(rng.randn(4, i, lat) * 0.3, jnp.float32)
    valid = jnp.arange(rows) < rows - 1
    out = {}
    for batched in (False, True):
        monkeypatch.setattr(moe, "_batched_form",
                            lambda t, top_k, n, b=batched: b)
        out[batched] = moe.moe_ffn_held.raw(
            x, router, None, up, down, (0, 1, 2, 5), k, valid=valid,
            select_bias=jnp.zeros((e,)), scale=5.0, expert_x=latent)
    assert out[True][0].shape == (rows, lat)
    np.testing.assert_allclose(np.asarray(out[True][0]),
                               np.asarray(out[False][0]), rtol=1e-5,
                               atol=1e-5)
    assert int(out[True][1]) == int(out[False][1])          # picks here
    assert int(out[True][2]) == int(out[False][2])          # experts hit
    assert int(out[False][3]) == moe.GROUPED_PRODUCTS - 1
    assert (int(out[True][3]), int(out[True][4])) == (0, 0)
    # by hand: each row's picks on held experts, relu^2, weighed and scaled
    s = jax.nn.sigmoid(x @ router)
    top, idx = jax.lax.top_k(s, k)
    w = 5.0 * top / jnp.sum(top, -1, keepdims=True)
    want = np.zeros((rows, lat), np.float32)
    for r in range(rows - 1):
        for j in range(k):
            place = {0: 0, 1: 1, 2: 2, 5: 3}.get(int(idx[r, j]))
            if place is not None:
                hid = jnp.square(jax.nn.relu(latent[r] @ up[place]))
                want[r] += float(w[r, j]) * np.asarray(hid @ down[place])
    np.testing.assert_allclose(np.asarray(out[False][0]), want, rtol=1e-4,
                               atol=1e-4)


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The LatentMoE layer cut four ways, experts 0-1, 2-3, 4-5, 6-7, each
    share routing over all 8: the four routed parts, with the shared expert
    (which every chip computes alike) counted once, are the uncut layer.
    `fc2_latent_proj` is linear, so the routed parts may be summed after it
    as well as before."""
    d = dims(list(range(8)))
    cfg = lambda held: models.NemotronHConfig(  # noqa: E731
        **TINY, experts_held=held, dtype="float32")
    layer = NH.LatentMoE(cfg(list(range(8))))
    leaves = dict(A.make_leaves(W.make, d, 2147483659, 1))   # layer 1: E
    for name, leaf in leaves.items():
        if name != "ln":
            p = A.program_name(name, 1).split(".mixer.")[1]
            layer.state_dict()[p]._set_data(leaf)
    x = jnp.asarray(np.random.RandomState(3).randn(10, 32), jnp.float32)
    valid = jnp.ones((10,), bool)
    uncut, _ = layer.forward(x, valid)
    shared = NH._mm(NH._relu2(NH._mm(x, layer.shared_up)), layer.shared_down)
    parts = []
    for share in ((0, 1), (2, 3), (4, 5), (6, 7)):
        mixer = NH.LatentMoE(cfg(share))
        for name, p in mixer.state_dict().items():
            leaf = layer.state_dict()[name]._data
            if name in ("experts.up", "experts.down"):
                leaf = leaf[jnp.asarray(share)]
            p._set_data(leaf)
        y, counts = mixer.forward(x, valid)
        parts.append(np.asarray(y - shared))
        assert int(counts[1]) == 10 * 3       # every chip routes every pick
    np.testing.assert_allclose(sum(parts) + np.asarray(shared),
                               np.asarray(uncut), rtol=1e-4, atol=1e-4)


# ------------------------------------------------ the counts on the spans

def test_the_counts_land_on_the_spans_counters_and_gauge(tiny):
    """A prompt's `serving_admit` and a decode call's `serving_decode` carry
    the routed family's counts beside the attention's rows and the state's
    slot-layers; the routed counters move; the gauge holds the rows of the
    attention layer; the scan's two forms count themselves at trace time."""
    model = tiny[0]
    reg = obs.metrics.get_registry()
    forms = reg.get("ssm_scan_form_total")
    before = {f: forms.value(form=f) for f in ("chunked", "recurrent")}
    eng = engine(model)
    picks = reg.get("moe_routed_picks_total")
    here0 = picks.value(where="here")
    obs.get_tracer().clear()
    resp = eng.submit(prompt_of(50), 6)
    eng.step()
    gauge = reg.get("serving_kv_rows")
    while eng.has_work():
        eng.step()
    assert len(list(resp.tokens(5))) == 6
    events = obs.get_tracer().events()
    admit = [ev[ARGS] for ev in events if ev[NAME] == "serving_admit"][-1]
    # 50 tokens x 3 picks x 2 E layers; the attention's rows: the prompt's
    # and the bucket's; two Mamba layers' state, one slot
    assert admit["routed_all"] == 50 * 3 * 2
    assert 0 < admit["routed_here"] <= admit["routed_all"]
    assert (admit["kv_rows_live"], admit["kv_rows_pool"]) == (50, 128)
    assert (admit["ssm_state_live"], admit["ssm_state_pool"]) == (2, 2)
    decode = [ev[ARGS] for ev in events if ev[NAME] == "serving_decode"][0]
    # a call of 4 steps: one active slot of 3, 2 Mamba layers
    assert (decode["ssm_state_live"], decode["ssm_state_pool"]) == (
        1 * 2 * 4, 3 * 2 * 4)
    assert decode["kv_rows_pool"] == 3 * MAX_LEN * 4
    assert 0 < decode["kv_rows_live"] <= 1 * MAX_LEN * 4
    assert decode["routed_all"] == 1 * 3 * 2 * 4
    assert picks.value(where="here") > here0
    assert gauge is not None and gauge.value(kind="full") > 0
    after = {f: forms.value(form=f) for f in ("chunked", "recurrent")}
    assert after["chunked"] > before["chunked"]
    assert after["recurrent"] > before["recurrent"]
    eng.close()


@pytest.mark.parametrize("over", [
    dict(hybrid_override_pattern="MEM-E"), dict(mlp_hidden_act="silu"),
    dict(expand=3), dict(n_shared_experts=2)])
def test_a_form_the_model_has_not_is_refused(over):
    with pytest.raises(InvalidArgumentError):
        models.NemotronHConfig(**dict(TINY, **over))
