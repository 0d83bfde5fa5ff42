"""Speculative decoding for the serving engine (ISSUE-7).

Covers the tentpole contracts: greedy speculative streams bit-identical
to solo `generation.generate` regardless of draft quality, heterogeneous
spec on/off + sampling-param traffic sharing the single verify trace
(compile bound unchanged at len(prefill_buckets) + 1), spec-off slots
reproducing the plain engine token-for-token, distribution preservation
of the rejection-sampling commit (the Leviathan/Chen theorem, checked
empirically), the PR-6 deadline rule across multi-token ticks, and the
PDTPU_FAULT_DRAFT_DIVERGE degradation path."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.nn.layer_base import Layer
from paddle_tpu.serving import ServingEngine, DeadlineExceededError
from paddle_tpu.utils import faults
from paddle_tpu.utils.monitor import stat_get

pytestmark = [pytest.mark.spec, pytest.mark.serving]


def tiny_gpt(layers=2, seed=7):
    cfg = models.GPTConfig(vocab_size=13, hidden_size=16,
                           num_hidden_layers=layers, num_attention_heads=2,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=64)
    paddle.seed(seed)
    m = models.GPTForPretraining(cfg)
    m.eval()
    return m


def solo(model, prompt, max_new, **kw):
    out, _ = model.generate(paddle.to_tensor(
        np.asarray(prompt, np.int32)[None]), max_new_tokens=max_new, **kw)
    return np.asarray(out.numpy())[0].tolist()


@pytest.fixture(scope="module")
def spec_engine():
    """Target GPT + an unrelated (random-weight) 1-layer draft: the
    worst-case draft — parity must hold no matter how bad the proposals
    are."""
    target = tiny_gpt(layers=2, seed=7)
    draft = tiny_gpt(layers=1, seed=11)
    eng = ServingEngine(target, max_slots=3, max_len=48,
                        prefill_buckets=(8, 16), draft_model=draft,
                        spec_tokens=3, max_queue_depth=64)
    eng.warmup()
    return target, eng


# ---------------------------------------------------------------------------
# greedy parity: bit-identical to solo generate, any draft
# ---------------------------------------------------------------------------

def test_spec_greedy_parity_random_draft(spec_engine):
    target, eng = spec_engine
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 13, (n,)) for n in (4, 7, 11)]
    resps = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_drained(timeout=120)
    for p, r in zip(prompts, resps):
        assert r.tokens(timeout=5) == solo(target, p, 6)
        assert r.finish_reason == "length"


@pytest.mark.parametrize("kv", [dict(), dict(kv="paged", block_size=4)],
                         ids=["fixed", "paged"])
def test_spec_identical_draft_accepts_everything(kv):
    """Draft == target (weight-identical clone): every proposal matches
    the target argmax, so accept rate is exactly 1.0 — this also proves
    the K+1-token verify forward is row-for-row bit-identical to the
    draft's sequential single-token forwards.  Over both cache views: the
    paged one gathers and publishes the draft's and the target's rows
    through one set of block tables."""
    target = tiny_gpt(layers=2, seed=7)
    clone = tiny_gpt(layers=2, seed=7)
    eng = ServingEngine(target, max_slots=2, max_len=48,
                        prefill_buckets=(8,), draft_model=clone,
                        spec_tokens=3, **kv)
    r = eng.submit([1, 2, 3, 4], max_new_tokens=9)
    eng.run_until_drained(timeout=120)
    assert r.tokens() == solo(target, [1, 2, 3, 4], 9)
    assert eng.metrics()["spec"]["accept_rate"] == 1.0


def test_spec_eos_stops_stream_and_frees_slot(spec_engine):
    target, eng = spec_engine
    prompt = [1, 2, 3]
    toks = solo(target, prompt, 6)
    eos = toks[2]  # lands mid-tick (spec_tokens=3 commits up to 4)
    r = eng.submit(prompt, max_new_tokens=6, eos_token_id=eos)
    eng.run_until_drained(timeout=120)
    assert r.tokens() == toks[:toks.index(eos) + 1]
    assert r.finish_reason == "eos"
    assert eng.scheduler.free_slot_count() == eng.max_slots


# ---------------------------------------------------------------------------
# one verify trace for every traffic mix
# ---------------------------------------------------------------------------

def test_spec_compile_bound_over_heterogeneous_traffic(spec_engine):
    """spec on/off × greedy/sampling × distinct sampling params share the
    verify trace: zero retraces across 16 mixed requests."""
    from paddle_tpu.core import op as core_op
    _, eng = spec_engine
    combos = [
        dict(max_new_tokens=3),
        dict(max_new_tokens=4, spec=False),
        dict(max_new_tokens=5, decode_strategy="sampling",
             temperature=0.7, seed=1),
        dict(max_new_tokens=4, decode_strategy="sampling", top_k=3,
             seed=2, spec=False),
        dict(max_new_tokens=6, decode_strategy="sampling", top_p=0.8,
             temperature=1.3, seed=3),
    ]
    rng = np.random.RandomState(0)
    before = eng.compile_counts()
    disp_before = core_op.dispatch_cache_stats()["misses"]
    resps = []
    for i in range(16):
        plen = int(rng.randint(2, 8))
        resps.append(eng.submit(rng.randint(0, 13, (plen,)),
                                **combos[i % len(combos)]))
        eng.step()
    eng.run_until_drained(timeout=120)
    for r in resps:
        assert r.done() and r.error is None
    after = eng.compile_counts()
    assert after == before, "mixed spec/sampling traffic must not retrace"
    assert after["total"] <= after["bound"] == len(eng.buckets) + 1
    assert core_op.dispatch_cache_stats()["misses"] == disp_before


def test_spec_off_matches_plain_engine_bit_exact(spec_engine):
    """A sampling request with spec=False inside a speculative engine
    must stream token-for-token what the plain continuous-batching
    engine produces for the same seed (same key folds, same
    distributions)."""
    target, eng = spec_engine
    kw = dict(max_new_tokens=8, decode_strategy="sampling", top_k=4,
              temperature=0.9, seed=9)
    off = eng.submit([1, 2, 3], spec=False, **kw)
    eng.run_until_drained(timeout=60)
    plain = ServingEngine(target, max_slots=2, max_len=48,
                          prefill_buckets=(8,))
    p = plain.submit([1, 2, 3], **kw)
    plain.run_until_drained(timeout=60)
    assert off.tokens() == p.tokens()


def test_spec_sampling_deterministic_per_seed(spec_engine):
    _, eng = spec_engine
    kw = dict(max_new_tokens=5, decode_strategy="sampling", top_k=4,
              seed=17)
    a = eng.submit([2, 4, 6], **kw)
    eng.run_until_drained(timeout=60)
    b = eng.submit([2, 4, 6], **kw)
    eng.run_until_drained(timeout=60)
    assert a.tokens() == b.tokens()


class _MarkerModel(Layer):
    """Clamp-detector protocol model: KV rows hold (position + token)
    markers and the greedy token is the masked prefix-sum mod vocab — a
    single misplaced/clamped KV write changes the stream immediately
    (real transformer logits can shrug off one corrupted row; this
    cannot)."""

    VOCAB = 97

    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        import jax.numpy as jnp
        dt = dtype or jnp.float32
        return [(jnp.zeros((batch_size, max_length, 1, 1), dt),
                 jnp.zeros((batch_size, max_length, 1, 1), dt))]

    def forward_fixed(self, input_ids, caches, pos):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import unwrap
        ids = unwrap(input_ids)
        p = unwrap(pos)
        s = ids.shape[1]
        k, v = caches[0]
        marker = (p + jnp.arange(s)[None, :] + 1 + ids).astype(k.dtype)
        k = jax.lax.dynamic_update_slice(k, marker[:, :, None, None],
                                         (0, p, 0, 0))
        t = k.shape[1]
        key_idx = jnp.arange(t)[None, None, :]
        q_idx = (p + jnp.arange(s))[None, :, None]
        mask = (key_idx <= q_idx).astype(k.dtype)
        sums = jnp.sum(k[:, :, 0, 0][:, None, :] * mask, axis=-1)
        tok = jnp.mod(sums, self.VOCAB).astype(jnp.int32)
        return jax.nn.one_hot(tok, self.VOCAB, dtype=jnp.float32), [(k, v)]


def test_spec_full_budget_request_keeps_parity_at_pool_end():
    """A request using the ENTIRE plen+max_new == max_len budget: the
    final verify ticks write K+1 rows near the end of the pool, which
    must land in the engine's spec headroom — without it
    dynamic_update_slice would CLAMP the write start and silently
    overwrite committed KV, corrupting the tail of the stream.  The
    spec=False request advances one position per tick, so its last
    ticks provably write past max_len (the clamp trigger); the marker
    model makes any clamp visible in the stream (regression for the
    pool-length bound)."""
    from paddle_tpu.generation import generate
    m = _MarkerModel()
    eng = ServingEngine(m, max_slots=2, max_len=16, prefill_buckets=(8,),
                        draft_model=_MarkerModel(), spec_tokens=3)
    r_off = eng.submit([1, 2, 3, 4], max_new_tokens=12, spec=False)
    r_on = eng.submit([5, 6, 7, 8], max_new_tokens=12)
    eng.run_until_drained(timeout=120)

    def oracle(prompt):
        out, _ = generate(m, paddle.to_tensor(
            np.asarray(prompt, np.int32)[None]), max_new_tokens=12)
        return np.asarray(out.numpy())[0].tolist()

    assert r_off.tokens() == oracle([1, 2, 3, 4])
    assert r_on.tokens() == oracle([5, 6, 7, 8])
    # and the same full-budget shape on a real model
    target = tiny_gpt(layers=2, seed=7)
    geng = ServingEngine(target, max_slots=1, max_len=16,
                         prefill_buckets=(8,),
                         draft_model=tiny_gpt(layers=1, seed=11),
                         spec_tokens=3)
    g = geng.submit([1, 2, 3, 4], max_new_tokens=12)
    geng.run_until_drained(timeout=120)
    assert g.tokens() == solo(target, [1, 2, 3, 4], 12)


def test_spec_requires_draft_and_valid_k():
    target = tiny_gpt()
    plain = ServingEngine(target, max_slots=2, max_len=48,
                          prefill_buckets=(8,))
    with pytest.raises(InvalidArgumentError, match="draft_model"):
        plain.submit([1, 2], max_new_tokens=2, spec=True)
    with pytest.raises(InvalidArgumentError, match="spec_tokens"):
        ServingEngine(target, max_slots=2, max_len=48,
                      prefill_buckets=(8,), draft_model=tiny_gpt(1, 3),
                      spec_tokens=0)


# ---------------------------------------------------------------------------
# distribution preservation (the rejection-sampling theorem, empirically)
# ---------------------------------------------------------------------------

def test_spec_sampled_commit_preserves_target_distribution():
    """The first committed token of a speculative tick must follow the
    PROCESSED TARGET distribution exactly, however bad the draft is:
    empirical TV distance over 4000 independent keys < 0.05."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation.speculative import (
        commit_speculative_sampled, draft_proposal_key)
    n, v, k = 4000, 5, 2
    rng = np.random.RandomState(0)
    p_logits = jnp.asarray(rng.randn(v).astype(np.float32)) * 1.5
    q_logits = jnp.asarray(rng.randn(v).astype(np.float32)) * 1.5  # != p
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    pos = jnp.zeros((n,), jnp.int32)
    # draft proposals drawn from q with the engine's key derivation
    qs = jnp.broadcast_to(jax.nn.softmax(q_logits), (n, k, v))
    props = jnp.stack([
        jax.vmap(lambda kk, i=i: jax.random.categorical(
            draft_proposal_key(kk, 0, i), q_logits))(keys)
        for i in range(k)], axis=1).astype(jnp.int32)
    plog = jnp.broadcast_to(p_logits, (n, k + 1, v))
    out, count, accepted, last, lp = commit_speculative_sampled(
        props, qs, plog, keys, pos, jnp.zeros((n,), bool),
        jnp.ones((n,), bool), 0)
    first = np.asarray(out[:, 0])
    emp = np.bincount(first, minlength=v) / n
    want = np.asarray(jax.nn.softmax(p_logits))
    tv = 0.5 * np.abs(emp - want).sum()
    assert tv < 0.05, (tv, emp, want)
    # sanity: the draft disagrees enough that rejections actually happen
    assert float(jnp.mean(accepted)) < k


# ---------------------------------------------------------------------------
# PR-6 deadline rule across multi-token ticks (satellite regression)
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_spec_deadline_mid_tick_delivers_no_post_expiry_tokens():
    """A verify tick can commit up to K+1 tokens; a deadline that expires
    while the tick is computing must deliver NONE of them (deadline
    shorter than one speculative tick — the tick is slowed with the
    slow_decode fault)."""
    target = tiny_gpt(layers=1, seed=3)
    draft = tiny_gpt(layers=1, seed=4)
    eng = ServingEngine(target, max_slots=2, max_len=48,
                        prefill_buckets=(8,), draft_model=draft,
                        spec_tokens=4)
    eng.warmup()
    faults.enable("slow_decode", "120")  # every tick sleeps 120 ms
    try:
        r = eng.submit([1, 2, 3], max_new_tokens=20, deadline=0.06)
        eng.step()   # prefill (1 token) + one slowed tick
        eng.step()
    finally:
        faults.reset()
    with pytest.raises(DeadlineExceededError):
        r.tokens(timeout=5)
    # only the prefill token (emitted before expiry) may have streamed:
    # the expired tick's K+1 ready commits were all withheld
    assert len(r.tokens_so_far()) <= 1
    assert eng.scheduler.free_slot_count() == eng.max_slots


# ---------------------------------------------------------------------------
# PDTPU_FAULT_DRAFT_DIVERGE (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_draft_diverge_degrades_to_target_only_without_corruption():
    """Draft poisoned EVERY tick (diverge stride 1): the accept/reject
    path must reject essentially everything — throughput falls to
    target-only — while every stream stays bit-identical to solo
    generate."""
    target = tiny_gpt(layers=2, seed=7)
    clone = tiny_gpt(layers=2, seed=7)  # accept rate would be 1.0 clean
    faults.enable("draft_diverge", "1")
    try:
        eng = ServingEngine(target, max_slots=2, max_len=48,
                            prefill_buckets=(8,), draft_model=clone,
                            spec_tokens=3)
        assert eng._diverge_every == 1
        acc0 = stat_get("STAT_spec_accepted")
        r0 = eng.submit([1, 2, 3, 4], max_new_tokens=9)
        r1 = eng.submit([5, 6, 7], max_new_tokens=9,
                        decode_strategy="sampling", top_k=5, seed=5)
        eng.run_until_drained(timeout=120)
    finally:
        faults.reset()
    assert r0.tokens() == solo(target, [1, 2, 3, 4], 9)
    assert r1.error is None and len(r1.tokens()) == 9
    met = eng.metrics()["spec"]
    assert met["accept_rate"] < 0.2, met
    assert stat_get("STAT_spec_accepted") - acc0 <= met["proposed"] * 0.2


def test_clean_engine_has_no_diverge_branch(spec_engine):
    _, eng = spec_engine
    assert eng._diverge_every is None


# ---------------------------------------------------------------------------
# observability: accept histogram, verify program tracking, STAT counters
# ---------------------------------------------------------------------------

def test_spec_metrics_and_program_tracking(spec_engine):
    from paddle_tpu import observability as obs
    _, eng = spec_engine
    ticks0 = stat_get("STAT_spec_ticks")
    r = eng.submit([3, 1, 4], max_new_tokens=5)
    eng.run_until_drained(timeout=60)
    assert r.done()
    met = eng.metrics()["spec"]
    assert met["enabled"] and met["spec_tokens"] == 3
    assert met["proposed"] > 0 and met["accept_rate"] is not None
    assert stat_get("STAT_spec_ticks") > ticks0
    assert stat_get("STAT_spec_proposed") >= met["proposed"]
    # the verify + spec-prefill programs are first-class registry entries
    names = list(obs.get_program_registry().names())
    assert "serving_verify" in names
    assert any(n.startswith("serving_prefill_spec_b") for n in names)
    # the accept-rate histogram is registered and populated
    reg = obs.get_registry()
    h = reg.snapshot().get("serving_spec_accept_rate")
    assert h is not None


def test_plain_engine_metrics_say_spec_disabled():
    target = tiny_gpt()
    eng = ServingEngine(target, max_slots=2, max_len=48,
                        prefill_buckets=(8,))
    assert eng.metrics()["spec"] == {"enabled": False}


# ---------------------------------------------------------------------------
# probe smoke (fresh interpreter: slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_decode_probe_smoke():
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "probes", "spec_decode_probe.py"),
         "--steps", "3"],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert proc.returncode == 0, (proc.stderr or proc.stdout)[-800:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("SPEC")]
    assert lines, proc.stdout[-400:]
    out = json.loads(lines[-1][len("SPEC"):])
    assert out["smoke"] is True
    assert "failures" not in out, out.get("failures")
    for leg in ("spec_decode", "quant"):
        cc = out[leg]["compile_counts"]
        assert cc["total"] <= cc["bound"]
    assert out["quant"]["max_logit_err"] >= 0
