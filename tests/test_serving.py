"""Continuous-batching serving engine (paddle_tpu.serving).

Covers the ISSUE-4 contracts: greedy streams bit-identical to solo
`generation.generate`, compilation bounded by len(prefill_buckets) + 1
regardless of traffic heterogeneity, scheduler edge cases (queue-full
backpressure, deadline expiry mid-decode, cancel before prefill, slot
recycling with no stale KV), per-request fault isolation
(PDTPU_FAULT_NAN_LOGITS), and the inference.Config serving mode."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.nn.layer_base import Layer
from paddle_tpu.nn.layer.common import Embedding
from paddle_tpu.serving import (ServingEngine, QueueFullError,
                                DeadlineExceededError, RequestCancelled,
                                NonFiniteLogitsError)
from paddle_tpu.utils import faults
from paddle_tpu.utils.monitor import stat_get

pytestmark = pytest.mark.serving

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ProtocolModel(Layer):
    """Minimal gen_fixed_cache/forward_fixed protocol model: logits are an
    embedding of the current token (deterministic greedy cycles), the KV
    "cache" is a ones-marker per written position — cheap to compile, and
    stale-KV leaks are directly visible in the pool."""

    def __init__(self, vocab=24):
        super().__init__()
        self.emb = Embedding(vocab, vocab)

    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        import jax.numpy as jnp
        dt = dtype or jnp.float32
        return [(jnp.zeros((batch_size, max_length, 1, 2), dt),
                 jnp.zeros((batch_size, max_length, 1, 2), dt))]

    def forward_fixed(self, input_ids, caches, pos):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import unwrap
        ids = unwrap(input_ids)
        p = unwrap(pos)
        b, s = ids.shape
        logits = unwrap(self.emb(input_ids)).astype(jnp.float32)
        k, v = caches[0]
        chunk = jnp.ones((b, s, 1, 2), k.dtype)
        k = jax.lax.dynamic_update_slice(k, chunk, (0, p, 0, 0))
        v = jax.lax.dynamic_update_slice(v, chunk, (0, p, 0, 0))
        return logits, [(k, v)]


class LeavesProtocolModel(Layer):
    """Minimal `serving_batch_decode` protocol model whose cache is NOT
    `(k, v)` pairs: layer 0 holds ONE leaf `(B, rows, 3)` (a latent layer's
    form), layer 1 THREE of different rank and dtype, `(B, rows, 1, 2)`
    float32, `(B, rows, 2)` bfloat16 and `(B, rows)` int32.  Logits are an
    embedding of the current token; a prompt's rows are marked 1 and a
    decode step's 2, so stale rows are directly visible in the pool.  Its
    counts are the five routed ones (zeros) and the cache's two."""

    serving_batch_decode = True
    serving_cache_kind = "latent"
    RESTS = (((3,),), ((1, 2), (2,), ()))

    def __init__(self, vocab=24):
        super().__init__()
        self.emb = Embedding(vocab, vocab)

    def _dtypes(self, dtype):
        import jax.numpy as jnp
        return ((dtype or jnp.float32,),
                (dtype or jnp.float32, jnp.bfloat16, jnp.int32))

    def gen_fixed_cache(self, batch_size, max_length, dtype=None):
        import jax.numpy as jnp
        return [tuple(jnp.zeros((batch_size, max_length) + rest, dt)
                      for rest, dt in zip(rests, dts))
                for rests, dts in zip(self.RESTS, self._dtypes(dtype))]

    def _counts(self, live, went_over):
        import jax.numpy as jnp
        return jnp.concatenate([jnp.zeros((5,), jnp.int32), jnp.stack(
            [live, went_over]).astype(jnp.int32) * len(self.RESTS)])

    def forward_prefill(self, input_ids, prompt_len):
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import unwrap
        ids, plen = unwrap(input_ids), unwrap(prompt_len)
        logits = unwrap(self.emb(input_ids)).astype(jnp.float32)
        last = jnp.take(logits, plen - 1, axis=1)[:, None]
        rows = [tuple(jnp.ones((1, ids.shape[1]) + rest, dt)
                      for rest, dt in zip(rests, dts))
                for rests, dts in zip(self.RESTS, self._dtypes(None))]
        return last, rows, self._counts(plen, ids.shape[1])

    def forward_decode(self, tokens, caches, pos, active):
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import unwrap
        pos, active = unwrap(pos), unwrap(active)
        logits = unwrap(self.emb(tokens)).astype(jnp.float32)
        slot = jnp.arange(pos.shape[0])
        new = [tuple(unwrap(leaf).at[slot, pos].set(2) for leaf in layer)
               for layer in caches]
        b, rows = new[0][0].shape[:2]
        return logits, new, self._counts(
            jnp.sum(jnp.where(active, pos + 1, 0)), b * rows)


def tiny_gpt():
    cfg = models.GPTConfig(vocab_size=13, hidden_size=16,
                           num_hidden_layers=2, num_attention_heads=2,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=64)
    paddle.seed(7)
    m = models.GPTForPretraining(cfg)
    m.eval()
    return m


def solo(model, prompt, max_new, **kw):
    out, _ = model.generate(paddle.to_tensor(
        np.asarray(prompt, np.int32)[None]), max_new_tokens=max_new, **kw)
    return np.asarray(out.numpy())[0].tolist()


def expected_stream(solo_tokens, eos):
    """Engine streams stop at eos (inclusive); solo pads after it."""
    if eos is not None and eos in solo_tokens:
        return solo_tokens[:solo_tokens.index(eos) + 1]
    return solo_tokens


@pytest.fixture(scope="module")
def gpt_engine():
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=3, max_len=48, prefill_buckets=(8, 16),
                        decode_chunk=4, max_queue_depth=64)
    eng.warmup()
    return m, eng


@pytest.fixture(scope="module")
def stub_engine():
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=2, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2, max_queue_depth=64)
    eng.warmup()
    return m, eng


# ---------------------------------------------------------------------------
# tier-1 smoke: greedy parity with solo generate (<= 3 requests, tiny GPT)
# ---------------------------------------------------------------------------

def test_serving_smoke_greedy_parity(gpt_engine):
    model, eng = gpt_engine
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 13, (n,)) for n in (4, 7, 11)]
    resps = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_drained(timeout=120)
    for p, r in zip(prompts, resps):
        assert r.tokens(timeout=5) == solo(model, p, 6)
        assert r.finish_reason == "length"
        assert r.ttft is not None and r.ttft >= 0


def test_serving_eos_stops_stream_and_frees_slot(gpt_engine):
    model, eng = gpt_engine
    prompt = [1, 2, 3]
    toks = solo(model, prompt, 6)
    eos = toks[2]  # force a mid-stream eos
    r = eng.submit(prompt, max_new_tokens=6, eos_token_id=eos)
    eng.run_until_drained(timeout=120)
    assert r.tokens() == expected_stream(toks, eos)
    assert r.finish_reason == "eos"
    assert eng.scheduler.free_slot_count() == eng.max_slots


def test_slot_reuse_keeps_no_stale_kv_gpt(gpt_engine):
    """A short request admitted into a slot that previously held a longer
    one must decode exactly like a solo run (stale KV beyond the new
    prompt would poison its attention)."""
    model, eng = gpt_engine
    rng = np.random.RandomState(5)
    long_p = rng.randint(0, 13, (12,))
    [eng.submit(long_p, max_new_tokens=20) for _ in range(eng.max_slots)]
    eng.run_until_drained(timeout=120)
    short_p = rng.randint(0, 13, (4,))
    rs = [eng.submit(short_p, max_new_tokens=5)
          for _ in range(eng.max_slots)]
    eng.run_until_drained(timeout=120)
    want = solo(model, short_p, 5)
    for r in rs:
        assert r.tokens() == want


def test_prefill_overwrites_full_slot_range():
    """Direct pool proof: after a long tenant, a bucket-8 prefill zeroes
    the slot's whole [bucket, max_len) tail."""
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=1, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    r = eng.submit(np.arange(6), max_new_tokens=20)  # writes up to pos ~26
    eng.run_until_drained(timeout=60)
    assert r.done()
    assert np.any(np.asarray(eng._pools[0][0])[0, 8:] != 0), \
        "sanity: the long tenant must have left KV beyond the bucket"
    # max_new=1 finishes at prefill: no decode write after the overwrite
    r2 = eng.submit(np.arange(4), max_new_tokens=1)
    eng.run_until_drained(timeout=60)
    assert r2.done()
    k = np.asarray(eng._pools[0][0])
    assert np.all(k[0, :8] == 1), "prefill chunk written"
    assert np.all(k[0, 8:] == 0), "tail beyond the bucket must be scrubbed"


def _leaves(shape_of, fill, layers):
    """A cache in `layers`' form (a tuple of (rest, dtype) a layer), every
    leaf `shape_of(rest)` full of `fill`."""
    import jax.numpy as jnp
    return [tuple(jnp.full(shape_of(rest), fill, dt) for rest, dt in layer)
            for layer in layers]


_F32, _BF16, _I32 = "float32", "bfloat16", "int32"
LAYER_FORMS = {
    "pair": ((((2, 4), _F32), ((2, 4), _F32)),),
    "one_leaf": ((((6,), _BF16),),),
    "three_leaves_of_three_ranks": ((((1, 2), _F32), ((5,), _BF16),
                                     ((), _I32)),),
    "mixed_layers": ((((6,), _BF16),), (((2, 4), _F32), ((2, 4), _F32))),
}


@pytest.mark.parametrize("form", sorted(LAYER_FORMS))
@pytest.mark.parametrize("rows", [16, 5], ids=["pool_len", "ring_of_5"])
def test_fixed_view_writes_a_prompt_into_layers_of_any_leaves(form, rows):
    """`FixedKVView.write_prompt` over a layer's leaves whatever their
    number, rank and dtype: the slot's row is overwritten over its whole
    length (the bucket's rows, then zeros; a ring keeps the prompt's last
    `rows` positions at p % rows), the other slots are left alone."""
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_pool import FixedKVView
    layers, bucket, plen, slot = LAYER_FORMS[form], 8, 7, 1
    pools = _leaves(lambda rest: (3, rows) + rest, 9, layers)
    # a prompt's row p holds p + 1 in every place
    kv = [tuple((jnp.arange(1, bucket + 1).reshape(
        (1, bucket) + (1,) * len(rest)) * jnp.ones((1, bucket) + rest)
                 ).astype(dt) for rest, dt in layer) for layer in layers]
    new = FixedKVView().write_prompt(pools, kv, {
        "slot": jnp.int32(slot), "prompt_len": jnp.int32(plen)})
    assert [len(layer) for layer in new] == [len(l) for l in layers]
    for layer, old in zip(new, pools):
        for leaf, was in zip(layer, old):
            assert leaf.shape == was.shape and leaf.dtype == was.dtype
            got = np.asarray(leaf.astype(jnp.float32))
            assert np.all(got[[0, 2]] == 9), "other slots untouched"
            flat = got[slot].reshape(rows, -1)
            assert np.all(flat == flat[:, :1]), "a row is one number wide"
            if rows >= bucket:
                want = list(range(1, bucket + 1)) + [0] * (rows - bucket)
            else:       # positions plen - rows .. plen - 1 at p % rows
                want = [0] * rows
                for p in range(plen - rows, plen):
                    want[p % rows] = p + 1
            assert flat[:, 0].tolist() == want


def test_fixed_view_writes_each_leaf_of_a_layer_by_its_own_length():
    """A layer of UNEQUAL leaves (`write_prompt` took the first leaf's length
    for the whole layer): a ring pair of 5 rows handed a token-indexed
    bucket of 8 (the prompt's last 5 positions at p % 5), a pair as long as
    the pool, and a summary pair of 3 rows that the model hands back as it
    lies, 2 rows long (a row a chunk: not a token's, not wrapped)."""
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_pool import FixedKVView
    bucket, plen, slot = 8, 7, 1
    lengths = (5, 5, 16, 16, 3, 3)
    pools = [tuple(jnp.full((3, rows, 2), 9.0) for rows in lengths)]
    token_rows = (jnp.arange(1, bucket + 1, dtype=jnp.float32)[None, :, None]
                  * jnp.ones((1, bucket, 2)))
    chunk_rows = jnp.asarray([[[70.0, 70.0], [80.0, 80.0]]])
    kv = [(token_rows, token_rows, token_rows, token_rows, chunk_rows,
           chunk_rows)]
    new = FixedKVView().write_prompt(pools, kv, {
        "slot": jnp.int32(slot), "prompt_len": jnp.int32(plen)})
    assert [leaf.shape for leaf in new[0]] == [(3, n, 2) for n in lengths]
    want = {5: [6, 7, 3, 4, 5], 16: list(range(1, 9)) + [0] * 8,
            3: [70, 80, 0]}
    for leaf, rows in zip(new[0], lengths):
        got = np.asarray(leaf)
        assert np.all(got[[0, 2]] == 9), "other slots untouched"
        assert got[slot, :, 0].tolist() == want[rows], rows
        assert np.all(got[slot, :, 0] == got[slot, :, 1])


def test_build_pools_and_pool_bytes_follow_the_models_leaves():
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_pool import PagedKVPool
    m = LeavesProtocolModel()
    pool = PagedKVPool(num_blocks=6, block_size=4, pool_len=16)
    assert PagedKVPool.leaf_shapes(m) == [
        (((3,), jnp.float32),),
        (((1, 2), jnp.float32), ((2,), jnp.bfloat16), ((), jnp.int32))]
    pools = pool.build_pools(m, put=lambda leaf: leaf + 0)
    assert [[leaf.shape for leaf in layer] for layer in pools] == [
        [(6, 4, 3)], [(6, 4, 1, 2), (6, 4, 2), (6, 4)]]
    assert all(isinstance(layer, tuple) for layer in pools)
    assert pool.pool_bytes(pools) == 6 * 4 * (3 * 4 + 2 * 4 + 2 * 2 + 4)
    # the pair the other models give is the two-leaf case
    pair = pool.build_pools(ProtocolModel())
    assert [leaf.shape for leaf in pair[0]] == [(6, 4, 1, 2)] * 2
    assert pool.pool_bytes(pair) == 2 * 6 * 4 * 2 * 4


def test_recycled_slot_keeps_no_stale_rows_in_any_leaf():
    """The engine over a model of one-leaf and three-leaf layers: after a
    long tenant, a short prompt's prefill leaves nothing of it beyond the
    bucket, in every leaf of every layer."""
    paddle.seed(3)
    m = LeavesProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=1, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    # a number a leaf: one leaf in layer 0, three in layer 1
    assert eng._leaf_rows == [(32,), (32, 32, 32)]
    assert eng._leaf_kinds == [("latent",), ("latent",) * 3]
    r = eng.submit(np.arange(6), max_new_tokens=20)
    eng.run_until_drained(timeout=60)
    assert r.done() and len(r.tokens()) == 20
    for layer in eng._pools:
        for leaf in layer:
            assert np.any(np.asarray(leaf, np.float32)[0, 8:] == 2), \
                "sanity: the long tenant's decode rows lie beyond the bucket"
    r2 = eng.submit(np.arange(4), max_new_tokens=1)
    eng.run_until_drained(timeout=60)
    assert r2.done()
    for layer in eng._pools:
        for leaf in layer:
            got = np.asarray(leaf, np.float32)[0]
            assert np.all(got[:8] == 1), "prefill chunk written"
            assert not np.any(got[8:]), "tail beyond the bucket scrubbed"
    eng.close()


# ---------------------------------------------------------------------------
# compile-count bound + heterogeneity retraces nothing
# ---------------------------------------------------------------------------

def test_compile_bound_over_heterogeneous_traffic(stub_engine):
    """>= 20 requests, >= 4 distinct (prompt_len, max_new, sampling-param)
    combos: at most len(prefill_buckets) + 1 compiled programs, and the
    jit/dispatch cache-miss counters stay flat across the mixed steps."""
    from paddle_tpu.core import op as core_op
    _, eng = stub_engine
    combos = [
        dict(max_new_tokens=3),
        dict(max_new_tokens=5, decode_strategy="sampling",
             temperature=0.7, seed=1),
        dict(max_new_tokens=4, decode_strategy="sampling", top_k=3, seed=2),
        dict(max_new_tokens=6, decode_strategy="sampling", top_p=0.8,
             temperature=1.3, seed=3),
        dict(max_new_tokens=3, decode_strategy="sampling", top_k=5,
             top_p=0.9, seed=4),
    ]
    rng = np.random.RandomState(0)
    before = eng.compile_counts()
    disp_before = core_op.dispatch_cache_stats()["misses"]
    resps = []
    for i in range(22):
        plen = int(rng.randint(2, 8))
        resps.append(eng.submit(rng.randint(0, 24, (plen,)),
                                **combos[i % len(combos)]))
        eng.step()
    eng.run_until_drained(timeout=120)
    for r in resps:
        assert r.done() and r.error is None
    after = eng.compile_counts()
    assert after == before, "mixed sampling params must not retrace"
    assert after["total"] <= after["bound"] == len(eng.buckets) + 1
    assert core_op.dispatch_cache_stats()["misses"] == disp_before


def test_sampling_deterministic_per_seed(stub_engine):
    _, eng = stub_engine
    kw = dict(max_new_tokens=5, decode_strategy="sampling", top_k=4, seed=9)
    a = eng.submit([1, 2, 3], **kw)
    eng.run_until_drained(timeout=60)
    b = eng.submit([1, 2, 3], **kw)
    eng.run_until_drained(timeout=60)
    assert a.tokens() == b.tokens()


# ---------------------------------------------------------------------------
# scheduler edge cases
# ---------------------------------------------------------------------------

def test_queue_full_rejection_backpressure():
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=1, max_len=16, prefill_buckets=(8,),
                        max_queue_depth=2)
    rejects0 = stat_get("STAT_serving_rejects")
    eng.submit([1, 2], max_new_tokens=3)
    eng.submit([1, 2], max_new_tokens=3)
    with pytest.raises(QueueFullError):
        eng.submit([1, 2], max_new_tokens=3)
    assert stat_get("STAT_serving_rejects") == rejects0 + 1
    eng.run_until_drained(timeout=60)  # the queued two still complete
    assert eng.scheduler.queue_depth() == 0


def test_deadline_expiry_mid_decode_frees_slot(stub_engine):
    _, eng = stub_engine
    r = eng.submit(np.arange(4), max_new_tokens=25, deadline=0.03)
    eng.step()  # prefill + first decode chunk
    assert eng.scheduler.occupancy() == 1
    time.sleep(0.05)
    eng.step()  # sweep notices the expired deadline
    with pytest.raises(DeadlineExceededError):
        r.tokens(timeout=5)
    assert r.finish_reason == "error"
    assert eng.scheduler.occupancy() == 0
    assert eng.scheduler.free_slot_count() == eng.max_slots


def test_deadline_expiry_while_queued(stub_engine):
    _, eng = stub_engine
    r = eng.submit(np.arange(4), max_new_tokens=5, deadline=0.01)
    time.sleep(0.03)
    eng.step()
    with pytest.raises(DeadlineExceededError):
        r.tokens(timeout=5)


def _admissions():
    """Prefills reached: the tracer's count of `serving_admit` spans."""
    from paddle_tpu.observability import get_tracer
    return get_tracer().aggregates().get("serving_admit", [0, 0.0])[0]


def test_cancel_before_prefill(stub_engine):
    _, eng = stub_engine
    prefills0 = _admissions()
    r = eng.submit(np.arange(4), max_new_tokens=5)
    r.cancel()
    eng.step()
    with pytest.raises(RequestCancelled):
        r.tokens(timeout=5)
    assert _admissions() == prefills0, \
        "cancelled-before-prefill must never reach the device"
    assert eng.scheduler.free_slot_count() == eng.max_slots


def test_cancel_mid_decode_recycles_slot(stub_engine):
    _, eng = stub_engine
    r = eng.submit(np.arange(4), max_new_tokens=25)
    eng.step()
    assert len(r.tokens_so_far()) >= 1
    r.cancel()
    eng.step()
    with pytest.raises(RequestCancelled):
        r.tokens(timeout=5)
    assert eng.scheduler.free_slot_count() == eng.max_slots


# ---------------------------------------------------------------------------
# per-request fault handling
# ---------------------------------------------------------------------------

def test_oversize_requests_rejected_individually(stub_engine):
    _, eng = stub_engine
    with pytest.raises(InvalidArgumentError):
        eng.submit(np.arange(9), max_new_tokens=2)  # > largest bucket (8)
    with pytest.raises(InvalidArgumentError):
        eng.submit(np.arange(4), max_new_tokens=40)  # 4 + 40 > max_len 32
    r = eng.submit(np.arange(4), max_new_tokens=3)  # engine keeps serving
    eng.run_until_drained(timeout=60)
    assert r.error is None and len(r.tokens()) == 3


@pytest.mark.faults
def test_nan_logits_poisons_one_request_not_the_batch():
    """PDTPU_FAULT_NAN_LOGITS=N: request N's decode logits go NaN — it must
    error individually, its slot recycled, every other slot unharmed."""
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    faults.enable("nan_logits", "1")
    try:
        eng = ServingEngine(m, max_slots=3, max_len=32, prefill_buckets=(8,),
                            decode_chunk=2)
        r0 = eng.submit(np.arange(4), max_new_tokens=6)
        r1 = eng.submit(np.arange(4), max_new_tokens=6)  # seq 1: poisoned
        r2 = eng.submit(np.arange(4), max_new_tokens=6)
        eng.run_until_drained(timeout=120)
    finally:
        faults.reset()
    with pytest.raises(NonFiniteLogitsError):
        r1.tokens(timeout=5)
    assert r0.tokens() == r2.tokens() and len(r0.tokens()) == 6
    assert eng.scheduler.free_slot_count() == eng.max_slots
    assert eng.metrics()["requests_errored"] == 1
    assert eng.metrics()["requests_completed"] == 2


def test_clean_engine_has_no_poison_branch(stub_engine):
    """Without the fault armed the decode trace must carry zero fault
    code (presence is decided at engine-construction trace time)."""
    _, eng = stub_engine
    assert eng._poison_target is None


# ---------------------------------------------------------------------------
# background loop + streaming
# ---------------------------------------------------------------------------

def test_streaming_iterator_with_background_loop():
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=2, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    eng.warmup()
    with eng:
        eng.start()
        r = eng.submit(np.arange(5), max_new_tokens=7)
        streamed = list(r)
        assert len(streamed) == 7
        assert streamed == r.tokens(timeout=5)
        assert r.ttft is not None
        met = eng.metrics()
        assert met["tokens_out"] >= 7
        assert met["ttft_p50_ms"] is not None


def test_engine_loop_death_fails_requests_instead_of_hanging():
    """A crash inside the background loop must error every outstanding
    response and make further submits refuse — never leave a consumer
    blocked in tokens()/iteration forever."""
    from paddle_tpu.core.errors import UnavailableError
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=2, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    eng.warmup()

    def boom(*a, **k):
        raise RuntimeError("injected decode crash")

    eng._decode_fn = boom
    eng.start()
    r = eng.submit(np.arange(4), max_new_tokens=9)
    with pytest.raises(UnavailableError, match="injected decode crash"):
        r.tokens(timeout=10)
    # the engine refuses new work with the recorded cause
    with pytest.raises(UnavailableError, match="died"):
        eng.submit(np.arange(4), max_new_tokens=2)
    eng.close()


def test_close_fails_outstanding_requests_instead_of_hanging():
    paddle.seed(3)
    m = ProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=1, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    active = eng.submit(np.arange(4), max_new_tokens=20)
    queued = eng.submit(np.arange(4), max_new_tokens=20)
    eng.step()  # `active` holds the slot mid-decode, `queued` waits
    eng.close()
    for r in (active, queued):
        with pytest.raises(RequestCancelled, match="engine closed"):
            r.tokens(timeout=10)
    from paddle_tpu.core.errors import UnavailableError
    with pytest.raises(UnavailableError, match="closed"):
        eng.submit(np.arange(2), max_new_tokens=2)


# ---------------------------------------------------------------------------
# inference.Config serving mode
# ---------------------------------------------------------------------------

def test_serving_predictor_in_memory_and_profile_report():
    from paddle_tpu.inference import Config, create_predictor
    model = tiny_gpt()
    cfg = Config()
    cfg.enable_serving(model=model, max_slots=2, max_len=48,
                       prefill_buckets=(8,), decode_chunk=2, start=False)
    cfg.enable_profile()
    cfg.set_cpu_math_library_num_threads(3)
    pred = create_predictor(cfg)
    try:
        prompt = [1, 2, 3, 4]
        r = pred.submit(prompt, max_new_tokens=5)
        pred.engine.run_until_drained(timeout=120)
        assert r.tokens() == solo(model, prompt, 5)
        rep = pred.profile_report()
        # the accepted-but-recorded knobs surface next to serving metrics
        assert rep["config"]["threads"] == 3
        assert rep["config"]["ir_optim"] is True
        assert rep["config"]["memory_optim"] is False
        assert rep["serving"]["requests_completed"] >= 1
        assert rep["serving"]["compile_counts"]["total"] <= 2
        assert any(k.startswith("STAT_serving_") for k in rep["stats"])
        assert "serving=True" in cfg.summary()
    finally:
        pred.close()


def test_serving_predictor_from_artifact(tmp_path):
    """model_provider + jit.save artifact: weights restored, streams match
    the in-memory model."""
    from paddle_tpu.inference import Config, create_predictor
    model = tiny_gpt()
    path = str(tmp_path / "gpt_srv")
    paddle.jit.save(model, path)  # weights-only artifact is enough
    cfg = Config()
    cfg.set_model(path)
    cfg.enable_serving(model_provider=tiny_gpt, max_slots=2, max_len=48,
                       prefill_buckets=(8,), decode_chunk=2, start=False,
                       warmup=False)
    pred = create_predictor(cfg)
    try:
        r = pred.submit([3, 1, 4], max_new_tokens=4)
        pred.engine.run_until_drained(timeout=120)
        assert r.tokens() == solo(model, [3, 1, 4], 4)
    finally:
        pred.close()


def test_enable_serving_validates_arguments():
    from paddle_tpu.inference import Config
    cfg = Config()
    with pytest.raises(ValueError):
        cfg.enable_serving()
    with pytest.raises(ValueError):
        cfg.enable_serving(model=object(), model_provider=lambda: None)


def test_one_shot_predictor_profile_report(tmp_path):
    from paddle_tpu.inference import Config, create_predictor
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 4))
    net.eval()
    path = str(tmp_path / "oneshot")
    paddle.jit.save(net, path, input_spec=[
        paddle.static.InputSpec([2, 8], "float32")])
    cfg = Config(path)
    cfg.enable_memory_optim()
    pred = create_predictor(cfg)
    h = pred.get_input_handle("x0")
    h.copy_from_cpu(np.zeros((2, 8), np.float32))
    pred.run()
    rep = pred.profile_report()
    assert rep["config"]["memory_optim"] is True
    assert rep["stats"].get("STAT_predictor_runs", 0) >= 1
    assert "serving" not in rep


# ---------------------------------------------------------------------------
# probe smoke (fresh interpreter: slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_probe_smoke(cpu8_env):
    import json
    env = cpu8_env
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "probes", "serving_probe.py"),
         "--steps", "3"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert proc.returncode == 0, (proc.stderr or proc.stdout)[-800:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("SERVE")]
    assert lines, proc.stdout[-400:]
    out = json.loads(lines[-1][len("SERVE"):])
    assert out["smoke"] is True
    assert "failures" not in out, out.get("failures")
    assert out["compile_counts"]["total"] <= out["compile_counts"]["bound"]
    assert out["metrics"]["requests_completed"] == 3
