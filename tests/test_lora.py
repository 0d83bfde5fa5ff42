"""Batched multi-tenant LoRA (ISSUE 19): train-side rank-r wrappers,
export -> registry round-trip over the sha256-verified artifact format,
and serve-side batched adapters where the per-slot adapter id is a
DYNAMIC input to the same compiled program family — heterogeneous
adapters batch in one tick at the unchanged compile bound, adapter id 0
is bit-identical to a no-LoRA engine, and hot-load reaches subprocess
workers over the chunked verified channel.

Tier-1 keeps every engine test on the tiny GPT with one prefill bucket
and <= 8-token decodes; the fleet hot-load smoke uses one REMOTE
--listen worker under a hard SIGALRM timeout (the subprocess-worker
variant rides `slow`).  The throughput/ship-latency bars live in
probes/lora_probe.py (bench `detail.lora`), smoked under `slow`.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import lora, models, nn
from paddle_tpu import optimizer as popt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.lora import (AdapterExhaustedError, AdapterIntegrityError,
                             AdapterNotFoundError, AdapterRegistry,
                             LoRAConfig, base_weights_hash)
from paddle_tpu.serving import (FleetRouter, ServingEngine, ServingGateway,
                                TenantConfig)
from paddle_tpu.utils import faults

pytestmark = pytest.mark.lora

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GPT_KW = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=2, hidden_dropout_prob=0.0,
              attention_probs_dropout_prob=0.0,
              max_position_embeddings=128)
ENGINE_KW = dict(max_slots=4, max_len=64, prefill_buckets=(8,),
                 decode_chunk=2)
LORA_CFG = dict(rank=4, max_adapters=3, targets=("qkv",))


def tiny_model(seed=11):
    paddle.seed(seed)
    m = models.GPTForPretraining(models.GPTConfig(**GPT_KW))
    m.eval()
    return m


def lora_wrapped(factor_seed, base_seed=11, rank=4, targets=("qkv",)):
    """A LoRA-wrapped tiny GPT with deterministic NONZERO factors (a
    fresh wrap has B=0 and would be the base model verbatim)."""
    m = tiny_model(base_seed)
    lora.apply_lora(m, rank=rank, targets=targets)
    rng = np.random.default_rng(factor_seed)
    for lyr in m.sublayers(include_self=True):
        if isinstance(lyr, lora.LoRALinear):
            lyr.lora_A._data = paddle.to_tensor(
                rng.normal(0, 0.2, lyr.lora_A.shape).astype("float32"))._data
            lyr.lora_B._data = paddle.to_tensor(
                rng.normal(0, 0.2, lyr.lora_B.shape).astype("float32"))._data
    return m


@pytest.fixture(scope="module")
def adapters(tmp_path_factory):
    """Three exported adapter artifacts against the seed-11 base
    (module-scoped: exports are deterministic and no test mutates
    them)."""
    tmp = tmp_path_factory.mktemp("lora_adapters")
    out = {}
    for name, seed in (("a1", 101), ("a2", 202), ("a3", 303)):
        path = str(tmp / f"{name}.npz")
        sha = lora.export_adapter(lora_wrapped(seed), path)
        out[name] = (path, sha)
    return out


def drain(eng, timeout=120):
    t0 = time.monotonic()
    while eng.has_work():
        eng.step()
        if time.monotonic() - t0 > timeout:
            raise AssertionError("engine drain timeout")


def stream(eng, prompt, max_new, adapter=None):
    resp = eng.submit(prompt, max_new, adapter=adapter)
    drain(eng)
    return resp.tokens(timeout=5)


def serving_compiles():
    from paddle_tpu import observability
    reg = observability.get_program_registry()
    return {k: v["compiles"] for k, v in reg.snapshot().items()
            if k.startswith("serving_")}


# ---------------------------------------------------------------------------
# train side: eager parity, frozen base
# ---------------------------------------------------------------------------

def test_lora_linear_matches_dense_merged_oracle():
    """y = base(x) + scaling*(x@A)@B must equal the dense layer built
    from merged_weight() — the offline-merge contract; and a fresh wrap
    (B=0) is the base layer bit-for-bit."""
    paddle.seed(3)
    base = nn.Linear(16, 24)
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        0, 1, (5, 16)).astype("float32"))
    before = base(x).numpy()
    wrapped = lora.LoRALinear(base, rank=4)
    np.testing.assert_array_equal(wrapped(x).numpy(), before)
    rng = np.random.default_rng(1)
    wrapped.lora_A._data = paddle.to_tensor(
        rng.normal(0, 0.3, (16, 4)).astype("float32"))._data
    wrapped.lora_B._data = paddle.to_tensor(
        rng.normal(0, 0.3, (4, 24)).astype("float32"))._data
    want = x.numpy() @ np.asarray(wrapped.merged_weight())
    want = want + base.bias.numpy()
    np.testing.assert_allclose(wrapped(x).numpy(), want, atol=1e-5)


def test_apply_lora_freezes_base_and_trains_only_factors():
    """apply_lora leaves ONLY the rank-r factors trainable; optimizer
    steps move them while every base parameter (and the recorded base
    hash) stays bit-identical — the frozen-base proof."""
    m = tiny_model()
    base_hash = base_weights_hash(m)
    wrapped = lora.apply_lora(m, rank=4, targets=("qkv",))
    assert len(wrapped) == GPT_KW["num_hidden_layers"]
    trainable = [p for p in m.parameters() if p.trainable]
    assert trainable and all(
        any(s in n for s in ("lora_A", "lora_B"))
        for n, _ in m.named_parameters() if _.trainable)
    base_snap = {n: p.numpy().copy() for n, p in m.named_parameters()
                 if not p.trainable}
    o = popt.Adam(0.05, parameters=trainable)
    ids = paddle.to_tensor(np.arange(1, 9, dtype=np.int64)[None])
    labels = paddle.to_tensor(np.arange(2, 10, dtype=np.int64)[None])
    losses = m(ids, labels=labels)
    losses.sum().backward()
    o.step()
    o.clear_grad()
    moved = [n for n, p in m.named_parameters()
             if p.trainable and np.abs(p.numpy()).sum() > 0
             and "lora_B" in n]
    assert moved, "training must move the adapter factors"
    for n, p in m.named_parameters():
        if not p.trainable:
            np.testing.assert_array_equal(p.numpy(), base_snap[n])
    # the hash strips the wrapper's `.base.` path segment and skips the
    # factors: training an adapter never changes the recorded base
    assert base_weights_hash(m) == base_hash


def test_lora_wrapper_grad_parity_and_adapter_restore(tmp_path):
    """The wrapper's factor gradients match the dense merged-weight
    calculus — for y = x(W + sAB): dL/dA = s*(dL/dW)Bᵀ and dL/dB =
    s*Aᵀ*(dL/dW) — and an exported adapter restores bit-identically
    into a fresh wrap via the train-side `load_adapter`."""

    class Probe(nn.Layer):
        def __init__(self, seed):
            super().__init__()
            paddle.seed(seed)
            self.qkv = nn.Linear(8, 6)

        def forward(self, x):
            return self.qkv(x)

    w = lora.LoRAWrapper(Probe(5), rank=2, targets=("qkv",))
    assert w.paths == ["qkv"]
    rng = np.random.default_rng(9)
    lyr = w.model.qkv
    lyr.lora_A._data = paddle.to_tensor(
        rng.normal(0, 0.3, (8, 2)).astype("float32"))._data
    lyr.lora_B._data = paddle.to_tensor(
        rng.normal(0, 0.3, (2, 6)).astype("float32"))._data
    assert all("lora_" in n for n, p in w.named_parameters()
               if p.trainable)
    x = paddle.to_tensor(rng.normal(0, 1, (4, 8)).astype("float32"))
    w(x).sum().backward()
    # dense oracle: a fresh layer carrying the merged weight, same loss
    dense = Probe(5)
    dense.qkv.weight._data = paddle.to_tensor(
        np.asarray(lyr.merged_weight()))._data
    dense(x).sum().backward()
    dW = dense.qkv.weight.grad
    s = lyr.scaling
    A = lyr.lora_A.numpy()
    B = lyr.lora_B.numpy()
    np.testing.assert_allclose(np.asarray(lyr.lora_A.grad),
                               s * np.asarray(dW) @ B.T, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lyr.lora_B.grad),
                               s * A.T @ np.asarray(dW), atol=1e-5)
    # export -> fresh wrap -> load_adapter: bit-identical forward
    path = str(tmp_path / "probe.npz")
    w.export(path)
    w2 = lora.LoRAWrapper(Probe(5), rank=2, targets=("qkv",))
    assert w2(x).numpy().tolist() != w(x).numpy().tolist()
    w2.load(path)
    np.testing.assert_array_equal(w2(x).numpy(), w(x).numpy())
    # typed mismatch: an unwrapped model cannot restore an adapter
    with pytest.raises(InvalidArgumentError, match="no LoRALinear"):
        lora.load_adapter(Probe(5), path)
    # typed mismatch: wrong rank never half-loads
    w3 = lora.LoRAWrapper(Probe(5), rank=4, targets=("qkv",))
    with pytest.raises(InvalidArgumentError, match="rank"):
        w3.load(path)


# ---------------------------------------------------------------------------
# artifact + registry: round-trip, verification, LRU/pin lifecycle
# ---------------------------------------------------------------------------

def test_export_register_round_trip_and_typed_rejects(tmp_path, adapters):
    base = tiny_model()
    shapes = lora.attach_serving_lora(base, ("qkv",))
    sha = base_weights_hash(base)
    reg = AdapterRegistry(LoRAConfig(**LORA_CFG), shapes, base_sha=sha)
    path, file_sha = adapters["a1"]
    idx = reg.register("a1", path)
    assert idx == 1 and reg.loaded() == {"a1": 1}
    assert reg.file_sha(idx) == file_sha
    # idempotent by artifact sha: the zero-byte re-attach key
    loads_before = reg.stats()["loads"]
    assert reg.register("a1", path) == idx
    assert reg.stats()["loads"] == loads_before
    # wrong base: the artifact records the TRAINING base's hash
    reg_other = AdapterRegistry(
        LoRAConfig(**LORA_CFG), shapes, base_sha="deadbeef" * 8)
    with pytest.raises(AdapterIntegrityError, match="base"):
        reg_other.register("a1", path)
    # ...unless the serving base differs by construction (int8 etc.)
    reg_nocheck = AdapterRegistry(
        LoRAConfig(rank=4, max_adapters=3, targets=("qkv",),
                   check_base_hash=False),
        shapes, base_sha="deadbeef" * 8)
    assert reg_nocheck.register("a1", path) == 1
    # rank is baked into the compiled programs: typed mismatch
    reg_r8 = AdapterRegistry(
        LoRAConfig(rank=8, max_adapters=3, targets=("qkv",)), shapes,
        base_sha=sha)
    with pytest.raises(InvalidArgumentError, match="rank"):
        reg_r8.register("a1", path)
    # truncated artifact: typed, never garbage factors
    bad = str(tmp_path / "trunc.npz")
    with open(path, "rb") as f:
        raw = f.read()
    with open(bad, "wb") as f:
        f.write(raw[:len(raw) // 2])
    with pytest.raises(AdapterIntegrityError):
        reg.register("trunc", bad)


def test_registry_lru_eviction_pinning_and_exhaustion(adapters):
    base = tiny_model()
    shapes = lora.attach_serving_lora(base, ("qkv",))
    reg = AdapterRegistry(
        LoRAConfig(rank=4, max_adapters=2, targets=("qkv",)), shapes,
        base_sha=base_weights_hash(base))
    assert reg.resolve(None) == 0 and reg.acquire("") == 0
    i1 = reg.register("a1", adapters["a1"][0])
    i2 = reg.register("a2", adapters["a2"][0])
    pin1 = reg.acquire("a1")
    assert pin1 == i1
    # full registry: the unpinned LRU slot (a2) is evicted for a3
    i3 = reg.register("a3", adapters["a3"][0])
    assert i3 == i2 and reg.stats()["evictions"] == 1
    with pytest.raises(AdapterNotFoundError, match="a2"):
        reg.resolve("a2")
    # pin the survivor too: nothing evictable -> typed backpressure
    reg.acquire("a3")
    with pytest.raises(AdapterExhaustedError, match="pinned"):
        reg.register("a2", adapters["a2"][0])
    # release unpins; the load then succeeds (evicting LRU a1)
    reg.release(pin1)
    assert reg.register("a2", adapters["a2"][0]) == i1


def test_adapter_corrupt_fault_is_typed_and_clean_on_retry(adapters):
    """PDTPU_FAULT_ADAPTER_CORRUPT=n poisons the n-th adapter artifact
    READ (in memory — the file is untouched), so the typed reject's
    retry succeeds: the supervised re-ship path, garbage factors never
    load."""
    path, _ = adapters["a1"]
    try:
        faults.enable("adapter_corrupt", "1")
        with pytest.raises(AdapterIntegrityError):
            lora.read_adapter(path)
        header, factors, _ = lora.read_adapter(path)  # retry: clean
        assert header["rank"] == 4 and factors
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# serving engine: adapter id 0 bit-identity, mixed batches, zero compiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", [dict(), dict(kv="paged", block_size=4)],
                         ids=["fixed", "paged"])
def test_engine_base_bit_identity_mixed_batch_and_swap_survival(adapters, kv):
    """Over both cache views (the oracle engine stays fixed and plain):
    the lora engine's adapter-id-0 streams are bit-identical to a
    separately built no-LoRA engine; a heterogeneous batch (base + two
    adapters on four slots IN ONE TICK) reproduces each stream's solo
    single-adapter oracle bit-for-bit; nothing compiles after warmup —
    a new adapter is a dynamic input, never a new program.  Then the
    PR-19 refresh path composes: `swap_weights` flips the BASE while
    loaded adapters survive (the factor stacks are registry state, not
    engine state) — an identity flip is bit-identical on base AND
    adapter streams, a real flip changes both streams, keeps the
    registry loaded, compiles nothing, and re-pins the registry's
    expected base so a later register() checks artifacts against the
    base actually being served."""
    from paddle_tpu.jit import state_arrays
    plain = ServingEngine(tiny_model(), **ENGINE_KW)
    eng = ServingEngine(tiny_model(), lora=LoRAConfig(**LORA_CFG),
                        **ENGINE_KW, **kv)
    plain.warmup()
    eng.warmup()
    eng.load_adapter("a1", adapters["a1"][0])
    eng.load_adapter("a2", adapters["a2"][0])
    mark = serving_compiles()
    prompts = [np.arange(1 + i, 6 + i, dtype=np.int32) for i in range(2)]
    # solo oracles: one request at a time on each engine
    want_base = [stream(plain, p, 8) for p in prompts]
    assert [stream(eng, p, 8) for p in prompts] == want_base
    solo = {name: stream(eng, prompts[0], 8, adapter=name)
            for name in ("a1", "a2")}
    assert solo["a1"] != want_base[0] and solo["a1"] != solo["a2"]
    # heterogeneous batch: all four admitted before any step
    mix = [eng.submit(prompts[0], 8, adapter="a1"),
           eng.submit(prompts[0], 8, adapter="a2"),
           eng.submit(prompts[0], 8),
           eng.submit(prompts[0], 8, adapter="a1")]
    drain(eng)
    assert mix[0].tokens(timeout=5) == solo["a1"]
    assert mix[1].tokens(timeout=5) == solo["a2"]
    assert mix[2].tokens(timeout=5) == want_base[0]
    assert mix[3].tokens(timeout=5) == solo["a1"]
    assert serving_compiles() == mark, "adapters must not compile"
    cc = eng.compile_counts()
    assert cc["total"] <= cc["bound"], cc
    # unknown adapter: typed at admission, never a hung consumer
    with pytest.raises(AdapterNotFoundError, match="ghost"):
        eng.make_request(prompts[0], 4, adapter="ghost")
    m = eng.metrics()["lora"]
    assert m["loaded"] == 2 and sorted(m["adapters"]) == ["a1", "a2"]
    plain.close()
    # -- swap survival on the SAME engine -------------------------------
    # identity flip: same seed -> same weights -> bit-identical streams
    eng.swap_weights(state_arrays(tiny_model(11)))
    assert stream(eng, prompts[0], 8, adapter="a1") == solo["a1"]
    assert stream(eng, prompts[0], 8) == want_base[0]
    # real flip: both streams move, adapters stay resident, no compile
    eng.swap_weights(state_arrays(tiny_model(7)), weights_sha="v2")
    got_base = stream(eng, prompts[0], 8)
    got_ad = stream(eng, prompts[0], 8, adapter="a1")
    assert got_base != want_base[0], "the flip must change the base"
    assert got_ad != got_base, "the adapter must act on the new base"
    m = eng.metrics()["lora"]
    assert m["loaded"] == 2 and sorted(m["adapters"]) == ["a1", "a2"]
    assert serving_compiles() == mark, "swap must not compile"
    # the registry's base pin followed the flip: an artifact trained
    # against the OLD base is now a typed reject
    with pytest.raises(AdapterIntegrityError, match="base"):
        eng.load_adapter("a3", adapters["a3"][0])
    eng.close()


@pytest.mark.slow
def test_paged_engine_mixed_adapters_parity(adapters):
    eng = ServingEngine(tiny_model(), lora=LoRAConfig(**LORA_CFG),
                        kv="paged", block_size=8, **ENGINE_KW)
    eng.warmup()
    eng.load_adapter("a1", adapters["a1"][0])
    eng.load_adapter("a2", adapters["a2"][0])
    mark = serving_compiles()
    prompt = np.arange(1, 6, dtype=np.int32)
    solo = {name: stream(eng, prompt, 12, adapter=name)
            for name in (None, "a1", "a2")}
    assert solo["a1"] != solo[None] != solo["a2"]
    mix = [eng.submit(prompt, 12, adapter=a)
           for a in (None, "a1", "a2", "a1")]
    drain(eng)
    got = [r.tokens(timeout=5) for r in mix]
    assert got == [solo[None], solo["a1"], solo["a2"], solo["a1"]]
    assert serving_compiles() == mark
    eng.close()


@pytest.mark.slow
def test_int8_base_composes_with_fp32_adapters(adapters):
    """Int8 weight-only serving bases wrap identically (the post-hook
    adds an fp32 delta on top of the int8 matmul); the training base
    hash no longer matches by construction, so check_base_hash=False is
    the documented opt-out."""
    from paddle_tpu.quantization import quantize_for_serving
    m = tiny_model()
    quantize_for_serving(m)
    eng = ServingEngine(m, lora=LoRAConfig(
        rank=4, max_adapters=3, targets=("qkv",), check_base_hash=False),
        **ENGINE_KW)
    eng.warmup()
    eng.load_adapter("a1", adapters["a1"][0])
    prompt = np.arange(1, 6, dtype=np.int32)
    base_s = stream(eng, prompt, 8)
    ad_s = stream(eng, prompt, 8, adapter="a1")
    assert base_s != ad_s, "the adapter must act on the int8 base"
    eng.close()


def test_lora_combination_rejects_name_both_knobs():
    m = tiny_model()
    draft = tiny_model(7)
    with pytest.raises(InvalidArgumentError) as ei:
        ServingEngine(m, lora=LoRAConfig(**LORA_CFG), draft_model=draft,
                      **ENGINE_KW)
    assert "lora" in str(ei.value) and "draft_model" in str(ei.value)
    with pytest.raises(InvalidArgumentError) as ei:
        ServingEngine(m, lora=LoRAConfig(**LORA_CFG), kv="paged",
                      block_size=8, prefix_cache=True, **ENGINE_KW)
    assert "lora" in str(ei.value) and "prefix_cache" in str(ei.value)
    # the PR-17 bare reject, reworded: names both knobs + the workaround
    with pytest.raises(InvalidArgumentError) as ei:
        ServingEngine(m, prefix_cache=True, **ENGINE_KW)
    msg = str(ei.value)
    assert "prefix_cache" in msg and "kv=" in msg and "paged" in msg
    # the documented PR-17 composition gap: speculative decoding and
    # prefix reuse reject typed AT CONSTRUCTION, naming both knobs —
    # never a silently-incoherent draft KV on a warm prefix hit
    with pytest.raises(InvalidArgumentError) as ei:
        ServingEngine(m, draft_model=draft, kv="paged", block_size=8,
                      prefix_cache=True, **ENGINE_KW)
    msg = str(ei.value)
    assert "prefix_cache" in msg and "draft_model" in msg


# ---------------------------------------------------------------------------
# gateway: tenant -> adapter mapping, typed unknown-adapter rejection
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gateway_tenant_adapter_stamping_and_typed_reject(adapters):
    eng = ServingEngine(tiny_model(), lora=LoRAConfig(**LORA_CFG),
                        **ENGINE_KW)
    eng.warmup()
    eng.load_adapter("a1", adapters["a1"][0])
    prompt = np.arange(1, 6, dtype=np.int32)
    want = stream(eng, prompt, 12, adapter="a1")
    want_base = stream(eng, prompt, 12)
    gw = ServingGateway(eng, tenants={
        "acme": TenantConfig(adapter="a1"),
        "ghost-inc": TenantConfig(adapter="ghost"),
    })
    gw.start()
    try:
        assert gw.submit(prompt, 12, tenant="acme").tokens(
            timeout=60) == want
        assert gw.submit(prompt, 12).tokens(timeout=60) == want_base
        # unloaded adapter: terminal typed failure through the normal
        # admission path — never a hung consumer
        r = gw.submit(prompt, 12, tenant="ghost-inc")
        with pytest.raises(AdapterNotFoundError):
            r.tokens(timeout=60)
        assert r.done() and isinstance(r.error, AdapterNotFoundError)
        # /healthz lists the loaded adapters' artifact shas — the
        # operator's "is tenant X resident on THIS replica" answer
        status, _, payload = gw.handle("GET", "/healthz")
        assert status == 200
        hz = json.loads(payload)
        assert hz["lora"]["shas"] == {"a1": adapters["a1"][1]}
    finally:
        gw.close()
    from paddle_tpu.observability import report
    rep = report()
    assert rep["lora"]["adapters_loaded"] >= 1
    assert rep["lora"]["rejects"] >= 1


# ---------------------------------------------------------------------------
# fleet: fleet-wide hot-load (in-process + REMOTE worker), convergence
# ---------------------------------------------------------------------------

@pytest.fixture
def hard_timeout():
    def handler(signum, frame):
        raise TimeoutError("lora worker hard per-test timeout")
    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(150)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def test_fleet_hot_load_remote_worker_reship_and_convergence(hard_timeout,
                                                             adapters):
    """Fleet-wide hot-load across a MIXED fleet — one in-process replica
    plus one REMOTE `--listen` worker attached over TCP: the artifact
    ships chunked + sha256-verified, `load_adapter` returns every
    replica's file sha, the adapter stream is identical from both
    replicas (the in-process engine is the oracle), a poisoned first
    read INSIDE the remote worker is re-shipped supervised, an unknown
    adapter fails the stream typed over the wire, NO replica restarts
    (hot-load is not a rollout), every health snapshot lists the
    adapter's sha, and a replica warmed AFTER the load converges onto
    the recorded adapter set.  (The same legs against a SUBPROCESS
    worker run under `slow`.)"""
    import threading
    env = dict(os.environ)
    env["PYTHONPATH"] = (_REPO + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else _REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving.worker",
         "--listen", "127.0.0.1:0", "--index", "0"],
        stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=env,
        start_new_session=True)
    mk = lambda: ServingEngine(tiny_model(), lora=LoRAConfig(**LORA_CFG),
                               **ENGINE_KW)
    fleet = None
    try:
        while True:  # SIGALRM guards the wait
            line = proc.stdout.readline()
            assert line, "remote worker exited before listening"
            if "worker listening on" in line:
                addr = line.strip().rsplit(" ", 1)[-1]
                break
        threading.Thread(target=lambda: proc.stdout.read(),
                         daemon=True).start()
        spec = {"model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                          "kwargs": dict(GPT_KW, seed=11)},
                "engine": dict(ENGINE_KW, prefill_buckets=[8]),
                "lora": dict(LORA_CFG, targets=["qkv"])}
        fleet = FleetRouter([mk()])
        remote_rid = fleet.add_worker(spec, address=addr,
                                      boot_timeout_s=140.0)
        fleet.warmup()
        rids0 = sorted(r.id for r in fleet.manager.replicas())
        path, sha = adapters["a1"]
        got = fleet.load_adapter("a1", path)
        assert sorted(got) == rids0 and set(got.values()) == {sha}
        # the stream is replica-independent: force a request through
        # EACH replica directly and compare the adapter streams
        prompt = np.arange(1, 6, dtype=np.int32)
        want = None
        for rep in fleet.manager.replicas():
            req, resp = rep.engine.make_request(prompt, 8, adapter="a1")
            rep.engine.scheduler.submit(req, resp)
            t0 = time.monotonic()
            while not resp.done():
                fleet.step()
                assert time.monotonic() - t0 < 120
            toks = resp.tokens(timeout=5)
            assert toks
            if want is None:
                want = toks
            assert toks == want, "replicas diverged on one adapter"
        # corrupt first read INSIDE the remote worker -> typed ->
        # supervised re-ship, no restart
        rem = next(r for r in fleet.manager.replicas()
                   if r.id == remote_rid)
        rem.engine.set_fault("adapter_corrupt", "1")
        got2 = fleet.load_adapter("a2", adapters["a2"][0])
        assert set(got2.values()) == {adapters["a2"][1]}
        from paddle_tpu.observability import report
        assert report()["serving"]["adapter_ship_retries"] >= 1
        # unknown adapter: typed terminal over the wire
        requ, respu = rem.engine.make_request(prompt, 4, adapter="nope")
        rem.engine.scheduler.submit(requ, respu)
        while not respu.done():
            fleet.step()
        assert isinstance(respu.error, AdapterNotFoundError)
        assert rem.engine.post_warmup_compiles() == 0
        # hot-load is NOT a rollout: same replica set, zero restarts,
        # and every replica's health snapshot lists the adapter sha
        deadline = time.monotonic() + 30
        while True:
            fleet.step()  # status frames carry the worker's registry
            snaps = fleet.health()["replicas"]
            if all((s.get("adapters") or {}).get("a1") == sha
                   for s in snaps.values()):
                break
            assert time.monotonic() < deadline, snaps
            time.sleep(0.02)
        assert sorted(r.id for r in fleet.manager.replicas()) == rids0
        assert all(int(s.get("restarts") or 0) == 0
                   for s in snaps.values())
        # a replica warmed AFTER the load converges onto the recorded
        # adapter set — a boot must not silently drop a tenant's adapter
        fleet.add_replica(mk())
        fleet.warmup()
        for rep in fleet.manager.replicas():
            assert "a1" in rep.engine.metrics()["lora"]["adapters"]
        srv = report()["serving"]
        assert srv["adapter_loads"] >= 2 and srv["adapter_active"] >= 1
    finally:
        if fleet is not None:
            fleet.close()
        proc.kill()
        proc.wait(timeout=10)


@pytest.mark.slow
def test_subprocess_worker_hot_load_and_reship(hard_timeout, adapters):
    """One SUBPROCESS worker booted with a lora spec: load_adapter over
    the RPC pages the artifact in (sha-verified), adapter streams are
    bit-identical to an in-process lora oracle, a poisoned first read
    inside the worker is re-shipped supervised, and an unknown adapter
    fails the stream typed over the wire."""
    from paddle_tpu.serving.worker import WorkerClient
    spec = {"model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                      "kwargs": dict(GPT_KW, seed=11)},
            "engine": dict(ENGINE_KW, prefill_buckets=[8]),
            "lora": dict(rank=4, max_adapters=3, targets=["qkv"])}
    wc = WorkerClient(spec, index=0, boot_timeout_s=180.0)
    try:
        while not wc.poll_ready():
            time.sleep(0.05)
        p1, sha1 = adapters["a1"]
        assert wc.load_adapter("a1", p1) == sha1
        eng = ServingEngine(tiny_model(), lora=LoRAConfig(**LORA_CFG),
                            **ENGINE_KW)
        eng.warmup()
        eng.load_adapter("a1", p1)
        prompt = np.arange(1, 6, dtype=np.int32)
        want = stream(eng, prompt, 8, adapter="a1")
        eng.close()
        req, resp = wc.make_request(prompt, 8, adapter="a1")
        wc.scheduler.submit(req, resp)
        while not resp.done():
            wc.step()
        assert resp.tokens(timeout=5) == want
        # corrupt first read INSIDE the worker -> typed -> re-ship ok
        wc.set_fault("adapter_corrupt", "1")
        assert wc.load_adapter("a2", adapters["a2"][0]) == adapters["a2"][1]
        from paddle_tpu.observability import report
        assert report()["serving"]["adapter_ship_retries"] >= 1
        # unknown adapter: typed terminal over the wire
        requ, respu = wc.make_request(prompt, 4, adapter="nope")
        wc.scheduler.submit(requ, respu)
        while not respu.done():
            wc.step()
        assert isinstance(respu.error, AdapterNotFoundError)
        assert wc.post_warmup_compiles() == 0
    finally:
        wc.close()


# ---------------------------------------------------------------------------
# probe smoke (slow tier): parity-only, tiny shapes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lora_probe_smoke():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "probes", "lora_probe.py"),
         "--steps", "3"],
        capture_output=True, text=True, timeout=900, env=env, cwd=_REPO)
    assert proc.returncode == 0, (proc.stderr or proc.stdout)[-800:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("LORA")]
    assert lines, proc.stdout[-400:]
    out = json.loads(lines[-1][len("LORA"):])
    assert out["smoke"] is True
    assert "failures" not in out, out.get("failures")
