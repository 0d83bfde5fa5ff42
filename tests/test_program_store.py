"""Program-lifecycle layer (ISSUE-9): persistent compilation store + AOT
program sets + fleet-grade warmup.

Covers: the store's content-addressed fingerprint (paddle version / op
version / jax version each invalidate), cache-key invalidation (changed
weight dtype/shape must MISS; corrupt entries fall back to a fresh
compile, never a crash), the subprocess-twice tier-1 smoke (second run
hits the disk cache — the fleet cold-start story at minimum size), AOT
program-set save/load round-trips (fixed + paged + mismatch/corruption
rejection + predictor fallback), `TrackedJit.warm`/`TrainStep.warmup`
compile-without-execute semantics, the AOT-fallback telemetry satellite,
and the gateway /healthz store report."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models, nn, observability
from paddle_tpu import optimizer as popt
from paddle_tpu import programs
from paddle_tpu.programs import ProgramSetError
from paddle_tpu.programs.store import get_program_store
from paddle_tpu.serving import ServingEngine

pytestmark = pytest.mark.programs


def tiny_gpt(seed=7, vocab=13):
    cfg = models.GPTConfig(vocab_size=vocab, hidden_size=16,
                           num_hidden_layers=2, num_attention_heads=2,
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


@pytest.fixture()
def store_dir(tmp_path):
    """An enabled store rooted in a tmpdir; ALWAYS disabled after (the
    store mutates global jax config)."""
    d = str(tmp_path / "store")
    programs.enable(d)
    yield d
    programs.disable()


# ---------------------------------------------------------------------------
# fingerprint: the content-addressed key
# ---------------------------------------------------------------------------

def test_fingerprint_folds_in_every_version_axis():
    base = programs.cache_fingerprint(
        paddle_version="1.0", op_versions={"op_a": 1}, jax_version="0.4")
    assert base == programs.cache_fingerprint(
        paddle_version="1.0", op_versions={"op_a": 1}, jax_version="0.4")
    # each axis alone must change the fingerprint (= a fresh cache
    # namespace = a guaranteed miss; stale reuse is impossible)
    assert base != programs.cache_fingerprint(
        paddle_version="1.1", op_versions={"op_a": 1}, jax_version="0.4")
    assert base != programs.cache_fingerprint(
        paddle_version="1.0", op_versions={"op_a": 2}, jax_version="0.4")
    assert base != programs.cache_fingerprint(
        paddle_version="1.0", op_versions={"op_a": 1, "op_b": 1},
        jax_version="0.4")
    assert base != programs.cache_fingerprint(
        paddle_version="1.0", op_versions={"op_a": 1}, jax_version="0.5")


def test_live_fingerprint_tracks_op_version_registry(monkeypatch):
    from paddle_tpu.utils import op_version
    before = programs.cache_fingerprint()
    monkeypatch.setitem(op_version._REGISTRY, "flash_attention",
                        op_version._REGISTRY["flash_attention"] + 1)
    after = programs.cache_fingerprint()
    assert before != after


def test_enable_uses_fingerprinted_subdir_and_stats(store_dir):
    st = programs.store_stats()
    assert st["enabled"]
    assert st["dir"].startswith(store_dir)
    assert os.path.basename(st["dir"]) == f"v-{st['fingerprint']}"
    assert st["fingerprint"] == programs.cache_fingerprint()


def test_cache_placed_from_outside_is_never_moved(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own directory stands whatever
    enable() is told; thresholds and counters still apply there."""
    import jax
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.setenv("PDTPU_PROGRAM_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", outside)  # as at import
    try:
        used = programs.enable(str(tmp_path / "elsewhere"))
        assert used == outside
        assert jax.config.jax_compilation_cache_dir == outside
        assert programs.ensure_enabled()
        assert jax.config.jax_compilation_cache_dir == outside
        st = programs.store_stats()
        assert st["enabled"] and st["dir"] == outside
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        miss0 = st["misses"]
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
        st = programs.store_stats()
        assert st["misses"] == miss0 + 1 and st["entries"] >= 1
        assert not (tmp_path / "elsewhere").exists()
        assert not (tmp_path / "env").exists()
    finally:
        programs.disable()
        jax.config.update("jax_compilation_cache_dir", before)


def test_enable_twice_gives_the_same_directory(tmp_path, monkeypatch):
    """No outside placement: the directory depends on the base and the
    fingerprint alone (the path is part of the cache key — one that moved
    between runs would never hit)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    base = str(tmp_path / "store")
    try:
        first = programs.enable(base)
        programs.disable()
        assert programs.enable(base) == first
        assert first == os.path.join(
            base, f"v-{programs.cache_fingerprint()}")
    finally:
        programs.disable()


# ---------------------------------------------------------------------------
# cache-key invalidation + corruption fallback
# ---------------------------------------------------------------------------

def test_changed_dtype_and_shape_each_miss(store_dir):
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.tanh(x @ x.T).sum()

    jax.jit(f)(jnp.ones((8, 8), jnp.float32)).block_until_ready()
    n1 = programs.store_stats()["entries"]
    assert n1 > 0
    # same program, different SHAPE -> new entry (native jax keying)
    jax.jit(f)(jnp.ones((16, 8), jnp.float32)).block_until_ready()
    n2 = programs.store_stats()["entries"]
    assert n2 > n1
    # same shape, different DTYPE -> new entry
    jax.jit(f)(jnp.ones((8, 8), jnp.bfloat16)).block_until_ready()
    assert programs.store_stats()["entries"] > n2


def test_corrupt_entry_falls_back_to_fresh_compile(store_dir):
    import jax
    import jax.numpy as jnp

    src = "lambda x: (jnp.sin(x) @ x.T).sum()"
    want = float(jax.jit(eval(src, {"jnp": jnp}))(
        jnp.ones((16, 16))).block_until_ready())
    cache_dir = programs.store_stats()["dir"]
    hit = [f for f in os.listdir(cache_dir) if f.endswith("-cache")]
    assert hit
    for name in hit:  # flip bytes in EVERY stored executable
        p = os.path.join(cache_dir, name)
        blob = bytearray(open(p, "rb").read())
        for i in range(0, len(blob), 7):
            blob[i] ^= 0xFF
        open(p, "wb").write(bytes(blob))
    # a fresh function object with the same computation maps to the same
    # cache key -> the corrupt entry is READ, rejected with a warning,
    # and recompiled — never a crash, and the result is still right
    get_program_store()._reset_jax_cache()
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = float(jax.jit(eval(src, {"jnp": jnp}))(
            jnp.ones((16, 16))).block_until_ready())
    assert got == want


def test_subprocess_second_run_hits_cache(tmp_path, cpu8_env):
    """The ISSUE-9 CI smoke: a tiny program compiled in a subprocess
    twice against the same PDTPU_PROGRAM_CACHE_DIR — run 1 writes
    (misses), run 2 reads (hits), purely via the env knob + the
    import-time bootstrap."""
    env = dict(cpu8_env)
    env["PDTPU_PROGRAM_CACHE_DIR"] = str(tmp_path / "store")
    script = (
        "import jax, jax.numpy as jnp, json, sys\n"
        "sys.path.insert(0, %r)\n"
        "import paddle_tpu\n"  # bootstrap enables the store from env
        "from paddle_tpu.programs import store_stats\n"
        "f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())\n"
        "f(jnp.ones((32, 32))).block_until_ready()\n"
        "print('STATS' + json.dumps(store_stats()))\n"
        % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    def run():
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-1500:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("STATS")][0]
        return json.loads(line[len("STATS"):])

    first = run()
    assert first["enabled"] and first["entries"] > 0
    assert first["misses"] > 0 and first["hits"] == 0
    second = run()
    assert second["hits"] > 0, second
    assert second["misses"] == 0, second


# ---------------------------------------------------------------------------
# AOT program sets
# ---------------------------------------------------------------------------

def test_program_set_roundtrip_streams_bit_identical(tmp_path):
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=48, prefill_buckets=(8,),
                        decode_chunk=2)
    rep = eng.warmup()
    assert rep["programs"] == {"prefill_b8": "traced", "decode": "traced"}
    r1 = eng.submit([1, 2, 3], max_new_tokens=6)
    r2 = eng.submit([4, 5], max_new_tokens=6, decode_strategy="sampling",
                    temperature=0.8, top_k=5, seed=11)
    eng.run_until_drained(timeout=240)
    greedy, sampled = r1.tokens(), r2.tokens()
    assert eng.post_warmup_compiles() == 0
    path = eng.save_program_set(str(tmp_path / "tiny"))
    # saving re-traces for export: the engine's own counters must not
    # drift past the compile bound because of it
    cc = eng.compile_counts()
    assert cc["total"] <= cc["bound"]

    eng2 = ServingEngine(m, max_slots=2, max_len=48, prefill_buckets=(8,),
                         decode_chunk=2, program_set=path)
    assert set(eng2.program_set_info["kinds"]) == {"prefill_b8", "decode"}
    rep2 = eng2.warmup()
    # native executables: zero traces, zero compiles, warmup skips exec
    assert all(v == "program_set:exe" for v in rep2["programs"].values())
    assert programs.read_manifest(path)["save_errors"] == {}
    q1 = eng2.submit([1, 2, 3], max_new_tokens=6)
    q2 = eng2.submit([4, 5], max_new_tokens=6, decode_strategy="sampling",
                     temperature=0.8, top_k=5, seed=11)
    eng2.run_until_drained(timeout=240)
    assert q1.tokens() == greedy == solo(m, [1, 2, 3], 6)
    assert q2.tokens() == sampled
    assert eng2.compile_counts()["total"] == 0
    assert eng2.post_warmup_compiles() == 0
    assert eng2.metrics()["program_set"]["kinds"] is not None


def test_program_set_paged_roundtrip(tmp_path):
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,),
                        kv="paged", block_size=8)
    eng.warmup()
    r = eng.submit([1, 2, 3], max_new_tokens=6)
    eng.run_until_drained(timeout=240)
    want = r.tokens()
    path = eng.save_program_set(str(tmp_path / "paged"))
    eng2 = ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,),
                         kv="paged", block_size=8, program_set=path)
    eng2.warmup()
    q = eng2.submit([1, 2, 3], max_new_tokens=6)
    eng2.run_until_drained(timeout=240)
    assert q.tokens() == want == solo(m, [1, 2, 3], 6)
    assert eng2.post_warmup_compiles() == 0
    # a paged artifact must never load into a fixed-layout engine
    with pytest.raises(ProgramSetError):
        ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,),
                      program_set=path)


def test_program_set_stablehlo_fallback_path(tmp_path):
    """When the native executables can't load (version/topology drift),
    the portable StableHLO representation must serve bit-identically —
    with the recorded donate_argnums re-applied (jax.export drops
    donation; losing it silently would copy the whole KV pool per
    tick)."""
    import pickle
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=48, prefill_buckets=(8,),
                        decode_chunk=2)
    eng.warmup()
    r = eng.submit([1, 2, 3], max_new_tokens=6)
    eng.run_until_drained(timeout=240)
    want = r.tokens()
    path = eng.save_program_set(str(tmp_path / "a"))
    # strip the native executables so only stablehlo remains
    with open(path, "rb") as f:
        envelope = pickle.load(f)
    body = pickle.loads(envelope["body"])
    for rec in body["programs"].values():
        assert rec["exe"] is not None and rec["stablehlo"] is not None
        assert rec["donate"] == (1,)
        rec["exe"] = None
    import hashlib
    blob = pickle.dumps(body, protocol=pickle.HIGHEST_PROTOCOL)
    hlo_only = str(tmp_path / "hlo_only.pdprograms")
    with open(hlo_only, "wb") as f:
        pickle.dump({"format": envelope["format"],
                     "sha256": hashlib.sha256(blob).hexdigest(),
                     "body": blob}, f)
    eng2 = ServingEngine(m, max_slots=2, max_len=48, prefill_buckets=(8,),
                         decode_chunk=2, program_set=hlo_only)
    assert set(eng2.program_set_info["kinds"].values()) == {"stablehlo"}
    rep = eng2.warmup()  # stablehlo programs compile here, not at traffic
    assert all(v == "program_set:stablehlo" for v in rep["programs"].values())
    q = eng2.submit([1, 2, 3], max_new_tokens=6)
    eng2.run_until_drained(timeout=240)
    assert q.tokens() == want
    assert eng2.post_warmup_compiles() == 0


def test_program_set_mismatch_and_corruption_are_typed(tmp_path):
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,))
    eng.warmup()
    path = eng.save_program_set(str(tmp_path / "a"))
    manifest = programs.read_manifest(path)
    assert manifest["manifest"]["max_slots"] == 2
    assert sorted(manifest["programs"]) == ["decode", "prefill_b8"]
    # engine-config mismatch
    with pytest.raises(ProgramSetError):
        ServingEngine(m, max_slots=3, max_len=24, prefill_buckets=(8,),
                      program_set=path)
    # weights mismatch (different seed -> same shapes, same artifact; a
    # different ARCH must be rejected via the state signature)
    other = tiny_gpt(vocab=17)
    with pytest.raises(ProgramSetError):
        ServingEngine(other, max_slots=2, max_len=24, prefill_buckets=(8,),
                      program_set=path)
    # byte corruption -> checksum rejection, typed
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    bad = str(tmp_path / "bad.pdprograms")
    open(bad, "wb").write(bytes(blob))
    with pytest.raises(ProgramSetError):
        ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,),
                      program_set=bad)
    # not-an-artifact
    junk = str(tmp_path / "junk.pdprograms")
    open(junk, "wb").write(b"not a program set")
    with pytest.raises(ProgramSetError):
        programs.read_manifest(junk)


def test_program_set_of_an_older_format_is_refused_typed(tmp_path):
    """A format-1 artifact holds programs with positional signatures; this
    build calls every program as `(weights, pools, inputs)`, so the older
    artifact must fail `ProgramSetError` at load (the predictor's
    fallback) and never be called with the wrong arguments."""
    import pickle
    from paddle_tpu.programs.program_set import PROGRAM_SET_FORMAT
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,))
    path = eng.save_program_set(str(tmp_path / "a"))
    with open(path, "rb") as f:
        envelope = pickle.load(f)
    assert envelope["format"] == PROGRAM_SET_FORMAT >= 2
    old = str(tmp_path / "old.pdprograms")
    with open(old, "wb") as f:
        pickle.dump(dict(envelope, format=1), f)
    with pytest.raises(ProgramSetError, match="format 1 unsupported"):
        ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,),
                      program_set=old)


def test_predictor_falls_back_on_bad_program_set(tmp_path):
    """enable_serving(program_set=<corrupt>) must warn + count + serve
    via a fresh trace — a stale artifact costs a recompile, not an
    outage, and never silent reuse."""
    from paddle_tpu import inference, jit
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=24, prefill_buckets=(8,))
    eng.warmup()
    good = eng.save_program_set(str(tmp_path / "good"))
    blob = bytearray(open(good, "rb").read())
    blob[-20] ^= 0xFF
    bad = str(tmp_path / "bad.pdprograms")
    open(bad, "wb").write(bytes(blob))
    prefix = str(tmp_path / "weights")
    jit.save(m, prefix)
    cfg = inference.Config(prefix)
    cfg.enable_serving(
        model_provider=lambda: tiny_gpt(),
        max_slots=2, max_len=24, prefill_buckets=(8,),
        program_set=bad, start=False)
    before = _counter_value("program_set_fallback_total")
    with pytest.warns(UserWarning, match="falling back"):
        pred = inference.create_predictor(cfg)
    assert _counter_value("program_set_fallback_total") == before + 1
    resp = pred.submit([1, 2, 3], max_new_tokens=4)
    pred.engine.run_until_drained(timeout=240)
    assert resp.tokens() == solo(m, [1, 2, 3], 4)
    pred.close()


def _counter_value(name):
    from paddle_tpu.observability.metrics import get_registry
    m = get_registry().get(name)
    if m is None:
        return 0
    try:
        return int(m.value())
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# warmup APIs
# ---------------------------------------------------------------------------

def test_trackedjit_warm_compiles_without_executing():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability.programs import ProgramRegistry, track
    ran = []

    def f(x):
        ran.append(1)  # trace-time only
        return x * 2

    reg = ProgramRegistry()
    tj = track("warmtest", jax.jit(f), registry=reg)
    x = jnp.ones((4,))
    assert tj.warm(x) is True
    assert reg.get("warmtest")["compiles"] == 1
    assert len(ran) == 1  # traced once, never executed beyond tracing
    assert tj.warm(x) is False  # already warm for this signature
    out = tj(x)  # uses the warmed executable: no second compile
    assert reg.get("warmtest")["compiles"] == 1
    np.testing.assert_array_equal(np.asarray(out), np.full((4,), 2.0))
    assert tj.compiled_for(x) is not None


def test_trainstep_warmup_compiles_without_update():
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
    ts = TrainStep(net, lambda o, t: nn.functional.cross_entropy(o, t), opt)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.array([0, 1, 2, 3], np.int64))
    before = {k: np.asarray(v.numpy()).copy()
              for k, v in net.state_dict().items()}
    rep = ts.warmup(x, y)
    assert rep["compiled"] is True
    after = {k: np.asarray(v.numpy()) for k, v in net.state_dict().items()}
    # no update applied, no optimizer step consumed
    assert all(np.array_equal(before[k], after[k]) for k in before)
    assert opt._step_count == 0
    reg = observability.get_program_registry()
    name = [n for n in reg.names() if n.startswith("train_step:")][0]
    compiles = reg.get(name)["compiles"]
    loss = ts(x, y)
    # the real step reuses the warmed executable: zero new compiles
    assert reg.get(name)["compiles"] == compiles
    assert np.isfinite(float(loss.numpy()))


def test_trainstep_warmup_preserves_rng_stream():
    """Warming must not consume a PRNG key: a warmed run's losses are
    bit-identical to an unwarmed run's (the bit-exact-resume contract)."""
    from paddle_tpu.jit import TrainStep
    x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    y = np.array([0, 1, 2, 3], np.int64)

    def run(warm):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Dropout(0.5), nn.Linear(16, 4))
        opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
        ts = TrainStep(net,
                       lambda o, t: nn.functional.cross_entropy(o, t), opt)
        if warm:
            ts.warmup(paddle.to_tensor(x), paddle.to_tensor(y))
        return [float(ts(paddle.to_tensor(x),
                         paddle.to_tensor(y)).numpy()) for _ in range(2)]

    assert run(False) == run(True)


@pytest.mark.slow
def test_sharded_trainstep_warmup():
    from paddle_tpu import parallel
    from paddle_tpu.parallel import ShardedTrainStep
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
    mesh = parallel.create_mesh({"dp": 8})
    ts = ShardedTrainStep(net,
                          lambda o, t: nn.functional.cross_entropy(o, t),
                          opt, mesh=mesh)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(8, 8).astype(np.float32))
    y = paddle.to_tensor(np.arange(8, dtype=np.int64) % 4)
    before = {k: np.asarray(v.numpy()).copy()
              for k, v in net.state_dict().items()}
    rep = ts.warmup(x, y)
    assert rep["compiled"] is True
    after = {k: np.asarray(v.numpy()) for k, v in net.state_dict().items()}
    assert all(np.array_equal(before[k], after[k]) for k in before)
    loss = ts(x, y)
    assert np.isfinite(float(loss.numpy()))


def test_engine_warmup_report_and_mixed_traffic_zero_compiles():
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=48, prefill_buckets=(8,),
                        decode_chunk=2)
    assert eng.post_warmup_compiles() == -1  # warmup never ran
    rep = eng.warmup()
    assert rep["compile_counts"]["total"] == rep["compile_counts"]["bound"]
    assert rep["seconds"] > 0
    rng = np.random.RandomState(2)
    rs = [eng.submit(rng.randint(0, 13, (4,)), max_new_tokens=5),
          eng.submit(rng.randint(0, 13, (6,)), max_new_tokens=5,
                     decode_strategy="sampling", temperature=0.7,
                     top_p=0.9, seed=3),
          eng.submit(rng.randint(0, 13, (3,)), max_new_tokens=5,
                     decode_strategy="sampling", top_k=4, seed=4)]
    eng.run_until_drained(timeout=240)
    for r in rs:
        assert len(r.tokens(timeout=5)) == 5
    assert eng.post_warmup_compiles() == 0
    assert eng.metrics()["post_warmup_compiles"] == 0


# ---------------------------------------------------------------------------
# AOT-fallback telemetry (satellite) + report/healthz surfaces
# ---------------------------------------------------------------------------

def test_aot_fallback_is_counted_named_and_logged(caplog):
    import logging
    from paddle_tpu.observability.programs import ProgramRegistry, TrackedJit

    class BrokenLower:
        def lower(self, *a, **k):
            raise RuntimeError("symbolic shapes say no")

        def __call__(self, *a, **k):
            return a[0] + 1

    reg = ProgramRegistry()
    tj = TrackedJit("fragile_prog", BrokenLower(), registry=reg)
    before = _counter_value("programs_aot_fallback_total")
    with caplog.at_level(logging.DEBUG,
                         logger="paddle_tpu.observability.programs"):
        assert tj(41) == 42
    assert _counter_value("programs_aot_fallback_total") == before + 1
    rec = reg.get("fragile_prog")
    assert rec["meta"]["aot"] is False
    assert "symbolic shapes say no" in rec["meta"]["fallback_error"]
    assert any("fragile_prog" in r.message for r in caplog.records)
    # the report line names the fallen-back program
    from paddle_tpu.observability.programs import aot_fallbacks
    assert "fragile_prog" in aot_fallbacks(reg)
    # calls keep working on the passthrough path
    assert tj(1) == 2


def test_report_carries_store_and_fallback_sections():
    rep = observability.report()
    assert "program_store" in rep
    assert isinstance(rep["programs_aot_fallbacks"], list)
    st = rep["program_store"]
    assert st is None or "enabled" in st


def test_gateway_healthz_reports_program_store():
    from paddle_tpu.serving import ServingGateway, TenantConfig
    m = tiny_gpt()
    eng = ServingEngine(m, max_slots=1, max_len=24, prefill_buckets=(8,))
    gw = ServingGateway(eng, tenants={"t": TenantConfig()})
    try:
        status, _, body = gw.handle("GET", "/healthz")
        payload = json.loads(body)
        assert status == 200
        assert "program_store" in payload
        assert payload["program_store"]["enabled"] in (True, False)
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# probe smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_program_cache_probe_smoke(cpu8_env):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(cpu8_env)
    env.pop("PDTPU_PROGRAM_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "probes",
                                      "program_cache_probe.py"),
         "--steps", "2"],
        capture_output=True, text=True, timeout=900, env=env, cwd=here)
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("PROGCACHE")]
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    rec = json.loads(line[0][len("PROGCACHE"):])
    assert rec["post_warmup_compiles"] == 0
    assert not rec.get("failures")
