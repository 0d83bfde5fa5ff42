"""Observability tests (VERDICT r1 weak #6/#7 + missing #9 summary/flops;
PR 5: the unified telemetry subsystem `paddle_tpu.observability`).

Reference behaviors matched: FLAGS_check_nan_inf op-output scanning
(framework/details/nan_inf_utils_detail.cc), hapi model_summary +
dynamic_flops, DeviceTracer chrome-trace export, monitor.h StatRegistry.
PR 5 adds: typed metrics registry (labels, histogram quantiles, concurrent
increments), tracer nesting + ring-buffer bounding, chrome-trace schema,
Prometheus exposition (rendered port-free via the handler body), the
compiled-program registry after a TrainStep + serving smoke, and legacy
`profiler.summary()` / STAT_ADD parity over the new backends.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.utils import set_flags

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_check_nan_inf_flag_catches_and_names_op():
    set_flags({"FLAGS_check_nan_inf": True})
    try:
        one = paddle.to_tensor(np.array([1.0], "float32"))
        zero = paddle.to_tensor(np.array([0.0], "float32"))
        with pytest.raises(FloatingPointError, match="divide"):
            one / zero
        # finite ops pass untouched
        assert float((one + one).numpy()[0]) == 2.0
    finally:
        set_flags({"FLAGS_check_nan_inf": False})
    # disabled again: nan flows silently (default behavior)
    bad = paddle.to_tensor(np.array([1.0], "float32")) / paddle.to_tensor(
        np.array([0.0], "float32"))
    assert np.isinf(np.asarray(bad.numpy())).all()


@pytest.mark.slow
def test_summary_reports_layers_params_flops():
    from paddle_tpu.vision.models import LeNet
    info = paddle.summary(LeNet(), (1, 1, 28, 28))
    assert info["total_params"] == 61610
    assert info["trainable_params"] == 61610
    # conv1: 28*28*6 out elems * (1*5*5) kernel = 117600? -> MAC-based total
    assert info["total_flops"] > 100_000


def test_flops_api():
    from paddle_tpu.vision.models import LeNet
    n = paddle.flops(LeNet(), (1, 1, 28, 28))
    assert isinstance(n, int) and n > 0


def test_profiler_chrome_trace_export(tmp_path):
    from paddle_tpu.utils import profiler as prof
    with prof.profiler():
        x = paddle.to_tensor(np.ones((4, 4), "float32"))
        (x @ x + x).sum()
    path = prof.export_chrome_tracing(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert len(events) >= 2
    names = {e["name"] for e in events}
    assert any("matmul" in n or "add" in n or "sum" in n for n in names)
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0


def test_hapi_metrics_reuse_train_forward():
    """train_batch with metrics must not run a second forward."""
    from paddle_tpu.hapi import Model
    from paddle_tpu.metric import Accuracy

    calls = {"n": 0}

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 3)

        def forward(self, x):
            calls["n"] += 1
            return self.fc(x)

    paddle.seed(0)
    net = Net()
    m = Model(net)
    m.prepare(paddle.optimizer.SGD(0.1, parameters=net.parameters()),
              loss=nn.CrossEntropyLoss(), metrics=Accuracy())
    x = np.random.RandomState(0).randn(8, 4).astype("float32")
    y = np.random.RandomState(0).randint(0, 3, (8, 1)).astype("int64")
    calls["n"] = 0
    loss, metrics = m.train_batch([x], [y])
    # forward traced once at compile; steady-state calls don't re-enter
    n_after_first = calls["n"]
    loss, metrics = m.train_batch([x], [y])
    assert calls["n"] == n_after_first  # no python re-entry, no 2nd forward
    assert np.isfinite(float(loss[0]) if isinstance(loss, (list, tuple))
                       else float(loss))
    assert 0.0 <= metrics[0] <= 1.0


def test_grad_scaler_explicit_unscale_then_step_not_double_unscaled():
    """unscale_ + clip + step must divide by the scale exactly once."""
    from paddle_tpu import amp

    def run(explicit_unscale):
        paddle.seed(0)
        w = paddle.core.tensor.Parameter(
            paddle.to_tensor(np.ones(4, "float32"))._data, name="w")
        o = paddle.optimizer.SGD(0.1, parameters=[w])
        scaler = amp.GradScaler(init_loss_scaling=1024.0)
        loss = (w * 2.0).sum()
        scaler.scale(loss).backward()
        if explicit_unscale:
            scaler.unscale_(o)  # e.g. to clip grads here
        scaler.step(o)
        return np.asarray(w.numpy())

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6)
    # and the update magnitude is the unscaled one: w - lr*2
    np.testing.assert_allclose(run(True), 1.0 - 0.1 * 2.0, rtol=1e-5)


def test_monitor_stat_counters():
    """STAT registry (reference platform/monitor.h:77 STAT_ADD/StatRegistry):
    counters bump from hot paths, surface in profiler.summary(), reset via
    flags."""
    from paddle_tpu.utils import monitor, profiler as prof
    monitor.stat_reset()
    monitor.STAT_ADD("STAT_test_counter", 5)
    monitor.STAT_ADD("STAT_test_counter", 2)
    monitor.STAT_SUB("STAT_test_counter", 1)
    assert monitor.stat_get("STAT_test_counter") == 6
    assert prof.summary()["__stats__"]["STAT_test_counter"] == 6

    # dataloader instrumentation
    from paddle_tpu.io import DataLoader
    class DS:
        def __len__(self):
            return 8
        def __getitem__(self, i):
            return np.ones((4,), "float32"), np.int64(i % 2)
    before = monitor.stat_get("STAT_dataloader_batch_count")
    for _ in DataLoader(DS(), batch_size=4, num_workers=0):
        pass
    assert monitor.stat_get("STAT_dataloader_batch_count") == before + 2
    assert monitor.stat_get("STAT_dataloader_bytes") > 0

    # reset through the flag system
    paddle.utils.flags.set_flags({"FLAGS_reset_stats": True})
    assert monitor.stat_get("STAT_test_counter") == 0
    assert "__stats__" not in prof.summary()


# ===========================================================================
# PR 5: paddle_tpu.observability — the unified telemetry subsystem
# ===========================================================================

obsmark = pytest.mark.observability


@obsmark
def test_metrics_registry_semantics():
    """Counter/Gauge/Histogram with label sets; type conflicts rejected."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("requests_total", "reqs", labelnames=("route",))
    c.labels(route="a").inc()
    c.labels(route="a").inc(2)
    c.labels(route="b").inc(5)
    assert c.value(route="a") == 3
    assert c.value(route="b") == 5
    with pytest.raises(ValueError):
        c.labels(route="a").inc(-1)  # counters are monotone
    with pytest.raises(ValueError):
        c.labels(wrong="a")  # label names enforced

    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    g.inc(0.5)
    assert g.value() == 3.5

    # get-or-create is type-checked: no silent series splitting
    assert reg.counter("requests_total", labelnames=("route",)) is c
    with pytest.raises(ValueError):
        reg.gauge("requests_total")
    with pytest.raises(ValueError):
        reg.counter("requests_total", labelnames=("other",))

    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["counts"] == [1, 2, 1, 1]
    assert snap["min"] == 0.005 and snap["max"] == 2.0
    assert abs(snap["sum"] - 2.605) < 1e-9

    # quantiles: interpolated within the landing bucket, exact at the ends
    hq = reg.histogram("q_seconds", buckets=tuple((i + 1) / 1000.0
                                                  for i in range(100)))
    for i in range(1, 101):
        hq.observe(i / 1000.0)
    assert hq.quantile(0.0) == 0.001
    assert hq.quantile(1.0) == 0.1
    p50 = hq.quantile(0.5)
    assert 0.04 <= p50 <= 0.06
    p99 = hq.quantile(0.99)
    assert 0.09 <= p99 <= 0.1


@obsmark
def test_metrics_registry_concurrent_increments():
    """8 threads hammering one counter/histogram lose no increments."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("obs_seconds")
    n_threads, per_thread = 8, 500
    barrier = threading.Barrier(n_threads)

    def worker():
        barrier.wait()
        for i in range(per_thread):
            c.inc()
            h.observe(0.001 * (i % 10))

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == n_threads * per_thread
    assert h.snapshot()["count"] == n_threads * per_thread


@obsmark
def test_tracer_nesting_and_ring_bound():
    from paddle_tpu.observability.tracer import Tracer

    tr = Tracer(max_events=100)
    with tr.span("outer") as outer:
        assert tr.current_span() is outer
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            with tr.span("leaf") as leaf:
                assert leaf.parent_id == inner.span_id
        # explicit parent override
        with tr.span("adopted", parent=outer) as adopted:
            assert adopted.parent_id == outer.span_id
    assert outer.parent_id is None
    assert tr.current_span() is None

    # ring buffer bounds memory; aggregates keep exact counts
    for _ in range(500):
        with tr.span("hot"):
            pass
    assert len(tr) == 100
    agg = tr.aggregates()
    assert agg["hot"][0] == 500
    assert agg["outer"][0] == 1


def test_deferred_args_are_read_once_by_readers_at_the_same_time():
    """Spans whose args wait on the device (`defer_args`) hold numbers for
    every reader of the ring, also readers that read it at once from
    several threads: none sees the key that held the device vector."""
    import jax.numpy as jnp
    from paddle_tpu.observability.tracer import DEFERRED, Tracer, defer_args

    tr = Tracer(max_events=1000)
    for i in range(200):
        with tr.span("step") as sp:
            defer_args(sp, ("a", "b"), jnp.asarray([i, 2 * i], jnp.int32))
    start, seen, errors = threading.Barrier(8), [], []

    def read():
        start.wait()
        try:
            seen.append([ev[6] for ev in tr.events()])
        except Exception as e:  # noqa: BLE001
            errors.append(e)
    threads = [threading.Thread(target=read) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors and len(seen) == 8
    for args in seen:
        assert [(a["a"], a["b"]) for a in args] == [
            (i, 2 * i) for i in range(200)]
        assert all(DEFERRED not in a for a in args)


@obsmark
def test_profiler_shim_thread_safety_hammer():
    """Regression for the pre-PR5 bug: profiler _records/_events were
    mutated without a lock from serving-engine threads.  8 threads x 200
    RecordEvent spans must land exactly, no exceptions, while a reader
    polls snapshots."""
    from paddle_tpu import observability as obs
    from paddle_tpu.utils import profiler as prof

    obs.get_tracer().clear()
    n_threads, per_thread = 8, 200
    barrier = threading.Barrier(n_threads + 1)
    errors = []

    def worker(tid):
        try:
            barrier.wait()
            for i in range(per_thread):
                with prof.RecordEvent(f"hammer_{tid % 2}"):
                    pass
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        barrier.wait()
        for _ in range(50):
            dict(prof._records)
            prof.summary()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    threads.append(threading.Thread(target=reader))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    agg = obs.get_tracer().aggregates()
    total = agg["hammer_0"][0] + agg["hammer_1"][0]
    assert total == n_threads * per_thread
    # the legacy internals view agrees
    recs = prof._records
    assert recs["hammer_0"][0] + recs["hammer_1"][0] == total


@obsmark
def test_chrome_trace_schema_with_threads_and_parents(tmp_path):
    from paddle_tpu import observability as obs

    tr = obs.get_tracer()
    tr.clear()
    with tr.span("main_outer"):
        with tr.span("main_inner"):
            pass

    def other():
        with tr.span("bg_span"):
            pass
    t = threading.Thread(target=other)
    t.start()
    t.join()

    path = obs.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert len(events) == 3
    by_name = {e["name"]: e for e in events}
    for e in events:
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] > 0
        assert isinstance(e["tid"], int) and isinstance(e["pid"], int)
        assert "span_id" in e["args"]
    assert (by_name["main_inner"]["args"]["parent_id"]
            == by_name["main_outer"]["args"]["span_id"])
    assert by_name["bg_span"]["tid"] != by_name["main_outer"]["tid"]
    assert by_name["bg_span"]["args"]["parent_id"] is None


def _parse_prometheus(text):
    """Minimal exposition-format parser: returns {series_name: [(labels,
    value)]}; raises on malformed lines."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            if line.startswith("#"):
                parts = line.split()
                assert parts[1] in ("HELP", "TYPE"), line
            continue
        name_part, value = line.rsplit(" ", 1)
        if "{" in name_part:
            name, rest = name_part.split("{", 1)
            assert rest.endswith("}"), line
            labels = {}
            for pair in rest[:-1].split(","):
                if pair:
                    k, v = pair.split("=", 1)
                    assert v.startswith('"') and v.endswith('"'), line
                    labels[k] = v[1:-1]
        else:
            name, labels = name_part, {}
        float(value if value != "+Inf" else "inf")  # parses
        out.setdefault(name, []).append((labels, value))
    return out


@obsmark
def test_prometheus_exposition_format():
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.observability.exporters import prometheus_text

    reg = MetricsRegistry()
    reg.counter("events_total", "events", labelnames=("kind",)) \
       .labels(kind="a b\"c").inc(7)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1))
    for v in (0.005, 0.05, 5.0):
        h.observe(v)
    text = prometheus_text(reg)
    series = _parse_prometheus(text)
    assert series["events_total"][0][0] == {"kind": 'a b\\"c'}
    assert series["depth"][0][1] == "3"
    buckets = {lab["le"]: int(v) for lab, v in series["lat_seconds_bucket"]}
    assert buckets == {"0.01": 1, "0.1": 2, "+Inf": 3}  # cumulative
    assert int(series["lat_seconds_count"][0][1]) == 3
    assert abs(float(series["lat_seconds_sum"][0][1]) - 5.055) < 1e-9
    # TYPE lines present for every family
    for fam in ("events_total", "depth", "lat_seconds"):
        assert f"# TYPE {fam} " in text


@obsmark
def test_metrics_endpoint_handler_port_free():
    """The HTTP endpoint body, exercised without binding a socket."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability.exporters import render_endpoint

    obs.counter("endpoint_probe_total").inc()
    status, ctype, body = render_endpoint("/metrics")
    assert status == 200 and ctype.startswith("text/plain")
    assert b"endpoint_probe_total" in body
    _parse_prometheus(body.decode())

    status, ctype, body = render_endpoint("/report")
    assert status == 200 and ctype == "application/json"
    rep = json.loads(body)
    assert "dispatch_cache" in rep and "programs" in rep

    status, _, _ = render_endpoint("/nope")
    assert status == 404


@obsmark
def test_jsonl_sink_manual_flush(tmp_path):
    from paddle_tpu.observability.exporters import JsonlSink

    path = str(tmp_path / "telemetry.jsonl")
    sink = JsonlSink(path, interval_seconds=None)
    sink.flush()
    sink.close()  # final flush -> 2 lines
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert len(lines) == 2
    for rec in lines:
        assert "dispatch_cache" in rec and "train" in rec


@obsmark
def test_stats_prefix_filter_and_flag_reset_clears_registry():
    """Satellite: monitor.stats(prefix=...) + FLAGS_reset_stats clearing
    the observability registry, not just the legacy name set."""
    from paddle_tpu import observability as obs
    from paddle_tpu.utils import monitor

    monitor.stat_reset()
    monitor.STAT_ADD("STAT_serving_probe_x", 3)
    monitor.STAT_ADD("STAT_serving_probe_y", 1)
    monitor.STAT_ADD("STAT_dataloader_probe_z", 2)
    assert set(monitor.stats(prefix="serving_")) == {
        "STAT_serving_probe_x", "STAT_serving_probe_y"}
    assert set(monitor.stats(prefix="STAT_serving_")) == {
        "STAT_serving_probe_x", "STAT_serving_probe_y"}
    assert monitor.stats(prefix="nomatch_") == {}

    h = obs.histogram("flag_reset_probe_seconds")
    h.observe(0.5)
    assert h.snapshot()["count"] == 1
    set_flags({"FLAGS_reset_stats": True})
    try:
        assert monitor.stats() == {}
        assert monitor.stat_get("STAT_serving_probe_x") == 0
        # the new registry was cleared too (values zeroed, handle valid)
        assert h.snapshot()["count"] == 0
    finally:
        set_flags({"FLAGS_reset_stats": False})


class _ObsDS:
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return np.ones((4,), "float32"), np.int64(i % 2)


class _ObsProtocolModel(nn.Layer):
    """Minimal gen_fixed_cache/forward_fixed protocol model (the serving
    smoke's stub: logits are an embedding of the current token)."""

    def __init__(self, vocab=24):
        super().__init__()
        from paddle_tpu.nn.layer.common import Embedding
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


@obsmark
def test_unified_report_after_train_and_serve_smoke(tmp_path):
    """THE acceptance check: one observability.report() pass surfaces
    dispatch-cache hit rate, dataloader data-wait, checkpoint save stall,
    train step time, serving TTFT/inter-token histograms, and
    per-compiled-program compile time + cost-analysis bytes — after an
    instrumented train + serve smoke."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu import observability as obs
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.serving import ServingEngine

    obs.reset()

    # eager ops -> dispatch cache traffic
    x = paddle.to_tensor(np.ones((4, 4), "float32"))
    for _ in range(3):
        (x @ x + x).sum()

    # dataloader -> data-wait histogram
    loader = DataLoader(_ObsDS(), batch_size=4, num_workers=0)
    batches = list(loader)
    assert len(batches) == 2

    # train 2 compiled steps + a checkpoint save
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = TrainStep(model, lambda o, lbl: F.cross_entropy(o, lbl), opt)
    for xb, yb in batches:
        step(xb, yb)
    step.save_checkpoint(str(tmp_path / "ckpt"))

    # serving smoke
    paddle.seed(3)
    m = _ObsProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=2, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    resp = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run_until_drained(timeout=120)
    assert len(resp.tokens(timeout=5)) == 4
    eng.close()

    rep = obs.report()
    # 1. dispatch cache hit rate
    assert rep["dispatch_cache"]["hits"] >= 1
    assert 0.0 < rep["dispatch_cache"]["hit_rate"] <= 1.0
    # 2. dataloader data-wait
    assert rep["dataloader"]["data_wait_seconds"]["count"] >= 2
    assert rep["dataloader"]["batches"] >= 2
    # 3. checkpoint save stall
    assert rep["checkpoint"]["save_stall_seconds"]["count"] >= 1
    assert rep["checkpoint"]["bytes_written"] > 0
    # 4. train step time
    assert rep["train"]["step_seconds"]["count"] >= 2
    assert rep["train"]["step_seconds"]["mean_ms"] > 0
    # 5. serving latency histograms + gauges
    assert rep["serving"]["ttft_seconds"]["count"] >= 1
    assert rep["serving"]["inter_token_seconds"]["count"] >= 1
    assert rep["serving"]["slot_occupancy"] == 0  # drained
    # 6. compiled-program registry: train + serving programs with compile
    #    time and cost-analysis bytes
    progs = rep["programs"]
    train_progs = [v for k, v in progs.items()
                   if k.startswith("train_step:")]
    assert train_progs and train_progs[0]["compiles"] == 1
    assert train_progs[0]["compile_seconds_total"] > 0
    assert train_progs[0]["bytes_accessed"] > 0
    assert train_progs[0]["flops"] > 0
    serve_progs = {k: v for k, v in progs.items()
                   if k.startswith("serving_")}
    assert any(k.startswith("serving_prefill") for k in serve_progs)
    assert "serving_decode" in serve_progs
    assert all(v["compile_seconds_total"] > 0 for v in serve_progs.values())
    assert any(v.get("bytes_accessed", 0) > 0 for v in serve_progs.values())
    # dispatch-cache compiles are in the registry too (wall time only)
    assert any(k.startswith("dispatch:") for k in progs)

    # the same single pass feeds the Prometheus exposition
    text = obs.prometheus_text()
    for series in ("dispatch_cache_hits_total", "dispatch_cache_hit_rate",
                   "dataloader_data_wait_seconds_bucket",
                   "checkpoint_save_stall_seconds_sum",
                   "train_step_seconds_count",
                   "serving_ttft_seconds_bucket",
                   "serving_inter_token_seconds_count",
                   "serving_slot_occupancy"):
        assert series in text, f"missing {series}"
    _parse_prometheus(text)


@obsmark
def test_legacy_profiler_and_stat_parity():
    """Legacy call sites keep working unchanged over the new backends:
    profiler.summary() / stop_profiler return the {name: [count, total]}
    shape, _records stays readable, STAT verbs round-trip."""
    from paddle_tpu.utils import monitor, profiler as prof

    monitor.stat_reset()
    monitor.STAT_ADD("STAT_parity_probe", 2)
    monitor.STAT_SUB("STAT_parity_probe", 1)
    assert monitor.stat_get("STAT_parity_probe") == 1

    prof.start_profiler()
    x = paddle.to_tensor(np.ones((4, 4), "float32"))
    (x @ x).sum()
    live = dict(prof._records)  # the internals poke some tests do
    assert any("matmul" in k for k in live)
    records = prof.stop_profiler(profile_path=os.devnull)
    assert any("matmul" in k for k in records)
    cnt, tot = records[next(k for k in records if "matmul" in k)]
    assert cnt >= 1 and tot >= 0
    s = prof.summary()
    assert s["__stats__"]["STAT_parity_probe"] == 1


@obsmark
def test_a_batched_models_cache_counts_ride_in_its_spans_and_the_gauge():
    """A model that counts its cache behind its routed counts (`[…, rows the
    call's requests hold, rows its attention went over]`): the engine puts
    them into `serving_admit` / `serving_decode` as `kv_rows_live` and
    `kv_rows_pool`, and gauges `serving_kv_rows` under the model's own word
    for its cache (`latent`).  No span is added for it."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import ServingEngine
    from test_serving import LeavesProtocolModel

    paddle.seed(3)
    m = LeavesProtocolModel()
    m.eval()
    eng = ServingEngine(m, max_slots=2, max_len=32, prefill_buckets=(8,),
                        decode_chunk=2)
    eng.warmup()
    tracer = obs.get_tracer()
    tracer.clear()
    rs = [eng.submit(np.arange(n), max_new_tokens=5) for n in (6, 3)]
    eng.run_until_drained(timeout=60)
    assert all(len(r.tokens()) == 5 for r in rs)
    args = {}
    for ev in tracer.events():
        if ev[6] and "kv_rows_live" in ev[6]:
            args.setdefault(ev[0], []).append(ev[6])
    assert set(args) == {"serving_admit", "serving_decode"}
    layers = 2
    assert [(a["kv_rows_live"], a["kv_rows_pool"]) for a in args[
        "serving_admit"]] == [(6 * layers, 8 * layers), (3 * layers,
                                                          8 * layers)]
    first = args["serving_decode"][0]
    # both slots, two steps: rows 7 + 4, then 8 + 5, a layer; the whole
    # pool of 2 slots x 32 rows is what each step went over
    assert first["active"] == 2
    assert first["kv_rows_live"] == layers * (7 + 4 + 8 + 5)
    assert first["kv_rows_pool"] == layers * 2 * 2 * 32
    assert first["routed_all"] == 0
    rows = obs.metrics.get_registry().get("serving_kv_rows")
    assert 0 < rows.value(kind="latent") <= layers * (6 + 5 + 3 + 5)
    eng.close()


@obsmark
@pytest.mark.slow
def test_observability_probe_smoke():
    """probes/observability_probe.py --steps 3: machinery end-to-end in a
    clean subprocess (overhead bar not enforced in smoke mode)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "probes", "observability_probe.py"),
         "--steps", "3", "--reps", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("OBS"))
    rec = json.loads(line[len("OBS"):])
    assert proc.returncode == 0, (rec, proc.stderr[-500:])
    assert rec["smoke"] is True
    assert "failures" not in rec
    assert rec["spans_exported"] == 200
    assert rec["export_ms"] > 0


@obsmark
def test_a_full_collection_is_one_python_gc_span():
    import gc
    from paddle_tpu import observability as obs
    tracer = obs.get_tracer()
    gc.disable()                  # no collection but the one asked for
    try:
        tracer.clear()
        gc.collect(2)
        spans = [ev for ev in tracer.events() if ev[0] == "python_gc"]
        # the ring's lock is not reentrant: a collection that ends while
        # it is held is recorded after the next span
        with tracer._lock:
            gc.collect(2)
        assert [ev[0] for ev in tracer.events()] == ["python_gc"]
        tracer.record("after", 1.0, 2.0)
        late = [ev for ev in tracer.events() if ev[0] == "python_gc"]
    finally:
        gc.enable()
    assert len(spans) == 1 and spans[0][6]["generation"] == 2
    assert spans[0][6]["collected"] >= 0 and spans[0][2] > 0
    assert spans[0][4] is not None and spans[0][5] is None
    assert len(late) == 2 and late[1][6]["generation"] == 2
    assert late[1][1] > spans[0][1] and late[1][4] != spans[0][4]
    assert tracer.aggregates()["python_gc"][0] == 2
