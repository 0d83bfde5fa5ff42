"""Profiler — compat shim over `paddle_tpu.observability.tracer`.

Reference: platform/profiler.h RecordEvent/EnableProfiler + CUPTI
DeviceTracer -> chrome trace (platform/device_tracer.h).  TPU-native:
jax.profiler (XLA/TensorBoard trace) for the device timeline + host spans
for eager-mode op accounting.

Since PR 5 the span storage is the observability tracer (bounded ring +
per-name aggregates under a lock) instead of this module's bare
`_records` dict / `_events` list — which serving-engine threads used to
mutate concurrently without a lock.  The public API (start_profiler /
stop_profiler / profiler / RecordEvent / summary / export_chrome_tracing)
is unchanged, and `_records` / `_events` remain readable as snapshots for
callers that poked the internals.
"""
from __future__ import annotations

import contextlib

import jax

from ..core import op as _op
from ..observability.tracer import get_tracer, span

_enabled = False
# aggregates snapshot taken at start_profiler: the profiler reports the
# DELTA since then, so starting a profile no longer wipes span history
# other subsystems (checkpoint writer, train loop, serving engine)
# accumulated in the shared tracer
_baseline: dict = {}


def _hook(name):
    # light span: the hook fires on EVERY eager dispatch — pay wall-time +
    # ring/aggregate recording only (no ids/parenting/annotation)
    return get_tracer().light_span(name)


def _delta():
    agg = get_tracer().aggregates()
    out = {}
    for k, (c, t) in agg.items():
        bc, bt = _baseline.get(k, (0, 0.0))
        if c - bc > 0:
            out[k] = [c - bc, t - bt]
    return out


def __getattr__(name):
    # legacy internals, now lock-safe snapshots of the tracer state
    if name == "_records":
        return _delta()
    if name == "_events":
        return [(n, t0, dur) for n, t0, dur, *_ in get_tracer().events()]
    raise AttributeError(name)


def start_profiler(state="All", tracer_option="Default", log_dir=None):
    """reference: fluid.profiler.start_profiler"""
    global _enabled, _baseline
    _enabled = True
    _baseline = get_tracer().aggregates()
    _op.set_profiler_hook(_hook)
    if log_dir:
        jax.profiler.start_trace(log_dir)
        start_profiler._trace_dir = log_dir
    else:
        start_profiler._trace_dir = None


def stop_profiler(sorted_key="total", profile_path=None):
    global _enabled
    _enabled = False
    _op.set_profiler_hook(None)
    if getattr(start_profiler, "_trace_dir", None):
        jax.profiler.stop_trace()
    agg = _delta()
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
    lines = [f"{'op':<32}{'calls':>10}{'total_s':>14}{'avg_ms':>12}"]
    for name, (cnt, tot) in rows[:50]:
        lines.append(f"{name:<32}{cnt:>10}{tot:>14.4f}{tot / cnt * 1e3:>12.4f}")
    # dispatch fast-path accounting (the hook fires on hit AND miss paths,
    # so per-op spans above already include both; this line attributes them)
    cs = _op.dispatch_cache_stats()
    lines.append(
        f"dispatch cache: hits={cs['hits']} misses={cs['misses']} "
        f"fallbacks={cs['fallbacks']} bypass={cs['bypass']} "
        f"entries={cs['entries']}/{cs['max_entries']} "
        f"enabled={cs['enabled']}")
    report = "\n".join(lines)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(report)
    else:
        print(report)
    return agg


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None, log_dir=None):
    """`with paddle_tpu.utils.profiler.profiler():` context."""
    start_profiler(state, log_dir=log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# RAII host span (reference: platform/profiler.h:127): the observability
# span itself, which is also a jax TraceAnnotation, so host spans line up
# with the XLA device timeline.  `with RecordEvent(name):` or `.end()`.
RecordEvent = span


def summary():
    """Op-span records plus the monitor's STAT counters (reference:
    platform/monitor.h StatRegistry — surfaced here the way the reference
    prints stats alongside the profiler report)."""
    out = _delta()  # == full aggregates when no profile was ever started
    from .monitor import stats
    st = stats()
    if st:
        out["__stats__"] = st
    return out


def export_chrome_tracing(path: str) -> str:
    """Write recorded host spans as a chrome://tracing (catapult) JSON —
    the analogue of the reference DeviceTracer's GenProfile chrome trace
    (platform/device_tracer.cc).  The XLA device timeline comes from the
    jax.profiler trace dir (TensorBoard); this file covers the host side.
    Spans carry real thread ids + parent links now (observability
    tracer)."""
    return get_tracer().export_chrome_trace(path)
