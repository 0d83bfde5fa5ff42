"""Host-side span tracer: nestable, thread-aware, ring-buffered.

Reference: platform/profiler.h RecordEvent + the CUPTI DeviceTracer's
GenProfile chrome-trace path (platform/device_tracer.cc).  Dapper-style
span model: every span carries a thread id and an explicit parent (the
innermost open span on its thread unless overridden), so the chrome
export nests correctly even when the serving engine, the checkpoint
writer and the training loop all record concurrently.

Replaces `utils/profiler.py`'s module-global `_records`/`_events` (which
were mutated without a lock from serving-engine threads); that module is
now a lock-correct compat shim over this tracer.

The device half stays jax.profiler: every span also opens a
`jax.profiler.TraceAnnotation`, so in any profiler session, whoever
started it, the program's spans lie in the host plane of the `.xplane.pb`
on the device events' clock.  With no session active an annotation checks
one flag (a few hundred nanoseconds: PERF.md, PR 27), so there is no
switch to remember.  `Tracer.record` takes a span whose start lies in the
past (a queue wait is known only when it ends); such a span is in the ring
only, an annotation cannot be back-dated.

Python's collector pauses every thread that holds the interpreter, so its
longer pauses are spans too: one `gc.callbacks` hook, installed when this
module is imported, records `python_gc {generation, collected}` for every
collection of the oldest generation and for any collection of
`GC_SPAN_MIN_S` or more (the others, hundreds a second, record nothing).
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

try:  # the tracer itself is pure host; without jax it records the ring only
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except ImportError:  # pragma: no cover
    _TraceAnnotation = None

__all__ = ["Span", "Tracer", "get_tracer", "span"]

DEFAULT_MAX_EVENTS = 200_000  # bound host memory (same cap profiler.py had)
GC_SPAN_MIN_S = 1e-3  # a younger generation's collection shorter than this
                      # records nothing


class Span:
    """One open (then closed) host span.  Use as a context manager or call
    `end()` explicitly (the RecordEvent idiom)."""

    __slots__ = ("name", "tracer", "span_id", "parent_id", "tid", "t0",
                 "dur", "args", "_annotation", "_ended")

    def __init__(self, tracer: "Tracer", name: str,
                 parent: Optional["Span"] = None,
                 args: Optional[dict] = None):
        self.tracer = tracer
        self.name = name
        self.span_id = next(tracer._ids)
        self.tid = threading.get_ident()
        self.args = args
        self.dur = None
        self._ended = False
        stack = tracer._stack()
        explicit = parent is not None
        if not explicit and stack:
            parent = stack[-1]
        self.parent_id = parent.span_id if parent is not None else None
        stack.append(self)
        self._annotation = None
        if _TraceAnnotation is not None:
            self._annotation = (_TraceAnnotation(name, **args) if args
                                else _TraceAnnotation(name))
            self._annotation.__enter__()
        self.t0 = time.perf_counter()

    def end(self):
        if self._ended:
            return
        self._ended = True
        now = time.perf_counter()
        self.dur = now - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = self.tracer._stack()
        if self in stack:  # pop through abandoned children
            while stack and stack[-1] is not self:
                stack.pop()
            stack.pop()
        self.tracer._record(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class _LightSpan:
    """Hot-path span: name + wall time only — no span id, no TLS parenting
    stack, no TraceAnnotation.  Used by the per-op profiler hook, where a
    full Span's bookkeeping would cost ~2x more per dispatch; still
    recorded through the same lock into the same ring/aggregates (thread
    ids included), with span_id/parent_id = None."""

    __slots__ = ("tracer", "name", "t0")

    def __init__(self, tracer: "Tracer", name: str):
        self.tracer = tracer
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t0 = self.t0
        dur = time.perf_counter() - t0
        tracer = self.tracer
        with tracer._lock:
            rec = tracer._agg.get(self.name)
            if rec is None:
                rec = tracer._agg[self.name] = [0, 0.0]
            rec[0] += 1
            rec[1] += dur
            tracer._ring.append((self.name, t0, dur, threading.get_ident(),
                                 None, None, None))
        return False


class Tracer:
    """Bounded span recorder + per-name aggregates, all under one lock."""

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=int(max_events))
        self._agg: Dict[str, list] = {}  # name -> [count, total_s]
        self._ids = itertools.count(1)
        self._tls = threading.local()

    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _append(self, name, t0, dur, tid, span_id, parent_id, args):
        with self._lock:
            rec = self._agg.get(name)
            if rec is None:
                rec = self._agg[name] = [0, 0.0]
            rec[0] += 1
            rec[1] += dur
            self._ring.append((name, t0, dur, tid, span_id, parent_id, args))
        if _gc_late and self is _default_tracer:
            self.record("python_gc", *_gc_late.popleft())

    def _record(self, sp: Span):
        self._append(sp.name, sp.t0, sp.dur, sp.tid, sp.span_id,
                     sp.parent_id, sp.args)

    # -- recording -----------------------------------------------------------
    def span(self, name: str, parent: Optional[Span] = None,
             args: Optional[dict] = None) -> Span:
        return Span(self, name, parent=parent, args=args)

    def record(self, name: str, t0: float, t1: float,
               parent: Optional[Span] = None,
               args: Optional[dict] = None) -> int:
        """A closed span whose start lies in the past, on this tracer's
        clock (`time.perf_counter`).  It takes no parent from the thread's
        stack (it need not lie inside what is open now) and is no
        annotation; returns its id."""
        sid = next(self._ids)
        self._append(name, t0, t1 - t0, threading.get_ident(), sid,
                     parent.span_id if parent is not None else None, args)
        return sid

    def light_span(self, name: str) -> _LightSpan:
        """Minimal-overhead span for per-op hot paths (see _LightSpan)."""
        return _LightSpan(self, name)

    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    # -- reading -------------------------------------------------------------
    def aggregates(self) -> Dict[str, list]:
        """{name: [count, total_seconds]} — the profiler.summary shape."""
        with self._lock:
            return {k: list(v) for k, v in self._agg.items()}

    def events(self) -> List[tuple]:
        """Snapshot of the ring: (name, t0, dur, tid, id, parent_id, args)."""
        with self._lock:
            return list(self._ring)

    def __len__(self):
        with self._lock:
            return len(self._ring)

    @property
    def max_events(self) -> int:
        return self._ring.maxlen

    def set_max_events(self, n: int):
        with self._lock:
            self._ring = deque(self._ring, maxlen=int(n))

    def clear(self):
        with self._lock:
            self._ring.clear()
            self._agg.clear()

    # -- export --------------------------------------------------------------
    def chrome_trace(self) -> dict:
        """catapult JSON document (the DeviceTracer GenProfile analogue —
        host side; the XLA device timeline comes from jax.profiler)."""
        events = []
        for name, t0, dur, tid, sid, parent, args in self.events():
            ev = {"name": name, "ph": "X", "cat": "host",
                  "ts": t0 * 1e6, "dur": dur * 1e6,
                  "pid": os.getpid(), "tid": tid,
                  "args": dict(args or {}, span_id=sid,
                               parent_id=parent)}
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        doc = self.chrome_trace()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


_default_tracer = Tracer()

_gc_t0 = None          # start of the collection running now (one at a time)
_gc_late: deque = deque()  # collections that ended inside the ring's lock


def _on_gc(phase, info):
    """The collector's hook: a `python_gc` span on the default tracer for a
    collection of generation 2 or of `GC_SPAN_MIN_S` or more.  A collection
    can start at any bytecode, also inside the ring's own lock, which is
    not reentrant: one that ends while the lock is held waits in `_gc_late`
    for the next span recorded."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    t0, _gc_t0 = _gc_t0, None
    t1 = time.perf_counter()
    gen = info["generation"]
    if t0 is None or (gen < 2 and t1 - t0 < GC_SPAN_MIN_S):
        return
    args = {"generation": gen, "collected": info["collected"]}
    if _default_tracer._lock.locked():
        _gc_late.append((t0, t1, None, args))
    else:
        _default_tracer.record("python_gc", t0, t1, args=args)


gc.callbacks.append(_on_gc)


def get_tracer() -> Tracer:
    return _default_tracer


def span(name: str, parent: Optional[Span] = None,
         args: Optional[dict] = None) -> Span:
    """Open a span on the default tracer (context manager)."""
    return Span(_default_tracer, name, parent=parent, args=args)
