"""From the profiler's `.xplane.pb` to numbers: the device's busy union,
device time by operation, a kernel's time by name pattern, and the idle gaps
joined to the host span that was open in them.

Everything is clipped to the window the benchmark marked in the trace with
its own annotation (`WINDOW_MARK`), so the profiler's start-up and shutdown
do not count.  Times are seconds.
"""
import glob
import json
import os
import re

WINDOW_MARK = "bench_traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans worth naming in an idle gap: the benchmark's own and the
# program's (TrainStep's and the engine's annotate under the profiler)
HOST_PREFIXES = ("bench_", "train_step", "serving_", "generate_load",
                 "collect_tokens", "engine_step", "submit")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    """-> {"device": {chip: [(name, start_s, end_s)]}  (operations),
           "modules": {chip: [...]}  (one event per program executed),
           "host": [(name, start_s, end_s)]}"""
    if path.endswith(".json"):
        # events recorded from a chip's trace by `record()`, for the tests
        with open(path) as f:
            return load_dict(json.load(f))
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(chip, []).extend(
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
                if line.name != OPS_LINE:
                    continue
                device.setdefault(chip, []).extend(
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK or ev.name.startswith(
                            HOST_PREFIXES):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"device": device, "modules": modules, "host": host}


def union(intervals):
    """Merged, sorted [(start, end)] and their total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def _clip(events, lo, hi):
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def window_of(events):
    marks = [(s, e) for name, s, e in events["host"] if name == WINDOW_MARK]
    if marks:
        return min(s for s, _ in marks), max(e for _, e in marks)
    all_dev = [x for evs in events["device"].values() for x in evs]
    if not all_dev:
        return None
    return min(s for _, s, _ in all_dev), max(e for _, _, e in all_dev)


_KIND = re.compile(r"^%?([A-Za-z_\-]+?)[_.\d]*(?: = |$)")


def op_kind(name):
    """An operation's name without its number: `%fusion.1894 = ...` ->
    `fusion`, `%transpose_jvp___.46 = ...` -> `transpose_jvp`.  A step is
    thousands of operations; their kinds are a few dozen.  A `while` spans
    its body's operations, which are counted too: its time is theirs."""
    m = _KIND.match(name)
    return m.group(1).rstrip("_") if m else name[:40]


def reduce(events, top=10):
    """-> None where no device operation was traced, else a summary:
    window_s, busy_s (mean over chips), busy_by_chip, busiest chip's
    device time by kind of operation [(kind, seconds)] and idle gaps
    [(host span, seconds)], and the clipped events of the busiest chip for
    the kernel readers."""
    win = window_of(events)
    if win is None or not events["device"]:
        return None
    lo, hi = win
    by_chip, clipped = {}, {}
    for chip, evs in events["device"].items():
        clipped[chip] = _clip(evs, lo, hi)
        _, by_chip[chip] = union((s, e) for _, s, e in clipped[chip])
    if not any(by_chip.values()):
        return None
    busiest = max(by_chip, key=by_chip.get)
    evs = clipped[busiest]
    by_op, by_kind = {}, {}
    for name, s, e in evs:
        by_op[name] = by_op.get(name, 0.0) + (e - s)
        kind = op_kind(name)
        by_kind[kind] = by_kind.get(kind, 0.0) + (e - s)
    merged, _ = union((s, e) for _, s, e in evs)
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    host = sorted((s, e, name) for name, s, e in events["host"]
                  if name != WINDOW_MARK)
    by_span = {}
    for gs, ge in gaps:
        # the innermost host span open at the gap's middle names it
        mid, best = 0.5 * (gs + ge), None
        for s, e, name in host:
            if s <= mid <= e and (best is None or s >= best[0]):
                best = (s, name)
        name = best[1] if best else "no_span_open"
        by_span[name] = by_span.get(name, 0.0) + (ge - gs)
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "window_s": hi - lo,
        "busy_s": sum(by_chip.values()) / len(by_chip),
        "busy_by_chip": by_chip,
        "busiest_chip": busiest,
        "device_ops": [[n, t] for n, t in order(by_kind)[:top]],
        "idle_gaps": [[n, t] for n, t in order(by_span)[:top]],
        "op_seconds": by_op,
        "events": evs,
        "modules": _clip(events.get("modules", {}).get(busiest, []), lo, hi),
    }


def pattern_seconds(summary, pattern, line="events"):
    """Device seconds and calls of the operations (line="events") or
    programs (line="modules") whose name matches."""
    rx = re.compile(pattern)
    hits = [(e - s) for name, s, e in summary[line] if rx.search(name)]
    return sum(hits), len(hits)


def describe(path, top=40):
    """What a trace holds, for reading one by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  line {line.name!r}: {len(evs)} events")
            agg = {}
            for ev in evs:
                a = agg.setdefault(ev.name, [0, 0.0])
                a[0] += 1
                a[1] += ev.duration_ns * 1e-9
            ranked = sorted(agg.items(), key=lambda kv: -kv[1][1])
            shown = ranked[:top] + [kv for kv in ranked[top:]
                                    if "custom-call" in kv[0]]
            for name, (n, t) in shown:
                lines.append(f"    {t:10.6f}s x{n:<6d} {name[:150]}")
    return "\n".join(lines)


def kernels(path, width=220):
    """Every device operation that is a hand-written kernel
    (`tpu_custom_call`), by name, with its time and calls: for reading a
    kernel's pattern off a trace by hand."""
    events = load(path)
    agg = {}
    for evs in events["device"].values():
        for name, s, e in evs:
            if "tpu_custom_call" in name:
                key = name[:width]
                a = agg.setdefault(key, [0, 0.0])
                a[0] += 1
                a[1] += e - s
    return "\n".join(f"{t:10.6f}s x{n:<5d} {k}" for k, (n, t) in sorted(
        agg.items(), key=lambda kv: -kv[1][1]))


def record(path, out, seconds=0.11, min_seconds=5e-6, name_width=48,
           kernel_width=200):
    """A stretch of a chip's trace as a small JSON file of events, for the
    tests: the first `seconds` of the marked window, device operations
    shorter than `min_seconds` dropped, names cut to `name_width` (kernels:
    `kernel_width`), with what `reduce` reads from it written beside."""
    events = load(path)
    lo, _ = window_of(events)
    hi = lo + seconds

    def cut(evs, floor=0.0):
        return [[n[:kernel_width if "custom-call" in n[:kernel_width]
                   else name_width], round(s - lo, 9), round(e - lo, 9)]
                for n, s, e in _clip(evs, lo, hi) if e - s >= floor]

    rec = {"device": {str(c): cut(v, min_seconds)
                      for c, v in events["device"].items()},
           "modules": {str(c): cut(v) for c, v in events["modules"].items()},
           "host": [[WINDOW_MARK, 0.0, seconds]] + [
               x for x in cut(events["host"]) if x[0] != WINDOW_MARK]}
    summary = reduce(load_dict(rec))
    rec["recorded"] = {
        "from": os.path.basename(path), "seconds": seconds,
        "dropped_operations_shorter_than_s": min_seconds,
        "window_s": summary["window_s"], "busy_s": summary["busy_s"]}
    with open(out, "w") as f:
        json.dump(rec, f, separators=(",", ":"))
    return rec


def load_dict(rec):
    as_events = lambda xs: [tuple(x) for x in xs]  # noqa: E731
    return {"device": {int(c): as_events(v) for c, v in rec["device"].items()},
            "modules": {int(c): as_events(v)
                        for c, v in rec["modules"].items()},
            "host": as_events(rec["host"])}
