"""The flood's loop (`flood.serve`: `open_loop.schedule`, `open_loop.drive`
and the top-up, all as they are) for a configuration too large to exist
twice: `open_loop.ServeSystem` and `serve_check.compare_sample` make the
whole layout in float32 in one call, which for a 4.7B-parameter cut is
18.9 GB beside the program's own copy.

Here the program's model is built once in the configuration's dtype, and
each leaf is made in float32 from `--seed`, the layer's index and the
leaf's place (`arch.make_leaves`), cast, loaded and dropped before the next.
After the window the engine is freed and the reference goes over the sampled
requests a LAYER at a time, that layer's weights made the same way.  The
numbers compared carry the names and meanings `serve_check.py` gives them.

The mix's file is the flood's.  Each step record also gets `rows_full` and
`rows_window` (rows the decoding requests held, the second capped at the
window a REQUEST) and, from the program's `serving_decode` span of that
step, `routed_here`, `routed_all`, `experts_hit`.
"""
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness, serve_check, stats, weights as W
from ..arch import build_program_model, load as load_arch
from . import flood, open_loop


def load_streamed(arch, d, model, seed):
    """The benchmark's weights into the program's model, a leaf at a time:
    made in float32, cast to the leaf's own dtype, the float32 dropped."""
    state = model.state_dict()
    loaded = set()
    for layer in range(-1, d["L"]):
        for ref, leaf in arch.make_leaves(W.make, d, seed, layer):
            name = arch.program_name(ref, layer)
            p = state[name]
            if tuple(p.shape) != tuple(leaf.shape):
                raise ValueError(f"{name}: program {tuple(p.shape)}, "
                                 f"benchmark {tuple(leaf.shape)}")
            p._set_data(leaf.astype(p._data.dtype))
            loaded.add(name)
    missing = set(state) - loaded
    if missing:
        raise KeyError(f"program leaves with no weight: {sorted(missing)}")


def memory_lap(run, name):
    """The device's bytes in use now and at their peak so far, beside the
    set-up's laps: which phase the run's `memory_peak_bytes` comes from."""
    stats = run.devices[0].memory_stats() or {}
    run.extra.setdefault("memory_laps", {})[name] = [
        int(stats.get("bytes_in_use", 0)),
        int(stats.get("peak_bytes_in_use", 0))]


class StreamedServeSystem(open_loop.ServeSystem):
    """`open_loop.ServeSystem` with another set-up (no second copy of the
    weights) and a log of the rows each step's decode call read."""

    def __init__(self, run):
        from paddle_tpu.serving import ServingEngine
        run.lap("import")
        cfg, e = run.config, run.traffic["engine"]
        self.arch = load_arch(cfg["arch"])
        self.d = self.arch.dims(cfg)
        self.layout = None
        self.model = build_program_model(cfg)
        run.lap("model_built")
        memory_lap(run, "model_built")
        load_streamed(self.arch, self.d, self.model, run.seed)
        self.model.eval()
        run.lap("weights_loaded")
        memory_lap(run, "weights_loaded")
        self.engine = ServingEngine(
            self.model, max_slots=e["max_slots"], max_len=e["max_len"],
            prefill_buckets=tuple(e["prefill_buckets"]),
            decode_chunk=e["decode_chunk"],
            max_queue_depth=e.get("max_queue_depth", 64))
        self.max_slots = e["max_slots"]
        self.live = []          # [prompt length, response, tokens seen]
        self.notes = []         # one a step(), beside run.engine_steps

    def reseed(self, seed):
        raise NotImplementedError("one seed a process: a second set of "
                                  "weights does not fit beside the first")

    def submit(self, prompt, out):
        resp = self.engine.submit(prompt, out)
        self.live.append([len(prompt), resp, 0])
        return resp

    def step(self):
        did = self.engine.step()
        full = window = 0
        for rec in self.live:
            n = len(rec[1].tokens_so_far())
            if n > rec[2]:
                # as `drive` counts live_rows: the prompt and the tokens
                # before this step; a window layer holds at most its window
                rows = rec[0] + rec[2]
                full += rows
                window += min(rows, self.d["window"])
                rec[2] = n
        self.live = [rec for rec in self.live if not rec[1].done()]
        self.notes.append({"rows_full": full, "rows_window": window})
        return did


def annotate_steps(run, system):
    """The system's notes and the program's routed counts into the step
    records, a `serving_decode` span to the step it began in.  A program
    that records no such args (or no such span) adds nothing."""
    from paddle_tpu.observability import get_tracer
    spans = sorted((ev[1], ev[6]) for ev in get_tracer().events()
                   if ev[0] == "serving_decode" and ev[6]
                   and "routed_here" in ev[6])
    k = 0
    for st, note in zip(run.engine_steps, system.notes):
        st.update(note)
        while k < len(spans) and spans[k][0] < st["t0"]:
            k += 1
        if k < len(spans) and spans[k][0] <= st["t1"]:
            st.update({key: spans[k][1][key] for key in
                       ("routed_here", "routed_all", "experts_hit")})
            k += 1


# ------------------------------------------------------------ the reference

@functools.lru_cache(maxsize=None)
def _reference_fns(arch_name, dims_json):
    arch = load_arch(arch_name)
    d, ref = json.loads(dims_json), arch.reference
    layer = jax.jit(lambda x, l, kind, precision: ref.layer(
        x, l, kind, d, precision), static_argnums=(2, 3))

    def gaps(top, x, judged):
        """Per position: the reference's best logit minus the judged
        token's."""
        logits = ref.head(top, x, d)
        picked = jnp.take_along_axis(logits, judged[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    choice = jax.jit(lambda top, x, precision: jnp.argmax(
        ref.head(top, x, d, precision), axis=-1).astype(jnp.int32),
        static_argnums=2)
    return jax.jit(ref.embed), layer, jax.jit(gaps), choice


def compare_streamed(arch, d, seed, plan, sample, max_len, control=None):
    """`serve_check.compare_sample` with the reference's weights held a
    layer at a time: every sampled row goes through layer 0, then layer 1,
    and so on.  With `control` (a precision) the tokens judged are those
    the reference computed in that precision puts first."""
    if not sample:
        return {"token_logit_gap": float("inf"), "checked_tokens": 0,
                "mismatched_tokens": 0}
    embed, layer, gaps_fn, choice_fn = _reference_fns(
        arch.__name__.rsplit(".", 1)[-1], json.dumps(d, sort_keys=True))
    ids, picks, mask = serve_check._rows(plan, sample, max_len)
    top = dict(arch.make_leaves(W.make, d, seed, -1))
    xs = [embed(top, jnp.asarray(row)) for row in ids]
    xc = list(xs) if control else []
    for i, kind in enumerate(d["kinds"]):
        lw = dict(arch.make_leaves(W.make, d, seed, i))
        xs = [layer(x, lw, kind, "float32") for x in xs]
        xc = [layer(x, lw, kind, control) for x in xc]
        del lw
    worst, wrong = 0.0, 0
    for k in range(len(sample)):
        judged = (choice_fn(top, xc[k], control) if control
                  else jnp.asarray(picks[k]))
        g = np.asarray(gaps_fn(top, xs[k], judged))[mask[k]]
        if not np.all(np.isfinite(g)):
            return {"token_logit_gap": float("inf"),
                    "checked_tokens": int(mask.sum()),
                    "mismatched_tokens": -1}
        worst = max(worst, float(g.max()))
        wrong += int((g > 0).sum())
    return {"token_logit_gap": worst, "checked_tokens": int(mask.sum()),
            "mismatched_tokens": wrong}


def mismatched_share(checked, mismatched):
    """The share of the judged tokens that are not the reference's own
    choice.  The widest gap alone does not separate this configuration from
    its control: where the 8th and 9th of a token's 128 router scores lie
    within bfloat16's rounding of the layer's input, the program and the
    reference pick different experts and that one position's logits move
    by up to 1 (PERF.md section 2), so the cell compares how OFTEN a served
    token is not the reference's choice and reports the gap beside it."""
    if checked <= 0 or mismatched < 0:
        return float("inf")
    return mismatched / checked


# ------------------------------------------------------------------ the end

def close(run, system, done):
    """After the window and its drain: the run's counters, the memory's
    peak, the step records completed, the engine freed, the request log.
    -> (arch, d)."""
    run.counters["compiles_after_warmup"] = system.compiles_after_warmup()
    from paddle_tpu import programs
    run.counters["store"] = programs.store_stats()
    run.extra["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    annotate_steps(run, system)
    arch, d = system.arch, system.d
    run.extra["max_slots"] = system.max_slots
    system.free()
    for rec in done:
        rec.pop("resp", None)
        rec["tokens"] = rec["n"]
        rec["queue_wait"] = (rec["admit_step"] - rec["due"]
                             if rec["admit_step"] is not None else None)
        rec["late"] = rec["submitted"] - rec["due"]
    run.requests = done
    return arch, d


def finish(run, system, plan, done, t0, t_end):
    """As `open_loop.finish`, with the streamed comparison."""
    arch, d = close(run, system, done)
    t_ref = time.perf_counter()
    sample = serve_check.pick_sample(done, run.seed,
                                     run.traffic["check_requests"])
    numbers = compare_streamed(arch, d, run.seed, plan, sample,
                               run.traffic["engine"]["max_len"])
    run.extra["reference_s"] = time.perf_counter() - t_ref
    memory_lap(run, "reference")
    run.extra["checked_tokens"] = numbers.pop("checked_tokens")
    run.extra["mismatched_tokens"] = numbers.pop("mismatched_tokens")
    numbers["mismatched_token_share"] = mismatched_share(
        run.extra["checked_tokens"], run.extra["mismatched_tokens"])
    numbers["compiles_in_window"] = float(
        run.counters["compiles_after_warmup"])
    failed = sum(1 for r in done if r["failed"])
    numbers["failed_requests"] = float(failed)
    run.extra.update(
        drain_s=t_end - (t0 + run.seconds),
        generator_late_p95_ms=stats.percentile(
            [r["late"] * 1e3 for r in done], 95),
        requests=len(done), engine_steps=len(run.engine_steps))
    if run.engine_steps:
        worst = max(run.engine_steps, key=lambda st: st["dur"])
        run.extra["longest_engine_step"] = {
            "ms": worst["dur"] * 1e3, "at_s": worst["t0"] - t0,
            "admitted": worst["admitted"]}
    e2e = stats.serve_end_to_end(done, run.seconds, (t_end - t0) * 1e3)
    # tokens DELIVERED IN THE WINDOW, not those of the drain after it
    e2e["serve_tokens_per_s"] = sum(
        st["tokens"] for st in run.engine_steps
        if st["t1"] <= t0 + run.seconds) / run.seconds
    return {"attempted": len(done), "failed": failed, "end_to_end": e2e,
            "numbers": numbers}


def run(run):
    system = StreamedServeSystem(run)
    warm = system.warmup()
    run.lap("engine_warmed")
    memory_lap(run, "engine_warmed")
    run.counters["warmup_seconds"] = warm["seconds"]
    plan, done, t0, t_end = flood.serve(run, system)
    memory_lap(run, "window_and_drain")
    return finish(run, system, plan, done, t0, t_end)
