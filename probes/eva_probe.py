#!/usr/bin/env python
"""What ISSUE 36 left to a chip measurement, at EvaByte's published widths
(one layer's attention, 32 heads of 128, random weights as arguments):

  decode   the decode step's attention over 16 slots x (2048 ring + 2048
           summary rows): projections, the ring write, one softmax over both
           kinds of row, the chunk's summary rewritten; against its bytes at
           819 GB/s (the four leaves read once; the four 4096 x 4096
           matrices beside them).  A decode loop's fusions carry no scope in
           a trace (PERF.md, PR 34), so this probe stands where a metric
           would never report.
  prefill  one window's attention: its 2048 queries against its 2048 keys
           behind `128 w` summaries (w = 0, 7, 15 by default: 2048, 2944 and
           3968 keys under the causal mask, `--windows` to choose) through
           the model's call into the flash kernel's forward against the
           chunked XLA form, a whole
           layer's windowed attention for a bucket of 8192 and of 32768,
           and one whole BLOCK (attention and the gated MLP) over each of
           the cell's five buckets: a prompt is eight of them and the head.

    chiprun -- python3 probes/eva_probe.py --out chiprun_out/eva_probe.json

Prints one `EVA{json}` line a measurement.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def timed(fn, *args, calls=10):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def drawn_positions(slots, max_len, seed):
    """A position a slot as the cell's mix leaves them in a steady state: a
    prompt's length plus a uniform part of its output's, the request drawn
    by how long it holds its slot (its output)."""
    import numpy as np
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "flood_longctx_32k.json")) as f:
        mix = json.load(f)
    rng = np.random.RandomState(seed)

    def lengths(spec, n):
        x = spec["median"] * np.exp(spec["sigma"] * rng.randn(n))
        return np.clip(np.round(x), spec["min"], spec["max"])

    n = 64 * slots
    prompts, outs = lengths(mix["prompt"], n), lengths(mix["output"], n)
    took = rng.choice(n, size=slots, p=outs / outs.sum())
    pos = prompts[took] + np.floor(rng.rand(slots) * outs[took])
    return np.minimum(pos, max_len - 1).astype(np.int32)


def live(args, note):
    """The kernel against the masked products; weights, leaves and
    positions are ARGUMENTS of every jitted call (a closure makes them
    constants of the program: PR 31's 40 chip-minutes), and jit's caches
    are cleared before each timing (which form a trace takes is read from
    the kernel's module, no part of jit's cache key: PR 37's first probe
    was misled by that)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import evabyte as M
    from paddle_tpu.ops import eva_decode_attention as K
    cfg = M.EvaByteConfig(num_hidden_layers=1)
    attn = M.EvaAttention(cfg)
    state = {k: v._data for k, v in attn.state_dict().items()}
    b, win, chunk = args.slots, cfg.window_size, cfg.chunk_size
    heads, hd = cfg.num_attention_heads, cfg.hidden_size // 32
    summaries = args.max_len // chunk
    pos = jnp.asarray(drawn_positions(b, args.max_len, args.seed))
    active = jnp.ones((b,), bool)
    seen = pos // win * (win // chunk)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    h = jax.random.normal(ks[0], (b, cfg.hidden_size), jnp.bfloat16)
    q = (jax.random.normal(ks[1], (b, heads, hd), jnp.float32)
         * 2.0).astype(jnp.bfloat16)
    steps = args.steps

    def leaves():
        return [jax.random.normal(k, (b, n, heads, hd), jnp.bfloat16)
                for k, n in zip(ks[2:], (win, win, summaries, summaries))]

    def decode(state, *arrays):
        """The layer's `forward_decode` with `state` swapped in."""
        attn.probe_body = lambda *a: attn.forward_decode(
            *(M.unwrap(x) for x in a))
        try:
            return functional_call(attn, state, *arrays, method="probe_body")
        finally:
            del attn.probe_body

    def layer(state, h, rk, rv, sk, sv, pos, active):
        """`steps` dependent decode steps of the layer in one program."""
        def one(_, carry):
            h, bufs = carry[0], carry[1:5]
            o, bufs, went_over = decode(state, h, *bufs, pos, active)
            return (h + (o * 1e-3).astype(h.dtype), *bufs, o,
                    went_over.astype(jnp.int32))
        return jax.lax.fori_loop(0, steps, one, (
            h, rk, rv, sk, sv, h.astype(jnp.float32), jnp.int32(0)))

    def core_kernel(q, rk, rv, sk, sv, pos, active):
        def one(_, q):
            o, _ = K.eva_decode_attention(q, rk, rv, sk, sv, pos % win,
                                          seen - 1, active, attn._scale)
            return (q + o * 1e-3).astype(q.dtype)
        return jax.lax.fori_loop(0, steps, one, q)

    def core_masked(q, rk, rv, sk, sv, pos, active):
        def one(_, q):
            o = M.masked_decode_attention(q, rk, rv, sk, sv, pos, seen,
                                          attn._scale)
            return (q + o * 1e-3).astype(q.dtype)
        return jax.lax.fori_loop(0, steps, one, q)

    def time_layer():
        jax.clear_caches()
        fn = jax.jit(layer, donate_argnums=(2, 3, 4, 5))
        out = fn(state, h, *leaves(), pos, active)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn(state, h, *out[1:5], pos, active)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps, out[5], int(out[6])

    def time_core(fn):
        jax.clear_caches()
        fn, bufs = jax.jit(fn), leaves()
        jax.block_until_ready(fn(q, *bufs, pos, active))
        t0 = time.perf_counter()
        out = fn(q, *bufs, pos, active)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps, out

    row_bytes = 2 * heads * hd * 2          # a key row and a value row
    weights = 4 * cfg.hidden_size ** 2 * 2
    live_rows = int(jnp.sum(pos % win + 1 + seen))
    available, K._available = K._available, lambda: False
    try:
        t_layer, want, went_over = time_layer()
        t_core, want_core = time_core(core_masked)
    finally:
        K._available = available
    at_819 = lambda n: n / 819e9 * 1e3  # noqa: E731
    note(what="live_masked", slots=b, ring_rows=win,
         summary_rows=summaries, steps=steps,
         positions=[int(p) for p in np.asarray(pos)], live_rows=live_rows,
         rows_went_over=went_over, layer_ms=t_layer * 1e3,
         core_ms=t_core * 1e3,
         live_read_at_819GBs_ms=at_819(live_rows * row_bytes),
         pool_read_at_819GBs_ms=at_819(went_over * row_bytes),
         layer_at_819GBs_ms=at_819(went_over * row_bytes + weights))
    block = K.BLOCK_ROWS
    diff = lambda a, c: float(jnp.max(jnp.abs(  # noqa: E731
        a.astype(jnp.float32) - c.astype(jnp.float32))))
    try:
        for r in (int(x) for x in args.live.split(",")):
            K.BLOCK_ROWS = r
            t_layer, got, went_over = time_layer()
            t_core, got_core = time_core(core_kernel)
            note(what="live_kernel", block_rows=r, layer_ms=t_layer * 1e3,
                 core_ms=t_core * 1e3, rows_went_over=went_over,
                 live_pct=100.0 * live_rows / went_over,
                 walked_read_at_819GBs_ms=at_819(went_over * row_bytes),
                 layer_at_819GBs_ms=at_819(went_over * row_bytes + weights),
                 core_bandwidth_pct=100.0 * at_819(went_over * row_bytes)
                 / (t_core * 1e3),
                 layer_differs_by=diff(got, want),
                 core_differs_by=diff(got_core, want_core),
                 core_size=float(jnp.max(jnp.abs(
                     want_core.astype(jnp.float32)))))
    finally:
        K.BLOCK_ROWS = block


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=32768)
    ap.add_argument("--windows", default="0,7,15")
    ap.add_argument("--buckets", default="8192,32768")
    ap.add_argument("--blocks", default="2048,4096,8192,16384,32768")
    ap.add_argument("--any-device", type=int, default=0,
                    help="1: rehearse off the chip (with --live, the "
                         "kernel through the interpreter)")
    ap.add_argument("--live", default=None,
                    help="block sizes of the live-rows decode kernel to "
                         "time, e.g. 128,256,512; the other measurements "
                         "are left out then")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=39)
    args = ap.parse_args(argv)
    recs = []

    def note(**rec):
        recs.append(rec)
        print("EVA" + json.dumps(rec), flush=True)

    if args.live:
        import jax
        from paddle_tpu.ops import eva_decode_attention as K
        if jax.default_backend() != "tpu":
            if not args.any_device:
                print("no TPU", file=sys.stderr)
                return 2
            K._INTERPRET = True
        live(args, note)
        return written(args, recs)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import evabyte as M
    if jax.default_backend() != "tpu" and not args.any_device:
        print("no TPU", file=sys.stderr)
        return 2
    cfg = M.EvaByteConfig(num_hidden_layers=1)
    attn = M.EvaAttention(cfg)
    state = {k: v._data for k, v in attn.state_dict().items()}
    b, win, chunk = args.slots, cfg.window_size, cfg.chunk_size
    heads, hd = cfg.num_attention_heads, cfg.hidden_size // 32

    def with_state(body, layer=attn):
        """`body(*arrays)` run with `state` swapped into `layer`."""
        def call(state, *arrays):
            layer.probe_body = lambda *a: body(*(M.unwrap(x) for x in a))
            try:
                return functional_call(layer, state, *arrays,
                                       method="probe_body")
            finally:
                del layer.probe_body
        return call

    # ---- decode: the layer's attention alone
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (b, cfg.hidden_size), jnp.bfloat16)
    # positions spread over the windows, inside chunks and at their ends
    pos = (jnp.arange(b, dtype=jnp.int32) * (args.max_len // b)
           + jnp.arange(b, dtype=jnp.int32) % chunk + win)
    pos = jnp.minimum(pos, args.max_len - 8)
    leaves = [jax.random.normal(jax.random.fold_in(key, i),
                                (b, rows, heads, hd), jnp.bfloat16)
              for i, rows in enumerate(
                  (win, win, args.max_len // chunk, args.max_len // chunk))]
    step = jax.jit(with_state(attn.forward_decode),
                   donate_argnums=(2, 3, 4, 5))
    o, leaves, _ = step(state, h, *leaves, pos)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(10):
        o, leaves, _ = step(state, h, *leaves, pos)
    jax.block_until_ready(o)
    ms = (time.perf_counter() - t0) / 10 * 1e3
    pool = sum(int(leaf.nbytes) for leaf in leaves)
    weights = 4 * cfg.hidden_size ** 2 * 2
    live_rows = int(jnp.sum(pos % win + 1 + pos // win * (win // chunk)))
    note(what="decode", slots=b, ring_rows=win,
         summary_rows=args.max_len // chunk, layer_ms=ms, pool_bytes=pool,
         pool_at_819GBs_ms=pool / 819e9 * 1e3,
         pool_and_weights_at_819GBs_ms=(pool + weights) / 819e9 * 1e3,
         live_rows=live_rows, live_rows_and_weights_at_819GBs_ms=(
             live_rows * 2 * heads * hd * 2 + weights) / 819e9 * 1e3)
    del leaves, o

    # ---- prefill: one window behind its summaries, flash against XLA
    def window(q, k, v):
        return attn._attend_window(q[None], k[None], v[None])

    def chunks(q, k, v):
        front = jnp.zeros((k.shape[0] - q.shape[0],) + q.shape[1:], q.dtype)
        q = jnp.concatenate([front, q])
        return M.attend_in_chunks(q[:, :, None], k, v, attn._scale).reshape(
            q.shape)[front.shape[0]:]

    for w in (int(n) for n in args.windows.split(",")):
        n = win + w * (win // chunk)
        ks = jax.random.split(jax.random.PRNGKey(n), 3)
        q, k, v = (jax.random.normal(kk, (rows, heads, hd), jnp.bfloat16)
                   for kk, rows in zip(ks, (win, n, n)))
        flash, xla = jax.jit(window), jax.jit(chunks)
        a, c = flash(q, k, v), xla(q, k, v)
        pairs = win * (win + 1) / 2 + win * (n - win)
        note(what="window", w=w, rows=n,
             flash_ms=timed(flash, q, k, v) * 1e3,
             xla_chunks_ms=timed(xla, q, k, v, calls=3) * 1e3,
             forms_differ_by=float(jnp.max(jnp.abs(
                 a.astype(jnp.float32) - c.astype(jnp.float32)))),
             pairs_at_peak_ms=4.0 * heads * hd * pairs / 197e12 * 1e3)
        del q, k, v, a, c

    # ---- prefill: a whole layer's attention over a bucket
    import benchmark.arch.evabyte as A
    d = {"window": win, "chunk": chunk, "heads": heads, "hd": hd,
         "H": cfg.hidden_size}
    seq = jax.jit(with_state(lambda h, plen: attn.forward_seq(h, plen)))
    for s_len in (int(n) for n in args.buckets.split(",")):
        hs = jax.random.normal(jax.random.PRNGKey(s_len),
                               (s_len, cfg.hidden_size), jnp.bfloat16)
        plen = jnp.int32(s_len - 5)
        ops, nbytes = A.prefill_attention_cost(s_len, d)
        note(what="layer", bucket=s_len,
             layer_attention_ms=timed(seq, state, hs, plen, calls=5) * 1e3,
             of_which_projections_at_peak_ms=(
                 2.0 * s_len * 4 * cfg.hidden_size ** 2 / 197e12 * 1e3),
             attended_pairs_at_peak_ms=ops / 197e12 * 1e3,
             attention_bytes_at_819GBs_ms=nbytes / 819e9 * 1e3)
        del hs

    # ---- prefill: one whole block over each bucket
    del state, seq
    block = M.EvaByteBlock(cfg)
    bstate = {k: v._data for k, v in block.state_dict().items()}

    whole = jax.jit(with_state(
        lambda h, plen: block.forward_seq(h, plen)[0], block))
    per_token = 2.0 * (4 * cfg.hidden_size ** 2
                       + 3 * cfg.hidden_size * cfg.intermediate_size)
    for s_len in (int(n) for n in args.blocks.split(",") if n):
        hs = jax.random.normal(jax.random.PRNGKey(s_len),
                               (s_len, cfg.hidden_size), jnp.bfloat16)
        ops, _ = A.prefill_attention_cost(s_len, d)
        note(what="block", bucket=s_len,
             block_ms=timed(whole, bstate, hs, jnp.int32(s_len - 5),
                            calls=5) * 1e3,
             operations_at_peak_ms=(per_token * s_len + ops) / 197e12 * 1e3)
        del hs
    return written(args, recs)


def written(args, recs):
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
