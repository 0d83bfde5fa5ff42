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
  prefill  one window's attention behind `128 w` summaries (w = 0, 7, 15:
           2048, 2944 and 3968 rows under the causal mask) through the
           flash kernel's forward against the chunked XLA form, a whole
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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=32768)
    ap.add_argument("--windows", default="0,7,15")
    ap.add_argument("--buckets", default="8192,32768")
    ap.add_argument("--blocks", default="2048,4096,8192,16384,32768")
    ap.add_argument("--any-device", type=int, default=0)
    args = ap.parse_args(argv)
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
    recs = []

    def note(**rec):
        recs.append(rec)
        print("EVA" + json.dumps(rec), flush=True)

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
    o, leaves = step(state, h, *leaves, pos)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(10):
        o, leaves = step(state, h, *leaves, pos)
    jax.block_until_ready(o)
    ms = (time.perf_counter() - t0) / 10 * 1e3
    pool = sum(int(leaf.nbytes) for leaf in leaves)
    weights = 4 * cfg.hidden_size ** 2 * 2
    live = int(jnp.sum(pos % win + 1 + pos // win * (win // chunk)))
    note(what="decode", slots=b, ring_rows=win,
         summary_rows=args.max_len // chunk, layer_ms=ms, pool_bytes=pool,
         pool_at_819GBs_ms=pool / 819e9 * 1e3,
         pool_and_weights_at_819GBs_ms=(pool + weights) / 819e9 * 1e3,
         live_rows=live, live_rows_and_weights_at_819GBs_ms=(
             live * 2 * heads * hd * 2 + weights) / 819e9 * 1e3)
    del leaves, o

    # ---- prefill: one window behind its summaries, flash against XLA
    def window(q, k, v):
        return attn._attend_window(q, k, v)

    def chunks(q, k, v):
        return M.attend_in_chunks(q[:, :, None], k, v,
                                  attn._scale).reshape(q.shape)

    for w in (int(n) for n in args.windows.split(",")):
        n = win + w * (win // chunk)
        ks = jax.random.split(jax.random.PRNGKey(n), 3)
        q, k, v = (jax.random.normal(kk, (n, heads, hd), jnp.bfloat16)
                   for kk in ks)
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
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
