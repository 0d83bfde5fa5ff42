#!/usr/bin/env python
"""What ISSUE 34 left to a chip measurement, at Moonlight's published widths
(one layer's attention, random weights):

  decode   the absorbed step over 48 slots x 8192 cached rows with the
           cache as TWO leaves (…, 512) and (…, 64) (the model's form)
           against ONE leaf (B, rows, 576), score and output each one
           product against the whole row;
  prefill  a prompt's expanded attention through the flash kernel with the
           heads padded to 256 lanes against the chunked XLA form;
  scopes   whether the profiler's device events of a decode step inside a
           `lax.scan` carry `mla_absorbed_attention` in any of their stats
           (the reader of `mla_decode_attention_roofline` finds them so).

    chiprun -- python3 probes/mla_probe.py --out chiprun_out/mla_probe.json

Prints one `MLA{json}` line a measurement.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def timed(fn, *args, calls=10):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--slots", type=int, default=48)
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--prefill", default="2048,8192")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import deepseek_v3 as M
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cfg = M.DeepseekV3Config(num_hidden_layers=1)
    attn = M.LatentAttention(cfg)
    state = {k: v._data for k, v in attn.state_dict().items()}
    b, rows, lat, rope = args.slots, args.rows, cfg.kv_lora_rank, \
        cfg.qk_rope_head_dim
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (b, cfg.hidden_size), jnp.bfloat16)
    pos = jnp.arange(b, dtype=jnp.int32) * (rows // b) + 7
    recs = []

    def note(**rec):
        recs.append(rec)
        print("MLA" + json.dumps(rec), flush=True)

    def with_state(body):
        """`body(*arrays)` run with `state` swapped into the layer."""
        def call(state, *arrays):
            attn.probe_body = lambda *a: body(*(M.unwrap(x) for x in a))
            try:
                return functional_call(attn, state, *arrays,
                                       method="probe_body")
            finally:
                del attn.probe_body
        return call

    # ---- decode: two leaves (the model's) against one
    two_leaves = attn.forward_decode

    def one_leaf(h, buf, pos):
        q_nope, q_pe, c, k_pe = attn._query_and_row(h, pos)
        w = attn._kv_b()
        nope = cfg.qk_nope_head_dim
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope.astype(h.dtype),
                           w[..., :nope], preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_pe], axis=-1).astype(h.dtype)
        buf = buf.at[jnp.arange(b), jnp.minimum(pos, rows - 1)].set(
            jnp.concatenate([c, k_pe], axis=-1))
        scores = jnp.einsum("bhc,brc->bhr", q, buf,
                            preferred_element_type=jnp.float32) * attn._scale
        keep = jnp.arange(rows)[None, None, :] <= pos[:, None, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        o_lat = jnp.einsum("bhr,brc->bhc", probs.astype(h.dtype), buf,
                           preferred_element_type=jnp.float32)
        o = jnp.einsum("bhc,chd->bhd", o_lat[..., :lat].astype(h.dtype),
                       w[..., nope:], preferred_element_type=jnp.float32)
        return attn._out(o.astype(h.dtype)), buf

    buf = jax.random.normal(key, (b, rows, lat + rope), jnp.bfloat16)
    f1 = jax.jit(with_state(one_leaf), donate_argnums=(2,))

    def loop1(buf):
        for _ in range(10):
            o, buf = f1(state, h, buf, pos)
        return o, buf

    o1, buf = f1(state, h, buf, pos)
    jax.block_until_ready(o1)
    t0 = time.perf_counter()
    o1, buf = loop1(buf)
    jax.block_until_ready(o1)
    t1 = (time.perf_counter() - t0) / 10
    cbuf, pbuf = buf[..., :lat] + 0, buf[..., lat:] + 0
    f2 = jax.jit(with_state(two_leaves), donate_argnums=(2, 3))
    o2, cbuf, pbuf = f2(state, h, cbuf, pbuf, pos)
    jax.block_until_ready(o2)
    t0 = time.perf_counter()
    for _ in range(10):
        o2, cbuf, pbuf = f2(state, h, cbuf, pbuf, pos)
    jax.block_until_ready(o2)
    t2 = (time.perf_counter() - t0) / 10
    pool = b * rows * (lat + rope) * 2
    note(what="decode", slots=b, rows=rows, one_leaf_ms=t1 * 1e3,
         two_leaves_ms=t2 * 1e3, pool_bytes=pool,
         pool_read_at_819GBs_ms=pool / 819e9 * 1e3,
         outputs_differ_by=float(jnp.max(jnp.abs(
             o1.astype(jnp.float32) - o2.astype(jnp.float32)))),
         buffer_bytes_on_device=int(buf.nbytes))

    # ---- scopes: a scan of 4 steps under the profiler
    def chunk(state, h, buf, pos):
        def step(carry, _):
            buf, pos = carry
            o, *buf = with_state(two_leaves)(state, h, *buf, pos)
            return (tuple(buf), pos + 1), jnp.sum(o)
        (buf, _), s = jax.lax.scan(step, (tuple(buf), pos), None, length=4)
        return s, buf

    fc = jax.jit(chunk, donate_argnums=(2,))
    del buf
    buf = (cbuf, pbuf)
    s, buf = fc(state, h, buf, pos)
    jax.block_until_ready(s)
    tdir = tempfile.mkdtemp(prefix="mla_probe_")
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        s, buf = fc(state, h, buf, pos)
    jax.block_until_ready(s)
    jax.profiler.stop_trace()
    from benchmark import trace_reduce
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_reduce.find_xplane(tdir))
    seen, total, with_scope, scoped_s, stat_names = [], 0, 0, 0.0, set()
    for plane in data.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                total += 1
                stats = {k: v for k, v in ev.stats}
                stat_names.update(stats)
                hit = [k for k, v in stats.items()
                       if isinstance(v, str) and "mla_absorbed_attention" in v]
                if hit:
                    with_scope += 1
                    scoped_s += ev.duration_ns * 1e-9
                    if len(seen) < 4:
                        seen.append({"name": ev.name[:80], "stat": hit[0],
                                     "value": str(stats[hit[0]])[:200]})
    note(what="scopes", device_events=total, with_scope=with_scope,
         scoped_ms_a_step=scoped_s / 12 * 1e3, stat_names=sorted(stat_names),
         examples=seen)
    del buf, cbuf, pbuf, s

    # ---- prefill: flash at 256 lanes against the XLA form
    for s_len in (int(n) for n in args.prefill.split(",")):
        ks = jax.random.split(jax.random.PRNGKey(s_len), 3)
        q = jax.random.normal(ks[0], (s_len, 16, 192), jnp.float32)
        k = jax.random.normal(ks[1], (s_len, 16, 192), jnp.bfloat16)
        v = jax.random.normal(ks[2], (s_len, 16, 128), jnp.bfloat16)
        flash = jax.jit(attn._attend_seq)
        xla = jax.jit(lambda q, k, v: M.attend_in_chunks(
            q.astype(k.dtype)[:, :, None], k, v, attn._scale).reshape(
                s_len, 16, 128))
        a, c = flash(q, k, v), xla(q, k, v)
        note(what="prefill", rows=s_len,
             flash256_ms=timed(flash, q, k, v) * 1e3,
             xla_chunks_ms=timed(xla, q, k, v, calls=3) * 1e3,
             forms_differ_by=float(jnp.max(jnp.abs(
                 a.astype(jnp.float32) - c.astype(jnp.float32)))),
             products_at_peak_ms=4.0 * 16 * 160 * s_len * (s_len + 1) / 2
             / 197e12 * 1e3)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
