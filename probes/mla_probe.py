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

and what ISSUE 37 asked of one (`--live 256,512,1024`, and nothing else of
the above then):

  live     the decode step's attention with the slots at positions drawn
           from `flood_longgen_8k`'s lengths (a prompt plus a part of its
           output): the kernel that walks each slot's live rows
           (`ops/latent_decode_attention.py`) at each block size given
           against the masked products over the whole pool, the layer
           whole and the attention's core alone.

    chiprun -- python3 probes/mla_probe.py --out chiprun_out/mla_probe.json
    chiprun -- python3 probes/mla_probe.py --live 256,512,1024 \
        --out chiprun_out/mla_live.json

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


def with_state(attn, body):
    """`body(*arrays)` as `call(state, *arrays)`, run with `state` swapped
    into the layer."""
    from paddle_tpu.core.tensor import unwrap
    from paddle_tpu.jit import functional_call

    def call(state, *arrays):
        attn.probe_body = lambda *a: body(*(unwrap(x) for x in a))
        try:
            return functional_call(attn, state, *arrays, method="probe_body")
        finally:
            del attn.probe_body
    return call


def drawn_positions(slots, rows, seed):
    """A position a slot as the cell's mix leaves them in a steady state: a
    prompt's length plus a uniform part of its output's, the request drawn
    by how long it holds its slot (its output)."""
    import numpy as np
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "flood_longgen_8k.json")) as f:
        mix = json.load(f)
    rng = np.random.RandomState(seed)

    def lengths(spec, n):
        x = spec["median"] * np.exp(spec["sigma"] * rng.randn(n))
        return np.clip(np.round(x), spec["min"], spec["max"])

    n = 64 * slots
    prompts, outs = lengths(mix["prompt"], n), lengths(mix["output"], n)
    took = rng.choice(n, size=slots, p=outs / outs.sum())
    pos = prompts[took] + np.floor(rng.rand(slots) * outs[took])
    return np.minimum(pos, rows - 1).astype(np.int32)


def live(args, note):
    """The kernel against the masked products; weights, pools and positions
    are ARGUMENTS of every jitted call (a closure makes them constants of
    the program: PR 31's 40 chip-minutes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import deepseek_v3 as M
    from paddle_tpu.ops import latent_decode_attention as K
    cfg = M.DeepseekV3Config(num_hidden_layers=1)
    attn = M.LatentAttention(cfg)
    state = {k: v._data for k, v in attn.state_dict().items()}
    b, rows = args.slots, args.rows
    lat, rope, heads = cfg.kv_lora_rank, cfg.qk_rope_head_dim, \
        cfg.num_attention_heads
    pos = jnp.asarray(drawn_positions(b, rows, args.seed))
    active = jnp.ones((b,), bool)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    h = jax.random.normal(ks[0], (b, cfg.hidden_size), jnp.bfloat16)
    q_lat = jax.random.normal(ks[1], (b, heads, lat), jnp.float32) * 0.05
    q_pe = jax.random.normal(ks[2], (b, heads, rope), jnp.float32) * 0.05
    steps = args.steps

    def layer(state, h, cbuf, pbuf, pos, active):
        """`steps` dependent decode steps of the layer in one program."""
        def one(_, carry):
            h, cbuf, pbuf = carry[:3]
            o, cbuf, pbuf, went_over = with_state(attn, attn.forward_decode)(
                state, h, cbuf, pbuf, pos, active)
            return (h + (o * 1e-3).astype(h.dtype), cbuf, pbuf, o,
                    went_over.astype(jnp.int32))
        return jax.lax.fori_loop(
            0, steps, one, (h, cbuf, pbuf, h.astype(jnp.float32),
                            jnp.int32(0)))

    def core_kernel(q_lat, q_pe, cbuf, pbuf, pos, active):
        def one(_, q):
            o, _ = K.mla_decode_attention(q, q_pe, cbuf, pbuf, pos, active)
            return q + o * 1e-3
        return jax.lax.fori_loop(0, steps, one, q_lat)

    def core_masked(q_lat, q_pe, cbuf, pbuf, pos, active):
        def one(_, q):
            return q + M.masked_latent_attention(
                q, q_pe, cbuf, pbuf, pos, 1.0, cbuf.dtype) * 1e-3
        return jax.lax.fori_loop(0, steps, one, q_lat)

    def pools():
        return (jax.random.normal(ks[3], (b, rows, lat), jnp.bfloat16),
                jax.random.normal(ks[4], (b, rows, rope), jnp.bfloat16))

    # which form a trace takes is read from the kernel's module, which is no
    # part of jit's cache key: every timing traces anew
    def time_layer():
        jax.clear_caches()
        fn = jax.jit(layer, donate_argnums=(2, 3))
        out = fn(state, h, *pools(), pos, active)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn(state, h, out[1], out[2], pos, active)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps, out[3], int(out[4])

    def time_core(fn):
        jax.clear_caches()
        fn, bufs = jax.jit(fn), pools()
        jax.block_until_ready(fn(q_lat, q_pe, *bufs, pos, active))
        t0 = time.perf_counter()
        out = fn(q_lat, q_pe, *bufs, pos, active)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps, out

    row_bytes = (lat + rope) * 2
    live_rows = int(np.sum(np.asarray(pos) + 1))
    available, K._available = K._available, lambda: False
    try:
        t_layer, want, went_over = time_layer()
        t_core, want_core = time_core(core_masked)
    finally:
        K._available = available
    note(what="live_masked", slots=b, rows=rows, steps=steps,
         mean_pos=float(np.mean(np.asarray(pos))), live_rows=live_rows,
         rows_went_over=went_over, layer_ms=t_layer * 1e3,
         core_ms=t_core * 1e3, live_bytes=live_rows * row_bytes,
         live_read_at_819GBs_ms=live_rows * row_bytes / 819e9 * 1e3,
         pool_read_at_819GBs_ms=b * rows * row_bytes / 819e9 * 1e3)
    block = K.BLOCK_ROWS
    try:
        for r in (int(x) for x in args.live.split(",")):
            K.BLOCK_ROWS = r
            t_layer, got, went_over = time_layer()
            t_core, got_core = time_core(core_kernel)
            diff = lambda a, c: float(jnp.max(jnp.abs(  # noqa: E731
                a.astype(jnp.float32) - c.astype(jnp.float32))))
            note(what="live_kernel", block_rows=r, layer_ms=t_layer * 1e3,
                 core_ms=t_core * 1e3, rows_went_over=went_over,
                 live_pct=100.0 * live_rows / went_over,
                 walked_read_at_819GBs_ms=went_over * row_bytes / 819e9
                 * 1e3, layer_differs_by=diff(got, want),
                 core_differs_by=diff(got_core, want_core),
                 core_size=float(jnp.max(jnp.abs(want_core))))
    finally:
        K.BLOCK_ROWS = block


def written(args, recs):
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--slots", type=int, default=48)
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--prefill", default="2048,8192")
    ap.add_argument("--live", default=None,
                    help="block sizes of the live-rows kernel to time, "
                         "e.g. 256,512,1024; the other measurements are "
                         "left out then")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=37)
    ap.add_argument("--any-device", type=int, default=0,
                    help="1: rehearse --live off the chip (the kernel "
                         "through the interpreter)")
    args = ap.parse_args(argv)
    if args.live:
        import jax
        from paddle_tpu.ops import latent_decode_attention as K
        if jax.default_backend() != "tpu":
            if not args.any_device:
                print("no TPU", file=sys.stderr)
                return 2
            K._INTERPRET = True
        recs = []

        def note(**rec):
            recs.append(rec)
            print("MLA" + json.dumps(rec), flush=True)

        live(args, note)
        return written(args, recs)
    import jax
    import jax.numpy as jnp
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
    f1 = jax.jit(with_state(attn, one_leaf), donate_argnums=(2,))

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
    f2 = jax.jit(with_state(attn, two_leaves), donate_argnums=(2, 3))
    o2, cbuf, pbuf, _ = f2(state, h, cbuf, pbuf, pos)
    jax.block_until_ready(o2)
    t0 = time.perf_counter()
    for _ in range(10):
        o2, cbuf, pbuf, _ = f2(state, h, cbuf, pbuf, pos)
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
            o, *buf, _ = with_state(attn, two_leaves)(state, h, *buf, pos)
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
    return written(args, recs)


if __name__ == "__main__":
    sys.exit(main())
