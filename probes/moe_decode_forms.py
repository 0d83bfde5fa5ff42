#!/usr/bin/env python
"""The two forms of `F.moe_ffn_held`'s expert products on the chip, one
routed layer at the published widths (random weights, bfloat16, passed as
ARGUMENTS: closed over, 1.6 GB of jit constants cost PR 31 40 chip-minutes):

  grouped  the picks sorted by expert, one `jax.lax.ragged_dot` a matrix;
  batched  every row through every held expert, one batched product a
           matrix, weighed by the row's share (0 where it did not pick).

At Moonlight's shape (64 experts of 2048 x 1408 all held, 6 a token, the
selection bias and the scale) over `--rows` tokens and at command-a-plus's
(16 held of 128 experts of 4096 x 4096, 8 a token) over `--cmdap-rows`: ms a
layer for each form, the share of the held experts the picks hit, and how
far the outputs lie apart.  These readings set `_BATCHED_COVER` and
`_BATCHED_ROWS` (`nn/functional/moe.py`; PERF.md, PR 35).

    chiprun -- python3 probes/moe_decode_forms.py \
        --out chiprun_out/moe_decode_forms.json

Prints one `MOEFORM{json}` line a shape.  Needs the chip.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# name: experts, held, d_model, d_hidden, top_k, bias and scale
SHAPES = {
    "moonlight": (64, 64, 2048, 1408, 6, True),
    "cmdap": (128, 16, 4096, 4096, 8, False),
}


def timed(fn, *args, calls=40):
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
    ap.add_argument("--rows", default="8,16,24,32,48,64,96,128,192,256")
    ap.add_argument("--cmdap-rows", default="16,48")
    ap.add_argument("--any-device", type=int, default=0,
                    help="1: run where there is no TPU (a rehearsal; the "
                    "times mean nothing)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional import moe
    if jax.default_backend() != "tpu" and not args.any_device:
        print("no TPU", file=sys.stderr)
        return 2
    recs = []
    rule = moe._batched_form
    for name, rows in (("moonlight", args.rows), ("cmdap", args.cmdap_rows)):
        n_experts, n_held, d, hid, top_k, biased = SHAPES[name]
        if args.any_device:
            d, hid = d // 16, hid // 16
        ks = jax.random.split(jax.random.PRNGKey(len(name)), 6)
        mat = lambda k, *shape: (jax.random.normal(  # noqa: E731
            k, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)
        router = jax.random.normal(ks[0], (d, n_experts), jnp.float32) * 0.02
        weights = (mat(ks[1], n_held, d, hid), mat(ks[2], n_held, d, hid),
                   mat(ks[3], n_held, hid, d))
        bias = (jax.random.normal(ks[4], (n_experts,), jnp.float32) * 0.03
                if biased else None)
        held = tuple(range(n_held))

        def layer(form, x, router, gate, up, down, bias):
            # the rule is static and takes no argument: the probe stands
            # in for it while each form is traced
            moe._batched_form = lambda *a: form == "batched"
            try:
                return moe.moe_ffn_held.raw(
                    x, router, gate, up, down, held, top_k,
                    valid=jnp.ones((x.shape[0],), bool), select_bias=bias,
                    scale=2.446 if biased else None)
            finally:
                moe._batched_form = rule

        for t in (int(n) for n in rows.split(",")):
            x = jax.random.normal(jax.random.fold_in(ks[5], t), (t, d),
                                  jnp.bfloat16)
            rec = {"shape": name, "rows": t, "rule_takes": (
                "batched" if rule(t, top_k, n_experts) else "grouped")}
            outs = {}
            for form in ("grouped", "batched"):
                fn = jax.jit(functools.partial(layer, form))
                rec[form + "_ms"] = timed(fn, x, router, *weights,
                                          bias) * 1e3
                y, here, hit, products, _ = fn(x, router, *weights, bias)
                outs[form] = y.astype(jnp.float32)
                rec[form + "_products"] = int(products)
            rec.update(
                picks_here=int(here), experts_hit_pct=100.0 * int(hit)
                / n_held, outputs_differ_by=float(jnp.max(jnp.abs(
                    outs["grouped"] - outs["batched"]))),
                largest_output=float(jnp.max(jnp.abs(outs["grouped"]))),
                held_bytes_at_819GBs_ms=3 * n_held * d * hid * 2
                / 819e9 * 1e3,
                batched_operations_at_197TFLOPs_ms=3 * 2 * t * n_held * d
                * hid / 197e12 * 1e3)
            recs.append(rec)
            print("MOEFORM" + json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "records": recs}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
