#!/usr/bin/env python
"""Where one prefill of a routed model spends the chip's time, by named
scope and operation (ISSUE 31: the routed layer's stretch, operation by
operation, on the parent and on the change).

Builds the program's model from a benchmark configuration's `program`
section (random weights of its own initializer: times do not depend on
them, routing is then near uniform), compiles `model.forward_prefill` for
each bucket, runs it under the profiler and joins every device operation
of the traced calls to the compiled module's own metadata: an event is
named like its instruction, and the instruction's `op_name` holds the
`jax.named_scope` path down to the primitive.  `F.moe_ffn_held` is wrapped
in a scope of the probe's (`routed_ffn`), which adds nothing to the
program but the name.  A Pallas kernel is one custom call named by the
scope it stands in (`%window_attention.1`, op_name
`.../window_attention/pallas_call`), so the flash kernel of ISSUE 33 falls
under the same `window_attention` / `full_attention` scopes as the XLA form
of the parent: the split of both reads the same three scopes
(`tests/test_chip_compile.py::test_flash_grouped_forward` holds the name).

    chiprun -- python3 probes/prefill_split.py --out chiprun_out/split/x.json

Needs the chip (a CPU trace has no device plane: it exits 2).  Prints one
`SPLIT{json}` line a bucket and writes every row to `--out`.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCOPES = ("routed_ffn", "window_attention", "full_attention")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# operations that span others' events: their time is their bodies'
_SPANNING = {"while", "conditional", "call"}
FILL = 0.72     # share of a bucket the prompt fills: the cell's mean prompt
#                 is padded 1.4-fold
CALLS = 3       # traced calls a bucket


def instruction_labels(hlo_text):
    """{instruction name: (scope, what)}: the scope is the first of SCOPES
    on the instruction's `op_name` path, `what` the path below it (it ends
    in the primitive) and the shape of the (first) result."""
    labels = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        path = op.group(1).split("/") if op else []
        scope = next((s for s in SCOPES if s in path), "other")
        below = path[path.index(scope) + 1:] if scope in path else path[-1:]
        labels[m.group(1)] = (scope, "/".join(below) + " -> "
                              + (m.group(2) or "?").lstrip("("))
    return labels


def split(events, modules, program, labels):
    """Device seconds a call of `program`, by (scope, kind of operation,
    what): the operations inside each of the program's module events."""
    from benchmark.trace_reduce import op_kind
    calls = [(s, e) for name, s, e in modules if name.startswith(program)]
    rows = {}
    for name, s, e in events:
        if not any(c0 <= s and e <= c1 + 1e-9 for c0, c1 in calls):
            continue
        kind = op_kind(name)
        if kind in _SPANNING:
            continue
        scope, what = labels.get(name.split(" ", 1)[0], ("other", "?"))
        if kind.startswith("ragged-dot"):   # the compiler's own name: no
            scope = "routed_ffn"            # scope of ours is left on it
        row = rows.setdefault((scope, kind, what), [0.0, 0])
        row[0] += e - s
        row[1] += 1
    n = max(len(calls), 1)
    return (sum(e - s for s, e in calls) / n, len(calls),
            sorted(([scope, kind, what, t / n * 1e3, c / n]
                    for (scope, kind, what), (t, c) in rows.items()),
                   key=lambda r: -r[3]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark", "configs", "command-a-plus-1of8.json"))
    ap.add_argument("--buckets", default="8192,2048")
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import trace_reduce
    from benchmark.arch import build_program_model
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import cohere_moe

    class Scoped:
        """`moe_ffn_held` with its raw form under the probe's scope."""

        def __init__(self, op):
            self.op = op

        def raw(self, *a, **kw):
            with jax.named_scope("routed_ffn"):
                return self.op.raw(*a, **kw)

    cohere_moe.moe_ffn_held = Scoped(cohere_moe.moe_ffn_held)
    with open(args.config) as f:
        model = build_program_model(json.load(f))
    model.eval()
    state = {k: t._data for k, t in model.state_dict().items()}

    def prefill(state, ids, plen):
        logits, kv, counts = functional_call(
            model, state, ids, plen, training=False,
            method="forward_prefill")
        return logits, kv, counts

    out = {"device": jax.devices()[0].device_kind, "fill": FILL,
           "buckets": {}}
    rng = np.random.RandomState(0)
    for bucket in (int(b) for b in args.buckets.split(",")):
        ids = jnp.asarray(rng.randint(0, model.config.vocab_size,
                                      (1, bucket)), jnp.int32)
        plen = jnp.asarray(int(bucket * FILL), jnp.int32)
        compiled = jax.jit(prefill).lower(state, ids, plen).compile()
        labels = instruction_labels(compiled.as_text())
        counts = np.asarray(jax.block_until_ready(
            compiled(state, ids, plen))[2])
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(CALLS):
                    jax.block_until_ready(compiled(state, ids, plen))
            events = trace_reduce.load(trace_reduce.find_xplane(tmp))
        if not events["device"]:
            print("no device plane in the trace: this probe needs the chip",
                  file=sys.stderr)
            return 2
        chip = sorted(events["device"])[0]
        call_s, calls, rows = split(events["device"][chip],
                                    events["modules"].get(chip, []),
                                    "jit_prefill", labels)
        by_scope = {}
        for scope, _, _, ms, _ in rows:
            by_scope[scope] = by_scope.get(scope, 0.0) + ms
        rec = {"bucket": bucket, "plen": int(plen), "calls": calls,
               "call_ms": call_s * 1e3, "counts": counts.tolist(),
               "by_scope_ms": by_scope}
        out["buckets"][str(bucket)] = dict(rec, rows=rows)
        routed = [r[1:] for r in rows if r[0] == "routed_ffn"]
        print("SPLIT" + json.dumps(dict(rec, routed_ffn=routed[:args.top])))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
