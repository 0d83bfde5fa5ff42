"""`prove_streamed.py` for a `deepseek_v3` cell: the same readings (the
program, the fp8-operand control, `compare.judge` under the cell's limits),
with this architecture's planted faults put beside its own:

    python3 benchmark/prove_deepseek_v3.py --workload <cell> --seed <n> \\
        [--seconds 12] [--control 1] [--fault <name>] [--flips 1] \\
        [--out <file>]

`selection_bias_left_out` and `routed_scaling_factor_left_out` are planted
as `prove_streamed.py` asks, through what the benchmark hands the program
(the weights it loads, the configuration it builds from).  `k_pe_left_
unrotated` and `kv_a_layernorm_left_out` cannot be reached so: no weight and
no key of the configuration undoes a rotation by position or a division by a
row's own size.  They replace one module function of the program's model
(`_rope`, `_rms`) by a wrapper that knows its one case by shape (the shared
key is the call with ONE head; the latent's norm is the one `kv_lora_rank`
wide) and hands every other call on.

`--tie` is `prove_streamed.py`'s and reads `cohere2_moe`'s leaves: not for
this architecture.  `--flips 1` takes its place (`flip_shares`): with the
reference alone, over the judged positions, how often rounding a routed
layer's input to bfloat16 (what the program's router sees) changes which
experts a token picks, beside the margin at a token's last place (every
expert is held here, so a changed pick changes the layer's output), and how
close the reference's own two best logits lie at those positions: with
163,840 random logits a position the two best of which lie within the
program's rounding is common, and there either token is a fair choice.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, prove_streamed, serve_check  # noqa: E402
from benchmark import weights as W  # noqa: E402
from benchmark.generators import flood_streamed as gen  # noqa: E402


def faults(cfg):
    """name -> () -> (object, attribute, what to put there), for a
    configuration's sizes."""

    def bias_left_out():
        load = gen.load_streamed

        def zeroed(arch, d, model, seed):
            load(arch, d, model, seed)
            state = model.state_dict()
            for layer, kind in enumerate(d["kinds"]):
                if kind == arch.MOE:
                    p = state[arch.program_name("bias", layer)]
                    p._set_data(p._data * 0)

        return gen, "load_streamed", zeroed

    def scale_left_out():
        build = gen.build_program_model

        def unscaled(cfg):
            program = dict(cfg["program"])
            program["kwargs"] = dict(program["kwargs"],
                                     routed_scaling_factor=1.0)
            return build({**cfg, "program": program})

        return gen, "build_program_model", unscaled

    def k_pe_left_unrotated():
        from paddle_tpu.models import deepseek_v3 as model
        rope = model._rope
        return model, "_rope", lambda x, pos, theta: rope(
            x, pos * 0 if x.shape[-2] == 1 else pos, theta)

    def kv_a_layernorm_left_out():
        import jax.numpy as jnp
        from paddle_tpu.models import deepseek_v3 as model
        rms, latent = model._rms, cfg["kv_lora_rank"]
        return model, "_rms", lambda x, g, eps: (
            x.astype(jnp.float32) * g.astype(jnp.float32)
            if x.shape[-1] == latent else rms(x, g, eps))

    return {"selection_bias_left_out": bias_left_out,
            "routed_scaling_factor_left_out": scale_left_out,
            "k_pe_left_unrotated": k_pe_left_unrotated,
            "kv_a_layernorm_left_out": kv_a_layernorm_left_out}


def flip_shares(arch, d, seed, plan, sample, max_len):
    """The reference alone, a layer at a time over the sampled requests: at
    each judged position of each routed layer, the experts picked from the
    layer's float32 input and from that input rounded to bfloat16.  -> a
    routed layer: the share of positions whose picks differ, the median
    margin between the K-th and (K+1)-th biased score, the median of the
    most a score moved; the share of positions with a changed pick in any
    layer; and the margin between the reference's two best logits there
    (its median and the share of positions under 0.01, 0.02, 0.05, 0.1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref, K = arch.reference, d["K"]
    ids, _, mask = serve_check._rows(plan, sample, max_len)
    dense = jax.jit(lambda x, l: ref.layer(x, l, arch.DENSE, d))

    @jax.jit
    def look(x, l):
        """A routed layer as `ref.layer` computes it, and what its router
        saw on the way."""
        x = x + ref.mla(ref.rms(x, l["ln1_g"], d["eps"]), l, d, "float32")
        h = ref.rms(x, l["ln2_g"], d["eps"])
        score = lambda h: jax.nn.sigmoid(  # noqa: E731
            ref.C.mm(h, l["router"], "float32"))
        s, s16 = score(h), score(h.astype(jnp.bfloat16).astype(jnp.float32))
        chosen = lambda s: jnp.sort(  # noqa: E731
            jax.lax.top_k(s + l["bias"], K)[1], axis=-1)
        ranked = jnp.sort(s + l["bias"], axis=-1)
        return (x + ref.ffn(h, l, d, "float32"),
                jnp.any(chosen(s) != chosen(s16), axis=-1),
                ranked[:, -K] - ranked[:, -K - 1],
                jnp.max(jnp.abs(s16 - s), axis=-1))

    top = dict(arch.make_leaves(W.make, d, seed, -1))
    xs = [ref.embed(top, jnp.asarray(row)) for row in ids]
    anywhere = [np.zeros(int(m.sum()), bool) for m in mask]
    layers = []
    for i, kind in enumerate(d["kinds"]):
        lw = dict(arch.make_leaves(W.make, d, seed, i))
        if kind == arch.DENSE:
            xs = [dense(x, lw) for x in xs]
            continue
        xs, *seen = zip(*(look(x, lw) for x in xs))
        del lw
        flipped, margin, moved = (
            [np.asarray(a)[mask[k]] for k, a in enumerate(part)]
            for part in seen)
        for k, f in enumerate(flipped):
            anywhere[k] |= f
        layers.append({
            "layer": i,
            "picks_changed_share": float(np.concatenate(flipped).mean()),
            "margin_median": float(np.median(np.concatenate(margin))),
            "bfloat16_input_moves_a_score_by_median": float(
                np.median(np.concatenate(moved)))})
    # the weights as an argument: closed over, 2.7 GB become constants
    best_two = jax.jit(lambda top, x: jax.lax.top_k(
        ref.head(top, x, d), 2)[0])
    margin = np.concatenate([
        np.asarray(best_two(top, x[np.nonzero(mask[k])[0]]))
        @ np.array([1., -1.]) for k, x in enumerate(xs)])
    return {"positions": int(sum(len(a) for a in anywhere)),
            "best_two_logits_margin": dict(
                median=float(np.median(margin)), **{
                    f"share_under_{t}": float((margin < t).mean())
                    for t in (0.01, 0.02, 0.05, 0.1)}),
            "picks_changed_in_some_layer_share": float(
                np.concatenate(anywhere).mean()),
            "layers": layers}


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--flips", type=int, default=0, choices=(0, 1))
    ap.add_argument("--out", default=None)
    known, rest = ap.parse_known_args(argv)
    argv = rest + ["--workload", known.workload]
    files = harness.Files(spec_path, data_dirs)
    cfg = files.config(files.cell(known.workload)["config"])
    if cfg["arch"] != "deepseek_v3":
        raise SystemExit(f"{known.workload} is no deepseek_v3 cell")
    mine = faults(cfg)
    prove_streamed.FAULTS.update(mine)
    compare, compared = gen.compare_streamed, []
    # what `prove_streamed.main` judged (it returns the numbers alone)
    gen.compare_streamed = lambda *a, **kw: (compared.append(a),
                                             compare(*a, **kw))[1]
    try:
        rec = prove_streamed.main(argv, need_tpu=need_tpu,
                                  spec_path=spec_path, data_dirs=data_dirs)
    finally:
        gen.compare_streamed = compare
        for name in mine:
            prove_streamed.FAULTS.pop(name, None)
    if known.flips:
        rec["flips"] = flip_shares(*compared[0])
        print(json.dumps({"flips": rec["flips"]}), flush=True)
    if known.out:
        os.makedirs(os.path.dirname(known.out) or ".", exist_ok=True)
        with open(known.out, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    main()
