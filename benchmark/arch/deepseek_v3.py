"""deepseek_v3 (Moonlight-16B-A3B): config keys -> sizes, the weights' layout
BY LAYER (the leading layer dense, the rest routed), the program's names, and
the least operations and bytes of its programs.  A layer's weights are made,
cast and loaded one leaf at a time (`leaf_seed`), as `arch/cohere2_moe.py`
does and for its reason.

The cache is one row of `kv_lora_rank + qk_rope_head_dim` numbers a token a
layer.  A decode step's least work is the ABSORBED form's: `kv_b_proj` is
multiplied once a token (into the query and out of the output: as many
products as expanding one row), and a cached row costs its `latent + rope`
numbers for the score and its `latent` for the output, a head."""
from ..reference import deepseek_v3 as reference  # noqa: F401

CAUSAL = True
BF16 = 2
DENSE, MOE = "dense", "moe"


def dims(cfg):
    L, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"V": cfg["vocab_size"], "H": cfg["hidden_size"], "L": L,
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "latent": cfg["kv_lora_rank"],
            "I_dense": cfg["intermediate_size"],
            "I": cfg["moe_intermediate_size"], "E": cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"], "S": cfg["n_shared_experts"],
            "held": list(range(cfg["n_routed_experts"])),
            "scale": float(cfg["routed_scaling_factor"]),
            # no window: `rows_window` of the step records equals `rows_full`
            "window": cfg["max_position_embeddings"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
            "kinds": [DENSE if i < first else MOE for i in range(L)],
            "std": cfg["initializer_range"],
            "bias_std": cfg["e_score_correction_bias_std"],
            "kva_std": cfg["kv_a_proj_with_mqa_std"]}


def layer_layout(d, kind):
    """One layer's leaves: every matrix normal(0, std) but `wkva` (normal(0,
    kva_std): the configuration's `assumed` says why), norm scales 1, the
    router's selection bias normal(0, bias_std)."""
    n, s, H = "normal", d["std"], d["H"]
    heads, lat = d["heads"], d["latent"]
    out = {"ln1_g": ((H,), "ones", 0.0), "ln2_g": ((H,), "ones", 0.0),
           "wq": ((H, heads * (d["nope"] + d["rope"])), n, s),
           "wkva": ((H, lat + d["rope"]), n, d["kva_std"]),
           "kva_g": ((lat,), "ones", 0.0),
           "wkvb": ((lat, heads * (d["nope"] + d["vd"])), n, s),
           "wo": ((heads * d["vd"], H), n, s)}
    if kind == DENSE:
        I = d["I_dense"]
        out.update(wg=((H, I), n, s), wu=((H, I), n, s), wd=((I, H), n, s))
        return out
    E, I, SI = d["E"], d["I"], d["S"] * d["I"]
    out.update(router=((H, E), n, s), bias=((E,), n, d["bias_std"]),
               eg=((E, H, I), n, s), eu=((E, H, I), n, s),
               ed=((E, I, H), n, s),
               sg=((H, SI), n, s), su=((H, SI), n, s), sd=((SI, H), n, s))
    return out


def top_layout(d):
    return {"emb": ((d["V"], d["H"]), "normal", d["std"]),
            "lnf_g": ((d["H"],), "ones", 0.0),
            "head": ((d["H"], d["V"]), "normal", d["std"])}


def leaf_seed(seed, layer, leaf_no):
    """The seed `weights.make` gets for one leaf: from `--seed`, the layer's
    index (-1: the top level) and the leaf's place in its sorted layout."""
    return int(seed) * 4096 + (layer + 1) * 64 + leaf_no + 1


def make_leaves(make, d, seed, layer):
    """Yields (reference leaf name, float32 array), one leaf at a time, for
    `layer` (an index, or -1 for the top level).  `make` is
    `benchmark.weights.make`."""
    layout = (top_layout(d) if layer < 0
              else layer_layout(d, d["kinds"][layer]))
    for k, name in enumerate(sorted(layout)):
        yield name, make({name: layout[name]},
                         leaf_seed(seed, layer, k))[name]


_TOP = {"emb": "embed_tokens", "lnf_g": "norm", "head": "lm_head"}
_LAYER = {"ln1_g": "input_layernorm", "ln2_g": "post_attention_layernorm",
          "wq": "self_attn.q_proj", "wkva": "self_attn.kv_a_proj_with_mqa",
          "kva_g": "self_attn.kv_a_layernorm", "wkvb": "self_attn.kv_b_proj",
          "wo": "self_attn.o_proj",
          "wg": "mlp.gate_proj", "wu": "mlp.up_proj", "wd": "mlp.down_proj",
          "router": "mlp.experts.router",
          "bias": "mlp.experts.e_score_correction_bias",
          "eg": "mlp.experts.gate", "eu": "mlp.experts.up",
          "ed": "mlp.experts.down", "sg": "mlp.shared_experts.gate_proj",
          "su": "mlp.shared_experts.up_proj",
          "sd": "mlp.shared_experts.down_proj"}


def program_name(ref, layer):
    """The program's state name of a reference leaf."""
    return _TOP[ref] if layer < 0 else f"layers.{layer}.{_LAYER[ref]}"


# ------------------------------------------------- operations and bytes

def attn_params(d):
    """A layer's attention: q, kv_a, kv_b and o projections."""
    H, heads, lat = d["H"], d["heads"], d["latent"]
    return (H * heads * (d["nope"] + d["rope"]) + H * (lat + d["rope"])
            + lat * heads * (d["nope"] + d["vd"]) + heads * d["vd"] * H)


def expert_params(d):
    return 3 * d["H"] * d["I"]


def _n(d, kind):
    return sum(k == kind for k in d["kinds"])


def layer_params(d, kind):
    """Every number of one layer, norm scales and the bias too."""
    small = 2 * d["H"] + d["latent"]
    if kind == DENSE:
        return attn_params(d) + 3 * d["H"] * d["I_dense"] + small
    return (attn_params(d) + (d["E"] + d["S"]) * expert_params(d)
            + d["H"] * d["E"] + d["E"] + small)


def param_count(d):
    return (2 * d["V"] * d["H"] + d["H"]
            + sum(layer_params(d, k) for k in d["kinds"]))


def _token_params(d):
    """What one token multiplies, summed over layers: attention's four
    matrices (`kv_b_proj` once, expanded or absorbed), the dense MLP or the
    router, K experts and the shared ones."""
    routed = (d["K"] + d["S"]) * expert_params(d) + d["H"] * d["E"]
    return (d["L"] * attn_params(d)
            + _n(d, DENSE) * 3 * d["H"] * d["I_dense"] + _n(d, MOE) * routed)


def prefill_flops(n, d):
    """Forward pass over a prompt of n tokens: the products of every token,
    EXPANDED attention under the causal mask (a pair costs a head its nope +
    rope numbers for the score and its v numbers for the output), and the
    head at the last position alone."""
    pairs = n * (n + 1) / 2
    return (2.0 * n * _token_params(d)
            + d["L"] * 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["vd"])
            * pairs + 2.0 * d["V"] * d["H"])


def decode_token_flops(rows, d, rows_window=None):
    """Forward pass of one token that attends `rows` cached rows, ABSORBED:
    a row costs a head latent + rope numbers for the score and latent for
    the output.  (`rows_window` is the harness's second reading of the same
    rows: there is no window.)"""
    return (2.0 * _token_params(d)
            + d["L"] * 2.0 * d["heads"] * rows
            * (2 * d["latent"] + d["rope"]) + 2.0 * d["V"] * d["H"])


def kv_row_bytes(d):
    """One cached row of one layer: the latent and the rotated key numbers,
    bfloat16."""
    return (d["latent"] + d["rope"]) * BF16


def decode_call_bytes(d, chunk, experts_hit, rows_full, rows_window):
    """The least bytes of one decode call of `chunk` dependent steps: each
    step reads the weights outside the routed experts once (bfloat16; the
    router and its bias float32), the head, and the LIVE rows of the cache;
    an expert is read only where a token was routed to it (`experts_hit`:
    experts hit, summed over layers and the chunk's steps).  `rows_full` is
    the rows the requests hold summed over requests (`rows_window` is the
    same number here)."""
    per_step = (d["L"] * attn_params(d) * BF16
                + _n(d, DENSE) * 3 * d["H"] * d["I_dense"] * BF16
                + _n(d, MOE) * (d["S"] * expert_params(d) * BF16
                                + (d["H"] + 1) * d["E"] * 4)
                + d["V"] * d["H"] * BF16
                + kv_row_bytes(d) * rows_full * d["L"])
    return float(chunk * per_step + experts_hit * expert_params(d) * BF16)


def expert_product_cost(picks, experts_hit, d):
    """(operations, bytes) of the grouped products at the least: three
    H x I products a pick, and an expert's three matrices once where it was
    hit (activations are noise beside them)."""
    return (2.0 * picks * expert_params(d),
            float(experts_hit * expert_params(d) * BF16))

