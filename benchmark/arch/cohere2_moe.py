"""cohere2_moe (command-a-plus): config keys -> sizes, the weights' layout BY
LAYER, the program's names, and the least operations and bytes of its
programs.  A layer's weights are made, cast and loaded one leaf at a time
(`leaf_seed`): the whole model in float32 does not fit beside the program's
copy, and the reference holds one layer at a time."""
from ..reference import cohere2_moe as reference  # noqa: F401

CAUSAL = True
BF16 = 2


def dims(cfg):
    L = cfg["num_hidden_layers"]
    held = list(cfg.get("experts_held", range(cfg["num_experts"])))
    assert len(held) == cfg.get("num_experts_held", len(held))
    return {"V": cfg["vocab_size"], "H": cfg["hidden_size"], "L": L,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "I": cfg["intermediate_size"], "E": cfg["num_experts"],
            "K": cfg["num_experts_per_tok"], "S": cfg["num_shared_experts"],
            "held": held, "window": cfg["sliding_window"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["layer_norm_eps"],
            "kinds": list(cfg["layer_types"][:L]),
            "logit_scale": float(cfg["logit_scale"]),
            "std": cfg["initializer_range"]}


def layer_layout(d):
    """One layer's leaves: every matrix normal(0, std), the norm's scale 1."""
    n, s = "normal", d["std"]
    H, I, q, kv = d["H"], d["I"], d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    held, S = len(d["held"]), d["S"]
    return {"ln_g": ((H,), "ones", 0.0),
            "wq": ((H, q), n, s), "wk": ((H, kv), n, s),
            "wv": ((H, kv), n, s), "wo": ((q, H), n, s),
            "router": ((H, d["E"]), n, s),
            "eg": ((held, H, I), n, s), "eu": ((held, H, I), n, s),
            "ed": ((held, I, H), n, s),
            "sg": ((S, H, I), n, s), "su": ((S, H, I), n, s),
            "sd": ((S, I, H), n, s)}


def top_layout(d):
    return {"emb": ((d["V"], d["H"]), "normal", d["std"]),
            "lnf_g": ((d["H"],), "ones", 0.0)}


def leaf_seed(seed, layer, leaf_no):
    """The seed `weights.make` gets for one leaf: from `--seed`, the layer's
    index (-1: the top level) and the leaf's place in its sorted layout."""
    return int(seed) * 4096 + (layer + 1) * 64 + leaf_no + 1


def make_leaves(make, d, seed, layer):
    """Yields (reference leaf name, float32 array), one leaf at a time, for
    `layer` (an index, or -1 for the top level).  `make` is
    `benchmark.weights.make`."""
    layout = top_layout(d) if layer < 0 else layer_layout(d)
    for k, name in enumerate(sorted(layout)):
        yield name, make({name: layout[name]},
                         leaf_seed(seed, layer, k))[name]


_TOP = {"emb": "embed_tokens", "lnf_g": "final_norm"}
_LAYER = {"ln_g": "norm_scale", "wq": "q_proj", "wk": "k_proj",
          "wv": "v_proj", "wo": "o_proj", "router": "experts.router",
          "eg": "experts.gate", "eu": "experts.up", "ed": "experts.down",
          "sg": "shared_gate", "su": "shared_up", "sd": "shared_down"}


def program_name(ref, layer):
    """The program's state name of a reference leaf."""
    return _TOP[ref] if layer < 0 else f"layers.{layer}.{_LAYER[ref]}"


# ------------------------------------------------- operations and bytes

def _attn_params(d):
    return 2 * d["H"] * d["hd"] * (d["heads"] + d["kv_heads"])


def expert_params(d):
    return 3 * d["H"] * d["I"]


def _dense_params(d):
    """What every token multiplies in a layer: attention's four matrices,
    the shared experts, the router."""
    return _attn_params(d) + d["S"] * expert_params(d) + d["H"] * d["E"]


def _routed_params(d):
    """A token's experts' worth of products that fall on this chip when
    routing is even: K experts scaled by the held share."""
    return d["K"] * len(d["held"]) / d["E"] * expert_params(d)


def _seen(rows, kind, d):
    return min(rows, d["window"]) if kind == "sliding_attention" else rows


def prefill_flops(n, d):
    """Forward pass over a prompt of n tokens: the products of every
    token, attention under the causal mask (and the window), and the head
    at the last position alone."""
    ops = 2.0 * n * d["L"] * (_dense_params(d) + _routed_params(d))
    for kind in d["kinds"]:
        w = _seen(n, kind, d)
        pairs = w * (w + 1) / 2 + (n - w) * w      # (i, j) pairs kept
        ops += 4.0 * d["heads"] * d["hd"] * pairs
    return ops + 2.0 * d["V"] * d["H"]


def decode_token_flops(rows, d, rows_window=None):
    """Forward pass of one token that attends `rows` cached rows, capped at
    the window in window layers (`rows_window`: that cap taken a request
    before the mean over requests, where `rows` is such a mean)."""
    if rows_window is None:
        rows_window = min(rows, d["window"])
    ops = 2.0 * d["L"] * (_dense_params(d) + _routed_params(d))
    ops += sum(4.0 * d["heads"] * d["hd"] * (
        rows_window if kind == "sliding_attention" else rows)
        for kind in d["kinds"])
    return ops + 2.0 * d["V"] * d["H"]


def kv_row_bytes(d):
    """One cached row of one layer: keys and values, bfloat16."""
    return 2 * d["kv_heads"] * d["hd"] * BF16


def decode_call_bytes(d, chunk, experts_hit, rows_full, rows_window):
    """The least bytes of one decode call of `chunk` dependent steps: each
    step reads the weights outside the routed experts once (bfloat16; the
    router float32), and the live rows of the cache; a held expert is read
    only where a token was routed to it (`experts_hit`: held experts hit,
    summed over layers and the chunk's steps).  `rows_full` is the rows the
    requests hold summed over requests, `rows_window` the same with each
    REQUEST capped at the window (not the mean over requests)."""
    n_window = sum(k == "sliding_attention" for k in d["kinds"])
    per_step = (d["L"] * ((_attn_params(d) + d["S"] * expert_params(d))
                          * BF16 + d["H"] * d["E"] * 4)
                + d["V"] * d["H"] * BF16
                + kv_row_bytes(d) * (rows_window * n_window
                                     + rows_full * (d["L"] - n_window)))
    return float(chunk * per_step + experts_hit * expert_params(d) * BF16)


def expert_product_cost(picks, experts_hit, d):
    """(operations, bytes) of the grouped products at the least: three
    H x I products a pick, and an expert's three matrices once where it was
    hit (activations are noise beside them)."""
    return (2.0 * picks * expert_params(d),
            float(experts_hit * expert_params(d) * BF16))

