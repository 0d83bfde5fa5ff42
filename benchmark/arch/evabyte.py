"""evabyte (EvaByte 6.5B): config keys -> sizes, the weights' layout BY LAYER,
the program's names, and the least operations and bytes of its programs.  A
layer's weights are made, cast and loaded one leaf at a time (`leaf_seed`),
as `arch/cohere2_moe.py` does and for its reason.

The cache is two kinds of row a layer, each `heads x head_dim` numbers twice
(a key and a value, or a pooled key and a pooled value): the ring's exact
rows, `ring_rows(n)` of them alive with position n the last written, and one
summary a chunk of every earlier window, `summary_rows(n)`."""
from ..reference import evabyte as reference  # noqa: F401

CAUSAL = True
BF16 = 2
EVA = "eva"


def dims(cfg):
    heads = cfg["num_attention_heads"]
    return {"V": cfg["vocab_size"], "H": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"], "heads": heads,
            "hd": cfg["hidden_size"] // heads,
            "I": cfg["intermediate_size"], "window": cfg["window_size"],
            "chunk": cfg["chunk_size"], "pred_heads": cfg["num_pred_heads"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
            "kinds": [EVA] * cfg["num_hidden_layers"],
            "std": cfg["initializer_range"], "qk_std": cfg["qk_proj_std"],
            "phi_std": cfg["adaptive_phi_std"],
            "mu_std": cfg["adaptive_mu_k_std"]}


def layer_layout(d, kind=EVA):
    """One layer's leaves: every matrix normal(0, std) but `wq` and `wk`
    (normal(0, qk_std)), `phi` and `mu` normal with their own stds (the
    configuration's `assumed` says why), norm offsets 0 (written as
    normal(0, 0): `weights.make` knows "normal" and "ones")."""
    n, s, H, I = "normal", d["std"], d["H"], d["I"]
    head = (d["heads"], d["hd"])
    return {"ln1_g": ((H,), "normal", 0.0), "ln2_g": ((H,), "normal", 0.0),
            "wq": ((H, H), n, d["qk_std"]), "wk": ((H, H), n, d["qk_std"]),
            "wv": ((H, H), n, s), "wo": ((H, H), n, s),
            "phi": (head, n, d["phi_std"]), "mu": (head, n, d["mu_std"]),
            "wg": ((H, I), n, s), "wu": ((H, I), n, s), "wd": ((I, H), n, s)}


def top_layout(d):
    return {"emb": ((d["V"], d["H"]), "normal", d["std"]),
            "lnf_g": ((d["H"],), "normal", 0.0),
            "head": ((d["H"], d["pred_heads"] * d["V"]), "normal", d["std"])}


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


_TOP = {"emb": "embed_tokens", "lnf_g": "norm", "head": "lm_head"}
_LAYER = {"ln1_g": "input_layernorm", "ln2_g": "post_attention_layernorm",
          "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
          "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
          "phi": "self_attn.adaptive_phi", "mu": "self_attn.adaptive_mu_k",
          "wg": "mlp.gate_proj", "wu": "mlp.up_proj", "wd": "mlp.down_proj"}


def program_name(ref, layer):
    """The program's state name of a reference leaf."""
    return _TOP[ref] if layer < 0 else f"layers.{layer}.{_LAYER[ref]}"


# ------------------------------------------------- operations and bytes

def layer_params(d):
    """Every number of one layer: four attention matrices, the gated MLP,
    two norms, `adaptive_phi` and `adaptive_mu_k`."""
    return (4 * d["H"] ** 2 + 3 * d["H"] * d["I"] + 2 * d["H"]
            + 2 * d["heads"] * d["hd"])


def param_count(d):
    return (d["L"] * layer_params(d) + d["V"] * d["H"] + d["H"]
            + d["H"] * d["pred_heads"] * d["V"])


def _token_params(d):
    """What one token multiplies, summed over layers: the four attention
    matrices and the gated MLP."""
    return d["L"] * (4 * d["H"] ** 2 + 3 * d["H"] * d["I"])


def ring_rows(n, d):
    """Exact rows alive in a layer's ring with position n the last
    written."""
    return n % d["window"] + 1


def summary_rows(n, d):
    """Summaries a query at n sees: one a chunk of every earlier window."""
    return n // d["window"] * (d["window"] // d["chunk"])


def _attended_pairs(n, d):
    """(query, key) pairs of a prompt of n tokens: a window's rows under
    the causal mask beside `window / chunk` summaries for each window
    before it (the last window may be short)."""
    win, per = d["window"], d["window"] // d["chunk"]
    pairs = 0.0
    for w in range(-(-n // win)):
        rows = min(win, n - w * win)
        pairs += rows * (rows + 1) / 2 + rows * per * w
    return pairs


def prefill_flops(n, d):
    """Forward pass over a prompt of n tokens: the products of every token,
    EVA's attended pairs (a pair costs a head 2 x head_dim for the score
    and as much for the output), the pooling (a cached row costs a head
    head_dim for its score and 2 x head_dim for the two weighted sums) and
    head 0 at the last position alone."""
    return (2.0 * n * _token_params(d)
            + d["L"] * 4.0 * d["H"] * _attended_pairs(n, d)
            + d["L"] * 6.0 * d["H"] * n + 2.0 * d["V"] * d["H"])


def decode_token_flops(rows, d, rows_window=None):
    """Forward pass of one token at position `rows` (the harness hands the
    MEAN rows the step's decoding requests hold, so this is the cost at the
    mean position, not the mean cost: `ring_rows` wraps at a window's end
    and the mean of a wrapped count is not the count at the mean; over many
    requests the two agree to a few percent of the attention's share, which
    is itself under a percent of a token's operations).  The step pools
    its chunk's 16 rows once more."""
    n = max(int(rows), 0)
    seen = ring_rows(n, d) + summary_rows(n, d)
    return (2.0 * _token_params(d) + d["L"] * 4.0 * d["H"] * seen
            + d["L"] * 6.0 * d["H"] * d["chunk"] + 2.0 * d["V"] * d["H"])


def kv_row_bytes(d):
    """One cached row of one layer, a ring's or a summary's: a key and a
    value of every head, bfloat16 (16,384 at the published widths)."""
    return 2 * d["heads"] * d["hd"] * BF16


def decode_weight_bytes(d):
    """What one decode step reads of the weights at the least: every layer
    (the embedding's 16 rows are noise) and prediction head 0 with the
    final norm, bfloat16."""
    return (d["L"] * layer_params(d) + d["H"] + d["V"] * d["H"]) * BF16


def prefill_attention_cost(n, d):
    """(operations, bytes) of ONE layer's attention over a prompt's bucket
    of n rows, as the windowed form needs them at the least: the attended
    pairs' two products, and each window's queries, keys, values, the
    summaries before it and its output read or written once."""
    win, per = d["window"], d["window"] // d["chunk"]
    row = d["heads"] * d["hd"] * BF16
    nbytes = 0.0
    for w in range(-(-n // win)):
        rows = min(win, n - w * win)
        nbytes += row * (4 * rows + 2 * per * w)
    return 4.0 * d["H"] * _attended_pairs(n, d), nbytes
