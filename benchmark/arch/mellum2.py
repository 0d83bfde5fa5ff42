"""mellum (Mellum2-12B-A2.5B): config keys -> sizes, the weights' layout
stacked over layers, the program's names, the feed of a training step, and
the least operations and bytes of a step, of the grouped flash kernel's
calls and of the held experts' products."""
import numpy as np

from ..reference import mellum2 as reference

CAUSAL = True
BF16, F32 = 2, 4
SLIDING = reference.SLIDING


def dims(cfg):
    """Sizes from the configuration's file; the reference's constants are
    set from it too (the published ones, for the cell's file)."""
    L = cfg["num_hidden_layers"]
    held = list(cfg.get("experts_held", range(cfg["num_experts"])))
    assert len(held) == cfg.get("num_experts_held", len(held))
    assert held == list(range(len(held))), "the reference holds 0..n-1"
    kinds = list(cfg["layer_types"][:L])
    reference.configure(cfg["sliding_window"], cfg["num_experts_per_tok"],
                        kinds, cfg["rms_norm_eps"], cfg["rope_parameters"])
    return {"V": cfg["vocab_size"], "H": cfg["hidden_size"], "L": L,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "I": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "K": cfg["num_experts_per_tok"], "held": held,
            "window": cfg["sliding_window"], "kinds": kinds,
            "std": cfg["initializer_range"],
            "embed_std": cfg.get("embedding_std", cfg["initializer_range"])}


def layout(d):
    """Every matrix normal(0, std), the embedding normal(0, embed_std), the
    norms' scales 1."""
    n, s = "normal", d["std"]
    L, H, I, n_held = d["L"], d["H"], d["I"], len(d["held"])
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    return {
        "embed": ((d["V"], H), n, d["embed_std"]),
        "norm": ((H,), "ones", 0.0),
        "head": ((H, d["V"]), n, s),
        "layers": {
            "ln1": ((L, H), "ones", 0.0),
            "q": ((L, H, q), n, s), "k": ((L, H, kv), n, s),
            "v": ((L, H, kv), n, s), "o": ((L, q, H), n, s),
            "ln2": ((L, H), "ones", 0.0),
            "router": ((L, H, d["E"]), n, s),
            "gate": ((L, n_held, H, I), n, s),
            "up": ((L, n_held, H, I), n, s),
            "down": ((L, n_held, I, H), n, s),
        },
    }


_TOP = {"embed": "embed_tokens", "norm": "norm", "head": "lm_head"}
_LAYER = {"ln1": "input_layernorm", "q": "q_proj", "k": "k_proj",
          "v": "v_proj", "o": "o_proj", "ln2": "post_attention_layernorm",
          "router": "experts.router", "gate": "experts.gate",
          "up": "experts.up", "down": "experts.down"}


def program_names(d):
    """(program's state name, reference leaf, layer index or None)."""
    out = [(prog, ref, None) for ref, prog in _TOP.items()]
    for i in range(d["L"]):
        out += [(f"layers.{i}.{prog}", ref, i) for ref, prog in _LAYER.items()]
    return out


def train_loss_fn():
    """The mean next-token loss over the slice; the model's counts, its
    second output, ride beside the logits and are not part of it."""
    from paddle_tpu import models
    crit = models.GPTPretrainingCriterion()
    return lambda logits, counts, label: crit(logits, label)


def train_batches(d, seed, batch, seq, count, vocab_used):
    """`count` batches of ids from the vocabulary's slice: (program feed,
    reference feed), both the same."""
    rng = np.random.RandomState(seed % (2 ** 32))
    out = []
    for _ in range(count):
        ids = rng.randint(0, vocab_used, (batch, seq + 1)).astype(np.int32)
        feed = (ids[:, :-1].copy(), ids[:, 1:].copy())
        out.append((feed, feed))
    return out


def pairs(seq, window):
    """(query, key) pairs a causal call over `seq` rows keeps, under a
    window of `window` keys (None: the whole prefix)."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def dense_params(d):
    """Parameters every token multiplies: the attention's four matrices and
    the router a layer, and the head."""
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    return d["L"] * d["H"] * (2 * q + 2 * kv + d["E"]) + d["H"] * d["V"]


def attention_pairs(batch, seq, d):
    """Kept (query, key) pairs of every layer of a step, each query head."""
    return sum(pairs(seq, d["window"] if kind == SLIDING else None)
               for kind in d["kinds"]) * batch * d["heads"]


def expert_params(d):
    return 3 * d["H"] * d["I"]


def train_flops(batch, seq, d, picks_here=None):
    """A step's model operations, forward and backward (3 x the forward),
    no recomputation: the dense products, the attention's two products over
    the kept pairs, and the held experts' three products a pick held here.
    `picks_here`: the step's picks on held experts summed over layers (the
    spans' `routed_here`); None: the share the router's uniform choice
    would bring, K x held / E a token a layer."""
    tokens = batch * seq
    if picks_here is None:
        picks_here = tokens * d["L"] * d["K"] * len(d["held"]) / d["E"]
    return float(6 * dense_params(d) * tokens
                 + 12 * attention_pairs(batch, seq, d) * d["hd"]
                 + 6 * picks_here * expert_params(d))


def flash_call_cost(batch, seq, d, window, backward):
    """(operations, bytes) of one grouped flash call at the least: QK^T and
    PV over the kept pairs forward, dV, dP, dQ and dK backward (the scores
    a flash kernel computes again are recomputation and not counted); q,
    the output and their gradients are Hq heads wide, k, v and theirs Hkv:
    q, k, v read and o written forward; q, k, v, o and dO read and dq, dk
    and dv written backward."""
    kept = pairs(seq, window) * batch * d["heads"]
    q_bytes = batch * seq * d["heads"] * d["hd"] * BF16
    kv_bytes = batch * seq * d["kv_heads"] * d["hd"] * BF16
    if backward:
        return 8.0 * kept * d["hd"], float(4 * q_bytes + 4 * kv_bytes)
    return 4.0 * kept * d["hd"], float(2 * q_bytes + 2 * kv_bytes)


def layer_windows(d):
    """Each layer's window (None: a full layer)."""
    return [d["window"] if kind == SLIDING else None for kind in d["kinds"]]


def expert_product_train_cost(picks, experts_hit, d):
    """(operations, bytes) of the held experts' products of a step, forward
    and backward, at the least: three H x I products a pick forward, twice
    that backward (the inputs' and the weights' gradients; the forward's
    products recomputed are not counted); each hit expert's three matrices
    read forward and backward and their float32 gradients written."""
    return (6.0 * picks * expert_params(d),
            float(experts_hit * expert_params(d) * (2 * BF16 + F32)))
