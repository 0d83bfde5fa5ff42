"""GPT-2: Hugging Face keys -> sizes, weights, the program's names."""
import numpy as np

from ..reference import gpt2 as reference  # noqa: F401  (the cell's reference)

CAUSAL = True


def dims(cfg):
    h = cfg["n_embd"]
    return {"V": cfg["vocab_size"], "H": h, "L": cfg["n_layer"],
            "heads": cfg["n_head"], "I": cfg.get("n_inner") or 4 * h,
            "P": cfg["n_positions"], "std": cfg["initializer_range"]}


def layout(d):
    """Every leaf normal(0, std) except the norms' scales: biases too, so
    that a bias left out shows."""
    n, s = "normal", d["std"]
    L, H, I = d["L"], d["H"], d["I"]
    return {
        "wte": ((d["V"], H), n, s), "wpe": ((d["P"], H), n, s),
        "lnf_g": ((H,), "ones", 0.0), "lnf_b": ((H,), n, s),
        "layers": {
            "ln1_g": ((L, H), "ones", 0.0), "ln1_b": ((L, H), n, s),
            "qkv_w": ((L, H, 3 * H), n, s), "qkv_b": ((L, 3 * H), n, s),
            "proj_w": ((L, H, H), n, s), "proj_b": ((L, H), n, s),
            "ln2_g": ((L, H), "ones", 0.0), "ln2_b": ((L, H), n, s),
            "fi_w": ((L, H, I), n, s), "fi_b": ((L, I), n, s),
            "fo_w": ((L, I, H), n, s), "fo_b": ((L, H), n, s),
        },
    }


_TOP = {"wte": "gpt.word_embeddings.weight",
        "wpe": "gpt.position_embeddings.weight",
        "lnf_g": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}
_LAYER = {"ln1_g": "ln1.weight", "ln1_b": "ln1.bias",
          "qkv_w": "qkv.weight", "qkv_b": "qkv.bias",
          "proj_w": "proj.weight", "proj_b": "proj.bias",
          "ln2_g": "ln2.weight", "ln2_b": "ln2.bias",
          "fi_w": "ffn_in.weight", "fi_b": "ffn_in.bias",
          "fo_w": "ffn_out.weight", "fo_b": "ffn_out.bias"}


def program_names(d):
    """(program's state name, reference leaf, layer index or None)."""
    out = [(prog, ref, None) for ref, prog in _TOP.items()]
    for i in range(d["L"]):
        out += [(f"gpt.blocks.{i}.{prog}", ref, i)
                for ref, prog in _LAYER.items()]
    return out


def train_loss_fn():
    from paddle_tpu import models
    crit = models.GPTPretrainingCriterion()
    return lambda logits, label: crit(logits, label)


def train_batches(d, seed, batch, seq, count, vocab_used):
    """`count` batches whose rows all differ: (program feed, reference
    feed), both from the same ids."""
    rng = np.random.RandomState(seed % (2 ** 32))
    out = []
    for _ in range(count):
        ids = rng.randint(0, vocab_used, (batch, seq + 1)).astype(np.int32)
        feed = (ids[:, :-1].copy(), ids[:, 1:].copy())
        out.append((feed, feed))
    return out
