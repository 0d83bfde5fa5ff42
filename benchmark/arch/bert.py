"""BERT: Hugging Face keys -> sizes, weights, the program's names."""
import numpy as np

from ..reference import bert as reference  # noqa: F401

CAUSAL = False


def dims(cfg):
    return {"V": cfg["vocab_size"], "H": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "I": cfg["intermediate_size"],
            "P": cfg["max_position_embeddings"],
            "T": cfg["type_vocab_size"], "std": cfg["initializer_range"]}


def layout(d):
    n, s = "normal", d["std"]
    L, H, I, V = d["L"], d["H"], d["I"], d["V"]
    return {
        "wte": ((V, H), n, s), "wpe": ((d["P"], H), n, s),
        "tte": ((d["T"], H), n, s),
        "emb_ln_g": ((H,), "ones", 0.0), "emb_ln_b": ((H,), n, s),
        "pool_w": ((H, H), n, s), "pool_b": ((H,), n, s),
        "tr_w": ((H, H), n, s), "tr_b": ((H,), n, s),
        "tr_ln_g": ((H,), "ones", 0.0), "tr_ln_b": ((H,), n, s),
        "dec_b": ((V,), n, s),
        "nsp_w": ((H, 2), n, s), "nsp_b": ((2,), n, s),
        "layers": {
            "qkv_w": ((L, H, 3 * H), n, s), "qkv_b": ((L, 3 * H), n, s),
            "out_w": ((L, H, H), n, s), "out_b": ((L, H), n, s),
            "attn_ln_g": ((L, H), "ones", 0.0), "attn_ln_b": ((L, H), n, s),
            "fi_w": ((L, H, I), n, s), "fi_b": ((L, I), n, s),
            "fo_w": ((L, I, H), n, s), "fo_b": ((L, H), n, s),
            "ffn_ln_g": ((L, H), "ones", 0.0), "ffn_ln_b": ((L, H), n, s),
        },
    }


_TOP = {"wte": "bert.embeddings.word_embeddings.weight",
        "wpe": "bert.embeddings.position_embeddings.weight",
        "tte": "bert.embeddings.token_type_embeddings.weight",
        "emb_ln_g": "bert.embeddings.layer_norm.weight",
        "emb_ln_b": "bert.embeddings.layer_norm.bias",
        "pool_w": "bert.pooler.dense.weight",
        "pool_b": "bert.pooler.dense.bias",
        "tr_w": "cls.transform.weight", "tr_b": "cls.transform.bias",
        "tr_ln_g": "cls.layer_norm.weight", "tr_ln_b": "cls.layer_norm.bias",
        "dec_b": "cls.decoder_bias",
        "nsp_w": "nsp.weight", "nsp_b": "nsp.bias"}
_LAYER = {"qkv_w": "attention.qkv.weight", "qkv_b": "attention.qkv.bias",
          "out_w": "attention.out.weight", "out_b": "attention.out.bias",
          "attn_ln_g": "attn_norm.weight", "attn_ln_b": "attn_norm.bias",
          "fi_w": "ffn_in.weight", "fi_b": "ffn_in.bias",
          "fo_w": "ffn_out.weight", "fo_b": "ffn_out.bias",
          "ffn_ln_g": "ffn_norm.weight", "ffn_ln_b": "ffn_norm.bias"}


def program_names(d):
    out = [(prog, ref, None) for ref, prog in _TOP.items()]
    for i in range(d["L"]):
        out += [(f"bert.layers.{i}.{prog}", ref, i)
                for ref, prog in _LAYER.items()]
    return out


def train_loss_fn():
    """Masked-LM plus next-sentence loss.  TrainStep hands the loss one
    label array, so the next-sentence label rides as its last column."""
    from paddle_tpu import models
    crit = models.BertPretrainingCriterion()
    return lambda scores, rel, lab: crit(scores, rel, lab[:, :-1],
                                         lab[:, -1])


def train_batches(d, seed, batch, seq, count, vocab_used):
    """15% of each row's positions carry a masked-LM label, the second half
    of each row is sentence B, and the next-sentence label is a coin."""
    rng = np.random.RandomState(seed % (2 ** 32))
    out = []
    for _ in range(count):
        ids = rng.randint(0, vocab_used, (batch, seq)).astype(np.int32)
        types = np.zeros((batch, seq), np.int32)
        types[:, seq // 2:] = 1
        masked = rng.rand(batch, seq) < 0.15
        mlm = np.where(masked, rng.randint(0, vocab_used, (batch, seq)),
                       -100).astype(np.int32)
        nsp = rng.randint(0, 2, (batch,)).astype(np.int32)
        packed = np.concatenate([mlm, nsp[:, None]], axis=1)
        out.append(((ids, types, packed), (ids, types, mlm, nsp)))
    return out
