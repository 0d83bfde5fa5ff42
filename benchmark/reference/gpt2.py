"""GPT-2 (Radford et al. 2019; the Hugging Face `gpt2` family): pre-norm
decoder blocks, learned positions, fused QKV, exact GELU, tied output head.
Weights are stacked over layers; see `benchmark/arch/gpt2.py` for the names.
"""
import jax
import jax.numpy as jnp

from . import common as C

LN_EPS = 1e-5


def hidden(w, ids, heads, precision):
    """ids (S,) -> final hidden states (S, H)."""
    s = ids.shape[0]
    x = w["wte"][ids] + w["wpe"][:s]
    if precision == "bfloat16":
        x = x.astype(jnp.bfloat16)

    @jax.checkpoint
    def block(x, l):
        h = C.layer_norm(x, l["ln1_g"], l["ln1_b"], LN_EPS)
        qkv = C.mm(h, l["qkv_w"], precision) + l["qkv_b"].astype(x.dtype)
        a = C.attention(qkv.astype(x.dtype), heads, True, precision)
        x = x + (C.mm(a, l["proj_w"], precision)
                 + l["proj_b"].astype(x.dtype)).astype(x.dtype)
        h = C.layer_norm(x, l["ln2_g"], l["ln2_b"], LN_EPS)
        h = C.gelu((C.mm(h, l["fi_w"], precision)
                    + l["fi_b"].astype(x.dtype)).astype(x.dtype))
        x = x + (C.mm(h, l["fo_w"], precision)
                 + l["fo_b"].astype(x.dtype)).astype(x.dtype)
        return x, None

    x, _ = jax.lax.scan(block, x, w["layers"])
    return C.layer_norm(x, w["lnf_g"], w["lnf_b"], LN_EPS)


def logits(w, ids, heads, precision="float32"):
    """ids (S,) -> next-token logits (S, V), float32."""
    h = hidden(w, ids, heads, precision)
    return C.mm(h, w["wte"].T, precision).astype(jnp.float32)


def row_loss(w, row, heads, precision, denom):
    """One row's part of the batch's mean next-token loss."""
    ids, labels = row
    return jnp.sum(C.cross_entropy(logits(w, ids, heads, precision),
                                   labels)) / denom


def loss_and_grads(w, batch, heads, precision="float32"):
    """batch = (ids (B,S), labels (B,S)) -> mean loss, gradients."""
    ids, labels = batch
    denom = jnp.float32(ids.size)
    return C.sum_over_rows(
        lambda ww, row: row_loss(ww, row, heads, precision, denom),
        w, (ids, labels))
