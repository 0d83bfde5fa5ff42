"""BERT (Devlin et al. 2018; Hugging Face `bert-large-uncased`): post-norm
encoder layers, learned positions and token types, exact GELU, a pooler, the
masked-LM head tied to the word embedding plus its bias, and the
next-sentence head.  No padding mask: every row is full.
"""
import jax
import jax.numpy as jnp

from . import common as C

LN_EPS = 1e-12


def encode(w, ids, types, heads, precision):
    s = ids.shape[0]
    x = w["wte"][ids] + w["wpe"][:s] + w["tte"][types]
    x = C.layer_norm(x, w["emb_ln_g"], w["emb_ln_b"], LN_EPS)
    if precision == "bfloat16":
        x = x.astype(jnp.bfloat16)

    @jax.checkpoint
    def layer(x, l):
        qkv = C.mm(x, l["qkv_w"], precision) + l["qkv_b"].astype(x.dtype)
        a = C.attention(qkv.astype(x.dtype), heads, False, precision)
        a = (C.mm(a, l["out_w"], precision)
             + l["out_b"].astype(x.dtype)).astype(x.dtype)
        x = C.layer_norm(x + a, l["attn_ln_g"], l["attn_ln_b"], LN_EPS)
        h = C.gelu((C.mm(x, l["fi_w"], precision)
                    + l["fi_b"].astype(x.dtype)).astype(x.dtype))
        h = (C.mm(h, l["fo_w"], precision)
             + l["fo_b"].astype(x.dtype)).astype(x.dtype)
        return C.layer_norm(x + h, l["ffn_ln_g"], l["ffn_ln_b"], LN_EPS), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return x


def heads_out(w, x, precision):
    """Encoder output (S, H) -> MLM logits (S, V), NSP logits (2,)."""
    pooled = jnp.tanh((C.mm(x[:1], w["pool_w"], precision)
                       + w["pool_b"]).astype(jnp.float32))
    t = C.gelu((C.mm(x, w["tr_w"], precision) + w["tr_b"].astype(x.dtype)
                ).astype(x.dtype))
    t = C.layer_norm(t, w["tr_ln_g"], w["tr_ln_b"], LN_EPS)
    mlm = C.mm(t, w["wte"].T, precision).astype(jnp.float32) + w["dec_b"]
    nsp = (C.mm(pooled.astype(x.dtype), w["nsp_w"], precision)
           .astype(jnp.float32) + w["nsp_b"])[0]
    return mlm, nsp


def row_loss(w, row, heads, precision, n_masked, n_rows):
    ids, types, mlm_labels, nsp_label = row
    x = encode(w, ids, types, heads, precision)
    mlm, nsp = heads_out(w, x, precision)
    mlm_part = jnp.sum(C.cross_entropy(mlm, mlm_labels)) / n_masked
    nsp_part = C.cross_entropy(nsp[None], nsp_label[None])[0] / n_rows
    return mlm_part + nsp_part


def loss_and_grads(w, batch, heads, precision="float32"):
    """batch = (ids, types, mlm_labels (B,S; -100 = not masked), nsp (B,))
    -> masked-LM mean over the batch's masked positions plus the
    next-sentence mean over its rows, and the gradients."""
    ids, types, mlm_labels, nsp = batch
    n_masked = jnp.maximum(jnp.sum(mlm_labels >= 0).astype(jnp.float32), 1.0)
    n_rows = jnp.float32(ids.shape[0])
    return C.sum_over_rows(
        lambda ww, row: row_loss(ww, row, heads, precision, n_masked, n_rows),
        w, (ids, types, mlm_labels, nsp))
