"""What the references share: the matrix product by precision, layer norm,
attention, cross entropy and AdamW, all plain `jax.numpy`.

`precision` is one of
  "float32"   products at `highest` (six bf16 passes on a TPU): the reference;
  "bfloat16"  weights, activations and products in bfloat16: the control of a
              cell whose configuration states float32;
  "int8"      both operands of every product rounded to 127 levels a tensor
              (what an int8 product with per-tensor scales computes);
  "fp8"       both operands of every product rounded to float8 e4m3 (four
              significant bits, one scale a tensor), sums in float32: the
              control of a cell whose configuration states bfloat16.
"""
import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8", "fp8")


def _fake_int8(x):
    """x rounded to the 255-level grid an int8 tensor with one scale holds;
    the gradient passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x):
    """x rounded to four significant bits (e4m3's) after scaling its
    largest magnitude to e4m3's 448; what lies more than 2**-9 of that
    below the largest is flushed, as e4m3's range does.  Straight-through
    gradient.  Written as arithmetic so that it runs wherever float32
    does."""
    top = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    m, e = jnp.frexp(x / top)               # x/top = m * 2**e, m in [.5, 1)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    q = jnp.where(jnp.abs(x / top) < 2.0 ** -18, 0.0, q) * top
    return x + jax.lax.stop_gradient(q - x)


def mm(x, w, precision, spec=None):
    """x @ w (or the einsum `spec`) in the stated precision, float32 out
    except under "bfloat16"."""
    if precision == "int8":
        x, w = _fake_int8(x), _fake_int8(w)
    if precision == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    if precision == "bfloat16":
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        prec = None
    else:
        prec = jax.lax.Precision.HIGHEST
    if spec is None:
        return jnp.matmul(x, w, precision=prec)
    return jnp.einsum(spec, x, w, precision=prec)


def layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * g + b
    return y.astype(x.dtype)


def gelu(x):
    """The exact form (erf), as both published models use."""
    x32 = x.astype(jnp.float32)
    return (0.5 * x32 * (1.0 + jax.lax.erf(x32 / jnp.sqrt(2.0)))).astype(
        x.dtype)


def attention(qkv, heads, causal, precision):
    """qkv: (S, 3*H) packed as [3][heads][head_dim]; returns (S, H)."""
    s, h3 = qkv.shape
    hd = h3 // 3 // heads
    qkv = qkv.reshape(s, 3, heads, hd)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = mm(q, k, precision, "qhd,khd->hqk").astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = mm(probs, v, precision, "hqk,khd->qhd")
    return ctx.reshape(s, heads * hd)


def cross_entropy(logits, labels):
    """Per-position loss, 0 where the label is -100."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.where(labels < 0, 0, labels)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(labels < 0, 0.0, lse - picked)


def adamw(params, grads, m, v, step, lr, beta1, beta2, eps, weight_decay):
    """One decoupled-decay Adam step on every leaf (decay on every leaf, as
    the configurations' training sections state)."""
    t = jnp.float32(step)

    def one(p, g, m1, v1):
        m2 = beta1 * m1 + (1 - beta1) * g
        v2 = beta2 * v1 + (1 - beta2) * jnp.square(g)
        upd = lr * (m2 / (1 - beta1 ** t)) / (
            jnp.sqrt(v2 / (1 - beta2 ** t)) + eps)
        return p - upd - lr * weight_decay * p, m2, v2

    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def sum_over_rows(row_loss, weights, rows):
    """Loss and gradients of a batch as the sum of its rows' parts, one row
    at a time so that a published-size model fits beside its gradients."""
    zero = jax.tree_util.tree_map(jnp.zeros_like, weights)

    def body(carry, row):
        loss, acc = carry
        l, g = jax.value_and_grad(row_loss)(weights, row)
        return (loss + l, jax.tree_util.tree_map(jnp.add, acc, g)), None

    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), rows)
    return loss, grads
