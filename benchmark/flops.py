"""Operations and bytes, from shapes alone: what the algorithm needs at the
least, so that a share of a peak computed from them cannot pass 100%.

`gpt_train_flops` and `bert_train_flops` are copied from `bench.py:41-65`
(6 x dense parameters x tokens plus the attention products, halved under
the causal mask); recomputation is not counted.
"""


def dense_params(d):
    """Parameters that every token multiplies: the blocks' matrices and the
    (tied) output head.  Embedding look-ups, norms and biases are not
    products."""
    return d["L"] * (4 * d["H"] * d["H"] + 2 * d["H"] * d["I"]) \
        + d["V"] * d["H"]


def gpt_train_flops(batch, seq, d):
    tokens = batch * seq
    return float(6 * dense_params(d) * tokens
                 + 6 * d["L"] * batch * seq * seq * d["H"])


def bert_train_flops(batch, seq, d):
    tokens = batch * seq
    return float(6 * dense_params(d) * tokens
                 + 12 * d["L"] * batch * seq * seq * d["H"])


def train_flops(arch_name, batch, seq, d):
    return {"gpt2": gpt_train_flops, "bert": bert_train_flops}[arch_name](
        batch, seq, d)


def prefill_flops(n, d):
    """Forward pass over a prompt of n tokens under the causal mask."""
    return float(2 * dense_params(d) * n + 2 * d["L"] * n * n * d["H"])


def decode_token_flops(context, d):
    """Forward pass of one token that attends `context` cached rows."""
    return float(2 * dense_params(d) + 4 * d["L"] * context * d["H"])


def flash_attention_cost(batch, heads, sq, sk, head_dim, causal, backward,
                         bytes_per=2):
    """(operations, bytes) of one attention call at the least: the forward
    pass is QK^T and PV; the backward pass is dV, dP, dQ and dK (the scores
    a flash kernel computes again are recomputation and not counted).
    Bytes: q, k, v read and the output written once forward; q, k, v, the
    output and its gradient read and three gradients written backward."""
    pair = 2.0 * batch * heads * sq * sk * head_dim
    if causal:
        pair *= 0.5
    q_bytes = batch * heads * sq * head_dim * bytes_per
    kv_bytes = batch * heads * sk * head_dim * bytes_per
    if backward:
        return 4 * pair, 3 * q_bytes + 2 * kv_bytes + q_bytes + 2 * kv_bytes
    return 2 * pair, 2 * q_bytes + 2 * kv_bytes


def least_seconds(ops, nbytes, peaks):
    """The roofline: the larger of operations over the peak rate and bytes
    over the peak bandwidth, and which of the two bounds."""
    by_ops = ops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("operations" if by_ops >= by_bytes
                                   else "bytes")


def decode_weight_bytes(d, bytes_per=4):
    """What one decoded token must read of the weights: every matrix and
    the output head once (biases and norms are noise beside them)."""
    return float(dense_params(d) * bytes_per)


def kv_row_bytes(d, bytes_per=4):
    """One cached token: keys and values of every layer."""
    return float(2 * d["L"] * d["H"] * bytes_per)


def decode_call_bytes(d, live_rows, chunk, weight_bytes_per=4,
                      kv_bytes_per=4):
    """The least bytes of one decode call of `chunk` tokens a slot: each of
    the chunk's dependent steps reads the weights once (they do not fit
    on the chip's fast memory) and the live rows of the cache, not the
    whole buffer."""
    return chunk * (decode_weight_bytes(d, weight_bytes_per)
                    + live_rows * kv_row_bytes(d, kv_bytes_per))
