"""Serving's side of `correct`: after the window, the reference runs once
over each sampled request's prompt with its served tokens, and the number
compared is the widest gap by which a served token's logit lies below the
reference's best at that position (0 where the served token is the
reference's own choice).  Greedy tokens only.
"""
import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W


def pick_sample(done, seed, count):
    """`count` finished requests drawn from the seed, the longest among
    them."""
    ok = [r for r in done if r["done"] and not r["failed"] and r["n"] > 0]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r["plen"] + r["n"], -r["i"]))
    rest = [r for r in ok if r is not longest]
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    picks = [rest[j] for j in rng.permutation(len(rest))[:count - 1]]
    return [longest] + picks


def _rows(plan, sample, max_len):
    """ids (n, max_len), picked token per position (n, max_len), mask."""
    ids = np.zeros((len(sample), max_len), np.int32)
    picks = np.zeros((len(sample), max_len), np.int32)
    mask = np.zeros((len(sample), max_len), bool)
    for k, rec in enumerate(sample):
        prompt = plan[rec["i"]]["prompt"]
        toks = np.asarray(rec["tokens_list"], np.int32)
        full = np.concatenate([prompt, toks])[:max_len + 1]
        plen, m = len(prompt), len(full) - len(prompt)
        ids[k, :len(full) - 1] = full[:-1]
        picks[k, plen - 1:plen - 1 + m] = full[plen:]
        mask[k, plen - 1:plen - 1 + m] = True
    return ids, picks, mask


_FNS = {}


def make_fns(arch, d):
    """The reference's two jitted readings, one pair per architecture and
    head count (a new pair would compile anew)."""
    key = (arch.__name__, d["heads"])
    if key not in _FNS:
        _FNS[key] = _make_fns(arch.reference, d["heads"])
    return _FNS[key]


def _make_fns(ref, heads):

    def gaps(w, ids, picks):
        """Per position: reference's best logit minus the picked token's."""
        logits = ref.logits(w, ids, heads, "float32")
        picked = jnp.take_along_axis(logits, picks[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    def choice(w, ids, precision):
        return jnp.argmax(ref.logits(w, ids, heads, precision),
                          axis=-1).astype(jnp.int32)

    return jax.jit(gaps), jax.jit(choice, static_argnums=2)


def compare_sample(arch, d, layout, seed, plan, sample, max_len,
                   control=None, w=None):
    """-> {"token_logit_gap", "checked_tokens", "mismatched_tokens"}.  With
    `control` (a precision), the tokens judged are not the served ones but
    those the reference computed in that precision puts first, at the same
    positions of the same prompts and tokens."""
    if not sample:
        return {"token_logit_gap": float("inf"), "checked_tokens": 0,
                "mismatched_tokens": 0}
    if w is None:
        w = W.make(layout, seed)
    gaps_fn, choice_fn = make_fns(arch, d)
    ids, picks, mask = _rows(plan, sample, max_len)
    worst, wrong = 0.0, 0
    for k in range(len(sample)):
        row = jnp.asarray(ids[k])
        judged = (choice_fn(w, row, control) if control
                  else jnp.asarray(picks[k]))
        g = np.asarray(gaps_fn(w, row, judged))[mask[k]]
        if not np.all(np.isfinite(g)):
            return {"token_logit_gap": float("inf"),
                    "checked_tokens": int(mask.sum()),
                    "mismatched_tokens": -1}
        worst = max(worst, float(g.max()))
        wrong += int((g > 0).sum())
    return {"token_logit_gap": worst, "checked_tokens": int(mask.sum()),
            "mismatched_tokens": wrong}
