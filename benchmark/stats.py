"""Rates and percentiles from logs, kept apart from the code that makes the
logs so that a hand-made log checks them."""
import math


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, as numpy's default; None on an empty list."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def stat(values, which):
    values = [v for v in values if v is not None]
    if which == "mean":
        return mean(values)
    if which == "median":
        return percentile(values, 50)
    if which.startswith("p"):
        return percentile(values, float(which[1:]))
    raise ValueError(which)


def serve_end_to_end(requests, window_s, worst_ms):
    """requests: dicts with due, first (first token seen), last, tokens,
    failed.  A failed or unfinished request counts as the worst (`worst_ms`:
    the time from its due time to the end of the run's drain).
    -> ttft_p95_ms, itl_p95_ms, tokens_per_s over all requests due."""
    ttft, itl, tokens = [], [], 0
    for r in requests:
        bad = r.get("failed") or r.get("first") is None or not r.get("done")
        if bad:
            ttft.append(worst_ms)
            itl.append(worst_ms)
            continue
        ttft.append((r["first"] - r["due"]) * 1e3)
        n = r["tokens"]
        tokens += n
        itl.append((r["last"] - r["first"]) * 1e3 / (n - 1) if n > 1
                   else 0.0)
    return {"serve_ttft_p95_ms": percentile(ttft, 95),
            "serve_itl_p95_ms": percentile(itl, 95),
            "serve_tokens_per_s": tokens / window_s}
