"""A share of a program counter: params counter (a key of run.counters
holding {label: count}), of (labels counted), among (all labels)."""


def read(run, params):
    counts = run.counters.get(params["counter"]) or {}
    total = sum(counts.get(k, 0) for k in params["among"])
    if not total:
        return None
    return 100.0 * sum(counts.get(k, 0) for k in params["of"]) / total
