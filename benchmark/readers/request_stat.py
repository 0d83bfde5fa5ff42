"""A statistic over the request log.  params: field (a key of the request
records, or "a-b" for a difference of two), stat, scale."""
from ..stats import stat


def read(run, params):
    field = params["field"]
    vals = []
    for r in run.requests:
        if "-" in field:
            a, b = field.split("-")
            if r.get(a) is None or r.get(b) is None:
                continue
            vals.append(r[a] - r[b])
        elif r.get(field) is not None:
            vals.append(r[field])
    value = stat(vals, params["stat"])
    return None if value is None else value * params.get("scale", 1.0)
