"""A statistic over the per-`engine.step()` log.  params: field, stat,
scale, where (optional: a flag of the record that must be true), over
(optional: divide each value by this run constant, e.g. "max_slots")."""
from ..stats import stat


def read(run, params):
    vals = [rec[params["field"]] for rec in run.engine_steps
            if rec.get(params["field"]) is not None
            and ("where" not in params or rec.get(params["where"]))]
    if "over" in params:
        vals = [v / run.extra[params["over"]] for v in vals]
    value = stat(vals, params["stat"])
    return None if value is None else value * params.get("scale", 1.0)
