"""`span_arg_share` with `among_held_steps` for a model whose routed layers
are not all of its layers (a leading dense layer): the experts hit, summed
over the spans of one name in the measured window, among the held experts x
the layers of the named kinds x the decode chunk's steps a span.  params:
span, of, kinds (the entries of the architecture's `kinds` that have
experts).  None where the ring holds no such span or arg."""
from ..arch import load as load_arch


def share(hits, d, kinds, chunk):
    """hits: the `of` arg of each span -> per cent of what could be hit."""
    layers = sum(k in kinds for k in d["kinds"])
    each = len(d["held"]) * layers * chunk
    return 100.0 * sum(hits) / (each * len(hits)) if hits and each else None


def read(run, params):
    from paddle_tpu.observability import get_tracer
    lo, hi = run.window
    hits = [ev[6][params["of"]] for ev in get_tracer().events()
            if ev[0] == params["span"] and ev[6] and params["of"] in ev[6]
            and ev[1] >= lo and ev[1] + ev[2] <= hi + 1e-9]
    d = load_arch(run.config["arch"]).dims(run.config)
    return share(hits, d, params["kinds"],
                 run.traffic["engine"]["decode_chunk"])
