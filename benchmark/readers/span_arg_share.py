"""A share of two counts the program's spans carry in their `args`, summed
over the spans of one name in the measured window.  params: span, of (the
arg counted), and among (the arg it is a share of) or among_held_steps
(true: the share is of the held experts x layers x the decode chunk's steps
a span, from the configuration and the mix).  None where the ring holds no
such span or arg, as in a program that does not record it."""
from ..arch import load as load_arch


def read(run, params):
    from paddle_tpu.observability import get_tracer
    lo, hi = run.window
    of = among = 0.0
    each = None
    if params.get("among_held_steps"):
        d = load_arch(run.config["arch"]).dims(run.config)
        each = (len(d["held"]) * d["L"]
                * run.traffic["engine"]["decode_chunk"])
    for ev in get_tracer().events():
        args = ev[6]
        if (ev[0] != params["span"] or not args or params["of"] not in args
                or ev[1] < lo or ev[1] + ev[2] > hi + 1e-9):
            continue
        of += args[params["of"]]
        among += each if each is not None else args[params["among"]]
    return 100.0 * of / among if among else None
