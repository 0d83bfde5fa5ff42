"""`decode_roofline` for a model whose cache holds a state of fixed size
beside its rows (`arch.decode_call_cost`): the least bytes of the decode
program's calls in the traced stretch over the peak bandwidth, over the
program's device time.  A traced `serving_decode` span carries what its call
went over, summed over layers and the call's steps: `experts_hit` (a held
expert is read only where a token was routed to it), `ssm_state_pool` (each
slot-layer of state read and written) and `kv_rows_live` (the attention's
live rows; a row that is not alive is not credited).  A program that records
no such args reports nothing.  params: program (pattern of the program's
events), span."""
from .. import trace_reduce
from ..arch import load as load_arch

ARGS = ("experts_hit", "ssm_state_pool", "kv_rows_live")


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or "t1" not in tr:
        return None
    measured, calls = trace_reduce.pattern_seconds(
        s, params["program"], line="modules")
    arch = load_arch(run.config["arch"])
    if not calls or not hasattr(arch, "decode_call_cost"):
        return None
    from paddle_tpu.observability import get_tracer
    spans = [ev[6] for ev in get_tracer().events()
             if ev[0] == params["span"] and ev[6]
             and all(k in ev[6] for k in ARGS)
             and tr["t0"] <= ev[1] <= tr["t1"]]
    if not spans:
        return None
    d = arch.dims(run.config)
    chunk = run.traffic["engine"]["decode_chunk"]
    nbytes = sum(arch.decode_call_cost(d, chunk, *(a[k] for k in ARGS))
                 for a in spans)
    # spans and traced calls can differ by the one at either edge
    nbytes *= min(1.0, calls / len(spans))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / measured
