"""`mfu_serve` for an architecture that counts its own operations
(`arch.prefill_flops`, `arch.decode_token_flops`): forward operations of
every prompt and output token served in the traced stretch over its seconds
and the peak.  A decoded token attends its request's rows, in window layers
at most the window: the step records' `rows_full` and `rows_window` (summed
over the decoding requests, the cap taken a request) give the means."""
from ..arch import load as load_arch


def read(run, params):
    s = run.trace_summary
    steps = [st for st in run.engine_steps if st.get("traced")]
    arch = load_arch(run.config["arch"])
    if s is None or not steps or not hasattr(arch, "prefill_flops"):
        return None
    d = arch.dims(run.config)
    ops = 0.0
    for st in steps:
        ops += sum(arch.prefill_flops(n, d) for n in st["admitted_plens"])
        decoded = st["tokens"] - st["admitted"]
        if decoded > 0 and st["running"] and "rows_full" in st:
            ops += decoded * arch.decode_token_flops(
                st["rows_full"] / st["running"], d,
                st["rows_window"] / st["running"])
    return 100.0 * ops / (s["window_s"] * len(run.devices)
                          * run.peaks["bf16_flops_per_s"])
