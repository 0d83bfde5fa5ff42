"""Forward operations of every prompt and output token served in the traced
stretch over its seconds and the peak."""
from .. import flops
from ..arch import load as load_arch


def read(run, params):
    s = run.trace_summary
    steps = [st for st in run.engine_steps if st.get("traced")]
    if s is None or not steps:
        return None
    d = load_arch(run.config["arch"]).dims(run.config)
    ops = 0.0
    for st in steps:
        ops += sum(flops.prefill_flops(n, d) for n in st["admitted_plens"])
        decoded = st["tokens"] - st["admitted"]
        if decoded > 0 and st["running"]:
            ops += decoded * flops.decode_token_flops(
                st["live_rows"] / st["running"], d)
    return 100.0 * ops / (s["window_s"] * len(run.devices)
                          * run.peaks["bf16_flops_per_s"])
