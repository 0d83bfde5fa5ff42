"""1 - the union of device-operation intervals over the traced window, on
the busiest chip."""


def read(run, params):
    s = run.trace_summary
    if s is None:
        return None
    busy = s["busy_by_chip"][s["busiest_chip"]]
    return 100.0 * (1.0 - busy / s["window_s"])
