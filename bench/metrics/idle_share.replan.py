"""Share of the traced window in which no op ran on the device, in %."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace_reduce.window(run.trace)
    return 100.0 * (1.0 - trace_reduce.busy(run.trace, lo, hi) / (hi - lo))
