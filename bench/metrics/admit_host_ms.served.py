"""Host milliseconds per admission sweep: each ``bench.admit`` span's
length less the device-busy time inside it, averaged over the sweeps of
the traced window."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    return trace_reduce.admit_host_ms(run.trace)
