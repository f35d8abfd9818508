"""Device milliseconds of the sweep kernel per device sweep: the summed
durations of its ops in the traced window over the window's sweeps that
ran on the device."""
from bench import roofline, trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace_reduce.window(run.trace)
    ns, n = trace_reduce.kernel_ns(run.trace, roofline.KERNELS["sweep"],
                                   lo, hi)
    sweeps = sum(s.device for s in run.sweeps)
    if not n or not sweeps:
        return None
    return ns / 1e6 / sweeps
