"""The rate_prefix kernel's share of its roofline, in %: the least time the
chip's published peaks allow for the operations and bytes the window's
live work needs (roofline.rate_prefix_cost), over the kernel's device time
in the traced window."""
from bench import roofline, trace_reduce


def read(run):
    if run.trace is None or run.work() is None:
        return None
    lo, hi = trace_reduce.window(run.trace)
    ns, n = trace_reduce.kernel_ns(run.trace, roofline.KERNELS["rate_prefix"],
                                   lo, hi)
    if not n:
        return None
    least, _bound = roofline.least_s(*run.work()["rate_prefix"], run.device_kind)
    return 100.0 * least / (ns / 1e9)
