"""Host milliseconds per admission sweep on the numpy planner: the
program's ``admit.numpy`` spans (a sweep below the device tier's batch
minimum) outside the fleet, over the traced window's admission sweeps."""
from bench import program_spans


def read(run):
    return program_spans.per_sweep_ms(run, ("admit.numpy",))
