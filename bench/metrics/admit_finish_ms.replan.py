"""Host milliseconds per admission sweep choosing each job's cell and
assembling its plan: the program's ``admit.select`` and ``admit.finish``
spans outside the fleet, over the traced window's admission sweeps."""
from bench import program_spans


def read(run):
    return program_spans.per_sweep_ms(run, ("admit.select", "admit.finish"))
