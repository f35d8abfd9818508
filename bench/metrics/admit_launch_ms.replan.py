"""Host milliseconds per admission sweep in launch and fetch: the
program's ``admit.device`` spans outside the fleet less the device-busy
time inside them, over the traced window's admission sweeps."""
from bench import program_spans


def read(run):
    return program_spans.per_sweep_ms(run, ("admit.device",),
                                     less_device=True)
