"""Host milliseconds per admission sweep building the cell table: the
program's ``admit.cells`` spans (``CarbonPlanner._batch_cells``) outside
the fleet, over the traced window's admission sweeps."""
from bench import program_spans


def read(run):
    return program_spans.per_sweep_ms(run, ("admit.cells",))
