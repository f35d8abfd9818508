"""Host milliseconds per admission sweep laying the kernels' inputs out:
the program's ``admit.chunks`` spans (``grid_jax._iter_chunks``, once per
sweep) and ``admit.inputs`` spans (``grid_pallas._kernel_inputs``, or
``grid_jax._chunk_tables`` on the jax tier, once per chunk) outside the
fleet, over the traced window's admission sweeps."""
from bench import program_spans


def read(run):
    return program_spans.per_sweep_ms(run, ("admit.chunks", "admit.inputs"))
