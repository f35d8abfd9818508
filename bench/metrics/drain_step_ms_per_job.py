"""Host milliseconds per completed job stepping transfers and settling
completions: the program's ``fleet.step`` and ``fleet.complete`` spans in
the traced window, each whole, over the jobs completed."""
from bench import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("fleet.step", "fleet.complete"))
