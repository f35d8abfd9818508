"""Host milliseconds per completed job in the fleet's re-plans of its
pending queues: the program's ``fleet.replan`` spans in the traced window,
each whole, over the jobs completed."""
from bench import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("fleet.replan",))
