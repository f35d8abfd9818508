"""Host milliseconds of the gateway and the fleet drain per completed
job: the run's wall time less the walls of its admission sweeps, over
the jobs completed (benchmark clock)."""


def read(run):
    if run.stats is None or not run.jobs_done:
        return None
    admit = sum(s.wall_s for s in run.sweeps)
    return 1000.0 * (run.wall_s - admit) / run.jobs_done
