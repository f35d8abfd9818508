"""Share of the gateway's micro-batches whose admission sweep ran on the
device tier: ``GatewayStats.device_sweeps / n_batches``, in %."""


def read(run):
    if run.stats is None or not run.stats.n_batches:
        return None
    return 100.0 * run.stats.device_sweeps / run.stats.n_batches
