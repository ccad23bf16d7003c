"""Layer: collectives. The part of collective_ms_per_step during which no
other op ran on that chip: what the step waits for."""


def read(run):
    if run.trace is None or not run.trace['collective_calls']:
        return None
    return 1e3 * run.trace['collective_exposed_s'] / run.trace['steps']
