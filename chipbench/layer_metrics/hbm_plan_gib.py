"""Layer: device. What XLA plans for the step program on one chip:
arguments + outputs - aliased + temporaries, in GiB. A plan, not a
measurement -- and the only figure that sees the step's temporaries."""


def read(run):
    if run.plan is None:
        return None
    return run.plan['total'] / 2 ** 30
