"""Layer: device. 1 - (union of the intervals in which any op ran on the
chip) / (the traced window, first op to last), mean over the cell's
chips, in percent."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace['idle_share']
