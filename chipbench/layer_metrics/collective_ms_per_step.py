"""Layer: collectives (GSPMD from parallel/step.py). Device time in which
a collective op (all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute, by HLO opcode, alone or inside a fusion) ran, per
traced step, mean over chips."""


def read(run):
    if run.trace is None or not run.trace['collective_calls']:
        return None
    return 1e3 * run.trace['collective_s'] / run.trace['steps']
