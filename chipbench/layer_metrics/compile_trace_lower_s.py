"""Layer: compile_cache (telemetry/compile.py). Seconds of Python
tracing + lowering of the step program in this run, from the compile
ledger's entry of the step's build site. Paid on every start, warm cache
or not."""
from chipbench import program


def read(run):
    entries = [e for e in run.ledger if e['site'] == program.STEP_SITE]
    if not entries:
        return None
    return entries[0]['seconds']['trace'] + entries[0]['seconds']['lower']
