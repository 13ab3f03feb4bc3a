"""``setup_jit_lower_s``: seconds lowering jaxprs to MLIR modules before
the window opens, every program. Read from the program's start-up log
(perf/lib/startup_log.py); None where the program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.compile_total(records, 'lower_s')
