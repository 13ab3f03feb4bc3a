"""``setup_jit_backend_s``: seconds in the backend before the window
opens, every program: XLA compilation, or the persistent cache's
retrieval. Read from the program's start-up log
(perf/lib/startup_log.py); None where the program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.compile_total(records, 'backend_s')
