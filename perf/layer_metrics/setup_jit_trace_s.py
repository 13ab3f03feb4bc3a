"""``setup_jit_trace_s``: seconds of Python tracing before the window
opens, outermost traces only, every program. Read from the program's
start-up log (perf/lib/startup_log.py); None where the program keeps
none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.compile_total(records, 'trace_s')
