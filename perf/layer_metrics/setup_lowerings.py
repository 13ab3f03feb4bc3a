"""``setup_lowerings``: programs lowered before the window opens (one per
compile request, whether the persistent cache then hits or not). Read
from the program's start-up log (perf/lib/startup_log.py); None where
the program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.compile_total(records, 'lowerings')
