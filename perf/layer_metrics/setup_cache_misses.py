"""``setup_cache_misses``: executables really compiled before the window
opens: 0 in a warm run, all of them in the first run of a call. Read
from the program's start-up log (perf/lib/startup_log.py); None where
the program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.compile_total(records, 'cache_misses')
