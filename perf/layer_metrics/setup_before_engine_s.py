"""``setup_before_engine_s``: seconds from the process's start to the
entry of the engine's constructor (stage ``engine.build``): imports,
the backend's start, the benchmark's weights. Read from the program's
start-up log (perf/lib/startup_log.py); None where the program keeps
none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.before_stage_seconds(records, 'engine.build')
