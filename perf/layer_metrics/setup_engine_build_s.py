"""``setup_engine_build_s``: seconds inside the engine's constructor
(stage ``engine.build``: pool, copy, verify and decode prewarms). Read
from the program's start-up log (perf/lib/startup_log.py); None where
the program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.stage_seconds(records, 'engine.build')
