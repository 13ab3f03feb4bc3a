"""``setup_prewarm_decode_s``: seconds of the engine's decode prewarm,
all widths (stage ``engine.build.prewarm_decode``; the seconds the
engine logs). Read from the program's start-up log
(perf/lib/startup_log.py); None where the program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.stage_seconds(
        records, 'engine.build.prewarm_decode')
