"""``setup_jit_decode_s``: trace + lowering + backend seconds of program
``decode_steps_paged`` before the window opens. Read from the
program's start-up log (perf/lib/startup_log.py); None where the
program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.jit_seconds(records, 'decode_steps_paged')
