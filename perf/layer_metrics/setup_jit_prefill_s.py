"""``setup_jit_prefill_s``: trace + lowering + backend seconds of program
``forward_paged`` before the window opens (the harness's warm-up
lowers its buckets on the loop's thread, outside any stage). Read from
the program's start-up log (perf/lib/startup_log.py); None where the
program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.jit_seconds(records, 'forward_paged')
