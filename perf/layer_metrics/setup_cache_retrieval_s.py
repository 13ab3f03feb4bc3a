"""``setup_cache_retrieval_s``: of ``setup_jit_backend_s``, the seconds
the persistent cache took to hand executables back. Read from the
program's start-up log (perf/lib/startup_log.py); None where the
program keeps none."""
from perf.lib import startup_log


def reduce(trace, records):
    del trace
    return startup_log.compile_total(records, 'retrieval_s')
