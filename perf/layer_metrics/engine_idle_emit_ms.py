"""``engine_idle_emit_ms``: milliseconds per scheduler iteration in
which the first device ran nothing while the engine's thread was
handing a dispatch's tokens to the clients and retiring finished
rows. Read from the program's ``skytpu.engine.*`` spans over the
traced stretch (perf/lib/engine_spans.py); None where the program
has none."""
from perf.lib import engine_spans


def reduce(trace, records):
    del records
    return engine_spans.idle_ms_per_iteration(trace, ('emit',))
