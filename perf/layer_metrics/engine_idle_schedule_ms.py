"""``engine_idle_schedule_ms``: milliseconds per scheduler iteration
in which the first device ran nothing while the engine's thread was
scheduling: sweeping cancellations and deadlines, polling adapter
loads, admitting requests (hash chains, prefix match, block
allocation) or setting gauges. Read from the program's
``skytpu.engine.*`` spans over the traced stretch
(perf/lib/engine_spans.py); None where the program has none."""
from perf.lib import engine_spans


def reduce(trace, records):
    del records
    return engine_spans.idle_ms_per_iteration(
        trace, ('sweep', 'admit', 'gauges'))
