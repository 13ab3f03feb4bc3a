"""``engine_idle_dispatch_ms``: milliseconds per scheduler iteration
in which the first device ran nothing while the engine's thread was
preparing and enqueueing the decode (or verify) dispatch and then
waiting for it: block growth, the ``active`` mask, and the launch
latency before the device starts. Read from the program's
``skytpu.engine.*`` spans over the traced stretch
(perf/lib/engine_spans.py); None where the program has none."""
from perf.lib import engine_spans


def reduce(trace, records):
    del records
    return engine_spans.idle_ms_per_iteration(
        trace, ('dispatch', 'device_wait'))
