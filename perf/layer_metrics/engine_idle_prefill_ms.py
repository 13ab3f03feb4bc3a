"""``engine_idle_prefill_ms``: milliseconds per scheduler iteration
in which the first device ran nothing while the engine's thread was
inside its prefill phase: building and enqueueing chunks, the launch
latency of the first chunk after the device has drained, and each
finished prompt's first token (the ``device_get`` of its logits, the
registration of its prefix). Read from the program's
``skytpu.engine.*`` spans over the traced stretch
(perf/lib/engine_spans.py); None where the program has none."""
from perf.lib import engine_spans


def reduce(trace, records):
    del records
    return engine_spans.idle_ms_per_iteration(trace, ('prefill',))
