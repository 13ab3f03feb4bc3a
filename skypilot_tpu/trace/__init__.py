"""End-to-end distributed tracing (docs/observability.md, Tracing).

Public surface:

    with trace.span('launch', new_trace=True) as sp: ...
    trace.current() / trace.attach(ctx)
    trace.context_env()            # env stamp for child processes
    trace.format_traceparent() / trace.parse_traceparent(header)
    trace.record_span(...)         # explicit-timestamp emission
    trace.phase('engine.admit')    # loop phase on the profiler's clock
    trace.collect                  # driver-side assembly/rendering
"""
from skypilot_tpu.trace import collect
from skypilot_tpu.trace.tracer import (ENV_CONTEXT, TRACEPARENT_HEADER,
                                       Span, SpanContext, attach,
                                       child_context, chrome_export,
                                       component, context_env,
                                       current, emit_span, enabled,
                                       format_traceparent,
                                       parse_traceparent, phase,
                                       record_span,
                                       reset_current, reset_sink,
                                       sample_root, set_component,
                                       set_current, sink_dir, span)

__all__ = [
    'ENV_CONTEXT', 'TRACEPARENT_HEADER', 'Span', 'SpanContext',
    'attach', 'child_context', 'chrome_export', 'collect',
    'component', 'context_env', 'current', 'emit_span', 'enabled',
    'format_traceparent', 'parse_traceparent', 'phase',
    'record_span', 'reset_current', 'reset_sink', 'sample_root',
    'set_component', 'set_current', 'sink_dir', 'span',
]
