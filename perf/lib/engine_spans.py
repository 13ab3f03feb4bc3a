"""The device's idle time split by what the engine's thread was
doing, per scheduler iteration.

The program (``skypilot_tpu/serve/batching.py:_iterate``) wraps each
pass of its loop in a ``jax.profiler.TraceAnnotation`` named
``skytpu.engine.iteration`` and partitions it into phases
(``.sweep``, ``.admit``, ``.prefill`` with ``.prefill_chunk`` /
``.first_token`` children, ``.dispatch``, ``.device_wait``, ``.emit``,
``.gauges``), all on one thread and on the clock of the device's own
operations. An idle gap of the first device is cut *exactly* along
those spans (interval intersection; the phases of one thread do not
overlap), not put down whole to the span that covers most of it.

A program without the spans (the parent commit, the train cells)
gives None."""
from typing import Any, Dict, List, Optional, Sequence

from perf import trace_reduce

PREFIX = 'skytpu.engine.'
_WORK = ('prefill_chunk', 'device_wait')


def engine_spans(trace: Dict[str, Any]
                 ) -> Dict[str, List[trace_reduce.Interval]]:
    """``{phase: [(start, end)]}`` of the engine's spans, sorted. A
    ``TraceAnnotation`` with keyword arguments may appear in the
    plane as ``name#k=v,...#``: the name ends at the first ``#``."""
    out: Dict[str, List[trace_reduce.Interval]] = {}
    for name, start, dur in trace['host']:
        if name.startswith(PREFIX):
            phase = name[len(PREFIX):].split('#')[0]
            out.setdefault(phase, []).append((start, start + dur))
    for spans in out.values():
        spans.sort()
    return out


def worked_iterations(spans: Dict[str, List[trace_reduce.Interval]],
                      window: trace_reduce.Interval
                      ) -> List[trace_reduce.Interval]:
    """The iterations wholly inside ``window`` that did work: they
    hold a prefill chunk or a wait for a dispatch."""
    work = [s for phase in _WORK for s, _ in spans.get(phase, [])]
    return [(s, e) for s, e in spans.get('iteration', [])
            if window[0] <= s and e <= window[1]
            and any(s <= w < e for w in work)]


def idle_account(trace: Optional[Dict[str, Any]]
                 ) -> Optional[Dict[str, Any]]:
    """The first device's idle gaps inside the worked iterations of
    the traced stretch: ``{'iterations': n, 'idle': [gaps], 'spans':
    {phase: [...]}}``, or None where the trace has no device, no
    operation or no such iteration."""
    if trace is None or not trace['devices']:
        return None
    lines = trace['devices'][sorted(trace['devices'])[0]]
    busy = trace_reduce.union(
        (s, s + d) for _, s, d in trace_reduce.busy_lines(lines))
    if not busy:
        return None
    window = (busy[0][0], busy[-1][1])
    spans = engine_spans(trace)
    worked = worked_iterations(spans, window)
    if not worked:
        return None
    return {'iterations': len(worked),
            'idle': trace_reduce.subtract(trace_reduce.union(worked),
                                          busy),
            'spans': spans}


def _under(gaps: List[trace_reduce.Interval],
           spans: List[trace_reduce.Interval]) -> float:
    """Seconds of ``gaps`` that ``spans`` cover."""
    covered = trace_reduce.union(spans)
    return trace_reduce.total(gaps) - trace_reduce.total(
        trace_reduce.subtract(gaps, covered))


def idle_ms_per_iteration(trace: Optional[Dict[str, Any]],
                          phases: Sequence[str]) -> Optional[float]:
    """Milliseconds per worked iteration in which the first device
    ran nothing while the engine's thread was inside one of
    ``phases`` (children included: a phase's interval is its own)."""
    account = idle_account(trace)
    if account is None:
        return None
    under = [iv for p in phases for iv in account['spans'].get(p, [])]
    return 1e3 * _under(account['idle'], under) / account['iterations']
