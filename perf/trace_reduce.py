"""The reduction from the profiler's ``.xplane.pb`` to numbers: busy
union and idle share, device time per compiled program and per
kernel, collective time that no compute hides, the longest idle gaps
by what the host was doing. Read with nothing but JAX
(``jax.profiler.ProfileData``). Checked on a trace recorded on the
chip and kept trimmed in ``perf/tests/data/``.

A TPU's device plane (``/device:TPU:<n>``) carries a line ``XLA
Modules`` (one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``) and a line ``XLA Ops`` (one event
per operation the core ran; operations of one core run one after
another). Host planes carry one line per thread, with
``jax.profiler.TraceAnnotation`` spans on the same clock."""
import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

Interval = Tuple[float, float]  # start, end, seconds

_MODULE_LINE = 'XLA Modules'
_OPS_LINE = 'XLA Ops'
# Zero-length spans the harness writes just after the profiler has
# started and just before it is stopped (perf/lib/harness.py).
TRACE_ON, TRACE_OFF = 'perf.trace_on', 'perf.trace_off'
COLLECTIVE = re.compile(
    r'all-reduce|all-gather|reduce-scatter|all-to-all|'
    r'collective-permute|collective-broadcast')


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not files:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return files[-1]


def short_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = bf16[
    ...] fusion(...)`` -> ``fusion.12``."""
    return name.split(' = ')[0].lstrip('%')


def result_shape(name: str) -> str:
    """The type and shape an operation's HLO text gives its result
    (``bf16[16,4096,8,128]``; a tuple's first member), or ''. The
    trace of this runtime carries no source scope on an operation, so
    the shape is what tells a reader which ``fusion.241`` it is."""
    m = re.search(r' = \(?([a-z0-9]+\[[0-9,]*\])', name)
    return m.group(1) if m else ''


def _is_container(name: str) -> bool:
    """A loop, branch or call: its event spans the operations inside
    it, which the same line lists too."""
    return re.match(r'%?(while|conditional|call)\b', name) is not None


def _programs(module_events) -> Tuple[List[float], List[Tuple]]:
    """The runs of compiled programs on one device, sorted, as
    ``(start, end, name)`` with ``jit_`` and the fingerprint cut
    off, and their starts for ``bisect``."""
    runs = sorted((s, s + d, name.split('(')[0].replace('jit_', '', 1))
                  for name, s, d in module_events)
    return [r[0] for r in runs], runs


def _program_at(starts: List[float], runs: List[Tuple],
                when: float) -> str:
    """The compiled program that was running at ``when``, or ''."""
    i = bisect.bisect_right(starts, when) - 1
    return runs[i][2] if i >= 0 and when < runs[i][1] else ''


def load(path: str) -> Dict[str, Any]:
    """The trace as plain data: ``{'devices': {plane name: {line
    name: [(name, start_s, dur_s)]}}, 'host': [(name, start_s,
    dur_s)], 'shapes': {(compiled program, operation name): result
    type and shape}}``. Operation names are cut to their short form;
    the compiler numbers each program's operations from nought, so
    ``fusion.241`` is one operation in ``decode_steps_paged`` and
    another in ``forward_paged``, and a shape is kept by both names.
    A path ending in ``.gz`` is a gzipped ``.xplane.pb``."""
    from jax.profiler import ProfileData
    if path.endswith('.gz'):
        import gzip
        with gzip.open(path, 'rb') as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    shapes: Dict[Tuple[str, str], str] = {}
    for plane in data.planes:
        is_dev = plane.name.startswith('/device:TPU:')
        is_host = plane.name.startswith('/host:')
        if not (is_dev or is_host):
            continue
        long_names = []
        for line in plane.lines:
            events = [(short_name(e.name), e.start_ns * 1e-9,
                       e.duration_ns * 1e-9) for e in line.events]
            if is_dev:
                devices.setdefault(plane.name, {})[line.name] = events
                if line.name == _OPS_LINE:
                    long_names = [e.name for e in line.events]
            else:
                host.extend(events)
        if long_names:
            lines = devices[plane.name]
            starts, runs = _programs(lines.get(_MODULE_LINE, []))
            for long, (short, s, _) in zip(long_names,
                                           lines[_OPS_LINE]):
                shapes.setdefault((_program_at(starts, runs, s), short),
                                  result_shape(long))
    return {'devices': devices, 'host': host, 'shapes': shapes}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _ivals(events) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def busy_lines(lines: Dict[str, list]) -> list:
    """The events that say when a device ran something: its
    operations, or its programs where the trace has no operations."""
    return lines.get(_OPS_LINE) or lines.get(_MODULE_LINE) or []


def window_of(trace: Dict[str, Any]) -> Interval:
    """First start to last end of anything a device ran."""
    starts, ends = [], []
    for lines in trace['devices'].values():
        for _, s, d in busy_lines(lines):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError('no operation ran on a device in this trace')
    return min(starts), max(ends)


def busy_seconds(trace: Dict[str, Any]) -> float:
    """Seconds in which an operation ran, averaged over devices."""
    per_dev = [total(union(_ivals(busy_lines(lines))))
               for lines in trace['devices'].values()]
    return sum(per_dev) / len(per_dev)


def traced_stretch(trace: Dict[str, Any]) -> Interval:
    """The stretch in which the profiler was certainly recording, on
    the trace's own clock: from the harness's ``perf.trace_on`` span
    (written once ``start_trace`` has returned) to its
    ``perf.trace_off`` (written before ``stop_trace`` is called), or
    where a trace has neither, between the profiler's own
    ``start_trace`` and ``stop_trace`` calls as its Python tracer
    records them. A program that was running at either instant is
    in the trace as far as the recording reaches: a call cut short."""
    on = [s + d for name, s, d in trace['host']
          if name == TRACE_ON or name.endswith(' start_trace')]
    off = [s for name, s, _ in trace['host']
           if name == TRACE_OFF or name.endswith(' stop_trace')]
    return (max(on) if on else float('-inf'),
            min(off) if off else float('inf'))


def whole_calls(lines: Dict[str, list], stretch: Interval) -> list:
    """The runs of compiled programs on one device that lie wholly
    inside ``stretch``. The two that the traced stretch cuts at its
    edges are left out: counted as whole calls they read a program's
    time per call low, by up to a call in ten over a 5 s stretch."""
    return [(name, s, d) for name, s, d in lines.get(_MODULE_LINE, [])
            if stretch[0] <= s and s + d <= stretch[1]]


def module_times(trace: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Per compiled program (``jit_<function>``, fingerprint cut
    off): calls and seconds on the busiest device, over the calls
    that lie wholly inside the traced stretch."""
    out: Dict[str, Dict[str, float]] = {}
    stretch = traced_stretch(trace)
    for lines in trace['devices'].values():
        mine: Dict[str, Dict[str, float]] = {}
        for name, _, d in whole_calls(lines, stretch):
            base = name.split('(')[0]
            rec = mine.setdefault(base, {'calls': 0, 'seconds': 0.0})
            rec['calls'] += 1
            rec['seconds'] += d
        for base, rec in mine.items():
            if rec['seconds'] > out.get(base, {'seconds': -1.0}
                                        )['seconds']:
                out[base] = rec
    return out


def op_seconds(trace: Dict[str, Any], pattern: str
               ) -> Dict[str, float]:
    """Calls and summed seconds of the operations whose name matches
    ``pattern`` (a regex), on the busiest device. Where the device
    names its programs, only operations inside the calls that
    ``module_times`` counts: a kernel's time and the steps it is
    set against are then of the same calls."""
    rx = re.compile(pattern)
    best = {'calls': 0, 'seconds': 0.0}
    stretch = traced_stretch(trace)
    for lines in trace['devices'].values():
        named = bool(lines.get(_MODULE_LINE))
        starts, runs = _programs(whole_calls(lines, stretch))
        hit = [d for name, s, d in lines.get(_OPS_LINE, [])
               if rx.search(name) and
               (not named or _program_at(starts, runs, s))]
        if sum(hit) > best['seconds']:
            best = {'calls': len(hit), 'seconds': sum(hit)}
    return best


def collective_exposed(trace: Dict[str, Any]) -> Dict[str, float]:
    """On the worst device: seconds in which a collective operation
    ran and no other operation did, and seconds busy at all."""
    worst = {'exposed_s': 0.0, 'busy_s': 0.0, 'collective_s': 0.0}
    for lines in trace['devices'].values():
        ops = lines.get(_OPS_LINE, [])
        coll = union(_ivals(e for e in ops if COLLECTIVE.search(e[0])))
        # A loop's own event spans everything inside it: left out,
        # or nothing would ever count as exposed.
        rest = union(_ivals(e for e in ops
                            if not COLLECTIVE.search(e[0])
                            and not _is_container(e[0])))
        exposed = total(subtract(coll, rest))
        if exposed >= worst['exposed_s']:
            worst = {'exposed_s': exposed,
                     'busy_s': total(union(_ivals(ops))),
                     'collective_s': total(coll)}
    return worst


def top_ops(trace: Dict[str, Any], n: int = 10
            ) -> List[List[Any]]:
    """The operations that took most device time on the first
    device, summed by name: ``[[name, seconds], ...]``, each named
    ``<compiled program>/<operation> <result shape>`` - the program
    (``decode_steps_paged``, ``forward_paged``, ``step_fn``) it ran
    inside is the nearest thing to a source scope this trace has.
    Loops and calls are left out: their time is their operations'."""
    if not trace['devices']:
        return []
    lines = trace['devices'][sorted(trace['devices'])[0]]
    starts, runs = _programs(lines.get(_MODULE_LINE, []))
    shapes = trace.get('shapes', {})
    by_name: Dict[str, float] = {}
    for name, s, d in busy_lines(lines):
        if _is_container(name):
            continue
        program = _program_at(starts, runs, s)
        shape = shapes.get((program, name))
        label = ((program + '/' if program else '') + name +
                 (' ' + shape if shape else ''))
        by_name[label] = by_name.get(label, 0.0) + d
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def idle_gaps(trace: Dict[str, Any], annotations_prefix: str,
              n: int = 10) -> List[List[Any]]:
    """Idle seconds of the first device inside the traced window,
    summed by what the host was doing: the harness's own
    ``TraceAnnotation`` span (names starting with
    ``annotations_prefix``) that covers most of each gap, or
    ``unattributed``."""
    if not trace['devices']:
        return []
    lines = trace['devices'][sorted(trace['devices'])[0]]
    busy = union(_ivals(busy_lines(lines)))
    if not busy:
        return []
    gaps = subtract([(busy[0][0], busy[-1][1])], busy)
    spans = sorted((s, s + d, name) for name, s, d in trace['host']
                   if name.startswith(annotations_prefix))
    by_name: Dict[str, float] = {}
    for gs, ge in gaps:
        best_name, best_cover = 'unattributed', 0.0
        for ss, se, name in spans:
            if ss >= ge:
                break
            cover = min(ge, se) - max(gs, ss)
            if cover > best_cover:
                best_name, best_cover = name, cover
        by_name[best_name] = by_name.get(best_name, 0.0) + (ge - gs)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]
