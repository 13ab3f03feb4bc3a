"""Process-level JAX set-up for every entry point that compiles
(``recipes/finetune``, ``recipes/serve_model``, ``bench.py``): where
the persistent compile cache lives, a plain statement of the device
and toolchain the process actually got — so a job log alone shows
whether it ran on the chip — and the start-up log: where the seconds
between a process's start and its first step or ``ready`` went.

The start-up log is kept in memory, always on, on this process's
``perf_counter`` clock, and holds two kinds of record:

- **stages** (``stage(name, **attrs)``): the named stretches of a
  start-up (``engine.build``, ``replica.start.weights``, ...), each
  with its parent, its self time and what was compiled inside it;
- **compilations**: one record per outermost compilation that JAX
  announces through ``jax.monitoring`` (trace, lowering, backend
  compile or the persistent cache's retrieval), with the program's
  name, the seconds of each part and the stage open on its thread.

``docs/observability.md`` ("Start-up and compilation") is the
contract of the span names and metric families fed from here.

Nothing here initialises a backend at import; ``device_facts`` and
``runtime_facts`` do when called (their callers own the chip).
"""
import collections
import contextlib
import functools
import importlib.metadata
import json
import os
import sys
import threading
# (Not the module: tests/test_chip_smoke.py holds this file to no
# clock near the cache's path.)
from time import perf_counter, time as wall_clock
from typing import Any, Dict, List, Optional

from skypilot_tpu import metrics as metrics_lib
from skypilot_tpu import tpu_logging
from skypilot_tpu import trace as trace_lib

logger = tpu_logging.init_logger(__name__)

CACHE_DIR_ENV = 'JAX_COMPILATION_CACHE_DIR'
_CACHE_SUBDIR = '.jax_cache'
# Prefix of the one-line device statement; chip_smoke.py finds the
# line in its children's logs by it.
DEVICE_LINE_PREFIX = 'skytpu device '

# JAX's own names (jax._src.dispatch, jax._src.compiler). Each of the
# three duration events is also announced at its START through
# ``record_scalar`` under the same name; only the trace's start is
# used (the nesting depth).
_TRACE_EVENT = '/jax/core/compile/jaxpr_trace_duration'
_LOWER_EVENT = '/jax/core/compile/jaxpr_to_mlir_module_duration'
_BACKEND_EVENT = '/jax/core/compile/backend_compile_duration'
_RETRIEVAL_EVENT = '/jax/compilation_cache/cache_retrieval_time_sec'
_HIT_EVENT = '/jax/compilation_cache/cache_hits'
_MISS_EVENT = '/jax/compilation_cache/cache_misses'
# Duration event -> the record's field it fills.
_PART_OF = {_TRACE_EVENT: 'trace_s', _LOWER_EVENT: 'lower_s',
            _BACKEND_EVENT: 'backend_s'}
SPAN_PREFIX = 'startup.'
# A replica that meets new shapes for weeks must not grow the log
# without bound; a start-up writes a few hundred records.
_MAX_RECORDS = 8192
# What a stage's record says of the compile account inside it, and
# what ``startup_log()['totals']`` holds.
_TOTAL_KEYS = ('lowerings', 'cache_hits', 'cache_misses', 'trace_s',
               'lower_s', 'backend_s', 'retrieval_s', 'inner_traces',
               'lowerings_after_ready')


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``, resolved from this package's own
    location: jobs launched through the local provider run from a
    runtime dir, so the working directory says nothing about where
    the code lives, and the path must be the same in every process
    and every run for the cache to hit."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, _CACHE_SUBDIR)


def configure_compile_cache() -> str:
    """Turn the persistent compile cache on; call before anything
    compiles. ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads
    it, and no other directory is set anywhere in code. Unset: the
    cache goes to ``default_cache_dir()``. Returns the directory in
    force.

    The entry thresholds are dropped either way: JAX's defaults skip
    executables that compiled in under a second, which is most of
    what a serving replica prewarms (block copies, samplers, small
    prefill buckets) — each is cheap, together they are the time to
    ready."""
    import jax
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update('jax_compilation_cache_dir',
                          default_cache_dir())
    jax.config.update('jax_persistent_cache_min_compile_time_secs',
                      0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    listen()
    return jax.config.jax_compilation_cache_dir


def _libtpu_version() -> Optional[str]:
    try:
        return importlib.metadata.version('libtpu')
    except importlib.metadata.PackageNotFoundError:
        return None


def device_facts() -> Dict[str, Any]:
    """The device as JAX reports it, plus the toolchain versions.
    Initialises the backend; raises whatever JAX raises when the
    platform named by ``JAX_PLATFORMS`` cannot start."""
    import jax
    import jaxlib
    devices = jax.devices()
    return {
        'platform': devices[0].platform,
        'device_kind': devices[0].device_kind,
        'device_count': len(devices),
        'jax': jax.__version__,
        'jaxlib': jaxlib.__version__,
        'libtpu': _libtpu_version(),
    }


def device_line(facts: Dict[str, Any]) -> str:
    """``skytpu device {...}`` — the first line both recipes print."""
    return DEVICE_LINE_PREFIX + json.dumps(facts)


# -- the start-up log -------------------------------------------------


class _ThreadState:
    """One thread's place in the log: how deep it is in nested
    parts, the compilation it is in the middle of (and whether that
    has been lowered), and its open stages. Only its own thread
    touches it."""
    __slots__ = ('depth', 'inner', 'record', 'lowered', 'stages')

    def __init__(self):
        self.depth = 0
        self.inner = 0  # inner traces not yet added to the totals
        self.record: Optional[Dict[str, Any]] = None
        self.lowered = False
        self.stages: List['_Stage'] = []


class _StartupLog:
    """The records and the running totals. A compilation's record is
    appended when its first part starts and filled in as its parts
    end, so the log never holds back a lowering that no backend
    compile followed (``jit(f).lower(...)`` alone)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.stages: 'collections.deque' = collections.deque(
            maxlen=_MAX_RECORDS)
        self.compilations: 'collections.deque' = collections.deque(
            maxlen=_MAX_RECORDS)
        self._totals = {k: 0.0 if k.endswith('_s') else 0
                        for k in _TOTAL_KEYS}
        self._local = threading.local()
        self.ready_at: Optional[float] = None
        self.listening = False
        self.faulted = False
        # The registry's families fed from the account, by the
        # total each follows; made when the listeners go in.
        self.families: Dict[str, Any] = {}

    def thread_state(self) -> _ThreadState:
        """This thread's state: it goes with its thread, so a
        handler thread's reused ident inherits nothing."""
        state = getattr(self._local, 'state', None)
        if state is None:
            state = self._local.state = _ThreadState()
        return state

    def totals(self) -> Dict[str, Any]:
        with self.lock:
            return dict(self._totals)

    def copy(self) -> Dict[str, Any]:
        with self.lock:
            return {'stages': [dict(r) for r in self.stages],
                    'compilations': [dict(r)
                                     for r in self.compilations],
                    'totals': dict(self._totals),
                    'ready_at': self.ready_at}

    def add(self, **amounts: float) -> None:
        """Add to the running totals and to the registry's families
        that follow them."""
        with self.lock:
            for key, amount in amounts.items():
                self._totals[key] += amount
        for key, amount in amounts.items():
            if key in self.families:
                self.families[key].inc(amount)

    def open_compilation(self, state: _ThreadState, program: str,
                         now: float) -> Dict[str, Any]:
        record = {
            'program': program, 'start': now, 'end': now,
            'trace_s': 0.0, 'lower_s': 0.0, 'backend_s': 0.0,
            'retrieval_s': 0.0, 'lowerings': 0, 'cache_hits': 0,
            'cache_misses': 0, 'inner_traces': 0,
            'stage': state.stages[-1].name if state.stages else None,
            'thread': threading.current_thread().name,
        }
        state.record = record
        state.lowered = False
        with self.lock:
            self.compilations.append(record)
        return record


_log = _StartupLog()


def _jit_families() -> Dict[str, Any]:
    reg = metrics_lib.registry()
    return {
        'lowerings': reg.counter(
            'skytpu_jit_lowerings_total',
            'Programs lowered by this process (one per compile '
            'request, whether the persistent cache then hits or '
            'not).'),
        'cache_misses': reg.counter(
            'skytpu_jit_cache_misses_total',
            'Executables really compiled (persistent-cache misses).'),
        'lowerings_after_ready': reg.counter(
            'skytpu_jit_lowerings_after_ready_total',
            'Programs lowered after mark_ready(): each stalled a '
            'request or a step for a compilation.'),
    }


def _bare(fun_name: Optional[str]) -> str:
    """``jit(outer)`` -> ``outer``: the trace event carries the bare
    function name, the lowering and the backend events the module's."""
    name = fun_name or '?'
    for prefix in ('jit(', 'pmap('):
        if name.startswith(prefix) and name.endswith(')'):
            return name[len(prefix):-1]
    return name


def _guarded(listener):
    """JAX calls its listeners from inside a compilation: a fault in
    the account must not take the program's compile down with it. The
    first one is logged with its traceback, later ones pass."""
    @functools.wraps(listener)
    def guarded(event, *args, **kwargs):
        try:
            listener(event, *args, **kwargs)
        except Exception:  # pylint: disable=broad-except
            if not _log.faulted:
                _log.faulted = True
                logger.exception('The start-up log failed on %s; its '
                                 'numbers are incomplete from here.',
                                 event)
    return guarded


@_guarded
def _on_scalar(event: str, value, **kwargs) -> None:
    """The START of a part: JAX announces each of the three through
    ``record_scalar`` under the duration event's own name. One depth
    per thread over all three, because a lowering traces too (its
    rules call ``jnp`` helpers): what starts at depth 0 is a part of
    an outermost compilation, anything deeper is inside one."""
    del value
    if event not in _PART_OF:
        return
    state = _log.thread_state()
    state.depth += 1
    if state.depth > 1:
        return
    record = state.record
    if event == _TRACE_EVENT:
        _log.open_compilation(state, kwargs.get('fun_name') or '?',
                              perf_counter())
        return
    # A lowering with no trace before it (JAX had the jaxpr cached:
    # a new sharding, a second ``lower``), or ``lowered.compile()``
    # long after its lowering: a record of the program's own.
    program = _bare(kwargs.get('fun_name'))
    if record is None or record['program'] != program or \
            (event == _LOWER_EVENT and state.lowered):
        _log.open_compilation(state, program, perf_counter())


@_guarded
def _on_duration(event: str, duration: float, **kwargs) -> None:
    """The END of a part. The thousands of inner ``jnp`` traces of a
    set-up leave by the early return: a depth and a count, no clock,
    no lock, nothing appended."""
    part = _PART_OF.get(event)
    if part is None:
        if event == _RETRIEVAL_EVENT:
            record = _log.thread_state().record
            if record is not None:
                record['retrieval_s'] += duration
            _log.add(retrieval_s=duration)
        return
    state = _log.thread_state()
    record = state.record
    outermost = state.depth <= 1 or record is None
    if outermost:  # (or the listeners came in mid-part)
        state.depth = 0
        now = perf_counter()
        if record is None:
            record = _log.open_compilation(
                state, _bare(kwargs.get('fun_name')), now - duration)
        record['end'] = now
    else:
        state.depth -= 1
        if part == 'trace_s':
            record['inner_traces'] += 1
            state.inner += 1
            return
    record[part] += duration
    amounts = {part: duration, 'inner_traces': state.inner}
    state.inner = 0
    if part == 'lower_s':
        record['lowerings'] += 1
        state.lowered = state.lowered or outermost
        amounts['lowerings'] = 1
        amounts['lowerings_after_ready'] = int(
            _log.ready_at is not None)
    _log.add(**amounts)
    if part == 'backend_s' and outermost:
        state.record = None
        if _log.ready_at is not None and record['lowerings']:
            logger.warning(
                'Program %s was lowered after ready: %.3f s (trace '
                '%.3f, lowering %.3f, backend %.3f; %s) inside a '
                'request or a step.', record['program'],
                record['trace_s'] + record['lower_s'] +
                record['backend_s'], record['trace_s'],
                record['lower_s'], record['backend_s'],
                'compiled' if record['cache_misses']
                else 'from the cache' if record['cache_hits']
                else 'no persistent cache')


@_guarded
def _on_event(event: str, **kwargs) -> None:
    del kwargs
    if event == _HIT_EVENT:
        key = 'cache_hits'
    elif event == _MISS_EVENT:
        key = 'cache_misses'
    else:
        return
    record = _log.thread_state().record
    if record is not None:
        record[key] += 1
    _log.add(**{key: 1})


def listen() -> None:
    """Register the account's listeners with ``jax.monitoring``,
    once. ``configure_compile_cache`` calls it; so does a stage, in a
    process that has imported jax without placing the cache (tests,
    a library user building an engine)."""
    if _log.listening:
        return
    import jax
    with _log.lock:
        if _log.listening:
            return
        _log.listening = True
        _log.families = _jit_families()
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


class _Stage:
    """An open stage: what ``stage`` yields (``seconds`` is set at
    exit) and what its thread's stack holds."""
    __slots__ = ('name', 'seconds', 'children_s', 'context')

    def __init__(self, name: str,
                 context: Optional[trace_lib.SpanContext]):
        self.name = name
        self.seconds = 0.0
        self.children_s = 0.0
        self.context = context  # of its span; None when untraced


@contextlib.contextmanager
def stage(name: str, **attrs: Any):
    """One named stretch of a start-up::

        with jax_runtime.stage('replica.start.weights'):
            ...

    At exit it appends a record to the start-up log: name, start and
    end on the ``perf_counter`` clock, parent stage, ``attrs``, self
    time (its seconds less its children's) and the compile account's
    change inside it (process-wide: what the engine's thread lowers
    while ``replica.start.warm`` waits for it counts). Where the
    process carries a trace context, it also emits a span
    ``startup.<name>`` from its two wall-clock instants, under the
    enclosing stage's span or else the context ambient at its entry,
    so ``xsky trace`` shows a start-up under ``job.run`` /
    ``serve.up``. A stage is never the ambient context itself: a span
    opened inside one (``train.step``, ``ckpt.save``) keeps the
    parent it would have had without it.

    For start-up, not for loops: an exit takes a lock twice and a
    dictionary's worth of arithmetic."""
    if not _log.listening and 'jax' in sys.modules:
        listen()
    stages = _log.thread_state().stages
    parent = stages[-1] if stages else None
    parent_context = parent.context if parent is not None \
        else trace_lib.current()
    this = _Stage(name, trace_lib.child_context(parent_context))
    stages.append(this)
    before = _log.totals()
    start_wall = wall_clock()
    start = perf_counter()
    failed = False
    try:
        yield this
    except BaseException:
        failed = True
        raise
    finally:
        end = perf_counter()
        seconds = this.seconds = end - start
        after = _log.totals()
        delta = {k: after[k] - before[k] for k in _TOTAL_KEYS}
        stages.remove(this)
        if parent is not None:
            parent.children_s += seconds
        record = {'name': name, 'start': start, 'end': end,
                  'seconds': seconds,
                  'self_s': seconds - this.children_s,
                  'parent': parent.name if parent else None,
                  'attrs': dict(attrs),
                  'thread': threading.current_thread().name, **delta}
        with _log.lock:
            _log.stages.append(record)
        if this.context is not None:
            trace_lib.emit_span(
                this.context, parent_context, SPAN_PREFIX + name,
                start_wall, start_wall + seconds,
                attrs={**attrs, **{k: round(v, 6)
                                   for k, v in delta.items() if v}},
                status='ERROR' if failed else 'OK')


def mark_ready() -> None:
    """The start-up is over (the replica prints ``ready``, the first
    train step has returned): from here on a lowering stalls a
    request or a step, so each one adds to
    ``skytpu_jit_lowerings_after_ready_total`` and logs one WARNING
    with the program's name and its seconds."""
    if _log.ready_at is None:
        _log.ready_at = perf_counter()


def startup_log() -> Dict[str, Any]:
    """A copy of the log: ``stages`` in order of their ends,
    ``compilations`` in order of their starts (a record whose
    backend part has not ended yet is still growing), the running
    ``totals``, and ``ready_at`` (None before ``mark_ready``). Every
    instant is on this process's ``time.perf_counter()`` clock."""
    return _log.copy()


def startup_seconds() -> Dict[str, float]:
    """``{stage name: seconds}``, a stage entered more than once
    summed: the ``startup`` key of the readiness reply and of
    ``finetune``'s ``runtime`` line."""
    out: Dict[str, float] = {}
    with _log.lock:
        for record in _log.stages:
            out[record['name']] = round(
                out.get(record['name'], 0.0) + record['seconds'], 3)
    return out


def runtime_facts() -> Dict[str, Any]:
    """What this process compiled and what it holds on the device so
    far: compile-cache misses (= executables really compiled) and
    hits, both from the compile account, the cache directory, and
    per-device memory (absent on backends without ``memory_stats``,
    e.g. the CPU)."""
    import jax

    from skypilot_tpu.metrics import device as device_metrics
    totals = _log.totals()
    return {
        'compiled': totals['cache_misses'],
        'cache_hits': totals['cache_hits'],
        'cache_dir': jax.config.jax_compilation_cache_dir,
        'memory': device_metrics.sample_device_memory(),
    }
