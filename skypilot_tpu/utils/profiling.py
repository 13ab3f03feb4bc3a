"""Step profiling: capture a ``jax.profiler`` trace and summarize
device-side op time — as a library AND as an on-demand runtime
service.

The reference ships Chrome-trace profiling hooks around its benchmark
harness (``sky bench`` timing callbacks; this module is the TPU-native
equivalent wired into ``bench.py`` via ``BENCH_PROFILE=1``). The
summary aggregates the XLA trace-event stream per op name so kernel
regressions show up as a diffable table instead of a 100 MB pprof
blob.

Library usage::

    with capture_trace() as tmpdir:
        run_steps()
    for row in summarize_trace(tmpdir, top=20):
        print(row)

Runtime service (docs/observability.md, On-demand profiling): the
host agent's ``POST /profile`` writes a TRIGGER file under the
shared profile dir; instrumented loops
(``parallel.instrument_train_step``, the serve batching engine) poll
for it via :class:`StepProfiler` and, when armed, capture the next N
steps with ``jax.profiler`` and write the op-time summary JSON next
to the trigger. ``xsky profile CLUSTER`` arms the capture, fetches
the summary through the agent, renders the table, and ``--diff``
shows per-op deltas against the previous fetch.
"""
import collections
import contextlib
import glob
import gzip
import json
import os
import re
import tempfile
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional


class OpTime(NamedTuple):
    name: str
    total_ms: float
    count: int
    category: str


@contextlib.contextmanager
def capture_trace(trace_dir: Optional[str] = None) -> Iterator[str]:
    """Context manager: profile the enclosed device work.

    Yields the directory the trace is written into. The caller must
    ``jax.block_until_ready`` its outputs inside the context or the
    device timeline will be truncated.
    """
    import jax

    out = trace_dir or tempfile.mkdtemp(prefix='xsky_trace_')
    with jax.profiler.trace(out):
        yield out


def _trace_files(trace_dir: str) -> List[str]:
    return sorted(glob.glob(
        os.path.join(trace_dir, '**', '*.trace.json.gz'),
        recursive=True))


# A loop, branch or call: its device event spans the operations
# inside it, which the same track lists too.
_CONTAINER_OP = re.compile(r'%?(while|conditional|call)\b')


def summarize_trace(trace_dir: str, top: int = 25,
                    device_only: bool = True) -> List[OpTime]:
    """Aggregate complete ('X') trace events by op name, descending
    total duration. ``device_only`` keeps TPU/GPU tracks and drops
    host threads. Container operations on device tracks (``while``,
    ``conditional``, ``call``) are left out: their time is their
    children's, and counting both would count it twice."""
    files = _trace_files(trace_dir)
    if not files:
        raise FileNotFoundError(
            f'no *.trace.json.gz under {trace_dir}')
    agg = collections.defaultdict(lambda: [0.0, 0, ''])
    for path in files:
        with gzip.open(path, 'rt') as f:
            trace = json.load(f)
        events = trace.get('traceEvents', [])
        pids = {}
        for ev in events:
            if ev.get('ph') == 'M' and ev.get('name') == 'process_name':
                pids[ev['pid']] = ev.get('args', {}).get('name', '')
        for ev in events:
            if ev.get('ph') != 'X':
                continue
            pname = pids.get(ev.get('pid'), '')
            on_device = 'TPU' in pname or 'GPU' in pname.upper()
            if device_only and not on_device:
                continue
            if on_device and _CONTAINER_OP.match(ev['name']):
                continue
            a = agg[ev['name']]
            a[0] += ev.get('dur', 0) / 1e3  # us -> ms
            a[1] += 1
            if not a[2]:
                a[2] = ev.get('args', {}).get('hlo_category', '')
    rows = [OpTime(name, ms, n, cat)
            for name, (ms, n, cat) in agg.items()]
    rows.sort(key=lambda r: -r.total_ms)
    return rows[:top]


def format_summary(rows: List[OpTime]) -> str:
    lines = [f'{"total ms":>10}  {"count":>6}  {"category":<22} name']
    for r in rows:
        lines.append(f'{r.total_ms:10.1f}  {r.count:6d}  '
                     f'{r.category:<22} {r.name}')
    return '\n'.join(lines)


# ---------------------------------------------------------------------
# On-demand runtime profiling service.
#
# Protocol (shared with BOTH host agents — pure files, so the C++
# agent and even the standalone k8s-bootstrap agent speak it without
# importing this module):
#   <profile_dir>/trigger.json   {"steps": N, "requested_at": ts}
#       written by the agent's POST /profile (or xsky profile's
#       put_file fallback); CONSUMED (unlinked) by the first
#       instrumented loop that sees it.
#   <profile_dir>/latest.json    the most recent op-time summary
#       {"kind", "steps", "captured_at", "rows": [...]} — written
#       atomically; fetched by `xsky profile` via the agent's /read.
# ---------------------------------------------------------------------

TRIGGER_FILE = 'trigger.json'
LATEST_SUMMARY = 'latest.json'
DEFAULT_PROFILE_STEPS = 5
# How often an instrumented loop stats the trigger file. Time-based,
# not step-count-based: a 50 ms decode dispatch must not stat 20x/s,
# and a 30 s train step must not add 30 s of arming latency.
TRIGGER_CHECK_SECONDS = 1.0


def profile_dir(base: Optional[str] = None) -> str:
    """The profile exchange directory shared by the host agent and
    the instrumented loops on one host: ``SKYTPU_PROFILE_DIR`` env
    override, else ``$SKYTPU_RUNTIME_DIR/profiles`` (set for every
    agent-spawned process), else ``$SKYTPU_STATE_DIR/profiles``
    (driver-local loops, tests). Mirrored in runtime/agent.py
    ``_profile_dir`` and host_agent.cc ``ProfileDir`` — keep the
    resolution order in sync."""
    if base:
        return os.path.expanduser(base)
    override = os.environ.get('SKYTPU_PROFILE_DIR')
    if override:
        return os.path.expanduser(override)
    runtime_dir = os.environ.get('SKYTPU_RUNTIME_DIR')
    if runtime_dir:
        return os.path.join(os.path.expanduser(runtime_dir),
                            'profiles')
    state_dir = os.path.expanduser(
        os.environ.get('SKYTPU_STATE_DIR', '~/.skypilot_tpu'))
    return os.path.join(state_dir, 'profiles')


def write_trigger(directory: Optional[str] = None,
                  steps: int = DEFAULT_PROFILE_STEPS) -> str:
    """Arm a capture: write the trigger file (what the py agent's
    POST /profile does; tests and local loops call it directly).
    Returns the trigger path."""
    directory = profile_dir(directory)
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, TRIGGER_FILE)
    tmp = path + '.tmp'
    with open(tmp, 'w', encoding='utf-8') as f:
        json.dump({'steps': int(steps), 'requested_at': time.time()},
                  f)
    os.replace(tmp, path)
    return path


def consume_trigger(directory: Optional[str] = None) -> Optional[int]:
    """If a trigger is armed, consume it (unlink) and return the
    requested step count; else None. Unlink-first so two loops in
    one process (train + decode) cannot both arm off one trigger."""
    directory = profile_dir(directory)
    path = os.path.join(directory, TRIGGER_FILE)
    try:
        with open(path, encoding='utf-8') as f:
            payload = json.load(f)
    except OSError:
        return None
    except ValueError:
        # Torn trigger (non-atomic /put fallback writer): drop it —
        # a permanently unparseable file must not be re-tried every
        # check interval forever.
        try:
            os.unlink(path)
        except OSError:
            pass
        return None
    try:
        os.unlink(path)
    except OSError:
        return None
    try:
        steps = int(payload.get('steps') or DEFAULT_PROFILE_STEPS)
    except (TypeError, ValueError):
        steps = DEFAULT_PROFILE_STEPS
    return max(1, steps)


def write_summary(rows: List[OpTime], kind: str, steps: int,
                  directory: Optional[str] = None) -> str:
    """Persist an op-time summary as the host's ``latest.json``
    (atomic write-then-rename: a concurrent /read fetch sees the old
    summary or the new one, never a torn file)."""
    directory = profile_dir(directory)
    os.makedirs(directory, exist_ok=True)
    payload = {
        'kind': kind,
        'steps': steps,
        'captured_at': time.time(),
        'rows': [{'name': r.name, 'total_ms': r.total_ms,
                  'count': r.count, 'category': r.category}
                 for r in rows],
    }
    path = os.path.join(directory, LATEST_SUMMARY)
    tmp = path + f'.{os.getpid()}.tmp'
    with open(tmp, 'w', encoding='utf-8') as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def load_summary(directory: Optional[str] = None
                 ) -> Optional[Dict[str, Any]]:
    path = os.path.join(profile_dir(directory), LATEST_SUMMARY)
    try:
        with open(path, encoding='utf-8') as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


class StepProfiler:
    """Per-loop hook for the on-demand profiling service.

    Call :meth:`on_step` once per train step / decode dispatch. The
    hook stats the trigger file at most once per
    ``TRIGGER_CHECK_SECONDS``; when armed it starts a
    ``jax.profiler`` trace, lets the next N steps run, then stops,
    summarizes and writes ``latest.json``. All failure modes degrade
    to "not profiling" — a broken profiler must never take down a
    training loop.
    """

    def __init__(self, kind: str, directory: Optional[str] = None):
        self.kind = kind
        self._dir = directory
        self._next_check = 0.0
        self._armed_steps = 0
        self._requested_steps = 0
        self._trace_dir: Optional[str] = None

    def on_step(self) -> None:
        if self._trace_dir is not None:
            self._armed_steps -= 1
            if self._armed_steps <= 0:
                self._finish()
            return
        now = time.monotonic()
        if now < self._next_check:
            return
        self._next_check = now + TRIGGER_CHECK_SECONDS
        steps = consume_trigger(self._dir)
        if steps is None:
            return
        try:
            import jax
            self._trace_dir = tempfile.mkdtemp(
                prefix=f'xsky_profile_{self.kind}_')
            jax.profiler.start_trace(self._trace_dir)
            self._armed_steps = steps
            self._requested_steps = steps
        except Exception:  # pylint: disable=broad-except
            self._trace_dir = None

    def _finish(self) -> None:
        trace_dir, self._trace_dir = self._trace_dir, None
        try:
            import jax
            jax.profiler.stop_trace()
            # CPU backend: no device tracks — fall back to host rows
            # so `xsky profile` works on dev boxes and in tests.
            rows = summarize_trace(trace_dir, top=40)
            if not rows:
                raise FileNotFoundError('no device rows')
        except Exception:  # pylint: disable=broad-except
            try:
                rows = summarize_trace(trace_dir, top=40,
                                       device_only=False)
            except Exception:  # pylint: disable=broad-except
                rows = []
        try:
            write_summary(rows, self.kind, self._requested_steps,
                          self._dir)
        except OSError:
            pass
        finally:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            self._armed_steps = 0


def diff_summaries(old: Dict[str, Any], new: Dict[str, Any],
                   top: int = 5) -> List[Dict[str, Any]]:
    """Top-``top`` per-op total-ms deltas between two summaries
    (largest absolute change first). Ops present on one side only
    count from/to zero — a kernel that appeared or vanished IS the
    regression story."""
    old_ms = {r['name']: float(r['total_ms'])
              for r in old.get('rows', [])}
    new_ms = {r['name']: float(r['total_ms'])
              for r in new.get('rows', [])}
    out = []
    for name in set(old_ms) | set(new_ms):
        before = old_ms.get(name, 0.0)
        after = new_ms.get(name, 0.0)
        delta = after - before
        if abs(delta) < 1e-9:
            continue
        out.append({
            'name': name,
            'old_ms': before,
            'new_ms': after,
            'delta_ms': delta,
            'delta_pct': (delta / before * 100.0) if before else None,
        })
    out.sort(key=lambda r: -abs(r['delta_ms']))
    return out[:top]


def format_diff(rows: List[Dict[str, Any]]) -> str:
    lines = [f'{"old ms":>10}  {"new ms":>10}  {"delta":>12}  name']
    for r in rows:
        pct = (f'{r["delta_pct"]:+.1f}%' if r['delta_pct'] is not None
               else 'new')
        lines.append(f'{r["old_ms"]:10.1f}  {r["new_ms"]:10.1f}  '
                     f'{r["delta_ms"]:+8.1f} {pct:>6}  {r["name"]}')
    return '\n'.join(lines)


def format_summary_payload(payload: Dict[str, Any],
                           top: int = 25) -> str:
    """Render a summary JSON (as written by ``write_summary``)."""
    rows = [OpTime(r['name'], r['total_ms'], r['count'],
                   r.get('category', ''))
            for r in payload.get('rows', [])[:top]]
    header = (f'profile kind={payload.get("kind")} '
              f'steps={payload.get("steps")} captured_at='
              f'{time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(payload.get("captured_at", 0)))}')
    return header + '\n' + format_summary(rows)
