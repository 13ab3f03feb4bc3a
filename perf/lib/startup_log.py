"""The program's start-up log (``skypilot_tpu/utils/jax_runtime.py``:
the stages of a start-up and one record per outermost compilation,
on the process's ``time.perf_counter()`` clock), cut at the measured
window's opening: what the ``setup_*`` per-layer metrics read.

After the window both drivers lower again (the reference; the train
driver's ``step_fn.lower(...).compile()`` for the memory reading),
so only records that END at or before the opening count. The opening
is ``setup_s`` past the instant ``perf/run.py`` noted at its top
(``_T_PROCESS_START`` of the running ``__main__``), which is how the
drivers compute ``setup_s`` in the first place: the same clock, the
same two instants.

A program without the log (the parent commit) gives None, and so
does every reader over it."""
import sys
from typing import Any, Dict, Optional

_COMPILE_PARTS = ('trace_s', 'lower_s', 'backend_s')


def cut_log(log: Dict[str, Any], t_start: float, t_open: float
            ) -> Dict[str, Any]:
    """``log`` (``jax_runtime.startup_log()``) without what ended
    after ``t_open``, with the two instants beside it."""
    def before(records):
        return [r for r in records if r['end'] <= t_open]
    return {'stages': before(log['stages']),
            'compilations': before(log['compilations']),
            't_start': t_start, 't_open': t_open}


_said = set()


def _say_once(message: str) -> None:
    """A log that cannot be cut drops eleven metrics: say why, once a
    process, where the run's result line does not go."""
    if message not in _said:
        _said.add(message)
        print('perf/lib/startup_log: ' + message, file=sys.stderr)


def cut(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The running program's log cut at this run's opening, or None
    where the program keeps none. A test hands a made-up log and
    start instant in as ``records['startup_log']`` and
    ``records['t_process_start']``."""
    log = records.get('startup_log')
    if log is None:
        from skypilot_tpu.utils import jax_runtime
        read = getattr(jax_runtime, 'startup_log', None)
        if read is None:
            return None
        log = read()
    t_start = records.get('t_process_start')
    if t_start is None:
        t_start = getattr(sys.modules.get('__main__'),
                          '_T_PROCESS_START', None)
    setup_s = records.get('e2e', {}).get('setup_s')
    if t_start is None or setup_s is None:
        _say_once('the program keeps a start-up log, but '
                  + ('the running __main__ has no _T_PROCESS_START '
                     '(not started as `python -m perf.run`)'
                     if t_start is None else 'records has no setup_s')
                  + ': no setup_* metric is reported.')
        return None
    return cut_log(log, t_start, t_start + setup_s)


def stage_seconds(records: Dict[str, Any], name: str
                  ) -> Optional[float]:
    """Seconds of stage ``name`` before the opening (a stage entered
    more than once summed); None where it never ran."""
    log = cut(records)
    if log is None:
        return None
    found = [s['seconds'] for s in log['stages'] if s['name'] == name]
    return sum(found) if found else None


def before_stage_seconds(records: Dict[str, Any], name: str
                         ) -> Optional[float]:
    """Seconds from the process's start to the first entry of stage
    ``name``."""
    log = cut(records)
    if log is None:
        return None
    starts = [s['start'] for s in log['stages'] if s['name'] == name]
    return min(starts) - log['t_start'] if starts else None


def compile_total(records: Dict[str, Any], *fields: str,
                  program: Optional[str] = None) -> Optional[float]:
    """Sum of ``fields`` over the compilations before the opening,
    of ``program`` alone where one is named. None where that program
    was never compiled (its cell does not run it); 0 is a reading."""
    log = cut(records)
    if log is None:
        return None
    mine = [c for c in log['compilations']
            if program is None or c['program'] == program]
    if program is not None and not mine:
        return None
    return float(sum(c[f] for c in mine for f in fields))


def jit_seconds(records: Dict[str, Any], program: str
                ) -> Optional[float]:
    """Trace + lowering + backend seconds of ``program``."""
    return compile_total(records, *_COMPILE_PARTS, program=program)
