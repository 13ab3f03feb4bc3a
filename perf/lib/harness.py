"""What every driver shares: the cell's files found by name, the
device check, the count of compilations, the profiler window, the
per-layer readers and the result line."""
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, NoReturn, Optional

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(PERF_DIR)
ANNOTATION_PREFIX = 'perf.'
# One lowering per compile request, whether the persistent cache
# then hits or not: any of these inside the window is a stall.
_LOWERING_EVENT = '/jax/core/compile/jaxpr_to_mlir_module_duration'


class HarnessError(Exception):
    """The run cannot give a result (no chip, bad cell, ...): exit
    code other than 0 and no result line."""


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool = False,
              root: str = REPO_DIR) -> Dict[str, Any]:
    """Everything data says about a cell: its entry, its
    configuration file, its traffic mix, and the metrics it reports.
    Nothing here names a cell, a configuration or a metric. With
    ``rehearse`` the configuration's ``rehearsal`` section overrides
    the model, the build, the mix and any other key of the file: the
    tiny size a CPU can walk."""
    from perf.lib import loadgen
    bench = load_json(root, 'BENCHMARK.json')
    cells = {w['name']: w for w in bench['workloads']}
    if workload not in cells:
        raise HarnessError(f'no workload {workload!r} in BENCHMARK.json;'
                           f' it has {sorted(cells)}')
    cell = cells[workload]
    cfg_entry = {c['name']: c for c in bench['configs']}[cell['config']]
    config = load_json(root, cfg_entry['file'])
    perf_dir = os.path.join(root, bench['paths'][0])
    traffic = loadgen.load_traffic(
        cell['traffic'], os.path.join(perf_dir, 'traffic'))
    if rehearse:
        tiny = config['rehearsal']
        config = dict(config, **tiny.get('config', {}))
        config['model'] = dict(config['model'], **tiny['model'])
        config['build'] = dict(config['build'], **tiny['build'])
        config['program_model'] = tiny['program_model']
        traffic.update(tiny['traffic'])

    def mine(metric: Dict[str, Any]) -> bool:
        return workload in metric.get('workloads', [workload])

    def moved_here(metric: Dict[str, Any], e2e_names) -> bool:
        # Without a ``workloads`` key a per-layer metric belongs to
        # every cell that reports the end-to-end metric it moves.
        if 'workloads' in metric:
            return workload in metric['workloads']
        return metric['moves'] in e2e_names

    e2e = [m for m in bench['end_to_end'] if mine(m)]
    names = {m['name'] for m in e2e}
    per_layer = [m for m in bench['per_layer']
                 if moved_here(m, names)]
    return {'cell': cell, 'config': config, 'traffic': traffic,
            'end_to_end': e2e, 'per_layer': per_layer,
            'perf_dir': perf_dir, 'bench': bench}


def driver_for(config: Dict[str, Any]):
    return importlib.import_module('perf.drivers.' + config['driver'])


def reference_for(config: Dict[str, Any]):
    return importlib.import_module('perf.reference.' +
                                   config['reference'])


def program_config(config: Dict[str, Any]):
    """The program's own model config, checked against the file's
    published widths: the file says what is run."""
    from skypilot_tpu.models import llama
    name = config['program_model']
    prog = llama.get_config(name)
    model = config['model']
    got = {'hidden_size': prog.dim,
           'intermediate_size': prog.ffn_hidden,
           'num_hidden_layers': prog.n_layers,
           'num_attention_heads': prog.n_heads,
           'num_key_value_heads': prog.n_kv_heads,
           'vocab_size': prog.vocab_size,
           'rope_theta': prog.rope_theta,
           'rms_norm_eps': prog.norm_eps}
    wrong = {k: (model[k], v) for k, v in got.items()
             if model[k] != v}
    if wrong:
        raise HarnessError(
            f'the program\'s {name!r} differs from the configuration '
            f'file (file, program): {wrong}')
    return prog


def require_devices(chips: int, rehearse: bool) -> Dict[str, Any]:
    """The device as JAX reports it. Without the rehearsal flag a run
    needs a TPU with at least ``chips`` chips, and fails otherwise."""
    import jax
    devices = jax.devices()
    facts = {'platform': devices[0].platform,
             'kind': devices[0].device_kind, 'count': len(devices)}
    if rehearse:
        if len(devices) < chips:
            raise HarnessError(
                f'rehearsal needs {chips} (virtual) devices, found '
                f'{len(devices)}: set XLA_FLAGS='
                f'--xla_force_host_platform_device_count={chips}')
        return facts
    if facts['platform'] != 'tpu':
        raise HarnessError(
            f'no accelerator: JAX found platform '
            f'{facts["platform"]!r}; a measured run needs a TPU '
            f'(--rehearse-cpu walks the cell on the CPU, without metrics)')
    if len(devices) < chips:
        raise HarnessError(f'the cell needs {chips} chips, JAX found '
                           f'{len(devices)}')
    return facts


class CompileCounter:
    """Counts lowerings; ``inside`` is the count between ``open`` and
    ``close``, which has to be zero."""

    def __init__(self):
        import jax
        self.total = 0
        self._at_open: Optional[int] = None
        self.inside: Optional[int] = None
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        del duration, kwargs
        if event == _LOWERING_EVENT:
            self.total += 1

    def open(self) -> None:
        self._at_open = self.total

    def close(self) -> None:
        self.inside = self.total - self._at_open


class TraceWindow:
    """The profiler over a stretch of the measured window, into a
    directory inside the checkout that the run empties first."""

    def __init__(self, name: str):
        import shutil
        self.dir = os.path.join(PERF_DIR, '.traces', name)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.seconds: Optional[float] = None
        self._t0: Optional[float] = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)
        self._t0 = time.perf_counter()
        # The traced stretch's edges on the trace's own clock
        # (trace_reduce.traced_stretch): a program running at either
        # is a call cut short, and is not counted as a whole one.
        with annotate('trace_on'):
            pass

    def stop(self) -> None:
        import jax
        with annotate('trace_off'):
            pass
        self.seconds = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def load(self) -> Dict[str, Any]:
        from perf import trace_reduce
        return trace_reduce.load(trace_reduce.find_xplane(self.dir))


def annotate(name: str):
    """A span on the profiler's clock around one of the harness's own
    calls, so that an idle gap can be put down to what the host did."""
    import jax
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


def executable_bytes(compiled) -> Optional[int]:
    """What one device holds while ``compiled`` runs, by the
    compiler's own account: arguments, outputs that alias no
    argument, and temporaries."""
    try:
        m = compiled.memory_analysis()
    except Exception as e:  # pylint: disable=broad-except
        say(f'memory_analysis() failed: {type(e).__name__}: {e}')
        return None
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes -
               m.alias_size_in_bytes + m.temp_size_in_bytes)


def memory_peak_bytes(executables=()) -> Optional[int]:
    """Peak bytes on the fullest chip. The allocator's
    ``peak_bytes_in_use`` counts live buffers and, on this runtime,
    leaves out what an executable takes for its temporaries while it
    runs (PR 21), so the window's own ``executables``, where the
    driver can hand them over, are counted by ``executable_bytes``
    and the larger reading stands. Both are printed."""
    import jax
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if 'peak_bytes_in_use' in stats:
            peaks.append(int(stats['peak_bytes_in_use']))
    if not peaks:
        return None
    held = [b for b in map(executable_bytes, executables)
            if b is not None]
    say(f'memory: allocator peak_bytes_in_use {max(peaks)}; '
        f"the window's executables hold {held or 'not read'}")
    return max(peaks + held)


def reader_for(name: str, perf_dir: str) -> Callable:
    """The reader of per-layer metric ``name``, as a function of
    ``(trace, records)``: ``layer_metrics/<name>.py`` (a function
    ``reduce(trace, records)``) where that file exists, else the
    generic reader of ``perf/lib/readers.py`` that
    ``layer_metrics/<name>.json`` names, with its parameters. A
    quantity split by the end-to-end metric it moves
    (``decode_step_ms.backlog``, ``decode_step_ms.steady``) shares
    the reader of the name before the last dot."""
    for stem in dict.fromkeys([name, name.rpartition('.')[0] or name]):
        own = os.path.join(perf_dir, 'layer_metrics', stem + '.py')
        if os.path.exists(own):
            loader = importlib.util.spec_from_file_location(
                'perf_layer_metric_' + stem.replace('.', '_'), own)
            mod = importlib.util.module_from_spec(loader)
            loader.loader.exec_module(mod)
            return mod.reduce
        spec_path = os.path.join(perf_dir, 'layer_metrics',
                                 stem + '.json')
        if os.path.exists(spec_path):
            from perf.lib import readers
            spec = load_json(spec_path)
            fn = getattr(readers, spec['reader'])
            params = spec.get('params', {})
            return lambda trace, records: fn(params, trace, records)
    raise HarnessError(
        f'per-layer metric {name!r} has neither layer_metrics/'
        f'{name}.py nor layer_metrics/{name}.json')


def read_layer_metrics(loaded: Dict[str, Any], trace,
                       records: Dict[str, Any]
                       ) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell whose reader finds
    something to read; one that finds nothing is left out."""
    out = {}
    for metric in loaded['per_layer']:
        value = reader_for(metric['name'], loaded['perf_dir'])(
            trace, records)
        if value is not None and math.isfinite(value):
            out[metric['name']] = {'value': float(value),
                                   'unit': metric['unit']}
    return out


def say(*parts: Any) -> None:
    """An earlier line of the output (never the last one)."""
    print(*parts, flush=True)


def compared(name: str, value: float, limit: float,
             results: List[Dict[str, Any]]) -> bool:
    """Print one number compared beside its limit and note whether it
    held. An exact comparison has the limit 0."""
    ok = bool(value <= limit)
    results.append({'name': name, 'value': float(value),
                    'limit': float(limit), 'ok': ok})
    say(compared_line(results[-1]))
    return ok


def compared_line(c: Dict[str, Any]) -> str:
    return (f'compare {c["name"]}: value {c["value"]:.6g} limit '
            f'{c["limit"]:.6g} -> {"ok" if c["ok"] else "NOT CORRECT"}')


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None,
                compared_numbers: Optional[List[Dict[str, Any]]] = None
                ) -> str:
    """The last line of standard output. ``compared`` comes last in
    it: each number that decided ``correct`` beside its limit."""
    line = {'correct': bool(correct), 'attempted': int(attempted),
            'failed': int(failed), 'metrics': metrics,
            'device': device}
    if breakdown is not None:
        line['breakdown'] = breakdown
    if compared_numbers is not None:
        line['compared'] = {
            c['name']: {'value': c['value'], 'limit': c['limit']}
            for c in compared_numbers}
    return json.dumps(line)


def fail(message: str) -> NoReturn:
    print(f'perf.run: {message}', file=sys.stderr, flush=True)
    sys.exit(2)
