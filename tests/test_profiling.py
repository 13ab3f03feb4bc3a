"""Profiling summary tests (CPU): capture_trace + summarize_trace.

Model: the reference's benchmark timing callbacks
(``sky/callbacks``/``sky bench``); this is the kernel-level analog
wired into bench.py via BENCH_PROFILE=1.
"""
import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.utils import profiling


def test_capture_and_summarize(tmp_path):
    x = jnp.ones((256, 256))

    @jax.jit
    def f(x):
        return (x @ x).sum()

    f(x).block_until_ready()  # compile outside the trace
    with profiling.capture_trace(str(tmp_path)) as tdir:
        f(x).block_until_ready()
    rows = profiling.summarize_trace(tdir, top=10, device_only=False)
    assert rows, 'expected at least one trace event'
    assert all(r.total_ms >= 0 for r in rows)
    # Descending by total time.
    totals = [r.total_ms for r in rows]
    assert totals == sorted(totals, reverse=True)
    text = profiling.format_summary(rows)
    assert 'total ms' in text and rows[0].name in text


def test_summarize_missing_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        profiling.summarize_trace(str(tmp_path / 'nope'))


def test_containers_are_not_counted_twice(tmp_path):
    """A ``while`` on a device track spans the fusions inside it,
    which the same track lists too: the summary's total is the
    fusions', not twice that. Host tracks are left as they are."""
    import gzip
    import json
    import os
    meta = [{'ph': 'M', 'name': 'process_name', 'pid': 1,
             'args': {'name': '/device:TPU:0'}},
            {'ph': 'M', 'name': 'process_name', 'pid': 2,
             'args': {'name': '/host:CPU'}}]
    device = [
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': 'while.8',
         'ts': 0, 'dur': 3000},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': 'fusion.1',
         'ts': 0, 'dur': 1000, 'args': {'hlo_category': 'fusion'}},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': 'fusion.2',
         'ts': 1000, 'dur': 2000},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': '%conditional.3',
         'ts': 3000, 'dur': 500},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': 'fusion.1',
         'ts': 3000, 'dur': 500},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': 'call.4',
         'ts': 3500, 'dur': 100},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'name': 'callback_fusion.5',
         'ts': 3500, 'dur': 100},
    ]
    host = [{'ph': 'X', 'pid': 2, 'tid': 9, 'name': 'while.8',
             'ts': 0, 'dur': 7000}]
    out = tmp_path / 'plugins' / 'profile' / 'run'
    os.makedirs(out)
    with gzip.open(out / 'host.trace.json.gz', 'wt') as f:
        json.dump({'traceEvents': meta + device + host}, f)
    rows = profiling.summarize_trace(str(tmp_path))
    assert {r.name: (r.total_ms, r.count) for r in rows} == {
        'fusion.2': (2.0, 1), 'fusion.1': (1.5, 2),
        'callback_fusion.5': (0.1, 1)}
    assert sum(r.total_ms for r in rows) == pytest.approx(3.6)
    # Not a device track: a host event that happens to be called
    # ``while.8`` is nobody's container.
    both = profiling.summarize_trace(str(tmp_path), device_only=False)
    assert ('while.8', 7.0) in [(r.name, r.total_ms) for r in both]
