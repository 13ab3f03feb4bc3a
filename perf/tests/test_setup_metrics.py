"""The start-up log cut at the window's opening
(perf/lib/startup_log.py) and the eleven ``setup_*`` readers over it,
on a made-up log with records on both sides of the opening."""
import json
import os

import pytest

from perf.lib import harness
from perf.lib import startup_log

T_START, SETUP_S = 100.0, 50.0  # so the window opens at 150.0


def _compilation(program, end, trace_s, lower_s, backend_s,
                 retrieval_s=0.0, hit=0, miss=0, lowerings=1):
    return {'program': program, 'start': end - 1.0, 'end': end,
            'trace_s': trace_s, 'lower_s': lower_s,
            'backend_s': backend_s, 'retrieval_s': retrieval_s,
            'lowerings': lowerings, 'cache_hits': hit,
            'cache_misses': miss, 'inner_traces': 7,
            'stage': None, 'thread': 'MainThread'}


def _stage(name, start, end, parent=None):
    return {'name': name, 'start': start, 'end': end,
            'seconds': end - start, 'self_s': end - start,
            'parent': parent, 'attrs': {}}


def _made_up():
    compilations = [
        _compilation('broadcast_in_dim', 104.0, 0.01, 0.02, 0.03,
                     retrieval_s=0.02, hit=1),
        _compilation('decode_steps_paged', 112.0, 0.5, 0.25, 0.125,
                     retrieval_s=0.0625, hit=1),
        _compilation('decode_steps_paged', 113.0, 0.5, 0.25, 2.0,
                     miss=1),
        _compilation('forward_paged', 120.0, 1.0, 0.5, 0.25,
                     retrieval_s=0.125, hit=1),
        # A lowering alone (no backend part): still a lowering.
        _compilation('step_fn', 130.0, 0.25, 0.25, 0.0),
        # After the opening: the reference, a second engine.
        _compilation('forward_paged', 151.0, 4.0, 4.0, 4.0, miss=1),
        _compilation('reference', 200.0, 8.0, 8.0, 8.0, miss=1),
    ]
    stages = [
        _stage('engine.build.pool', 110.0, 110.5, 'engine.build'),
        _stage('engine.build.prewarm_decode.width', 111.0, 112.0,
               'engine.build.prewarm_decode'),
        _stage('engine.build.prewarm_decode.width', 112.0, 113.5,
               'engine.build.prewarm_decode'),
        _stage('engine.build.prewarm_decode', 111.0, 114.0,
               'engine.build'),
        _stage('engine.build', 110.0, 115.0),
        # Ends exactly at the opening: counted.
        _stage('test.at_the_opening', 149.0, 150.0),
        # A second engine, after the window (a control run).
        _stage('engine.build', 160.0, 170.0),
    ]
    return {'stages': stages, 'compilations': compilations,
            'totals': {}, 'ready_at': None}


@pytest.fixture
def records():
    return {'startup_log': _made_up(), 't_process_start': T_START,
            'e2e': {'setup_s': SETUP_S}}


def test_the_cut_keeps_what_ended_by_the_opening(records):
    log = startup_log.cut(records)
    assert (log['t_start'], log['t_open']) == (100.0, 150.0)
    assert [c['end'] for c in log['compilations']] == [
        104.0, 112.0, 113.0, 120.0, 130.0]
    assert [s['name'] for s in log['stages']].count(
        'engine.build') == 1
    assert log['stages'][-1]['name'] == 'test.at_the_opening'
    # The log it was handed is left whole.
    assert len(records['startup_log']['compilations']) == 7


def test_no_log_no_reading(records):
    """The parent commit's program keeps no log; a run that reports
    no ``setup_s`` has no opening to cut at."""
    assert startup_log.cut({'startup_log': _made_up(),
                            't_process_start': T_START,
                            'e2e': {}}) is None
    from skypilot_tpu.utils import jax_runtime
    read = jax_runtime.__dict__.pop('startup_log')
    try:
        assert startup_log.cut({'e2e': {'setup_s': 1.0},
                                't_process_start': 0.0}) is None
        assert startup_log.stage_seconds(
            {'e2e': {'setup_s': 1.0}}, 'engine.build') is None
        assert startup_log.compile_total(
            {'e2e': {'setup_s': 1.0}}, 'lowerings') is None
    finally:
        jax_runtime.startup_log = read


def test_the_running_programs_own_log_is_read(capsys, monkeypatch):
    """Without the test's two keys the helper reads the program's
    log and ``__main__``'s start instant (absent under pytest: None,
    no raise, and one line on stderr for the eleven readers)."""
    monkeypatch.setattr(startup_log, '_said', set())
    assert startup_log.cut({'e2e': {'setup_s': 1.0}}) is None
    assert startup_log.cut({'e2e': {'setup_s': 2.0}}) is None
    said = capsys.readouterr()
    assert said.out == '' and said.err.count('\n') == 1
    assert '_T_PROCESS_START' in said.err
    log = startup_log.cut({'e2e': {'setup_s': 1e12},
                           't_process_start': 0.0})
    assert set(log) == {'stages', 'compilations', 't_start',
                        't_open'}


_EXPECTED = {
    'setup_before_engine_s': 10.0,
    'setup_engine_build_s': 5.0,
    'setup_prewarm_decode_s': 3.0,
    'setup_jit_decode_s': 3.625,
    'setup_jit_prefill_s': 1.75,
    'setup_jit_trace_s': 2.26,
    'setup_jit_lower_s': 1.27,
    'setup_jit_backend_s': 2.405,
    'setup_cache_retrieval_s': 0.2075,
    'setup_lowerings': 5.0,
    'setup_cache_misses': 1.0,
}


@pytest.mark.parametrize('name', sorted(_EXPECTED))
def test_each_reader_on_the_made_up_log(name, records):
    reduce = harness.reader_for(name, harness.PERF_DIR)
    assert reduce(None, records) == pytest.approx(_EXPECTED[name])
    # Where the program keeps no such stage or program (a training
    # cell has no engine), nothing is read and nothing raises.
    empty = dict(records, startup_log={'stages': [],
                                       'compilations': []})
    per_program = name in ('setup_before_engine_s',
                           'setup_engine_build_s',
                           'setup_prewarm_decode_s',
                           'setup_jit_decode_s',
                           'setup_jit_prefill_s')
    assert reduce(None, empty) == (None if per_program else 0.0)


def test_the_benchmark_lists_the_eleven():
    bench = harness.load_json(harness.REPO_DIR, 'BENCHMARK.json')
    mine = [m for m in bench['per_layer'] if m['moves'] == 'setup_s']
    assert sorted(m['name'] for m in mine) == sorted(_EXPECTED)
    cells = [w['name'] for w in bench['workloads']]
    serving = [c for c in cells if c.startswith('serve-')]
    rounds = {c: harness.load_cell(c)['config']['build'].get(
        'speculative') == 'mtp' for c in serving}
    for m in mine:
        assert (m['source'], m['better'], m['layer']) == (
            'program_counter', 'lower', 'start-up')
        engine_only = m['name'] in (
            'setup_before_engine_s', 'setup_engine_build_s',
            'setup_prewarm_decode_s', 'setup_jit_decode_s',
            'setup_jit_prefill_s')
        expected = serving if engine_only else cells
        if m['name'] == 'setup_jit_decode_s':
            # An engine that drafts with the model's own module
            # decodes through ``mtp_rounds_paged`` and never compiles
            # ``decode_steps_paged``: the reader finds nothing there.
            expected = [c for c in serving if not rounds[c]]
        assert m['workloads'] == expected
        assert os.path.exists(os.path.join(
            harness.PERF_DIR, 'layer_metrics', m['name'] + '.py'))
    json.dumps(mine)
