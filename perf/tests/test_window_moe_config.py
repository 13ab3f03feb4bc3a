"""The window-and-experts configuration
(``command-a-plus-int8-serve-ep8``) as data and as a cell with teeth.
Its CPU rehearsal is ``test_rehearsal``'s, which walks every cell of
BENCHMARK.json. Here, at the rehearsal size and through the harness's
own driver:

- the file holds the catalog row's published keys at its top level,
  ``reduced`` lists exactly the depth, the experts held and the
  vocabulary, with the published counts beside them, and ``model``
  repeats what the harness and the reference read;
- the traffic files hold ISSUE 35's table, number for number, and
  offer every seed the same work;
- the control (int4 weights where int8 is stated) is not correct on
  three seeds, by the limit the cell runs under;
- two broken paths each end a whole run with ``correct`` false: the
  window bound left out; a held expert's part left out;
- the decode step's bytes by hand, the sizing arithmetic, and a
  program that lacks the model failing at once.
"""
import json
import os
import time

import pytest

from perf.costs import window_moe_decode_step
from perf.lib import harness
from perf.lib import loadgen

_CELL = 'serve-longdoc-backlog'
_NAME = 'command-a-plus-int8-serve-ep8'
_CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
_REDUCED = {'num_hidden_layers': (32, 8), 'num_experts': (128, 16),
            'vocab_size': (262144, 32768)}


def _file():
    return harness.load_json(harness.PERF_DIR, 'configs',
                             _NAME + '.json')


def test_reduced_lists_exactly_depth_experts_held_and_vocabulary(
        cell_stands_in_its_lists):
    config = _file()
    assert sorted(config['reduced']) == sorted(_REDUCED)
    for key, (published, here) in _REDUCED.items():
        assert config[key] == config['model'][key] == here, key
        assert config['published'][key] == \
            config['model']['published'][key] == published, key
    for key, value in config['model'].items():
        if key not in ('published', 'experts_first'):
            assert config[key] == value, key
    bench = harness.load_json(harness.REPO_DIR, 'BENCHMARK.json')
    entry = {c['name']: c for c in bench['configs']}[_NAME]
    assert sorted(entry['reduced']) == sorted(_REDUCED)
    assert entry['source'] == config['source']
    cell = {w['name']: w for w in bench['workloads']}[_CELL]
    # The form BENCHMARK.json is held to before any run.
    assert all(1 <= len(e['why']) <= 200 for e in (entry, cell))
    # No width among them.
    assert not any(k.endswith(('_dim', '_rank', '_size'))
                   and k != 'vocab_size' for k in config['reduced'])
    # The cell's name stands in the lists of the families it reports
    # (one entry a family and judged metric since PR 45), and its own
    # three entries exist.
    families = {
        'decode_step_ms.backlog', 'prefill_chunk_ms.backlog',
        'iter_ms.backlog', 'iter_host_gap_ms.backlog',
        'prefill_chunks_per_iter.backlog', 'prefill_real_pct.backlog',
        'prefix_hit_pct.backlog', 'decode_view_pct.backlog',
        'decode_walk_read_pct.backlog',
        'engine_idle_schedule_ms.backlog',
        'engine_idle_prefill_ms.backlog',
        'engine_idle_dispatch_ms.backlog',
        'engine_idle_emit_ms.backlog', 'tokens_per_dispatch',
        'slots_occupied_mean', 'kv_blocks_used_peak_pct',
        'moe_experts_hit_pct', 'moe_busiest_over_mean',
        'moe_tiled_pairs_pct'}
    own = {'moe_held_share_pct', 'kv_window_blocks_peak_pct',
           'window_moe_decode_hbm_roofline'}
    cell_stands_in_its_lists(_CELL, families, own)


@pytest.mark.skipif(not os.path.exists(_CATALOG),
                    reason='the catalog is not on this machine')
def test_top_level_holds_every_published_key():
    with open(_CATALOG) as f:
        rows = [json.loads(line) for line in f]
    entry = next(r for r in rows
                 if r['name'] == 'command-a-plus-05-2026')
    config = _file()
    assert config['source'] == entry['source_url']
    for key, value in entry['config'].items():
        if key in _REDUCED:
            assert value == _REDUCED[key][0], key
        else:
            assert config[key] == value, key


def test_sizing_arithmetic_of_the_file():
    from perf.tools import size_window_moe_serve as sizing
    build, model = _file()['build'], _file()['model']
    bs, slots = build['block_size'], build['slots']
    # Global: 4 documents of 512 blocks held once + a row's own p95
    # context past its document (2,238 tokens = 140 blocks) + scratch.
    assert build['num_blocks'] == 4 * 512 + slots * 140 + 1
    # Window: the documents' tails held once (the rows share them)
    # + the same own blocks a row (+ 1), which lie under what a row
    # can hold (its window's blocks, the chunk in flight, 1).
    assert model['sliding_window'] // bs + 1 + \
        build['prefill_chunk'] // bs + 1 == 290 > 141
    assert build['window_num_blocks'] == 4 * 257 + slots * 141 + 1
    assert (build['num_blocks'], build['window_num_blocks']) == \
        sizing.group_blocks(_file(), slots, 2048, 140, 1028)
    assert slots % 8 == 0
    spec = loadgen.load_traffic('longdoc-backlog')
    longest = spec['prompt_len']['max'] + spec['output_len']['max']
    assert longest == 11776 < build['max_seq'] == 12288


def test_traffic_files_hold_the_issues_table():
    base = harness.load_json(harness.PERF_DIR, 'traffic',
                             'longdoc.json')
    cell = harness.load_json(harness.PERF_DIR, 'traffic',
                             'longdoc-backlog.json')
    assert (base['shared_prompts'], base['shared_len'],
            base['shared_zipf_s']) == (4, 8192, 1.0)
    assert base['prompt_len'] == {'median': 8704, 'sigma': 0.06,
                                  'min': 8320, 'max': 10240}
    assert base['output_len'] == {'median': 512, 'sigma': 0.6,
                                  'min': 128, 'max': 1536}
    assert base['deal_block'] == 10 and base['trace_seconds'] == 5.0
    assert (cell['extends'], cell['kind']) == ('longdoc', 'backlog')
    assert (cell['n_requests'], cell['lead_s'], cell['window_edges'],
            cell['trace_start_s']) == (640, 30.0, 'bursts', 20.0)


def test_longdoc_backlog_offers_the_same_work_to_every_seed():
    spec = loadgen.load_traffic('longdoc-backlog')
    a, b = (loadgen.generate_backlog(spec, seed, 51, 32768)
            for seed in (1, 2**31 + 3))
    assert len(a) == len(b) == 640
    assert [(len(r['prompt']), r['max_new'], r['shared'])
            for r in a] == [(len(r['prompt']), r['max_new'],
                             r['shared']) for r in b]
    assert [r['prompt'] for r in a] != [r['prompt'] for r in b]
    assert all(0 <= t < 32768 for r in a[:8] for t in r['prompt'])
    # Every question has 128-2,048 tokens of its own, median 512.
    own = sorted(len(r['prompt']) - 8192 for r in a)
    assert own[0] == 128 and own[-1] == 2048 and \
        own[319] + own[320] == 2 * 512
    # One document a request, four in all, the first the hottest.
    docs = {}
    for r in a:
        docs.setdefault(r['shared'], r['prompt'][:8192])
        assert r['prompt'][:8192] == docs[r['shared']]
    assert sorted(docs) == [0, 1, 2, 3]


def test_decode_step_bytes_by_hand():
    model = _file()['model']
    got = window_moe_decode_step.window_moe_decode_step_bytes(
        model, 1, 1, rows=32, global_tokens=80_000,
        window_tokens=130_000, experts_hit_share=0.875)
    d, ffn = 4096, 4096
    attention = (2 * d * 16384 + 2 * d * 1024 +
                 2 * (16384 + 2 * 1024 + d))
    expert = 3 * d * ffn + 2 * (2 * ffn + d)
    layer = (attention + 4 * expert + d * 128 * 2 +
             16 * 0.875 * expert + d * 2)
    head = 32768 * d * 2 + 32 * d * 2
    kv = 2 * (1024 + 16) * (130_000 * 6 + 80_000 * 2)
    assert got == pytest.approx(8 * layer + head + kv)
    # ISSUE 35's arithmetic: a layer's share is 1,149.8 M parameters.
    assert (attention + 4 * expert + 16 * expert) // 10**6 == 1149


def test_a_program_without_the_model_fails_at_once():
    from perf.drivers import serve_window_moe
    config = dict(_file(), program_model='no-such-model')
    with pytest.raises(harness.HarnessError, match='no model'):
        serve_window_moe.program_config(config)
    other = dict(_file(), program_model='mixtral-8x7b')
    with pytest.raises((harness.HarnessError, ValueError)):
        serve_window_moe.program_config(other)
    prog = serve_window_moe.program_config(_file())
    assert prog.experts_held == (0, 16) and prog.n_layers == 8
    assert prog.layer_kinds == ('window',) * 3 + ('global',)


# ---------------------------------------------------------------------
# Teeth
# ---------------------------------------------------------------------


def _run(seed=11, seconds=2.0):
    loaded = harness.load_cell(_CELL, rehearse=True)
    driver = harness.driver_for(loaded['config'])
    return driver.run(loaded, seed, seconds, False, True,
                      time.perf_counter())


def _gap(out):
    return {c['name']: c for c in out['compared']}[
        'served_logit_gap_max']


@pytest.fixture
def fresh_programs():
    """The broken paths are patched in underneath the jitted steps:
    a trace cached from a sound run must not stand in for them, nor
    theirs for a later sound run."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(fresh_programs):
    out = _run()
    assert out['correct'], out['compared']
    assert out['attempted'] > 0 and out['failed'] == 0


@pytest.mark.parametrize('seed', [13, 2**31 + 5, 77])
def test_control_at_lower_precision_is_not_correct(seed):
    loaded = harness.load_cell(_CELL, rehearse=True)
    driver = harness.driver_for(loaded['config'])
    got = driver.control_readings(loaded, seed, 2.0, True)
    limit = loaded['config']['limits']['served_logit_gap_max']
    name = 'served_logit_gap_max'
    assert got['sound'][name] <= limit < got['control'][name], got


def test_the_window_bound_left_out(monkeypatch, fresh_programs):
    """Window layers that see the whole context."""
    from skypilot_tpu.ops import decode_attention as da
    real_view, real_chunk = da.view_attention, da.chunk_attention

    def wide_view(*args, **kwargs):
        if kwargs.get('window') is not None:
            kwargs['window'] = 10**6
        elif len(args) > 8 and args[8] is not None:
            args = args[:8] + (10**6,) + args[9:]
        return real_view(*args, **kwargs)

    def wide_chunk(*args, **kwargs):
        if kwargs.get('window') is not None:
            kwargs['window'] = 10**6
        return real_chunk(*args, **kwargs)

    monkeypatch.setattr(da, 'view_attention', wide_view)
    monkeypatch.setattr(da, 'chunk_attention', wide_chunk)
    # The host must then keep what the wide window reads.
    from skypilot_tpu.serve.batching import BatchingEngine
    monkeypatch.setattr(BatchingEngine, '_release_behind',
                        lambda self, row, next_pos: None)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_a_held_experts_part_left_out(monkeypatch, fresh_programs):
    """The pairs of one held expert routed as if it lived elsewhere."""
    import jax.numpy as jnp
    from skypilot_tpu.models import moe
    real = moe.route

    def without_one(config, x, router):
        weights, experts = real(config, x, router)
        first = config.experts_held[0]
        return weights, jnp.where(experts == first + 1,
                                  config.n_experts + 7, experts)

    monkeypatch.setattr(moe, 'route', without_one)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']
