"""The latent-cache configuration (``xing4.0-29b-a4b-int8-serve``) as
data and as a cell with teeth. Its CPU rehearsal is also
``test_rehearsal``'s, which walks every cell of BENCHMARK.json. Here,
at the rehearsal size and through the harness's own driver:

- the file holds the catalog row's published keys at its top level,
  ``reduced`` lists exactly the depth, with the published count beside
  it, and ``model`` repeats what the harness and the reference read;
- the traffic files hold ISSUE 38's table, number for number, and
  offer every seed the same work;
- the control (int4 weights where int8 is stated) is not correct on
  three seeds, by the limit the cell runs under;
- four broken paths each end a whole run with ``correct`` false: the
  rotated part of the score left out; Sinkhorn cut to one pass; the
  selection bias left out; the routed scale left out;
- the decode step's bytes by hand at the tiny size, the sizing
  arithmetic, and a program that lacks the model failing at once.
"""
import dataclasses
import json
import os
import time

import pytest

from perf.costs import latent_moe_decode_step
from perf.lib import harness
from perf.lib import loadgen

_CELL = 'serve-longdoc16k-backlog'
_NAME = 'xing4.0-29b-a4b-int8-serve'
_CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
_REDUCED = {'num_hidden_layers': (40, 10)}
# The per-layer entries the cell's name stands in (one entry a family
# and judged metric since PR 45), and the entries that are its own.
_FAMILIES = {
    'decode_step_ms.backlog', 'prefill_chunk_ms.backlog',
    'iter_ms.backlog', 'iter_host_gap_ms.backlog',
    'prefill_chunks_per_iter.backlog', 'prefill_real_pct.backlog',
    'prefix_hit_pct.backlog', 'decode_view_pct.backlog',
    'engine_idle_schedule_ms.backlog', 'engine_idle_prefill_ms.backlog',
    'engine_idle_dispatch_ms.backlog', 'engine_idle_emit_ms.backlog',
    'tokens_per_dispatch', 'slots_occupied_mean',
    'kv_blocks_used_peak_pct', 'moe_experts_hit_pct',
    'moe_busiest_over_mean', 'moe_tiled_pairs_pct'}
_OWN = {'mla_context_tokens_mean', 'mla_expanded_share_pct',
        'latent_moe_decode_hbm_roofline'}


def _file():
    return harness.load_json(harness.PERF_DIR, 'configs',
                             _NAME + '.json')


def test_reduced_lists_exactly_the_depth(cell_stands_in_its_lists):
    config = _file()
    assert config['reduced'] == list(_REDUCED)
    for key, (published, here) in _REDUCED.items():
        assert config[key] == config['model'][key] == here, key
        assert config['published'][key] == \
            config['model']['published'][key] == published, key
    for key, value in config['model'].items():
        if key != 'published':
            assert config[key] == value, key
    # The leading dense layers as published, 8 expert layers after.
    assert config['first_k_dense_replace'] == 2
    bench = harness.load_json(harness.REPO_DIR, 'BENCHMARK.json')
    entry = {c['name']: c for c in bench['configs']}[_NAME]
    assert entry['reduced'] == list(_REDUCED)
    assert entry['source'] == config['source']
    cell = {w['name']: w for w in bench['workloads']}[_CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        _NAME, 'longdoc16k-backlog', 1)
    # The form BENCHMARK.json is held to before any run.
    assert all(1 <= len(e['why']) <= 200 for e in (entry, cell))
    assert not any(k.endswith(('_dim', '_rank', '_size'))
                   for k in config['reduced'])
    judged = {m['name']: m for m in bench['end_to_end']}['out_tok_s']
    assert _CELL in judged['workloads'] and judged['bound'] == 0.03
    mine = cell_stands_in_its_lists(_CELL, _FAMILIES, _OWN)
    assert 'moe_held_share_pct' not in mine  # every expert is held


@pytest.mark.skipif(not os.path.exists(_CATALOG),
                    reason='the catalog is not on this machine')
def test_top_level_holds_every_published_key():
    with open(_CATALOG) as f:
        rows = [json.loads(line) for line in f]
    entry = next(r for r in rows if r['name'] == 'Xing4.0-29B-A4B')
    config = _file()
    assert config['source'] == entry['source_url']
    for key, value in entry['config'].items():
        if key in _REDUCED:
            assert value == _REDUCED[key][0], key
        else:
            assert config[key] == value, key
    assert set(config['assumed']) >= {
        'mixers', 'streams', 'rope_pairs', 'selection_bias',
        'next_token_module', 'latent_row', 'sizing'}


def test_sizing_arithmetic_of_the_file():
    from perf.tools import size_latent_moe_serve as sizing
    build = _file()['build']
    bs, slots = build['block_size'], build['slots']
    # 8 documents of 1,024 blocks held once + the most a row owns
    # past its document (question 2,048 + output 1,536 = 224 blocks)
    # + scratch.
    spec = loadgen.load_traffic('longdoc16k-backlog')
    own = (spec['prompt_len']['max'] - spec['shared_len'] +
           spec['output_len']['max'])
    assert own == 3584 == 224 * bs
    assert build['num_blocks'] == 8 * 1024 + slots * 224 + 1 == \
        sizing.group_blocks(slots, 8 * 1024, 224)
    assert slots % 8 == 0 and 32 <= slots <= 64
    longest = spec['prompt_len']['max'] + spec['output_len']['max']
    assert longest == 19968 < build['max_seq'] == 20480
    assert not build['kv_int8'] and not build['speculative']
    assert _file()['check_pad_to'] == [17408, 18432, 19456, 20480]


def test_traffic_files_hold_the_issues_table():
    base = harness.load_json(harness.PERF_DIR, 'traffic',
                             'longdoc16k.json')
    cell = harness.load_json(harness.PERF_DIR, 'traffic',
                             'longdoc16k-backlog.json')
    assert (base['shared_prompts'], base['shared_len'],
            base['shared_zipf_s']) == (8, 16384, 1.0)
    assert base['prompt_len'] == {'median': 16896, 'sigma': 0.03,
                                  'min': 16512, 'max': 18432}
    assert base['output_len'] == {'median': 512, 'sigma': 0.6,
                                  'min': 128, 'max': 1536}
    assert base['deal_block'] == 10 and base['trace_seconds'] == 5.0
    assert (cell['extends'], cell['kind']) == ('longdoc16k', 'backlog')
    assert (cell['n_requests'], cell['lead_s'], cell['window_edges'],
            cell['trace_start_s']) == (640, 30.0, 'bursts', 20.0)


def test_longdoc16k_backlog_offers_the_same_work_to_every_seed():
    spec = loadgen.load_traffic('longdoc16k-backlog')
    a, b = (loadgen.generate_backlog(spec, seed, 51, 131072)
            for seed in (1, 2**31 + 3))
    assert len(a) == len(b) == 640
    assert [(len(r['prompt']), r['max_new'], r['shared'])
            for r in a] == [(len(r['prompt']), r['max_new'],
                             r['shared']) for r in b]
    assert [r['prompt'][:64] for r in a] != \
        [r['prompt'][:64] for r in b]
    assert all(0 <= t < 131072 for r in a[:4] for t in r['prompt'])
    # Every question has 128-2,048 tokens of its own, median 512.
    own = sorted(len(r['prompt']) - 16384 for r in a)
    assert own[0] == 128 and own[-1] == 2048 and \
        own[319] + own[320] == 2 * 512
    # One document a request, eight in all, the first the hottest.
    docs, count = {}, {}
    for r in a:
        docs.setdefault(r['shared'], r['prompt'][:16384])
        count[r['shared']] = count.get(r['shared'], 0) + 1
        assert r['prompt'][:256] == docs[r['shared']][:256]
    assert sorted(docs) == list(range(8))
    assert count[0] == max(count.values()) == 235


def test_decode_step_bytes_by_hand_at_the_tiny_size():
    model = harness.load_cell(_CELL, rehearse=True)['config']['model']
    got = latent_moe_decode_step.latent_moe_decode_step_bytes(
        model, 1, rows=4, context_tokens=500.0,
        experts_hit_share=0.75)
    d, heads = 128, 4

    def mm(fan_in, fan_out):              # int8 + a bf16 scale a channel
        return fan_in * fan_out + 2 * fan_out

    attention = (mm(d, 32) + mm(32, heads * 48) + mm(d, 64) +
                 mm(48, heads * 64) + mm(heads * 32, d))
    every = (attention + 2 * (2 * d + 32 + 48) +
             2 * 2 * (4 * d + 1) * 24 + 12)
    dense = 2 * mm(d, 256) + mm(256, d)
    expert = 2 * mm(d, 64) + mm(64, d)
    moe = d * 8 * 2 + 8 * 2 + expert + 8 * 0.75 * expert
    head = mm(d, 512) + d * 2 + 4 * d * 2
    latent = 500.0 * 64 * 2 * 4
    assert got == pytest.approx(
        4 * every + 2 * dense + 2 * moe + head + latent)
    # ISSUE 38's arithmetic at the published widths: attention
    # 28.41 M, an expert 11.01 M, the latent row 1,152 B an entry.
    full = _file()['model']
    weights = latent_moe_decode_step.latent_moe_decode_step_bytes(
        full, 1, rows=0, context_tokens=0, experts_hit_share=1.0)
    assert round(weights / 1e8) == 67           # 6.7 GB, all experts
    one = latent_moe_decode_step.latent_moe_decode_step_bytes(
        full, 1, rows=0, context_tokens=1, experts_hit_share=1.0)
    assert one - weights == 10 * 1152


def test_a_program_without_the_model_fails_at_once():
    from perf.drivers import serve_latent_moe
    config = dict(_file(), program_model='no-such-model')
    with pytest.raises(harness.HarnessError, match='no model'):
        serve_latent_moe.program_config(config)
    other = dict(_file(), program_model='mistral-7b')
    with pytest.raises(harness.HarnessError, match='no model'):
        serve_latent_moe.program_config(other)
    wrong = dict(_file(), model=dict(_file()['model'], hc_mult=2))
    with pytest.raises(harness.HarnessError, match='hc_mult'):
        serve_latent_moe.program_config(wrong)
    prog = serve_latent_moe.program_config(_file())
    assert prog.n_layers == 10 and prog.dense_first == 2
    assert prog.layer_kinds == ('latent',) and prog.n_experts == 64


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


def _with_program(monkeypatch, **overrides):
    """The driver's program with some keys of its configuration
    changed AFTER the check against the file: a program that says it
    runs the file's model and computes another."""
    from perf.drivers import serve_latent_moe
    real = serve_latent_moe.program_config
    monkeypatch.setattr(
        serve_latent_moe, 'program_config',
        lambda config: dataclasses.replace(real(config), **overrides))


def test_the_rotated_score_term_left_out(monkeypatch, fresh_programs):
    """q_pe . k_pe missing from the scores, in both forms."""
    import jax.numpy as jnp
    from skypilot_tpu.ops import decode_attention as da
    absorbed, expanded = (da.latent_decode_attention,
                          da.latent_chunk_attention)
    monkeypatch.setattr(
        da, 'latent_decode_attention',
        lambda q_lat, q_pe, *a, **k: absorbed(
            q_lat, jnp.zeros_like(q_pe), *a, **k))
    monkeypatch.setattr(
        da, 'latent_chunk_attention',
        lambda q_nope, q_pe, *a, **k: expanded(
            q_nope, jnp.zeros_like(q_pe), *a, **k))
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_sinkhorn_cut_to_one_pass(monkeypatch, fresh_programs):
    _with_program(monkeypatch, hc_sinkhorn_iters=1)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_the_selection_bias_left_out(monkeypatch, fresh_programs):
    from skypilot_tpu.models import moe
    real = moe.route
    monkeypatch.setattr(
        moe, 'route',
        lambda config, x, router, bias=None: real(config, x, router))
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_the_routed_scale_left_out(monkeypatch, fresh_programs):
    _with_program(monkeypatch, moe_routed_scale=1.0)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']
