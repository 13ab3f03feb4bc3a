"""The looped configuration (``ouro-2.6b-int8-serve``) as data and as
a cell with teeth. Its CPU rehearsal is ``test_rehearsal``'s, which
walks every cell of BENCHMARK.json. Here, at the rehearsal size and
through the harness's own driver:

- the file holds the catalog's published keys at its top level, and
  ``model`` repeats them unchanged;
- the control (int4 weights where int8 is stated) is not correct on
  three seeds, by the limit the cell runs under;
- three broken paths each end a whole run with ``correct`` false:
  three passes run where four are published; every pass reading pass
  1's KV entries; a branch norm left out;
- the step's bytes by hand, the traffic's arithmetic, and a program
  that lacks the model failing at once.
"""
import dataclasses
import json
import os
import time

import pytest

from perf.costs import looped_decode_step
from perf.lib import harness
from perf.lib import loadgen

_CELL = 'serve-reason-backlog'
_CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def _file():
    return harness.load_json(harness.PERF_DIR, 'configs',
                             'ouro-2.6b-int8-serve.json')


def test_model_group_repeats_the_top_level_keys():
    config = _file()
    for key, value in config['model'].items():
        assert config[key] == value, key
    assert config['reduced'] == []
    assert config['build']['num_blocks'] == \
        config['build']['slots'] * 55 + 1


@pytest.mark.skipif(not os.path.exists(_CATALOG),
                    reason='the catalog is not on this machine')
def test_top_level_holds_every_published_key():
    with open(_CATALOG) as f:
        rows = [json.loads(line) for line in f]
    entry = next(r for r in rows if r['name'] == 'Ouro-2.6B')
    config = _file()
    assert config['source'] == entry['source_url']
    for key, value in entry['config'].items():
        assert config[key] == value, key


def test_the_cell_stands_in_the_lists_of_its_families(
        cell_stands_in_its_lists):
    # One entry a family and judged metric since PR 45: the cell's
    # name in the family's ``workloads``, its own two entries beside.
    families = {
        'decode_step_ms.backlog', 'prefill_chunk_ms.backlog',
        'iter_ms.backlog', 'iter_host_gap_ms.backlog',
        'prefill_chunks_per_iter.backlog', 'prefill_real_pct.backlog',
        'prefix_hit_pct.backlog', 'decode_view_pct.backlog',
        'decode_walk_read_pct.backlog', 'prefill_keys_read_pct.backlog',
        'engine_idle_schedule_ms.backlog',
        'engine_idle_prefill_ms.backlog',
        'engine_idle_dispatch_ms.backlog',
        'engine_idle_emit_ms.backlog', 'tokens_per_dispatch',
        'slots_occupied_mean', 'kv_blocks_used_peak_pct'}
    own = {'loop_passes_per_token', 'looped_decode_hbm_roofline'}
    cell_stands_in_its_lists(_CELL, families, own)


def test_looped_step_bytes_by_hand():
    model = _file()['model']
    got = looped_decode_step.looped_decode_step_bytes(
        model, 1, 1, rows=12, kv_tokens=12 * 500)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    weights = 4 * 48 * layer + 2048 * 49152
    scales = 2 * (4 * 48 * (4 * 2048 + 2 * 5632 + 2048) + 49152)
    kv = 12 * 500 * 798720
    assert got == pytest.approx(weights + scales + kv + 12 * 2048 * 2)
    assert 192 * 2 * (16 * 128 + 16 * 2) == 798720


def test_reason_backlog_offers_the_same_work_to_every_seed():
    spec = loadgen.load_traffic('reason-backlog')
    runs = [loadgen.generate_backlog(spec, seed, 51, 49152)
            for seed in (1, 2**31 + 3)]
    a, b = runs
    assert len(a) == len(b) == 400
    for key in ('max_new', 'shared'):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert sorted(len(r['prompt']) for r in a) == \
        sorted(len(r['prompt']) for r in b)
    assert [r['max_new'] for r in a] == [r['max_new'] for r in b]
    assert [r['prompt'] for r in a] != [r['prompt'] for r in b]
    longest = max(len(r['prompt']) for r in a) + \
        max(r['max_new'] for r in a)
    assert longest <= _file()['build']['max_seq']
    assert all(r['due_s'] == -30.0 for r in a)


def test_a_program_without_the_model_fails_at_once():
    from perf.drivers import serve_looped
    config = dict(_file(), program_model='no-such-model')
    with pytest.raises(harness.HarnessError, match='no model'):
        serve_looped.program_config(config)
    plain = dict(_file(), program_model='mistral-7b')
    with pytest.raises(harness.HarnessError):
        serve_looped.program_config(plain)


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


def test_three_passes_run_instead_of_four(monkeypatch, fresh_programs):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import decode
    real = decode.looped_stack

    def short(config, cparams, x, layer, *args, **kwargs):
        fewer = dataclasses.replace(config,
                                    loop_passes=config.loop_passes - 1)
        served, rows = real(fewer, cparams, x, layer, *args, **kwargs)
        # The last pass's entries are written with nothing.
        rows = jax.tree.map(lambda r: jnp.concatenate(
            [r, jnp.zeros_like(r[:config.n_layers])]), rows)
        return served, rows

    monkeypatch.setattr(decode, 'looped_stack', short)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_every_pass_reading_the_first_passes_entries(
        monkeypatch, fresh_programs):
    from skypilot_tpu.models import decode
    real = decode.looped_stack

    def first_only(config, cparams, x, layer, *args, **kwargs):
        return real(config, cparams, x,
                    lambda xc, lp, entry, ad: layer(
                        xc, lp, entry % config.n_layers, ad),
                    *args, **kwargs)

    monkeypatch.setattr(decode, 'looped_stack', first_only)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_a_branch_norm_left_out(monkeypatch, fresh_programs):
    from skypilot_tpu.models import decode
    real = decode.layer_tail

    def bare(config, xc, attn, lp):
        return real(dataclasses.replace(config, sandwich_norms=False),
                    xc, attn, lp)

    monkeypatch.setattr(decode, 'layer_tail', bare)
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']
