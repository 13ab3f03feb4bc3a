"""The configuration whose own next-token-prediction module drafts
(``joyai-llm-flash-int8-serve-mtp``) as data and as a cell with
teeth. Its CPU rehearsal is also ``test_rehearsal``'s, which walks
every cell of BENCHMARK.json. Here, at the rehearsal size and through
the harness's own driver:

- the file holds the catalog row's published keys at its top level,
  ``reduced`` lists exactly the depth, with the published count beside
  it, and ``model`` repeats what the harness and the reference read;
- the traffic files hold ISSUE 43's table, number for number, offer
  every seed the same work, and every request is sampled under a seed
  of its own;
- the reference's Gumbel argmax is ``jax.random.categorical`` under
  the installed jax, key for key with the program's;
- the requests checked are the mix's, whatever finished;
- the control (int4 weights where int8 is stated) is not correct on
  three seeds, by the limit the cell runs under, and two broken paths
  each end a whole run with ``correct`` false: the routed scale left
  out; a token drawn under another position's key;
- the round's bytes by hand at the tiny size, the sizing arithmetic,
  and a program that lacks the model failing at once.
"""
import dataclasses
import json
import os
import time

import numpy as np
import pytest

from perf.costs import latent_mtp_round
from perf.lib import harness
from perf.lib import loadgen

_CELL = 'serve-reason2k-mtp-backlog'
_NAME = 'joyai-llm-flash-int8-serve-mtp'
_CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
_REDUCED = {'num_hidden_layers': (40, 8)}
# The per-layer entries the cell's name stands in (one entry a family
# and judged metric since PR 45; the last ten are the readings the
# cell had no entry for while the list stood at 128), and its own.
_FAMILIES = {
    'prefill_chunk_ms.backlog', 'iter_host_gap_ms.backlog',
    'prefix_hit_pct.backlog', 'tokens_per_dispatch',
    'moe_experts_hit_pct', 'moe_tiled_pairs_pct',
    'iter_ms.backlog', 'prefill_chunks_per_iter.backlog',
    'prefill_real_pct.backlog', 'slots_occupied_mean',
    'kv_blocks_used_peak_pct', 'moe_busiest_over_mean',
    'engine_idle_schedule_ms.backlog', 'engine_idle_prefill_ms.backlog',
    'engine_idle_dispatch_ms.backlog', 'engine_idle_emit_ms.backlog'}
_OWN = {'mtp_accept_pct', 'mtp_tokens_per_round', 'mtp_round_ms',
        'latent_mtp_round_hbm_roofline'}


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
    # The leading dense layer as published, 7 expert layers after,
    # the module beside them, every expert, the whole vocabulary.
    assert config['first_k_dense_replace'] == 1
    assert config['num_nextn_predict_layers'] == 1
    assert config['n_routed_experts'] == 256
    assert config['vocab_size'] == 129280
    assert config['build']['speculative'] == 'mtp'
    bench = harness.load_json(harness.REPO_DIR, 'BENCHMARK.json')
    entry = {c['name']: c for c in bench['configs']}[_NAME]
    assert entry['reduced'] == list(_REDUCED)
    assert entry['source'] == config['source']
    cell = {w['name']: w for w in bench['workloads']}[_CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        _NAME, 'reason2k-sampled-backlog', 1)
    # The form BENCHMARK.json is held to before any run.
    assert all(1 <= len(e['why']) <= 200 for e in (entry, cell))
    assert not any(k.endswith(('_dim', '_rank', '_size'))
                   for k in config['reduced'])
    assert len(bench['per_layer']) <= 128
    judged = {m['name']: m for m in bench['end_to_end']}['out_tok_s']
    assert _CELL in judged['workloads'] and judged['bound'] == 0.03
    # Its decode program is ``mtp_rounds_paged``: ``mtp_round_ms``
    # stands where the other cells have ``decode_step_ms``, and it has
    # no view or walk counters.
    mine = cell_stands_in_its_lists(_CELL, _FAMILIES, _OWN)
    assert not set(mine) & {'decode_step_ms.backlog',
                            'decode_view_pct.backlog',
                            'decode_walk_read_pct.backlog'}
    # Ten of the eleven set-up readings: this engine never compiles
    # ``decode_steps_paged``, so ``setup_jit_decode_s`` finds nothing.
    setup = {m['name'] for m in bench['per_layer']
             if m['moves'] == 'setup_s' and _CELL in m['workloads']}
    assert len(setup) == 10 and 'setup_jit_decode_s' not in setup


@pytest.mark.skipif(not os.path.exists(_CATALOG),
                    reason='the catalog is not on this machine')
def test_top_level_holds_every_published_key():
    with open(_CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row, = [r for r in rows if r['name'] == 'JoyAI-LLM-Flash']
    config = _file()
    assert config['source'] == row['source_url']
    for key, value in row['config'].items():
        if key in _REDUCED:
            assert config['published'][key] == value
        else:
            assert config[key] == value, key
    assert config['rope_scaling'] is None


def test_sizing_arithmetic_of_the_file():
    build = _file()['build']
    traffic = loadgen.load_traffic('reason2k-sampled-backlog')
    longest = traffic['prompt_len']['max'] + \
        traffic['output_len']['max']
    own = -(-(longest + 1) // build['block_size'])
    shared = traffic['shared_prompts'] * traffic['shared_len'] // \
        build['block_size']
    assert (longest, own, shared) == (3072, 193, 32)
    assert build['num_blocks'] == build['slots'] * own + shared + 1
    # A dispatch's rounds may write 2 x steps + 1 positions past the
    # longest request's last but one position.
    assert build['max_seq'] >= longest + 2 * \
        build['steps_per_dispatch'] + 1
    assert build['max_seq'] % build['block_size'] == 0
    assert build['slots'] % 8 == 0
    assert max(_file()['check_pad_to']) >= longest


def test_traffic_files_hold_the_issues_table():
    spec = loadgen.load_traffic('reason2k-sampled-backlog')
    assert spec['kind'] == 'backlog' and spec['n_requests'] == 640
    assert spec['lead_s'] == 30.0
    assert spec['window_edges'] == 'bursts'
    assert spec['prompt_len'] == {'median': 512, 'sigma': 0.5,
                                  'min': 256, 'max': 1024}
    assert spec['output_len'] == {'median': 768, 'sigma': 0.6,
                                  'min': 192, 'max': 2048}
    assert (spec['shared_prompts'], spec['shared_len'],
            spec['shared_zipf_s']) == (4, 128, 1.0)
    assert spec['sampling'] == {'temperature': 1.0, 'top_p': 1.0}


def test_the_mix_offers_the_same_sampled_work_to_every_seed():
    from perf.drivers import serve_latent_mtp as driver
    spec = loadgen.load_traffic('reason2k-sampled-backlog')
    a, b = (driver.sampled(
        loadgen.generate_backlog(spec, seed, 51, 129280), spec, seed)
        for seed in (1, 2**31 + 3))
    assert len(a) == len(b) == 640
    assert [(len(r['prompt']), r['max_new'], r['shared'])
            for r in a] == [(len(r['prompt']), r['max_new'],
                             r['shared']) for r in b]
    assert all(r['index'] == i for i, r in enumerate(a))
    assert max(len(r['prompt']) + r['max_new'] for r in a) <= 3072
    seeds = [r['prompt'].sampling['seed'] for r in a]
    assert len(set(seeds)) == 640
    assert seeds != [r['prompt'].sampling['seed'] for r in b]
    assert all(r['prompt'].sampling['temperature'] == 1.0 and
               r['prompt'].sampling['top_p'] == 1.0 for r in a + b)
    # The prompt is still the list the engine takes.
    assert isinstance(a[0]['prompt'], list) and \
        all(0 <= t < 129280 for t in a[0]['prompt'])


def test_gumbel_argmax_is_categorical_under_this_jax():
    """``argmax(l / T + g)`` with the reference's own noise is the
    program's draw, key for key (``ops.sampling.prng.row_key`` and
    ``jax.random.categorical``), for seeds on both sides of 2**31."""
    import jax
    import jax.numpy as jnp

    from perf.reference import joyai_mtp_block_f32 as reference
    from skypilot_tpu.ops.sampling import prng
    from skypilot_tpu.ops.sampling import sample
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((6, 4096)), jnp.float32)
    positions = jnp.asarray([0, 1, 17, 255, 3071, 70000], jnp.int32)
    for seed in (0, 12345, 2**31 - 1, 2**31 + 9, 2**32 - 1):
        stored = seed - (1 << 32) if seed >= 1 << 31 else seed
        for temp in (1.0, 0.6):
            noise = reference.gumbel_noise(
                jnp.asarray(np.uint32(seed)), positions, 4096)
            ours = np.asarray((logits / temp + noise).argmax(-1))
            theirs = [int(jax.random.categorical(
                prng.row_key(jnp.asarray(stored, jnp.int32), p),
                logits[i] / temp))
                for i, p in enumerate(positions)]
            drawn = np.asarray(sample.sample_rows(
                logits, jnp.full((6,), temp), jnp.ones((6,)),
                jnp.full((6,), stored, jnp.int32), positions))
            assert ours.tolist() == theirs == drawn.tolist()


def test_round_bytes_by_hand_at_the_tiny_size():
    model = harness.load_cell(_CELL, rehearse=True)['config']['model']
    got = latent_mtp_round.latent_mtp_round_bytes(
        model, 1, rows=12.0, context_tokens=900.0,
        experts_hit_share=0.5, main_share=0.6)
    d, rq, rkv, rope, heads = 128, 32, 48, 16, 4

    def mm(a, b):
        return a * b + 2 * b

    attention = (mm(d, rq) + mm(rq, heads * 48) + mm(d, rkv + rope) +
                 mm(rkv, heads * 64) + mm(heads * 32, d))
    every = attention + (2 * d + rq + rkv) * 2
    gated64 = 2 * mm(d, 64) + mm(64, d)
    moe = d * 8 * 2 + 8 * 2 + gated64 + 8 * 0.5 * gated64
    want = (5 * every + (2 * mm(d, 256) + mm(256, d)) + 4 * moe +
            mm(2 * d, d) + 3 * d * 2 +
            2 * mm(d, 512) + d * 2 + 12.0 * d * 2 +
            900.0 * 64 * 2 * (0.6 * 4 + 0.4 * 1))
    assert got == pytest.approx(want, rel=1e-12)


def test_the_roofline_reader_reads_the_counters(monkeypatch):
    """A round of 87 ms at the cell's counts reads a share under
    100; a program without the counters, a configuration without
    the module and a trace without the program read nothing."""
    from perf.lib import readers
    counts = {
        'skytpu_batch_mla_absorbed_row_steps_total': 96 * 8 * 3.38 * 70,
        'skytpu_batch_mla_absorbed_context_tokens_total':
            96 * 8 * 3.38 * 70 * 1500,
        'skytpu_batch_decode_dispatches_total': 70,
        'skytpu_batch_mtp_row_rounds_total': 96 * 8 * 70,
        'skytpu_batch_moe_experts_hit_total': 0.95e6,
        'skytpu_batch_moe_experts_held_total': 1e6}

    class Registry:
        def delta(self, name):
            return (counts[name], 0.0) if name in counts else None

    records = {'model': _file()['model'], 'registry': Registry(),
               'facts': {'steps_per_dispatch': 8, 'weight_bytes': 1},
               'peaks': {'hbm_bytes_per_s': 819e9}}
    reduce = harness.reader_for('latent_mtp_round_hbm_roofline',
                                harness.PERF_DIR)
    monkeypatch.setattr(readers, 'xla_module_ms',
                        lambda params, trace, records: 87.0)
    assert 10.0 < reduce(object(), records) < 30.0
    assert reduce(object(), dict(records, registry=None)) is None
    del counts['skytpu_batch_mtp_row_rounds_total']
    assert reduce(object(), records) is None
    assert reduce(object(), dict(
        records, model={'kv_lora_rank': 512})) is None
    monkeypatch.setattr(readers, 'xla_module_ms',
                        lambda params, trace, records: None)
    assert reduce(None, records) is None


def test_a_program_without_the_model_fails_at_once():
    from perf.drivers import serve_latent_mtp
    config = dict(_file(), program_model='no-such-model')
    with pytest.raises(harness.HarnessError, match='no model'):
        serve_latent_mtp.program_config(config)
    # A latent preset without the module's field, as the parent's.
    other = dict(_file(), program_model='xing4.0-29b-a4b')
    with pytest.raises(harness.HarnessError,
                       match='differs from the configuration'):
        serve_latent_mtp.program_config(other)
    wrong = dict(_file(), model=dict(_file()['model'],
                                     num_nextn_predict_layers=0))
    with pytest.raises(harness.HarnessError,
                       match='num_nextn_predict_layers'):
        serve_latent_mtp.program_config(wrong)
    prog = serve_latent_mtp.program_config(_file())
    assert prog.n_layers == 8 and prog.dense_first == 1
    assert prog.layer_kinds == ('latent',) and prog.n_experts == 256
    assert prog.nextn_layers == 1 and prog.kv_entries == 9


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


def test_sound_run_is_correct_and_checks_requests_of_the_window(
        fresh_programs, capsys):
    """The sampled cell's check is ``serve_engine.check_served``'s
    since PR 45: three requests that finished inside the window, the
    last ``check_tokens`` tokens of each, all stamped inside it."""
    out = _run()
    assert out['correct'], out['compared']
    assert out['attempted'] > 0 and out['failed'] == 0
    said = capsys.readouterr().out
    line, = [l for l in said.splitlines()
             if l.startswith('reference: requests')]
    picked = json.loads(line.split('requests ')[1].split(' of ')[0])
    config = harness.load_cell(_CELL, rehearse=True)['config']
    cap = config['check_tokens']
    assert len(picked) == len(set(picked)) == 3
    assert max(picked) >= config['build']['slots']  # a later wave
    assert f'tokens compared {[cap] * 3}' in line
    assert f'{3 * cap} of them stamped inside the window' in line
    assert {c['name']: c['value'] for c in out['compared']}[
        'served_tokens_missing'] == 0


@pytest.mark.parametrize('seed', [13, 2**31 + 5, 77])
def test_control_at_lower_precision_is_not_correct(seed):
    loaded = harness.load_cell(_CELL, rehearse=True)
    driver = harness.driver_for(loaded['config'])
    got = driver.control_readings(loaded, seed, 2.0, True)
    limit = loaded['config']['limits']['served_logit_gap_max']
    name = 'served_logit_gap_max'
    assert got['sound'][name] <= limit < got['control'][name], got


def test_the_routed_scale_left_out(monkeypatch, fresh_programs):
    """The routed sum at weight 1 where the file says 2: a program
    that says it runs the file's model and computes another. (The
    selection bias left out does not show at this size: under
    sampled rows a token changes only where the logits move by more
    than the top two perturbed scores lie apart.)"""
    from perf.drivers import serve_latent_mtp
    real = serve_latent_mtp.program_config
    monkeypatch.setattr(
        serve_latent_mtp, 'program_config',
        lambda config: dataclasses.replace(real(config),
                                           moe_routed_scale=1.0))
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']


def test_a_token_drawn_under_another_positions_key(monkeypatch,
                                                   fresh_programs):
    """The engine's draws keyed one position on: every served token
    is some other draw's, and the gap by the reference's noise is a
    random token's."""
    from skypilot_tpu.ops.sampling import prng
    real = prng.row_key
    monkeypatch.setattr(prng, 'row_key',
                        lambda seed, position: real(seed, position + 1))
    out = _run()
    assert not out['correct'] and not _gap(out)['ok'], out['compared']
