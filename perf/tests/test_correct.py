"""``correct`` has teeth. At a size a test run can hold (the
configurations' rehearsal size, through the harness's own drivers,
skipping only its look for a chip):

- the control - the reference put in the program's place at the
  nearest precision below the one the configuration states - comes
  out as not correct against the same limits;
- with the timed path broken underneath (a token altered where it is
  produced; a train step that returns its state unchanged) a whole
  run ends with ``correct`` false;
- a sound run ends with ``correct`` true.
"""
import time

import pytest

from perf.lib import harness


def _run(workload, seed=11, seconds=2.0):
    loaded = harness.load_cell(workload, rehearse=True)
    driver = harness.driver_for(loaded['config'])
    return loaded, driver.run(loaded, seed, seconds, False, True,
                              time.perf_counter())


def _by_name(out):
    return {c['name']: c for c in out['compared']}


def test_sound_serving_run_is_correct():
    _, out = _run('serve-chat-steady')
    assert out['correct'], out['compared']
    assert out['attempted'] > 0 and out['failed'] == 0


def test_serving_with_a_token_altered_where_it_is_produced(monkeypatch):
    from skypilot_tpu.serve import batching
    real = batching.decode_steps_paged

    def altered(params, tokens, *args, **kwargs):
        toks, caches, pos = real(params, tokens, *args, **kwargs)
        vocab = params['embed'].shape[0]
        return (toks + 1) % vocab, caches, pos

    monkeypatch.setattr(batching, 'decode_steps_paged', altered)
    _, out = _run('serve-chat-steady')
    assert not out['correct']
    assert not _by_name(out)['served_logit_gap_max']['ok']


def _control(workload, seed):
    loaded = harness.load_cell(workload, rehearse=True)
    driver = harness.driver_for(loaded['config'])
    got = driver.control_readings(loaded, seed, 2.0, True)
    return loaded['config']['limits'], got


@pytest.mark.parametrize('seed', [13, 2**31 + 5, 77])
def test_serving_control_at_lower_precision_is_not_correct(seed):
    """int4 weights where the configuration states int8: the token
    the lower precision puts first lies further below the reference's
    best than the limit allows, while the program's own stays under."""
    limits, got = _control('serve-chat-steady', seed)
    name = 'served_logit_gap_max'
    assert got['sound'][name] <= limits[name] < got['control'][name]


def test_sound_training_run_is_correct():
    _, out = _run('train-qlora-2k')
    assert out['correct'], out['compared']


def test_training_with_a_step_that_returns_its_state_unchanged(
        monkeypatch):
    from skypilot_tpu.parallel import train as train_lib
    real = train_lib.build_train_step

    def build(*args, **kwargs):
        step = real(*args, **{**kwargs, 'donate': False})

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return unchanged

    monkeypatch.setattr(train_lib, 'build_train_step', build)
    _, out = _run('train-qlora-2k')
    assert not out['correct']
    assert not _by_name(out)['param_change_norm_worst_leaf_gap']['ok']


def test_training_with_part_of_the_batch_left_out(monkeypatch):
    from skypilot_tpu.parallel import train as train_lib
    real = train_lib.build_train_step

    def build(*args, **kwargs):
        step = real(*args, **kwargs)

        def half(state, batch):
            rows = batch['tokens']
            n = rows.shape[0] // 2
            import jax.numpy as jnp
            return step(state, {'tokens': jnp.concatenate(
                [rows[:n], rows[:n]])})
        return half

    monkeypatch.setattr(train_lib, 'build_train_step', build)
    _, out = _run('train-qlora-2k')
    assert not out['correct']


@pytest.mark.parametrize('seed', [17, 2**31 + 9, 99])
def test_training_control_at_lower_precision_is_not_correct(seed):
    """The reference at int4 weights, held against the reference at
    the stated precision with the configuration's limits: at least
    one number fails."""
    limits, got = _control('train-qlora-2k', seed)
    assert any(v > limits[k] for k, v in got['control'].items()), got
