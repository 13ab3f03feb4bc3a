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


def test_the_tokens_compared_do_not_grow_with_the_programs_speed(
        monkeypatch):
    """One schedule served by a program, by one half as fast and by
    one a hundredth as fast (four rows, a fixed rate a row): the first two
    finish other requests inside the window, yet ``check_served``
    compares as many requests and as many tokens of each, the last
    ``check_tokens`` of requests that finished inside the window, every
    one stamped inside it; the third finishes too few, and the check
    says how many tokens are missing."""
    import numpy as np
    from perf.drivers import serve_engine
    from perf.lib import loadgen
    loaded = harness.load_cell('serve-chat-backlog', rehearse=True)
    config = loaded['config']
    cap, n = config['check_tokens'], config['check_requests']
    requests = loadgen.generate_backlog(loaded['traffic'], 7, 2.0, 512)
    followed = []

    class Reference:
        @staticmethod
        def served_token_gaps(params, model, prompt, tokens, pad_to,
                              weight_format=None):
            followed.append((tuple(prompt), tuple(tokens)))
            gaps = np.arange(len(tokens), dtype=np.float64)
            return gaps, 2 * gaps

    monkeypatch.setattr(harness, 'reference_for',
                        lambda config: Reference)
    t_open, t_close = 30.0, 81.0

    def window(tokens_per_s, seed=7):
        free = [0.0] * 4  # when each row takes its next request
        tracked = []
        for j, spec in enumerate(requests):
            row = free.index(min(free))
            if free[row] >= t_close:
                break
            item = serve_engine._Tracked(spec, 0.0, [])
            times = [free[row] + (k + 1) / tokens_per_s
                     for k in range(spec['max_new'])]
            free[row] = times[-1]
            item.times = [t for t in times if t < t_close + 1.0]
            item.tokens = [(j + k) % 512 for k in range(len(item.times))]
            tracked.append(item)
        drove = {'tracked': tracked, 't_open': t_open, 't_close': t_close,
                 'finished_in_window': [
                     i for i in tracked
                     if len(i.tokens) == i.spec['max_new'] and
                     t_open <= i.times[-1] < t_close]}
        del followed[:]
        got = serve_engine.check_served(loaded, None, None, drove, seed,
                                        weight_format=None)
        picks = [tracked[j] for j in
                 serve_engine.check_sample(config, drove, seed)]
        assert [(tuple(i.spec['prompt']), tuple(i.tokens))
                for i in picks] == followed
        assert all(i in drove['finished_in_window'] for i in picks)
        return drove, picks, got

    fast, fast_picks, fast_got = window(20.0)
    slow, slow_picks, slow_got = window(10.0)
    assert len(fast['finished_in_window']) > \
        1.5 * len(slow['finished_in_window'])
    for drove, picks, got in ((fast, fast_picks, fast_got),
                              (slow, slow_picks, slow_got)):
        assert len(picks) == n and got['missing'] == 0
        assert all(len(i.tokens) >= cap and i.times[-cap] >= t_open
                   for i in picks)
        late = [i for i in drove['finished_in_window']
                if len(i.tokens) >= cap and i.times[-cap] >= t_open]
        sizes = [len(i.spec['prompt']) + len(i.tokens) for i in late]
        assert len(picks[0].spec['prompt']) + len(picks[0].tokens) == \
            max(sizes)
        # The widest gap is over the last ``cap`` tokens alone.
        longest_out = max(len(i.tokens) for i in picks)
        assert got['served'] == longest_out - 1
        assert got['lower'] == 2 * (longest_out - 1)
    # Another seed draws another sample round the same longest one.
    _, other, _ = window(20.0, seed=8)
    assert other[0] is not fast_picks[0] and \
        other[0].spec is fast_picks[0].spec
    assert [i.spec for i in other[1:]] != [i.spec for i in fast_picks[1:]]
    # A hundredth as fast: no request serves ``cap`` tokens inside the
    # window, so those that finished inside it stand in, and are short.
    _, _, crawl_got = window(0.25)
    assert crawl_got['missing'] > 0


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
