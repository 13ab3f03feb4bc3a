"""Percentile and TPOT arithmetic, the schedule as a function of the
seed, operations and bytes against hand-worked numbers, the peaks."""
import json
import math
import os

import numpy as np
import pytest

from perf import costs
from perf.costs import decode_step
from perf.costs import flash_attention
from perf.costs import model as model_costs
from perf.lib import loadgen
from perf.lib import peaks
from perf.lib import stats

_HERE = os.path.dirname(os.path.abspath(__file__))
_MISTRAL = json.load(open(os.path.join(
    _HERE, '..', 'configs', 'mistral-7b-int8-serve.json')))['model']


@pytest.mark.parametrize('values,pct,want', [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9.1),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5.5),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 100, 10),
    ([5.0], 90, 5.0),
    ([3, 1, 2], 25, 1.5),
])
def test_percentile_is_linearly_interpolated(values, pct, want):
    assert stats.percentile(values, pct) == pytest.approx(want)
    assert stats.percentile(values, pct) == pytest.approx(
        np.percentile(values, pct))


def test_percentile_rejects_nothing_and_bad_pct():
    with pytest.raises(ValueError):
        stats.percentile([], 90)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_tpot_is_per_request_not_per_gap():
    # 9 tokens, first at 1.0 s, last at 1.8 s: 100 ms a token though
    # a dispatch hands them back eight at a time.
    assert stats.tpot_ms(1.0, 1.8, 9) == pytest.approx(100.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def test_quartile_spread_matches_statistics_quantiles():
    values = [100, 101, 102, 103, 104, 105]
    # quantiles(n=4) exclusive: q1 = 100.75, q3 = 104.25
    assert stats.quartile_spread(values) == pytest.approx(3.5 / 102.5)


def _steady(rate=2.0):
    spec = loadgen.load_traffic('chat-steady')
    spec['rate_rps'] = rate
    return spec


def test_trimmed_spread_leaves_out_the_farthest_run():
    values = [100, 101, 102, 103, 104, 150]
    assert stats.trimmed_spread(values) == pytest.approx(
        stats.quartile_spread([100, 101, 102, 103, 104]))
    assert stats.trimmed_spread(values) < stats.quartile_spread(values)


def test_schedule_is_a_function_of_the_seed_alone():
    a = loadgen.generate_open_loop(_steady(), 2**31 + 7, 30, 32000)
    b = loadgen.generate_open_loop(_steady(), 2**31 + 7, 30, 32000)
    c = loadgen.generate_open_loop(_steady(), 2**31 + 8, 30, 32000)
    assert a == b
    assert a != c


_SERVING_MIXES = ['chat-backlog', 'chat-steady', 'reason-backlog']
_SIX_SEEDS = (0, 1, 2, 77, 2**31 + 3, 2**32 + 5)


def _six_runs(mix):
    spec = loadgen.load_traffic(mix)
    gen = loadgen.generator_for(spec['kind'])
    return spec, [gen(spec, seed, 51, 32000) for seed in _SIX_SEEDS]


def _triples(reqs):
    return sorted((len(r['prompt']), r['max_new'], r['shared'])
                  for r in reqs)


@pytest.mark.parametrize('mix', _SERVING_MIXES)
def test_every_seed_offers_the_same_schedule_with_other_tokens(mix):
    """Not only the same marginals: the (prompt length, output
    length, system prompt) triples, their order and their due
    instants are the mix's, whatever the seed, and so is every count
    made from them. The seed draws the token ids."""
    _, runs = _six_runs(mix)
    a = runs[0]
    schedule = lambda rs: [  # noqa: E731
        (r['due_s'], len(r['prompt']), r['max_new'], r['shared'])
        for r in rs]
    for b in runs[1:]:
        assert schedule(a) == schedule(b)
        assert _triples(a) == _triples(b)
        assert loadgen.offered(a, 3072) == loadgen.offered(b, 3072)
        assert all(x['prompt'] != y['prompt'] for x, y in zip(a, b))


@pytest.mark.parametrize('mix', _SERVING_MIXES)
def test_marginals_count_and_rate_are_exact(mix):
    spec, runs = _six_runs(mix)
    reqs = runs[3]
    n = len(reqs)
    if spec['kind'] == 'backlog':
        assert n == spec['n_requests']
        assert all(r['due_s'] == -spec['lead_s'] for r in reqs)
    else:
        span = spec['lead_s'] + 51
        assert n == math.floor(spec['rate_rps'] * span)
        # The gaps' mean is 1 / rate exactly: the last arrival falls
        # on n / rate after the lead-in's start.
        assert reqs[-1]['due_s'] + spec['lead_s'] == pytest.approx(
            n / spec['rate_rps'])
    import statistics
    nd = statistics.NormalDist()
    for key, got in (('prompt_len', [len(r['prompt']) for r in reqs]),
                     ('output_len', [r['max_new'] for r in reqs])):
        d = spec[key]
        want = sorted(int(np.clip(np.rint(d['median'] * math.exp(
            d['sigma'] * nd.inv_cdf((i + 0.5) / n))), d['min'],
            d['max'])) for i in range(n))
        assert sorted(got) == want


@pytest.mark.parametrize('mix', _SERVING_MIXES)
def test_every_block_spans_both_ranges_and_tenths_meet_evenly(mix):
    spec, runs = _six_runs(mix)
    block = spec['deal_block']
    for reqs in runs[:2]:
        n = len(reqs)
        n_blocks = math.ceil(n / block)
        # The schedule is whole blocks one after another; each holds
        # one prompt and one output from every round of n_blocks
        # ranks (a last, short round reaches only some blocks).
        lengths = sorted(len(r['prompt']) for r in reqs)
        outs = sorted(r['max_new'] for r in reqs)
        at = 0
        round_of = lambda v, ordered: {  # noqa: E731
            k // n_blocks for k, x in enumerate(ordered) if x == v}
        for size in _schedule_block_sizes(n, block):
            members = reqs[at:at + size]
            at += size
            for values, ordered in (
                    ([len(r['prompt']) for r in members], lengths),
                    ([r['max_new'] for r in members], outs)):
                # Ties (clipped lengths) may sit in either of two
                # rounds: every round is met by some member.
                rounds = [round_of(v, ordered) for v in values]
                for want in range(size):
                    assert any(want in r for r in rounds), \
                        (mix, want, values)
        assert at == n


def _schedule_block_sizes(n, block):
    """Sizes of the blocks in the order the schedule holds them."""
    blocks = loadgen._snake_blocks(n, block)  # pylint: disable=protected-access
    stride = loadgen._stride(len(blocks))  # pylint: disable=protected-access
    return [len(blocks[k * stride % len(blocks)])
            for k in range(len(blocks))]


@pytest.mark.parametrize('mix', _SERVING_MIXES)
def test_prompt_and_output_lengths_are_independent_in_the_large(mix):
    _, runs = _six_runs(mix)
    reqs = runs[0]
    n = len(reqs)
    by_p = sorted(range(n), key=lambda i: (len(reqs[i]['prompt']), i))
    by_o = sorted(range(n), key=lambda i: (reqs[i]['max_new'], i))
    fifth_p = {i: k * 5 // n for k, i in enumerate(by_p)}
    fifth_o = {i: k * 5 // n for k, i in enumerate(by_o)}
    table = np.zeros((5, 5), int)
    for i in range(n):
        table[fifth_p[i], fifth_o[i]] += 1
    # Every fifth of the prompts meets every fifth of the outputs
    # about n / 25 times: to within a tenth where the blocks are many
    # (640: 24-27 of 25.6), more loosely where they are a dozen.
    slack = 0.1 if n >= 400 else 0.65
    assert table.min() >= (1 - slack) * n / 25
    assert table.max() <= (1 + slack) * n / 25
    logs = np.log([[len(r['prompt']), r['max_new']] for r in reqs])
    assert abs(np.corrcoef(logs.T)[0, 1]) < 0.1


def test_a_stride_visits_every_residue():
    for m in range(1, 70):
        s = loadgen._stride(m)  # pylint: disable=protected-access
        assert sorted(k * s % m for k in range(m)) == list(range(m))
    assert loadgen._stride(64) == 39  # pylint: disable=protected-access
    assert loadgen._stride(10) == 7  # pylint: disable=protected-access


def test_lengths_are_the_quantile_mid_points():
    spec = loadgen.load_traffic('chat-backlog')
    reqs = loadgen.generate_backlog(spec, 9, 51, 32000)
    n = len(reqs)
    assert n == spec['n_requests']
    assert all(r['due_s'] == -spec['lead_s'] for r in reqs)
    import statistics
    nd = statistics.NormalDist()
    want = sorted(int(np.clip(np.rint(768 * math.exp(
        0.8 * nd.inv_cdf((i + 0.5) / n))), 320, 3072))
        for i in range(n))
    assert sorted(len(r['prompt']) for r in reqs) == want
    # Any whole block carries about the same work, of either kind,
    # and any stretch of two dozen requests nearly so.
    for size, slack in ((spec['deal_block'], 1.1), (24, 1.45)):
        for work in (lambda r: len(r['prompt']),
                     lambda r: r['max_new']):
            sums = [sum(work(r) for r in reqs[i:i + size])
                    for i in range(0, n - size + 1, size)]
            assert max(sums) < slack * min(sums)


def test_schedule_keeps_rate_lead_and_limits():
    spec = _steady(2.0)
    reqs = loadgen.generate_open_loop(spec, 5, 40, 32000)
    assert len(reqs) == math.floor(2.0 * (spec['lead_s'] + 40))
    assert -spec['lead_s'] < reqs[0]['due_s'] < 0
    assert reqs[-1]['due_s'] == pytest.approx(40)
    for r in reqs:
        assert 320 <= len(r['prompt']) <= 3072
        assert 16 <= r['max_new'] <= 512
        assert len(r['prompt']) + r['max_new'] <= 4096
    by_sys = {}
    for r in reqs:
        by_sys.setdefault(r['shared'], set()).add(
            tuple(r['prompt'][:256]))
    assert all(len(v) == 1 for v in by_sys.values())
    assert len(by_sys) == 8
    # Zipf s = 1 over 8: the first system prompt opens 1 / H_8 = 36.8 %
    share = sum(r['shared'] == 0 for r in reqs) / len(reqs)
    assert share == pytest.approx(0.368, abs=0.02)
    bodies = {tuple(r['prompt'][256:]) for r in reqs}
    assert len(bodies) == len(reqs)


def test_training_batches_differ_by_row_step_and_seed():
    spec = loadgen.load_traffic('synthetic-2k')
    gen = loadgen.generator_for(spec['kind'])
    a = gen(spec, 3, 0, 4, 32000)
    assert a.shape == (4, 2049) and a.dtype == np.int32
    assert len({row.tobytes() for row in a}) == 4
    assert not np.array_equal(a, gen(spec, 3, 1, 4, 32000))
    assert np.array_equal(a, gen(spec, 3, 0, 4, 32000))


def test_extends_overrides_the_base_mix():
    steady = loadgen.load_traffic('chat-steady')
    backlog = loadgen.load_traffic('chat-backlog')
    assert steady['prompt_len'] == backlog['prompt_len']
    assert steady['kind'] == 'open_loop'
    assert backlog['kind'] == 'backlog'
    assert backlog['window_edges'] == 'bursts'


def test_whole_burst_window_edges():
    from perf.drivers import serve_engine
    # Bursts every 1.0 s from t = 0.3, eight tokens a burst, a few
    # ms apart as consumer threads stamp them.
    stamps = [0.3 + k + 0.002 * j for k in range(12) for j in range(8)]
    edges = serve_engine._whole_burst_edges(  # pylint: disable=protected-access
        stamps[::-1], t_from=2.0, seconds=5.0, gap_s=0.02)
    assert edges == pytest.approx((2.3, 7.3))
    inside = [t for t in stamps if edges[0] <= t < edges[1]]
    assert len(inside) == 5 * 8
    # The closing burst has not come yet: no edges.
    assert serve_engine._whole_burst_edges(  # pylint: disable=protected-access
        stamps, 2.0, 10.0, 0.02) is None


def test_mistral_7b_matmul_parameters_by_hand():
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # gate/up/down 3 x 4096x14336 = 218,103,808; x32 + head 4096x32000
    assert model_costs.matmul_params(_MISTRAL) == \
        32 * 218_103_808 + 131_072_000 == 7_110_393_856


def test_lora_training_flops_per_token_by_hand():
    # 4 x 7.110 G (frozen base: forward 2, backward 2) + attention
    # 32 layers x 6 matmuls x 4096 x 2048 = 1.611 G -> 30.05 GFLOP
    got = model_costs.train_flops_per_token(_MISTRAL, 2048, frozen_base=True)
    assert got == pytest.approx(4 * 7_110_393_856 + 32 * 6 * 4096 * 2048)
    assert got == pytest.approx(30.05e9, rel=1e-3)
    full = model_costs.train_flops_per_token(_MISTRAL, 2048, frozen_base=False)
    assert full - got == pytest.approx(2 * 7_110_393_856)


def test_flash_call_work_by_hand():
    fwd = flash_attention.flash_fwd_call(_MISTRAL, 4, 2048)
    # two matmuls over the causal half: 2 x (2 x 4 x 4096 x 2048^2 / 2)
    assert fwd['flops'] == pytest.approx(2 * 4 * 4096 * 2048 * 2048)
    # q, out (4096 wide) and k, v (1024 wide), bf16, once each
    assert fwd['bytes'] == pytest.approx(
        2 * 4 * 2048 * (2 * 4096 + 2 * 1024))
    bwd = flash_attention.flash_bwd_call(_MISTRAL, 4, 2048)
    assert bwd['flops'] == pytest.approx(2.5 * fwd['flops'])


def test_decode_step_bytes_by_hand():
    got = decode_step.decode_step_bytes(_MISTRAL, 1, 1, rows=16,
                                  kv_tokens=16 * 800)
    weights = 7_110_393_856
    scales = 2 * (32 * (2 * 4096 + 2 * 1024 + 2 * 14336 + 4096)
                  + 32000)
    kv = 16 * 800 * 32 * 2 * (1024 + 16)
    assert got == pytest.approx(weights + scales + kv + 16 * 4096 * 2)


def test_cost_functions_are_found_by_file_and_name():
    fn = costs.cost_function('flash_attention.flash_fwd_call')
    assert fn(_MISTRAL, 4, 2048) == \
        flash_attention.flash_fwd_call(_MISTRAL, 4, 2048)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for('TPU v5 lite')['hbm_bytes_per_s'] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for('TPU v9 imaginary')


class _Compiled:
    """Stands in for a compiled executable."""

    def __init__(self, stats):
        self._stats = stats

    def memory_analysis(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_executable_bytes_counts_arguments_outputs_and_temporaries():
    import types
    from perf.lib import harness
    stats = types.SimpleNamespace(
        argument_size_in_bytes=3_000, output_size_in_bytes=3_100,
        alias_size_in_bytes=2_900, temp_size_in_bytes=10_000,
        generated_code_size_in_bytes=77)
    # Donated arguments are counted once: 3,000 + (3,100 - 2,900)
    # + 10,000.
    assert harness.executable_bytes(_Compiled(stats)) == 13_200
    assert harness.executable_bytes(_Compiled(None)) is None
    assert harness.executable_bytes(
        _Compiled(RuntimeError('unimplemented'))) is None


def test_memory_peak_is_the_larger_reading(monkeypatch):
    import types
    import jax
    from perf.lib import harness
    devs = [types.SimpleNamespace(
        memory_stats=lambda p=p: {'peak_bytes_in_use': p})
        for p in (3_600, 3_700)]
    monkeypatch.setattr(jax, 'local_devices', lambda: devs)
    stats = types.SimpleNamespace(
        argument_size_in_bytes=3_000, output_size_in_bytes=0,
        alias_size_in_bytes=0, temp_size_in_bytes=9_000)
    assert harness.memory_peak_bytes() == 3_700
    assert harness.memory_peak_bytes([_Compiled(stats)]) == 12_000
    assert harness.memory_peak_bytes([_Compiled(None)]) == 3_700
    # A backend that reports no statistics gives no number at all.
    monkeypatch.setattr(jax, 'local_devices', lambda: [
        types.SimpleNamespace(memory_stats=lambda: None)])
    assert harness.memory_peak_bytes([_Compiled(stats)]) is None
