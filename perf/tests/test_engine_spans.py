"""The device's idle time cut along the engine's own spans
(perf/lib/engine_spans.py), on a hand-made trace: two iterations that
did work, one that did none, one cut by the window's edge."""
import pytest

from perf.lib import engine_spans
from perf.lib import harness

P = engine_spans.PREFIX


def _synthetic():
    # Busy 1.0-2.0, 2.3-4.0, 4.5-5.0, 5.2-6.0, 6.5-7.0; so the window
    # is 1.0-7.0 and the gaps 2.0-2.3, 4.0-4.5, 5.0-5.2, 6.0-6.5.
    ops = [('fusion.1', 1.0, 1.0), ('fusion.2', 2.3, 1.7),
           ('fusion.3', 4.5, 0.5), ('fusion.4', 5.2, 0.8),
           ('fusion.5', 6.5, 0.5)]
    host = [
        # cut by the window's opening: not counted, its gap neither
        (P + 'iteration#n=1,queued=0#', 0.5, 1.2),
        (P + 'emit#rows=2#', 1.6, 0.1),
        # iteration 2 (1.8-4.2): a gap under admit, 2.0-2.3, then a
        # chunk nested in the prefill phase, device busy under it
        (P + 'iteration#n=2,queued=1#', 1.8, 2.4),
        (P + 'sweep', 1.8, 0.1),
        (P + 'admit#queued=1#', 1.9, 0.45),
        (P + 'prefill', 2.35, 0.5),
        (P + 'prefill_chunk#row=1,bucket=8,real=5,offset=0#', 2.4, 0.4),
        (P + 'dispatch#rows=2,steps=8#', 2.85, 0.1),
        (P + 'device_wait#rows=2,kind=decode#', 2.95, 1.05),
        # the gap 4.0-4.5 opens under emit and outlives iteration 2
        (P + 'emit#rows=2#', 4.0, 0.15),
        (P + 'gauges', 4.15, 0.05),
        # iteration 3 (4.2-6.1): idle under the prefill phase AND its
        # child (4.2-4.5): counted once; then 5.0-5.2 under dispatch
        (P + 'iteration#n=3,queued=0#', 4.2, 1.9),
        (P + 'prefill', 4.2, 0.7),
        (P + 'prefill_chunk#row=1,bucket=8,real=3,offset=5#', 4.25, 0.3),
        (P + 'first_token#row=1#', 4.6, 0.3),
        (P + 'dispatch#rows=3,steps=8#', 4.9, 0.2),
        (P + 'device_wait#rows=3,kind=decode#', 5.1, 0.9),
        (P + 'emit#rows=3#', 6.0, 0.1),
        # iteration 4 did no work: neither it nor its gap is counted
        (P + 'iteration#n=4,queued=0#', 6.1, 0.2),
        (P + 'admit#queued=0#', 6.1, 0.2),
        (P + 'idle_wait', 6.3, 0.2),
        ('perf.collect', 0.0, 8.0), ('$batching.py:1 _iterate', 1.8, 2.4),
    ]
    return {'devices': {'/device:TPU:0': {'XLA Ops': ops}},
            'host': host}


def test_names_end_at_the_first_hash():
    spans = engine_spans.engine_spans(_synthetic())
    assert len(spans['iteration']) == 4
    assert spans['admit'] == [(1.9, 2.35), (6.1, pytest.approx(6.3))]
    assert set(spans) == {
        'iteration', 'sweep', 'admit', 'prefill', 'prefill_chunk',
        'first_token', 'dispatch', 'device_wait', 'emit', 'gauges',
        'idle_wait'}


def test_only_whole_iterations_that_did_work_are_counted():
    account = engine_spans.idle_account(_synthetic())
    assert account['iterations'] == 2
    # idle inside iterations 2 and 3: 2.0-2.3, 4.0-4.5, 5.0-5.2,
    # 6.0-6.1
    assert sum(e - s for s, e in account['idle']) == \
        pytest.approx(0.3 + 0.5 + 0.2 + 0.1)


def test_idle_is_cut_exactly_along_the_phases():
    t = _synthetic()
    per_iter = {name: engine_spans.idle_ms_per_iteration(t, phases)
                for name, phases in (
                    ('schedule', ('sweep', 'admit', 'gauges')),
                    ('prefill', ('prefill',)),
                    ('dispatch', ('dispatch', 'device_wait')),
                    ('emit', ('emit',)))}
    # admit 2.0-2.3 and gauges 4.15-4.2; the parked iteration's
    # admit is outside every counted iteration
    assert per_iter['schedule'] == pytest.approx((300 + 50) / 2)
    # 4.2-4.5 under the phase and its child: once
    assert per_iter['prefill'] == pytest.approx(300 / 2)
    assert per_iter['dispatch'] == pytest.approx(200 / 2)
    # 4.0-4.15 of the longer gap, and 6.0-6.1
    assert per_iter['emit'] == pytest.approx((150 + 100) / 2)
    whole = sum(e - s for s, e in
                engine_spans.idle_account(t)['idle']) * 1e3 / 2
    assert sum(per_iter.values()) == pytest.approx(whole)
    # children alone: named on purpose, they are not counted twice
    # when their parent is named too
    assert engine_spans.idle_ms_per_iteration(
        t, ('prefill', 'prefill_chunk', 'first_token')) == \
        pytest.approx(per_iter['prefill'])


@pytest.mark.parametrize('trace', [
    None,
    {'devices': {}, 'host': []},
    {'devices': {'/device:TPU:0': {'XLA Ops': []}}, 'host': []},
    # the parent commit and the train cells: no engine span
    {'devices': {'/device:TPU:0': {'XLA Ops': [('fusion.1', 0.0, 1.0)]}},
     'host': [('perf.collect', 0.0, 1.0)]},
    # spans, but no whole iteration that did work
    {'devices': {'/device:TPU:0': {'XLA Ops': [('fusion.1', 0.0, 1.0)]}},
     'host': [(P + 'iteration#n=1#', 0.1, 0.2), (P + 'admit', 0.1, 0.2)]},
])
def test_nothing_to_read_gives_none(trace):
    assert engine_spans.idle_ms_per_iteration(trace, ('emit',)) is None


@pytest.mark.parametrize('name', [
    'engine_idle_schedule_ms', 'engine_idle_prefill_ms',
    'engine_idle_dispatch_ms', 'engine_idle_emit_ms'])
def test_the_four_readers_are_found_by_name(name):
    t = _synthetic()
    for cell in ('backlog', 'steady'):
        reader = harness.reader_for(f'{name}.{cell}', harness.PERF_DIR)
        assert reader(t, {}) > 0
        assert reader(None, {}) is None


@pytest.mark.parametrize('name,families', [
    ('iter_ms', {'skytpu_batch_iteration_seconds_total': 46.0,
                 'skytpu_batch_iterations_total': 40.0}),
    ('iter_host_gap_ms', {'skytpu_batch_host_gap_seconds_total': 2.0,
                          'skytpu_batch_iterations_total': 40.0}),
    ('prefill_chunks_per_iter',
     {'skytpu_batch_prefill_chunks_total': 120.0,
      'skytpu_batch_iterations_total': 40.0}),
    ('prefill_real_pct',
     {'skytpu_batch_prefill_tokens_total': 950.0,
      'skytpu_batch_prefill_bucket_tokens_total': 1000.0}),
])
def test_the_counter_metrics_read_their_families(name, families):
    class Window:
        def __init__(self, deltas):
            self.deltas = deltas

        def delta(self, family):
            return (self.deltas[family], 0.0) \
                if family in self.deltas else None

    reader = harness.reader_for(name + '.backlog', harness.PERF_DIR)
    expected = {'iter_ms': 1150.0, 'iter_host_gap_ms': 50.0,
                'prefill_chunks_per_iter': 3.0,
                'prefill_real_pct': 95.0}[name]
    assert reader(None, {'registry': Window(families)}) == \
        pytest.approx(expected)
    # the parent commit has no such family
    assert reader(None, {'registry': Window({})}) is None
    assert reader(None, {'registry': None}) is None
