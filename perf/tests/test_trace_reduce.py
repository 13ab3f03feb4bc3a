"""The reduction from a trace to numbers: interval arithmetic on
hand-made intervals, then the whole reduction on a trace recorded on
the chip in PR 23 and trimmed (perf/tests/data/)."""
import glob
import os

import pytest

from perf import trace_reduce

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data')


def test_union_and_subtract():
    u = trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert u == [(0, 3), (5, 7)]
    assert trace_reduce.total(u) == 5
    assert trace_reduce.subtract([(0, 10)], u) == [(3, 5), (7, 10)]
    assert trace_reduce.subtract(u, [(0, 10)]) == []
    assert trace_reduce.subtract([(0, 4), (6, 9)], [(1, 2), (3, 7)]) == \
        [(0, 1), (2, 3), (7, 9)]


def _synthetic():
    ops = [('fusion.1', 0.0, 1.0), ('all-gather-start.2', 1.0, 0.1),
           ('fusion.3', 1.1, 0.9), ('all-gather-done.2', 2.0, 0.5),
           ('flash_fwd.4', 3.0, 1.0), ('all-reduce.5', 4.0, 0.5)]
    mods = [('jit_step_fn(123)', 0.0, 2.5), ('jit_step_fn(123)', 3.0, 1.5)]
    return {'devices': {'/device:TPU:0': {'XLA Ops': ops,
                                          'XLA Modules': mods}},
            'host': [('perf.feed_batch', 2.5, 0.5),
                     ('other', 2.5, 0.5)]}


def test_short_names_and_containers():
    long = '%while.8 = (s32[]{:T(128)}, bf16[4,2048]) while(...)'
    assert trace_reduce.short_name(long) == 'while.8'
    t = _synthetic()
    t['devices']['/device:TPU:0']['XLA Ops'].append(
        ('while.8', 0.0, 4.5))
    assert trace_reduce.top_ops(t, 1)[0][0] == 'step_fn/fusion.1'
    assert trace_reduce.busy_seconds(t) == pytest.approx(4.5)


def test_reduction_on_a_hand_made_trace():
    t = _synthetic()
    assert trace_reduce.window_of(t) == (0.0, 4.5)
    assert trace_reduce.busy_seconds(t) == pytest.approx(4.0)
    mods = trace_reduce.module_times(t)
    assert mods['jit_step_fn']['calls'] == 2
    assert mods['jit_step_fn']['seconds'] == pytest.approx(4.0)
    assert trace_reduce.op_seconds(t, 'flash_fwd') == \
        {'calls': 1, 'seconds': 1.0}
    exp = trace_reduce.collective_exposed(t)
    assert exp['exposed_s'] == pytest.approx(1.1)
    assert exp['busy_s'] == pytest.approx(4.0)
    t['shapes'] = {('step_fn', 'fusion.1'): 'bf16[4,8]'}
    assert trace_reduce.top_ops(t, 2)[0] == \
        ['step_fn/fusion.1 bf16[4,8]', 1.0]
    assert trace_reduce.idle_gaps(t, 'perf.') == [['perf.feed_batch', 0.5]]


def test_a_shape_belongs_to_its_program_and_operation():
    """The compiler numbers every program's operations from nought:
    ``fusion.241`` of the prefill program and ``fusion.241`` of the
    decode program are two operations, each with its own shape."""
    ops = [('fusion.241', 0.0, 1.0), ('fusion.241', 2.0, 3.0),
           ('fusion.7', 6.0, 0.5)]
    mods = [('jit_forward_paged(11)', 0.0, 1.5),
            ('jit_decode_steps_paged(22)', 2.0, 3.5)]
    t = {'devices': {'/device:TPU:0': {'XLA Ops': ops,
                                       'XLA Modules': mods}},
         'host': [],
         'shapes': {('forward_paged', 'fusion.241'): 'bf16[512,4096]',
                    ('decode_steps_paged', 'fusion.241'):
                        's8[6144,16,8,128]'}}
    assert trace_reduce.top_ops(t) == [
        ['decode_steps_paged/fusion.241 s8[6144,16,8,128]', 3.0],
        ['forward_paged/fusion.241 bf16[512,4096]', 1.0],
        ['fusion.7', 0.5]]


def test_result_shape_from_the_hlo_text():
    long = ('%convert_multiply_fusion.10 = (bf16[16,8192,8,128]{3,2,1,0'
            ':T(8,128)(2,1)}, bf16[16,8192,8,128]{3,2,1,0}) fusion(s8[16]'
            ' %bitcast.257), kind=kLoop')
    assert trace_reduce.result_shape(long) == 'bf16[16,8192,8,128]'
    assert trace_reduce.result_shape('%copy.1 = s8[32,4096]{1,0} copy('
                                     's8[32,4096] %p)') == 's8[32,4096]'
    assert trace_reduce.result_shape('fusion.3') == ''


def test_only_calls_wholly_inside_the_traced_stretch_count():
    """Hand-made: tracing came on while the first call ran and went
    off while the third did; both are in the trace as far as the
    recording reaches, and neither is a whole call."""
    mods = [('jit_decode_steps_paged(1)', 0.0, 0.2),   # cut: 0.368 whole
            ('jit_decode_steps_paged(1)', 0.5, 0.368),
            ('jit_forward_paged(2)', 0.9, 0.098),
            ('jit_decode_steps_paged(1)', 1.0, 0.1)]   # cut
    ops = [('fusion.1', 0.0, 0.2), ('fusion.1', 0.5, 0.3),
           ('fusion.9', 0.9, 0.09), ('fusion.1', 1.0, 0.1)]
    t = {'devices': {'/device:TPU:0': {'XLA Ops': ops,
                                       'XLA Modules': mods}},
         'host': [('perf.trace_on', 0.0005, 0.0),
                  ('perf.trace_off', 1.05, 0.0)]}
    assert trace_reduce.traced_stretch(t) == (0.0005, 1.05)
    got = trace_reduce.module_times(t)
    assert got['jit_decode_steps_paged'] == {
        'calls': 1, 'seconds': pytest.approx(0.368)}
    assert got['jit_forward_paged']['calls'] == 1
    # A kernel's time is of the same calls.
    assert trace_reduce.op_seconds(t, r'fusion\.1$') == {
        'calls': 1, 'seconds': pytest.approx(0.3)}
    # Without the marks (and without the profiler's own calls in the
    # trace) nothing says where the edges are: every call counts.
    t['host'] = []
    assert trace_reduce.module_times(t)[
        'jit_decode_steps_paged']['calls'] == 3


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.window_of({'devices': {'/device:TPU:0': {}}, 'host': []})


@pytest.mark.parametrize('path', sorted(glob.glob(
    os.path.join(_DATA, '*.xplane.pb.gz'))) or [None])
def test_reduction_on_the_recorded_trace(path):
    if path is None:
        pytest.skip('no recorded trace in perf/tests/data')
    t = trace_reduce.load(path)
    assert t['devices'], 'the recorded trace has a TPU plane'
    start, end = trace_reduce.window_of(t)
    busy = trace_reduce.busy_seconds(t)
    assert 0 < busy <= (end - start) * (1 + 1e-9)
    mods = trace_reduce.module_times(t)
    assert any(name.startswith('jit_') for name in mods)
    top = trace_reduce.top_ops(t)
    assert top and all(' = ' not in name and not name.startswith(
        'while') for name, _ in top)
    # Every shape is kept under its program and operation, and the
    # names printed carry both.
    assert t['shapes'] and all(
        program == 'step_fn' for program, _ in t['shapes'])
    assert all(name.startswith('step_fn/') for name, _ in top)
    # The recorded run was three steps of the QLoRA train step, with
    # the three named flash kernels in each of its 32 layers.
    assert mods['jit_step_fn']['calls'] == 3
    for kernel in ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv'):
        assert trace_reduce.op_seconds(t, kernel)['calls'] == 96
    assert busy / (end - start) > 0.99
    # The stretch was traced between two steps (the profiler's own
    # start_trace and stop_trace calls are in the trace): all three
    # calls are whole. Had tracing come on inside the first step and
    # gone off inside the third, one whole call would be left, with
    # its 32 layers' kernels.
    on, off = trace_reduce.traced_stretch(t)
    first, _, third = sorted(
        (s, s + d) for _, s, d in
        t['devices'][sorted(t['devices'])[0]]['XLA Modules'])
    assert on <= first[0] and third[1] <= off
    t['host'] += [('perf.trace_on', first[0] + 0.5, 0.0),
                  ('perf.trace_off', third[1] - 0.5, 0.0)]
    cut = trace_reduce.module_times(t)['jit_step_fn']
    assert cut['calls'] == 1
    assert cut['seconds'] == pytest.approx(mods['jit_step_fn'][
        'seconds'] / 3, rel=1e-3)
    assert trace_reduce.op_seconds(t, 'flash_fwd')['calls'] == 32
