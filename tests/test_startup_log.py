"""The start-up log of ``utils/jax_runtime.py``: stages of a start-up
and one record per outermost compilation, on the ``perf_counter``
clock; the registry families and ``startup.*`` spans fed from it; the
engine's constructor marked in it. All on the CPU: what is asserted
is structure and counts, never a speed."""
import contextlib
import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu import metrics as metrics_lib
from skypilot_tpu import trace
from skypilot_tpu.utils import jax_runtime


@pytest.fixture(autouse=True)
def _listening():
    jax_runtime.listen()


@pytest.fixture
def not_ready(monkeypatch):
    """``mark_ready`` is for the life of a process: a test that
    calls it hands the log back as it found it."""
    log = jax_runtime._log  # pylint: disable=protected-access
    monkeypatch.setattr(log, 'ready_at', None)
    return log


@pytest.fixture
def runtime_log(caplog):
    """``skypilot_tpu``'s logger tree does not propagate to the root
    logger: caplog's handler goes on the module's own."""
    logger = logging.getLogger(jax_runtime.__name__)
    logger.addHandler(caplog.handler)
    yield caplog
    logger.removeHandler(caplog.handler)


def _programs():
    """Fresh jitted functions (a jit cache never carries over from
    one test to the next): ``outer`` calls ``inner``, which calls
    three ``jnp`` functions that are themselves jitted."""
    @jax.jit
    def inner(x):
        return jnp.where(x > 0, x, 0.) + jnp.linalg.norm(x) + \
            jnp.sum(x)

    @jax.jit
    def outer(x):
        return inner(x) * 2.0

    @jax.jit
    def other(x):
        return jnp.cumsum(x)

    return outer, other


def _new(before, program=None):
    """The compilations appended since ``before`` (a count), of
    ``program`` alone where one is named."""
    records = jax_runtime.startup_log()['compilations'][before:]
    return [r for r in records
            if program is None or r['program'] == program]


def _count():
    return len(jax_runtime.startup_log()['compilations'])


def _family(name):
    return {f.name: f for f in
            metrics_lib.registry().families()}[name]


class TestCompileAccount:

    def test_nested_traces_are_one_record(self):
        outer, _ = _programs()
        x = jnp.ones((7,))
        before = _count()
        t0 = time.perf_counter()
        jax.block_until_ready(outer(x))
        wall = time.perf_counter() - t0
        mine = _new(before, 'outer')
        assert len(mine) == 1, _new(before)
        record = mine[0]
        # inner, and the three jnp functions under it, moved a depth
        # and a count; none of them has a record of its own.
        assert record['inner_traces'] >= 4
        assert not _new(before, 'inner')
        assert 0.0 < record['trace_s'] <= wall
        assert record['trace_s'] + record['lower_s'] + \
            record['backend_s'] <= wall
        assert record['lowerings'] == 1
        assert record['start'] <= record['end']
        assert t0 <= record['start'] and \
            record['end'] <= t0 + wall

    def test_two_programs_are_attributed_by_name(self):
        outer, other = _programs()
        x = jnp.ones((5,))
        before = _count()
        outer(x)
        other(x)
        assert len(_new(before, 'outer')) == 1
        assert len(_new(before, 'other')) == 1
        # The lowering's and the backend's ``jit(outer)`` went to the
        # record the trace's ``outer`` opened.
        assert not [r for r in _new(before)
                    if r['program'].startswith('jit(')]
        for record in _new(before):
            assert record['backend_s'] > 0.0, record

    def test_a_second_call_of_the_same_shapes_appends_nothing(self):
        outer, _ = _programs()
        x = jnp.ones((6,))
        outer(x)
        before, totals = _count(), jax_runtime.startup_log()['totals']
        outer(x)
        assert _count() == before
        assert jax_runtime.startup_log()['totals'] == totals

    def test_a_new_shape_is_a_new_record(self):
        outer, _ = _programs()
        outer(jnp.ones((3,)))
        x = jnp.ones((4,))
        before = _count()
        outer(x)
        assert len(_new(before, 'outer')) == 1

    def test_a_lowering_alone_is_a_record_that_a_compile_completes(
            self):
        outer, _ = _programs()
        x = jnp.ones((9,))
        before = _count()
        lowered = outer.lower(x)
        mine = _new(before, 'outer')
        assert len(mine) == 1
        assert mine[0]['lowerings'] == 1 and \
            mine[0]['backend_s'] == 0.0
        lowered.compile()
        mine = _new(before, 'outer')
        assert len(mine) == 1, mine
        assert mine[0]['backend_s'] > 0.0

    def test_a_compile_inside_a_trace_stays_in_its_parents_record(
            self):
        @jax.jit
        def helper(n):
            return jnp.arange(5) * n

        @jax.jit
        def outer(x):
            with jax.ensure_compile_time_eval():
                c = helper(3)  # compiled and run while outer traces
            return x * c.sum()

        x = jnp.ones((5,))
        before = _count()
        totals = jax_runtime.startup_log()['totals']
        outer(x)
        (record,) = _new(before)
        assert record['program'] == 'outer'
        # Its own lowering and the ones nested in its trace: every
        # lowering event counts once, in the record and the totals.
        assert record['lowerings'] >= 2
        assert jax_runtime.startup_log()['totals']['lowerings'] - \
            totals['lowerings'] == record['lowerings']

    def test_a_fault_in_a_listener_never_reaches_the_compile(
            self, monkeypatch, runtime_log):
        log = jax_runtime._log  # pylint: disable=protected-access
        monkeypatch.setattr(log, 'faulted', False)

        def broken():
            raise RuntimeError('the account is broken')

        monkeypatch.setattr(log, 'thread_state', broken)
        outer, _ = _programs()
        got = outer(jnp.ones((41,)))
        assert got.shape == (41,)
        assert log.faulted
        errors = [r for r in runtime_log.records
                  if r.levelno == logging.ERROR]
        assert len(errors) == 1  # the first fault, with its traceback
        assert errors[0].exc_info is not None

    def test_totals_and_families_follow_the_records(self):
        outer, other = _programs()
        x = jnp.ones((11,))
        before = _count()
        totals = jax_runtime.startup_log()['totals']
        lowered = _family('skytpu_jit_lowerings_total').value
        outer(x)
        other(x)
        after = jax_runtime.startup_log()['totals']
        new = _new(before)
        assert after['lowerings'] - totals['lowerings'] == \
            sum(r['lowerings'] for r in new) == 2
        assert after['inner_traces'] - totals['inner_traces'] == \
            sum(r['inner_traces'] for r in new)
        for key in ('trace_s', 'lower_s', 'backend_s'):
            assert after[key] - totals[key] == pytest.approx(
                sum(r[key] for r in new))
        assert _family('skytpu_jit_lowerings_total').value - \
            lowered == 2
        # The seconds stay in the log: the registry has the three
        # counts an operator alerts on, and no more.
        assert sorted(f.name for f in metrics_lib.registry().families()
                      if f.name.startswith(('skytpu_jit_',
                                            'skytpu_startup_'))) == [
            'skytpu_jit_cache_misses_total',
            'skytpu_jit_lowerings_after_ready_total',
            'skytpu_jit_lowerings_total']

    def test_each_thread_has_its_own_depth_and_stage(self):
        outer, other = _programs()
        x = jnp.ones((13,))
        before = _count()

        def elsewhere():
            other(x)

        with jax_runtime.stage('test.threads'):
            th = threading.Thread(target=elsewhere, daemon=True)
            th.start()
            outer(x)
            th.join(timeout=120)
        assert not th.is_alive()
        (mine,) = _new(before, 'outer')
        (theirs,) = _new(before, 'other')
        assert mine['stage'] == 'test.threads'
        assert theirs['stage'] is None  # no stage open on that thread
        assert mine['thread'] != theirs['thread']

    def test_the_persistent_cache_shows_as_miss_then_hit(self,
                                                         tmp_path):
        names = ('jax_compilation_cache_dir',
                 'jax_persistent_cache_min_compile_time_secs',
                 'jax_persistent_cache_min_entry_size_bytes')
        saved = {n: getattr(jax.config, n) for n in names}
        from jax.experimental.compilation_cache import \
            compilation_cache
        try:
            jax.config.update(names[0], str(tmp_path / 'cache'))
            jax.config.update(names[1], 0.0)
            jax.config.update(names[2], -1)
            compilation_cache.reset_cache()
            x = jnp.ones((17,))
            before = _count()
            hits = jax_runtime.runtime_facts()['cache_hits']
            _programs()[0](x)
            (cold,) = _new(before, 'outer')
            assert (cold['cache_misses'], cold['cache_hits']) == (1, 0)
            before = _count()
            _programs()[0](x)  # a new function object, the same module
            (warm,) = _new(before, 'outer')
            assert (warm['cache_misses'], warm['cache_hits']) == (0, 1)
            assert 0.0 < warm['retrieval_s'] <= warm['backend_s']
            assert jax_runtime.runtime_facts()['cache_hits'] == \
                hits + 1
        finally:
            for n, v in saved.items():
                jax.config.update(n, v)
            compilation_cache.reset_cache()

    def test_runtime_facts_keeps_its_keys(self):
        facts = jax_runtime.runtime_facts()
        assert set(facts) == {'compiled', 'cache_hits', 'cache_dir',
                              'memory'}
        totals = jax_runtime.startup_log()['totals']
        assert facts['compiled'] == totals['cache_misses']
        assert facts['cache_hits'] == totals['cache_hits']


class TestStages:

    def test_a_stage_inside_a_stage(self):
        outer, other = _programs()
        x = jnp.ones((19,))
        n = len(jax_runtime.startup_log()['stages'])
        with jax_runtime.stage('test.outer', flavour='plain'):
            outer(x)
            with jax_runtime.stage('test.outer.inner', width=3):
                other(x)
        inner_rec, outer_rec = jax_runtime.startup_log()['stages'][n:]
        assert inner_rec['name'] == 'test.outer.inner'
        assert inner_rec['parent'] == 'test.outer'
        assert inner_rec['attrs'] == {'width': 3}
        assert outer_rec['name'] == 'test.outer'
        assert outer_rec['parent'] is None
        assert outer_rec['attrs'] == {'flavour': 'plain'}
        # A stage's self time is its seconds less its children's.
        assert inner_rec['self_s'] == inner_rec['seconds']
        assert outer_rec['self_s'] == pytest.approx(
            outer_rec['seconds'] - inner_rec['seconds'])
        assert outer_rec['start'] <= inner_rec['start'] <= \
            inner_rec['end'] <= outer_rec['end']
        # The compile account's change inside each.
        assert inner_rec['lowerings'] == 1
        assert outer_rec['lowerings'] == 2
        assert 0.0 < inner_rec['trace_s'] < outer_rec['trace_s']
        assert outer_rec['inner_traces'] > inner_rec['inner_traces']
        assert outer_rec['backend_s'] <= outer_rec['seconds']

    def test_as_a_decorator_each_call_is_a_stage_of_its_own(self):
        @jax_runtime.stage('test.decorated', kind='call')
        def build(value):
            """Doc kept."""
            return value + 1

        n = len(jax_runtime.startup_log()['stages'])
        assert build(1) == 2 and build(2) == 3
        assert build.__name__ == 'build' and build.__doc__ == \
            'Doc kept.'
        new = jax_runtime.startup_log()['stages'][n:]
        assert [r['name'] for r in new] == ['test.decorated'] * 2
        assert all(r['attrs'] == {'kind': 'call'} for r in new)

    def test_the_reply_sums_a_repeated_stage(self):
        for _ in range(2):
            with jax_runtime.stage('test.repeated'):
                time.sleep(0.01)
        records = [r for r in jax_runtime.startup_log()['stages']
                   if r['name'] == 'test.repeated']
        total = sum(r['seconds'] for r in records)
        assert len(records) >= 2
        assert jax_runtime.startup_seconds()['test.repeated'] == \
            pytest.approx(total, abs=1e-3)

    def test_a_stage_that_raises_is_still_recorded(self):
        n = len(jax_runtime.startup_log()['stages'])
        with pytest.raises(RuntimeError):
            with jax_runtime.stage('test.raises'):
                raise RuntimeError('boom')
        (record,) = jax_runtime.startup_log()['stages'][n:]
        assert record['name'] == 'test.raises'
        # ... and is off the thread's stack.
        with jax_runtime.stage('test.after'):
            pass
        assert jax_runtime.startup_log()['stages'][-1]['parent'] is None

    def test_under_a_trace_context_stages_are_spans(self):
        _, other = _programs()
        x = jnp.ones((23,))
        with trace.span('job.run', new_trace=True) as root:
            tid = root.context.trace_id
            with jax_runtime.stage('test.traced', slots=2):
                with jax_runtime.stage('test.traced.child'):
                    other(x)
        spans = trace.collect.load_spans(
            [os.environ['SKYTPU_STATE_DIR']], trace_id=tid)
        by_name = {s['name']: s for s in spans}
        assert set(by_name) == {'job.run', 'startup.test.traced',
                                'startup.test.traced.child'}
        stage_span = by_name['startup.test.traced']
        child = by_name['startup.test.traced.child']
        assert stage_span['parent_id'] == by_name['job.run']['span_id']
        assert child['parent_id'] == stage_span['span_id']
        assert stage_span['attrs']['slots'] == 2
        # What was compiled inside rides on the span.
        assert child['attrs']['lowerings'] == 1
        assert child['attrs']['trace_s'] > 0
        assert stage_span['start'] <= child['start'] <= \
            child['end'] <= stage_span['end']

    def test_a_stage_is_never_the_ambient_context(self):
        """A span opened inside a stage keeps the parent it would
        have had without it, and a stage closed out of order (an
        ``ExitStack`` of two, as the recipes hold them) leaves the
        ambient context as it found it."""
        with trace.span('job.run', new_trace=True) as root:
            tid = root.context.trace_id
            with contextlib.ExitStack() as starting:
                starting.enter_context(jax_runtime.stage('test.held'))
                assert trace.current() == root.context
                starting.enter_context(
                    jax_runtime.stage('test.held.last'))
                with trace.span('ckpt.save'):
                    pass
            assert trace.current() == root.context
        by_name = {s['name']: s for s in trace.collect.load_spans(
            [os.environ['SKYTPU_STATE_DIR']], trace_id=tid)}
        job = by_name['job.run']['span_id']
        assert by_name['ckpt.save']['parent_id'] == job
        assert by_name['startup.test.held']['parent_id'] == job
        assert by_name['startup.test.held.last']['parent_id'] == \
            by_name['startup.test.held']['span_id']

    def test_train_steps_stay_under_the_job_not_the_start_up(self):
        """``recipes/finetune``'s shape: ``train.start`` and its
        ``first_step`` are open when the instrumented step is first
        called and closed after it returns. Every ``train.step``,
        the first and those after the start-up closed, is a child of
        the job's context, and a span inside a step is the step's."""
        from skypilot_tpu.parallel import instrument_train_step

        def plain_step(state, batch):
            with trace.span('ckpt.save'):
                pass
            return state, {}

        step_fn = instrument_train_step(plain_step, tokens_per_step=8)
        with trace.span('job.run', new_trace=True) as root:
            tid = root.context.trace_id
            with contextlib.ExitStack() as starting:
                starting.enter_context(
                    jax_runtime.stage('test.train.start'))
                starting.enter_context(
                    jax_runtime.stage('test.train.start.first_step'))
                for step in range(3):  # the third closes the second
                    step_fn(None, {})
                    if step == 0:
                        starting.close()
        spans = trace.collect.load_spans(
            [os.environ['SKYTPU_STATE_DIR']], trace_id=tid)
        job = root.context.span_id
        steps = sorted((s for s in spans if s['name'] == 'train.step'),
                       key=lambda s: s['attrs']['step'])
        assert [s['attrs']['step'] for s in steps] == [0, 1]
        assert [s['parent_id'] for s in steps] == [job, job]
        saves = [s['parent_id'] for s in spans
                 if s['name'] == 'ckpt.save']
        assert saves[:2] == [s['span_id'] for s in steps]
        by_name = {s['name']: s for s in spans}
        start = by_name['startup.test.train.start']
        assert start['parent_id'] == job
        assert by_name['startup.test.train.start.first_step'][
            'parent_id'] == start['span_id']

    def test_without_a_trace_context_nothing_is_written(self):
        with jax_runtime.stage('test.untraced'):
            pass
        assert trace.collect.load_spans(
            [os.environ['SKYTPU_STATE_DIR']]) == []
        assert jax_runtime.startup_log()['stages'][-1]['name'] == \
            'test.untraced'

    def test_the_env_stamp_is_a_trace_context(self, monkeypatch):
        """What ``launch``, ``jobs`` and ``serve up`` hand every
        process they start."""
        monkeypatch.setenv(trace.ENV_CONTEXT,
                           '00-' + 'ab' * 16 + '-' + 'cd' * 8 + '-01')
        with jax_runtime.stage('test.stamped'):
            pass
        (span,) = trace.collect.load_spans(
            [os.environ['SKYTPU_STATE_DIR']], trace_id='ab' * 16)
        assert span['name'] == 'startup.test.stamped'
        assert span['parent_id'] == 'cd' * 8


class TestAfterReady:

    def test_a_lowering_after_ready_is_counted_and_named(
            self, not_ready, runtime_log):
        outer, _ = _programs()
        counter = _family('skytpu_jit_lowerings_after_ready_total')
        outer(jnp.ones((29,)))
        before = counter.value
        assert jax_runtime.startup_log()['ready_at'] is None
        jax_runtime.mark_ready()
        ready_at = jax_runtime.startup_log()['ready_at']
        assert ready_at is not None
        jax_runtime.mark_ready()  # the first call stands
        assert jax_runtime.startup_log()['ready_at'] == ready_at
        outer(jnp.ones((29,)))  # warmed: nothing is lowered
        assert counter.value == before
        x = jnp.ones((31,))
        before = counter.value
        runtime_log.clear()
        outer(x)  # a shape the start-up did not warm
        assert counter.value == before + 1
        warnings = [r.getMessage() for r in runtime_log.records
                    if r.levelno == logging.WARNING]
        assert len(warnings) == 1, warnings
        assert 'outer' in warnings[0] and \
            'after ready' in warnings[0]

    def test_before_ready_nothing_is_counted_or_logged(
            self, not_ready, runtime_log):
        outer, _ = _programs()
        counter = _family('skytpu_jit_lowerings_after_ready_total')
        before = counter.value
        outer(jnp.ones((37,)))
        assert counter.value == before
        assert not runtime_log.records


class TestEngineBuild:

    @pytest.fixture(scope='class')
    def built(self):
        from skypilot_tpu.models import llama
        from skypilot_tpu.ops import decode_attention as da
        from skypilot_tpu.serve import batching
        config = llama.get_config('tiny')
        params = llama.init_params(config, jax.random.PRNGKey(0))
        jax_runtime.listen()
        n = len(jax_runtime.startup_log()['stages'])
        c = len(jax_runtime.startup_log()['compilations'])
        # Rows and length no other test file builds: the jit caches
        # of the engine's module-level programs outlive an engine,
        # and a shape met before is not lowered again.
        engine = batching.BatchingEngine(params, config, slots=5,
                                         max_seq=112)
        engine.close()
        log = jax_runtime.startup_log()
        return {'stages': log['stages'][n:],
                'compilations': log['compilations'][c:],
                'widths': list(da.view_widths(
                    engine.max_blocks_per_req))}

    def test_the_constructor_is_one_stage_with_four_children(
            self, built):
        (build,) = [s for s in built['stages']
                    if s['name'] == 'engine.build']
        assert built['stages'][-1] is build  # it ends last
        children = [s['name'] for s in built['stages']
                    if s['parent'] == 'engine.build']
        assert children == ['engine.build.pool',
                            'engine.build.prewarm_copy',
                            'engine.build.prewarm_verify',
                            'engine.build.prewarm_decode']
        inside = sum(s['seconds'] for s in built['stages']
                     if s['parent'] == 'engine.build')
        assert build['self_s'] == pytest.approx(
            build['seconds'] - inside)
        assert 0.0 <= build['self_s']

    def test_one_decode_child_a_width(self, built):
        widths = [s for s in built['stages'] if s['name'] ==
                  'engine.build.prewarm_decode.width']
        assert [s['attrs']['width'] for s in widths] == \
            built['widths']
        assert all(s['parent'] == 'engine.build.prewarm_decode'
                   for s in widths)
        # One decode program a width, each lowered inside its stage.
        programs = [c for c in built['compilations']
                    if c['program'] == 'decode_steps_paged']
        assert len(programs) == len(built['widths'])
        assert {c['stage'] for c in programs} == {
            'engine.build.prewarm_decode.width'}
        assert all(s['lowerings'] >= 1 for s in widths)

    def test_the_prewarms_name_their_programs(self, built):
        by_stage = {}
        for c in built['compilations']:
            by_stage.setdefault(c['stage'], set()).add(c['program'])
        assert 'copy_pool_block' in \
            by_stage['engine.build.prewarm_copy']
        assert 'verify_step_paged' in \
            by_stage['engine.build.prewarm_verify']
        (decode,) = [s for s in built['stages'] if s['name'] ==
                     'engine.build.prewarm_decode']
        assert decode['lowerings'] >= len(built['widths'])
        assert decode['trace_s'] + decode['lower_s'] + \
            decode['backend_s'] <= decode['seconds']
