"""The scheduler iteration as the unit of account (ISSUE 25):
``trace.phase`` (loop phases on the profiler's clock), the engine
loop's ``engine.*`` phases, the ``skytpu_batch_iteration*`` /
``host_gap`` / ``prefill_*`` counters, and the named scopes of the
decode program. CPU, tiny model; a CPU run checks names, nesting and
counts, never a time."""
import contextlib
import glob
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu import metrics as metrics_lib
from skypilot_tpu import trace as trace_lib
from skypilot_tpu.models import llama
from skypilot_tpu.serve import batching
from skypilot_tpu.serve.batching import BatchingEngine

FAMILIES = ('iterations', 'iteration_seconds', 'host_gap_seconds',
            'prefill_chunks', 'prefill_tokens', 'prefill_bucket_tokens',
            'prefill_keys_read', 'prefill_keys_view',
            'decode_dispatches')
TOP_PHASES = ('sweep', 'admit', 'prefill', 'dispatch', 'device_wait',
              'emit', 'gauges')
PREFIX = 'skytpu.engine.'


@pytest.fixture(scope='module')
def setup():
    config = llama.get_config('tiny')
    params = llama.init_params(config, jax.random.PRNGKey(0))
    return config, params


def _engine(setup, **kw):
    config, params = setup
    kw = dict(dict(slots=2, max_seq=64, steps_per_dispatch=2,
                   block_size=8, prefill_chunk=8,
                   max_num_batched_tokens=16, prefix_caching=False),
              **kw)
    return BatchingEngine(params, config, **kw)


def _prompt(k, n=20):
    return [(i * k) % 250 + 1 for i in range(n)]


def _drain(q):
    out = []
    while True:
        t = q.get(timeout=120)
        if t is None:
            return out
        assert not isinstance(t, BaseException), t
        out.append(t)


def _counters():
    fams = batching._engine_metrics()
    return {k: fams[k].value for k in FAMILIES}


def _delta(before):
    after = _counters()
    return {k: after[k] - before[k] for k in FAMILIES}


def _passes(engine, n):
    """Wait until the loop has begun ``n`` more passes: every pass
    begun before the call is then fully accounted."""
    target = engine._iter_n + n
    deadline = time.monotonic() + 60
    while engine._iter_n < target:
        assert time.monotonic() < deadline, 'engine loop stood still'
        engine.wake.set()
        time.sleep(0.005)


def _chunks_from_events(events, prefill_chunk):
    """(real, bucket) of every prefill chunk, rebuilt from the
    engine's own event log: an admission starts a row at its cached
    offset, each chunk event carries the offset it reached."""
    off, out = {}, []
    for e in events:
        if e[0] == 'admit':
            off[e[1]] = e[2]
        elif e[0] == 'prefill_chunk':
            real = e[2] - off[e[1]]
            off[e[1]] = e[2]
            bucket = 1
            while bucket < real:
                bucket *= 2
            out.append((real, min(bucket, prefill_chunk)))
    return out


# ---------------------------------------------------------------------
# trace.phase
# ---------------------------------------------------------------------


class TestPhasePrimitive:

    def test_null_context_and_no_jax_import_without_jax(self, tmp_path):
        """The tracing package stays stdlib-only at import, and a
        process that never imported jax gets a null context that
        writes nothing to the jsonl sink."""
        code = (
            'import contextlib, os, sys\n'
            'from skypilot_tpu import trace\n'
            'assert "jax" not in sys.modules\n'
            'with trace.span("launch", new_trace=True):\n'
            '    p = trace.phase("engine.admit", queued=3)\n'
            '    assert isinstance(p, contextlib.nullcontext), p\n'
            '    with p:\n'
            '        pass\n'
            'assert "jax" not in sys.modules\n'
            'trace.reset_sink()\n'
            'lines = [l for f in os.listdir(trace.sink_dir())\n'
            '         for l in open(os.path.join(trace.sink_dir(), f))]\n'
            'assert len(lines) == 1 and "launch" in lines[0], lines\n')
        env = {'SKYTPU_STATE_DIR': str(tmp_path), 'PATH': '/usr/bin',
               'PYTHONPATH': ':'.join(p for p in sys.path if p)}
        done = subprocess.run([sys.executable, '-c', code], env=env,
                              capture_output=True, text=True,
                              timeout=60)
        assert done.returncode == 0, done.stderr

    def test_with_jax_it_is_a_trace_annotation_off_the_sink(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv('SKYTPU_TRACE_DIR', str(tmp_path))
        trace_lib.reset_sink()
        with trace_lib.span('launch', new_trace=True) as root:
            with trace_lib.phase('engine.admit', queued=3) as p:
                # Not a request span: the ambient context is the
                # caller's, untouched.
                assert trace_lib.current() == root.context
            assert isinstance(p, jax.profiler.TraceAnnotation)
        trace_lib.reset_sink()
        lines = [l for f in glob.glob(str(tmp_path / '*.jsonl'))
                 for l in open(f)]
        assert len(lines) == 1 and '"launch"' in lines[0], lines


# ---------------------------------------------------------------------
# The loop's phases in the profiler's own trace
# ---------------------------------------------------------------------


@pytest.fixture(scope='module')
def profiled(setup, tmp_path_factory):
    """One engine run inside a profiler session; the engine's spans
    by host thread: ``[[(name, start_ns, end_ns, stats)]]``."""
    from jax.profiler import ProfileData
    engine = _engine(setup)
    try:
        engine.generate(_prompt(3), 4)          # compile outside
        trace_dir = str(tmp_path_factory.mktemp('xplane'))
        with jax.profiler.trace(trace_dir):
            queues = [engine.submit(_prompt(k), 4) for k in (3, 5, 7)]
            for q in queues:
                assert len(_drain(q)) == 4
            _passes(engine, 2)                   # and park
    finally:
        engine.close()
    path, = glob.glob(trace_dir + '/plugins/profile/*/*.xplane.pb')
    threads = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name[len(PREFIX):].split('#')[0], e.start_ns,
                      e.start_ns + e.duration_ns,
                      {k: v for k, v in e.stats})
                     for e in line.events if e.name.startswith(PREFIX)]
            if spans:
                threads.append(sorted(spans, key=lambda s: s[1]))
    # The session records every thread of the process. Under xdist a
    # worker runs other files' tests first, and an engine one of them
    # left open keeps waking every half second to a pass that finds
    # nothing (``dispatch`` with rows 0, no ``device_wait``): a
    # second thread of ``engine.*`` spans that is not this engine's.
    # The threads that waited on the device are this run's; that
    # there is exactly one is the first test's to assert.
    return [t for t in threads
            if any(s[0] == 'device_wait' for s in t)]


class TestLoopPhases:

    def test_every_phase_is_in_the_xplane(self, profiled):
        names = {s[0] for spans in profiled for s in spans}
        for want in ('iteration', 'sweep', 'admit', 'prefill',
                     'prefill_chunk', 'first_token', 'dispatch',
                     'device_wait', 'emit', 'gauges', 'idle_wait'):
            assert want in names, (want, sorted(names))

    def test_one_thread_carries_them(self, profiled):
        assert len(profiled) == 1, [len(t) for t in profiled]

    def test_top_level_phases_partition_their_iteration(self, profiled):
        spans, = profiled
        iterations = [s for s in spans if s[0] == 'iteration']
        # The session may open or close inside a pass: its phases
        # are then recorded without it. Only whole passes count.
        tops = [s for s in spans if s[0] in TOP_PHASES
                and iterations[0][1] <= s[1]
                and s[2] <= iterations[-1][2]]
        assert len(iterations) >= 3 and tops
        for a, b in zip(tops, tops[1:]):
            assert a[2] <= b[1], (a, b)          # no overlap
        for a, b in zip(iterations, iterations[1:]):
            assert a[2] <= b[1], (a, b)
        for s in tops:
            assert any(it[1] <= s[1] and s[2] <= it[2]
                       for it in iterations), s
        # idle_wait lies outside every iteration
        for s in spans:
            if s[0] == 'idle_wait':
                assert not any(it[1] < s[2] and s[1] < it[2]
                               for it in iterations), s

    def test_children_lie_inside_the_prefill_phase(self, profiled):
        spans, = profiled
        prefills = [s for s in spans if s[0] == 'prefill']
        kids = [s for s in spans
                if s[0] in ('prefill_chunk', 'first_token')]
        assert kids
        for s in kids:
            assert any(p[1] <= s[1] and s[2] <= p[2]
                       for p in prefills), s

    def test_attributes_are_taken_at_entry(self, profiled):
        spans, = profiled
        by = {}
        for s in spans:
            by.setdefault(s[0], []).append(s[3])
        ns = [st['n'] for st in by['iteration']]
        assert ns == list(range(ns[0], ns[0] + len(ns)))
        assert all(set(st) == {'n', 'queued'}
                   for st in by['iteration'])
        # three 20-token prompts in 8-token chunks: 8 + 8 + 4 each
        chunks = by['prefill_chunk']
        assert sorted((c['real'], c['bucket'], c['offset'])
                      for c in chunks) == sorted(
            [(8, 8, 0), (8, 8, 8), (4, 4, 16)] * 3)
        assert {c['row'] for c in chunks} <= {0, 1}
        assert all(st['kind'] == 'decode' and st['rows'] >= 1
                   for st in by['device_wait'])
        assert all(st['steps'] == 2 for st in by['dispatch'])
        assert len(by['first_token']) == 3
        assert len(by['device_wait']) == len(by['emit'])


# ---------------------------------------------------------------------
# Iteration counters
# ---------------------------------------------------------------------


class TestIterationCounters:

    def test_counts_match_the_event_log(self, setup):
        before = _counters()
        engine = _engine(setup)
        prompts = [_prompt(3, 20), _prompt(5, 13), _prompt(7, 8),
                   _prompt(11, 27)]
        try:
            queues = [engine.submit(p, 5) for p in prompts]
            for q in queues:
                assert len(_drain(q)) == 5
        finally:
            engine.close()                       # joins the loop
        d = _delta(before)
        events = list(engine.events)
        assert not [e for e in events if e[0] == 'preempt']
        chunks = _chunks_from_events(events, 8)
        assert d['iterations'] > 0
        assert d['prefill_tokens'] == sum(len(p) for p in prompts)
        assert d['prefill_chunks'] == len(chunks) == \
            len([e for e in events if e[0] == 'prefill_chunk'])
        assert d['prefill_bucket_tokens'] == sum(b for _, b in chunks)
        assert d['prefill_bucket_tokens'] >= d['prefill_tokens']
        assert d['prefill_bucket_tokens'] > d['prefill_tokens'], \
            'the 13-token prompt pads its last chunk (5 -> 8)'
        assert d['decode_dispatches'] == \
            len([e for e in events if e[0] == 'decode'])
        assert d['iterations'] >= d['decode_dispatches']
        assert 0 <= d['host_gap_seconds'] <= d['iteration_seconds']
        assert d['host_gap_seconds'] > 0

    def test_a_verify_dispatch_counts_once(self):
        # A 16-token vocabulary: greedy decode loops at once, so the
        # n-gram drafter has drafts (tests/test_speculative.py).
        import dataclasses
        config = dataclasses.replace(llama.get_config('tiny'),
                                     vocab_size=16)
        params = llama.init_params(config, jax.random.PRNGKey(0))
        before = _counters()
        engine = _engine((config, params), max_seq=96, draft_k=8,
                         max_num_batched_tokens=64, speculative=True)
        try:
            engine.generate(([3, 9, 4, 1] * 5)[:18], 40)
        finally:
            engine.close()
        d = _delta(before)
        events = list(engine.events)
        assert [e for e in events if e[0] == 'verify']
        assert d['decode_dispatches'] == \
            len([e for e in events if e[0] == 'decode'])

    def test_a_parked_engine_adds_nothing(self, setup):
        engine = _engine(setup)
        try:
            _passes(engine, 2)
            idle = _counters()
            _passes(engine, 3)
            assert _counters() == idle
            engine.generate(_prompt(3), 3)
            _passes(engine, 2)       # the last working pass is over
            worked = _counters()
            assert worked['iterations'] > idle['iterations']
            _passes(engine, 3)
            assert _counters() == worked
        finally:
            engine.close()

    def test_a_prefix_hit_adds_only_its_uncached_tokens(self, setup):
        engine = _engine(setup, prefix_caching=True)
        prompt = _prompt(3, 30)
        try:
            first = _counters()
            out = engine.generate(prompt, 3)
            _passes(engine, 2)
            cold = _delta(first)
            second = _counters()
            assert engine.generate(prompt, 3) == out
            _passes(engine, 2)
            warm = _delta(second)
        finally:
            engine.close()
        admits = [e for e in engine.events if e[0] == 'admit']
        assert [a[2] for a in admits][0] == 0 and admits[1][2] >= 16
        assert cold['prefill_tokens'] == len(prompt)
        assert warm['prefill_tokens'] == len(prompt) - admits[1][2]
        assert warm['prefill_chunks'] < cold['prefill_chunks']

    @pytest.mark.parametrize('cached', [0, 16])
    def test_the_key_counters_follow_the_tiles(self, setup,
                                               monkeypatch, cached):
        """``prefill_keys_read`` rises by the whole key tiles up to a
        chunk's offset plus its bucket, ``prefill_keys_view`` by
        ``max_seq`` a chunk: over a 30-token prompt in chunks of 8
        at tiles of 16 positions, cold (offsets 0, 8, 16, 24) and
        behind a prefix hit of two blocks (offsets 16, 24)."""
        from skypilot_tpu.ops import decode_attention as da
        monkeypatch.setattr(da, 'chunk_tile_blocks', lambda bs, mb: 2)
        engine = _engine(setup, prefix_caching=True)
        shared, tail = _prompt(3, 16), _prompt(7, 14)
        try:
            if cached:
                engine.generate(shared + _prompt(5, 4), 2)
                _passes(engine, 2)
            before = _counters()
            engine.generate(shared + tail, 2)
            _passes(engine, 2)
            d = _delta(before)
        finally:
            engine.close()
        assert [e[2] for e in engine.events
                if e[0] == 'admit'][-1] == cached
        offsets = list(range(cached, 30, 8))
        assert d['prefill_chunks'] == len(offsets)
        assert d['prefill_bucket_tokens'] == 8 * len(offsets)
        assert d['prefill_keys_view'] == 64 * len(offsets)
        assert d['prefill_keys_read'] == sum(
            -(-off // 16) * 16 + 8 for off in offsets) == \
            (64 if cached else 96)
        assert d['prefill_keys_read'] < d['prefill_keys_view']


# ---------------------------------------------------------------------
# Named scopes in the decode program
# ---------------------------------------------------------------------


def _op_names(compiled_text):
    """The ``op_name`` metadata of a compiled program's operations
    (its text also lists source function names, which prove
    nothing)."""
    return [l.split('op_name="')[1].split('"')[0]
            for l in compiled_text.splitlines() if 'op_name="' in l]


class TestProgramScopes:
    """Scopes change HLO metadata only. Compiled at the benchmark's
    rehearsal size (perf/configs/mistral-7b-int8-serve.json: 4 rows,
    max_seq 256, 96 blocks of 16, int8 KV, 8 steps a dispatch)."""

    def _args(self, setup):
        config, params = setup
        slots, max_seq, nb, bs = 4, 256, 96, 16
        rng = np.random.default_rng(0)
        shape = (config.n_layers, nb, bs, config.n_kv_heads,
                 config.head_dim)
        caches = tuple(
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
            for _ in range(2)) + tuple(
            jnp.asarray(rng.uniform(0.001, 0.02, shape[:-1]),
                        jnp.bfloat16) for _ in range(2))
        tables = jnp.asarray(
            1 + rng.permutation(nb - 1)[:slots * (max_seq // bs)]
            .reshape(slots, max_seq // bs), jnp.int32)
        pos = jnp.asarray([40, 17, 100, 3], jnp.int32)
        tokens = jnp.asarray([5, 9, 250, 1], jnp.int32)
        active = jnp.asarray([True, True, True, False])
        return (params, tokens, caches, tables, pos, active), \
            (config, 8, bs)

    def test_scopes_reach_the_compiled_text_and_change_no_bit(
            self, setup, monkeypatch):
        args, static = self._args(setup)

        def scoped(*a):
            return batching.decode_steps_paged(*a, *static)

        def unscoped(*a):
            return batching.decode_steps_paged(*a, *static)

        compiled = jax.jit(scoped).lower(*args).compile()
        op_names = _op_names(compiled.as_text())
        for scope in ('paged_gather', 'kv_dequant', 'decode_attention',
                      'kv_write', 'qkv_proj', 'o_proj', 'mlp',
                      'sampler'):
            assert any(scope in n for n in op_names), scope
        monkeypatch.setattr(
            jax, 'named_scope',
            lambda name: contextlib.nullcontext())
        plain = jax.jit(unscoped).lower(*args).compile()
        assert not [n for n in _op_names(plain.as_text())
                    if 'paged_gather' in n or 'kv_dequant' in n]
        got, want = compiled(*args), plain(*args)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_prefill_and_verify_programs_carry_the_scopes(self, setup):
        from skypilot_tpu.models import decode
        (params, tokens, caches, tables, pos, _), (config, _, bs) = \
            self._args(setup)
        prefill = jax.jit(decode.forward_paged,
                          static_argnums=(6, 7)).lower(
            params, jnp.zeros((1, 32), jnp.int32), caches, tables[0],
            jnp.asarray(16, jnp.int32), jnp.asarray(20, jnp.int32),
            config, bs).compile().as_text()
        verify = jax.jit(batching.verify_step_paged,
                         static_argnums=(6, 7, 8)).lower(
            params, jnp.zeros((4, 3), jnp.int32), caches, tables, pos,
            jnp.asarray([3, 1, 2, 0], jnp.int32), config, 3,
            bs).compile().as_text()
        for text in (prefill, verify):
            op_names = _op_names(text)
            for scope in ('paged_gather', 'kv_dequant', 'kv_write',
                          'qkv_proj', 'mlp'):
                assert any(scope in n for n in op_names), scope
