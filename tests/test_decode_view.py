"""The decode dispatch reads the block table no further than a
prewarmed width that holds its longest active row
(ops/decode_attention.view_widths, models/decode.decode_steps_paged's
``view_blocks``, serve/batching.BatchingEngine._view_blocks).

Tiny models on the CPU, every case over a plain and a looped stack
(the engine's also over one with window layers and experts, where
the cut is the global layers' table) and over an int8 and a float
pool: the narrower program has to emit the whole-table program's
tokens, touch no block of a row it does not read, and exist before
the first request.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import harness
from skypilot_tpu.models import llama
from skypilot_tpu.models.decode import decode_steps_paged
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.batching import BatchingEngine

_BLOCK = 8
_STACKS = ['tiny', 'tiny-loop']
# The engine sizes a window group by itself: the stack with window
# and global layers and a share of the experts rides the engine case.
_ENGINE_STACKS = _STACKS + ['tiny-window-moe']


def _setup(name):
    config = llama.get_config(name)
    return config, llama.init_params(config, jax.random.PRNGKey(7))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


# ---------------------------------------------------------------------
# The width rule
# ---------------------------------------------------------------------


@pytest.mark.parametrize('table_blocks, want', [
    (256, (32, 64, 160, 192, 224, 256)),   # Mistral's cells
    (65, (9, 18, 45, 54, 63, 65)),         # Ouro's cell
    (768, (96, 192, 480, 576, 672, 768)),  # the global layers' table
    (8, (1, 2, 5, 6, 7, 8)),
    (5, (1, 2, 5)),
    (1, (1,)),
])
def test_widths_are_eighths_of_the_table_and_end_on_it(table_blocks,
                                                       want):
    assert da.view_widths(table_blocks) == want


@pytest.mark.parametrize('table_blocks', [1, 5, 8, 65, 100, 256])
@pytest.mark.parametrize('n', [1, 8])
def test_chosen_width_covers_longest_plus_steps(table_blocks, n):
    widths = da.view_widths(table_blocks)
    assert list(widths) == sorted(set(widths))
    assert widths[-1] == table_blocks and len(widths) <= 8
    chosen = [da.view_width(widths, longest + n, _BLOCK)
              for longest in range(table_blocks * _BLOCK + 1)]
    assert chosen == sorted(chosen)                      # monotone
    for longest, width in enumerate(chosen):
        assert width in widths
        if longest + n <= table_blocks * _BLOCK:
            assert width * _BLOCK >= longest + n         # covers
            narrower = [w for w in widths if w < width]
            assert all(w * _BLOCK < longest + n for w in narrower)
        else:
            assert width == table_blocks                 # the top


@pytest.mark.parametrize('table_blocks, positions, want', [
    (256, 3072, 192),     # a prompt clipped at 3,072 fills 6/8 ...
    (256, 3073, 224),     # ... and its first dispatch needs 7/8,
    (256, 3584, 224),     # as does the longest chat request's last.
    (256, 3585, 256),
    (65, 54 * 16 + 1, 63),
    (65, 63 * 16 + 1, 65),
    (768, 9217, 672),
    (768, 10752, 672),
    (768, 10753, 768),
    (8, 6 * 16 + 1, 7),
    (8, 7 * 16 + 1, 8),
])
def test_a_row_past_six_eighths_takes_seven_and_past_seven_the_table(
        table_blocks, positions, want):
    assert da.view_width(da.view_widths(table_blocks), positions,
                         16) == want


# ---------------------------------------------------------------------
# The step: a narrower view is the same step, and a row it does not
# read survives it
# ---------------------------------------------------------------------


@pytest.mark.parametrize('kv_int8', [False, True])
@pytest.mark.parametrize('name', _STACKS)
def test_narrow_step_equals_whole_table_and_spares_unread_rows(
        name, kv_int8):
    """Row 0 decodes inside the first two columns. Row 1 is parked
    as a row in prefill is (position = the table's capacity), row 2
    is inactive at a position of its own past the width: neither is
    read, and under the cut table neither's write may land in a
    block it owns (``write_index`` clips the column: only its bound
    check keeps the write out of column ``view_blocks - 1``)."""
    config, params = _setup(name)
    table_blocks, num_blocks, steps, view = 8, 20, 4, 2
    pools = kv_pool.KVBlockPool(config, num_blocks, _BLOCK,
                                kv_int8=kv_int8).caches
    rng = np.random.default_rng(11)

    def noise(p):
        if p is None:
            return None
        if p.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, p.shape),
                               jnp.int8)
        return jnp.asarray(rng.standard_normal(p.shape), p.dtype)

    pools = tuple(noise(p) for p in pools)
    tables = jnp.asarray(
        [[1, 2] + [da.SCRATCH_BLOCK] * 6,
         list(range(3, 11)), list(range(11, 19))], jnp.int32)
    pos = jnp.asarray([5, table_blocks * _BLOCK, 40], jnp.int32)
    active = jnp.asarray([True, False, False])
    tokens = jnp.asarray([17, 23, 29], jnp.int32)
    step = jax.jit(decode_steps_paged, static_argnums=(6, 7, 8),
                   static_argnames=('view_blocks',))

    def run(**kw):
        return step(params, tokens, pools, tables, pos, active,
                    config, steps, _BLOCK, **kw)

    whole_toks, whole_pools, whole_pos = run()
    toks, new_pools, new_pos = run(view_blocks=view)
    np.testing.assert_array_equal(np.asarray(toks[0]),
                                  np.asarray(whole_toks[0]))
    np.testing.assert_array_equal(np.asarray(new_pos),
                                  np.asarray(whole_pos))
    for before, after, whole in zip(pools, new_pools, whole_pools):
        if before is None:
            continue
        # The unread rows' blocks (3..18): bit for bit as they were.
        np.testing.assert_array_equal(np.asarray(after[:, 3:19]),
                                      np.asarray(before[:, 3:19]))
        # The decoding row's blocks: what the whole table wrote.
        np.testing.assert_allclose(
            np.asarray(after[:, 1:3], np.float32),
            np.asarray(whole[:, 1:3], np.float32), atol=1e-5)


# ---------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------


def _engine(params, config, kv_int8, **kw):
    kw = dict(dict(slots=4, max_seq=256, block_size=_BLOCK,
                   kv_int8=kv_int8, prefill_chunk=16,
                   max_num_batched_tokens=16, speculative=False,
                   prefix_caching=False), **kw)
    return BatchingEngine(params, config, **kw)


def _serve(engine, requests, **kw):
    reqs = [engine.submit_request(p, n, **kw) for p, n in requests]
    outs = []
    for req in reqs:
        out = []
        while True:
            item = req.out.get(timeout=300)
            if item is None:
                break
            assert not isinstance(item, BaseException), item
            out.append(item)
        outs.append(out)
    return outs


def _spy(engine):
    """Every decode dispatch's (view_blocks, sampled?, longest
    active row + steps), read at the call the live dispatch makes."""
    calls = []
    inner = engine._step_fn  # pylint: disable=protected-access

    def step(*args, **kw):
        rows = np.asarray(args[5])
        longest = max((engine.slot_len[i] for i in range(engine.slots)
                       if rows[i]), default=0)
        calls.append((kw['view_blocks'], kw['sampling'] is not None,
                      longest + args[7]))
        return inner(*args, **kw)

    engine._step_fn = step  # pylint: disable=protected-access
    return calls


# Three short rows that cross the first widths' edges (32 and 64
# positions) while they decode, and a 170-token prompt that is in
# prefill for eleven passes beside them, 22 blocks long.
_MIX = [(_prompt(10, 1), 90), (_prompt(21, 2), 50),
        (_prompt(170, 3), 12), (_prompt(30, 4), 70)]
# A short row beside one that decodes from 150 positions to the
# table's end (the engine serves 255 of its 256): through six and
# seven eighths (192 and 224 positions) onto the whole table.
_LONG_MIX = [(_prompt(12, 5), 40), (_prompt(150, 6), 105)]


@pytest.mark.parametrize('mix, to_the_end', [(_MIX, False),
                                             (_LONG_MIX, True)],
                         ids=['edges', 'to-the-end'])
@pytest.mark.parametrize('kv_int8', [False, True])
@pytest.mark.parametrize('name', _ENGINE_STACKS)
def test_engine_emits_the_whole_table_tokens_and_never_lowers(
        name, kv_int8, mix, to_the_end):
    config, params = _setup(name)
    counter = harness.CompileCounter()
    engine = _engine(params, config, kv_int8)
    try:
        table = engine.max_blocks_per_req
        assert engine._view_widths == da.view_widths(table)
        # What the constructor cannot know: the prefill buckets, as
        # the benchmark's warm-up runs them. Two tokens each: decode
        # at the two narrowest widths only.
        _serve(engine, [(_prompt(b, 20 + b), 2)
                        for b in (1, 2, 4, 8, 16, 20)])
        calls = _spy(engine)
        counter.open()
        got = _serve(engine, mix)
        counter.close()
        assert counter.inside == 0, 'a width compiled under load'
    finally:
        engine.close()
    views = [view for view, _, _ in calls]
    widths = da.view_widths(table)
    if to_the_end:
        # Every width the constructor built from 5/8 up was met
        # while decoding, 7/8 among them, and none compiled there.
        assert set(views) >= set(widths[2:]), views
    else:
        assert len(set(views)) >= 4 and max(views) < table, views
    for view, sampled, need in calls:
        assert not sampled and view * _BLOCK >= need
        assert view == da.view_width(widths, need, _BLOCK)
    # The long prompt sat in prefill beside dispatches narrower than
    # its own blocks.
    assert min(views) * _BLOCK < 150

    whole = _engine(params, config, kv_int8)
    try:
        # The same engine held to the whole table.
        whole._view_widths = (table,)
        whole_calls = _spy(whole)
        want = _serve(whole, mix)
    finally:
        whole.close()
    assert {view for view, _, _ in whole_calls} == {table}
    assert [len(out) for out in got] == [n for _, n in mix]
    assert got == want


@pytest.mark.parametrize('kv_int8', [False, True])
@pytest.mark.parametrize('name', _STACKS)
def test_a_signature_that_was_not_prewarmed_reads_the_whole_table(
        name, kv_int8):
    """A sampled row rides another executable than the constructor
    built: it compiles once, on the whole table, not once a width.
    The two counters say what every dispatch read."""
    config, params = _setup(name)
    engine = _engine(params, config, kv_int8, slots=2, max_seq=128)
    try:
        table = engine.max_blocks_per_req
        counted = engine._metrics  # pylint: disable=protected-access
        calls = _spy(engine)

        def totals():
            return (counted['decode_view_blocks'].value,
                    counted['decode_table_blocks'].value)

        before = totals()
        _serve(engine, [(_prompt(9, 5), 30)], temperature=0.8,
               seed=3)
        assert calls and all(sampled and view == table
                             for view, sampled, _ in calls)
        sampled_calls = len(calls)
        after = totals()
        assert after[0] - before[0] == after[1] - before[1] == \
            table * sampled_calls
        _serve(engine, [(_prompt(9, 6), 30)])
        greedy = calls[sampled_calls:]
        assert greedy and all(not sampled and view < table
                              for view, sampled, _ in greedy)
        last = totals()
        assert last[0] - after[0] == sum(v for v, _, _ in greedy)
        assert last[1] - after[1] == table * len(greedy)
    finally:
        engine.close()
