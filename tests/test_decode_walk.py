"""The block walk of the int8 decode attention
(ops/decode_attention.walk_attention, a Pallas TPU kernel run here in
the interpreter) against the gathered view it replaces
(``gather_blocks`` + ``view_attention``): the same numbers to bf16
rounding on the three serving shapes' head layouts, whatever the
table holds past a row's length; the rule that chooses between the
two, on its observables; and the paged engine serving the view's
greedy tokens through the walk.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.serve.batching import BatchingEngine

_BS, _HD = 16, 128
_NB = 150                       # pool blocks, the scratch block among them
_TABLE = 64                     # a row's table: widths 8, 16, 40, .. 64
# (KV heads, query heads a KV head): Mistral's, Ouro's, command-a's.
_SHAPES = [(8, 4), (16, 1), (8, 16)]
_SHAPE_IDS = ['8x4', '16x1', '8x16']
_ULP = 2.0 ** -6                # of a bf16 value in [2, 4)


@pytest.fixture
def walking(monkeypatch):
    """The rule as a TPU would answer it; the kernel then runs in the
    Pallas interpreter (``da._interpret``: the backend is the CPU)."""
    monkeypatch.setattr(da, '_on_tpu', lambda: True)


def _pool(hkv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (_NB, _BS, hkv, _HD)
    codes = [jax.random.randint(k, shape, -127, 128, jnp.int8)
             for k in ks[:2]]
    scales = [jax.random.uniform(k, shape[:-1], jnp.float32, 0.004,
                                 0.03).astype(jnp.bfloat16)
              for k in ks[2:]]
    return codes + scales


def _step_rows(hkv, groups, rows, seed=1):
    """A step's queries and its own new rows (codes and scales)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (rows, hkv * groups, _HD),
                          jnp.float32).astype(jnp.bfloat16)
    new = tuple(
        jax.random.randint(k, (rows, hkv, _HD), -127, 128, jnp.int8)
        for k in ks[1:3]) + tuple(
        jax.random.uniform(k, (rows, hkv), jnp.float32, 0.004,
                           0.03).astype(jnp.bfloat16)
        for k in ks[3:])
    return q, new


@functools.partial(jax.jit, static_argnames=('tile',))
def _walked(q, pool, tables, lengths, new, tile):
    """The attention of one step as the engine's program asks for it;
    compiled once for each shape (and tile: a test that patches
    ``walk_tile_blocks`` says which, so that no other tile's program
    answers for it)."""
    kp, vp, ksc, vsc = pool
    # At trace time: the program compiled here is the walk's.
    assert da.walk_engages(*kp.shape[1:], codes=True, positions=1,
                           window=None)
    return da.paged_decode_attention(
        q, kp, vp, tables, lengths, _HD ** -0.5,
        k_scale=da.walk_scales(ksc, tables),
        v_scale=da.walk_scales(vsc, tables), new=new)


@jax.jit
def _viewed(q, pool, tables, lengths, new):
    kp, vp, ksc, vsc = pool
    return da.view_attention(
        q, da.gather_blocks(kp, tables), da.gather_blocks(vp, tables),
        lengths, _HD ** -0.5, da.gather_scales(ksc, tables),
        da.gather_scales(vsc, tables), new)


def _both(pool, tables, lengths, q, new):
    """(the walk's, the view's) attention of one step."""
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    got = _walked(q, tuple(pool), tables, lengths, new,
                  tile=da.walk_tile_blocks(pool[0].shape[2]))
    want = _viewed(q, tuple(pool), tables, lengths, new)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _assert_same(got, want):
    """To bf16 rounding (an output is a mean of values up to 3.8,
    where a unit in the last place is 2 ** -6), and where the view
    has its largest value a row the walk has its own, to that unit
    (two values a unit apart may change places)."""
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=_ULP)
    rows = got.shape[0]
    got, want = got.reshape(rows, -1), want.reshape(rows, -1)
    at = want.argmax(-1)
    assert (got[np.arange(rows), at] >= got.max(-1) - _ULP).all()


def _tables(width, rng):
    """Three rows on shuffled blocks of their own, every column of
    the width a real (non-scratch) block."""
    blocks = 1 + rng.permutation(_NB - 1)
    return np.stack([blocks[r * 40:r * 40 + width]
                     for r in range(3)]).astype(np.int32)


def _case(name, width):
    """(tables [3, width], lengths [3]) of a named case."""
    rng = np.random.default_rng(5)
    tables = _tables(width, rng)
    top = width * _BS
    if name == 'shuffled':
        lengths = [top - 16, top // 2, 48]
    elif name == 'shared_prefix':
        tables[1, :5] = tables[0, :5]
        tables[2, :5] = tables[0, :5]
        lengths = [top - 9, 100, 81]
    elif name == 'parked_row':
        lengths = [70, 0, top - 1]
    elif name == 'mid_block':
        lengths = [5, 16 * 9 + 7, top - 15]
    elif name == 'fills_last_block':
        lengths = [top, top, 16]
    elif name == 'one_position':
        lengths = [1, 0, 0]
    else:
        raise ValueError(name)
    return tables, np.asarray(lengths, np.int32)


_CASES = ['shuffled', 'shared_prefix', 'parked_row', 'mid_block',
          'fills_last_block', 'one_position']


@pytest.mark.parametrize('case', _CASES)
@pytest.mark.parametrize('shape', _SHAPES, ids=_SHAPE_IDS)
def test_walk_equals_the_gathered_view(walking, shape, case):
    hkv, groups = shape
    tables, lengths = _case(case, 40)
    q, new = _step_rows(hkv, groups, len(lengths))
    _assert_same(*_both(_pool(hkv), tables, lengths, q, new))


@pytest.mark.parametrize('width', da.view_widths(_TABLE))
@pytest.mark.parametrize('shape', _SHAPES, ids=_SHAPE_IDS)
def test_every_prewarmed_width(walking, shape, width):
    """The table cut to each width a dispatch may take: widths that
    are no whole number of tiles among them (a tile is 32 blocks at 8
    KV heads, 16 at 16), a row at the width's end beside a short one
    and a parked one."""
    hkv, groups = shape
    assert da.view_widths(_TABLE) == (8, 16, 40, 48, 56, 64)
    rng = np.random.default_rng(width)
    tables = _tables(_TABLE, rng)[:, :width]
    lengths = np.asarray([width * _BS - 8, 0, 37], np.int32)
    q, new = _step_rows(hkv, groups, 3, seed=width)
    _assert_same(*_both(_pool(hkv), tables, lengths, q, new))


@pytest.mark.parametrize('tile', [2, 3, 4])
@pytest.mark.parametrize('shape', _SHAPES, ids=_SHAPE_IDS)
def test_many_tiles_a_row_and_rows_of_no_tile(walking, monkeypatch,
                                              shape, tile):
    """Small tiles: rows of an odd and an even count of tiles (the
    two buffers change hands from row to row), a parked row first,
    last and in the middle."""
    hkv, groups = shape
    monkeypatch.setattr(da, 'walk_tile_blocks', lambda _: tile)
    rng = np.random.default_rng(tile)
    blocks = 1 + rng.permutation(_NB - 1)
    tables = np.stack([blocks[r * 20:r * 20 + 24]
                       for r in range(6)]).astype(np.int32)
    lengths = np.asarray([0, 16 * 6 * tile - 3, 16 * tile + 1, 0,
                          16 * 3 * tile, 0], np.int32)
    q, new = _step_rows(hkv, groups, 6)
    _assert_same(*_both(_pool(hkv), tables, lengths, q, new))


@pytest.mark.parametrize('shape', _SHAPES, ids=_SHAPE_IDS)
def test_what_lies_past_a_length_counts_for_exactly_nothing(walking,
                                                            shape):
    """Stale entries past a row's length (a recycled block's rows,
    another request's block, the scratch block): the output is the
    same bit for bit whatever they hold, and a row that outgrew the
    width is read to the width and no further."""
    hkv, groups = shape
    kp, vp, ksc, vsc = _pool(hkv)
    tables, _ = _case('shuffled', 40)
    lengths = np.asarray([16 * 7 + 3, 0, 16 * 40 + 500], np.int32)
    q, new = _step_rows(hkv, groups, 3)
    got, want = _both((kp, vp, ksc, vsc), tables, lengths, q, new)
    _assert_same(got[:2], want[:2])
    # Row 0 owns 8 blocks. Everything else it could reach changes:
    # the rest of its last block, its table's tail, the parked row's
    # whole table.
    other = tables.copy()
    other[0, 8:] = np.random.default_rng(9).integers(0, _NB, 32)
    other[1] = other[2][::-1]
    last = int(tables[0, 7])
    kp2 = kp.at[last, 3:].set(77)
    vp2 = vp.at[last, 3:].set(-128)
    ksc2 = ksc.at[last, 3:].set(3.0)
    vsc2 = vsc.at[last, 3:].set(1e4)
    again, _ = _both((kp2, vp2, ksc2, vsc2), other, lengths, q, new)
    np.testing.assert_array_equal(again[:2], got[:2])


# ---------------------------------------------------------------------
# The rule
# ---------------------------------------------------------------------


def _lowers_the_walk(q, window=None, codes=True, block=_BS):
    hkv = 8
    shape = (_NB, block, hkv, _HD)
    pool = jnp.zeros(shape, jnp.int8 if codes else jnp.bfloat16)
    tables = jnp.ones((q.shape[0], 8), jnp.int32)
    lengths = jnp.full((q.shape[0],), 40, jnp.int32)
    single = q.ndim == 3
    rows = (q.shape[0],) if single else q.shape[:2]
    kind = jnp.int8 if codes else jnp.bfloat16
    new = (jnp.zeros((*rows, hkv, _HD), kind),) * 2 + (
        (jnp.ones((*rows, hkv), jnp.bfloat16),) * 2 if codes
        else (None, None))
    scales = {}
    if codes:
        walks = da.walk_engages(
            block, hkv, _HD, codes=True,
            positions=1 if single else q.shape[1], window=window)
        lay = da.walk_scales if walks else da.gather_scales
        view = lay(jnp.ones(shape[:-1], jnp.bfloat16), tables)
        scales = {'k_scale': view, 'v_scale': view}
    extra = {} if window is None else {
        'window': window, 'key_start': jnp.zeros_like(lengths)}
    text = str(jax.make_jaxpr(
        lambda q: da.paged_decode_attention(
            q, pool, pool, tables, lengths, 0.1, new=new, **scales,
            **extra))(q))
    return 'pallas_call' in text


def test_the_rule_reads_platform_and_operands_alone(monkeypatch):
    one = jnp.zeros((2, 32, _HD), jnp.bfloat16)
    draft = jnp.zeros((2, 3, 32, _HD), jnp.bfloat16)
    # The CPU takes the view, whatever the operands.
    assert not da.walk_engages(_BS, 8, _HD, codes=True, positions=1,
                               window=None)
    assert not _lowers_the_walk(one)
    monkeypatch.setattr(da, '_on_tpu', lambda: True)
    assert _lowers_the_walk(one)
    # The verify window, a window layer, the float pool and a block
    # the kernel does not tile keep the view on a TPU too.
    assert not _lowers_the_walk(draft)
    assert not _lowers_the_walk(one, window=64)
    assert not _lowers_the_walk(one, codes=False)
    assert not _lowers_the_walk(one, block=8)
    assert not da.walk_engages(_BS, 8, 64, codes=True, positions=1,
                               window=None)
    assert not da.walk_engages(_BS, 1, _HD, codes=True, positions=1,
                               window=None)


# ---------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------


def _stack(name):
    """A tiny stack at the head width and block the kernel tiles:
    'tiny' with 2 KV heads of 2 query heads each (Mistral's kind),
    'tiny-loop' with a query head a KV head run twice over (Ouro's),
    'tiny-window-moe' with window and global layers (command-a's)."""
    config = llama.get_config(name)
    config = dataclasses.replace(
        config, head_dim_override=_HD,
        n_kv_heads=config.n_heads if name == 'tiny-loop'
        else config.n_kv_heads)
    return config, llama.init_params(config, jax.random.PRNGKey(7))


def _serve(engine, requests):
    reqs = [engine.submit_request(p, n) for p, n in requests]
    outs = []
    for req in reqs:
        out = []
        while True:
            item = req.out.get(timeout=600)
            if item is None:
                break
            assert not isinstance(item, BaseException), item
            out.append(item)
        outs.append(out)
    return outs


def _engine(params, config):
    return BatchingEngine(
        params, config, slots=3, max_seq=128, block_size=_BS,
        kv_int8=True, prefill_chunk=16, max_num_batched_tokens=16,
        steps_per_dispatch=4, speculative=False, prefix_caching=False)


@pytest.mark.parametrize('name',
                         ['tiny', 'tiny-loop', 'tiny-window-moe'])
def test_engine_serves_the_views_tokens_through_the_walk(
        monkeypatch, name):
    config, params = _stack(name)
    rng = np.random.default_rng(3)
    # Rows that start and end at different passes: lanes stand free
    # and parked in prefill beside decoding ones.
    mix = [(rng.integers(1, 500, n).tolist(), out)
           for n, out in ((9, 20), (40, 9), (17, 14), (5, 6))]
    def counts(engine):
        counted = engine._metrics  # pylint: disable=protected-access
        return np.asarray([counted[name].value for name in (
            'decode_walk_blocks', 'decode_walk_lane_blocks',
            'decode_view_blocks')])

    engine = _engine(params, config)
    try:
        before = counts(engine)
        want = _serve(engine, mix)
        # A dispatch that gathers a view moves neither counter.
        assert (counts(engine) - before)[:2].tolist() == [0, 0]
    finally:
        engine.close()
    monkeypatch.setattr(da, '_on_tpu', lambda: True)
    engine = _engine(params, config)
    try:
        before = counts(engine)
        got = _serve(engine, mix)
        read, lanes, views = counts(engine) - before
    finally:
        engine.close()
    assert [len(out) for out in got] == [n for _, n in mix]
    assert got == want
    # Every dispatch walked: the lanes' blocks are 3 slots x the
    # widths the dispatches took, and the walk read fewer.
    assert lanes == 3 * views and 0 < read < lanes
