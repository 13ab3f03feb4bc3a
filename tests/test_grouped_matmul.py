"""The pair-tiled grouped product of the serving expert layer
(ops/grouped_matmul.pair_tiled_matmul, a Pallas TPU kernel run here
in the interpreter) against ``jax.lax.ragged_dot``, which it replaces
where ``tiles_engage`` says so: the same numbers to float32 rounding
at the three expert cells' group counts scaled down, whatever the
groups hold; a row's result the same to the bit whatever ``M`` is and
whoever its neighbours are; the rule that chooses between the two, on
its observables; and the paged engine serving the same greedy tokens
and counting the pairs that went through the kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama, moe, quant
from skypilot_tpu.ops import grouped_matmul as gm
from skypilot_tpu.serve.batching import BatchingEngine


@pytest.fixture(scope='module', autouse=True)
def _fresh_programs():
    """As ``tests/test_latent_mtp.py::_no_persistent_cache`` (which
    says why): this file compiles an engine's programs twice over, so
    it keeps JAX's persistent cache off and drops the compiled
    programs the worker brought, and its own afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()
    jax.clear_caches()


@pytest.fixture
def tiling(monkeypatch):
    """The rule as a TPU would answer it; the kernel then runs in the
    Pallas interpreter (``gm._interpret``: the backend is the CPU)."""
    monkeypatch.setattr(gm, '_on_tpu', lambda: True)


def _operands(m, groups, k, n, layers=1, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xs = jax.random.normal(ks[0], (m, k), jnp.float32).astype(dtype)
    codes = jax.random.randint(ks[1], (layers, groups, k, n), -127, 128,
                               jnp.int8)
    scales = jax.random.uniform(ks[2], (layers, groups, 1, n),
                                jnp.float32, 0.004, 0.03
                                ).astype(jnp.bfloat16)
    return xs, codes, scales


@jax.jit
def _tiled(xs, codes, layer, sizes):
    return gm.pair_tiled_matmul(xs, codes, layer, sizes)


def _ragged(xs, codes, layer, sizes):
    return jax.lax.ragged_dot(xs, codes[layer], sizes,
                              preferred_element_type=jnp.float32)


def _assert_same_product(xs, codes, layer, sizes):
    """To float32 rounding over the rows the groups hold (sums of
    ``k`` products of magnitude up to 127 x 4)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    held = int(sizes.sum())
    got = np.asarray(_tiled(xs, codes, jnp.int32(layer), sizes))[:held]
    want = np.asarray(_ragged(xs, codes, layer, sizes))[:held]
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=2e-6 * max(float(np.abs(want).max(initial=0.0)), 1.0))


# pairs, groups, k, n, pairs held here: JoyAI's round (256 groups of
# 6 pairs), Xing4.0's step (64 of 4) and command-a's (16 held
# experts, an eighth of the pairs routed to them) at an eighth of
# their group counts and a fraction of their widths.
_CELLS = [(192, 32, 256, 128, 192), (32, 8, 384, 128, 32),
          (32, 2, 512, 512, 4)]
_CELL_IDS = ['joyai-round', 'xing4-step', 'command-a-step']


@pytest.mark.parametrize('cell', _CELLS, ids=_CELL_IDS)
@pytest.mark.parametrize('layer', [0, 2])
def test_pair_tiles_equal_ragged_dot_at_the_cells_group_counts(
        tiling, cell, layer):
    m, groups, k, n, held = cell
    xs, codes, _ = _operands(m, groups, k, n, layers=3)
    sizes = np.random.default_rng(m + layer).multinomial(
        held, np.ones(groups) / groups)
    _assert_same_product(xs, codes, layer, sizes)


_GROUPINGS = {
    # Under a row tile of 16 (the fixture's; 64 pairs over 8 groups).
    'empty_groups_between': [0, 9, 0, 0, 20, 0, 3, 0],
    'a_group_over_three_tiles': [3, 0, 40, 1, 0, 0, 0, 0],
    'groups_end_on_tile_edges': [16, 16, 0, 32, 0, 0, 0, 0],
    'rows_past_the_last_group': [2, 1, 0, 0, 0, 5, 0, 0],
    'one_pair': [0, 0, 0, 0, 0, 0, 0, 1],
    'every_row_in_the_last_group': [0, 0, 0, 0, 0, 0, 0, 64],
    'every_group_one_tile': [8, 8, 8, 8, 8, 8, 8, 8],
}


@pytest.mark.parametrize('name', list(_GROUPINGS))
def test_whatever_the_groups_hold(tiling, monkeypatch, name):
    monkeypatch.setattr(gm, 'row_tile', lambda m: 16)
    xs, codes, _ = _operands(64, 8, 256, 256, layers=2, seed=3)
    _assert_same_product(xs, codes, 1, _GROUPINGS[name])


def test_no_pair_held_runs_no_visit(tiling):
    """Every pair's expert on another chip: the walk is empty."""
    xs, codes, _ = _operands(32, 4, 128, 128)
    sizes = jnp.zeros((4,), jnp.int32)
    offsets, _, _, count = gm.visits(sizes, 32, 16)
    assert int(count[0]) == 0 and not np.asarray(offsets).any()
    assert _tiled(xs, codes, jnp.int32(0), sizes).shape == (32, 128)


@pytest.mark.parametrize('tm', [16, 32, 64])
def test_visits_list_each_row_tile_a_group_touches_once(tm):
    rng = np.random.default_rng(tm)
    for _ in range(20):
        groups = int(rng.integers(1, 12))
        m = tm * int(rng.integers(1, 6))
        sizes = rng.multinomial(int(rng.integers(0, m + 1)),
                                np.ones(groups) / groups)
        offsets, group, tile, count = (np.asarray(a) for a in gm.visits(
            jnp.asarray(sizes, jnp.int32), m, tm))
        assert group.shape == tile.shape == (m // tm + groups - 1,)
        assert (offsets == np.concatenate([[0], np.cumsum(sizes)])
                ).all()
        want = [(g, t) for g in range(groups) if sizes[g]
                for t in range(offsets[g] // tm,
                               (offsets[g + 1] - 1) // tm + 1)]
        n = int(count[0])
        assert list(zip(group[:n], tile[:n])) == want
        assert (tile >= 0).all() and (tile < m // tm).all()
        assert (group >= 0).all() and (group < groups).all()


def test_a_rows_result_is_the_same_to_the_bit_whatever_m_and_whoever_its_neighbours(
        tiling):
    """The same rows of one expert, once among 32 pair rows (a row
    tile of 32) and once among 256 with other neighbours, other
    groups filled and another place in the array (a row tile of 64):
    every bit the same."""
    _, codes, _ = _operands(16, 4, 512, 256, seed=5)
    rows = jax.random.normal(jax.random.PRNGKey(9), (5, 512),
                             jnp.float32).astype(jnp.bfloat16)
    others = jax.random.normal(jax.random.PRNGKey(10), (256, 512),
                               jnp.float32).astype(jnp.bfloat16)
    small = jnp.concatenate([others[:3], rows, others[3:27]])
    large = jnp.concatenate([others[:70], rows, others[70:251]])
    assert gm.row_tile(32) == 32 and gm.row_tile(256) == 64
    got_small = np.asarray(_tiled(
        small, codes, jnp.int32(0), jnp.asarray([3, 0, 5, 9])))[3:8]
    got_large = np.asarray(_tiled(
        large, codes, jnp.int32(0), jnp.asarray([20, 50, 5, 181])))[70:75]
    assert np.array_equal(got_small, got_large)


@pytest.mark.parametrize('layered', [False, True],
                         ids=['a-layers-leaves', 'the-whole-stack'])
def test_grouped_takes_scales_and_the_whole_stack_with_a_layer_index(
        monkeypatch, layered):
    """``moe._grouped`` through the kernel against itself through
    ``ragged_dot``: int8 codes with their per-channel scales, as one
    layer's leaves and as ``LayerOf`` the whole stack, to a unit in
    bf16's last place."""
    xs, codes, scales = _operands(48, 8, 256, 128, layers=3, seed=7)
    sizes = jnp.asarray([5, 0, 17, 1, 0, 9, 3, 2], jnp.int32)
    group = jnp.minimum(jnp.repeat(
        jnp.arange(8), sizes, total_repeat_length=48), 7)
    w = {'q': codes, 's': scales}
    w = moe.LayerOf(w, jnp.int32(2)) if layered else \
        jax.tree.map(lambda a: a[2], w)
    want = np.asarray(moe._grouped(xs, w, sizes, group), np.float32)
    monkeypatch.setattr(gm, '_on_tpu', lambda: True)
    assert gm.tiles_engage(256, 128, codes=True, rows_dtype=xs.dtype)
    got = np.asarray(moe._grouped(xs, w, sizes, group), np.float32)
    held = int(sizes.sum())
    np.testing.assert_allclose(got[:held], want[:held], rtol=2 ** -7,
                               atol=0)


# The products of the three expert cells (pairs, groups, in, out) and
# the row tile each takes: on a TPU every one over int8 codes goes
# through the kernel, however many pairs a group holds.
_PRODUCTS = [
    ('joyai-round-gate-up', 1536, 256, 2048, 768, 64),
    ('joyai-round-down', 1536, 256, 768, 2048, 64),
    ('joyai-plain-step', 768, 256, 2048, 768, 64),
    ('joyai-chunk-512', 4096, 256, 2048, 768, 64),
    ('joyai-first-draft', 8, 256, 2048, 768, 16),
    ('xing4-step-gate-up', 256, 64, 3584, 1024, 64),
    ('xing4-step-down', 256, 64, 1024, 3584, 64),
    ('xing4-chunk-512', 2048, 64, 3584, 1024, 64),
    ('command-a-step', 256, 16, 4096, 4096, 64),
    ('command-a-chunk-512', 4096, 16, 4096, 4096, 64),
    ('command-a-chunk-4', 32, 16, 4096, 4096, 32),
    ('sixteen-experts-2048-pairs-each', 32768, 16, 4096, 4096, 64),
]


@pytest.mark.parametrize('name, m, groups, k, n, tm', _PRODUCTS,
                         ids=[p[0] for p in _PRODUCTS])
def test_which_products_engage(monkeypatch, name, m, groups, k, n, tm):
    ask = dict(codes=True, rows_dtype=jnp.bfloat16)
    # The CPU keeps ragged_dot whatever the shapes.
    assert not gm.tiles_engage(k, n, **ask)
    monkeypatch.setattr(gm, '_on_tpu', lambda: True)
    assert gm.tiles_engage(k, n, **ask)
    assert gm.row_tile(m + -m % gm._ROW_QUANTUM) == tm
    # A float expert stack, rows of another type, a width that is no
    # whole number of lane tiles.
    assert not gm.tiles_engage(k, n, codes=False,
                               rows_dtype=jnp.bfloat16)
    assert not gm.tiles_engage(k, n, codes=True,
                               rows_dtype=jnp.float16)
    assert not gm.tiles_engage(k + 64, n, **ask)
    assert not gm.tiles_engage(k, n + 64, **ask)


@pytest.mark.parametrize('m', [8, 24], ids=['one-token', 'three'])
def test_pair_rows_short_of_a_row_tile_are_padded_up_to_one(m):
    """A one-token bucket's 8 pairs (a first draft's too) take the
    kernel like every other array: padded to 16 rows, the same
    numbers as a larger array gives those rows, to the bit."""
    xs, codes, _ = _operands(64, 4, 256, 128, seed=11)
    sizes = jnp.asarray([2, 0, 5, 1], jnp.int32)
    got = np.asarray(_tiled(xs[:m], codes, jnp.int32(0), sizes))
    assert got.shape == (m, 128)
    among = np.asarray(_tiled(xs, codes, jnp.int32(0), sizes))
    assert np.array_equal(got[:8], among[:8])
    want = np.asarray(_ragged(xs[:m], codes, 0, sizes))[:8]
    np.testing.assert_allclose(got[:8], want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def test_the_tiles_follow_the_static_shapes():
    assert [gm.row_tile(m) for m in (16, 32, 48, 64, 768, 1536,
                                     4096)] == [
                                         16, 32, 16, 64, 64, 64, 64]
    assert [gm.depth_tile(k) for k in (2048, 768, 4096, 3584, 1024,
                                       128, 384)] == [
                                           512, 256, 512, 512, 512,
                                           128, 128]
    # A column tile's codes fit the budget and divide the width.
    for k, n in [(2048, 768), (768, 2048), (4096, 4096), (3584, 1024),
                 (1024, 3584), (128, 128)]:
        tn = gm.column_tile(k, n)
        assert n % tn == 0 and tn % 128 == 0
        assert tn == 128 or k * tn <= gm._CODE_TILE_BYTES
    assert gm.column_tile(2048, 768) == 768
    assert gm.column_tile(4096, 4096) == 512


# ---------------------------------------------------------------------
# The expert layer and the engine
# ---------------------------------------------------------------------


def _int8_layer(config, seed=0):
    """One expert layer's leaves with int8 experts: (lp, h)."""
    params = llama.init_params(config, jax.random.PRNGKey(seed))
    lp = jax.tree.map(lambda a: a[0], params['layers'])
    lp = dict(lp, **{k: quant.quantize_weight(lp[k])
                     for k in moe.EXPERT_LEAVES})
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, 8, config.dim)), jnp.float32)
    return lp, h


@pytest.mark.parametrize('held', [None, (4, 8), (12, 4)],
                         ids=['all-held', 'eight-of-sixteen',
                              'the-last-four'])
def test_the_expert_layer_with_pairs_held_on_another_chip(
        monkeypatch, held):
    """``moe_layer`` through the kernel against itself through
    ``ragged_dot``: a share of the experts held here, the other
    pairs' rows past the last group."""
    config = llama.get_config('tiny-window-moe', ffn_hidden=128,
                              experts_held=held)
    lp, h = _int8_layer(config)
    assert lp['w_gate']['q'].shape[0] == (held or (0, 16))[1]
    want, want_tally = moe.moe_layer(config, h, lp)
    monkeypatch.setattr(gm, '_on_tpu', lambda: True)
    assert moe.pairs_tiled(jax.tree.map(
        lambda a: a[None], {k: lp[k] for k in moe.EXPERT_LEAVES}),
        h.dtype)
    got, tally = moe.moe_layer(config, h, lp)
    assert np.array_equal(np.asarray(tally), np.asarray(want_tally))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)


def _serve(engine, prompt, n):
    req = engine.submit_request(prompt, n)
    out = []
    while True:
        item = req.out.get()
        if item is None:
            return out
        if isinstance(item, BaseException):
            raise item
        out.append(int(item))


def _counted(engine):
    return np.asarray([engine._metrics[k].value for k in (
        'moe_held_pairs', 'moe_tiled_pairs')])


def test_the_engine_serves_the_same_tokens_and_counts_the_tiled_pairs(
        monkeypatch):
    """A paged engine over int8 experts whose products engage the
    kernel (decode dispatches of 4 rows x 4 experts, chunks of 16
    tokens) serves what it serves through ``ragged_dot``, and
    ``skytpu_batch_moe_tiled_pairs_total`` counts every held pair
    there and none here."""
    config = llama.get_config('tiny-window-moe', ffn_hidden=128)
    params = quant.quantize_params(
        llama.init_params(config, jax.random.PRNGKey(1)), config)
    prompt = [int(t) for t in np.random.default_rng(2).integers(
        1, config.vocab_size, 21)]
    build = dict(slots=4, max_seq=128, block_size=16,
                 steps_per_dispatch=4, prefill_chunk=16,
                 speculative=False, sampling=False, num_blocks=40)

    def run():
        engine = BatchingEngine(params, config, **build)
        try:
            before = _counted(engine)
            tokens = _serve(engine, prompt, 6)
            return (tokens, *(_counted(engine) - before))
        finally:
            engine.close()

    want, held, tiled = run()
    assert held > 0 and tiled == 0
    monkeypatch.setattr(gm, '_on_tpu', lambda: True)
    jax.clear_caches()
    got, held_tiled, tiled = run()
    jax.clear_caches()
    assert got == want
    assert held_tiled == held and tiled == held > 0
