"""Window and global layers in one cache, the dropless expert layer at
a chip's share, the parallel block (models/moe.py, models/decode.py,
ops/decode_attention.py, serve/kv_pool.py, serve/batching.py), at a
tiny size on the CPU with seeded random float32 weights, against the
plain reference ``perf/reference/cohere2_moe_block_f32.py``. Logits
are compared, not tokens: on random weights the largest logit changes
on rounding. Each tolerance says what it allows for."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import weights_cohere2_moe
from perf.reference import cohere2_moe_block_f32 as reference
from skypilot_tpu import exceptions
from skypilot_tpu.models import decode, llama, moe
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.batching import BatchingEngine

_BLOCK = 8
_WINDOW = 32
_HELD = (4, 8)          # experts 4 .. 11 of the 16 published
# Float32 weights and float32 arithmetic on both sides: what is left
# is the order of the sums (key tiles with a running maximum against
# one softmax over the row; experts grouped against one at a time).
_TOL = 2e-4


def _config(**overrides):
    return llama.get_config('tiny-window-moe',
                            **{'experts_held': _HELD, **overrides})


def _ref_cfg(config):
    """The reference's view of ``config``: the configuration file's
    ``model`` keys."""
    first, count = config.experts_held or (0, config.n_experts)
    return {
        'hidden_size': config.dim, 'intermediate_size': config.ffn_hidden,
        'num_hidden_layers': config.n_layers,
        'num_attention_heads': config.n_heads,
        'num_key_value_heads': config.n_kv_heads,
        'head_dim': config.head_dim, 'vocab_size': config.vocab_size,
        'num_experts': count, 'experts_first': first,
        'num_experts_per_tok': config.moe_top_k,
        'num_shared_experts': config.n_shared_experts,
        'published': {'num_experts': config.n_experts},
        'layer_norm_eps': config.norm_eps,
        'rope_theta': config.rope_theta,
        'sliding_window': config.sliding_window,
        'layer_types': [
            'sliding_attention' if k == 'window' else 'full_attention'
            for k in config.layer_kinds] * (
                config.n_layers // len(config.layer_kinds))}


def _weights(config, seed=3):
    return weights_cohere2_moe.make_weights(
        _ref_cfg(config), seed, int8=False, dtype=jnp.float32)[0]


@pytest.fixture(scope='module')
def model():
    config = _config()
    return config, _weights(config)


def _pools(config, n_blocks=40, kv_int8=False):
    pool = kv_pool.KVBlockPool(config, n_blocks, _BLOCK,
                               kv_int8=kv_int8,
                               window_num_blocks=n_blocks)
    return {kind: g.caches for kind, g in pool.groups.items()}


def _tables(rows, per_row):
    """Every column held in both groups (no release): what the bodies
    compute must not depend on the allocator having let go."""
    t = 1 + jnp.arange(rows * per_row, dtype=jnp.int32).reshape(
        rows, per_row)
    return {'global': t, 'window': t}


_PREFILL = jax.jit(decode.forward_paged, static_argnums=(6, 7))


def _prefill(params, config, tokens, pools, table_row, chunk):
    logits = None
    for start in range(0, len(tokens), chunk):
        part = tokens[start:start + chunk]
        logits, pools, _ = _PREFILL(
            params, jnp.asarray([part + [0] * (chunk - len(part))],
                                jnp.int32),
            pools, table_row, jnp.asarray(start, jnp.int32),
            jnp.asarray(len(part), jnp.int32), config, _BLOCK)
    return np.asarray(logits[0]), pools


def _reference_logits(params, config, tokens, positions):
    return np.asarray(reference.logits_at(
        params, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions), _ref_cfg(config)))


# ---------------------------------------------------------------------
# (a) prefill then decode through the two block groups
# ---------------------------------------------------------------------


def test_prefill_then_decode_against_the_reference(model, monkeypatch):
    """Two rows, prefilled in chunks and decoded 24 steps with their
    own greedy tokens: row 0's context is over three windows long,
    row 1 crosses the window while it decodes (20 -> 44 > 32). Every
    step's logits against the reference's full forward pass over the
    sequence as it came out."""
    config, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (100, 20)]
    steps = 24
    tables = _tables(2, 16)
    pools = _pools(config)
    first = []
    for row, prompt in enumerate(prompts):
        logits, pools = _prefill(
            params, config, prompt, pools,
            {k: t[row] for k, t in tables.items()}, 16)
        want = _reference_logits(params, config, prompt,
                                 [len(prompt) - 1])[0]
        np.testing.assert_allclose(logits, want, atol=_TOL, rtol=0)
        first.append(int(logits.argmax()))
    seen = []
    real = decode.sample_lib.sample_rows

    def recorded(logits, *args):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return real(logits, *args)

    monkeypatch.setattr(decode.sample_lib, 'sample_rows', recorded)
    sampling = {'temps': jnp.zeros((2,), jnp.float32),
                'top_ps': jnp.ones((2,), jnp.float32),
                'seeds': jnp.zeros((2,), jnp.int32),
                'mask_idx': jnp.zeros((2,), jnp.int32),
                'mask_table': jnp.ones((1, config.vocab_size), bool)}
    toks, _, pos, routed = decode.decode_steps_paged(
        params, jnp.asarray(first, jnp.int32), pools, tables,
        jnp.asarray([100, 20], jnp.int32), jnp.asarray([True, True]),
        config, steps, _BLOCK, None, None, sampling)
    jax.effects_barrier()
    assert np.asarray(pos).tolist() == [100 + steps, 20 + steps]
    got = np.stack(seen)                          # [steps, 2, vocab]
    for row, prompt in enumerate(prompts):
        seq = prompt + [first[row]] + np.asarray(toks)[row].tolist()
        want = _reference_logits(
            params, config, seq[:-1],
            np.arange(len(prompt), len(prompt) + steps))
        np.testing.assert_allclose(got[:, row], want, atol=_TOL,
                                   rtol=0)
    # The tally: pairs summed over the steps, and the steps an expert
    # was hit in, a layer and held expert.
    pairs, hit_steps = np.asarray(routed)
    assert pairs.shape == (config.n_layers, _HELD[1])
    assert 0 < pairs.sum() < 2 * steps * config.moe_top_k * \
        config.n_layers
    assert np.all(hit_steps <= steps) and np.all(hit_steps <= pairs)


def test_a_window_left_out_shows(model):
    """The control of the above: the same prefill with the window
    bound taken away departs from the reference by far more than the
    tolerance, so the comparison can tell."""
    config, params = model
    prompt = np.random.default_rng(1).integers(0, 512, 100).tolist()
    wide = dataclasses.replace(config, sliding_window=10_000)
    logits, _ = _prefill(params, wide, prompt, _pools(config),
                         {k: t[0] for k, t in _tables(1, 16).items()},
                         16)
    want = _reference_logits(params, config, prompt, [99])[0]
    assert np.abs(logits - want).max() > 50 * _TOL


# ---------------------------------------------------------------------
# (b) no dependence on chunking or on neighbours
# ---------------------------------------------------------------------


def _layer_params(params, layer=0):
    return jax.tree.map(lambda w: w[layer], params['layers'])


def test_the_expert_layer_is_the_same_to_the_bit_however_chunked(model):
    """Dropless: a token's result is a sum over its own k slots, so
    64 tokens as one chunk, as four chunks of 16 and as 64 rows of a
    decode step give the same bits.
    (A capacity-bound dispatch drops a token or not by who shares its
    chunk.)"""
    config, params = model
    lp = _layer_params(params)
    h = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 64, config.dim)), jnp.float32)
    whole, tally = moe.moe_layer(config, h, lp)
    chunks = [moe.moe_layer(config, h[:, i:i + 16], lp)
              for i in range(0, 64, 16)]
    np.testing.assert_array_equal(
        np.asarray(whole),
        np.concatenate([np.asarray(c) for c, _ in chunks], axis=1))
    np.testing.assert_array_equal(
        np.asarray(tally), sum(np.asarray(t) for _, t in chunks))
    as_rows, _ = moe.moe_layer(config, h.reshape(64, 1, -1), lp)
    np.testing.assert_array_equal(np.asarray(as_rows)[:, 0],
                                  np.asarray(whole)[0])
    # One token alone: the same pairs through the same experts; the
    # CPU backend's product of a 4-row matrix is another kernel than
    # that of 256 rows and rounds its sums in another order.
    alone, _ = moe.moe_layer(config, h[:, 5:6], lp)
    np.testing.assert_allclose(np.asarray(alone)[0, 0],
                               np.asarray(whole)[0, 5], atol=1e-6,
                               rtol=0)


def test_chunked_prefill_and_neighbours_leave_the_logits(model):
    """One chunk of 64 against four of 16: the expert layer is equal
    to the bit (above); attention sums the earlier chunks' keys tile
    by tile with a running maximum where one chunk sums them in one
    softmax, so the logits agree to float32 rounding. And a row's
    decode logits do not depend on the row beside it."""
    config, params = model
    prompt = np.random.default_rng(4).integers(0, 512, 64).tolist()
    row = {k: t[0] for k, t in _tables(1, 16).items()}
    once, _ = _prefill(params, config, prompt, _pools(config), row, 64)
    four, pools = _prefill(params, config, prompt, _pools(config), row,
                           16)
    np.testing.assert_allclose(four, once, atol=_TOL, rtol=0)

    def step(tokens, tables, pos):
        return np.asarray(decode.decode_steps_paged(
            params, jnp.asarray(tokens, jnp.int32), pools, tables,
            jnp.asarray(pos, jnp.int32), jnp.ones((len(pos),), bool),
            config, 4, _BLOCK)[0])

    both = _tables(2, 16)
    alone = step([7], {k: t[:1] for k, t in both.items()}, [64])
    beside = step([7, 9], both, [64, 40])
    np.testing.assert_array_equal(alone[0], beside[0])


# ---------------------------------------------------------------------
# (c) the share adds up
# ---------------------------------------------------------------------


def test_the_shares_add_up_to_the_whole_layer():
    """Eight chips of two experts each: the parts of the expert
    layer's result that the eight shares give, with the shared
    experts (which every chip computes alike) counted once, sum to
    the uncut reference's layer over all 16 experts."""
    whole = _config(experts_held=None)
    params = _weights(whole, seed=11)
    lp = _layer_params(params, layer=1)
    n = jnp.asarray(np.random.default_rng(6).standard_normal(
        (40, whole.dim)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        want = (reference.expert_share(n, lp, _ref_cfg(whole)) +
                reference.shared_mean(n, lp, _ref_cfg(whole)))
    routed_only = dataclasses.replace(whole, n_shared_experts=0)
    total = np.zeros((40, whole.dim), np.float32)
    pairs = 0
    for first in range(0, 16, 2):
        share = dataclasses.replace(routed_only,
                                    experts_held=(first, 2))
        mine = dict(lp, **{k: lp[k][first:first + 2]
                           for k in ('w_gate', 'w_up', 'w_down')})
        part, tally = moe.moe_layer(share, n[None], mine)
        total += np.asarray(part[0])
        pairs += int(np.asarray(tally).sum())
    assert pairs == 40 * whole.moe_top_k        # every pair, once
    one_share = dataclasses.replace(whole, experts_held=(0, 2))
    with_shared, _ = moe.moe_layer(
        one_share, n[None], dict(lp, **{k: lp[k][:2] for k in (
            'w_gate', 'w_up', 'w_down')}))
    without, _ = moe.moe_layer(
        dataclasses.replace(one_share, n_shared_experts=0), n[None],
        dict(lp, **{k: lp[k][:2] for k in ('w_gate', 'w_up',
                                           'w_down')}))
    total += np.asarray(with_shared[0]) - np.asarray(without[0])
    np.testing.assert_allclose(total, np.asarray(want), atol=_TOL,
                               rtol=0)


def test_softmax_scores_and_all_experts_held_is_the_mixtral_layer():
    """The Mixtral-style presets go through the same function:
    softmax scores over all experts, top-k normalised, every expert
    held, no shared ones; against the plain sum over experts."""
    config = llama.get_config('tiny-moe')
    params = llama.init_params(config, jax.random.PRNGKey(2))
    lp = _layer_params(params)
    h = jnp.asarray(np.random.default_rng(8).standard_normal(
        (2, 9, config.dim)), jnp.float32)
    got, tally = moe.moe_layer(config, h, lp)
    x = np.asarray(h, np.float64).reshape(-1, config.dim)
    logits = x @ np.asarray(lp['router'], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t])[:config.moe_top_k]
        for e in top:
            g = x[t] @ np.asarray(lp['w_gate'][e], np.float64)
            u = x[t] @ np.asarray(lp['w_up'][e], np.float64)
            want[t] += probs[t, e] / probs[t, top].sum() * (
                (g / (1 + np.exp(-g)) * u) @
                np.asarray(lp['w_down'][e], np.float64))
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1, config.dim), want, atol=_TOL,
        rtol=0)
    assert int(np.asarray(tally).sum()) == 18 * config.moe_top_k


def test_int8_expert_weights_go_through_the_grouped_product(model):
    """Quantised expert leaves ({'q', 's'}, a scale an expert and
    output channel) give what their dequantised copies give."""
    from skypilot_tpu.models import quant
    config, params = model
    lp = _layer_params(params)
    names = ('w_gate', 'w_up', 'w_down', 'ws_gate', 'ws_up', 'ws_down')
    q = {k: quant.quantize_weight(lp[k]) for k in names}
    back = {k: q[k]['q'].astype(jnp.float32) *
            q[k]['s'].astype(jnp.float32) for k in names}
    h = jnp.asarray(np.random.default_rng(12).standard_normal(
        (1, 24, config.dim)), jnp.float32)
    got, _ = moe.moe_layer(config, h, dict(lp, **q))
    want, _ = moe.moe_layer(config, h, dict(lp, **back))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=_TOL, rtol=0)


# ---------------------------------------------------------------------
# (d) the engine: prefix hits, release behind the window
# ---------------------------------------------------------------------


def _engine(params, config, **kwargs):
    build = dict(slots=3, max_seq=256, block_size=_BLOCK,
                 steps_per_dispatch=4, prefill_chunk=16,
                 speculative=False, sampling=False, num_blocks=100)
    build.update(kwargs)
    return BatchingEngine(params, config, **build)


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


def _watch(engine):
    """Record the final prefill chunk's logits of every request and
    the most window-group blocks any row held."""
    seen = {'logits': [], 'held': 0}
    prefill, ensure = engine._prefill_fn, engine._ensure_window

    def prefill_fn(*args, **kwargs):
        out = prefill(*args, **kwargs)
        seen['logits'].append(np.asarray(out[0][0]))
        return out

    def ensure_window(row, lo, hi):
        ok = ensure(row, lo, hi)
        seen['held'] = max(seen['held'],
                           max(len(b) for b in engine.slot_wblocks))
        return ok

    engine._prefill_fn = prefill_fn
    engine._ensure_window = ensure_window
    return seen


def test_the_engine_serves_what_the_reference_computes(model):
    """Through admission, chunked prefill, release behind the window
    and decode dispatches: every served token's logit lies within the
    tolerance of the reference's best at its position (a key released
    too early, or a stale block read, moves whole logits)."""
    config, params = model
    engine = _engine(params, config)
    try:
        prompt = np.random.default_rng(20).integers(0, 512, 120).tolist()
        served = _serve(engine, prompt, 40)
    finally:
        engine.close()
    gap, _ = reference.served_token_gaps(
        params, _ref_cfg(config), prompt, served, pad_to=160)
    assert len(served) == 40 and float(gap.max()) <= _TOL


def test_the_tables_a_program_gets_are_copies_of_the_hosts(model):
    """The engine writes its two block tables in place on the host
    and hands a program a copy: a transfer may read its argument
    after the call returns, so what a dispatch was given must not
    move when a row's table is written next. A row's table reads its
    blocks by column and the scratch block elsewhere."""
    config, params = model
    engine = _engine(params, config)
    try:
        _serve(engine, list(range(40)), 2)
        engine.slot_blocks[1] = [5, 6, 7]
        engine.slot_wblocks[1] = {1: 9, 2: 4}
        engine._set_table_row(1)
        engine._set_wtable_row(1)
        given, row = engine._tables(), engine._tables(1)
        engine.slot_blocks[1], engine.slot_wblocks[1] = [], {}
        engine._set_table_row(1)
        engine._set_wtable_row(1)
        after = engine._tables()
    finally:
        engine.close()
    for got in (given, row):
        assert all(isinstance(t, np.ndarray) and t.dtype == np.int32
                   for t in got.values())
    assert given['global'][1, :4].tolist() == [5, 6, 7, 0]
    assert given['window'][1, :4].tolist() == [0, 9, 4, 0]
    assert row['global'][:4].tolist() == [5, 6, 7, 0]
    assert row['window'][:4].tolist() == [0, 9, 4, 0]
    assert not after['global'][1].any() and not after['window'][1].any()


def test_a_prefix_hit_gives_the_logits_of_no_hit_after_release(model):
    """A document of over three windows, served once: by then the
    window group has released the document's early blocks (published
    as their chunks completed, they wait in its cache) and the row
    holds only the tail. A second request over the same
    document hits, prefills its question alone, and its logits are
    those of an engine without the cache, to float32 rounding (the
    hit's chunks start elsewhere, so the sums are ordered otherwise).
    No row ever holds more window-group blocks than the window can
    touch plus the chunk in flight."""
    config, params = model
    rng = np.random.default_rng(21)
    doc = rng.integers(0, 512, 104).tolist()        # 13 whole blocks
    ask = [rng.integers(0, 512, n).tolist() for n in (9, 14)]
    cached = _engine(params, config)
    plain = _engine(params, config, prefix_caching=False)
    try:
        seen, seen_plain = _watch(cached), _watch(plain)
        _serve(cached, doc + ask[0], 6)
        released = cached._metrics['kv_window_released'].value
        assert released > 0
        chunks = cached._metrics['prefill_chunks'].value
        hit = _serve(cached, doc + ask[1], 12)
        # 104 tokens hit: the question's 14 take one chunk.
        assert cached._metrics['prefill_chunks'].value - chunks == 1
        assert len(cached.slot_wblocks[0]) == 0     # all given back
        miss = _serve(plain, doc + ask[1], 12)
    finally:
        cached.close()
        plain.close()
    np.testing.assert_allclose(seen['logits'][-1],
                               seen_plain['logits'][-1], atol=_TOL,
                               rtol=0)
    assert hit == miss
    cap = _WINDOW // _BLOCK + 1 + 16 // _BLOCK + 1
    assert 0 < seen['held'] <= cap, (seen['held'], cap)
    assert cached._window_cap == cap


def test_a_hit_is_cut_to_what_the_window_group_still_holds(model):
    """The global group matches a whole chain; the window group,
    here of 11 blocks, publishes a prompt's blocks as their chunks
    complete, gives them back as the row's window moves on and has
    reclaimed the oldest by the end of a 14-block request. A SHORTER
    prompt over the same document, whose window needs blocks that
    were reclaimed, is served from as much of the chain as the window
    group can still back: here none, so the hit is refused and the
    prompt prefilled, and the served tokens are those of an engine
    without the cache."""
    config, params = model
    rng = np.random.default_rng(22)
    doc = rng.integers(0, 512, 104).tolist()
    cached = _engine(params, config, window_num_blocks=12)
    plain = _engine(params, config, prefix_caching=False)
    try:
        _serve(cached, doc + [1, 2, 3], 4)
        assert cached.wpool.evictions >= 3
        hits = cached._metrics['prefix_hits'].value
        # 48 tokens: its window reaches back to block 2, reclaimed.
        short = _serve(cached, doc[:48] + [5, 6, 7], 10)
        assert cached._metrics['prefix_hits'].value == hits
        assert short == _serve(plain, doc[:48] + [5, 6, 7], 10)
    finally:
        cached.close()
        plain.close()


# ---------------------------------------------------------------------
# (e) the allocator, a group
# ---------------------------------------------------------------------


def test_one_kind_builds_one_group_and_two_kinds_two():
    plain = kv_pool.KVBlockPool(llama.get_config('tiny'), 9, _BLOCK)
    assert plain.groups == {'global': plain}
    assert plain.caches[0].shape[0] == 2
    config = _config()
    with pytest.raises(ValueError, match='window_num_blocks'):
        kv_pool.KVBlockPool(config, 9, _BLOCK)
    pool = kv_pool.KVBlockPool(config, 9, _BLOCK,
                               window_num_blocks=5)
    window = pool.groups['window']
    assert pool.groups['global'] is pool and pool.kind == 'global'
    assert pool.caches[0].shape[:2] == (1, 9)       # 1 global layer
    assert window.caches[0].shape[:2] == (3, 5)     # 3 window layers
    assert (pool.usable_blocks, window.usable_blocks) == (8, 4)


def test_a_groups_refcounts_free_list_and_scratch_are_its_own():
    pool = kv_pool.KVBlockPool(_config(), 6, _BLOCK,
                               window_num_blocks=4)
    window = pool.groups['window']
    mine = window.alloc(3)
    assert kv_pool.SCRATCH_BLOCK not in mine and len(set(mine)) == 3
    assert window.try_alloc(1) is None and pool.free_blocks == 5
    assert pool.used_blocks == 0 and window.used_blocks == 3
    # A block two rows share: releasing it behind one row's window is
    # a decrement; the other row still reads it.
    window.register(mine[0], b'h0', kv_pool.ROOT_HASH, range(_BLOCK))
    window.pin([mine[0]])
    window.free([mine[0]])
    assert window.used_blocks == 3 and window.lookup([b'h0']) == \
        [mine[0]]
    window.free([mine[0]])
    assert window.used_blocks == 2 and window.cached_blocks == 1
    assert window.lookup([b'h0', b'h1']) == [mine[0], None]
    with pytest.raises(exceptions.KVBlockError):
        window.free([mine[0]])
    # The cached block is taken back when the free list is dry.
    assert window.try_alloc(1) == [mine[0]]
    assert window.lookup([b'h0']) == [None] and window.evictions == 1


@pytest.mark.parametrize('present,want', [
    ([True] * 13, 13),                  # the whole chain is there
    ([False] * 8 + [True] * 5, 13),     # its tail: a 13-block hit
    ([False] * 10 + [True] * 3, 0),     # too little of it: no hit
    ([True] * 4 + [False] * 9, 4),      # its head: 4 blocks usable
    ([True] * 3 + [False] + [True] * 9, 13),
    ([], 0)])
def test_usable_prefix(present, want):
    """Window 32 in blocks of 8: a hit of k blocks needs blocks
    [first_window_block(8 k), k), the last four or so."""
    assert kv_pool.usable_prefix(present, _WINDOW, _BLOCK) == want


def test_window_index_arithmetic():
    assert kv_pool.first_window_block(0, 32, 8) == 0
    assert kv_pool.first_window_block(31, 32, 8) == 0
    assert kv_pool.first_window_block(39, 32, 8) == 1
    assert kv_pool.first_window_block(104, 32, 8) == 9
    assert da.window_blocks(4096, 16, 768) == 257
    assert da.window_blocks(32, 8, 32) == 5
    assert da.window_blocks(32, 8, 3) == 3
    tables = jnp.arange(1, 21, dtype=jnp.int32).reshape(2, 10)
    sub, start = da.window_view(tables, jnp.asarray([5, 70]), 32, 8)
    assert np.asarray(start).tolist() == [0, 32]
    assert np.asarray(sub)[0].tolist() == [1, 2, 3, 4, 5]
    # Columns past the table read the scratch block.
    assert np.asarray(sub)[1].tolist() == [15, 16, 17, 18, 19]
    sub, start = da.window_view(tables, jnp.asarray([80, 90]), 32, 8)
    assert np.asarray(sub)[1].tolist() == [18, 19, 20, 0, 0]


def test_the_dense_bodies_refuse_the_configuration(model):
    config, params = model
    with pytest.raises(exceptions.NotSupportedError,
                       match='only the paged engine'):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), config)
    with pytest.raises(exceptions.NotSupportedError):
        decode.forward_cached(
            params, jnp.zeros((1, 8), jnp.int32),
            decode.init_cache(dataclasses.replace(
                config, sliding_window=None, global_every=0), 1, 16),
            config)


def test_config_counts_and_kinds():
    config = llama.get_config('command-a-plus', n_layers=8,
                              vocab_size=32768, experts_held=(0, 16))
    assert config.layer_kinds == ('window',) * 3 + ('global',)
    assert (config.kind_entries('window'),
            config.kind_entries('global')) == (6, 2)
    layer = (4096 * 16384 * 2 + 4096 * 1024 * 2 + 4096 * 128 +
             (16 + 4) * 3 * 4096 * 4096 + 4096)
    assert config.num_params() == 32768 * 4096 + 8 * layer + 4096
    assert not config.plain_stack
    shapes = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0)))
    assert shapes['layers']['w_gate'].shape == (8, 16, 4096, 4096)
    assert shapes['layers']['ws_down'].shape == (8, 16384, 4096)
    assert shapes['layers']['router'].shape == (8, 4096, 128)
    assert 'mlp_norm' not in shapes['layers'] and \
        'lm_head' not in shapes
    rules = llama.param_sharding_rules(config)
    assert set(rules['layers']) == set(shapes['layers'])
    with pytest.raises(ValueError, match='experts_held'):
        llama.get_config('command-a-plus', experts_held=(120, 16))
    with pytest.raises(ValueError, match='global_every'):
        llama.get_config('command-a-plus', n_layers=6)


class TestRecipe:

    def test_the_recipe_serves_a_replicas_experts(self, monkeypatch):
        """``recipes/serve_model --experts-held 4:8
        --window-num-blocks 41`` on ``tiny-window-moe``: the engine it
        builds holds that share and a window group of that size, and
        what it answers over HTTP is what the reference computes for
        the same share from the recipe's own weights
        (``init_params`` under ``PRNGKey(0)``)."""
        import http.client
        import json
        import socket
        import sys
        import threading
        import time

        from skypilot_tpu.recipes import serve_model
        from skypilot_tpu.serve import batching

        built = []

        class Capture(BatchingEngine):

            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                built.append(self)

        monkeypatch.setattr(batching, 'BatchingEngine', Capture)
        sock = socket.socket()
        sock.bind(('127.0.0.1', 0))
        port = sock.getsockname()[1]
        sock.close()
        monkeypatch.setattr(sys, 'argv', [
            'serve_model', '--model', 'tiny-window-moe', '--port',
            str(port), '--slots', '2', '--max-seq', '256',
            '--block-size', str(_BLOCK), '--num-blocks', '70',
            '--experts-held', '4:8', '--window-num-blocks', '41',
            '--speculative', 'off'])
        # main() never returns: the daemon thread dies with the test
        # process, as tests/test_adapters.py::TestReplicaE2E's does.
        threading.Thread(target=serve_model.main, daemon=True).start()

        prompt = np.random.default_rng(21).integers(0, 512, 90).tolist()
        body = json.dumps({'prompt_ids': prompt, 'max_new_tokens': 12})
        deadline = time.time() + 300
        while True:
            try:
                conn = http.client.HTTPConnection('127.0.0.1', port,
                                                  timeout=120)
                conn.request('POST', '/generate', body=body)
                resp = conn.getresponse()
                out = json.loads(resp.read())
                assert resp.status == 200, out
                break
            except OSError:
                assert time.time() < deadline, 'replica never ready'
                time.sleep(1.0)
            finally:
                conn.close()

        engine, = built
        assert engine.config.experts_held == _HELD
        assert engine.pool.groups['window'].num_blocks == 41
        assert engine.pool.groups['global'].num_blocks == 70
        config = _config()
        gap, _ = reference.served_token_gaps(
            llama.init_params(config, jax.random.PRNGKey(0)),
            _ref_cfg(config), prompt, out['output_ids'], pad_to=128)
        assert len(out['output_ids']) == 12
        assert float(gap.max()) <= _TOL

    @pytest.mark.parametrize('held', ['4', 'a:b', '12:8'])
    def test_the_recipe_refuses_a_share_that_is_none(self, held,
                                                     monkeypatch, capsys):
        """No count, no numbers, experts past the last: refused at
        start-up, before any weight is made."""
        import sys

        from skypilot_tpu.recipes import serve_model

        monkeypatch.setattr(sys, 'argv', [
            'serve_model', '--model', 'tiny-window-moe', '--slots',
            '2', '--experts-held', held])
        with pytest.raises(SystemExit) as excinfo:
            serve_model.main()
        assert excinfo.value.code == 2
        assert '--experts-held' in capsys.readouterr().err
