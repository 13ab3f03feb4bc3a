"""The paged engine's three model steps (models/decode.py) share
``layer_head``, ``layer_tail`` and ``looped_stack``; what each keeps
of its own is its attention over its own view of the pool. These
tests are the drift alarm for that remainder: one position computed
by each of the three must come out the same (by each of the two a
latent layer has: ``latent_head``, ``latent_tail``, one layer in an
expanded and an absorbed form). Also here: the one
``rope`` against ``ops.attention.apply_rope`` on its three ``angles``
layouts, and the rule that nothing below the scheduler imports it."""
import ast
import functools
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import skypilot_tpu
from skypilot_tpu.models import decode, llama
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.serve import kv_pool

_BLOCK = 8
_LENS = (11, 6)         # each row's context: position p differs a row
_RANK, _SLOTS = 4, 3    # adapter slots: 0 is the all-zeros base slot


def _case(name):
    """(config, params, kv_int8, adapters, adapter_idx) of a case."""
    overrides = {'qkv_bias': True} if name == 'qkv_bias' else {}
    if name == 'tiny-window-moe':
        # Window 8 binds inside row 0's context of 11; experts 2 .. 9
        # of 16 held.
        overrides = {'sliding_window': 8, 'experts_held': (2, 8)}
    config = llama.get_config(
        name if name in ('tiny-loop', 'tiny-window-moe')
        else 'tiny', **overrides)
    params = llama.init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    if name == 'qkv_bias':         # initialised to zeros: make them count
        for leaf in ('bq', 'bk', 'bv'):
            b = params['layers'][leaf]
            params['layers'][leaf] = jnp.asarray(
                0.3 * rng.standard_normal(b.shape), b.dtype)
    adapters = adapter_idx = None
    if name == 'adapters':
        wq, wv = params['layers']['wq'], params['layers']['wv']
        shapes = {'wq_a': (wq.shape[1], _RANK),
                  'wq_b': (_RANK, wq.shape[2]),
                  'wv_a': (wv.shape[1], _RANK),
                  'wv_b': (_RANK, wv.shape[2])}
        adapters = {
            leaf: jnp.asarray(
                0.2 * rng.standard_normal(
                    (config.n_layers, _SLOTS, *shape)), jnp.float32
            ).at[:, 0].set(0.0) for leaf, shape in shapes.items()}
        adapter_idx = jnp.asarray([1, 2], jnp.int32)
    return config, params, name == 'int8', adapters, adapter_idx


def _leaves(pools):
    """The pool arrays of one 4-tuple, or of a dict of them by kind
    of layer, in one fixed order."""
    if isinstance(pools, dict):
        return [a for kind in sorted(pools) for a in pools[kind]]
    return list(pools)


def _recording(monkeypatch, name, sink):
    """``sample_lib.<name>`` as it is, with the logits it was handed
    copied out: the decode and verify steps return tokens only."""
    real = getattr(decode.sample_lib, name)

    def recorded(logits, *args):
        jax.debug.callback(lambda x: sink.append(np.asarray(x)),
                           logits)
        return real(logits, *args)

    monkeypatch.setattr(decode.sample_lib, name, recorded)


@pytest.mark.parametrize(
    'name', ['plain', 'qkv_bias', 'adapters', 'int8', 'tiny-loop',
             'tiny-window-moe'])
def test_the_three_paged_bodies_agree_on_one_position(name,
                                                      monkeypatch):
    """Position p of each row from a one-token ``forward_paged``
    chunk, from ``verify_step_paged`` at width 1 and from one step
    of ``decode_steps_paged``, over the same context in the same
    pool: the same token, logits equal to float32 tolerance, and the
    same rows written at p for every KV entry.

    An int8 pool is the one place the bodies differ by design: the
    prefill chunk attends its OWN rows exact (``forward_paged``'s
    docstring), the decode and verify steps attend theirs as the
    codes the pool stores, so there the tolerance is the
    quantisation step's and the tokens need only be within it."""
    config, params, kv_int8, adapters, adapter_idx = _case(name)
    tol = 0.05 if kv_int8 else 2e-4
    rng = np.random.default_rng(9)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    two_kinds = len(set(config.layer_kinds)) > 1
    if two_kinds:
        # A block group and a table a kind of layer; every column
        # held in both, so that the three read the same context.
        groups = kv_pool.KVBlockPool(config, 9, _BLOCK,
                                     window_num_blocks=9).groups
        pools = {kind: g.caches for kind, g in groups.items()}
        tables = {kind: tables for kind in groups}
    else:
        pools = kv_pool.KVBlockPool(config, 9, _BLOCK,
                                    kv_int8=kv_int8).caches
    pos = jnp.asarray(_LENS, jnp.int32)
    token = jnp.asarray(rng.integers(1, 500, 2), jnp.int32)

    def prefill(tokens, pools, row, start):
        padded = tokens + [0] * (-len(tokens) % 16)
        return decode.forward_paged(
            params, jnp.asarray([padded], jnp.int32), pools,
            {k: t[row] for k, t in tables.items()} if two_kinds
            else tables[row], jnp.asarray(start, jnp.int32),
            jnp.asarray(len(tokens), jnp.int32), config, _BLOCK,
            adapters,
            None if adapter_idx is None else adapter_idx[row:row + 1])

    # The context [0, p) of each row, then position p three ways.
    for row, n in enumerate(_LENS):
        _, pools, _ = prefill(rng.integers(1, 500, n).tolist(),
                               pools, row, 0)
    chunk_logits, chunk_pools = [], pools
    for row, n in enumerate(_LENS):
        logits, chunk_pools, _ = prefill([int(token[row])],
                                          chunk_pools, row, n)
        chunk_logits.append(np.asarray(logits[0]))
    chunk_logits = np.stack(chunk_logits)

    sampling = {'temps': jnp.zeros((2,), jnp.float32),
                'top_ps': jnp.ones((2,), jnp.float32),
                'seeds': jnp.zeros((2,), jnp.int32),
                'mask_idx': jnp.zeros((2,), jnp.int32)}
    seen = {'verify_targets': [], 'sample_rows': []}
    for fn, sink in seen.items():
        _recording(monkeypatch, fn, sink)
    preds, accepted, new_pos, _, verify_pools = decode.verify_step_paged(
        params, token[:, None], pools, tables, pos,
        jnp.ones((2,), jnp.int32), config, 1, _BLOCK, adapters,
        adapter_idx, dict(sampling, mask_table=jnp.ones(
            (1, 1, config.vocab_size), bool)))
    toks, decode_pools, *_ = decode.decode_steps_paged(
        params, token, pools, tables, pos, jnp.asarray([True, True]),
        config, 1, _BLOCK, adapters, adapter_idx, dict(
            sampling,
            mask_table=jnp.ones((1, config.vocab_size), bool)))
    jax.effects_barrier()
    verify_logits = seen['verify_targets'][0][:, 0]
    decode_logits = seen['sample_rows'][0]
    assert np.asarray(accepted).tolist() == [0, 0]
    assert np.asarray(new_pos).tolist() == [n + 1 for n in _LENS]

    picked = {'chunk': chunk_logits.argmax(-1),
              'verify': np.asarray(preds)[:, 0],
              'decode': np.asarray(toks)[:, 0]}
    for logits in (chunk_logits, verify_logits, decode_logits):
        np.testing.assert_allclose(logits, chunk_logits, atol=tol,
                                   rtol=0)
        for body, tok in picked.items():
            # The same token, or (int8) one within the tolerance of
            # the top.
            gap = logits.max(-1) - logits[np.arange(2), tok]
            assert np.all(gap <= (2 * tol if kv_int8 else 0)), (
                body, tok, gap)
    assert np.array_equal(picked['verify'], picked['decode'])

    # What each body wrote at position p, for every KV entry.
    table = tables['global'] if two_kinds else tables
    slot = np.asarray(table)[np.arange(2), np.asarray(_LENS) //
                             _BLOCK] * _BLOCK + np.asarray(_LENS) % _BLOCK
    for want, v_got, d_got in zip(_leaves(chunk_pools),
                                  _leaves(verify_pools),
                                  _leaves(decode_pools)):
        if want is None:
            continue

        def at_p(pool):
            flat = np.asarray(pool.astype(jnp.float32))
            return flat.reshape(flat.shape[0], -1,
                                *flat.shape[3:])[:, slot]

        # A code may differ by one where a value sits on a rounding
        # edge; float rows and scales by float32 rounding.
        for got in (v_got, d_got):
            np.testing.assert_allclose(
                at_p(got), at_p(want), rtol=0,
                atol=1 if want.dtype == jnp.int8 else 2e-4)


def test_the_latent_bodies_agree_on_one_position(monkeypatch):
    """A latent layer has three bodies: position p of each row from
    a one-token ``forward_paged`` chunk, the EXPANDED form over the
    cached latent rows, from one step of ``decode_steps_paged``, the
    ABSORBED form over the same rows, and from a width-1
    ``verify_step_paged`` (its latent body, since PR 43). The same
    token, logits equal to float32 tolerance, and the same latent
    row written at p for every entry, dense layers' and expert
    layers' alike."""
    config = llama.get_config('tiny-latent-moe')
    params = llama.init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    # The selection bias is initialised to zeros: make it count.
    params['layers']['router_bias'] = jnp.asarray(
        0.05 * rng.standard_normal(
            params['layers']['router_bias'].shape), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pools = kv_pool.KVBlockPool(config, 9, _BLOCK).caches
    pos = jnp.asarray(_LENS, jnp.int32)
    token = jnp.asarray(rng.integers(1, 500, 2), jnp.int32)

    def prefill(tokens, pools, row, start):
        padded = tokens + [0] * (-len(tokens) % 16)
        return decode.forward_paged(
            params, jnp.asarray([padded], jnp.int32), pools,
            tables[row], jnp.asarray(start, jnp.int32),
            jnp.asarray(len(tokens), jnp.int32), config, _BLOCK)

    for row, n in enumerate(_LENS):
        _, pools, _ = prefill(rng.integers(1, 500, n).tolist(),
                               pools, row, 0)
    chunk_logits, chunk_pools = [], pools
    for row, n in enumerate(_LENS):
        logits, chunk_pools, _ = prefill([int(token[row])],
                                          chunk_pools, row, n)
        chunk_logits.append(np.asarray(logits[0]))
    chunk_logits = np.stack(chunk_logits)
    seen = []
    _recording(monkeypatch, 'sample_rows', seen)
    toks, decode_pools, new_pos, _ = decode.decode_steps_paged(
        params, token, pools, tables, pos, jnp.asarray([True, True]),
        config, 1, _BLOCK, None, None, {
            'temps': jnp.zeros((2,), jnp.float32),
            'top_ps': jnp.ones((2,), jnp.float32),
            'seeds': jnp.zeros((2,), jnp.int32),
            'mask_idx': jnp.zeros((2,), jnp.int32),
            'mask_table': jnp.ones((1, config.vocab_size), bool)})
    jax.effects_barrier()
    assert np.asarray(new_pos).tolist() == [n + 1 for n in _LENS]
    np.testing.assert_allclose(seen[0], chunk_logits, atol=2e-4,
                               rtol=0)
    assert np.array_equal(np.asarray(toks)[:, 0],
                          chunk_logits.argmax(-1))
    slot = np.asarray(tables)[np.arange(2), np.asarray(_LENS) //
                              _BLOCK] * _BLOCK + np.asarray(_LENS) % _BLOCK
    want, got = (np.asarray(p[0]).reshape(config.n_layers, -1,
                                          p[0].shape[-1])[:, slot]
                 for p in (chunk_pools, decode_pools))
    assert chunk_pools[1:] == decode_pools[1:] == (None, None, None)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert np.abs(want[..., :config.latent_width]).min(axis=-1).max() \
        > 0 and not want[..., config.latent_width:].any()
    preds, accepted, verify_pos, _, verify_pools = \
        decode.verify_step_paged(
            params, token[:, None], pools, tables, pos,
            jnp.ones((2,), jnp.int32), config, 1, _BLOCK)
    assert np.array_equal(np.asarray(preds)[:, 0],
                          np.asarray(toks)[:, 0])
    assert np.asarray(accepted).tolist() == [0, 0]
    assert np.array_equal(np.asarray(verify_pos), np.asarray(new_pos))
    np.testing.assert_allclose(
        np.asarray(verify_pools[0]).reshape(want.shape[0], -1,
                                            want.shape[-1])[:, slot],
        want, atol=2e-4, rtol=0)


# ---------------------------------------------------------------------
# A prefill chunk over key tiles against the row's whole view
# ---------------------------------------------------------------------

_T = 16                 # the chunk's bucket
_TILE_BLOCKS = 2        # a tile of 16 positions: tables of 64 hold four
_TABLE = (11, 3, 7, 5, 12, 2, 9, 6)     # the request's own blocks
_OTHER = (11, 3, 14, 15, 1, 4, 8, 10)   # a row that shares the first two
# ``start`` of the chunk under test, and whose prefill wrote [0, start).
_STARTS = {'nothing cached': (0, _TABLE),
           'inside a block': (5, _TABLE),
           'whole tiles': (32, _TABLE),
           'past one tile': (21, _TABLE),
           'a prefix another row wrote': (16, _OTHER)}


def _chunk_config(stack):
    """4 query heads a KV head, 1, or the looped stack (1 a KV head,
    8 KV entries: the table's offset by entry counts)."""
    if stack == 'loop':
        return llama.get_config('tiny-loop')
    return llama.get_config(
        'tiny', n_heads=8, head_dim_override=16,
        n_kv_heads=2 if stack == 'groups of 4' else 8)


def _view_attention(q, k_new, v_new, k_pool, v_pool, block_row, start,
                    scale, k_scale=None, v_scale=None, window=None,
                    tile_blocks=None):
    """``da.chunk_attention``'s contract computed the plain way, as
    ``forward_paged`` did until PR 42: the row's WHOLE view gathered
    position by position and dequantised, the chunk's exact rows
    spliced in over their own positions, one dense masked softmax
    (``decode._masked_attention``)."""
    assert window is None and tile_blocks is None
    t = q.shape[0]
    bs = k_pool.shape[1]
    at = da.read_indices(block_row[None], bs)               # [1, S]

    def view(pool, scales, own):                # scales [Hkv, S]
        flat = pool.reshape(-1, *pool.shape[2:])
        rows = decode._dequant_kv(
            jnp.take(flat, at, axis=0),
            None if scales is None else scales.T[None], q.dtype)
        rel = jnp.arange(at.shape[1]) - start
        mine = (rel >= 0) & (rel < t)
        return jnp.where(mine[None, :, None, None],
                         own[jnp.clip(rel, 0, t - 1)][None], rows)

    return decode._masked_attention(
        q[None], view(k_pool, k_scale, k_new),
        view(v_pool, v_scale, v_new), q_pos=start, kv_len=start + t,
        scale=scale)[0]


@functools.lru_cache(maxsize=None)
def _chunk_fn(form):
    """``forward_paged`` jitted in one of two forms: ``tiles`` as it
    is, at a tile of ``_TILE_BLOCKS``; ``view`` with the attention
    swapped for ``_view_attention``. The swap acts while a call
    traces, which is the only time the name is looked up."""
    def run(params, tokens, pools, row, start, real_len, config):
        swap = ((da, 'chunk_tile_blocks', lambda bs, mb: _TILE_BLOCKS)
                if form == 'tiles' else
                (da, 'chunk_attention', _view_attention))
        with mock.patch.object(*swap):
            return decode.forward_paged(params, tokens, pools, row,
                                        start, real_len, config,
                                        _BLOCK)
    return jax.jit(run, static_argnums=(6,))


def _chunk(form, config, params, pools, table, tokens, start,
           bucket=_T):
    padded = list(tokens) + [0] * (bucket - len(tokens))
    logits, pools, _ = _chunk_fn(form)(
        params, jnp.asarray([padded], jnp.int32), pools,
        jnp.asarray(table, jnp.int32), jnp.asarray(start, jnp.int32),
        jnp.asarray(len(tokens), jnp.int32), config)
    return np.asarray(logits[0]), pools


def _flat(pool):
    """[E, NB * bs, ...] float32 of one pool array."""
    a = np.asarray(pool.astype(jnp.float32))
    return a.reshape(a.shape[0], -1, *a.shape[3:])


def _slots(table, lo, hi):
    p = np.arange(lo, hi)
    return np.asarray(table)[p // _BLOCK] * _BLOCK + p % _BLOCK


@pytest.mark.parametrize('real_len', [_T, 11])
@pytest.mark.parametrize('where', list(_STARTS))
@pytest.mark.parametrize('stack', ['groups of 4', 'groups of 1', 'loop'])
@pytest.mark.parametrize('pool', ['float', 'int8'])
def test_a_chunk_over_key_tiles_equals_the_whole_view(pool, stack,
                                                      where, real_len):
    """One prefill chunk of ``forward_paged`` (key tiles up to
    ``start`` plus the chunk's own exact rows) against the same chunk
    over the row's whole gathered view: the same logits to float32
    rounding, and the same pool afterwards, of which only the chunk's
    ``real_len`` slots (and the scratch block, where its padding
    lands) differ from the pool before: the one merged scatter is
    the only write."""
    config = _chunk_config(stack)
    params = llama.init_params(config, jax.random.PRNGKey(1))
    rng = np.random.default_rng(13)
    start, writer = _STARTS[where]
    before = kv_pool.KVBlockPool(config, 16, _BLOCK,
                                 kv_int8=pool == 'int8').caches
    if start:
        # Whoever prefilled [0, start): this row, or the row whose
        # first two blocks this row's table shares.
        _, before = _chunk('tiles', config, params, before, writer,
                           rng.integers(1, 500, start).tolist(), 0,
                           bucket=32)
    tokens = rng.integers(1, 500, real_len).tolist()
    want_logits, want = _chunk('view', config, params, before, _TABLE,
                               tokens, start)
    got_logits, got = _chunk('tiles', config, params, before, _TABLE,
                             tokens, start)
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-4,
                               rtol=0)
    assert got_logits.argmax() == want_logits.argmax()
    written = _slots(_TABLE, start, start + real_len)
    rest = np.setdiff1d(
        np.arange(_BLOCK, 16 * _BLOCK), written)  # scratch left out
    for was, w, g in zip(before, want, got):
        if was is None:
            assert w is None and g is None
            continue
        assert g.dtype == was.dtype and g.shape == was.shape
        np.testing.assert_array_equal(_flat(g)[:, rest],
                                      _flat(was)[:, rest])
        # The first entry's rows hang on no attention: bit for bit.
        np.testing.assert_array_equal(_flat(g)[0, written],
                                      _flat(w)[0, written])
        # A code may differ by one where a value sits on a rounding
        # edge; float rows and scales by float32 rounding.
        np.testing.assert_allclose(
            _flat(g)[:, written], _flat(w)[:, written], rtol=0,
            atol=1 if was.dtype == jnp.int8 else 2e-4)
        assert np.abs(_flat(g)[:, written]).max() > 0


@pytest.mark.parametrize('stack', ['groups of 4', 'groups of 1', 'loop'])
@pytest.mark.parametrize('pool', ['float', 'int8'])
def test_three_chunks_equal_one(pool, stack):
    """A prompt of 40 tokens prefilled as chunks of 16, 16 and 8
    (the last padded to its bucket; the second and third read the
    tiles the earlier ones wrote) against the same prompt in one
    chunk of 48: the same last-position logits and the same 40 rows
    in the pool. A float pool to float32 rounding; an int8 pool to
    the quantisation step's (a later chunk reads the earlier ones'
    codes where the single chunk reads its own rows exact)."""
    config = _chunk_config(stack)
    params = llama.init_params(config, jax.random.PRNGKey(1))
    tokens = np.random.default_rng(17).integers(1, 500, 40).tolist()
    empty = kv_pool.KVBlockPool(config, 16, _BLOCK,
                                kv_int8=pool == 'int8').caches
    want_logits, want = _chunk('tiles', config, params, empty, _TABLE,
                               tokens, 0, bucket=48)
    got = empty
    for lo in (0, 16, 32):
        got_logits, got = _chunk('tiles', config, params, got, _TABLE,
                                 tokens[lo:lo + 16], lo)
    tol = 0.05 if pool == 'int8' else 2e-4
    np.testing.assert_allclose(got_logits, want_logits, atol=tol,
                               rtol=0)
    gap = want_logits.max() - want_logits[got_logits.argmax()]
    assert gap <= (2 * tol if pool == 'int8' else 0)
    written = _slots(_TABLE, 0, 40)
    for w, g in zip(want, got):
        if w is None:
            continue
        if w.dtype == jnp.int8:
            # Codes against their own scales: compare what they
            # stand for, below.
            continue
        np.testing.assert_allclose(_flat(g)[:, written],
                                   _flat(w)[:, written], rtol=0,
                                   atol=tol)
    if pool == 'int8':
        for codes, scales in ((0, 2), (1, 3)):
            step = [_flat(p[scales])[:, written][..., None]
                    for p in (want, got)]
            value = [_flat(p[codes])[:, written] * s
                     for p, s in zip((want, got), step)]
            # Within the rows' own drift and a code step and a half.
            assert np.all(np.abs(value[1] - value[0]) <=
                          tol + 1.5 * np.maximum(*step))


@pytest.mark.parametrize('layout', ['chunk', 'decode rows',
                                    'verify window'])
def test_rope_equals_apply_rope(layout):
    """``decode.rope`` on each ``angles`` layout a paged body hands
    it, against ``apply_rope`` (x [B, T, H, D], angles [T, D/2]) a
    row at a time."""
    b, t = {'chunk': (1, 7), 'decode rows': (3, 1),
            'verify window': (3, 5)}[layout]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((b, t, 4, 16)), jnp.float32)
    angles = jnp.asarray(rng.uniform(0, 6.0, (b, t, 8)), jnp.float32)
    got = decode.rope(x, angles[0] if layout == 'chunk' else angles)
    want = jnp.concatenate([
        attention_ops.apply_rope(x[i:i + 1], angles[i])
        for i in range(b)])
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nothing_below_the_scheduler_imports_it():
    """models/, ops/ and parallel/ are below serve/: the scheduler
    imports the model's steps, never the other way round."""
    root = os.path.dirname(skypilot_tpu.__file__)
    offenders = []
    for layer in ('models', 'ops', 'parallel'):
        for dirpath, _, files in os.walk(os.path.join(root, layer)):
            for fn in files:
                if not fn.endswith('.py'):
                    continue
                path = os.path.join(dirpath, fn)
                tree = ast.parse(open(path, encoding='utf-8').read())
                for node in ast.walk(tree):
                    if isinstance(node, ast.Import):
                        names = [a.name for a in node.names]
                    elif isinstance(node, ast.ImportFrom):
                        names = [f'{node.module}.{a.name}'
                                 for a in node.names]
                    else:
                        continue
                    offenders += [
                        f'{os.path.relpath(path, root)}:{node.lineno}'
                        for n in names
                        if (n + '.').startswith('skypilot_tpu.serve.')]
    assert not offenders, offenders
