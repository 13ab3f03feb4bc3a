"""A model whose own next-token-prediction module drafts on the
device (``nextn_layers``; ``tiny-latent-mtp``): the module through
the cache against the plain reference
(``perf/reference/joyai_mtp_block_f32.py``), the latent verify body,
and the drafting rounds (``decode.mtp_rounds_paged``); the engine
that runs them is ``tests/test_latent_mtp_engine.py``'s (a file of
its own, so that another worker takes it). Seeded weights
(``perf/lib/weights_joyai.py``), float32, ``highest`` matmul
precision.

Tolerances: logits of the program against the reference 2e-4
absolute (float32 sums in another order: absorbed against expanded,
tiles against whole rows, a grouped product against an expert at a
time; the logits themselves are of size 1); a prefix hit against the
cold run 2e-5 (the same program on rows computed in chunks of another
length). Tokens are compared exactly."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import weights_joyai
from perf.reference import joyai_mtp_block_f32 as reference
from skypilot_tpu import exceptions
from skypilot_tpu.models import decode, llama
from skypilot_tpu.serve import kv_pool

_BLOCK = 8
_TOL = 2e-4
_HIT_TOL = 2e-5


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision('highest'):
        yield


@pytest.fixture(scope='module', autouse=True)
def _no_persistent_cache():
    """Two things a worker of a whole run of the tests brings to
    this file, both met in PR 43's first whole runs. (1) A test file
    it ran earlier may have started a replica in-process
    (``recipes/serve_model``), which turns JAX's persistent
    compilation cache on for the whole process; writing this file's
    dozens of programs to a directory that six workers share aborted
    a worker inside ``executable.serialize()``: off for this file,
    restored after it. (2) By the time it gets here the process
    holds the compiled programs of some hundred tests, and XLA:CPU's
    compiler segfaulted on this file's first large program
    (reproduced in one process: ``test_latent_moe.py``,
    ``test_window_moe.py``, ``test_paged_bodies.py``, then this
    file; any two of them pass): the earlier files' programs are
    dropped first, and this file's afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()
    jax.clear_caches()


# The paged steps as the engine jits them: one program a shape. Run
# op by op, a chunk or a round compiles some hundred small programs,
# and the workers of a whole run of the tests, long-lived and full of
# compiled code by the time they reach this file, died in XLA:CPU's
# compiler (PR 43's first whole runs).
_forward = jax.jit(decode.forward_paged, static_argnums=(6, 7))
_steps = jax.jit(decode.decode_steps_paged, static_argnums=(6, 7, 8),
                 static_argnames=('view_blocks',))
_verify = jax.jit(decode.verify_step_paged, static_argnums=(6, 7, 8))
_rounds = jax.jit(decode.mtp_rounds_paged, static_argnums=(8, 9, 10),
                  static_argnames=('view_blocks',))
_first = jax.jit(decode.mtp_first_paged, static_argnums=(6, 7))


def _ref_cfg(config):
    """The reference's view of ``config``: the configuration file's
    ``model`` keys."""
    return {
        'hidden_size': config.dim,
        'intermediate_size': config.dense_ffn_hidden,
        'moe_intermediate_size': config.ffn_hidden,
        'num_hidden_layers': config.n_layers,
        'first_k_dense_replace': config.dense_first,
        'num_attention_heads': config.n_heads,
        'vocab_size': config.vocab_size,
        'q_lora_rank': config.q_lora_rank,
        'kv_lora_rank': config.kv_lora_rank,
        'qk_nope_head_dim': config.qk_nope_head_dim,
        'qk_rope_head_dim': config.qk_rope_head_dim,
        'v_head_dim': config.v_head_dim,
        'n_routed_experts': config.n_experts,
        'n_shared_experts': config.n_shared_experts,
        'num_experts_per_tok': config.moe_top_k,
        'routed_scaling_factor': config.moe_routed_scale,
        'rms_norm_eps': config.norm_eps,
        'rope_theta': config.rope_theta,
        'num_nextn_predict_layers': config.nextn_layers}


@pytest.fixture(scope='module')
def model():
    config = llama.get_config('tiny-latent-mtp')
    params, _ = weights_joyai.make_weights(
        _ref_cfg(config), 5, int8=False, dtype=jnp.float32)
    return config, params


def _pool(config, n_blocks=48):
    return kv_pool.KVBlockPool(config, n_blocks, _BLOCK).caches


def _prefill(config, params, pools, table, tokens, chunk=16, start=0,
             hidden=0):
    """``tokens`` [start - hidden, ..) of a request in chunks of
    ``chunk``, the module's carry handed from chunk to chunk.
    Returns (last logits, pools, last state)."""
    h = jnp.zeros((1, 1, config.dim), config.dtype)
    off = start - hidden
    logits = None
    while off < len(tokens):
        part = tokens[off:off + chunk]
        padded = part + [0] * (chunk - len(part))
        logits, pools, _, h = _forward(
            params, jnp.asarray([padded], jnp.int32), pools, table,
            jnp.asarray(off, jnp.int32),
            jnp.asarray(len(part), jnp.int32), config, _BLOCK,
            mtp=(h, jnp.asarray(hidden, jnp.int32)))
        off, hidden = off + len(part), 0
    return logits, pools, h


@functools.partial(jax.jit, static_argnums=(0,))
def _probe(config, params, pools, table, h, token, pos):
    flat, nb = decode._flat_pools(config, 'latent', pools, _BLOCK)
    y, _, _ = decode._mtp_step(
        config, params, h, token, pos, jnp.ones((1,), jnp.int32),
        flat, nb, table[None], _BLOCK)
    return decode.mtp_logits(config, params, y[:, 0])[0]


def _module_probe(config, params, pools, table, h, token, pos):
    """l' of the pair (``h``, ``token``) at pair index ``pos``
    against the module's cached rows; the pools are left alone."""
    return np.asarray(_probe(
        config, params, pools, table, h,
        jnp.asarray([[token]], jnp.int32),
        jnp.asarray([pos], jnp.int32)))


def _sampling(rows, temperature, seeds=None):
    return {'temps': jnp.full((rows,), temperature, jnp.float32),
            'top_ps': jnp.ones((rows,), jnp.float32),
            'seeds': jnp.asarray(seeds if seeds is not None
                                 else range(7, 7 + rows), jnp.int32)}


# ---------------------------------------------------------------------
# (a) configuration, leaves, the pool's entries
# ---------------------------------------------------------------------


def test_the_preset_its_leaves_and_its_entries(model):
    config, params = model
    big = llama.get_config('joyai-llm-flash')
    assert (big.dim, big.n_layers, big.dense_first) == (2048, 40, 1)
    assert (big.n_experts, big.moe_top_k) == (256, 8)
    assert big.rope_yarn is None and big.hc_mult == 1
    assert llama.attention_scale(big) == pytest.approx(192 ** -0.5)
    assert big.kv_entries == 41 and big.kind_entries('latent') == 41
    # 48.9 B of main model (the published 48B) and the module's
    # expert layer, projection and three norms.
    module = big.num_params() - llama.get_config(
        'joyai-llm-flash', nextn_layers=0).num_params()
    # ISSUE 43's arithmetic: 1,239.6 M + 8.4 M.
    assert module == pytest.approx(1.248e9, rel=1e-3)
    assert config.kv_entries == config.n_layers + 1 == 5
    drawn = llama.init_params(config, jax.random.PRNGKey(0))
    assert jax.tree.structure(drawn) == jax.tree.structure(params)
    assert config.num_params() == sum(
        x.size for x in jax.tree.leaves(drawn))
    assert set(llama.param_sharding_rules(config)['mtp']) == \
        set(params['mtp'])
    with pytest.raises(ValueError, match='nextn_layers'):
        llama.get_config('tiny-latent-moe', nextn_layers=1)
    with pytest.raises(ValueError, match='nextn_layers'):
        llama.get_config('tiny', nextn_layers=1)


def test_quantised_leaves_cover_the_module(model):
    from skypilot_tpu.models import quant
    config, params = model
    q = quant.quantize_params(params, config)
    assert set(q['mtp']['eh_proj']) == {'q', 's'}
    assert set(q['mtp']['layers']['w_gate']) == {'q', 's'}
    assert q['mtp']['layers']['router'].dtype == jnp.float32
    init = quant.init_quantized(config, jax.random.PRNGKey(1),
                                dtype=jnp.float32)
    assert jax.tree.structure(init) == jax.tree.structure(q)


# ---------------------------------------------------------------------
# (b) the program against the reference, through the cache
# ---------------------------------------------------------------------


def test_main_and_module_logits_equal_the_reference(model):
    """Prefill in three chunks, then the first pair, then six
    greedy rounds through the cache: the main logits that chose each
    token and the module's logits at the first pair and at the
    frontier equal the reference's over the whole sequence."""
    config, params = model
    cfg = _ref_cfg(config)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, config.vocab_size, 37).tolist()
    table = jnp.arange(1, 13, dtype=jnp.int32)
    logits, pools, h = _prefill(config, params, _pool(config), table,
                                prompt)
    want = np.asarray(reference.logits_at(
        params, jnp.asarray(prompt), jnp.asarray([36]), cfg))[0]
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=_TOL,
                               rtol=0)
    first = int(want.argmax())
    assert int(np.asarray(logits[0]).argmax()) == first

    def module_ref(tokens, i):
        return np.asarray(reference.module_logits_at(
            params, jnp.asarray(tokens), jnp.asarray([i]), cfg))[0]

    got = _module_probe(config, params, pools, table, h, first, 36)
    np.testing.assert_allclose(got, module_ref(prompt + [first], 36),
                               atol=_TOL, rtol=0)
    # The engine's own first draft, then rounds.
    draft, pools, _ = _first(
        params, h, jnp.asarray(first), pools, table,
        jnp.asarray(36), config, _BLOCK, jnp.asarray(0.0),
        jnp.asarray(1.0), jnp.asarray(0))
    assert int(draft) == int(got.argmax())
    toks, counts, pools, pos, tok, draft, routed = \
        _rounds(
            params, jnp.asarray([first]), jnp.asarray([int(draft)]),
            pools, table[None], jnp.asarray([37]),
            jnp.asarray([True]), jnp.asarray([True]), config, 6,
            _BLOCK)
    kept = np.arange(2)[None] < np.asarray(counts)[0][:, None]
    emitted = np.asarray(toks)[0][kept].tolist()
    assert int(pos[0]) == 37 + len(emitted) and 6 <= len(emitted) <= 12
    seq = prompt + [first] + emitted
    ref = np.asarray(reference.logits_at(
        params, jnp.asarray(seq[:-1]),
        jnp.arange(37, len(seq) - 1), cfg))
    assert ref.argmax(-1).tolist() == emitted
    assert int(tok[0]) == emitted[-1]
    # The next draft is the module's argmax at the last pair.
    last = len(seq) - 2
    want = module_ref(seq, last)
    top2 = np.sort(want)[-2:]
    assert top2[1] - top2[0] > 10 * _TOL    # no near tie to excuse
    assert int(draft[0]) == int(want.argmax())
    # Tallies: 4 layers' worth of expert layers + the module's.
    assert routed.shape == (2, config.n_layers - config.dense_first
                            + 1, config.n_experts)
    assert int(routed[0].sum()) == 6 * 2 * config.moe_top_k * 4


def test_module_rows_lie_one_slot_past_their_pairs(model):
    """Slot 0 of the module's entry stays as the pool was made, slot
    i holds the pair (h_{i-1}, t_i), and the main entries hold their
    rows at the tokens' own slots."""
    config, params = model
    prompt = np.random.default_rng(2).integers(0, 512, 20).tolist()
    table = jnp.arange(1, 13, dtype=jnp.int32)
    _, pools, _ = _prefill(config, params, _pool(config), table,
                           prompt, chunk=8)
    rows = np.asarray(pools[0])            # [E, NB, bs, W]
    module = rows[-1, 1:4].reshape(-1, rows.shape[-1])
    assert not module[0].any() and np.abs(module[1:20]).max(-1).min() > 0
    assert not module[20:].any()
    main = rows[0, 1:4].reshape(-1, rows.shape[-1])
    assert np.abs(main[:20]).max(-1).min() > 0 and not main[20:].any()


# ---------------------------------------------------------------------
# (c) a prefix hit
# ---------------------------------------------------------------------


def test_module_logits_after_a_prefix_hit_equal_the_cold_run(model):
    """Another request wrote the first two blocks (16 tokens); this
    one attaches them and prefills from token 15 with a read-only
    lane. Its main logits and the module's logits at the first pair
    equal the cold run's, and the shared blocks are not written."""
    config, params = model
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 512, 16).tolist()
    other = shared + rng.integers(0, 512, 9).tolist()
    mine = shared + rng.integers(0, 512, 21).tolist()
    cold_table = jnp.arange(20, 30, dtype=jnp.int32)
    cold_logits, cold_pools, cold_h = _prefill(
        config, params, _pool(config), cold_table, mine)
    first = int(np.asarray(cold_logits[0]).argmax())
    cold = _module_probe(config, params, cold_pools, cold_table,
                         cold_h, first, len(mine) - 1)

    other_table = jnp.arange(1, 11, dtype=jnp.int32)
    _, pools, _ = _prefill(config, params, _pool(config), other_table,
                           other)
    before = np.asarray(pools[0])[:, 1:3].copy()
    table = jnp.asarray([1, 2, 11, 12, 13, 14, 15, 16, 17, 18],
                        jnp.int32)
    logits, pools, h = _prefill(config, params, pools, table, mine,
                                start=16, hidden=1)
    np.testing.assert_array_equal(np.asarray(pools[0])[:, 1:3], before)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(
        cold_logits), atol=_HIT_TOL, rtol=0)
    hit = _module_probe(config, params, pools, table, h, first,
                        len(mine) - 1)
    np.testing.assert_allclose(hit, cold, atol=_HIT_TOL, rtol=0)
    # Without the read-only lane the pair at token 16 lacks its
    # state, and it shows.
    _, pools, h = _prefill(config, params, pools, table, mine,
                           start=16, hidden=0)
    wrong = _module_probe(config, params, pools, table, h, first,
                          len(mine) - 1)
    assert np.abs(wrong - cold).max() > 100 * _HIT_TOL


# ---------------------------------------------------------------------
# (d) the latent verify body and the rounds
# ---------------------------------------------------------------------


def _two_rows(config, params):
    rng = np.random.default_rng(4)
    tables = jnp.asarray([list(range(1, 9)), list(range(9, 17))],
                         jnp.int32)
    pools, firsts, drafts = _pool(config), [], []
    lens = (13, 22)
    for row, n in enumerate(lens):
        prompt = rng.integers(0, 512, n).tolist()
        logits, pools, h = _prefill(config, params, pools,
                                    tables[row], prompt)
        firsts.append(int(np.asarray(logits[0]).argmax()))
        draft, pools, _ = _first(
            params, h, jnp.asarray(firsts[-1]), pools, tables[row],
            jnp.asarray(n - 1), config, _BLOCK, jnp.asarray(1.0),
            jnp.asarray(1.0), jnp.asarray(7 + row))
        drafts.append(int(draft))
    return (pools, tables, jnp.asarray(lens, jnp.int32),
            jnp.asarray(firsts, jnp.int32),
            jnp.asarray(drafts, jnp.int32))


@pytest.mark.parametrize('temperature', [0.0, 1.0])
def test_the_latent_verify_body_equals_width_one_decode(model,
                                                        temperature):
    """Three decode steps, then one verify of the same three
    positions with the decode's own tokens as drafts: the same
    tokens, every draft kept, the same rows in the pool."""
    config, params = model
    pools, tables, pos, tok, _ = _two_rows(config, params)
    sampling = dict(_sampling(2, temperature),
                    mask_idx=jnp.zeros((2,), jnp.int32),
                    mask_table=jnp.ones((1, config.vocab_size), bool))
    toks, stepped, new_pos, _ = _steps(
        params, tok, pools, tables, pos, jnp.asarray([True, True]),
        config, 3, _BLOCK, None, None, sampling)
    window = jnp.concatenate([tok[:, None], toks[:, :2]], axis=1)
    preds, accepted, verify_pos, last, verified = \
        _verify(
            params, window, pools, tables, pos,
            jnp.asarray([3, 3], jnp.int32), config, 3, _BLOCK,
            sampling=dict(sampling, mask_table=jnp.ones(
                (1, 3, config.vocab_size), bool)))
    assert np.array_equal(np.asarray(preds), np.asarray(toks))
    assert np.asarray(accepted).tolist() == [2, 2]
    assert np.array_equal(np.asarray(verify_pos), np.asarray(new_pos))
    assert np.array_equal(np.asarray(last), np.asarray(toks)[:, 2])
    main = slice(0, config.n_layers)
    np.testing.assert_allclose(np.asarray(verified[0])[main],
                               np.asarray(stepped[0])[main],
                               atol=_TOL, rtol=0)
    # A draft that is not the target's token: kept up to it.
    wrong = window.at[0, 2].set((window[0, 2] + 1) % 512)
    _, accepted, verify_pos, *_ = _verify(
        params, wrong, pools, tables, pos,
        jnp.asarray([3, 3], jnp.int32), config, 3, _BLOCK)
    if temperature == 0.0:
        assert np.asarray(accepted).tolist() == [1, 2]
        assert np.asarray(verify_pos).tolist() == [15, 25]


@pytest.mark.parametrize('temperature', [0.0, 1.0])
def test_rounds_emit_what_plain_decode_emits(model, temperature):
    """Rounds that draft, rounds that never draft (no grant: a round
    after a rejection equals one of these), and the plain decode
    scan emit the same stream; the drafting rounds kept some drafts
    and dropped some, so both paths of a round ran."""
    config, params = model
    pools, tables, pos, tok, draft = _two_rows(config, params)
    on = jnp.asarray([True, True])
    sampling = None if temperature == 0.0 else _sampling(
        2, temperature)

    def streams(grant, rounds):
        toks, counts, *_ = _rounds(
            params, tok, draft, pools, tables, pos, on,
            jnp.asarray([grant, grant]), config, rounds, _BLOCK,
            sampling)
        counts = np.asarray(counts)
        kept = np.arange(2)[None, None] < counts[..., None]
        return [np.asarray(toks)[r][kept[r]].tolist()
                for r in range(2)], counts

    drafted, counts = streams(True, 8)
    plain, ones = streams(False, 16)
    assert (ones == 1).all()
    full = None if sampling is None else dict(
        sampling, mask_idx=jnp.zeros((2,), jnp.int32),
        mask_table=jnp.ones((1, config.vocab_size), bool))
    scanned, *_ = _steps(
        params, tok, pools, tables, pos, on, config, 16, _BLOCK,
        None, None, full)
    for r in range(2):
        assert 8 <= len(drafted[r]) <= 16
        assert drafted[r] == plain[r][:len(drafted[r])]
        assert plain[r] == np.asarray(scanned)[r].tolist()
    if temperature:
        assert (counts == 2).any() and (counts == 1).any()


def test_inactive_rows_and_narrow_views_change_nothing(model):
    config, params = model
    pools, tables, pos, tok, draft = _two_rows(config, params)
    args = (params, tok, draft, pools, tables, pos)
    whole, *_ = _rounds(
        *args, jnp.asarray([True, True]), jnp.asarray([True, True]),
        config, 4, _BLOCK, _sampling(2, 1.0))
    toks, counts, _, new_pos, new_tok, new_draft, _ = \
        _rounds(
            *args, jnp.asarray([True, False]),
            jnp.asarray([True, True]), config, 4, _BLOCK,
            _sampling(2, 1.0), view_blocks=6)
    assert np.array_equal(np.asarray(toks)[0], np.asarray(whole)[0])
    assert not np.asarray(counts)[1].any()
    assert int(new_pos[1]) == int(pos[1])
    assert int(new_tok[1]) == int(tok[1])
    assert int(new_draft[1]) == int(draft[1])
    with pytest.raises(exceptions.NotSupportedError,
                       match='no next-token-prediction module'):
        plain = llama.get_config('tiny-latent-moe')
        _rounds(
            llama.init_params(plain, jax.random.PRNGKey(0)), tok,
            draft, _pool(plain), tables, pos,
            jnp.asarray([True, True]), jnp.asarray([True, True]),
            plain, 1, _BLOCK)
