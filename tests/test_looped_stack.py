"""A looped layer stack (Ouro / LoopLM) on the paged engine: the same
layers run ``loop_passes`` times over shared weights, a KV entry for
every pass and layer, a norm on each branch's output, the final norm
after every pass, the exit gate (models/decode.looped_stack,
serve/batching.py, serve/kv_pool.py).

Everything is held to the PLAIN REFERENCE
(perf/reference/ouro_block_f32.py: float32, ``highest`` precision, no
cache, imports nothing of the program), on logits, at a tiny size, on
seeded random weights whose norms are 1 + 0.1 N(0, 1) so that an
ignored norm shows.

Tolerances, and why: the tiny configurations compute in float32, so
with a float pool program and reference differ by summation order
only - 2e-4 on logits of size about 1 holds it and an omitted norm
(0.1 relative) or pass misses it by three orders. An int8 pool rounds
every cached key and value to 1 part in 254 of its head's largest:
0.05 on logits holds what was measured (under 0.02) and still fails
an omitted pass or norm (0.3 and more, tested below).
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import ouro_block_f32 as reference
from skypilot_tpu import exceptions
from skypilot_tpu.models import decode, llama
from skypilot_tpu.models.decode import (decode_steps_paged,
                                        verify_step_paged)
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.batching import BatchingEngine

_BLOCK = 8
_TOL = {False: 2e-4, True: 0.05}   # by int8 pool; module docstring


def _config(passes=4, q=1.0, sandwich=True):
    return llama.get_config('tiny-loop', loop_passes=passes,
                            exit_threshold=q,
                            sandwich_norms=sandwich)


def _ref_cfg(config):
    """The published keys the reference reads. A configuration with
    no gate serves the last pass: a threshold no sum reaches."""
    return {'hidden_size': config.dim,
            'num_attention_heads': config.n_heads,
            'num_key_value_heads': config.n_kv_heads,
            'rms_norm_eps': config.norm_eps,
            'rope_theta': config.rope_theta,
            'total_ut_steps': config.loop_passes,
            'early_exit_threshold': 2.0 if config.exit_threshold
            is None else config.exit_threshold}


def _weights(config, seed=0, gate_scale=1.0):
    """Seeded weights: the program's own initialiser for the shapes,
    then every norm 1 + 0.1 N(0, 1) and a live gate."""
    params = llama.init_params(config, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed + 1)

    def noisy(w):
        return jnp.asarray(1.0 + 0.1 * rng.standard_normal(w.shape),
                           w.dtype)

    params['final_norm'] = noisy(params['final_norm'])
    for name in list(params['layers']):
        if 'norm' in name:
            params['layers'][name] = noisy(params['layers'][name])
    d = config.dim
    params['exit_gate_w'] = jnp.asarray(
        gate_scale * d ** -0.5 * rng.standard_normal((d, 1)),
        config.dtype)
    params['exit_gate_b'] = jnp.asarray(
        0.1 * rng.standard_normal((1,)), config.dtype)
    return params


def _prompt(n, seed=3):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def _ref_logits(params, config, tokens, positions):
    return np.asarray(reference.logits_at(
        params, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32), _ref_cfg(config)))


_PREFILL = jax.jit(decode.forward_paged, static_argnums=(6, 7))


def _pools(config, num_blocks=12, kv_int8=False):
    return kv_pool.KVBlockPool(config, num_blocks, _BLOCK,
                               kv_int8=kv_int8).caches


def _prefill(params, config, tokens, pools, chunk, table=None):
    """``tokens`` through ``forward_paged`` in chunks of ``chunk``,
    into blocks 1, 2, ...: (logits at the last token, pools)."""
    if table is None:
        table = jnp.arange(1, 9, dtype=jnp.int32)
    logits = None
    for start in range(0, len(tokens), chunk):
        part = tokens[start:start + chunk]
        padded = part + [0] * (chunk - len(part))
        logits, pools, _ = _PREFILL(
            params, jnp.asarray([padded], jnp.int32), pools, table,
            jnp.asarray(start, jnp.int32),
            jnp.asarray(len(part), jnp.int32), config, _BLOCK)
    return np.asarray(logits[0]), pools


def _served_gap(params, config, prompt, served):
    gap, _ = reference.served_token_gaps(
        params, _ref_cfg(config), prompt, served, pad_to=64)
    return float(gap.max())


# ---------------------------------------------------------------------
# The program against the reference, on logits
# ---------------------------------------------------------------------


@pytest.mark.parametrize('kv_int8', [False, True])
@pytest.mark.parametrize('passes', [1, 2, 4])
def test_prefill_logits_match_the_reference(passes, kv_int8):
    config = _config(passes)
    params = _weights(config)
    tokens = _prompt(24)
    got, _ = _prefill(params, config, tokens,
                      _pools(config, kv_int8=kv_int8), chunk=32)
    want = _ref_logits(params, config, tokens, [23])[0]
    # One chunk attends its own exact rows: no int8 rounding yet.
    assert np.abs(got - want).max() < _TOL[False]


@pytest.mark.parametrize('kv_int8', [False, True])
@pytest.mark.parametrize('passes', [1, 2, 4])
def test_engine_prefill_then_decode_matches_the_reference(passes,
                                                          kv_int8):
    """Prefill (two chunks), then decode through the cache: every
    served token's logit lies within the tolerance of the best logit
    of the reference's full forward over prompt + served."""
    config = _config(passes)
    params = _weights(config)
    engine = BatchingEngine(params, config, slots=2, max_seq=64,
                            steps_per_dispatch=3, block_size=_BLOCK,
                            prefill_chunk=16, kv_int8=kv_int8,
                            speculative=False)
    try:
        assert engine.pool.caches is None
        assert engine.caches[0].shape[0] == passes * config.n_layers
        prompt = _prompt(21)
        served = engine.generate(prompt, 12)
        assert len(served) == 12
        assert _served_gap(params, config, prompt, served) < \
            _TOL[kv_int8]
        counted = engine._metrics  # pylint: disable=protected-access
        assert counted['kv_token_bytes'].value == \
            engine.pool.token_bytes
    finally:
        engine.close()


@pytest.mark.parametrize('kv_int8', [False, True])
def test_chunked_prefill_equals_one_chunk(kv_int8):
    config = _config(4)
    params = _weights(config)
    tokens = _prompt(29)
    one, pools_one = _prefill(params, config, tokens,
                              _pools(config, kv_int8=kv_int8), 32)
    many, pools_many = _prefill(params, config, tokens,
                                _pools(config, kv_int8=kv_int8), 8)
    # A later chunk reads earlier chunks through the pool: exact
    # with a float pool, an int8 round trip otherwise.
    assert np.abs(one - many).max() < _TOL[kv_int8]
    if not kv_int8:
        # Every entry of every written block holds the same rows.
        np.testing.assert_allclose(
            np.asarray(pools_one[0][:, 1:4]),
            np.asarray(pools_many[0][:, 1:4]), atol=1e-5)


def test_a_pass_reads_its_own_entries_and_no_other():
    """Chunk 2 attends chunk 1 through the pool. The rows pass 1
    writes for chunk 2 depend on pass 1's entries of chunk 1 alone:
    noise in another pass's entry leaves them bit for bit, noise in
    its own changes them."""
    config = _config(4)
    n_layers = config.n_layers
    params = _weights(config)
    tokens = _prompt(16)
    _, pools = _prefill(params, config, tokens[:8], _pools(config), 8)

    def second_chunk(pools_in):
        _, out, _ = _PREFILL(
            params, jnp.asarray([tokens[8:]], jnp.int32), pools_in,
            jnp.arange(1, 9, dtype=jnp.int32),
            jnp.asarray(8, jnp.int32), jnp.asarray(8, jnp.int32),
            config, _BLOCK)
        return np.asarray(out[0])        # K pool [E, NB, bs, H, hd]

    def with_noise(entry):
        k = pools[0]
        noise = jax.random.normal(jax.random.PRNGKey(entry),
                                  k[entry, 1].shape, k.dtype)
        return (k.at[entry, 1].set(noise),) + tuple(pools[1:])

    clean = second_chunk(pools)
    first_pass = slice(0, n_layers)
    # Pass 2, layer 0's entry of block 1 (chunk 1's keys).
    other = second_chunk(with_noise(n_layers))
    assert np.array_equal(other[first_pass, 2], clean[first_pass, 2])
    assert not np.allclose(other[n_layers + 1:, 2],
                           clean[n_layers + 1:, 2], atol=1e-3)
    # Pass 1, layer 0's own entry: its later layers' rows move.
    own = second_chunk(with_noise(0))
    assert not np.allclose(own[1:n_layers, 2], clean[1:n_layers, 2],
                           atol=1e-3)


def test_exit_gate_serves_the_pass_the_rule_picks():
    """q = 0.5 with a lively gate: the logits at each position are
    those of the pass the reference's rule picks there, and the
    positions do not all pick the same pass."""
    config = _config(4, q=0.5)
    params = _weights(config, gate_scale=3.0)
    tokens = _prompt(24)
    cfg = _ref_cfg(config)
    with jax.default_matmul_precision('highest'):
        _, lam = reference.pass_states(
            params, jnp.asarray(tokens, jnp.int32), cfg)
    picked = np.asarray(reference.exit_pass(lam, 0.5))
    assert len(set(picked.tolist())) >= 2, picked
    # Leave out a position whose sum lands on the threshold: there
    # rounding, not the rule, decides.
    lam = np.asarray(lam, np.float64)
    before = np.concatenate([np.ones_like(lam[:1]),
                             np.cumprod(1 - lam, 0)[:-1]])
    margin = np.abs(np.cumsum(lam * before, 0) - 0.5).min(0)
    checked = 0
    for n in range(8, 25, 2):
        if margin[n - 1] < 1e-3:
            continue
        got, _ = _prefill(params, config, tokens[:n], _pools(config),
                          32)
        want = _ref_logits(params, config, tokens, [n - 1])[0]
        assert np.abs(got - want).max() < _TOL[False], (n, picked)
        checked += 1
    assert checked >= 6
    # ... and through the engine's decode step.
    engine = BatchingEngine(params, config, slots=2, max_seq=64,
                            steps_per_dispatch=3, block_size=_BLOCK,
                            speculative=False)
    try:
        served = engine.generate(tokens[:15], 9)
        assert _served_gap(params, config, tokens[:15], served) < \
            _TOL[False]
    finally:
        engine.close()


@pytest.mark.parametrize('kv_int8', [False, True])
def test_verify_step_equals_the_decode_steps(kv_int8):
    """The speculative twin runs the same looped stack: a window of
    the TRUE continuation is accepted whole, predicts what the plain
    steps served and commits the same frontier."""
    config = _config(4)
    params = _weights(config)
    prompt = _prompt(12)
    logits, pools = _prefill(params, config, prompt,
                             _pools(config, kv_int8=kv_int8), 16)
    first = jnp.asarray([int(logits.argmax())], jnp.int32)
    tables = jnp.arange(1, 9, dtype=jnp.int32)[None]
    pos = jnp.asarray([12], jnp.int32)
    want, _, _ = decode_steps_paged(
        params, first, pools, tables, pos, jnp.asarray([True]),
        config, 5, _BLOCK)
    want = np.asarray(want)                              # [1, 5]
    window = jnp.concatenate(
        [first[:, None], jnp.asarray(want[:, :3])], axis=1)
    preds, accepted, new_pos, new_tok, _ = verify_step_paged(
        params, window.astype(jnp.int32), pools, tables, pos,
        jnp.asarray([4], jnp.int32), config, 4, _BLOCK)
    np.testing.assert_array_equal(np.asarray(accepted), [3])
    np.testing.assert_array_equal(np.asarray(preds), want[:, :4])
    np.testing.assert_array_equal(np.asarray(new_pos), [16])
    np.testing.assert_array_equal(np.asarray(new_tok), want[:, 3])
    assert _served_gap(params, config, prompt,
                       [int(first[0])] + want[0].tolist()) < \
        _TOL[kv_int8]


# ---------------------------------------------------------------------
# The scheduler's paths on all entries of a block
# ---------------------------------------------------------------------


def _engine(params, config, **kwargs):
    build = dict(slots=2, max_seq=64, steps_per_dispatch=3,
                 block_size=_BLOCK, prefill_chunk=8,
                 max_num_batched_tokens=16, speculative=False)
    build.update(kwargs)
    return BatchingEngine(params, config, **build)


@pytest.fixture(scope='module')
def looped():
    config = _config(4)
    return config, _weights(config)


def test_prefix_cache_hit_leaves_the_output_unchanged(looped):
    config, params = looped
    prompt = _prompt(24, seed=5)
    engine = _engine(params, config)
    try:
        first = engine.generate(prompt, 8)
        again = engine.generate(prompt, 8)
        hits = engine._metrics['prefix_hits'].value  # pylint: disable=protected-access
    finally:
        engine.close()
    assert hits >= 2
    assert again == first
    assert _served_gap(params, config, prompt, again) < _TOL[False]


def test_copy_on_write_copies_every_entry(looped):
    config, params = looped
    base = _prompt(24, seed=6)
    fork = base[:20] + [99, 98, 97, 96]
    cold = _engine(params, config, prefix_caching=False)
    try:
        want = cold.generate(fork, 8)
    finally:
        cold.close()
    engine = _engine(params, config)
    try:
        engine.generate(base, 8)
        got = engine.generate(fork, 8)
        admits = [e for e in engine.events if e[0] == 'admit']
    finally:
        engine.close()
    assert admits[-1][2] == 20      # 16 by whole blocks + 4 copied
    assert got == want
    assert _served_gap(params, config, fork, got) < _TOL[False]


def test_preempt_and_resume_leaves_the_output_unchanged(looped):
    config, params = looped
    prompts = [_prompt(20, seed=s) for s in (7, 8)]
    roomy = _engine(params, config, prefix_caching=False)
    try:
        want = [roomy.generate(p, 20) for p in prompts]
    finally:
        roomy.close()
    # 5 usable blocks of 8: both rows cannot reach 40 positions.
    tight = _engine(params, config, num_blocks=8,
                    prefix_caching=False)
    try:
        queues = [tight.submit(p, 20) for p in prompts]
        got = []
        for q in queues:
            toks = []
            while True:
                t = q.get(timeout=300)
                if t is None:
                    break
                assert not isinstance(t, BaseException), t
                toks.append(t)
            got.append(toks)
        preempted = tight._metrics['preemptions'].value  # pylint: disable=protected-access
    finally:
        tight.close()
    assert preempted >= 1
    assert got == want
    for p, toks in zip(prompts, got):
        assert _served_gap(params, config, p, toks) < _TOL[False]


def test_loop_passes_are_counted_with_the_tokens(looped):
    config, params = looped
    engine = _engine(params, config)
    try:
        counted = engine._metrics  # pylint: disable=protected-access
        tokens0 = counted['tokens'].value
        passes0 = counted['loop_passes'].value
        engine.generate(_prompt(12, seed=9), 7)
        # The loop counts a dispatch's tokens after handing them on.
        deadline = time.time() + 10
        while counted['tokens'].value - tokens0 < 7 and \
                time.time() < deadline:
            time.sleep(0.01)
        tokens = counted['tokens'].value - tokens0
        passes = counted['loop_passes'].value - passes0
    finally:
        engine.close()
    assert tokens == 7 and passes == 7 * config.loop_passes


# ---------------------------------------------------------------------
# What stays as it was, and what refuses
# ---------------------------------------------------------------------


def test_one_pass_without_norms_or_gate_is_the_path_before():
    """``tiny`` (one pass, no branch norms, no gate) takes the form
    every model traced before: one scan over the layers inside the
    token scan, no pass loop, no gate; and a gate over ONE pass,
    which can only pick that pass, leaves its logits bit for bit."""
    plain = llama.get_config('tiny')
    assert plain.plain_stack and plain.kv_entries == plain.n_layers
    params = llama.init_params(plain, jax.random.PRNGKey(0))
    pools = _pools(plain)

    def scans(config, weights):
        return str(jax.make_jaxpr(
            lambda p, c: decode_steps_paged(
                p, jnp.zeros((2,), jnp.int32), c,
                jnp.zeros((2, 8), jnp.int32),
                jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
                config, 2, _BLOCK))(weights, _pools(config))
                   ).count(' scan[')

    assert scans(plain, params) == 2       # tokens, layers
    looped = _config(4)
    assert scans(looped, _weights(looped)) == 3    # and passes
    gated = dataclasses.replace(plain, exit_threshold=1.0)
    extra = dict(params, exit_gate_w=jnp.ones((plain.dim, 1)),
                 exit_gate_b=jnp.zeros((1,)))
    tokens = _prompt(16)
    want, _ = _prefill(params, plain, tokens, pools, 16)
    got, _ = _prefill(extra, gated, tokens, _pools(gated), 16)
    assert np.array_equal(got, want)
    # ... and the paged path still equals the dense one, as before.
    dense = decode.forward_cached(
        params, jnp.asarray([tokens], jnp.int32),
        decode.init_cache(plain, 1, 64), plain, last_only=True,
        prefill=True)[0]
    np.testing.assert_allclose(want, np.asarray(dense[0, -1]),
                               atol=1e-5)


@pytest.mark.parametrize('body', ['forward', 'greedy_generate'])
def test_dense_bodies_refuse_a_looped_stack(body, looped):
    config, params = looped
    tokens = jnp.asarray([_prompt(8)], jnp.int32)
    with pytest.raises(exceptions.NotSupportedError,
                       match='loop_passes=4'):
        if body == 'forward':
            llama.forward(params, tokens, config)
        else:
            decode.greedy_generate(params, tokens, config, 4,
                                   max_seq=32)


@pytest.mark.parametrize('leave_out', ['a pass', 'a branch norm'])
def test_the_tolerance_fails_what_it_has_to(leave_out, looped):
    """The comparison is tight enough: the reference with a pass or
    the branch norms left out lies far outside the int8 tolerance."""
    config, params = looped
    tokens = _prompt(24)
    want = _ref_logits(params, config, tokens, [23])[0]
    if leave_out == 'a pass':
        broken = _ref_logits(
            params, dataclasses.replace(config, loop_passes=3),
            tokens, [23])[0]
    else:
        ones = dict(params, layers=dict(
            params['layers'],
            attn_out_norm=jnp.ones_like(
                params['layers']['attn_out_norm']),
            mlp_out_norm=jnp.ones_like(
                params['layers']['mlp_out_norm'])))
        broken = _ref_logits(ones, config, tokens, [23])[0]
    assert np.abs(broken - want).max() > 5 * _TOL[True]


def test_published_config_and_parameter_count():
    config = llama.get_config('ouro-2.6b')
    assert (config.loop_passes, config.kv_entries) == (4, 192)
    assert config.sandwich_norms and config.exit_threshold == 1.0
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert config.num_params() == (48 * layer + 2 * 49152 * 2048 +
                                   2048 + 2048 + 1)
    shapes = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(shapes)) == config.num_params()
    rules = llama.param_sharding_rules(config)
    assert jax.tree.structure(rules, is_leaf=lambda x: not
                              isinstance(x, dict)) == \
        jax.tree.structure(shapes)
    # KV a token: 192 entries x 2 x 16 heads x (128 codes + a bf16
    # scale).
    pool = kv_pool.KVBlockPool(
        llama.get_config('tiny-loop'), 4, 16, kv_int8=True)
    assert pool.token_bytes == 8 * 2 * 4 * (32 + 2)
    assert 192 * 2 * 16 * (128 + 2) == 798720
