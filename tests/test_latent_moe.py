"""Latent attention (MLA) as a block group of its own, in its absorbed
and its expanded form, four residual streams mixed through Sinkhorn-
projected matrices, dense layers before the expert layers, selection
by score plus a bias (models/llama.py, models/moe.py, models/decode.py,
ops/decode_attention.py, serve/kv_pool.py, serve/batching.py), at a
tiny size on the CPU with seeded random weights, against the plain
reference ``perf/reference/xing4_block_f32.py``. Logits are compared,
not tokens: on random weights the largest logit changes on rounding.
Each tolerance says what it allows for."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import weights_xing4
from perf.reference import xing4_block_f32 as reference
from skypilot_tpu import exceptions
from skypilot_tpu.models import decode, llama, moe
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.batching import BatchingEngine

_BLOCK = 8
# Float32 weights and float32 arithmetic on both sides: what is left
# is the order of the sums (key tiles with a running maximum and the
# absorbed products against one softmax over expanded keys; experts
# grouped against one at a time).
_TOL = 2e-4


def _config(**overrides):
    return llama.get_config('tiny-latent-moe', **overrides)


def _ref_cfg(config):
    """The reference's view of ``config``: the configuration file's
    ``model`` keys."""
    factor, orig, fast, slow, all_dim = config.rope_yarn
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
        'rope_scaling': {
            'type': 'yarn', 'factor': factor,
            'original_max_position_embeddings': orig,
            'beta_fast': fast, 'beta_slow': slow, 'mscale': 1,
            'mscale_all_dim': all_dim},
        'hc_mult': config.hc_mult,
        'hc_sinkhorn_iters': config.hc_sinkhorn_iters,
        'hc_eps': config.hc_eps,
        'mhc_h_res_clamp_min': config.hc_clamp[0],
        'mhc_h_res_clamp_max': config.hc_clamp[1]}


def _weights(config, seed=3, int8=False, dtype=jnp.float32):
    return weights_xing4.make_weights(_ref_cfg(config), seed,
                                      int8=int8, dtype=dtype)[0]


@pytest.fixture(scope='module')
def model():
    config = _config()
    return config, _weights(config)


def _pool(config, n_blocks=48):
    return kv_pool.KVBlockPool(config, n_blocks, _BLOCK).caches


def _tables(rows, per_row=20):
    return 1 + jnp.arange(rows * per_row, dtype=jnp.int32).reshape(
        rows, per_row)


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


def _decode_logits(monkeypatch, params, config, first, pools, tables,
                   pos, steps):
    """``decode_steps_paged`` with every step's logits copied out
    (the step returns tokens): ([steps, rows, vocab], tokens, routed).
    """
    seen = []
    real = decode.sample_lib.sample_rows

    def recorded(logits, *args):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return real(logits, *args)

    monkeypatch.setattr(decode.sample_lib, 'sample_rows', recorded)
    rows = len(pos)
    sampling = {'temps': jnp.zeros((rows,), jnp.float32),
                'top_ps': jnp.ones((rows,), jnp.float32),
                'seeds': jnp.zeros((rows,), jnp.int32),
                'mask_idx': jnp.zeros((rows,), jnp.int32),
                'mask_table': jnp.ones((1, config.vocab_size), bool)}
    toks, _, new_pos, routed = decode.decode_steps_paged(
        params, jnp.asarray(first, jnp.int32), pools, tables,
        jnp.asarray(pos, jnp.int32), jnp.ones((rows,), bool), config,
        steps, _BLOCK, None, None, sampling)
    jax.effects_barrier()
    assert np.asarray(new_pos).tolist() == [p + steps for p in pos]
    return np.stack(seen), np.asarray(toks), np.asarray(routed)


# ---------------------------------------------------------------------
# (a) prefill (expanded) then decode through the cache (absorbed)
# ---------------------------------------------------------------------


@pytest.mark.parametrize('served', ['float32', 'bf16-int8'])
def test_prefill_then_decode_against_the_reference(served,
                                                   monkeypatch):
    """Two rows, prefilled in chunks through the expanded form and
    decoded 16 steps through the cache and the absorbed form with
    their own greedy tokens; every step's logits against the
    reference's full forward pass over the sequence as it came out.

    float32: the order of the sums alone, at every position. The
    served types (bf16 streams and cache, int8 matmul weights that
    the reference widens to the same values): bf16 keeps 8 bits and
    twenty sublayers round the streams in turn, which moves the
    logits, of magnitude 2 to 3 here, by 0.1 to 0.35 (read over
    three seeds); and at a few positions a router near-tie falls the
    other way, which at 8 experts of width 64 moves a whole
    position's logits by 1 to 3. So: three quarters of the positions
    within 0.5, their median within 0.25, none beyond 5. (What a
    mixer, the bias or the rotated score term left out does is held
    to the float32 tolerance below.)"""
    if served == 'float32':
        config, tol = _config(), _TOL
        params = _weights(config)
    else:
        config, tol = _config(dtype=jnp.bfloat16), None
        params = _weights(config, int8=True, dtype=jnp.bfloat16)
    worst = []

    def compare(got, want):
        if tol is not None:
            np.testing.assert_allclose(got, want, atol=tol, rtol=0)
        worst.extend(np.abs(got - want).reshape(
            -1, want.shape[-1]).max(-1))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (100, 20)]
    steps = 16
    tables, pools = _tables(2), _pool(config)
    first = []
    for row, prompt in enumerate(prompts):
        logits, pools = _prefill(params, config, prompt, pools,
                                 tables[row], 16)
        want = _reference_logits(params, config, prompt,
                                 [len(prompt) - 1])[0]
        compare(logits, want)
        first.append(int(logits.argmax()))
    got, toks, routed = _decode_logits(
        monkeypatch, params, config, first, pools, tables, [100, 20],
        steps)
    for row, prompt in enumerate(prompts):
        seq = prompt + [first[row]] + toks[row].tolist()
        want = _reference_logits(
            params, config, seq[:-1],
            np.arange(len(prompt), len(prompt) + steps))
        compare(got[:, row], want)
    assert len(worst) == 2 + 2 * steps
    assert np.percentile(worst, 75) <= 0.5 and max(worst) <= 5.0
    assert np.median(worst) <= 0.25
    # The tally counts the EXPERT layers only, every expert held.
    pairs, hit_steps = routed
    n_moe = config.n_layers - config.dense_first
    assert pairs.shape == (n_moe, config.n_experts)
    assert pairs.sum() == 2 * steps * config.moe_top_k * n_moe
    assert np.all(hit_steps <= steps) and np.all(hit_steps <= pairs)


@pytest.mark.parametrize('fault', ['k_pe', 'sinkhorn', 'bias', 'scale'])
def test_what_is_left_out_shows(model, fault, monkeypatch):
    """The controls of the above: the rotated part of the score left
    out, Sinkhorn cut to one pass, the selection bias left out, the
    routed scale left out. Each departs from the reference by far
    more than the tolerance, so the comparison can tell."""
    config, params = model
    prompt = np.random.default_rng(1).integers(0, 512, 100).tolist()
    if fault == 'k_pe':
        # Patched in underneath the jitted chunk: a trace cached
        # from a sound run must not stand in for it, nor its for a
        # later sound run.
        jax.clear_caches()
        real = da.latent_chunk_attention
        monkeypatch.setattr(
            da, 'latent_chunk_attention',
            lambda q_nope, q_pe, *a, **k: real(
                q_nope, jnp.zeros_like(q_pe), *a, **k))
    broken = {'sinkhorn': {'hc_sinkhorn_iters': 1},
              'scale': {'moe_routed_scale': 1.0}}.get(fault, {})
    if fault == 'bias':
        params = dict(params, layers={
            k: v for k, v in params['layers'].items()
            if k != 'router_bias'})
    logits, _ = _prefill(
        params, dataclasses.replace(config, **broken), prompt,
        _pool(config), _tables(1)[0], 16)
    want = _reference_logits(model[1], config, prompt, [99])[0]
    if fault == 'k_pe':
        jax.clear_caches()
    assert np.abs(logits - want).max() > 50 * _TOL


# ---------------------------------------------------------------------
# (b) one layer in two forms
# ---------------------------------------------------------------------


@pytest.mark.parametrize('weights', ['float32', 'int8'])
def test_the_absorbed_form_equals_the_expanded_form(weights):
    """One position over a cached context of 37 rows: the absorbed
    form (the key up-projection folded into the query, the value
    up-projection after the sum, over the latent view) against the
    expanded form (per-head keys and values multiplied out of the
    same rows), and both against the textbook softmax over expanded
    keys. Float32 rounding; with int8 ``wkv_b`` the per-channel
    scales multiply the query before the codes (absorbed) or the
    product after them (expanded): the same numbers."""
    config = _config()
    params = _weights(config, int8=weights == 'int8')
    lp = jax.tree.map(lambda w: w[0], params['layers'])
    rng = np.random.default_rng(7)
    width, rank = config.latent_width, config.kv_lora_rank
    n = 37
    rows = da.latent_row(*jnp.split(jnp.asarray(rng.standard_normal(
        (n + 1, width)), jnp.float32), [rank], axis=-1))
    assert rows.shape == (n + 1, 128) == (
        n + 1, da.latent_pool_width(width))
    width = rows.shape[-1]
    q_nope = jnp.asarray(rng.standard_normal(
        (1, config.n_heads, config.qk_nope_head_dim)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal(
        (1, config.n_heads, config.qk_rope_head_dim)), jnp.float32)
    pool = jnp.zeros((9, _BLOCK, width), jnp.float32)
    table = jnp.asarray([3, 1, 4, 7, 5], jnp.int32)
    slots = (table[jnp.arange(n) // _BLOCK] * _BLOCK +
             jnp.arange(n) % _BLOCK)
    pool = pool.reshape(-1, width).at[slots].set(rows[:n]).reshape(
        pool.shape)
    scale = llama.attention_scale(config)

    o_lat = da.latent_decode_attention(
        decode.absorb_query(config, q_nope, lp), q_pe,
        da.latent_view(pool, table[None]), jnp.asarray([n]), scale,
        rows[n:])
    absorbed = np.asarray(decode.value_up(config, o_lat, lp))[0]
    expanded = np.asarray(da.latent_chunk_attention(
        q_nope, q_pe, rows[n:], pool, table, jnp.asarray(n), scale,
        lambda c: decode.expand_latent(config, c, lp), rank,
        tile_blocks=2))[0]
    k_nope, v = decode.expand_latent(config, rows[:, :rank], lp)
    scores = (jnp.einsum('hn,shn->hs', q_nope[0], k_nope) +
              jnp.einsum('hr,sr->hs', q_pe[0],
                         rows[:, rank:config.latent_width])) * scale
    textbook = np.asarray(jnp.einsum(
        'hs,shd->hd', jax.nn.softmax(scores, axis=-1), v))
    np.testing.assert_allclose(absorbed, textbook, atol=2e-5, rtol=0)
    np.testing.assert_allclose(expanded, textbook, atol=2e-5, rtol=0)


def test_the_pool_holds_one_latent_row_a_token():
    """A group of kind 'latent': one array of rank + rope values a
    token and entry, no K and V pair, no head axis; an int8 latent is
    refused by name; a copied block copies that one array."""
    config = _config()
    pool = kv_pool.KVBlockPool(config, 12, _BLOCK)
    assert pool.kind == 'latent' and list(pool.groups) == ['latent']
    rows, *rest = pool.caches
    assert rest == [None, None, None]
    # 48 + 16 values a row, in whole 128-lane registers.
    assert rows.shape == (config.n_layers, 12, _BLOCK, 128)
    assert pool.token_bytes == config.n_layers * 128 * 4   # float32
    with pytest.raises(exceptions.NotSupportedError, match='int8 latent'):
        kv_pool.KVBlockPool(config, 12, _BLOCK, kv_int8=True)
    marked = (rows.at[:, 3].set(1.5), None, None, None)
    copied = kv_pool.copy_pool_block(marked, jnp.asarray(3),
                                     jnp.asarray(5))
    assert copied[1:] == (None, None, None)
    assert float(copied[0][:, 5].min()) == 1.5
    assert float(jnp.abs(copied[0][:, 4]).max()) == 0.0
    # The published widths: 576 values a token and entry, 640 in
    # memory, 1,280 B in bf16.
    big = llama.get_config('xing4.0-29b-a4b', n_layers=10)
    assert big.latent_width == 576 and big.kv_entries == 10
    assert da.latent_pool_width(576) == 640


def test_pool_shardings_replicate_a_latent_group_over_tp():
    """Keys and values shard their KV-head axis over 'tp'; a latent
    row has no head axis (every head reads the same row), so its one
    array is whole on every chip and the tuple's other members are
    None."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ('fsdp', 'tp'))
    rows, *rest = kv_pool.pool_shardings(_config(), mesh)
    assert rest == [None, None, None]
    assert all(axis is None for axis in rows.spec) and \
        len(rows.spec) == 4
    plain = kv_pool.pool_shardings(llama.get_config('tiny'), mesh,
                                   kv_int8=True)
    assert plain[0].spec[3] == 'tp' and plain[2].spec[3] == 'tp'


# ---------------------------------------------------------------------
# (c) the streams
# ---------------------------------------------------------------------


def _mix(config, lp, xc, sub='attn'):
    _, (h_post, h_res) = decode.hc_pre(config, xc, lp, sub)
    return np.asarray(h_post), np.moveaxis(np.asarray(h_res), -1, 0)


def test_h_res_is_doubly_stochastic_and_the_clamp_holds(model):
    """After 20 Sinkhorn passes every H_res has columns that sum to 1
    within 1e-5 (the last pass divides them) and rows that do as far
    as 20 passes bring them: within 1e-5 where the pre-activations
    lie within +-0.5 (entries within a factor e of one another),
    within 0.05 under this configuration's drawn mixers (diagonal 2,
    off it -2, noise of 1.1: factors of e^8). Also where the
    pre-activations are driven far past the clamp: exp(30) stays
    finite in float32, and a bias of 1e4 gives what a bias of 30
    gives."""
    config, params = model
    lp = jax.tree.map(lambda w: w[0], params['layers'])
    xc = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 5, config.hc_mult, config.dim)), jnp.float32)
    _, h_res = _mix(config, lp, xc)
    assert h_res.shape == (10, 4, 4) and np.all(h_res > 0)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=0.05)
    mild = dict(lp, hc_attn_a=jnp.asarray([1.0, 1.0, 0.1]),
                hc_attn_b=0.1 * lp['hc_attn_b'])
    _, h_mild = _mix(config, mild, xc)
    assert np.abs(np.log(h_mild * 4)).max() > 0.05      # not uniform
    np.testing.assert_allclose(h_mild.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_mild.sum(-2), 1.0, atol=1e-5)
    # Against the reference's own Sinkhorn, from the same streams.
    want = reference.mixers(xc.reshape(10, 4, -1), lp, 'attn',
                            _ref_cfg(config))
    np.testing.assert_allclose(h_res, np.asarray(want[2]), atol=1e-6)

    def forced(value):
        b = lp['hc_attn_b'].at[8:].set(
            value * (2.0 * jnp.eye(4).reshape(-1) - 1.0))
        return _mix(config, dict(lp, hc_attn_b=b,
                                 hc_attn_phi=0 * lp['hc_attn_phi']),
                    xc)[1]

    far, edge = forced(1e4), forced(30.0)
    assert np.all(np.isfinite(far))
    np.testing.assert_array_equal(far, edge)
    np.testing.assert_allclose(far.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(far[0], np.eye(4), atol=1e-5)


@pytest.mark.parametrize('stack', ['dense_layers', 'layers'])
def test_identity_mixers_give_a_plain_residual_stack(model, stack):
    """With H_pre = (1, 0, 0, 0), H_post = (1, 0, 0, 0)^T and H_res =
    I forced through the mixers' own leaves (phi 0; the gates' biases
    at +-40 and 0, 2 sigmoid(0) = 1; the residual bias at the clamp's
    two ends), a layer leaves streams 1 to 3 as they came and makes
    of stream 0 what the same layer makes of ONE stream (``hc_mult``
    1: x + F(norm(x)) a sublayer, a plain residual layer). Layer by
    layer that is the plain residual stack; a dense layer and an
    expert layer."""
    config, params = model
    n, t = config.hc_mult, 24
    bias = jnp.concatenate([
        jnp.asarray([40.0] + [-40.0] * (n - 1)),
        jnp.asarray([0.0] + [-40.0] * (n - 1)),
        60.0 * jnp.eye(n).reshape(-1) - 30.0])
    lp = jax.tree.map(lambda w: w[1], params[stack])
    for sub in ('attn', 'mlp'):
        lp[f'hc_{sub}_phi'] = 0 * lp[f'hc_{sub}_phi']
        lp[f'hc_{sub}_b'] = bias
    angles = llama._rope_frequencies(config, jnp.arange(t))
    pool = jnp.zeros((2, _BLOCK, 128), jnp.float32)

    def layer(cfg, xc):
        q_nope, q_pe, rows, mix = decode.latent_head(cfg, xc, lp,
                                                     angles)
        attn = da.latent_chunk_attention(
            q_nope[0], q_pe[0], rows[0], pool,
            jnp.zeros((4,), jnp.int32), jnp.asarray(0),
            llama.attention_scale(cfg),
            lambda c: decode.expand_latent(cfg, c, lp),
            cfg.kv_lora_rank)
        return decode.latent_tail(cfg, xc, attn.reshape(1, t, -1), lp,
                                  mix)[0]

    xc = jnp.asarray(np.random.default_rng(5).standard_normal(
        (1, t, n, config.dim)), jnp.float32)
    got = np.asarray(layer(config, xc))
    plain = np.asarray(layer(dataclasses.replace(config, hc_mult=1),
                             xc[:, :, 0]))
    np.testing.assert_allclose(got[:, :, 0], plain, atol=1e-5, rtol=0)
    np.testing.assert_allclose(got[:, :, 1:], np.asarray(xc[:, :, 1:]),
                               atol=1e-5, rtol=0)
    assert np.abs(plain - np.asarray(xc[:, :, 0])).max() > 0.1


# ---------------------------------------------------------------------
# (d) routing: a bias on the choice, not on the weights
# ---------------------------------------------------------------------


def test_the_selection_bias_moves_the_choice_and_not_the_weights(model):
    """``route`` with a bias chooses the top k of score + bias, and
    weighs the chosen by their scores WITHOUT it, normalised and
    doubled: a bias large on one expert puts it in every token's
    choice, and the weights are what the scores alone give for that
    choice."""
    config, params = model
    router = params['layers']['router'][0]
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (32, config.dim)), jnp.float32)
    small = params['layers']['router_bias'][0]
    w0, e0 = moe.route(config, x, router)
    w1, e1 = moe.route(config, x, router, small)
    assert np.any(np.asarray(e0) != np.asarray(e1))
    pushed = jnp.zeros((config.n_experts,)).at[5].set(10.0)
    w2, e2 = moe.route(config, x, router, pushed)
    assert np.all(np.any(np.asarray(e2) == 5, axis=-1))
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    for w, e in ((w1, e1), (w2, e2)):
        chosen = np.take_along_axis(scores, np.asarray(e), axis=-1)
        np.testing.assert_allclose(
            np.asarray(w),
            2.0 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 2.0,
                                   atol=1e-6)
    # And against the reference's routing, choice for choice.
    rw, re_ = reference.route(x, router, small, _ref_cfg(config))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(re_))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(rw),
                               atol=1e-6)


def test_command_a_routes_as_it_did_to_the_bit():
    """No bias, scale 1 (every configuration before this one): the
    weights and experts are those of the expression ``route`` was,
    bit for bit."""
    config = llama.get_config('tiny-window-moe')
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((64, config.dim)), jnp.float32)
    router = jnp.asarray(rng.standard_normal(
        (config.dim, config.n_experts)) / 11.3, jnp.float32)
    got_w, got_e = moe.route(config, x, router)
    scores = jax.nn.sigmoid(x @ router)
    want_w, want_e = jax.lax.top_k(scores, config.moe_top_k)
    want_w = want_w / jnp.maximum(want_w.sum(-1, keepdims=True), 1e-20)
    np.testing.assert_array_equal(np.asarray(got_w),
                                  np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(got_e),
                                  np.asarray(want_e))


# ---------------------------------------------------------------------
# (e) no dependence on chunking or on neighbours
# ---------------------------------------------------------------------


def test_chunked_prefill_and_neighbours_leave_the_logits(model,
                                                         monkeypatch):
    """One chunk of 64 against four of 16: the mixers, the experts
    and the dense MLP are per token and equal to the bit however the
    prompt is cut (``test_window_moe`` holds the expert layer to
    that); attention folds the earlier chunks' rows tile by tile with
    a running maximum where one chunk sums them at once, so the
    logits agree to float32 rounding. A row's decode logits do not
    depend on WHO is beside it, to the bit: the same row next to
    another request at another position gives the same bits (the
    engine's batch has one shape, ``slots``; a batch of another
    shape is another compiled kernel and rounds its sums in another
    order, so one row alone agrees to float32 rounding)."""
    config, params = model
    prompt = np.random.default_rng(4).integers(0, 512, 64).tolist()
    row = _tables(1)[0]
    once, _ = _prefill(params, config, prompt, _pool(config), row, 64)
    four, pools = _prefill(params, config, prompt, _pool(config), row,
                           16)
    np.testing.assert_allclose(four, once, atol=_TOL, rtol=0)

    three = _tables(3)
    for row, n in ((1, 40), (2, 23)):
        other = np.random.default_rng(5 + row).integers(
            0, 512, n).tolist()
        _, pools = _prefill(params, config, other, pools, three[row],
                            16)
    beside, _, _ = _decode_logits(
        monkeypatch, params, config, [7, 9], pools, three[:2],
        [64, 40], 4)
    elsewhere, _, _ = _decode_logits(
        monkeypatch, params, config, [7, 300], pools,
        three[jnp.asarray([0, 2])], [64, 23], 4)
    np.testing.assert_array_equal(beside[:, 0], elsewhere[:, 0])
    assert np.abs(beside[:, 1] - elsewhere[:, 1]).max() > 0.1
    alone, _, _ = _decode_logits(monkeypatch, params, config, [7],
                                 pools, three[:1], [64], 4)
    np.testing.assert_allclose(alone[:, 0], beside[:, 0], atol=_TOL,
                               rtol=0)


def test_a_narrower_view_reads_the_same(model, monkeypatch):
    """The decode step cut to the table's first columns that hold the
    row (``view_blocks``) gives the logits of the whole table: what
    lies past a row's length is masked either way."""
    config, params = model
    prompt = np.random.default_rng(9).integers(0, 512, 30).tolist()
    tables = _tables(1)
    _, pools = _prefill(params, config, prompt, _pool(config),
                        tables[0], 16)
    seen = []
    real = decode.sample_lib.sample_rows
    monkeypatch.setattr(
        decode.sample_lib, 'sample_rows',
        lambda logits, *a: (jax.debug.callback(
            lambda x: seen.append(np.asarray(x)), logits),
            real(logits, *a))[1])
    sampling = {'temps': jnp.zeros((1,), jnp.float32),
                'top_ps': jnp.ones((1,), jnp.float32),
                'seeds': jnp.zeros((1,), jnp.int32),
                'mask_idx': jnp.zeros((1,), jnp.int32),
                'mask_table': jnp.ones((1, config.vocab_size), bool)}
    for width in (None, 5):
        decode.decode_steps_paged(
            params, jnp.asarray([3]), pools, tables, jnp.asarray([30]),
            jnp.asarray([True]), config, 2, _BLOCK, None, None,
            sampling, view_blocks=width)
        jax.effects_barrier()
    # Other widths, other kernels: float32 rounding.
    np.testing.assert_allclose(np.stack(seen[:2]), np.stack(seen[2:]),
                               atol=2e-5, rtol=0)


# ---------------------------------------------------------------------
# (f) the engine on the latent group
# ---------------------------------------------------------------------


def _engine(params, config, **kwargs):
    build = dict(slots=3, max_seq=256, block_size=_BLOCK,
                 steps_per_dispatch=4, prefill_chunk=16,
                 speculative=False, sampling=False, num_blocks=100)
    build.update(kwargs)
    return BatchingEngine(params, config, **build)


def _serve(engine, prompt, n):
    return _collect(engine.submit_request(prompt, n))


def _collect(req):
    out = []
    while True:
        item = req.out.get()
        if item is None:
            return out
        if isinstance(item, BaseException):
            raise item
        out.append(int(item))


def _watch(engine):
    """Record the final prefill chunk's logits of every request."""
    seen = []
    prefill = engine._prefill_fn

    def prefill_fn(*args, **kwargs):
        out = prefill(*args, **kwargs)
        seen.append(np.asarray(out[0][0]))
        return out

    engine._prefill_fn = prefill_fn
    return seen


def test_the_engine_serves_what_the_reference_computes(model):
    """Through admission, chunked prefill into the latent group and
    decode dispatches at the prewarmed widths: every served token's
    logit lies within the tolerance of the reference's best at its
    position; the counters count what ran."""
    config, params = model
    engine = _engine(params, config)
    try:
        assert engine.pool.kind == 'latent' and engine.wpool is None
        before = {k: engine._metrics[k].value for k in (
            'mla_absorbed_row_steps', 'mla_absorbed_context',
            'mla_expanded_tokens')}
        prompt = np.random.default_rng(20).integers(0, 512, 120).tolist()
        served = _serve(engine, prompt, 40)
        moved = {k: engine._metrics[k].value - v
                 for k, v in before.items()}
    finally:
        engine.close()
    gap, _ = reference.served_token_gaps(
        params, _ref_cfg(config), prompt, served, pad_to=160)
    assert len(served) == 40 and float(gap.max()) <= _TOL
    # 120 prompt tokens expanded; the first token comes of the
    # prefill, the other 39 of 10 dispatches of 4 steps, the step at
    # length L attending L cached positions and its own.
    assert moved['mla_expanded_tokens'] == 120
    assert moved['mla_absorbed_row_steps'] == 40
    assert moved['mla_absorbed_context'] == sum(range(121, 161))


def test_a_prefix_hit_gives_the_logits_of_a_fresh_prefill(model):
    """A document served once; a second request over the same
    document hits its whole blocks in the latent group, prefills its
    question alone, and its logits and tokens are those of an engine
    without the cache, to float32 rounding (the hit's chunks start
    elsewhere, so the tiles are cut otherwise). A question that
    diverges inside a block copies the block first (copy-on-write of
    the one latent array)."""
    config, params = model
    rng = np.random.default_rng(21)
    doc = rng.integers(0, 512, 104).tolist()        # 13 whole blocks
    ask = [rng.integers(0, 512, n).tolist() for n in (9, 14)]
    cached = _engine(params, config)
    plain = _engine(params, config, prefix_caching=False)
    try:
        seen, seen_plain = _watch(cached), _watch(plain)
        _serve(cached, doc + ask[0], 6)
        chunks = cached._metrics['prefill_chunks'].value
        hit = _serve(cached, doc + ask[1], 12)
        # 104 tokens hit: the question's 14 take one chunk.
        assert cached._metrics['prefill_chunks'].value - chunks == 1
        miss = _serve(plain, doc + ask[1], 12)
        np.testing.assert_allclose(seen[-1], seen_plain[-1],
                                   atol=_TOL, rtol=0)
        assert hit == miss
        # Diverging 4 tokens into the document's last block but one.
        cow = doc[:92] + ask[1]
        got = _serve(cached, cow, 8)
        want = _serve(plain, cow, 8)
        np.testing.assert_allclose(seen[-1], seen_plain[-1],
                                   atol=_TOL, rtol=0)
        assert got == want
    finally:
        cached.close()
        plain.close()


def test_a_preempted_row_resumes_with_the_logits_of_no_preemption(
        model):
    """A pool too small for three long rows: the engine preempts the
    youngest and requeues it, and it resumes by prefilling its prompt
    and what it had generated; every request still gets the tokens an
    engine with room gives."""
    config, params = model
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, 512, 60).tolist() for _ in range(3)]
    tight = _engine(params, config, num_blocks=28,
                    prefix_caching=False)
    roomy = _engine(params, config, prefix_caching=False)
    try:
        reqs = [tight.submit_request(p, 48) for p in prompts]
        got = [_collect(r) for r in reqs]
        preempted = tight._metrics['preemptions'].value
        want = [_serve(roomy, p, 48) for p in prompts]
    finally:
        tight.close()
        roomy.close()
    assert preempted > 0
    for p, g, w in zip(prompts, got, want):
        gap, _ = reference.served_token_gaps(
            params, _ref_cfg(config), p, g, pad_to=128)
        assert len(g) == 48 and float(gap.max()) <= _TOL
        assert g == w


# ---------------------------------------------------------------------
# (g) configuration
# ---------------------------------------------------------------------


def test_the_dense_bodies_and_the_verify_step_refuse(model):
    config, params = model
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(exceptions.NotSupportedError,
                       match='latent attention'):
        llama.forward(params, tokens, config)
    with pytest.raises(exceptions.NotSupportedError,
                       match='latent attention'):
        decode.greedy_generate(params, tokens, config, 2)
    # The verify step has a latent body since PR 43, and the n-gram
    # drafter an engine to ride; what is refused is a drafter the
    # model does not have.
    preds, accepted, *_ = decode.verify_step_paged(
        params, tokens[:, :1], _pool(config), _tables(1),
        jnp.asarray([0]), jnp.asarray([1]), config, 1, _BLOCK)
    assert preds.shape == (1, 1) and int(accepted[0]) == 0
    with pytest.raises(exceptions.NotSupportedError,
                       match='no next-token-prediction module'):
        _engine(params, config, speculative='mtp')


def test_config_counts_and_kinds():
    """The preset's published widths, its parameter count (ISSUE 38's
    arithmetic: 29.5 B, 4.4 B a token), YaRN's scale and frequencies,
    and that the other presets keep their kinds and defaults."""
    big = llama.get_config('xing4.0-29b-a4b')
    assert big.layer_kinds == ('latent',) and not big.plain_stack
    assert big.kind_entries('latent') == big.kv_entries == 40
    assert (big.head_dim, big.latent_width) == (192, 576)
    assert round(big.num_params() / 1e8) == 295
    assert round(big.num_active_params() / 1e8) == 44
    cut = llama.get_config('xing4.0-29b-a4b', n_layers=10)
    attn = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 +
            512 * 32 * 256 + 32 * 128 * 3584)
    assert round(attn / 1e4) == 2841                      # 28.41 M
    assert cut.num_params() == 10 * (
        attn + 768 + 512 + 2 * 3584 + 2 * (4 * 3584 * 24 + 24 + 3)
    ) + 2 * 3 * 3584 * 9216 + 8 * (
        65 * 3 * 3584 * 1024 + 3584 * 64 + 64
    ) + 2 * 131072 * 3584 + 3584
    np.testing.assert_allclose(
        llama.attention_scale(big),
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    freqs = np.asarray(llama._rope_frequencies(big, jnp.arange(2))[1])
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(freqs[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(freqs[-6:], plain[-6:] / 64, rtol=1e-6)
    assert np.all(np.diff(freqs) < 0)
    np.testing.assert_allclose(
        freqs, np.asarray(reference.yarn_frequencies(
            _ref_cfg(big))), rtol=1e-6)
    for name in ('mistral-7b', 'ouro-2.6b', 'command-a-plus', 'tiny'):
        other = llama.get_config(name)
        assert other.kv_lora_rank is None and other.hc_mult == 1
        assert 'latent' not in other.layer_kinds
        assert llama.attention_scale(other) == other.head_dim ** -0.5
    assert llama.get_config('mistral-7b').plain_stack
    with pytest.raises(ValueError, match='q_lora_rank'):
        llama.get_config('tiny', kv_lora_rank=16)
    with pytest.raises(ValueError, match='dense_first'):
        llama.get_config('tiny', dense_first=2)


def test_init_and_sharding_rules_cover_the_same_leaves():
    config = _config()
    params = llama.init_params(config, jax.random.PRNGKey(0))
    rules = llama.param_sharding_rules(config)
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, params)) == jax.tree.structure(
            jax.tree.map(lambda _: 0, rules,
                         is_leaf=lambda x: not isinstance(x, dict)))
    assert sum(x.size for x in jax.tree.leaves(params)) == \
        config.num_params()
    spec = rules['layers']
    assert spec['wkv_b'][2] == 'tp' and spec['wo'][1] == 'tp'
    assert all(a is None for a in spec['hc_attn_phi'])
    # The streams start as the mixers' own leaves say: the identity
    # favoured, both gates at sigmoid(0).
    lp = jax.tree.map(lambda w: w[0], params['layers'])
    xc = jnp.zeros((1, 1, 4, config.dim), jnp.float32)
    h_post, h_res = _mix(config, lp, xc)
    np.testing.assert_allclose(h_post, 1.0, atol=1e-6)
    assert np.all(np.diagonal(h_res[0]) > 0.8)


# ---------------------------------------------------------------------
# (h) the recipe
# ---------------------------------------------------------------------


class TestRecipe:

    def test_the_recipe_serves_the_preset(self, monkeypatch):
        """``recipes/serve_model --model tiny-latent-moe --slots 2
        --speculative off``: the engine it builds has one latent
        block group, and what it answers over HTTP is what the
        reference computes from the recipe's own weights
        (``init_params`` under ``PRNGKey(0)``: the mixers at their
        start, the selection bias 0)."""
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
            'serve_model', '--model', 'tiny-latent-moe', '--port',
            str(port), '--slots', '2', '--max-seq', '256',
            '--block-size', str(_BLOCK), '--num-blocks', '70',
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
        assert list(engine.pool.groups) == ['latent']
        assert engine.pool.num_blocks == 70 and not engine.speculative
        config = _config()
        gap, _ = reference.served_token_gaps(
            llama.init_params(config, jax.random.PRNGKey(0)),
            _ref_cfg(config), prompt, out['output_ids'], pad_to=128)
        assert len(out['output_ids']) == 12
        assert float(gap.max()) <= _TOL

    def test_the_recipe_names_the_preset_and_refuses_speculation(
            self, monkeypatch, capsys):
        """``--help`` names the preset among the stacks only the
        engine runs; ``--speculative mtp`` with it is refused at
        start-up, before any weight is made, because it has no
        module to draft with (``--speculative on`` is served since
        PR 43: the verify step has a latent body); without
        ``--slots`` it is refused as the other such stacks are."""
        import sys

        from skypilot_tpu.recipes import serve_model

        monkeypatch.setattr(sys, 'argv', ['serve_model', '--help'])
        with pytest.raises(SystemExit):
            serve_model.main()
        assert 'xing4.0-29b-a4b' in capsys.readouterr().out
        for extra, said in ((['--slots', '2', '--speculative', 'mtp'],
                             'has no next-token-prediction module'),
                            ([], 'pass --slots N')):
            monkeypatch.setattr(sys, 'argv', [
                'serve_model', '--model', 'tiny-latent-moe'] + extra)
            with pytest.raises(SystemExit) as excinfo:
                serve_model.main()
            assert excinfo.value.code == 2
            assert said in ' '.join(capsys.readouterr().err.split())
