"""The sampling subsystem (ops/sampling/, serve/sampling/):
batch-invariant sampled decode, distribution-preserving speculative
sampling, and grammar-constrained structured decoding on the paged
engine.

The contract under test everywhere: a request's sampled tokens are a
pure function of its own ``(seed, position)`` — never of batch width,
slot index, speculation on/off, or a preempt/resume cycle. The
speculative half rides the maximal-coupling acceptance
(ops/sampling/accept.py): the verify step REALIZES the target
draw for every position with the key plain decode would have used,
so spec-on output is bitwise spec-off output and the emitted
distribution is exactly the target distribution (the chi-square
tests below pin that down numerically).
"""
import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.models import decode, llama
from skypilot_tpu.ops.sampling import (accept_tokens, gather_masks,
                                       row_key, row_keys,
                                       sample_first, sample_rows,
                                       verify_targets)
from skypilot_tpu.serve.batching import BatchingEngine
from skypilot_tpu.serve.sampling import (GrammarError,
                                         compile_grammar,
                                         grammar_hash)
from skypilot_tpu.serve.sampling.grammar import schema_to_regex


@pytest.fixture(scope='module')
def setup():
    config = llama.get_config('tiny')
    params = llama.init_params(config, jax.random.PRNGKey(0))
    return config, params


@pytest.fixture(scope='module')
def loopy_setup():
    """Vocab-restricted tiny config (the test_speculative fixture):
    low-temperature decode enters repetition loops quickly, which is
    the regime where n-gram drafting actually fires — needed to
    exercise the sampled verify path, not just its spec-off twin."""
    config = dataclasses.replace(llama.get_config('tiny'),
                                 vocab_size=16)
    params = llama.init_params(config, jax.random.PRNGKey(0))
    return config, params


def _reference(params, config, prompt_ids, max_new, max_seq=64):
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    out = decode.greedy_generate(params, prompt, config,
                                 max_new_tokens=max_new,
                                 max_seq=max_seq)
    return [int(t) for t in out[0]]


def _drain(q, timeout=120):
    toks = []
    while True:
        t = q.get(timeout=timeout)
        if t is None:
            return toks
        assert not isinstance(t, BaseException), t
        toks.append(t)


def _grammar_vocab_512():
    """Decoded strings for the tiny (512) vocab: JSON lexicon at ids
    1.., everything else never-legal, EOS at 40 (a None entry — EOS
    legality is decided by the DFA's accepting state, not by text)."""
    gv = [None] * 512
    syms = list('0123456789{}[],:."ab') + ['true', 'false', 'null']
    for i, s in enumerate(syms, start=1):
        gv[i] = s
    return gv


GV512_EOS = 40

# Vocab-16 grammar vocab for the loopy config: digits at 1..10, then
# '[' ']' ',' '-', EOS at 15.
GV16 = ([None] + [str(d) for d in range(10)]
        + ['[', ']', ',', '-', None])
GV16_EOS = 15


def _text(gv, toks, eos):
    return ''.join(gv[t] or '' for t in toks if t != eos)


def _chisq(counts, probs):
    n = counts.sum()
    exp = probs * n
    return float(((counts - exp) ** 2 / exp).sum())


# Upper 0.001 quantiles of chi-square (hardcoded — no scipy in the
# image). With keyed draws the statistic is DETERMINISTIC for a fixed
# seed, so these are stable pass/fail lines, not a 1-in-1000 flake.
CHI2_999 = {4: 18.467, 5: 20.515, 7: 24.322}


def _draws(logits_row, n, temp, top_p, seed, pos0=0):
    """n independent keyed draws from one logit row: positions
    pos0..pos0+n-1 under a single request seed — exactly the stream
    of draws one request would see decoding n tokens."""
    logits = jnp.tile(jnp.asarray(logits_row, jnp.float32)[None, :],
                      (n, 1))
    toks = sample_rows(
        logits,
        jnp.full((n,), temp, jnp.float32),
        jnp.full((n,), top_p, jnp.float32),
        jnp.full((n,), seed, jnp.int32),
        jnp.arange(pos0, pos0 + n, dtype=jnp.int32))
    return np.asarray(toks)


# ---------------------------------------------------------------------
# Counter-based PRNG
# ---------------------------------------------------------------------


class TestRowKeys:

    def test_pure_function_of_seed_and_position(self):
        a = row_key(jnp.int32(7), jnp.int32(3))
        b = row_key(jnp.int32(7), jnp.int32(3))
        assert (np.asarray(a) == np.asarray(b)).all()
        assert not (np.asarray(row_key(jnp.int32(8), jnp.int32(3)))
                    == np.asarray(a)).all()
        assert not (np.asarray(row_key(jnp.int32(7), jnp.int32(4)))
                    == np.asarray(a)).all()

    def test_vectorized_matches_scalar(self):
        seeds = jnp.asarray([1, 1, 9], jnp.int32)
        poss = jnp.asarray([0, 5, 5], jnp.int32)
        batch = np.asarray(row_keys(seeds, poss))
        for i in range(3):
            one = np.asarray(row_key(seeds[i], poss[i]))
            assert (batch[i] == one).all()


# ---------------------------------------------------------------------
# Per-row sampling units
# ---------------------------------------------------------------------


class TestSampleRows:

    def test_temperature_zero_is_argmax(self):
        logits = jnp.asarray([[0.1, 3.0, -1.0, 0.5],
                              [2.0, 0.0, 9.0, 1.0]], jnp.float32)
        toks = sample_rows(logits,
                           jnp.zeros(2, jnp.float32),
                           jnp.ones(2, jnp.float32),
                           jnp.asarray([123, 456], jnp.int32),
                           jnp.asarray([0, 17], jnp.int32))
        assert list(np.asarray(toks)) == [1, 2]

    def test_row_is_invariant_to_batch_composition(self):
        """The load-bearing property: a row's draw depends only on
        its own (logits, knobs, seed, position) — sample it alone,
        then next to arbitrary neighbors, bitwise identical."""
        rng = np.random.default_rng(0)
        mine = jnp.asarray(rng.normal(size=8), jnp.float32)
        solo = sample_rows(mine[None, :],
                           jnp.asarray([0.9], jnp.float32),
                           jnp.asarray([0.95], jnp.float32),
                           jnp.asarray([42], jnp.int32),
                           jnp.asarray([13], jnp.int32))
        for width in (4, 16):
            others = rng.normal(size=(width - 1, 8))
            logits = jnp.concatenate(
                [mine[None, :],
                 jnp.asarray(others, jnp.float32)], axis=0)
            batch = sample_rows(
                logits,
                jnp.concatenate([jnp.asarray([0.9]),
                                 jnp.full((width - 1,), 1.3)]
                                ).astype(jnp.float32),
                jnp.concatenate([jnp.asarray([0.95]),
                                 jnp.full((width - 1,), 0.7)]
                                ).astype(jnp.float32),
                jnp.arange(42, 42 + width, dtype=jnp.int32),
                jnp.full((width,), 13, jnp.int32))
            assert int(batch[0]) == int(solo[0]), width

    def test_top_p_restricts_support(self):
        probs = np.asarray([0.55, 0.25, 0.12, 0.05, 0.03])
        draws = _draws(np.log(probs), 200, temp=1.0, top_p=0.5,
                       seed=3)
        # Nucleus at 0.5 is the single top token (0.55 covers it).
        assert set(draws) == {0}
        draws = _draws(np.log(probs), 400, temp=1.0, top_p=0.7,
                       seed=3)
        assert set(draws) <= {0, 1}
        assert 1 in set(draws)

    def test_sample_first_matches_decode_keying(self):
        """The prompt/decode boundary is invisible: the first token
        drawn from prefill logits equals the draw plain decode would
        make at the same absolute position."""
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=8), jnp.float32)
        first = sample_first(logits[None, :], jnp.float32(0.8),
                             jnp.float32(0.9), jnp.int32(5),
                             jnp.int32(31))
        again = _draws(np.asarray(logits), 1, temp=0.8, top_p=0.9,
                       seed=5, pos0=31)
        assert int(first) == int(again[0])

    @pytest.mark.parametrize('temp', [1.0, 0.7])
    def test_chi_square_matches_target_distribution(self, temp):
        """GOF of the keyed sampler against softmax(logits/T): the
        empirical counts over 4000 (seed, position) draws sit inside
        the 0.999 chi-square quantile."""
        logits = np.log(np.asarray([0.4, 0.25, 0.18, 0.1, 0.07]))
        draws = _draws(logits, 4000, temp=temp, top_p=1.0, seed=17)
        counts = np.bincount(draws, minlength=5).astype(float)
        probs = np.exp(logits / temp)
        probs /= probs.sum()
        stat = _chisq(counts, probs)
        assert stat < CHI2_999[4], (stat, counts)


class TestGatherMasks:

    def test_gathers_rows_by_traced_index(self):
        table = jnp.asarray([[1, 1, 1, 1],
                             [1, 0, 0, 1],
                             [0, 1, 0, 0]], bool)
        out = np.asarray(gather_masks(
            table, jnp.asarray([2, 0, 1], jnp.int32)))
        assert (out == np.asarray([[0, 1, 0, 0],
                                   [1, 1, 1, 1],
                                   [1, 0, 0, 1]], bool)).all()

    def test_masked_sampling_stays_in_support(self):
        logits = jnp.zeros((64, 6), jnp.float32)
        allowed = jnp.asarray([[False, True, False, True, False,
                                False]] * 64, bool)
        toks = np.asarray(sample_rows(
            logits,
            jnp.ones(64, jnp.float32),
            jnp.ones(64, jnp.float32),
            jnp.full((64,), 9, jnp.int32),
            jnp.arange(64, dtype=jnp.int32),
            allowed=allowed))
        assert set(toks) <= {1, 3}


# ---------------------------------------------------------------------
# Speculative sampling: the maximal-coupling verify path
# ---------------------------------------------------------------------


class TestVerifyTargets:

    def test_realizations_equal_plain_decode_draws(self):
        """The coupling identity itself: verify column j draws with
        the key plain decode uses at position pos+j, so realized
        tokens are BITWISE the plain sampled-decode stream — which
        is why spec-on output equals spec-off output."""
        rng = np.random.default_rng(2)
        w, v = 6, 8
        logits = rng.normal(size=(w, v))
        real = np.asarray(verify_targets(
            jnp.asarray(logits, jnp.float32)[None],
            jnp.asarray([0.8], jnp.float32),
            jnp.asarray([0.9], jnp.float32),
            jnp.asarray([21], jnp.int32),
            jnp.asarray([10], jnp.int32)))[0]
        for j in range(w):
            plain = _draws(logits[j], 1, temp=0.8, top_p=0.9,
                           seed=21, pos0=10 + j)
            assert int(real[j]) == int(plain[0]), j

    def test_chi_square_of_emitted_distribution(self):
        """The emitted token of speculative sampling at a position
        is ALWAYS the realization x* (accepted or not — rejection
        just truncates the run), so the verify realizations ARE the
        output distribution. GOF against the target softmax."""
        logits = np.log(np.asarray([0.35, 0.3, 0.2, 0.1, 0.05]))
        real = np.asarray(verify_targets(
            jnp.tile(jnp.asarray(logits, jnp.float32)[None, None, :],
                     (1, 2000, 1)),
            jnp.asarray([1.0], jnp.float32),
            jnp.asarray([1.0], jnp.float32),
            jnp.asarray([29], jnp.int32),
            jnp.asarray([0], jnp.int32)))[0]
        counts = np.bincount(real, minlength=5).astype(float)
        probs = np.exp(logits)
        probs /= probs.sum()
        stat = _chisq(counts, probs)
        assert stat < CHI2_999[4], (stat, counts)

    def test_acceptance_frequency_tracks_draft_probability(self):
        """With a deterministic drafter (q = point mass at d), the
        Chen et al. rule accepts iff x* == d, so the acceptance rate
        at a position is exactly p(d). Empirically: ~0.5 for a draft
        with p = 0.5."""
        probs = np.asarray([0.5, 0.2, 0.15, 0.1, 0.05])
        draws = _draws(np.log(probs), 4000, temp=1.0, top_p=1.0,
                       seed=37)
        rate = float((draws == 0).mean())
        assert abs(rate - 0.5) < 0.05, rate

    def test_accept_tokens_is_the_leading_realization_run(self):
        toks = jnp.asarray([[9, 5, 6, 7]], jnp.int32)   # drafted
        preds = jnp.asarray([[5, 6, 2, 4]], jnp.int32)  # realized
        n = jnp.asarray([4], jnp.int32)
        # Drafts at cols 1..3 are compared against realizations at
        # cols 0..2: two matches then a miss -> accept 2 drafted +
        # the realized correction is emitted by the engine.
        assert int(accept_tokens(toks, preds, n)[0]) == 2


# ---------------------------------------------------------------------
# Grammar units
# ---------------------------------------------------------------------


class TestGrammarUnit:

    def _compile(self, pattern, vocab, eos):
        return compile_grammar({'type': 'regex', 'pattern': pattern},
                               vocab, eos)

    def test_regex_walk_and_eos_gating(self):
        vocab = [None, 'a', 'b', None]   # eos = 3
        g = self._compile('a+b', vocab, 3)
        s = g.start
        mask = g.allowed(s)
        assert list(mask) == [False, True, False, False]
        s = g.advance(s, 1)              # 'a'
        mask = g.allowed(s)
        assert mask[1] and mask[2] and not mask[3]
        s = g.advance(s, 2)              # 'b' -> complete
        assert g.is_accepting(s)
        mask = g.allowed(s)
        assert mask[3] and not mask[1] and not mask[2]
        assert g.advance(s, 1) is None   # 'a' after match: dead

    def test_multichar_tokens_walk_whole_text(self):
        vocab = [None, 'true', 'false', 'tr', None]  # eos = 4
        g = self._compile('true|false', vocab, 4)
        mask = g.allowed(g.start)
        assert mask[1] and mask[2] and mask[3]
        assert not mask[4]
        done = g.advance(g.start, 1)
        assert g.is_accepting(done)
        partial = g.advance(g.start, 3)  # 'tr' — viable, not done
        assert partial is not None and not g.is_accepting(partial)

    def test_schema_to_regex_forms(self):
        assert schema_to_regex({'type': 'boolean'}) == '(true|false)'
        assert schema_to_regex({'const': 'hi'}) == '"hi"'
        arr = schema_to_regex({'type': 'array',
                               'items': {'type': 'boolean'},
                               'minItems': 1, 'maxItems': 2})
        assert arr == r'\[((true|false)(,(true|false)){0,1})\]'
        with pytest.raises(GrammarError):
            schema_to_regex({'type': 'array', 'minItems': -1,
                             'items': {'type': 'integer'}})
        with pytest.raises(GrammarError):
            schema_to_regex('not-an-object')

    def test_hash_is_key_order_insensitive(self):
        a = {'type': 'json_schema', 'schema': {'type': 'integer'}}
        b = {'schema': {'type': 'integer'}, 'type': 'json_schema'}
        assert grammar_hash(a) == grammar_hash(b)
        assert grammar_hash(a) != grammar_hash(
            {'type': 'regex', 'pattern': 'x'})

    def test_compile_cache_returns_same_object(self):
        vocab = [None, 'a', None]
        g1 = self._compile('a+', vocab, 2)
        g2 = self._compile('a+', vocab, 2)
        assert g1 is g2

    def test_typed_errors(self):
        vocab = [None, 'a', None]
        with pytest.raises(GrammarError):
            compile_grammar({'type': 'xml'}, vocab, 2)
        with pytest.raises(GrammarError):
            compile_grammar({'type': 'regex', 'pattern': ''},
                            vocab, 2)
        with pytest.raises(GrammarError):
            compile_grammar({'type': 'json_schema',
                             'schema': 'nope'}, vocab, 2)
        with pytest.raises(GrammarError):
            compile_grammar('nope', vocab, 2)


# ---------------------------------------------------------------------
# Engine end-to-end: the batch-invariance acceptance tests
# ---------------------------------------------------------------------


class TestEngineBatchInvariance:

    CASES = [
        # (prompt, max_new, temperature, top_p, seed)
        ([3, 1, 4, 1, 5, 9], 14, 0.8, 0.9, 11),
        ([2, 7, 1, 8, 2, 8], 14, 0.7, 0.8, 22),
        ([1, 6, 1, 8, 9, 3], 14, 1.0, 1.0, 33),
        ([3, 1, 4, 1, 5, 9], 14, 0.0, 1.0, 0),  # greedy rider
    ]

    def _run(self, params, config, slots, speculative):
        engine = BatchingEngine(params, config, slots=slots,
                                max_seq=64, speculative=speculative,
                                draft_k=4)
        try:
            queues = [engine.submit(p, m, temperature=t, top_p=tp,
                                    seed=s)
                      for p, m, t, tp, s in self.CASES]
            return [_drain(q) for q in queues]
        finally:
            engine.close()

    def test_bitwise_across_batch_width_and_speculation(
            self, setup):
        """THE acceptance criterion: fixed seeds, batch widths 1, 4
        and 16, speculation on and off — six engines, bitwise
        identical token streams per request. The greedy rider also
        matches single-stream greedy_generate (a sampled neighbor
        and a sampling-capable executable change nothing for a
        temperature-0 row)."""
        config, params = setup
        baseline = self._run(params, config, 1, False)
        for slots in (1, 4, 16):
            for spec in (False, True):
                if (slots, spec) == (1, False):
                    continue
                outs = self._run(params, config, slots, spec)
                assert outs == baseline, (slots, spec)
        prompt, max_new = self.CASES[3][0], self.CASES[3][1]
        assert baseline[3] == _reference(params, config, prompt,
                                         max_new)

    def test_sampled_rows_differ_across_seeds(self, setup):
        """Sanity that the invariance above is not vacuous: the two
        requests sharing a prompt but not a seed diverge, and a
        sampled stream differs from the greedy one."""
        config, params = setup
        outs = self._run(params, config, 4, False)
        assert outs[0] != outs[3]   # same prompt, sampled vs greedy
        assert outs[0] != outs[1]


class TestEngineSpecSampled:

    def test_spec_on_equals_spec_off_with_live_verifies(
            self, loopy_setup):
        """Sampled speculation actually FIRES (loopy vocab, low
        temperature -> draftable repetition) and the outputs stay
        bitwise equal to the spec-off engine — the
        distribution-preserving coupling, observed end-to-end. A
        greedy row decodes alongside and still matches
        single-stream greedy."""
        config, params = loopy_setup
        cases = [([1, 2, 3, 4] * 3, 20, 0.3, 0.9, 5),
                 ([6, 7, 8, 6, 7, 8], 20, 0.3, 0.9, 6),
                 ([1, 2, 3, 1, 2, 3], 20, 0.0, 1.0, 0)]

        def run(spec):
            engine = BatchingEngine(params, config, slots=3,
                                    max_seq=64,
                                    steps_per_dispatch=4,
                                    speculative=spec, draft_k=8)
            try:
                qs = [engine.submit(p, m, temperature=t, top_p=tp,
                                    seed=s)
                      for p, m, t, tp, s in cases]
                outs = [_drain(q) for q in qs]
                return outs, list(engine.events)
            finally:
                engine.close()

        on, events = run(True)
        off, _ = run(False)
        assert on == off
        assert any(e[0] == 'verify' for e in events), events
        assert on[2] == _reference(params, config, cases[2][0],
                                   cases[2][1])


class TestEnginePreemptResume:

    def test_preempted_sampled_rows_resume_bitwise(
            self, loopy_setup):
        """Pool pressure preempts mid-decode; resume re-prefills
        prompt+generated and continues at the same absolute
        positions, so the counter keys — and the tokens — are the
        ones an unpressured engine derives. A grammar-constrained
        row rides along (its DFA state is recomputed from
        ``generated`` at re-admission)."""
        config, params = loopy_setup
        rf = {'type': 'regex',
              'pattern': r'\[[0-9](,[0-9]){0,3}\]'}
        sampled = [([1, 2, 3, 4] * 3, 12, 0.6, 0.9, 5),
                   ([6, 7, 8, 6, 7, 8], 12, 0.6, 0.9, 6),
                   ([2, 4, 2, 4, 2], 12, 0.6, 0.9, 7)]

        def run(num_blocks):
            engine = BatchingEngine(params, config, slots=3,
                                    max_seq=64,
                                    steps_per_dispatch=4,
                                    block_size=8,
                                    num_blocks=num_blocks,
                                    draft_k=8,
                                    grammar_vocab=GV16)
            try:
                qs = [engine.submit(p, m, temperature=t, top_p=tp,
                                    seed=s)
                      for p, m, t, tp, s in sampled]
                qs.append(engine.submit(
                    [1, 2, 3], 12, temperature=0.7, seed=9,
                    response_format=rf, eos_id=GV16_EOS))
                outs = [_drain(q) for q in qs]
                return outs, list(engine.events)
            finally:
                engine.close()

        tight, events = run(7)
        roomy, _ = run(64)
        assert any(e[0] == 'preempt' for e in events), events
        assert tight == roomy
        text = _text(GV16, tight[3], GV16_EOS)
        assert re.fullmatch(r'\[[0-9](,[0-9]){0,3}\]', text), text


class TestEngineGrammar:

    def test_constrained_sampled_decode_end_to_end(self, setup):
        """Structured decoding on the live engine (speculation on):
        a regex request emits a full match and a json_schema request
        emits canonical JSON that parses AND validates — while a
        free sampled row shares the batch. The sampled/constrained
        admission counters move."""
        config, params = setup
        gv = _grammar_vocab_512()
        engine = BatchingEngine(params, config, slots=3, max_seq=64,
                                grammar_vocab=gv)
        sampled_c = engine._metrics['sampled_requests'].value
        constr_c = engine._metrics['constrained_requests'].value
        try:
            q_regex = engine.submit(
                [1, 2, 3], 24, temperature=0.8, seed=3,
                response_format={'type': 'regex',
                                 'pattern': r'\{"a":[0-9]{1,4}\}'},
                eos_id=GV512_EOS)
            q_schema = engine.submit(
                [4, 5, 6], 24, temperature=0.9, seed=4,
                response_format={
                    'type': 'json_schema',
                    'schema': {'type': 'object',
                               'properties': {
                                   'a': {'type': 'boolean'}}}},
                eos_id=GV512_EOS)
            q_free = engine.submit([7, 8, 9], 12, temperature=0.9,
                                   seed=5)
            t_regex = _text(gv, _drain(q_regex), GV512_EOS)
            t_schema = _text(gv, _drain(q_schema), GV512_EOS)
            _drain(q_free)
        finally:
            engine.close()
        assert re.fullmatch(r'\{"a":[0-9]{1,4}\}', t_regex), t_regex
        parsed = json.loads(t_schema)
        assert isinstance(parsed, dict) and \
            isinstance(parsed.get('a'), bool), t_schema
        assert engine._metrics['sampled_requests'].value \
            >= sampled_c + 3
        assert engine._metrics['constrained_requests'].value \
            >= constr_c + 2

    def test_grammar_refusals_are_typed(self, setup):
        """A bad grammar fails THAT request with the GrammarError on
        its queue (the serve handler maps it to HTTP 400) — the
        engine stays up and the error names the problem, whether
        it is a missing eos_id or an unsupported grammar type."""
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                grammar_vocab=_grammar_vocab_512())
        try:
            no_eos = engine.submit_request(
                [1, 2], 4, temperature=0.5,
                response_format={'type': 'regex', 'pattern': 'a+'})
            item = no_eos.out.get(timeout=60)
            assert isinstance(item, GrammarError), item
            assert 'eos_id' in str(item)
            assert no_eos.out.get(timeout=60) is None
            req = engine.submit_request(
                [1, 2], 4, temperature=0.5,
                response_format={'type': 'xml'},
                eos_id=GV512_EOS)
            item = req.out.get(timeout=60)
            assert isinstance(item, GrammarError), item
            assert req.out.get(timeout=60) is None
        finally:
            engine.close()


class TestEngineValidation:

    def test_knob_errors_name_the_field(self, setup):
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=64)
        try:
            with pytest.raises(ValueError, match='temperature'):
                engine.submit([1, 2], 4, temperature=-0.5)
            with pytest.raises(ValueError, match='top_p'):
                engine.submit([1, 2], 4, top_p=0.0)
            with pytest.raises(ValueError, match='top_p'):
                engine.submit([1, 2], 4, top_p=1.5)
            with pytest.raises(ValueError, match='seed'):
                engine.submit([1, 2], 4, seed=True)
            with pytest.raises(ValueError, match='seed'):
                engine.submit([1, 2], 4, seed=1.5)
            # A vocab-less engine refuses structured decoding per
            # REQUEST (GrammarError on the queue -> HTTP 400), like
            # any other bad grammar.
            req = engine.submit_request(
                [1, 2], 4, temperature=0.5,
                response_format={'type': 'regex', 'pattern': 'a'},
                eos_id=1)
            item = req.out.get(timeout=60)
            assert isinstance(item, GrammarError), item
            assert 'grammar_vocab' in str(item)
            assert req.out.get(timeout=60) is None
        finally:
            engine.close()

    def test_huge_and_negative_seeds_never_kill_the_engine(
            self, setup):
        """Seeds key the PRNG as uint32, so ANY Python int is taken
        mod 2**32 at admission: an unseeded HTTP request draws 4
        random bytes (up to 2**32-1), and a hostile client can send
        anything — neither may OverflowError inside the scheduler
        thread (which kills the engine for every tenant). Congruent
        seeds mod 2**32 are the same key, hence the same stream."""
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=64)

        def sample(seed):
            return _drain(engine.submit([1, 2, 3], 8,
                                        temperature=0.8, top_p=0.9,
                                        seed=seed))
        try:
            assert len(sample(2746413216)) == 8   # > 2**31: uint32
            assert sample(-1) == sample(2**32 - 1)
            assert sample(2**32 + 7) == sample(7)
        finally:
            engine.close()

    def test_sampling_off_engine_refuses_sampled_work(self, setup):
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                sampling=False)
        try:
            with pytest.raises(ValueError):
                engine.submit([1, 2], 4, temperature=0.5)
            with pytest.raises(ValueError):
                engine.submit([1, 2], 4,
                              response_format={'type': 'regex',
                                               'pattern': 'a'},
                              eos_id=1)
        finally:
            engine.close()


# ---------------------------------------------------------------------
# LB routing stays sampling-blind
# ---------------------------------------------------------------------


class TestLBRoutingSamplingBlind:

    def test_prefix_key_ignores_sampling_fields(self):
        """KV reuse depends only on (adapter, prompt prefix):
        changing the seed, temperature or grammar must not move a
        warm-prefix request to a cold replica, so the routing key
        is identical across sampling-field variations."""
        from skypilot_tpu.serve import load_balancer as lb
        ids = list(range(1, 1 + lb.ROUTING_BLOCK_TOKENS * 2))
        base = lb.request_prefix_key(
            json.dumps({'prompt_ids': ids}).encode())
        assert base is not None
        for extra in (
                {'temperature': 0.9, 'top_p': 0.8, 'seed': 7},
                {'temperature': 0.2, 'seed': 12345,
                 'response_format': {'type': 'regex',
                                     'pattern': '[0-9]+'}},
        ):
            body = json.dumps({'prompt_ids': ids, **extra}).encode()
            assert lb.request_prefix_key(body) == base, extra
        other = lb.request_prefix_key(json.dumps(
            {'prompt_ids': [9] + ids[1:], 'seed': 7}).encode())
        assert other != base


# ---------------------------------------------------------------------
# Knob plumbing (YAML -> spec -> env, the TestSpecKnobs shape)
# ---------------------------------------------------------------------


class TestSamplingKnobs:

    def test_round_trip_and_env(self):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        spec = SkyServiceSpec.from_yaml_config({
            'engine': {'sampling': {
                'enabled': True,
                'grammar_vocab': '/models/vocab.json'}},
        })
        assert spec.engine_sampling is True
        assert spec.engine_sampling_grammar_vocab == \
            '/models/vocab.json'
        out = spec.to_yaml_config()
        assert out['engine'] == {'sampling': {
            'enabled': True,
            'grammar_vocab': '/models/vocab.json'}}
        again = SkyServiceSpec.from_yaml_config(out)
        env = again.engine_env()
        assert env['SKYTPU_ENGINE_SAMPLING'] == '1'
        assert env['SKYTPU_ENGINE_SAMPLING_GRAMMAR_VOCAB'] == \
            '/models/vocab.json'
        off = SkyServiceSpec.from_yaml_config(
            {'engine': {'sampling': {'enabled': False}}})
        assert off.engine_sampling is False
        assert off.engine_env()['SKYTPU_ENGINE_SAMPLING'] == '0'
        bare = SkyServiceSpec.from_yaml_config({})
        assert bare.engine_sampling is None
        assert bare.engine_sampling_grammar_vocab is None
        assert 'SKYTPU_ENGINE_SAMPLING' not in bare.engine_env()

    def test_validation(self):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        with pytest.raises(exceptions.InvalidSpecError):
            SkyServiceSpec(engine_sampling='on')
        with pytest.raises(exceptions.InvalidSpecError):
            SkyServiceSpec(engine_sampling_grammar_vocab='')
        with pytest.raises(exceptions.InvalidSpecError):
            SkyServiceSpec(engine_sampling=False,
                           engine_sampling_grammar_vocab='/v.json')

    def test_schema_fields(self):
        from skypilot_tpu.utils import schemas
        props = schemas.SERVICE_SCHEMA['properties']['engine'][
            'properties']
        assert props['sampling'] == {
            'type': 'object',
            'additionalProperties': False,
            'properties': {
                'enabled': {'type': 'boolean'},
                'grammar_vocab': {'type': 'string',
                                  'minLength': 1}}}
