"""The engine whose drafter is the model's own next-token-prediction
module (``BatchingEngine(speculative='mtp')``, ``tiny-latent-mtp``):
spec-on output is spec-off output under batch company, preemption
and resume; budgets, EOS and cancellation under rounds; acceptance
well above chance; the n-gram drafter on a latent model; the recipe.
The functions beneath it are ``tests/test_latent_mtp.py``'s, whose
weights, reference view and tolerances these tests share."""
import jax
import numpy as np
import pytest

from perf.reference import joyai_mtp_block_f32 as reference
from skypilot_tpu.models import llama
from skypilot_tpu.serve.batching import BatchingEngine
from skypilot_tpu.serve.sampling import grammar as grammar_lib
from test_latent_mtp import (_BLOCK, _TOL,  # noqa: F401 (fixtures)
                             _highest, _no_persistent_cache, _ref_cfg,
                             model)


def _engine(params, config, **kwargs):
    # One set of shapes wherever a test can live with it: the
    # programs of one engine are the next one's, compiled once.
    build = dict(slots=4, max_seq=256, block_size=_BLOCK,
                 steps_per_dispatch=8, prefill_chunk=16,
                 speculative='mtp', num_blocks=100)
    build.update(kwargs)
    return BatchingEngine(params, config, **build)


def _collect(req):
    out = []
    while True:
        item = req.out.get(timeout=300)
        if item is None:
            return out
        if isinstance(item, BaseException):
            raise item
        out.append(int(item))


def _mix(n=6, seed=11):
    """Prompts that share a 24-token opening (three whole blocks),
    greedy and sampled rows side by side."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 512, 24).tolist()
    lengths = (3, 41, 17, 66, 30, 9, 52, 25)[:n]
    temps = (0.0, 1.0, 1.0, 0.7, 1.0, 0.0, 1.0, 1.0)[:n]
    return [(shared + rng.integers(0, 512, k).tolist(), t, 900 + i)
            for i, (k, t) in enumerate(zip(lengths, temps))]


def _serve_all(engine, mix, max_new):
    reqs = [engine.submit_request(p, max_new, temperature=t, seed=s)
            for p, t, s in mix]
    return [_collect(r) for r in reqs]


@pytest.fixture(scope='module')
def plain_outputs(model):
    config, params = model
    engine = _engine(params, config, speculative=False)
    try:
        return _serve_all(engine, _mix(), 40)
    finally:
        engine.close()


def test_spec_on_equals_spec_off_under_batch_company(model,
                                                     plain_outputs):
    """Six requests over four rows, greedy and sampled, sharing a
    prefix: the module's engine emits what the engine without it
    emits, token for token; the served tokens are the reference's
    (a sampled one by the Gumbel noise of its key); drafts were
    kept, prefixes were hit, and nothing compiled after the
    constructor but the prefill buckets."""
    config, params = model
    engine = _engine(params, config)
    try:
        # (A jitted function's cache is its function's, whichever
        # engine made the wrapper: what is held is that serving
        # added no signature to what the constructor warmed.)
        warmed = (engine._rounds_fn._cache_size(),
                  engine._mtp_first_fn._cache_size(),
                  engine._first_fn._cache_size())
        got = _serve_all(engine, _mix(), 40)
        assert warmed == (engine._rounds_fn._cache_size(),
                          engine._mtp_first_fn._cache_size(),
                          engine._first_fn._cache_size())
        m = engine._metrics
        proposed = m['spec_proposed'].value
        accepted = m['spec_accepted'].value
        hits = m['prefix_hits'].value
        events = [e for e in engine.events if e[0] == 'rounds']
    finally:
        engine.close()
    assert got == plain_outputs
    assert events and proposed > 0 and 0 < accepted < proposed
    assert hits > 0
    cfg = _ref_cfg(config)
    for (prompt, temp, seed), out in zip(_mix(), got):
        gap, _ = reference.served_token_gaps(
            params, cfg, prompt, out, pad_to=256, temperature=temp,
            seed=seed)
        assert len(out) == 40 and float(gap.max()) <= _TOL


def test_preemption_and_resume_keep_the_tokens(model, plain_outputs):
    """A pool too small for four rows at their longest: rows are
    preempted and resumed (the resume re-prefills, so the module's
    rows come back with the main ones) and the tokens stay."""
    config, params = model
    engine = _engine(params, config, num_blocks=30)
    try:
        got = _serve_all(engine, _mix(), 40)
        preempted = engine._metrics['preemptions'].value
        free = engine.pool.free_blocks + \
            engine._metrics['prefix_cached_blocks'].value
    finally:
        engine.close()
    assert preempted > 0
    assert got == plain_outputs
    assert free >= 0


def test_cancel_eos_and_budget_under_rounds(model):
    """A row that ends on its budget mid-dispatch emits exactly its
    budget; an EOS inside a round's pair ends the row at it; a
    cancelled row frees its blocks; the token budget that leaves no
    room for drafts leaves plain decode."""
    config, params = model
    mix = _mix(3)
    engine = _engine(params, config)
    try:
        outs = _serve_all(engine, mix, 11)
        assert [len(o) for o in outs] == [11, 11, 11]
        eos = outs[1][6]
        again = _collect(engine.submit_request(
            mix[1][0], 11, eos_id=eos, temperature=mix[1][1],
            seed=mix[1][2]))
        assert again == outs[1][:outs[1].index(eos) + 1]
        req = engine.submit_request(mix[2][0], 200, temperature=1.0,
                                    seed=5)
        assert req.out.get(timeout=300) is not None
        engine.cancel(req)
        while req.out.get(timeout=300) is not None:
            pass
        with pytest.raises(grammar_lib.GrammarError,
                           match='grammar mask is not implemented'):
            _collect(engine.submit_request(
                mix[0][0], 4, eos_id=1,
                response_format={'type': 'regex', 'pattern': 'a'}))
    finally:
        engine.close()
    tight = _engine(params, config, max_num_batched_tokens=2)
    try:
        got = _serve_all(tight, mix, 11)
        events = [e for e in tight.events if e[0] == 'rounds']
    finally:
        tight.close()
    assert got == outs
    # While prompts are prefilled the budget of 2 is spent before
    # the rounds are reached: no draft is granted, and the rounds
    # are plain decode.
    assert events and events[0][2] == 0


def test_sampled_acceptance_lies_well_above_chance(model):
    """Some three thousand sampled tokens at temperature 1 over 512
    ids: two independent argmaxes would agree once in 512 draws; the
    module's drafts, drawn with the target's own key, are kept a
    third of the time or more."""
    config, params = model
    engine = _engine(params, config)
    try:
        rng = np.random.default_rng(17)
        reqs = [engine.submit_request(
            rng.integers(0, 512, 12).tolist(), 200, temperature=1.0,
            seed=int(rng.integers(1 << 30))) for _ in range(16)]
        total = sum(len(_collect(r)) for r in reqs)
        m = engine._metrics
        rate = m['spec_accepted'].value / m['spec_proposed'].value
        per_round = m['mtp_tokens'].value / m['mtp_rounds'].value
    finally:
        engine.close()
    assert total == 3200
    assert 0.2 < rate < 0.8, rate
    assert per_round == pytest.approx(1 + rate, abs=0.1)


def test_ngram_speculation_runs_on_a_latent_model(model):
    """The n-gram drafter on the latent verify body: a stream that
    repeats itself is drafted, verified and emitted as plain decode
    emits it."""
    config, _ = model
    import dataclasses
    small = dataclasses.replace(config, vocab_size=16, nextn_layers=0)
    params = llama.init_params(small, jax.random.PRNGKey(0))
    prompt = [3, 5, 3, 5, 3, 5, 3, 5, 3, 5, 3, 5]
    outs = []
    for spec in (False, True):
        engine = _engine(params, small, speculative=spec,
                         sampling=False)
        try:
            outs.append(_collect(engine.submit_request(prompt, 48)))
            verifies = [e for e in engine.events if e[0] == 'verify']
        finally:
            engine.close()
    assert outs[0] == outs[1] and len(outs[0]) == 48
    assert verifies and sum(e[3] for e in verifies) > 0


# ---------------------------------------------------------------------
# The recipe
# ---------------------------------------------------------------------


def test_the_recipe_reaches_the_drafter(monkeypatch, capsys):
    """``recipes/serve_model --model tiny-latent-mtp --slots 2
    --speculative mtp`` builds an engine whose drafter is the module
    and answers a sampled request over HTTP with what an engine
    without it answers; ``--help`` names the preset; ``--speculative
    on`` (the n-gram drafter) is no longer refused for a latent
    model."""
    import http.client
    import json
    import socket
    import sys
    import threading
    import time

    from skypilot_tpu.recipes import serve_model
    from skypilot_tpu.serve import batching

    monkeypatch.setattr(sys, 'argv', ['serve_model', '--help'])
    with pytest.raises(SystemExit):
        serve_model.main()
    assert 'joyai-llm-flash' in capsys.readouterr().out

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
        'serve_model', '--model', 'tiny-latent-mtp', '--port',
        str(port), '--slots', '2', '--max-seq', '128',
        '--block-size', str(_BLOCK), '--num-blocks', '40',
        '--speculative', 'mtp'])
    # main() never returns: the daemon thread dies with the test
    # process, as tests/test_latent_moe.py::TestRecipe's does.
    threading.Thread(target=serve_model.main, daemon=True).start()
    prompt = np.random.default_rng(21).integers(0, 512, 30).tolist()
    body = json.dumps({'prompt_ids': prompt, 'max_new_tokens': 24,
                       'temperature': 1.0, 'seed': 77})
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
    assert engine._mtp and not engine.speculative
    assert engine._metrics['mtp_rounds'].value > 0
    config = llama.get_config('tiny-latent-mtp')
    plain = BatchingEngine(
        llama.init_params(config, jax.random.PRNGKey(0)), config,
        slots=2, max_seq=128, block_size=_BLOCK, num_blocks=40,
        speculative=False)
    try:
        want = _collect(plain.submit_request(
            prompt, 24, temperature=1.0, seed=77))
    finally:
        plain.close()
    assert out['output_ids'] == want
