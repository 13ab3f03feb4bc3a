"""Driver of a served configuration with latent attention whose own
next-token-prediction module drafts on the device
(``joyai_llm_flash``), under SAMPLED traffic: ``serve_engine``'s
engine build, load, window and result, but for

- what is built: the architecture's leaves, the module's among them
  (``perf/lib/weights_joyai.py``), on the program's published preset
  cut to the depth the file states, which has to hold every published
  key the file states (the MLA widths, the dense layer and its width,
  the experts and their routing, NO rope_scaling, the module) or the
  run ends before it touches a device;
- how a request is submitted: with the mix's ``sampling`` arguments
  (temperature, top_p) and a sampling seed of its own, a function of
  ``--seed`` and the request's index in the mix's order
  (``request_seed``);
- the warm-up: sampled requests, so that the sampled first-token
  program and every prefill bucket compile before the window (the
  engine prewarms its rounds and its first draft itself);
- ``correct``: a sampled token is held to the reference by the
  Gumbel noise of its own key
  (``joyai_mtp_block_f32.served_token_gaps``), so each request
  carries its temperature and sampling seed for the reference
  (``spec['reference']``); which requests and how many of their
  tokens are compared is ``serve_engine.check_served``'s rule, the
  same for every serving cell since PR 45 (it was this driver's own
  before).

``build.speculative`` is the drafter: ``"mtp"`` in the file; the
environment's ``PERF_MTP_DRAFTER=off`` builds the same engine with
speculation off (the builder's comparison run, PERF.md section 6, PR
43; not a file of the benchmark)."""
import functools
import os
from typing import Any, Dict, List, Optional

import numpy as np

from perf.drivers import serve_engine as base
from perf.lib import harness
from perf.lib import weights_joyai


def program_config(config: Dict[str, Any]):
    """The program's model at the file's depth, checked against the
    file's keys."""
    from skypilot_tpu.models import llama
    name, model = config['program_model'], config['model']
    try:
        prog = llama.get_config(
            name, n_layers=model['num_hidden_layers'])
        got = {
            'hidden_size': prog.dim,
            'intermediate_size': prog.dense_ffn_hidden,
            'moe_intermediate_size': prog.ffn_hidden,
            'num_hidden_layers': prog.n_layers,
            'first_k_dense_replace': prog.dense_first,
            'num_attention_heads': prog.n_heads,
            'num_key_value_heads': prog.n_kv_heads,
            'vocab_size': prog.vocab_size,
            'q_lora_rank': prog.q_lora_rank,
            'kv_lora_rank': prog.kv_lora_rank,
            'qk_nope_head_dim': prog.qk_nope_head_dim,
            'qk_rope_head_dim': prog.qk_rope_head_dim,
            'v_head_dim': prog.v_head_dim,
            'n_routed_experts': prog.n_experts_held,
            'n_shared_experts': prog.n_shared_experts,
            'num_experts_per_tok': prog.moe_top_k,
            'routed_scaling_factor': prog.moe_routed_scale,
            'scoring_func': prog.moe_score,
            'topk_method': 'noaux_tc' if prog.moe_select_bias
            else 'greedy',
            'rms_norm_eps': prog.norm_eps,
            'rope_theta': prog.rope_theta,
            'rope_scaling': None if prog.rope_yarn is None
            else 'yarn',
            'num_nextn_predict_layers': prog.nextn_layers,
            'tie_word_embeddings': prog.tie_embeddings}
    except (KeyError, TypeError, AttributeError):
        raise harness.HarnessError(
            f'the program has no model {name!r} with latent '
            f'attention and a next-token-prediction module: this '
            f'tree cannot run the configuration') from None
    wrong = {k: (model[k], v) for k, v in got.items() if model[k] != v}
    if wrong or prog.layer_kinds != ('latent',) or \
            not prog.rope_interleaved or prog.hc_mult != 1:
        raise harness.HarnessError(
            f'the program\'s {name!r} differs from the configuration '
            f'file (file, program): {wrong}; layer kinds '
            f'{prog.layer_kinds}, interleaved RoPE '
            f'{prog.rope_interleaved}, streams {prog.hc_mult}')
    return prog


def request_seed(seed: int, index: int) -> int:
    """The sampling seed of the mix's ``index``-th request under
    ``--seed``: 31 bits of a generator keyed by both."""
    return int(np.random.default_rng(
        [int(seed), 0x736d, int(index)]).integers(1 << 31))


class _SampledPrompt(list):
    """A request's prompt with its sampling arguments beside it:
    ``serve_engine.drive`` hands the engine ``spec['prompt']`` and
    ``spec['max_new']`` and nothing else."""
    sampling: Optional[Dict[str, Any]] = None


class _SampledEngine:
    """The engine as ``serve_engine.drive`` and ``_warm_up`` call it:
    ``submit_request(prompt, max_new)`` passes the prompt's own
    sampling arguments on (the warm-up's plain lists go as sampled
    rows of temperature 1); everything else is the engine's."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit_request(self, prompt, max_new):
        sampling = getattr(prompt, 'sampling', None) or {
            'temperature': 1.0, 'top_p': 1.0, 'seed': len(prompt)}
        return self._engine.submit_request(list(prompt), max_new,
                                           **sampling)


def sampled(requests: List[Dict[str, Any]], traffic: Dict[str, Any],
            seed: int) -> List[Dict[str, Any]]:
    """The mix's requests with its ``sampling`` arguments and each
    one's own seed attached to the prompt, its index in the mix's
    order, and what the reference needs to follow a sampled row."""
    knobs = traffic['sampling']
    for index, spec in enumerate(requests):
        prompt = _SampledPrompt(spec['prompt'])
        prompt.sampling = {
            'temperature': float(knobs['temperature']),
            'top_p': float(knobs['top_p']),
            'seed': request_seed(seed, index)}
        spec['prompt'], spec['index'] = prompt, index
        spec['reference'] = {
            'temperature': prompt.sampling['temperature'],
            'seed': prompt.sampling['seed']}
    return requests


class Served(base.Served):
    """``serve_engine.Served`` on this architecture's weights, its
    engine taking sampled requests."""

    def __init__(self, loaded: Dict[str, Any], seed: int,
                 rehearse: bool):  # pylint: disable=super-init-not-called
        import jax
        from skypilot_tpu.serve.batching import BatchingEngine
        from skypilot_tpu.utils import jax_runtime

        config = loaded['config']
        self.prog = program_config(config)
        self.device = harness.require_devices(
            loaded['cell']['chips'], rehearse)
        jax_runtime.configure_compile_cache()
        self.model = config['model']
        self.build = dict(config['build'])
        if os.environ.get('PERF_MTP_DRAFTER') == 'off':
            harness.say('PERF_MTP_DRAFTER=off: the same engine with '
                        'speculation off')
            self.build['speculative'] = False
        self.traffic = loaded['traffic']
        self.params, _ = weights_joyai.make_weights(
            self.model, seed, int8=config['weights'] == 'int8',
            dtype=self.prog.dtype)
        engine = BatchingEngine(self.params, self.prog, **self.build)
        if engine.pool.kind != 'latent':
            raise harness.HarnessError(
                'the engine built no block group of latent rows')
        self.engine = _SampledEngine(engine)
        base._warm_up(self.engine, self.model['vocab_size'],
                      engine.prefill_chunk, seed)
        jax.block_until_ready(engine.caches)

    def close(self) -> None:
        self.engine.close()
        del self.engine._engine.caches


def _on_this_system(fn):
    """``serve_engine.run`` and ``control_readings`` build the system
    under test as ``serve_engine.Served`` and load it through
    ``drive``, by those names: run them with the names bound to this
    module's, the load sampled under the run's seed."""
    @functools.wraps(fn)
    def call(loaded, seed, *args, **kwargs):
        theirs = base.Served, base.drive

        def drive(served, requests, *a, **k):
            return theirs[1](served, sampled(
                requests, served.traffic, seed), *a, **k)

        base.Served, base.drive = Served, drive
        try:
            return fn(loaded, seed, *args, **kwargs)
        finally:
            base.Served, base.drive = theirs
    return call


run = _on_this_system(base.run)
control_readings = _on_this_system(base.control_readings)
