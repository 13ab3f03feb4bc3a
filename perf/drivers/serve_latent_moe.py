"""Driver of a served configuration with latent attention, several
residual streams and dense layers before an expert layer held whole
(``xing4_0``): ``serve_engine`` in every respect - the same engine
build, warm-up, load, window and comparison - but for what is built
and for the cache it starts from:

- the weights carry the architecture's leaves
  (``perf/lib/weights_xing4.py``);
- the program's model is its published preset cut to the depth the
  file states, and has to hold every published key the file states
  (the MLA widths, the stream mixers' keys, the dense layers and
  their width, the experts and their routing, YaRN), or the run ends
  before it touches a device;
- the mix's shared documents are each served once in set-up, before
  the lead-in (``serve_window_moe.prime_documents``: a deployment
  with a few hot documents has them cached). Counted in ``setup_s``.
"""
import functools
from typing import Any, Dict

from perf.drivers import serve_engine as base
from perf.drivers.serve_window_moe import prime_documents
from perf.lib import harness
from perf.lib import weights_xing4


def program_config(config: Dict[str, Any]):
    """The program's model at the file's depth, checked against the
    file's keys."""
    from skypilot_tpu.models import llama
    name, model = config['program_model'], config['model']
    try:
        prog = llama.get_config(
            name, n_layers=model['num_hidden_layers'])
        yarn = dict(zip(
            ('factor', 'original_max_position_embeddings',
             'beta_fast', 'beta_slow', 'mscale_all_dim'),
            prog.rope_yarn))
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
            'hc_mult': prog.hc_mult,
            'hc_sinkhorn_iters': prog.hc_sinkhorn_iters,
            'hc_eps': prog.hc_eps,
            'mhc_h_res_clamp_min': prog.hc_clamp[0],
            'mhc_h_res_clamp_max': prog.hc_clamp[1],
            'tie_word_embeddings': prog.tie_embeddings}
    except (KeyError, TypeError, AttributeError):
        raise harness.HarnessError(
            f'the program has no model {name!r} with latent '
            f'attention and residual streams: this tree cannot run '
            f'the configuration') from None
    wrong = {k: (model[k], v) for k, v in got.items() if model[k] != v}
    wrong.update({'rope_scaling.' + k: (model['rope_scaling'][k], v)
                  for k, v in yarn.items()
                  if model['rope_scaling'][k] != v})
    if wrong or prog.layer_kinds != ('latent',) or \
            not prog.rope_interleaved:
        raise harness.HarnessError(
            f'the program\'s {name!r} differs from the configuration '
            f'file (file, program): {wrong}; layer kinds '
            f'{prog.layer_kinds}, interleaved RoPE '
            f'{prog.rope_interleaved}')
    return prog


class Served(base.Served):
    """``serve_engine.Served`` on this architecture's weights, with
    the documents' caches built."""

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
        self.build = config['build']
        self.traffic = loaded['traffic']
        self.params, _ = weights_xing4.make_weights(
            self.model, seed, int8=config['weights'] == 'int8',
            dtype=self.prog.dtype)
        self.engine = BatchingEngine(self.params, self.prog,
                                     **self.build)
        if self.engine.pool.kind != 'latent':
            raise harness.HarnessError(
                'the engine built no block group of latent rows')
        base._warm_up(self.engine, self.model['vocab_size'],
                      self.engine.prefill_chunk, seed)
        primed = prime_documents(self.engine, self.traffic, seed,
                                 self.model['vocab_size'])
        harness.say(f'primed {primed} shared documents of '
                    f'{self.traffic["shared_len"]} tokens')
        jax.block_until_ready(self.engine.caches)


def _on_this_system(fn):
    """``serve_engine.run`` and ``control_readings`` build the system
    under test as ``serve_engine.Served``, by that name: run them with
    the name bound to this module's."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        theirs, base.Served = base.Served, Served
        try:
            return fn(*args, **kwargs)
        finally:
            base.Served = theirs
    return call


run = _on_this_system(base.run)
control_readings = _on_this_system(base.control_readings)
