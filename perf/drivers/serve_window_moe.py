"""Driver of a served configuration with window and global layers and
a chip's share of an expert layer (``cohere2_moe``): ``serve_engine``
in every respect - the same engine build, warm-up, load, window and
comparison - but for what is built and for the cache it starts from:

- the weights carry the architecture's leaves at this chip's share
  (``perf/lib/weights_cohere2_moe.py``);
- the program's model is its published preset cut to the share the
  file states (layers, experts held, vocabulary rows), and has to hold
  the experts, the window, the layer kinds and the routing the file
  states, or the run ends before it touches a device;
- the mix's shared documents are each served once in set-up, before
  the lead-in (a request of the document and one more token, for one
  output token, through ``submit_request``): a deployment with a few
  hot documents has them cached, and the engine publishes a prompt's
  blocks only as its prefill completes, so the rows admitted together
  at the start of a backlog would otherwise each prefill their whole
  document. Counted in ``setup_s``."""
import functools
from typing import Any, Dict

from perf.drivers import serve_engine as base
from perf.lib import harness
from perf.lib import loadgen
from perf.lib import weights_cohere2_moe


def program_config(config: Dict[str, Any]):
    """The program's model at the file's share, checked against the
    file's keys."""
    from skypilot_tpu.models import llama
    name, model = config['program_model'], config['model']
    held = (model.get('experts_first', 0), model['num_experts'])
    try:
        prog = llama.get_config(
            name, n_layers=model['num_hidden_layers'],
            vocab_size=model['vocab_size'], experts_held=held)
    except (KeyError, TypeError):
        raise harness.HarnessError(
            f'the program has no model {name!r} that holds a share '
            f'of its experts: this tree cannot run the '
            f'configuration') from None
    kinds = ['sliding_attention' if k == 'window' else 'full_attention'
             for k in prog.layer_kinds] * (
                 prog.n_layers // len(prog.layer_kinds))
    got = {
        'hidden_size': prog.dim, 'intermediate_size': prog.ffn_hidden,
        'num_hidden_layers': prog.n_layers,
        'num_attention_heads': prog.n_heads,
        'num_key_value_heads': prog.n_kv_heads,
        'head_dim': prog.head_dim, 'vocab_size': prog.vocab_size,
        'rope_theta': prog.rope_theta,
        'layer_norm_eps': prog.norm_eps,
        'sliding_window': prog.sliding_window,
        'num_experts': prog.n_experts_held,
        'num_experts_per_tok': prog.moe_top_k,
        'num_shared_experts': prog.n_shared_experts,
        'expert_selection_fn': prog.moe_score,
        'use_parallel_block': prog.parallel_block,
        'tie_word_embeddings': prog.tie_embeddings,
        'layer_types': kinds}
    want = dict(model, layer_types=model['layer_types'][:prog.n_layers])
    wrong = {k: (want[k], v) for k, v in got.items() if want[k] != v}
    if prog.n_experts != model['published']['num_experts']:
        wrong['published.num_experts'] = (
            model['published']['num_experts'], prog.n_experts)
    if wrong or not (prog.layer_norm and prog.rope_interleaved
                     and not prog.global_rope):
        raise harness.HarnessError(
            f'the program\'s {name!r} differs from the configuration '
            f'file (file, program): {wrong}; LayerNorm '
            f'{prog.layer_norm}, interleaved RoPE '
            f'{prog.rope_interleaved}, positions on global layers '
            f'{prog.global_rope}')
    return prog


def prime_documents(engine, traffic: Dict[str, Any], seed: int,
                    vocab: int) -> int:
    """Serve each shared document of the mix once (the document and
    one token more, one output token), so that its blocks are in the
    prefix cache of both block groups when the lead-in starts."""
    requests = loadgen.generator_for(traffic['kind'])(
        traffic, seed, 0.0, vocab)
    shared_len = int(traffic['shared_len'])
    documents = {}
    for r in requests:
        documents.setdefault(r['shared'], r['prompt'][:shared_len])
    for _, doc in sorted(documents.items()):
        out = base._collect(engine.submit_request(doc + [doc[0]], 1))
        if len(out) != 1:
            raise harness.HarnessError(
                f'priming a document of {len(doc)} tokens returned '
                f'{len(out)} tokens')
    return len(documents)


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
        self.params, _ = weights_cohere2_moe.make_weights(
            self.model, seed, int8=config['weights'] == 'int8',
            dtype=self.prog.dtype)
        self.engine = BatchingEngine(self.params, self.prog,
                                     **self.build)
        if self.engine.wpool is None or \
                self.engine.wpool.kind != 'window':
            raise harness.HarnessError(
                'the engine built no block group for the window '
                'layers')
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
