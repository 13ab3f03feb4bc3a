"""The run's weights for a ``cohere2_moe`` configuration (Command A+),
from ``--seed``, laid out as the program's loaders lay a checkpoint
out: one LayerNorm a layer (``attn_norm``; the parallel block has no
second), the attention projections, the router over ALL published
experts, the routed experts HELD HERE stacked ``[layers, held, in,
out]``, the shared experts side by side along the hidden axis
(``ws_*``), a tied embedding of the vocabulary rows held here. On the
device, in one jitted call, an expert at a time so that no float32
stack exists.

Values as ``perf/lib/weights.py``: matmul weights and the embedding
N(0, 1 / fan_in) (int8 codes with one bf16 scale an output channel, of
each expert its own), norm weights 1 + 0.1 N(0, 1) so that an ignored
norm shows, the router N(0, 1 / hidden) kept in the served float type
(it drives the top-k choice).

``wq`` alone is drawn ``SCORE_STD`` times wider, so that a query's
scores against its keys have that standard deviation and not 1. At 1
the softmax over 4,096 or 10,000 keys is a flat average, the same
vector whatever the query: the next greedy token is then a function
of the current token alone, a random map of the vocabulary onto
itself, whose streams end in short cycles and fixed points, and the
routers see a few dozen distinct tokens all run, how many by the
seed's luck (``PERF.md`` section 6, PR 35: ``out_tok_s`` 444-475 by
seed). At 2.5 some eight of a window's 4,096 keys carry the sum
(N exp(-sigma^2) of N keys), the sliding layers' RoPE moves which
ones with every position, and attention weighs in the stream what the
shared experts do: the context matters, as it does under trained
weights, and no stream repeats. At 6 one key takes all of it and the
bf16 stream's rounding moves logits by 4 and more (measured): the
comparison with the reference then tells nothing."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perf.lib import weights as plain

SCORE_STD = 2.5


def make_weights(cfg: Dict[str, Any], seed: int, int8: bool,
                 dtype=jnp.bfloat16):
    """``(model, None)``, as ``weights.make_weights`` without LoRA.
    ``cfg``: the configuration file's ``model`` keys."""
    d, ffn = cfg['hidden_size'], cfg['intermediate_size']
    n_layers, vocab = cfg['num_hidden_layers'], cfg['vocab_size']
    hd = cfg['head_dim']
    q_out = cfg['num_attention_heads'] * hd
    kv_out = cfg['num_key_value_heads'] * hd
    held, shared = cfg['num_experts'], cfg['num_shared_experts']
    routed = cfg['published']['num_experts']

    def one(kk, fan_in, fan_out, scale_by=None):
        w = jax.random.normal(kk, (fan_in, fan_out), jnp.float32) / (
            (scale_by or fan_in) ** 0.5)
        return plain._int8(w) if int8 else w.astype(dtype)

    def stacked(k, fan_in, fan_out, per_layer=None, scale_by=None):
        """[layers, (per_layer,) in, out], one matrix at a time."""
        n = n_layers * (per_layer or 1)
        w = jax.lax.map(lambda kk: one(kk, fan_in, fan_out, scale_by),
                        jax.random.split(k, n))
        if per_layer is None:
            return w
        return jax.tree.map(
            lambda a: a.reshape(n_layers, per_layer, *a.shape[1:]), w)

    def build(key):
        ks = jax.random.split(jax.random.fold_in(key, 1), 14)
        return {
            'embed': (jax.random.normal(ks[0], (vocab, d), jnp.float32)
                      / (d ** 0.5)).astype(dtype),
            'layers': {
                'wq': stacked(ks[1], d, q_out,
                              scale_by=d / SCORE_STD ** 2),
                'wk': stacked(ks[2], d, kv_out),
                'wv': stacked(ks[3], d, kv_out),
                'wo': stacked(ks[4], q_out, d),
                'router': (jax.random.normal(
                    ks[5], (n_layers, d, routed), jnp.float32)
                    / (d ** 0.5)).astype(dtype),
                'w_gate': stacked(ks[6], d, ffn, per_layer=held),
                'w_up': stacked(ks[7], d, ffn, per_layer=held),
                'w_down': stacked(ks[8], ffn, d, per_layer=held),
                # The shared experts side by side: each down
                # projection's fan-in is one expert's width.
                'ws_gate': stacked(ks[9], d, shared * ffn),
                'ws_up': stacked(ks[10], d, shared * ffn),
                'ws_down': stacked(ks[11], shared * ffn, d,
                                   scale_by=ffn),
                'attn_norm': (1.0 + 0.1 * jax.random.normal(
                    ks[12], (n_layers, d), jnp.float32)).astype(dtype),
            },
            'final_norm': (1.0 + 0.1 * jax.random.normal(
                ks[13], (d,), jnp.float32)).astype(dtype),
        }

    return jax.jit(build)(plain.seed_key(seed)), None
