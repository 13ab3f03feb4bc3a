"""The run's weights for a ``joyai_llm_flash`` configuration
(JoyAI-LLM-Flash), from ``--seed``, laid out as the program's loaders
lay a checkpoint out: ``dense_layers`` (the ``first_k_dense_replace``
leading layers: the MLA projections, a gated MLP of
``intermediate_size``), ``layers`` (the expert layers: the same
attention, the router over all ``n_routed_experts`` with its selection
bias, the routed experts stacked ``[layers, experts, in, out]``, the
shared expert as ``ws_*``), an embedding, an untied head, and ``mtp``,
the next-token-prediction module (``num_nextn_predict_layers`` 1):
``enorm`` and ``hnorm``, ``eh_proj`` [2 hidden, hidden], ``layers``
(ONE expert layer, stacked with a leading 1) and ``final_norm``; it
shares the embedding and the head. On the device, in one jitted call,
a matrix at a time so that no float32 stack exists.

Values as ``perf/lib/weights_xing4.py`` without the stream mixers:
matmul weights N(0, 1 / fan_in) (int8 codes with one bf16 scale an
output channel, of each expert its own), norm weights 1 + 0.1 N(0,
1), the router N(0, 1 / hidden) in the served float type, its
selection bias N(0, 0.03^2); the embedding N(0, 1) and the
projections that write into the stream (``wo``, every ``w_down``,
``ws_down``) ``BRANCH_GAIN`` = 1/4 as wide, so that a sublayer moves
the stream by a few tenths of its size and one expert choice that
falls the other way in bf16 does not cascade (PERF.md section 6, PR
38); ``wq_b`` wider so that attention scores have standard deviation
``SCORE_STD`` = 2 under the plain 192^-0.5 softmax scale (no YaRN
here): some fifty of 3 k keys carry the sum.

The module's draw is the layers' own, from keys of its own:
``eh_proj`` N(0, 1 / (2 hidden)) over two unit-RMS halves gives a
unit stream, its layer's branches are a quarter as wide, its norms 1
+ 0.1 N. So its logits have the main model's scale (standard
deviation 1: a unit-RMS normed state times a head of N(0, 1 /
hidden)) and are INDEPENDENT of the main model's: nothing was
trained to make the draft agree. What acceptance a sampled row then
reads is the shared Gumbel noise's doing (PERF.md section 6, PR
43)."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perf.lib import weights as plain

SCORE_STD = 2.0
BRANCH_GAIN = 0.25


def make_weights(cfg: Dict[str, Any], seed: int, int8: bool,
                 dtype=jnp.bfloat16):
    """``(model, None)``, as ``weights.make_weights`` without LoRA.
    ``cfg``: the configuration file's ``model`` keys."""
    d, vocab = cfg['hidden_size'], cfg['vocab_size']
    n_dense = cfg['first_k_dense_replace']
    n_moe = cfg['num_hidden_layers'] - n_dense
    heads = cfg['num_attention_heads']
    rq, rkv = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    experts, ffn = cfg['n_routed_experts'], cfg['moe_intermediate_size']
    shared = cfg['n_shared_experts'] * ffn

    def one(kk, fan_in, fan_out, gain=1.0):
        w = jax.random.normal(kk, (fan_in, fan_out), jnp.float32) * (
            gain / fan_in ** 0.5)
        return plain._int8(w) if int8 else w.astype(dtype)

    def normal(k, shape, std=1.0, mean=0.0):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def stack(k, fan_in, fan_out, count, gain=1.0):
        return jax.lax.map(lambda kk: one(kk, fan_in, fan_out, gain),
                           jax.random.split(k, count))

    def attention_leaves(key, count):
        """What every layer has, dense or expert: [count, ...]."""
        ks = jax.random.split(key, 9)
        return {
            'wq_a': stack(ks[0], d, rq, count),
            'q_norm': normal(ks[1], (count, rq), 0.1, 1.0),
            'wq_b': stack(ks[2], rq, heads * (nope + rope), count,
                          SCORE_STD),
            'wkv_a': stack(ks[3], d, rkv + rope, count),
            'kv_norm': normal(ks[4], (count, rkv), 0.1, 1.0),
            'wkv_b': stack(ks[5], rkv,
                           heads * (nope + cfg['v_head_dim']), count),
            'wo': stack(ks[6], heads * cfg['v_head_dim'], d, count,
                        BRANCH_GAIN),
            'attn_norm': normal(ks[7], (count, d), 0.1, 1.0),
            'mlp_norm': normal(ks[8], (count, d), 0.1, 1.0),
        }

    def expert_layers(key, count):
        ks = jax.random.split(key, 9)

        def expert_stack(k, fan_in, fan_out, gain=1.0):
            w = stack(k, fan_in, fan_out, count * experts, gain)
            return jax.tree.map(
                lambda a: a.reshape(count, experts, *a.shape[1:]), w)

        return dict(
            attention_leaves(ks[0], count),
            router=normal(ks[1], (count, d, experts), d ** -0.5),
            router_bias=normal(ks[2], (count, experts), 0.03),
            w_gate=expert_stack(ks[3], d, ffn),
            w_up=expert_stack(ks[4], d, ffn),
            w_down=expert_stack(ks[5], ffn, d, BRANCH_GAIN),
            ws_gate=stack(ks[6], d, shared, count),
            ws_up=stack(ks[7], d, shared, count),
            ws_down=stack(ks[8], shared, d, count, BRANCH_GAIN))

    def build(key):
        ks = jax.random.split(jax.random.fold_in(key, 1), 13)
        wide = cfg['intermediate_size']
        return {
            'embed': normal(ks[0], (vocab, d)),
            'dense_layers': dict(
                attention_leaves(ks[1], n_dense),
                w_gate=stack(ks[2], d, wide, n_dense),
                w_up=stack(ks[3], d, wide, n_dense),
                w_down=stack(ks[4], wide, d, n_dense, BRANCH_GAIN)),
            'layers': expert_layers(ks[5], n_moe),
            'final_norm': normal(ks[6], (d,), 0.1, 1.0),
            'lm_head': one(ks[7], d, vocab),
            'mtp': {
                'enorm': normal(ks[8], (d,), 0.1, 1.0),
                'hnorm': normal(ks[9], (d,), 0.1, 1.0),
                'eh_proj': one(ks[10], 2 * d, d),
                'layers': expert_layers(
                    ks[11], cfg['num_nextn_predict_layers']),
                'final_norm': normal(ks[12], (d,), 0.1, 1.0)},
        }

    return jax.jit(build)(plain.seed_key(seed)), None
