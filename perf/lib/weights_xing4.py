"""The run's weights for a ``xing4_0`` configuration (Xing4.0-29B-A4B),
from ``--seed``, laid out as the program's loaders lay a checkpoint
out: ``dense_layers`` (the ``first_k_dense_replace`` leading layers:
the MLA projections, the two stream mixers, a gated MLP of
``intermediate_size``) and ``layers`` (the expert layers: the same
attention and mixers, the router over all ``n_routed_experts`` with
its selection bias, the routed experts stacked ``[layers, experts,
in, out]``, the shared expert as ``ws_*``), an embedding and an untied
head. On the device, in one jitted call, a matrix at a time so that
no float32 stack exists.

Values as ``perf/lib/weights.py``: matmul weights N(0, 1 / fan_in)
(int8 codes with one bf16 scale an output channel, of each expert its
own), norm weights 1 + 0.1 N(0, 1) so that an ignored norm shows, the
router N(0, 1 / hidden) kept in the served float type (it drives the
top-k choice). What this architecture adds:

- the embedding is N(0, 1), a stream of unit scale, and the
  projections that write a sublayer's result back into the streams
  (``wo``, every ``w_down``, ``ws_down``) are drawn ``BRANCH_GAIN``
  = 1/4 as wide, so that a sublayer moves the streams by a few tenths
  of their size, as a trained stack's does. With an embedding of
  scale 1 / sqrt(hidden) and branches of full width (the other
  modules' draw) every layer REPLACES the stream: one expert choice
  that falls the other way in bf16 (a near-tie among 64 scores: one
  token-layer in ten) moves the next layer's input by a tenth, the
  layers after it flip in turn, and over 2 + 8 layers the served
  bf16 stream and the float32 reference decorrelate: the first
  traced run read ``served_logit_gap_max`` 4.60, a random token's
  distance from the best of 131,072 unit logits, and a 10-layer
  stack of width 512 on the CPU read a median logit difference of
  0.3-0.55 where this draw reads 0.01-0.02 (float32 against the
  reference: 0 either way; ``PERF.md`` section 6, PR 38). Greedy
  streams do not repeat under either draw (256 tokens, 240-254
  distinct).

- ``router_bias`` (``e_score_correction_bias``) N(0, 0.03^2): the
  sigmoid scores of neighbouring ranks lie about 0.02 apart, so a
  bias of that size moves which experts are chosen for a good share
  of the tokens, and a program that leaves it out shows.
- the stream mixers: ``phi`` N(0, 1 / (n hidden)), so that the 24
  pre-activations of a token have unit scale; the three scalars
  1 + 0.1 N(0, 1); the gates' biases N(0, 0.5^2); the residual
  matrix's bias 1 on the diagonal and -1 off it, + N(0, 1): after
  the Sinkhorn passes a stream keeps about half of itself and takes
  the rest from the other three, unevenly, so neither the identity
  nor the uniform mix stands in for it, and rows and columns are
  far enough from even before the passes that one pass in place of
  twenty shows (at the rehearsal size 0.030-0.087 on three seeds
  where a sound run reads 0.000; with the bias at 2 and -2 and
  noise of 0.5 it read 0.010-0.048).
- ``wq_b`` alone is drawn wider, so that a query's scores against its
  keys have standard deviation ``SCORE_STD`` where unit-variance q
  and k give (0.1 ln 64 + 1)^2 = 2.0 under this configuration's YaRN
  softmax scale: at 2.5 some thirty of 16 k keys carry the sum
  (N exp(-sigma^2)), the context decides the next token, and greedy
  streams do not fall into the seed's cycles
  (``weights_cohere2_moe.py`` and ``PERF.md`` section 6, PR 35, say
  what happens at 1 and at 6)."""
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perf.lib import weights as plain

SCORE_STD = 2.5
BRANCH_GAIN = 0.25


def softmax_scale_gain(cfg: Dict[str, Any]) -> float:
    """What YaRN multiplies 1 / sqrt(head size) by."""
    yarn = cfg['rope_scaling']
    return (0.1 * yarn['mscale_all_dim'] * math.log(yarn['factor'])
            + 1.0) ** 2


def make_weights(cfg: Dict[str, Any], seed: int, int8: bool,
                 dtype=jnp.bfloat16):
    """``(model, None)``, as ``weights.make_weights`` without LoRA.
    ``cfg``: the configuration file's ``model`` keys."""
    d, vocab = cfg['hidden_size'], cfg['vocab_size']
    n_dense = cfg['first_k_dense_replace']
    n_moe = cfg['num_hidden_layers'] - n_dense
    heads, n = cfg['num_attention_heads'], cfg['hc_mult']
    rq, rkv = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    experts, ffn = cfg['n_routed_experts'], cfg['moe_intermediate_size']
    shared = cfg['n_shared_experts'] * ffn
    q_gain = SCORE_STD / softmax_scale_gain(cfg)

    def one(kk, fan_in, fan_out, gain=1.0):
        w = jax.random.normal(kk, (fan_in, fan_out), jnp.float32) * (
            gain / fan_in ** 0.5)
        return plain._int8(w) if int8 else w.astype(dtype)

    def normal(k, shape, std=1.0, mean=0.0):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def shared_leaves(key, count):
        """What every layer has, dense or expert: [count, ...]."""
        ks = jax.random.split(key, 14)

        def stacked(k, fan_in, fan_out, gain=1.0):
            return jax.lax.map(
                lambda kk: one(kk, fan_in, fan_out, gain),
                jax.random.split(k, count))

        out = {
            'wq_a': stacked(ks[0], d, rq),
            'q_norm': normal(ks[1], (count, rq), 0.1, 1.0),
            'wq_b': stacked(ks[2], rq, heads * (nope + rope), q_gain),
            'wkv_a': stacked(ks[3], d, rkv + rope),
            'kv_norm': normal(ks[4], (count, rkv), 0.1, 1.0),
            'wkv_b': stacked(ks[5], rkv,
                             heads * (nope + cfg['v_head_dim'])),
            'wo': stacked(ks[6], heads * cfg['v_head_dim'], d,
                          BRANCH_GAIN),
            'attn_norm': normal(ks[7], (count, d), 0.1, 1.0),
            'mlp_norm': normal(ks[8], (count, d), 0.1, 1.0),
        }
        res = 2.0 * jnp.eye(n).reshape(-1) - 1.0
        for i, sub in enumerate(('attn', 'mlp')):
            kp, ka, kb = jax.random.split(ks[9 + i], 3)
            out[f'hc_{sub}_phi'] = normal(
                kp, (count, n * d, 2 * n + n * n), (n * d) ** -0.5)
            out[f'hc_{sub}_a'] = normal(ka, (count, 3), 0.1, 1.0)
            out[f'hc_{sub}_b'] = normal(
                kb, (count, 2 * n + n * n),
                jnp.concatenate([jnp.full((2 * n,), 0.5),
                                 jnp.ones((n * n,))]),
                jnp.concatenate([jnp.zeros((2 * n,)), res]))
        return out

    def expert_stack(k, fan_in, fan_out, gain=1.0):
        w = jax.lax.map(lambda kk: one(kk, fan_in, fan_out, gain),
                        jax.random.split(k, n_moe * experts))
        return jax.tree.map(
            lambda a: a.reshape(n_moe, experts, *a.shape[1:]), w)

    def layer_stack(k, fan_in, fan_out, count, gain=1.0):
        return jax.lax.map(lambda kk: one(kk, fan_in, fan_out, gain),
                           jax.random.split(k, count))

    def build(key):
        ks = jax.random.split(jax.random.fold_in(key, 1), 16)
        wide = cfg['intermediate_size']
        return {
            'embed': normal(ks[0], (vocab, d)),
            'dense_layers': dict(
                shared_leaves(ks[1], n_dense),
                w_gate=layer_stack(ks[2], d, wide, n_dense),
                w_up=layer_stack(ks[3], d, wide, n_dense),
                w_down=layer_stack(ks[4], wide, d, n_dense,
                                   BRANCH_GAIN)),
            'layers': dict(
                shared_leaves(ks[5], n_moe),
                router=normal(ks[6], (n_moe, d, experts), d ** -0.5),
                router_bias=normal(ks[7], (n_moe, experts), 0.03),
                w_gate=expert_stack(ks[8], d, ffn),
                w_up=expert_stack(ks[9], d, ffn),
                w_down=expert_stack(ks[10], ffn, d, BRANCH_GAIN),
                ws_gate=layer_stack(ks[11], d, shared, n_moe),
                ws_up=layer_stack(ks[12], d, shared, n_moe),
                ws_down=layer_stack(ks[13], shared, d, n_moe,
                                    BRANCH_GAIN)),
            'final_norm': normal(ks[14], (d,), 0.1, 1.0),
            'lm_head': one(ks[15], d, vocab),
        }

    return jax.jit(build)(plain.seed_key(seed)), None
