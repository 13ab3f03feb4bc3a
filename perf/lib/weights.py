"""The run's weights, made by the benchmark from ``--seed``: on the
device, in ONE jitted call, in the type they are served in, laid out
as the program's loaders lay a checkpoint out (stacked layers; int8
matmul weights as ``{'q': int8, 's': bf16}`` with one scale per output
channel). The program and the plain reference both get them as data,
so neither takes anything the other has made.

Values: matmul weights and the embedding ~ N(0, 1/fan_in); norm
weights 1 + 0.1 N(0, 1), so that a norm weight that is ignored shows;
LoRA A ~ N(0, 1/hidden) and B = 0, the published LoRA start."""
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed (the driver's pass 2**31).
    ``rbg`` keys draw through the device's own bit generator: the 7 G
    normals of a 7B model take seconds, not a minute."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl='rbg')
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _int8(w: jax.Array) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel int8 against the bf16 scale that
    is stored, so that codes times scale is the weight exactly."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    s = (jnp.maximum(amax, 1e-8) / 127.0).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(w / s.astype(jnp.float32)), -127, 127)
    return {'q': q.astype(jnp.int8), 's': s}


def _model(cfg: Dict[str, Any], key: jax.Array, int8: bool,
           dtype) -> Weights:
    d, ffn = cfg['hidden_size'], cfg['intermediate_size']
    n_layers, vocab = cfg['num_hidden_layers'], cfg['vocab_size']
    hd = d // cfg['num_attention_heads']
    q_out = cfg['num_attention_heads'] * hd
    kv_out = cfg['num_key_value_heads'] * hd

    def matmul(k, fan_in, fan_out, stacked=True):
        def one(kk):
            w = jax.random.normal(kk, (fan_in, fan_out),
                                  jnp.float32) / (fan_in ** 0.5)
            return _int8(w) if int8 else w.astype(dtype)
        if not stacked:
            return one(k)
        # One layer at a time: the float32 stack never exists.
        return jax.lax.map(one, jax.random.split(k, n_layers))

    def norm(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    ks = jax.random.split(key, 12)
    return {
        'embed': (jax.random.normal(ks[0], (vocab, d), jnp.float32) /
                  (d ** 0.5)).astype(dtype),
        'layers': {
            'wq': matmul(ks[1], d, q_out),
            'wk': matmul(ks[2], d, kv_out),
            'wv': matmul(ks[3], d, kv_out),
            'wo': matmul(ks[4], q_out, d),
            'w_gate': matmul(ks[5], d, ffn),
            'w_up': matmul(ks[6], d, ffn),
            'w_down': matmul(ks[7], ffn, d),
            'attn_norm': norm(ks[8], (n_layers, d)),
            'mlp_norm': norm(ks[9], (n_layers, d)),
        },
        'final_norm': norm(ks[10], (d,)),
        'lm_head': matmul(ks[11], d, vocab, stacked=False),
    }


def _lora(cfg: Dict[str, Any], key: jax.Array, rank: int, dtype
          ) -> Weights:
    d, n_layers = cfg['hidden_size'], cfg['num_hidden_layers']
    hd = d // cfg['num_attention_heads']
    kq, kv = jax.random.split(key)

    def a(k):
        return (jax.random.normal(k, (n_layers, d, rank), jnp.float32)
                / (d ** 0.5)).astype(dtype)

    return {
        'wq_a': a(kq),
        'wq_b': jnp.zeros(
            (n_layers, rank, cfg['num_attention_heads'] * hd), dtype),
        'wv_a': a(kv),
        'wv_b': jnp.zeros(
            (n_layers, rank, cfg['num_key_value_heads'] * hd), dtype),
    }


def make_weights(cfg: Dict[str, Any], seed: int, int8: bool,
                 lora_rank: Optional[int] = None,
                 dtype=jnp.bfloat16, shardings=None):
    """``(model, lora)`` from the seed in one jitted call; ``lora`` is
    None without ``lora_rank``. ``shardings`` is the matching pair of
    sharding trees for a mesh (None: the default device)."""
    def build(key):
        model = _model(cfg, jax.random.fold_in(key, 1), int8, dtype)
        lora = None
        if lora_rank is not None:
            lora = _lora(cfg, jax.random.fold_in(key, 2), lora_rank,
                         dtype)
        return model, lora

    # The key is an argument, not a constant of the program: every
    # seed then finds the same executable in the compile cache.
    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
