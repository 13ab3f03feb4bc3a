"""The run's weights for a looped (Ouro) configuration, from
``--seed``: the Llama-shaped leaves as ``perf/lib/weights.py`` makes
them (one layer stack: the passes share it), and the leaves the
architecture adds - a norm on each branch's output (1 + 0.1 N(0, 1),
so that an ignored norm shows) and the exit gate (weight N(0,
1/hidden), bias 0.1 N(0, 1)). On the device, in one jitted call."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perf.lib import weights as plain


def make_weights(cfg: Dict[str, Any], seed: int, int8: bool,
                 dtype=jnp.bfloat16):
    """``(model, None)``, as ``weights.make_weights`` without LoRA."""
    d, n_layers = cfg['hidden_size'], cfg['num_hidden_layers']

    def build(key):
        model = plain._model(cfg, jax.random.fold_in(key, 1), int8,
                             dtype)
        ks = jax.random.split(jax.random.fold_in(key, 3), 4)

        def normal(k, shape, scale):
            return scale * jax.random.normal(k, shape, jnp.float32)

        model['layers']['attn_out_norm'] = (
            1.0 + normal(ks[0], (n_layers, d), 0.1)).astype(dtype)
        model['layers']['mlp_out_norm'] = (
            1.0 + normal(ks[1], (n_layers, d), 0.1)).astype(dtype)
        model['exit_gate_w'] = normal(ks[2], (d, 1),
                                      d ** -0.5).astype(dtype)
        model['exit_gate_b'] = normal(ks[3], (1,), 0.1).astype(dtype)
        return model

    return jax.jit(build)(plain.seed_key(seed)), None
