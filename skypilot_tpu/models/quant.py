"""Weight-only int8 quantization for serving.

Decode throughput on a single chip is weight-bandwidth-bound: every
generated token re-reads all matmul weights from HBM. Symmetric
per-output-channel int8 halves that traffic vs bf16; the int8->bf16
convert is fused by XLA into the dot-general's operand read (the
weights cross HBM as int8), and the per-channel scale applies AFTER
the matmul, which is exact for per-output-channel scaling.

Scope: the stacked layer projections (wq/wk/wv/wo, gate/up/down —
including MoE expert stacks, per (layer, expert, out-channel), and
the shared experts' ``ws_*``) and the LM head. Embedding stays bf16
(decode gathers one row per token — negligible traffic); norms/biases/MoE router stay bf16 (tiny; the
router also drives top-k selection — selective precision); the KV
cache is not quantized yet.

The reference has no quantization anywhere (serving is delegated to
external engines, ``llm/vllm/service.yaml``); this is TPU-native new
scope.
"""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama

Params = Dict[str, Any]

# Leaves under params['layers'] (and, for a stack with leading dense
# layers, params['dense_layers']) that are [L, in, out] matmul
# weights; the last four are a latent-attention layer's.
_LAYER_MATMULS = ('wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down',
                  'ws_gate', 'ws_up', 'ws_down',
                  'wq_a', 'wq_b', 'wkv_a', 'wkv_b')
_LAYER_STACKS = ('layers', 'dense_layers')
# Matmul weights that stand alone: the head, and a
# next-token-prediction module's projection (params['mtp'], whose
# 'layers' is one more stack).
_LONE_MATMULS = ('lm_head', 'eh_proj')


def _quantize_stack(stack: Params, quantize, other=lambda w: w
                    ) -> Params:
    return {name: quantize(w) if name in _LAYER_MATMULS else other(w)
            for name, w in stack.items()}


def _quantize_module(mtp: Params, quantize, other=lambda w: w
                     ) -> Params:
    """``params['mtp']``: its layer as the stacks, its projection,
    its three norms left as they are."""
    return {name: _quantize_stack(w, quantize, other)
            if name == 'layers' else quantize(w) if name == 'eh_proj'
            else other(w) for name, w in mtp.items()}


def quantize_weight(w: jax.Array) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel int8: w ~= q * s with q int8 and
    s = amax/127 reduced over the contraction axis (-2) only — any
    leading axes (the stacked layer dim) keep their own scales so the
    pair scans layer-by-layer alongside the weights."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    # Quantize against the bf16-rounded scale that will actually be
    # stored, so q*s reconstructs exactly (codes computed against the
    # f32 scale carry a ~0.2% systematic per-channel mismatch).
    s = s.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return {'q': q, 's': s.astype(jnp.bfloat16)}


# Canonical impl lives in llama.py (the training forward also needs
# it, and quant imports llama — re-export keeps one definition).
matmul = llama.matmul


def expert_einsum(subscript: str, x: jax.Array, w) -> jax.Array:
    """``jnp.einsum(subscript, x, w)`` for plain or quantized expert
    weights. Quantized w is [E, in, out] int8 with per-(expert,
    out-channel) scales [E, 1, out]; the scale applies after the
    contraction (exact for per-output-channel scaling). Used by the
    MoE dispatch path (llama._moe_mlp)."""
    if isinstance(w, dict) and 'q' in w:
        out = jnp.einsum(subscript, x, w['q'].astype(x.dtype))
        # [E, 1, out] -> broadcast over the token/capacity dims of
        # the [E, ..., out] result.
        s = w['s'].astype(out.dtype)
        return out * s.reshape(s.shape[0],
                               *([1] * (out.ndim - 2)), s.shape[-1])
    return jnp.einsum(subscript, x, w)


def quantize_params(params: Params, config: llama.LlamaConfig
                    ) -> Params:
    """Return a params pytree with the big matmul weights replaced by
    {'q': int8, 's': bf16} pairs (shape-compatible with the decode
    path via ``matmul``/``expert_einsum``). MoE expert weights
    [L, E, in, out] quantize per (layer, expert, out-channel) — the
    router stays full precision (selective precision, it is tiny and
    drives top-k selection)."""
    out = dict(params)
    for stack in _LAYER_STACKS:
        if stack in params:
            out[stack] = _quantize_stack(params[stack],
                                         quantize_weight)
    if 'mtp' in params:
        out['mtp'] = _quantize_module(params['mtp'], quantize_weight)
    if 'lm_head' in params:
        out['lm_head'] = quantize_weight(params['lm_head'])
    return out


def init_quantized(config: llama.LlamaConfig, key: jax.Array,
                   dtype=jnp.bfloat16) -> Params:
    """Random-init a params tree LEAF-STREAMED with the matmul weights
    quantized as they materialize — the full bf16 tree never exists on
    device (an 8B bf16 tree alone exceeds a v5e chip's 16 GB HBM; the
    int8 tree is ~8 GB and serves fine).

    Weight VALUES are random benchmark/demo weights (norms at their
    init, biases zero, dense ~N(0, 1/dim)) — real serving loads a
    checkpoint leaf-by-leaf through ``quantize_weight`` the same way.
    """
    shapes = jax.eval_shape(
        lambda: llama.init_params(config, key, dtype=dtype))
    quantize = jax.jit(quantize_weight)

    def init_leaf(name, sd, k):
        if name.startswith('hc_') and name.endswith('_a'):
            return jnp.ones(sd.shape, dtype)     # a mixer's scalars
        if name.startswith('hc_') and name.endswith('_b'):
            return jnp.broadcast_to(
                llama.hc_bias_init(config.hc_mult), sd.shape
            ).astype(dtype)
        if 'norm' in name:
            return (jnp.zeros(sd.shape, dtype) if config.norm_offset
                    else jnp.ones(sd.shape, dtype))
        if name in ('bq', 'bk', 'bv', 'exit_gate_b', 'router_bias'):
            return jnp.zeros(sd.shape, dtype)
        # Same per-leaf fan-in rule as init_params' dense(): matmul
        # weights are [..., in, out] (fan_in = shape[-2]); the
        # embedding's fan-in is its model dim (shape[-1]).
        fan_in = sd.shape[-1] if name == 'embed' else sd.shape[-2]
        scale = 1.0 / (fan_in ** 0.5)
        normal = jax.jit(
            lambda k_: (jax.random.normal(k_, sd.shape, jnp.float32) *
                        scale).astype(dtype))
        return normal(k)

    out: Params = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    for i, (path, sd) in enumerate(flat):
        name = path[-1].key
        leaf = init_leaf(name, sd, jax.random.fold_in(key, i))
        if name in _LAYER_MATMULS + _LONE_MATMULS:
            leaf = quantize(leaf)  # frees the wide original
        node = out
        for part in path[:-1]:
            node = node.setdefault(part.key, {})
        node[name] = leaf
    return out


def quantize_params_streamed(params: Params,
                             config: llama.LlamaConfig) -> Params:
    """``quantize_params`` for HOST-resident trees (checkpoint
    restores): transfers and quantizes ONE leaf at a time so the
    bf16 tree never fully materializes on device (8B bf16 alone
    exceeds a v5e chip's HBM)."""
    quantize = jax.jit(quantize_weight)
    cast = jax.jit(lambda x: x.astype(config.dtype))

    def to_device(leaf):
        return cast(jnp.asarray(leaf))

    out = dict(params)
    for stack in _LAYER_STACKS:
        if stack in params:
            out[stack] = _quantize_stack(params[stack], quantize,
                                         to_device)
    if 'mtp' in params:
        out['mtp'] = _quantize_module(params['mtp'], quantize,
                                      to_device)
    for name in params:
        if name not in _LAYER_STACKS + ('lm_head', 'mtp'):
            # embed, final_norm and the exit gate's two leaves.
            out[name] = cast(jnp.asarray(params[name]))
    if 'lm_head' in params:
        out['lm_head'] = quantize(params['lm_head'])
    return out


def is_quantized(params: Params) -> bool:
    layers = params.get('layers', {})
    wq = layers.get('wq', layers.get('wq_a'))
    return isinstance(wq, dict) and 'q' in wq
