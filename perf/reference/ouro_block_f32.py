"""Plain float32 reference of the Ouro (LoopLM) stack: one stack of
layers run ``total_ut_steps`` times over the same weights.

With N1..N4 a layer's four RMSNorms, Nf the model's final norm, T =
``total_ut_steps``, q = ``early_exit_threshold``:

    x = Embed(tokens)
    for t = 1..T:
        for l = 1..L:
            u = x + N2_l(Attn_l(N1_l(x)))
            x = u + N4_l(W_down_l(silu(W_gate_l N3_l(u)) * (W_up_l N3_l(u))))
        x     = Nf(x)                  # end of EVERY pass; goes on into t + 1
        lam_t = sigmoid(w_g . x + b_g)
        h_t   = x
    p_t  = lam_t prod_{j<t} (1 - lam_j)
    t*   = the first t < T with p_1 + ... + p_t >= q, else T
    logits = W_head h_{t*}

``Attn`` is causal multi-head attention with rotate-half RoPE on q and
k, scale head_size^-0.5, no bias. No cache: every pass recomputes its
own keys and values from its own input, which is what "keys and values
of pass t are never shared with another pass" means for a full forward.

``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``; a layer's weights are widened one layer at a time
inside a scan and attention scores are formed a block of query rows at
a time, so a 1,024-position request through 192 layer applications
fits beside the weights. It imports nothing of the program; RMSNorm,
RoPE, attention and the widening (with the control's re-quantisation)
are the Llama reference's, which this block shares.

Not keys of ``config.json`` but the published modelling code's, as the
builder knows it (the configuration file lists each under
``assumed``): the two norms on the branch outputs, the final norm
inside the loop, the gate's form, a separate cache a pass.
"""
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import llama_block_f32 as plain

Weights = Dict[str, Any]
_F32 = jnp.float32
_JITTED: Dict[tuple, Any] = {}


def _layer(x: jax.Array, lw: Weights, positions: jax.Array,
           cfg: Dict[str, Any], weight_format: Optional[str]
           ) -> jax.Array:
    """One layer on one sequence ``[S, hidden]``."""
    heads = cfg['num_attention_heads']
    kv_heads = cfg['num_key_value_heads']
    size = cfg['hidden_size'] // heads
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    s = x.shape[0]

    def w(name):
        return plain._widen(lw[name], weight_format)

    h = plain._rms_norm(x, lw['attn_norm'], eps)
    q = plain._rope((h @ w('wq')).reshape(s, heads, size), positions,
                    theta)
    k = plain._rope((h @ w('wk')).reshape(s, kv_heads, size),
                    positions, theta)
    v = (h @ w('wv')).reshape(s, kv_heads, size)
    a = plain._attention(q, k, v) @ w('wo')
    u = x + plain._rms_norm(a, lw['attn_out_norm'], eps)
    h = plain._rms_norm(u, lw['mlp_norm'], eps)
    m = (jax.nn.silu(h @ w('w_gate')) * (h @ w('w_up'))) @ w('w_down')
    return u + plain._rms_norm(m, lw['mlp_out_norm'], eps)


def pass_states(weights: Weights, tokens: jax.Array,
                cfg: Dict[str, Any],
                weight_format: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """``(h [T, S, hidden], lam [T, S])``: the normed state and the
    exit gate's value after every pass."""
    x = weights['embed'][tokens].astype(_F32)
    positions = jnp.arange(tokens.shape[0])
    gate_w = weights['exit_gate_w'].astype(_F32)[:, 0]
    gate_b = weights['exit_gate_b'].astype(_F32)[0]

    def one_layer(xc, lw):
        return _layer(xc, lw, positions, cfg, weight_format), None

    def one_pass(xc, _):
        xc, _ = jax.lax.scan(one_layer, xc, weights['layers'])
        xc = plain._rms_norm(xc, weights['final_norm'],
                             cfg['rms_norm_eps'])
        return xc, (xc, jax.nn.sigmoid(xc @ gate_w + gate_b))

    _, (h, lam) = jax.lax.scan(one_pass, x, None,
                               length=cfg['total_ut_steps'])
    return h, lam


def exit_pass(lam: jax.Array, q: float) -> jax.Array:
    """The pass served at each position, 0-based: the first t < T - 1
    whose cumulative exit probability reaches ``q``, else T - 1.
    ``lam`` [T, S]."""
    survive = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), survive[:-1]])
    reached = jnp.cumsum(lam * before, axis=0) >= q
    last = lam.shape[0] - 1
    reached = reached.at[last].set(True)
    return jnp.argmax(reached, axis=0)


def logits_at(weights: Weights, tokens: jax.Array,
              positions: jax.Array, cfg: Dict[str, Any],
              weight_format: Optional[str] = None) -> jax.Array:
    """Float32 logits ``[len(positions), vocab]`` of one sequence's
    full forward pass, at the given positions."""
    with jax.default_matmul_precision('highest'):
        h, lam = pass_states(weights, tokens, cfg, weight_format)
        h, lam = h[:, positions], lam[:, positions]
        chosen = exit_pass(lam, float(cfg['early_exit_threshold']))
        served = jnp.take_along_axis(
            h, chosen[None, :, None], axis=0)[0]
        return served @ plain._widen(weights['lm_head'],
                                     weight_format)


def served_token_gaps(weights: Weights, cfg: Dict[str, Any],
                      prompt: Sequence[int], served: Sequence[int],
                      pad_to: int, weight_format: Optional[str] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """The interface ``perf/drivers/serve_engine.check_served`` uses,
    as ``llama_block_f32.served_token_gaps`` states it: for one
    finished request, at each served position the gap by which the
    served token's logit (and, with ``weight_format``, the token the
    lower precision puts first) lies below this reference's best."""
    seq = list(prompt) + list(served)
    n_p, n_s = len(prompt), len(served)
    if len(seq) > pad_to:
        raise ValueError(f'sequence of {len(seq)} exceeds {pad_to}')
    tokens = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
    pos = np.full((plain._pad_count(n_s),), n_p - 1, np.int32)
    pos[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    key = ('gaps', pad_to, len(pos), weight_format,
           plain._cfg_key(cfg))
    if key not in _JITTED:
        def gaps(w, toks, positions, served_ids):
            ref = logits_at(w, toks, positions, cfg)
            best = ref.max(axis=-1)
            rows = jnp.arange(ref.shape[0])
            gap = best - ref[rows, served_ids]
            if weight_format is None:
                return gap, gap
            low = logits_at(w, toks, positions, cfg, weight_format)
            return gap, best - ref[rows, low.argmax(axis=-1)]
        _JITTED[key] = jax.jit(gaps)
    served_ids = np.zeros((len(pos),), np.int32)
    served_ids[:n_s] = served
    gap, gap_low = _JITTED[key](weights, tokens, jnp.asarray(pos),
                                jnp.asarray(served_ids))
    return np.asarray(gap)[:n_s], np.asarray(gap_low)[:n_s]
