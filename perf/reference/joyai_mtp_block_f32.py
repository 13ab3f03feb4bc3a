"""Plain float32 reference of the ``joyai_llm_flash`` block
(JoyAI-LLM-Flash) WITH its next-token-prediction module: latent
attention (MLA) in its EXPANDED form only, plain residuals,
``first_k_dense_replace`` dense layers before the expert layers, every
routed expert held, and the module of ``num_nextn_predict_layers`` 1
(DeepSeek-V3 report, arXiv 2412.19437, section 2.2, depth 1).

The main stack, per token x in R^d (float32), with input RMSNorms g:

    x <- x + Attn(RMSNorm(x) g_attn);   x <- x + F(RMSNorm(x) g_mlp)
    h  = RMSNorm(x_L) g_final;          l = h W_head        (untied)

Attention, from the normed u [d]:

    c_q   = RMSNorm(u W_qa) g_q;   q = c_q W_qb -> heads of (q_nope [nope], q_pe [rope])
    (c, p) = split(u W_kva, rank | rope);  c_kv = RMSNorm(c) g_kv;  k_pe = p
    q_pe, k_pe <- RoPE (theta^(-2i/rope), NO rope_scaling, interleaved pairs (x0,x1),(x2,x3)..; ONE k_pe for all heads)
    k_h   = [c_kv W_kb^K_h ; k_pe],  v_h = c_kv W_kb^V_h
    a     = softmax(q k^T s, causal) v W_o,   s = (nope + rope)^-0.5

F is a gated SiLU MLP of ``intermediate_size`` in the dense layers,
else the expert layer: p = sigmoid(u W_r) over all experts; I = the
top k of p + bias; w_e = p_e / sum_{e' in I} p_e' x
routed_scaling_factor; sum_{e in I} w_e W_d,e(silu(W_g,e u) * W_u,e
u) plus the one shared expert (``n_group`` 1: no group limit).

The module, for a position i with the main stack's state h_i (AFTER
the final norm) and the NEXT token t_{i+1}:

    x_i  = [ RMSNorm(Emb(t_{i+1})) g_e ; RMSNorm(h_i) g_h ] W_eh      W_eh: [2 d, d], the embedding's half first
    y_i  = Layer_mtp(x)_i        one expert layer as above, causal over the pairs 0..i, pair i at RoPE position i
    l'_i = RMSNorm(y_i) g_s W_head                                    Emb and W_head are the main model's

l'_i predicts t_{i+2}. What ``config.json`` does not fix, each also
under ``assumed`` in the configuration file: that h is the state
after the final norm, the order of the concatenation, the pairs'
positions, the selection bias's scale.

SAMPLED rows. The program draws the token after position p as
``jax.random.categorical(key(seed, p), l / T)``, which is the argmax
of l / T + g with g the Gumbel noise of that key. ``gumbel_noise``
writes that noise out (``PRNGKey(uint32 seed)``, ``fold_in(p)``,
``jax.random.gumbel``; a test ties its argmax to
``jax.random.categorical`` under the installed jax), and a served
token's gap is ``max_v(l_v / T + g_v) - (l_tok / T + g_tok)``: zero
where this reference would have drawn the same token, and small where
the served logits' rounding let a near neighbour win. top_p is 1 in
the mix that is checked (no nucleus), and is not modelled.

``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
cache, no absorbed form, no grouped product: the experts are applied
one at a time to every position, each weighted by the router's weight
for it (zero where it was not chosen), widened an expert at a time,
so that a 3,200-token request fits beside the run's 10.8 GB of int8
weights. It imports nothing of the program; widening, padding and the
cache key are the Llama reference's."""
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import llama_block_f32 as plain

Weights = Dict[str, Any]
_F32 = jnp.float32
_Q_BLOCK = 128      # query rows per block of scores ([H, 128, S])
_JITTED: Dict[tuple, Any] = {}


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(_F32)


def _rope_interleaved(x: jax.Array, positions: jax.Array,
                      theta: float) -> jax.Array:
    """RoPE over pairs (x[2i], x[2i + 1]) of ``[S, ..., D]`` at
    frequencies theta^(-2i/D)."""
    dim = x.shape[-1]
    freqs = jnp.asarray(
        1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)),
        _F32)
    ang = positions.astype(_F32)[:, None] * freqs[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(n: jax.Array, lw: Weights, cfg: Dict[str, Any],
               weight_format: Optional[str]) -> jax.Array:
    """Causal latent attention of one sequence, expanded, with its
    output projection, from the normed input ``n`` [S, hidden]; row
    i stands at position i. Keys and values are formed for the whole
    sequence; queries and scores a block of rows at a time."""
    heads, rank = cfg['num_attention_heads'], cfg['kv_lora_rank']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, eps, s = cfg['v_head_dim'], cfg['rms_norm_eps'], n.shape[0]
    theta, scale = cfg['rope_theta'], (nope + rope) ** -0.5

    def w(name):
        return plain._widen(lw[name], weight_format)

    ckv = n @ w('wkv_a')
    c_kv = _rms(ckv[:, :rank], lw['kv_norm'], eps)
    cols = jnp.arange(s)
    k_pe = _rope_interleaved(ckv[:, rank:], cols, theta)
    kv = (c_kv @ w('wkv_b')).reshape(s, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    wq_a, wq_b, wo = w('wq_a'), w('wq_b'), w('wo')
    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s

    def rows(start):
        at = start + jnp.arange(blk)
        nb = jax.lax.dynamic_slice_in_dim(n, start, blk, axis=0)
        q = (_rms(nb @ wq_a, lw['q_norm'], eps) @ wq_b).reshape(
            blk, heads, nope + rope)
        q_pe = _rope_interleaved(q[..., nope:], at, theta)
        sc = (jnp.einsum('qhn,khn->hqk', q[..., :nope], k_nope) +
              jnp.einsum('qhr,kr->hqk', q_pe, k_pe)) * scale
        sc = jnp.where((cols[None, :] <= at[:, None])[None], sc,
                       -jnp.inf)
        out = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(sc, axis=-1),
                         v)
        return out.reshape(blk, heads * vd) @ wo

    return jax.lax.map(rows, jnp.arange(0, s, blk)).reshape(s, -1)


def route(n: jax.Array, router: jax.Array, bias: jax.Array,
          cfg: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """(weights [S, k] summing to routed_scaling_factor, experts [S,
    k]): the top k of score + bias, weighted by the scores without
    it."""
    scores = jax.nn.sigmoid(n @ router.astype(_F32))
    _, idx = jax.lax.top_k(scores + bias.astype(_F32),
                           cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / w.sum(-1, keepdims=True) * float(
        cfg['routed_scaling_factor']), idx


def _gated(n: jax.Array, gate, up, down,
           weight_format: Optional[str]) -> jax.Array:
    return (jax.nn.silu(n @ plain._widen(gate, weight_format)) *
            (n @ plain._widen(up, weight_format))
            ) @ plain._widen(down, weight_format)


def experts(n: jax.Array, lw: Weights, cfg: Dict[str, Any],
            weight_format: Optional[str] = None) -> jax.Array:
    """The expert layer on the normed input ``n`` [S, hidden]: the
    routed experts one at a time over every position, weighted by
    the router (zero where not chosen), plus the shared expert."""
    w, idx = route(n, lw['router'], lw['router_bias'], cfg)

    def one_expert(total, scanned):
        e, ew = scanned
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return total + mine[:, None] * _gated(
            n, ew['w_gate'], ew['w_up'], ew['w_down'],
            weight_format), None

    held = {k: lw[k] for k in ('w_gate', 'w_up', 'w_down')}
    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(n),
        (jnp.arange(cfg['n_routed_experts']), held))
    return total + _gated(n, lw['ws_gate'], lw['ws_up'],
                          lw['ws_down'], weight_format)


def _layer(x: jax.Array, lw: Weights, cfg: Dict[str, Any],
           weight_format: Optional[str]) -> jax.Array:
    """One layer on ``x`` [S, d]; an expert layer where its weights
    have a router, else a dense one."""
    eps = cfg['rms_norm_eps']
    x = x + _attention(_rms(x, lw['attn_norm'], eps), lw, cfg,
                       weight_format)
    n = _rms(x, lw['mlp_norm'], eps)
    if 'router' in lw:
        return x + experts(n, lw, cfg, weight_format)
    return x + _gated(n, lw['w_gate'], lw['w_up'], lw['w_down'],
                      weight_format)


def hidden(weights: Weights, tokens: jax.Array, cfg: Dict[str, Any],
           weight_format: Optional[str] = None) -> jax.Array:
    """The final-normed state ``[S, hidden]`` of one sequence: the
    dense layers, then the expert layers, each a scan."""
    x = weights['embed'][tokens].astype(_F32)

    def one_layer(xc, lw):
        return _layer(xc, lw, cfg, weight_format), None

    for stack in ('dense_layers', 'layers'):
        if stack in weights:
            x, _ = jax.lax.scan(one_layer, x, weights[stack])
    return _rms(x, weights['final_norm'], cfg['rms_norm_eps'])


def module_hidden(weights: Weights, h: jax.Array, tokens: jax.Array,
                  cfg: Dict[str, Any],
                  weight_format: Optional[str] = None) -> jax.Array:
    """The module's state ahead of the head, ``[S, hidden]``: row i
    is RMSNorm(y_i) g_s of the pair (h_i, t_{i+1}). The last row's
    next token is not in ``tokens`` (a 0 stands for it): its row
    means nothing, and by causality touches no other."""
    mtp, eps = weights['mtp'], cfg['rms_norm_eps']
    nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    pair = jnp.concatenate(
        [_rms(weights['embed'][nxt].astype(_F32), mtp['enorm'], eps),
         _rms(h, mtp['hnorm'], eps)], axis=-1)
    x = pair @ plain._widen(mtp['eh_proj'], weight_format)
    x, _ = jax.lax.scan(
        lambda xc, lw: (_layer(xc, lw, cfg, weight_format), None), x,
        mtp['layers'])
    return _rms(x, mtp['final_norm'], eps)


def _head(h: jax.Array, lm_head, weight_format: Optional[str]
          ) -> jax.Array:
    return h @ plain._widen(lm_head, weight_format)


def logits_at(weights: Weights, tokens: jax.Array,
              positions: jax.Array, cfg: Dict[str, Any],
              weight_format: Optional[str] = None) -> jax.Array:
    """Float32 logits ``[len(positions), vocab]`` of one sequence's
    full forward pass, at the given positions."""
    with jax.default_matmul_precision('highest'):
        h = hidden(weights, tokens, cfg, weight_format)[positions]
        return _head(h, weights['lm_head'], weight_format)


def module_logits_at(weights: Weights, tokens: jax.Array,
                     positions: jax.Array, cfg: Dict[str, Any],
                     weight_format: Optional[str] = None) -> jax.Array:
    """The module's float32 logits l'_i ``[len(positions), vocab]``
    at the given pair indices i (each needs ``tokens[i + 1]``)."""
    with jax.default_matmul_precision('highest'):
        h = hidden(weights, tokens, cfg, weight_format)
        y = module_hidden(weights, h, tokens, cfg, weight_format)
        return _head(y[positions], weights['lm_head'], weight_format)


def gumbel_noise(seed, positions: jax.Array, vocab: int) -> jax.Array:
    """The Gumbel noise ``[len(positions), vocab]`` of the program's
    draws for request seed ``seed`` at ``positions``: the key is
    ``fold_in(PRNGKey(uint32(seed)), position)`` and
    ``jax.random.categorical(key, z)`` = argmax(z + this)."""
    root = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    return jax.vmap(lambda p: jax.random.gumbel(
        jax.random.fold_in(root, p), (vocab,), _F32))(
            positions.astype(jnp.int32))


def served_token_gaps(weights: Weights, cfg: Dict[str, Any],
                      prompt: Sequence[int], served: Sequence[int],
                      pad_to: int, weight_format: Optional[str] = None,
                      temperature: float = 0.0, seed: int = 0
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """For one finished request, at each served position the gap by
    which the served token's score (and, with ``weight_format``, the
    token the lower precision would have served) lies below this
    reference's best. The score is the logit for a greedy row
    (``temperature`` 0), else logit / temperature + the Gumbel noise
    of ``(seed, position)``, which is what the program's draw
    maximises (``gumbel_noise``)."""
    seq = list(prompt) + list(served)
    n_p, n_s = len(prompt), len(served)
    if len(seq) > pad_to:
        raise ValueError(f'sequence of {len(seq)} exceeds {pad_to}')
    tokens = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
    pos = np.full((plain._pad_count(n_s),), n_p - 1, np.int32)
    pos[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    sampled = temperature > 0.0
    key = ('gaps', pad_to, len(pos), weight_format, sampled,
           plain._cfg_key(cfg))
    if key not in _JITTED:
        def gaps(w, toks, positions, served_ids, temp, seed_):
            def scores(fmt):
                ref = logits_at(w, toks, positions, cfg, fmt)
                if not sampled:
                    return ref
                return ref / temp + gumbel_noise(seed_, positions,
                                                 ref.shape[-1])
            ref = scores(None)
            best = ref.max(axis=-1)
            rows = jnp.arange(ref.shape[0])
            gap = best - ref[rows, served_ids]
            if weight_format is None:
                return gap, gap
            low = scores(weight_format)
            return gap, best - ref[rows, low.argmax(axis=-1)]
        _JITTED[key] = jax.jit(gaps)
    served_ids = np.zeros((len(pos),), np.int32)
    served_ids[:n_s] = served
    gap, gap_low = _JITTED[key](
        weights, tokens, jnp.asarray(pos), jnp.asarray(served_ids),
        jnp.asarray(max(temperature, 1e-6), _F32),
        jnp.asarray(np.uint32(seed & 0xFFFFFFFF)))
    return np.asarray(gap)[:n_s], np.asarray(gap_low)[:n_s]
