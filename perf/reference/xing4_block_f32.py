"""Plain float32 reference of the ``xing4_0`` block (Xing4.0-29B-A4B):
latent attention (MLA) in its EXPANDED form only, n = ``hc_mult``
residual streams mixed through Sinkhorn-projected matrices (mHC,
arXiv 2512.24880), ``first_k_dense_replace`` dense layers before the
expert layers, every routed expert held.

Per token the stack carries X in R^{n x d}; X_0 = the embedding copied
to all n. Each layer has two sublayers F (attention; then the dense
MLP or the expert layer), each with mixer leaves phi [(n d) x (2n +
n n)], b [2n + n n], three scalars a = (pre, post, res) and an input
RMSNorm g (all float32):

    x      = vec(X);  m = (x phi) rsqrt(mean(x^2) + rms_norm_eps)
    H_pre  = sigmoid(a_pre m[:n] + b[:n])
    H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
    R      = clamp(a_res mat(m[2n:]) + mat(b[2n:]), mhc_h_res_clamp_min, .._max)
    H_res  = exp(R), then hc_sinkhorn_iters times: rows / (row sum + hc_eps), columns / (column sum + hc_eps)
    u      = sum_j H_pre[j] X_j;   y = F(RMSNorm(u) g)
    X'_i   = sum_j H_res[i, j] X_j + H_post[i] y
    logits = RMSNorm(sum_i X_i) g_final W_head                      after the last layer; the head is untied

Attention F, from the normed u [d]:

    c_q   = RMSNorm(u W_qa) g_q;   q = c_q W_qb -> heads of (q_nope [nope], q_pe [rope])
    (c, p) = split(u W_kva, rank | rope);  c_kv = RMSNorm(c) g_kv;  k_pe = p
    q_pe, k_pe <- RoPE (YaRN frequencies, interleaved pairs (x0,x1),(x2,x3)..; ONE k_pe for all heads)
    k_h   = [c_kv W_kb^K_h ; k_pe],  v_h = c_kv W_kb^V_h            W_kb's columns: a head's nope key values, then its v values
    a     = softmax(q k^T s, causal) v W_o,   s = (nope + rope)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2

Expert F, from the normed u: p = sigmoid(u W_r) over all experts; I =
the top k of p + bias; w_e = p_e / sum_{e' in I} p_e' x
routed_scaling_factor; sum_{e in I} w_e W_d,e(silu(W_g,e u) * W_u,e u)
plus the one shared expert. ``n_group`` 1 and ``topk_group`` 1: no
group limit. The next-token-prediction module changes no logit of the
model and is not built.

Departures that ``config.json`` does not fix, each also under
``assumed`` in the configuration file: the mixers' exact form (2
sigmoid on H_post, the norm inside m, the streams' start and their
sum at the end), interleaved RoPE pairs, the selection bias's scale.

``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
cache, no absorbed form, no grouped product: every position recomputes
its keys from the whole sequence, and the experts are applied one at
a time to every position, each weighted by the router's weight for it
(zero where it was not chosen). So that a 20,480-token request fits
beside the run's 7.6 GB of int8 weights, the layers are scans, what
is per token (the mixers, the MLP, the experts, the head) runs a
block of rows at a time, and attention forms queries, scores and its
output projection a block of 128 query rows at a time. Weights are
widened (int8 codes times their scales; re-quantised for the control)
a layer, an expert and a block of head columns at a time. It imports
nothing of the program; widening, padding and the cache key are the
Llama reference's."""
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import llama_block_f32 as plain

Weights = Dict[str, Any]
_F32 = jnp.float32
_Q_BLOCK = 128      # query rows per block of scores ([H, 128, S])
_ROW_BLOCK = 1024   # rows per block of what is per token
_HEAD_BLOCK = 16384  # head columns widened at a time
_JITTED: Dict[tuple, Any] = {}


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(_F32)


def yarn_frequencies(cfg: Dict[str, Any]) -> jax.Array:
    """The ``qk_rope_head_dim / 2`` rotation frequencies under YaRN:
    a pair that turns more than beta_fast times over the original
    context keeps theta^(-2i/dim), one that turns fewer than
    beta_slow times is slowed by ``factor``, a linear ramp between
    (the bounds rounded outwards, as the published modelling code of
    this family does)."""
    dim, theta = cfg['qk_rope_head_dim'], cfg['rope_theta']
    yarn = cfg['rope_scaling']
    orig = yarn['original_max_position_embeddings']
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64)
                           / dim))

    def turns_at(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi)) /
                (2 * math.log(theta)))
    low = max(math.floor(turns_at(yarn['beta_fast'])), 0)
    high = min(math.ceil(turns_at(yarn['beta_slow'])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) /
                   max(high - low, 0.001), 0.0, 1.0)
    return jnp.asarray(inv / yarn['factor'] * ramp + inv * (1 - ramp),
                       _F32)


def softmax_scale(cfg: Dict[str, Any]) -> float:
    yarn = cfg['rope_scaling']
    gain = 0.1 * yarn['mscale_all_dim'] * math.log(yarn['factor']) + 1
    return (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
            ) ** -0.5 * gain * gain


def _rope_interleaved(x: jax.Array, positions: jax.Array,
                      freqs: jax.Array) -> jax.Array:
    """RoPE over pairs (x[2i], x[2i + 1]) of ``[S, ..., D]``."""
    ang = positions.astype(_F32)[:, None] * freqs[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def sinkhorn(r: jax.Array, iters: int, eps: float) -> jax.Array:
    """exp(r) [.., n, n] made doubly stochastic: ``iters`` times,
    rows over (their sum + eps), then columns likewise."""
    m = jnp.exp(r)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def mixers(x: jax.Array, lw: Weights, sub: str, cfg: Dict[str, Any]
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(H_pre [S, n], H_post [S, n], H_res [S, n, n]) of sublayer
    ``sub`` ('attn' or 'mlp') from the streams ``x`` [S, n, d]."""
    s, n, d = x.shape
    flat = x.reshape(s, n * d)
    m = (flat @ lw[f'hc_{sub}_phi'].astype(_F32)) * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) +
        cfg['rms_norm_eps'])
    a = lw[f'hc_{sub}_a'].astype(_F32)
    b = lw[f'hc_{sub}_b'].astype(_F32)
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    r = jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:],
                 cfg['mhc_h_res_clamp_min'], cfg['mhc_h_res_clamp_max'])
    return h_pre, h_post, sinkhorn(r.reshape(s, n, n),
                                   cfg['hc_sinkhorn_iters'],
                                   cfg['hc_eps'])


def _by_rows(fn, *arrays):
    """``fn`` over blocks of rows of ``arrays`` (leading axis S),
    results joined along it: what is per token, a block at a time."""
    s = arrays[0].shape[0]
    blk = _ROW_BLOCK if s % _ROW_BLOCK == 0 else s
    out = jax.lax.map(
        lambda parts: fn(*parts),
        tuple(a.reshape(s // blk, blk, *a.shape[1:]) for a in arrays))
    return jax.tree.map(lambda o: o.reshape(s, *o.shape[2:]), out)


def _attention(n: jax.Array, lw: Weights, positions: jax.Array,
               cfg: Dict[str, Any], weight_format: Optional[str]
               ) -> jax.Array:
    """Causal latent attention of one sequence, expanded, with its
    output projection, from the normed input ``n`` [S, hidden]. Keys
    and values are formed for the whole sequence; queries, scores and
    the output projection a block of rows at a time."""
    heads, rank = cfg['num_attention_heads'], cfg['kv_lora_rank']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, eps, s = cfg['v_head_dim'], cfg['rms_norm_eps'], n.shape[0]
    freqs, scale = yarn_frequencies(cfg), softmax_scale(cfg)

    def w(name):
        return plain._widen(lw[name], weight_format)

    ckv = n @ w('wkv_a')
    c_kv = _rms(ckv[:, :rank], lw['kv_norm'], eps)
    k_pe = _rope_interleaved(ckv[:, rank:], positions, freqs)
    kv = (c_kv @ w('wkv_b')).reshape(s, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    wq_a, wq_b, wo = w('wq_a'), w('wq_b'), w('wo')
    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(start):
        at = start + jnp.arange(blk)
        nb = jax.lax.dynamic_slice_in_dim(n, start, blk, axis=0)
        q = (_rms(nb @ wq_a, lw['q_norm'], eps) @ wq_b).reshape(
            blk, heads, nope + rope)
        q_pe = _rope_interleaved(q[..., nope:], at, freqs)
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


def _layer(x: jax.Array, lw: Weights, positions: jax.Array,
           cfg: Dict[str, Any], weight_format: Optional[str]
           ) -> jax.Array:
    """One layer on the streams ``x`` [S, n, d]; an expert layer
    where its weights have a router, else a dense one."""
    eps = cfg['rms_norm_eps']

    def read(xb, sub, norm):
        h_pre, h_post, h_res = mixers(xb, lw, sub, cfg)
        u = jnp.einsum('sj,sjd->sd', h_pre, xb)
        return _rms(u, lw[norm], eps), h_post, h_res

    def write(xb, y, h_post, h_res):
        return jnp.einsum('sij,sjd->sid', h_res, xb) + \
            h_post[:, :, None] * y[:, None, :]

    n, h_post, h_res = _by_rows(
        lambda xb: read(xb, 'attn', 'attn_norm'), x)
    y = _attention(n, lw, positions, cfg, weight_format)

    def second(xb, yb, h_post_b, h_res_b):
        xb = write(xb, yb, h_post_b, h_res_b)
        nb, h_post2, h_res2 = read(xb, 'mlp', 'mlp_norm')
        if 'router' in lw:
            out = experts(nb, lw, cfg, weight_format)
        else:
            out = _gated(nb, lw['w_gate'], lw['w_up'], lw['w_down'],
                         weight_format)
        return write(xb, out, h_post2, h_res2)

    return _by_rows(second, x, y, h_post, h_res)


def hidden(weights: Weights, tokens: jax.Array, cfg: Dict[str, Any],
           weight_format: Optional[str] = None) -> jax.Array:
    """The final-normed state ``[S, hidden]`` of one sequence: the
    dense layers, then the expert layers, each a scan."""
    emb = weights['embed'][tokens].astype(_F32)
    x = jnp.broadcast_to(emb[:, None, :],
                         (emb.shape[0], cfg['hc_mult'], emb.shape[1]))
    positions = jnp.arange(tokens.shape[0])

    def one_layer(xc, lw):
        return _layer(xc, lw, positions, cfg, weight_format), None

    for stack in ('dense_layers', 'layers'):
        if stack in weights:
            x, _ = jax.lax.scan(one_layer, x, weights[stack])
    return _rms(x.sum(axis=1), weights['final_norm'],
                cfg['rms_norm_eps'])


def _head(h: jax.Array, lm_head, weight_format: Optional[str]
          ) -> jax.Array:
    """``h`` [P, hidden] through the untied head, a block of its
    columns widened at a time."""
    vocab = jax.tree.leaves(lm_head)[0].shape[-1]
    blk = _HEAD_BLOCK if vocab % _HEAD_BLOCK == 0 else vocab

    def part(start):
        cols = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, start, blk,
                                                   axis=-1), lm_head)
        return h @ plain._widen(cols, weight_format)

    out = jax.lax.map(part, jnp.arange(0, vocab, blk))  # [n, P, blk]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], vocab)


def logits_at(weights: Weights, tokens: jax.Array,
              positions: jax.Array, cfg: Dict[str, Any],
              weight_format: Optional[str] = None) -> jax.Array:
    """Float32 logits ``[len(positions), vocab]`` of one sequence's
    full forward pass, at the given positions."""
    with jax.default_matmul_precision('highest'):
        h = hidden(weights, tokens, cfg, weight_format)[positions]
        return _head(h, weights['lm_head'], weight_format)


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
           plain._cfg_key(cfg),
           tuple(sorted(cfg['rope_scaling'].items())))
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
