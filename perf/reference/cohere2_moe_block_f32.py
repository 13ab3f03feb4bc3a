"""Plain float32 reference of the ``cohere2_moe`` block (Command A+)
at ONE CHIP'S SHARE of a layer: the routed experts held here, the
vocabulary rows held here; attention, the router over all published
experts and the shared experts whole.

With ``x`` the stream, per layer (all float32):

    n     = LayerNorm(x) = (x - mean x) / sqrt(var x + eps) * g     one norm a layer, no bias
    q,k,v = n Wq, n Wk, n Wv                                        no bias, no q/k norm
    sliding layer: q, k <- RoPE(theta, interleaved pairs (x0,x1),(x2,x3).., all head dims);
                   key j visible to query i iff 0 <= i - j < sliding_window
    global  layer: no positional transform; key j visible iff j <= i
    a     = softmax(q k^T / sqrt(head_dim)) v Wo                    grouped: 16 query heads a KV head
    s     = sigmoid(n Wr) over ALL published experts;  I = top-k(s);  w_e = s_e / sum_{e' in I} s_e'
    r     = sum_{e in I, e held here} w_e Wd_e(silu(Wg_e n) * Wu_e n)
    h     = mean_j Wd'_j(silu(Wg'_j n) * Wu'_j n)                   the shared experts
    y     = x + a + r + h                                           the parallel block
    logits = LayerNorm_final(y_L) E^T                               E: the rows held of the tied embedding

Which layers slide is ``layer_types`` of the configuration, read for
the layers that are run. What the absent experts would add is left
out, here as in the program: the partial result goes on to the next
layer (``model-configs`` guide, section 4).

``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
cache, no kernels: every position recomputes its keys from the whole
sequence. So that an 11,776-token request fits beside the run's int8
weights, the layers are a scan, queries, scores and the output
projection are formed a block of query rows at a time, and the
experts are applied one at a time to every position, each weighted by
the router's weight for it (zero where it was not chosen): no
sorting, no grouping. Weights are widened (int8 codes times their
scales; re-quantised for the control) a layer and an expert at a
time. It imports nothing of the program; widening,
padding and the cache key are the Llama reference's."""
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import llama_block_f32 as plain

Weights = Dict[str, Any]
_F32 = jnp.float32
_Q_BLOCK = 128  # query rows per block of scores ([H, 128, S] float32)
_JITTED: Dict[tuple, Any] = {}


def _layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(
        jnp.mean(c * c, axis=-1, keepdims=True) + eps) * w.astype(_F32)


def _rope_interleaved(x: jax.Array, positions: jax.Array,
                      theta: float) -> jax.Array:
    """RoPE over pairs (x[2i], x[2i + 1]) of ``[S, H, D]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = positions.astype(_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(n: jax.Array, lw: Weights, sliding, positions,
               cfg: Dict[str, Any], weight_format: Optional[str]
               ) -> jax.Array:
    """Causal grouped-query attention of one sequence with its
    output projection, from the normed stream ``n`` [S, hidden]; in a
    ``sliding`` layer (a traced flag) q and k are rotated and a query
    sees only the keys less than ``sliding_window`` positions behind
    it, its own counted. Keys and values are formed for the whole
    sequence; queries, scores and the output projection a block of
    rows at a time, so that neither ``[S, heads x size]`` nor ``[H,
    S, S]`` exists at once."""
    heads, kv_heads = (cfg['num_attention_heads'],
                       cfg['num_key_value_heads'])
    size, s = cfg['head_dim'], n.shape[0]
    theta = cfg['rope_theta']
    reach = jnp.where(sliding, cfg['sliding_window'], s + 1)

    def w(name):
        return plain._widen(lw[name], weight_format)

    def placed(x, at):
        return jnp.where(sliding, _rope_interleaved(x, at, theta), x)

    wq, wo = w('wq'), w('wo')
    k = placed((n @ w('wk')).reshape(s, kv_heads, size), positions)
    v = (n @ w('wv')).reshape(s, kv_heads, size)
    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(start):
        at = start + jnp.arange(blk)
        nb = jax.lax.dynamic_slice_in_dim(n, start, blk, axis=0)
        qb = placed((nb @ wq).reshape(blk, heads, size), at)
        qb = qb.reshape(blk, kv_heads, heads // kv_heads, size)
        sc = jnp.einsum('qhgd,khd->hgqk', qb, k) / np.sqrt(size)
        seen = (cols[None, :] <= at[:, None]) & \
            (at[:, None] - cols[None, :] < reach)
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        out = jnp.einsum('hgqk,khd->qhgd', jax.nn.softmax(sc, axis=-1),
                         v)
        return out.reshape(blk, heads * size) @ wo

    return jax.lax.map(rows, jnp.arange(0, s, blk)).reshape(s, -1)


def route(n: jax.Array, router: jax.Array, top_k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """(weights [S, k] summing to 1, experts [S, k]) over all the
    router's outputs."""
    scores = jax.nn.sigmoid(n @ router.astype(_F32))
    w, idx = jax.lax.top_k(scores, top_k)
    return w / w.sum(-1, keepdims=True), idx


def expert_share(n: jax.Array, lw: Weights, cfg: Dict[str, Any],
                 weight_format: Optional[str] = None) -> jax.Array:
    """The routed experts' part of one layer that the experts held
    here give: ``n`` [S, hidden] (the normed stream) -> [S, hidden].
    ``lw['w_*']`` hold experts ``cfg['experts_first']`` ..
    + ``cfg['num_experts']`` of the published
    ``cfg['published']['num_experts']``."""
    w, idx = route(n, lw['router'], cfg['num_experts_per_tok'])
    first = cfg.get('experts_first', 0)

    def one_expert(total, scanned):
        e, ew = scanned
        mine = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        up = jax.nn.silu(n @ plain._widen(ew['w_gate'], weight_format)
                         ) * (n @ plain._widen(ew['w_up'],
                                               weight_format))
        return total + mine[:, None] * (
            up @ plain._widen(ew['w_down'], weight_format)), None

    held = {k: lw[k] for k in ('w_gate', 'w_up', 'w_down')}
    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(n),
        (jnp.arange(cfg['num_experts']), held))
    return total


def shared_mean(n: jax.Array, lw: Weights, cfg: Dict[str, Any],
                weight_format: Optional[str] = None) -> jax.Array:
    """The mean of the shared experts, each a gated MLP of the
    expert width; ``ws_*`` hold them side by side, and they are
    widened one at a time."""
    count, ffn = cfg['num_shared_experts'], cfg['intermediate_size']

    def part(name, j, axis):
        return plain._widen(jax.tree.map(
            lambda a: jax.lax.slice_in_dim(
                a, j * ffn, (j + 1) * ffn, axis=axis)
            if a.shape[axis] == count * ffn else a, lw[name]),
            weight_format)

    total = jnp.zeros_like(n)
    for j in range(count):
        total = total + (
            jax.nn.silu(n @ part('ws_gate', j, 1)) *
            (n @ part('ws_up', j, 1))) @ part('ws_down', j, 0)
    return total / count


def _layer(x: jax.Array, lw: Weights, sliding, positions: jax.Array,
           cfg: Dict[str, Any], weight_format: Optional[str]
           ) -> jax.Array:
    n = _layer_norm(x, lw['attn_norm'], cfg['layer_norm_eps'])
    return (x + _attention(n, lw, sliding, positions, cfg,
                           weight_format) +
            expert_share(n, lw, cfg, weight_format) +
            shared_mean(n, lw, cfg, weight_format))


def hidden(weights: Weights, tokens: jax.Array, cfg: Dict[str, Any],
           weight_format: Optional[str] = None) -> jax.Array:
    """The final-normed state ``[S, hidden]`` of one sequence: a scan
    over the layers that are run, each told whether it slides."""
    x = weights['embed'][tokens].astype(_F32)
    positions = jnp.arange(tokens.shape[0])
    sliding = jnp.asarray(
        [kind == 'sliding_attention' for kind in
         cfg['layer_types'][:cfg['num_hidden_layers']]])

    def one_layer(xc, scanned):
        lw, slides = scanned
        return _layer(xc, lw, slides, positions, cfg,
                      weight_format), None

    x, _ = jax.lax.scan(one_layer, x, (weights['layers'], sliding))
    return _layer_norm(x, weights['final_norm'], cfg['layer_norm_eps'])


def logits_at(weights: Weights, tokens: jax.Array,
              positions: jax.Array, cfg: Dict[str, Any],
              weight_format: Optional[str] = None) -> jax.Array:
    """Float32 logits ``[len(positions), rows held]`` of one
    sequence's full forward pass, at the given positions."""
    with jax.default_matmul_precision('highest'):
        h = hidden(weights, tokens, cfg, weight_format)[positions]
        return h @ weights['embed'].astype(_F32).T * float(
            cfg.get('logit_scale', 1.0))


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
           plain._cfg_key(cfg), tuple(cfg['layer_types']))
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
