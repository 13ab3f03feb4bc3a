"""Plain float32 reference of the Llama/Mistral block, as published.

RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU, the
output head; for training, cross-entropy, its gradient with respect to
the LoRA factors, global-norm clipping and AdamW. ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: no kernel,
no cache, no batching trick. It imports nothing of the program.

It reads the run's weights as DATA, in the type they are served in
(int8 codes with per-output-channel scales, or bf16), and widens one
layer at a time inside a scan: a layer of Mistral-7B is 0.87 GB in
float32 and the whole model would be 29 GB.

Departures from the published model: none in the mathematics. The
published ``sliding_window`` of 4,096 is not applied; every cell keeps
its contexts at or under 4,096 positions, where it never binds.

``weight_format`` is the control of the benchmark's comparison: the
same mathematics with every matmul weight re-quantised per output
channel to ``int4``, ``int8`` or ``fp8_e4m3`` - the nearest precision
below the one the configuration states.
"""
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]
_F32 = jnp.float32
_Q_BLOCK = 512  # query rows per block of attention scores
# Compiled entry points, by shape and settings: tracing the same
# function anew for every request would compile it anew.
_JITTED: Dict[tuple, Any] = {}


def _cfg_key(cfg: Dict[str, Any]) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


_FORMAT_TOP = {'int4': 7.0, 'int8': 127.0, 'fp8_e4m3': 448.0}


def _widen(w, weight_format: Optional[str] = None) -> jax.Array:
    """A matmul weight as float32 ``[in, out]``: int8 codes times
    their scale, or bf16 widened. With ``weight_format``,
    re-quantised to it per output channel first (the control): the
    channel's largest magnitude maps to the format's largest value."""
    if isinstance(w, dict):
        w = w['q'].astype(_F32) * w['s'].astype(_F32)
    else:
        w = w.astype(_F32)
    if weight_format is not None:
        top = _FORMAT_TOP[weight_format]
        scale = jnp.maximum(
            jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12) / top
        if weight_format.startswith('int'):
            w = jnp.clip(jnp.round(w / scale), -top, top) * scale
        else:
            w = (w / scale).astype(jnp.float8_e4m3fn).astype(_F32) \
                * scale
    return w


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(_F32)


def _rope(x: jax.Array, positions: jax.Array, theta: float
          ) -> jax.Array:
    """Rotate-half RoPE on ``[T, H, D]`` at integer ``positions``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = positions.astype(_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal grouped-query attention of one sequence. q ``[T, H,
    D]``, k and v ``[T, Hkv, D]``. Scores are formed a block of query
    rows at a time so that ``[H, T, T]`` never exists at once."""
    t, h, d = q.shape
    hkv = k.shape[1]
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    blk = _Q_BLOCK if t % _Q_BLOCK == 0 else t
    cols = jnp.arange(t)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=0)
        s = jnp.einsum('qhd,khd->hqk', qb, k) / np.sqrt(d)
        mask = cols[None, :] <= (start + jnp.arange(blk))[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1),
                          v)

    out = jax.lax.map(rows, jnp.arange(0, t, blk))
    return out.reshape(t, h * d)


def _block(x: jax.Array, lw: Weights, lora: Optional[Weights],
           positions: jax.Array, cfg: Dict[str, Any],
           lora_scale: float, weight_format: Optional[str]
           ) -> jax.Array:
    """One transformer block on one sequence ``[T, hidden]``."""
    h_, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    d = cfg['hidden_size'] // h_
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    h = _rms_norm(x, lw['attn_norm'], eps)
    q = h @ _widen(lw['wq'], weight_format)
    k = h @ _widen(lw['wk'], weight_format)
    v = h @ _widen(lw['wv'], weight_format)
    if lora is not None:
        q = q + (h @ lora['wq_a'].astype(_F32)) @ \
            lora['wq_b'].astype(_F32) * lora_scale
        v = v + (h @ lora['wv_a'].astype(_F32)) @ \
            lora['wv_b'].astype(_F32) * lora_scale
    t = x.shape[0]
    q = _rope(q.reshape(t, h_, d), positions, theta)
    k = _rope(k.reshape(t, hkv, d), positions, theta)
    a = _attention(q, k, v.reshape(t, hkv, d))
    x = x + a @ _widen(lw['wo'], weight_format)
    h = _rms_norm(x, lw['mlp_norm'], eps)
    gate = jax.nn.silu(h @ _widen(lw['w_gate'], weight_format))
    up = h @ _widen(lw['w_up'], weight_format)
    return x + (gate * up) @ _widen(lw['w_down'], weight_format)


def _hidden(weights: Weights, lora: Optional[Weights],
            tokens: jax.Array, cfg: Dict[str, Any], lora_scale: float,
            weight_format: Optional[str], remat: bool) -> jax.Array:
    """Final-norm hidden states ``[T, hidden]`` of one sequence."""
    x = weights['embed'][tokens].astype(_F32)
    positions = jnp.arange(tokens.shape[0])

    def body(xc, scanned):
        lw, ll = scanned
        return _block(xc, lw, ll, positions, cfg, lora_scale,
                      weight_format), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (weights['layers'], lora))
    return _rms_norm(x, weights['final_norm'], cfg['rms_norm_eps'])


def logits_at(weights: Weights, tokens: jax.Array,
              positions: jax.Array, cfg: Dict[str, Any],
              weight_format: Optional[str] = None) -> jax.Array:
    """Float32 logits ``[len(positions), vocab]`` of one sequence's
    full forward pass, at the given positions."""
    with jax.default_matmul_precision('highest'):
        hid = _hidden(weights, None, tokens, cfg, 1.0, weight_format,
                      remat=False)
        return hid[positions] @ _widen(weights['lm_head'],
                                       weight_format)


def served_token_gaps(weights: Weights, cfg: Dict[str, Any],
                      prompt: Sequence[int], served: Sequence[int],
                      pad_to: int, weight_format: Optional[str] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """For one finished request: the reference's forward over prompt
    plus served tokens, and at each served position the gap by which
    a token's logit lies below the reference's best.

    Returns ``(gap_served, gap_lower)``: the gap of the token the
    system served, and (with ``weight_format``) the gap, under the SAME
    full-precision reference, of the token the lower precision puts
    first there - the control's reading. The sequence is padded at
    its end to ``pad_to`` (causal: padding changes nothing before
    it) so that few shapes compile."""
    seq = list(prompt) + list(served)
    n_p, n_s = len(prompt), len(served)
    if len(seq) > pad_to:
        raise ValueError(f'sequence of {len(seq)} exceeds {pad_to}')
    tokens = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
    # Served token i was chosen from the logits at position
    # n_p - 1 + i; pad the position list to a fixed length too.
    pos = np.full((_pad_count(n_s),), n_p - 1, np.int32)
    pos[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    key = ('gaps', pad_to, len(pos), weight_format, _cfg_key(cfg))
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


def _pad_count(n: int) -> int:
    size = 64
    while size < n:
        size *= 2
    return size


# ---------------------------------------------------------------------
# Training: loss, LoRA gradients, clipping, AdamW
# ---------------------------------------------------------------------


def _row_loss(lora: Weights, weights: Weights, row: jax.Array,
              cfg: Dict[str, Any], lora_scale: float,
              weight_format: Optional[str]) -> jax.Array:
    """Mean next-token cross-entropy of one row ``[T + 1]``."""
    hid = _hidden(weights, lora, row[:-1], cfg, lora_scale,
                  weight_format, remat=True)
    logits = hid @ _widen(weights['lm_head'], weight_format)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - tgt)


def _devices_of(weights: Weights) -> list:
    """The devices the weights lie on, in their sharding's order."""
    leaf = jax.tree.leaves(weights)[0]
    mesh = getattr(leaf.sharding, 'mesh', None)
    if mesh is None:
        return list(leaf.sharding.device_set)
    return list(mesh.devices.flatten())


def loss_and_grads(weights: Weights, lora: Weights, batch: np.ndarray,
                   cfg: Dict[str, Any], lora_scale: float,
                   weight_format: Optional[str] = None
                   ) -> Tuple[float, Weights]:
    """Loss of a batch ``[B, T + 1]`` and its gradient with respect
    to the float32 LoRA factors, in blocks of rows: one row at a time
    on each device the weights lie on (every row has the same length,
    so the batch mean is the mean of row means). Where the weights
    are sharded over several chips, each chip follows its own rows
    against the layer it gathers whole."""
    devices = _devices_of(weights)
    n = len(devices)
    if len(batch) % n:
        raise ValueError(f'{len(batch)} rows over {n} devices')
    mesh = jax.sharding.Mesh(np.array(devices), ('rows',))
    by_row = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec('rows'))
    whole = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())
    key = ('grads', batch.shape[1], n, weight_format, lora_scale,
           _cfg_key(cfg))
    if key not in _JITTED:
        def fn(lo, w, rows):
            with jax.default_matmul_precision('highest'):
                losses, grads = jax.vmap(
                    lambda row: jax.value_and_grad(_row_loss)(
                        lo, w, row, cfg, lora_scale, weight_format)
                )(rows)
            return losses.sum(), jax.tree.map(
                lambda g: g.sum(axis=0), grads)
        _JITTED[key] = jax.jit(fn, out_shardings=whole)
    lora = jax.device_put(lora, whole)
    total, grads = 0.0, None
    for i in range(0, len(batch), n):
        rows = jax.device_put(batch[i:i + n], by_row)
        loss, g = _JITTED[key](lora, weights, rows)
        total += float(loss)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = len(batch)
    return total / count, jax.tree.map(lambda x: x / count, grads)


def clip_by_global_norm(grads: Weights, max_norm: float) -> Weights:
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(params: Weights, grads: Weights, mu: Weights,
               nu: Weights, count: int, opt: Dict[str, float]
               ) -> Tuple[Weights, Weights, Weights]:
    """One AdamW update (decoupled weight decay on every leaf, bias
    correction, epsilon outside the root); ``count`` is the number of
    this step, from 1."""
    b1, b2 = opt['b1'], opt['b2']
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu,
                      grads)

    def upd(p, m, v):
        m_hat = m / (1 - b1 ** count)
        v_hat = v / (1 - b2 ** count)
        return p - opt['lr'] * (m_hat / (jnp.sqrt(v_hat) + opt['eps'])
                                + opt['weight_decay'] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu


def follow_training(weights: Weights, lora0: Weights,
                    batches: List[np.ndarray], cfg: Dict[str, Any],
                    opt: Dict[str, float], lora_scale: float,
                    weight_format: Optional[str] = None
                    ) -> Dict[str, Any]:
    """Follow the first ``len(batches)`` steps from the seeded state:
    each step's loss, the first gradient as the optimizer gets it
    (after clipping), and the change of the LoRA factors at the end."""
    lora = jax.tree.map(lambda x: x.astype(_F32), lora0)
    start = lora
    mu = jax.tree.map(jnp.zeros_like, lora)
    nu = jax.tree.map(jnp.zeros_like, lora)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        loss, grads = loss_and_grads(weights, lora, batch, cfg,
                                     lora_scale, weight_format)
        grads = clip_by_global_norm(grads, opt['grad_clip'])
        if first_grad is None:
            first_grad = grads
        lora, mu, nu = adamw_step(lora, grads, mu, nu, i + 1, opt)
        losses.append(loss)
    change = jax.tree.map(jnp.subtract, lora, start)
    return {'losses': losses, 'first_grad': first_grad,
            'change': change}
