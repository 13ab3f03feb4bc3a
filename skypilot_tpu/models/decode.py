"""KV-cache incremental decoding for the in-tree Llama.

The reference serves LLMs through external engines (vLLM/TGI flags in
``llm/vllm/service.yaml``); this module is the TPU-native in-tree
equivalent for the serve recipe: prefill once, then O(1) work per
generated token instead of re-running the full prefix
(``recipes/serve_model.py`` previously recomputed the whole sequence
per token — O(T^2) per reply).

TPU-first design:
- STATIC shapes throughout: the cache is [L, B, max_seq, Hkv, hd] and
  decode attends over all max_seq positions with a position mask —
  no dynamic shapes, so one compiled step serves every position.
- The per-layer loop is a ``lax.scan`` over the stacked [L, ...]
  params AND the cache, which is updated functionally
  (``dynamic_update_slice``) and donated by the caller's jit.
- Decode attention is a plain masked einsum: at q-length 1 the MXU
  tile is tiny either way and flash's block machinery buys nothing.

The paged engine's three model steps live here too, below the
scheduler that jits them (``serve/batching.py``): ``forward_paged``
(a prefill chunk), ``decode_steps_paged`` and ``verify_step_paged``,
and ``mtp_rounds_paged``, the drafting rounds of a model with a
next-token-prediction module (verify, acceptance, the module and the
next draft as one scanned program). Their layer is ``layer_head`` -> the body's own attention over its
own view of the pool -> ``layer_tail``, under ``looped_stack``; the
pool's format and index arithmetic are ``ops/decode_attention.py``'s.
Nothing here imports the serve package, which is above it.
"""
import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu import exceptions
from skypilot_tpu.models import llama
from skypilot_tpu.models import moe
from skypilot_tpu.models.quant import matmul as _mm
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.ops.sampling import sample as sample_lib
from skypilot_tpu.ops.sampling.accept import accept_tokens

Params = Dict[str, Any]
_NEG_INF = -1e30


@dataclasses.dataclass
class KVCache:
    """Functional KV cache. k/v: [L, B, max_seq, Hkv, hd] (compute
    dtype, or int8 with per-(position, head) ``k_scale``/``v_scale``
    [L, B, max_seq, Hkv] when quantized); ``pos`` — number of
    positions already written (same for every sequence in the batch;
    ragged batches left-pad).

    int8 KV (``init_cache(kv_int8=True)``) halves the cache's HBM
    traffic — decode TPOT is cache-bandwidth-bound at long context,
    so this is the serving bandwidth lever (JetStream ships the same
    int8-KV option)."""
    k: jax.Array
    v: jax.Array
    pos: jax.Array  # scalar int32
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


jax.tree_util.register_pytree_node(
    KVCache,
    lambda c: ((c.k, c.v, c.pos, c.k_scale, c.v_scale), None),
    lambda _, leaves: KVCache(*leaves))


def init_cache(config: llama.LlamaConfig, batch: int,
               max_seq: Optional[int] = None,
               kv_int8: bool = False) -> KVCache:
    max_seq = max_seq or config.max_seq_len
    shape = (config.n_layers, batch, max_seq, config.n_kv_heads,
             config.head_dim)
    if kv_int8:
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            pos=jnp.zeros((), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
            v_scale=jnp.zeros(shape[:-1], jnp.bfloat16))
    return KVCache(k=jnp.zeros(shape, config.dtype),
                   v=jnp.zeros(shape, config.dtype),
                   pos=jnp.zeros((), jnp.int32))


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(batch, position, head) symmetric int8: x [B, T, Hkv, hd]
    -> (codes int8, scales bf16 [B, T, Hkv]). The scale is
    bf16-rounded BEFORE encoding so codes reconstruct against the
    stored scale (same rule as models/quant.py)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    s = s.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127,
                 127).astype(jnp.int8)
    return q, s.astype(jnp.bfloat16)


def _dequant_kv(q: jax.Array, scale: Optional[jax.Array],
                dtype) -> jax.Array:
    """Dequantise a [B, S, Hkv] -scaled int8 view to ``dtype`` for
    the contiguous cache's multi-token append (``_layer_cached``),
    whose attention takes float K/V. NOT fused into the consumer on
    the v5e (PR 25's chip trace: a quarter of the decode program's
    time), so the paged steps read codes as codes: the decode and
    verify steps through ``da.view_attention`` since PR 26, the
    prefill chunk through ``da.chunk_attention`` since PR 42."""
    if scale is None:
        return q
    with jax.named_scope('kv_dequant'):
        return q.astype(dtype) * scale[..., None].astype(dtype)


def _masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, kv_len: jax.Array,
                      scale: float) -> jax.Array:
    """q: [B, T, H, hd]; k/v: [B, S, Hkv, hd] (S = max_seq, only
    ``kv_len`` positions valid). Causal within the valid window:
    query at absolute position ``q_pos + i`` sees keys [0, q_pos+i].
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    groups = h // hkv
    qg = q.reshape(b, t, hkv, groups, hd)
    logits = jnp.einsum('bthgd,bshd->bhgts', qg, k,
                        preferred_element_type=jnp.float32) * scale
    key_idx = jnp.arange(s)[None, :]                       # [1, S]
    query_abs = q_pos + jnp.arange(t)[:, None]             # [T, 1]
    mask = (key_idx <= query_abs) & (key_idx < kv_len)     # [T, S]
    logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhgts,bshd->bthgd', probs.astype(v.dtype), v)
    return out.reshape(b, t, h, hd)


def _layer_cached(config: llama.LlamaConfig, x: jax.Array,
                  layer_params: Params, k_cache: jax.Array,
                  v_cache: jax.Array, pos: jax.Array,
                  angles: jax.Array, prefill: bool = False,
                  k_scale: Optional[jax.Array] = None,
                  v_scale: Optional[jax.Array] = None):
    """One transformer layer over ``T`` new positions with cache
    append. x: [B, T, D]; k_cache/v_cache: [B, S, Hkv, hd] (int8 with
    ``k_scale``/``v_scale`` [B, S, Hkv] when the cache is
    quantized). Returns (y, new_k_cache, new_v_cache, new_k_scale,
    new_v_scale). Weight math mirrors ``_layer`` (models/llama.py)
    minus LoRA (serving uses merged weights —
    ``parallel/lora.merge_lora``)."""
    llama.require_plain_stack(
        config, 'decode._layer_cached (the contiguous-cache decode)')
    b, t, _ = x.shape
    nh, nkv, hd = (config.n_heads, config.n_kv_heads, config.head_dim)

    h = llama._rms_norm(x, layer_params['attn_norm'],
                        config.norm_eps, config.norm_offset)
    q = _mm(h, layer_params['wq'])
    k = _mm(h, layer_params['wk'])
    v = _mm(h, layer_params['wv'])
    if config.qkv_bias:
        q = q + layer_params['bq']
        k = k + layer_params['bk']
        v = v + layer_params['bv']
    q = q.reshape(b, t, nh, hd)
    k = k.reshape(b, t, nkv, hd)
    v = v.reshape(b, t, nkv, hd)
    q = attention_ops.apply_rope(q, angles)
    k = attention_ops.apply_rope(k, angles)

    # The caller persists only the NEW rows ([B, t, ...]) into the
    # [L, ...] cache after the layer scan; ``updated`` below exists
    # solely so the float paths' attention reads this step's keys —
    # emitting the full updated [B, S] slice as scan output would
    # write the entire cache to fresh buffers every decoded token
    # (~1 GB/token at 8B, measured ~3.3 ms of the r3 TPOT).
    quantized = k_scale is not None
    if quantized:
        k_rows, ks_rows = _quantize_kv(k)
        v_rows, vs_rows = _quantize_kv(v)
    else:
        k_rows, v_rows = k, v
        ks_rows = vs_rows = None

    def updated(cache, rows):
        return jax.lax.dynamic_update_slice(
            cache, rows, (0, pos) + (0,) * (cache.ndim - 2))

    if t == 1 and quantized:
        # int8 decode step: the cache is read as int8 and the new row
        # goes to attention as an operand (da.view_attention) — the
        # paged engine's arithmetic, which the token-equality tests
        # hold this path to.
        lengths = jnp.full((b,), 0, jnp.int32) + pos
        attn = da.view_attention(
            q[:, 0], k_cache, v_cache, lengths, hd ** -0.5,
            jnp.swapaxes(k_scale, 1, 2), jnp.swapaxes(v_scale, 1, 2),
            new=(k_rows[:, 0], v_rows[:, 0], ks_rows[:, 0],
                 vs_rows[:, 0]))[:, None]
    elif t == 1:
        # Decode step: length-aware attention over the valid cache
        # prefix (Pallas when opted in, dense masked otherwise).
        lengths = jnp.full((b,), 0, jnp.int32) + (pos + 1)
        attn = da.decode_attention(
            q[:, 0], updated(k_cache, k), updated(v_cache, v),
            lengths, hd ** -0.5)[:, None]
    elif prefill:
        # Prefill at pos=0: the cache holds exactly this chunk, so
        # causal flash over the LOCAL q/k/v is the whole attention —
        # O(T) memory vs the dense mask's [B, H, T, max_seq] f32
        # logits (38 GB at T=4k, B=16, S=4.6k). The cache write
        # above may quantize; attention here reads the exact bf16
        # chunk (quantization error only enters later decode steps).
        attn = attention_ops.flash_attention(q, k, v, causal=True,
                                             scale=hd ** -0.5)
    else:
        kd = _dequant_kv(updated(k_cache, k_rows),
                         None if not quantized
                         else updated(k_scale, ks_rows), k.dtype)
        vd = _dequant_kv(updated(v_cache, v_rows),
                         None if not quantized
                         else updated(v_scale, vs_rows), v.dtype)
        attn = _masked_attention(q, kd, vd, q_pos=pos,
                                 kv_len=pos + t, scale=hd ** -0.5)
    x = x + _mm(attn.reshape(b, t, nh * hd), layer_params['wo'])

    h = llama._rms_norm(x, layer_params['mlp_norm'],
                        config.norm_eps, config.norm_offset)
    if config.n_experts:
        # The dropless layer of the paged bodies (models/moe.py), so
        # that the two engines keep serving the same tokens.
        moe_out, _ = moe.moe_layer(config, h, layer_params)
        x = x + moe_out
    else:
        gate = llama.mlp_act(config)(
            _mm(h, layer_params['w_gate']).astype(jnp.float32)
        ).astype(h.dtype)
        up = _mm(h, layer_params['w_up'])
        x = x + _mm(gate * up, layer_params['w_down'])
    return x, k_rows, v_rows, ks_rows, vs_rows


def forward_cached(params: Params, tokens: jax.Array,
                   cache: KVCache, config: llama.LlamaConfig,
                   last_only: bool = False,
                   prefill: bool = False
                   ) -> Tuple[jax.Array, KVCache]:
    """Run ``tokens`` [B, T] at absolute positions
    [cache.pos, cache.pos + T) and append to the cache. Returns
    (logits [B, T, vocab] f32, new cache). Used both for prefill
    (T = prompt length) and decode (T = 1) — same compiled step per
    distinct T.

    ``last_only`` (static): project only the final position through
    the LM head — prefill feeding greedy decode needs just
    logits[:, -1], and skipping the rest avoids materializing a
    [B, T, 128k-vocab] f32 tensor (4.2 GB at B=8, T=1024).

    ``prefill`` (static): promise that ``cache.pos == 0`` — long
    chunks then run causal FLASH attention over the local q/k/v
    instead of the dense mask over the whole cache (O(T) memory).
    Callers feeding a prompt into a fresh cache should set it."""
    if config.kv_lora_rank is not None:
        # Other leaves altogether: refused ahead of the layer scan.
        llama.require_plain_stack(
            config, 'decode.forward_cached (the contiguous-cache '
            'decode)')
    # int8 leaves (weight-only quantization, models/quant.py) must NOT
    # be upcast here — they cross HBM as int8 and convert in-register
    # inside the matmuls.
    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)
    _, t = tokens.shape
    positions = cache.pos + jnp.arange(t)
    angles = llama._rope_frequencies(config, positions)

    x = cparams['embed'][tokens]
    if config.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(config.dim), x.dtype)

    quantized = cache.quantized

    def body(carry, scanned):
        xc, pos = carry
        if quantized:
            layer_params, kc, vc, ks, vs = scanned
        else:
            layer_params, kc, vc = scanned
            ks = vs = None
        y, k_rows, v_rows, ks_rows, vs_rows = _layer_cached(
            config, xc, layer_params, kc, vc, pos, angles,
            prefill=prefill, k_scale=ks, v_scale=vs)
        ys = ((k_rows, v_rows, ks_rows, vs_rows) if quantized
              else (k_rows, v_rows))
        return (y, pos), ys

    xs = ((cparams['layers'], cache.k, cache.v, cache.k_scale,
           cache.v_scale) if quantized
          else (cparams['layers'], cache.k, cache.v))
    (x, _), rows = jax.lax.scan(body, (x, cache.pos), xs)
    # Persist only the new rows: one small [L, B, t, ...] write into
    # the (donated) cache instead of a full-cache rewrite per step.
    new_k = jax.lax.dynamic_update_slice(
        cache.k, rows[0], (0, 0, cache.pos, 0, 0))
    new_v = jax.lax.dynamic_update_slice(
        cache.v, rows[1], (0, 0, cache.pos, 0, 0))
    if quantized:
        new_ks = jax.lax.dynamic_update_slice(
            cache.k_scale, rows[2], (0, 0, cache.pos, 0))
        new_vs = jax.lax.dynamic_update_slice(
            cache.v_scale, rows[3], (0, 0, cache.pos, 0))
    else:
        new_ks = new_vs = None
    if last_only:
        x = x[:, -1:]
    x = llama._rms_norm(x, cparams['final_norm'], config.norm_eps,
                        config.norm_offset)
    if config.tie_embeddings:
        logits = (x @ llama.output_head(cparams, config)
                  ).astype(jnp.float32)
    else:
        # _mm absorbs the quantized-vs-plain distinction.
        logits = _mm(x, cparams['lm_head']).astype(jnp.float32)
    return logits, KVCache(k=new_k, v=new_v, pos=cache.pos + t,
                           k_scale=new_ks, v_scale=new_vs)


def rope(x: jax.Array, angles: jax.Array,
         interleaved: bool = False) -> jax.Array:
    """Rotate-half RoPE of the three paged bodies: x [B, T, H, D];
    angles float32 [..., T, D/2], broadcast over x's leading axes —
    [T, D/2] for one request's chunk, [B, T, D/2] where each row
    stands at positions of its own (T = 1 for a decode step). The
    arithmetic is ``ops.attention.apply_rope``'s, which the training
    path keeps for its one layout. ``interleaved``: pair i is
    (x[2i], x[2i + 1]) ("rope_gptj") in place of (x[i], x[i + D/2]),
    with the same angle i."""
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    # To x's rank in one step: leading axes angles lacks, and heads.
    to_x = (None,) * (x.ndim - 1 - angles.ndim) + (..., None,
                                                   slice(None))
    cos = jnp.cos(angles)[to_x]
    sin = jnp.sin(angles)[to_x]
    r1, r2 = x1 * cos - x2 * sin, x1 * sin + x2 * cos
    if interleaved:
        return jnp.stack([r1, r2], axis=-1).reshape(
            x.shape).astype(x.dtype)
    return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)


def lora_gather_delta(h: jax.Array, a_slots: jax.Array,
                      b_slots: jax.Array,
                      adapter_idx: jax.Array) -> jax.Array:
    """Per-row LoRA delta for mixed-adapter batches (the
    S-LoRA/Punica gather, serve/adapters/): row ``b`` picks ITS
    adapter's stacked factors by slot index and applies
    ``(h @ A) @ B`` — one einsum pair serves every adapter in the
    batch. ``h`` [B, T, d]; ``a_slots`` [C+1, d, R]; ``b_slots``
    [C+1, R, out]; ``adapter_idx`` [B] int32, 0 = the reserved
    all-zeros slot so base-model rows get a delta of exactly 0.
    float32 accumulation, cast by the caller. Per-row math only — a
    row's output is independent of its batch-mates, which is the
    mixed-vs-alone exactness contract the adapter tests assert."""
    with jax.named_scope('lora_delta'):
        a = a_slots[adapter_idx]                    # [B, d, R]
        bm = b_slots[adapter_idx]                   # [B, R, out]
        hf = h.astype(jnp.float32)
        mid = jnp.einsum('btd,bdr->btr', hf, a)
        return jnp.einsum('btr,bro->bto', mid, bm)


def layer_head(config: llama.LlamaConfig, xc: jax.Array, lp: Params,
               ad, adapter_idx, angles: jax.Array, quantized: bool,
               kind: str = 'global'):
    """What precedes attention in a layer of the three PAGED bodies
    (``forward_paged``, ``decode_steps_paged``,
    ``verify_step_paged``; ``layer_tail`` is its complement): the
    attention norm, the three projections (scope ``qkv_proj``), the
    row-gathered LoRA attach on q and v (``ad`` is this layer's slice
    of the resident adapters, None without; every body MUST attach
    the identical delta, or prefill would write KV that decode's
    arithmetic does not imply and verify would accept drafts against
    a different model), ``qkv_bias``, the reshape to heads, RoPE
    (``rope``: ``angles`` in any of its layouts; none on a ``kind``
    'global' layer of a configuration without ``global_rope``), and
    the new rows in the pool's type.

    ``xc`` [B, T, D]. Returns (q [B, T, H, hd], k, v [B, T, Hkv,
    hd], rows): ``rows`` = (k_rows, v_rows, ks_rows, vs_rows) is what
    the pool stores of k and v — int8 codes and bf16 scales [B, T,
    Hkv] for a ``quantized`` pool, else k and v themselves and None
    scales. What each body does with them differs and stays with the
    body."""
    b, t, _ = xc.shape
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    h = llama.norm(config, xc, lp['attn_norm'])
    with jax.named_scope('qkv_proj'):
        q = _mm(h, lp['wq'])
        k = _mm(h, lp['wk'])
        v = _mm(h, lp['wv'])
    if ad is not None:
        q = q + lora_gather_delta(
            h, ad['wq_a'], ad['wq_b'], adapter_idx).astype(q.dtype)
        v = v + lora_gather_delta(
            h, ad['wv_a'], ad['wv_b'], adapter_idx).astype(v.dtype)
    if config.qkv_bias:
        q = q + lp['bq']
        k = k + lp['bk']
        v = v + lp['bv']
    q = q.reshape(b, t, nh, hd)
    k = k.reshape(b, t, nkv, hd)
    v = v.reshape(b, t, nkv, hd)
    if config.global_rope or kind != 'global':
        q = rope(q, angles, config.rope_interleaved)
        k = rope(k, angles, config.rope_interleaved)
    if quantized:
        k_rows, ks_rows = _quantize_kv(k)
        v_rows, vs_rows = _quantize_kv(v)
    else:
        k_rows, v_rows = k, v
        ks_rows = vs_rows = None
    return q, k, v, (k_rows, v_rows, ks_rows, vs_rows)


def layer_tail(config: llama.LlamaConfig, xc: jax.Array,
               attn: jax.Array, lp: Params
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """What follows attention in a layer of the three PAGED bodies
    (``forward_paged``, ``decode_steps_paged``,
    ``verify_step_paged``; ``layer_head`` is its complement): the
    output projection and the
    MLP, each added to the residual stream. ``xc`` [B, T, D]; ``attn``
    [B, T, H * hd]. With ``config.sandwich_norms`` each branch's
    output passes a norm of its own before the add
    (``attn_out_norm``, ``mlp_out_norm``; scope ``branch_norm``).
    With ``config.parallel_block`` the MLP reads the layer's ONE norm
    of the incoming stream, as attention did (taken here again from
    ``xc``: the same expression, which the compiler computes once),
    and both results are added at once. An expert layer is the
    dropless ``moe.moe_layer``. Returns the stream and the pairs the
    expert layer routed to each held expert (None for a dense
    layer)."""
    def branch_norm(out, name):
        if not config.sandwich_norms:
            return out
        with jax.named_scope('branch_norm'):
            return llama._rms_norm(out, lp[name], config.norm_eps,
                                   config.norm_offset)

    if config.parallel_block:
        h = llama.norm(config, xc, lp['attn_norm'])
    with jax.named_scope('o_proj'):
        xc = xc + branch_norm(_mm(attn, lp['wo']), 'attn_out_norm')
    if not config.parallel_block:
        h = llama.norm(config, xc, lp['mlp_norm'])
    with jax.named_scope('mlp'):
        out, routed = _mlp(config, h, lp)
        return xc + branch_norm(out, 'mlp_out_norm'), routed


def _mlp(config: llama.LlamaConfig, h: jax.Array, lp: Params,
         route_on: Optional[jax.Array] = None
         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The layer's MLP on the normed stream ``h`` [B, T, D]: the
    dropless expert layer where the layer has a router (with the
    pairs it routed to each held expert; ``route_on`` as
    ``moe.moe_layer`` takes it), else the dense gated MLP (and
    None)."""
    if 'router' in lp:
        if route_on is None:
            return moe.moe_layer(config, h, lp)
        return moe.moe_layer(config, h, lp, route_on)
    gate = llama.mlp_act(config)(
        _mm(h, lp['w_gate']).astype(jnp.float32)).astype(h.dtype)
    return _mm(gate * _mm(h, lp['w_up']), lp['w_down']), None


# ---------------------------------------------------------------------
# A latent (MLA) layer over several residual streams
# ---------------------------------------------------------------------


def streams(config: llama.LlamaConfig, x: jax.Array) -> jax.Array:
    """The stack's carry from the embedding ``x`` [B, T, D]: with
    ``config.hc_mult`` = n > 1 the token's n residual streams [B, T,
    n, D], each starting as the embedding, in FLOAT32: the mixers
    compute in float32 anyway, the streams are 4 x D values a token
    and never cached, and a carry rounded to bf16 after each of
    twenty sublayers drifts by a percent, enough to make one expert
    choice in ten fall the other way (PERF.md section 6, PR 38); the
    sublayers still read and multiply in the model's type. A latent
    stack with ONE stream carries it in float32 for the same reason
    (its router then reads the normed stream unrounded, as the four
    streams' does: PERF.md section 6, PR 43); every other stack's
    carry is the embedding as it is."""
    if config.hc_mult == 1:
        return x.astype(jnp.float32) \
            if config.kv_lora_rank is not None else x
    return jnp.broadcast_to(
        x[..., None, :].astype(jnp.float32),
        (*x.shape[:-1], config.hc_mult, x.shape[-1]))


def hc_pre(config: llama.LlamaConfig, xc: jax.Array, lp: Params,
           sub: str, wide: bool = False):
    """What a sublayer ``sub`` ('attn' or 'mlp') reads of the n
    streams ``xc`` [B, T, n, D], and how its result goes back (mHC).
    With x = vec(X) [n D], in float32:

        m      = (x phi) rsqrt(mean(x^2) + norm_eps)        [2 n + n n]
        H_pre  = sigmoid(a_pre m[:n] + b[:n])
        H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
        H_res  = Sinkhorn(exp(clamp(a_res mat(m[2n:]) + mat(b[2n:]))))

    Sinkhorn: ``hc_sinkhorn_iters`` times, every row over (its sum +
    ``hc_eps``), then every column likewise (scope ``hc_sinkhorn``;
    the n x n matrices lie [n, n, rows] so that both sums are adds
    across whole registers). Returns (u [B, T, D] = sum_j H_pre[j]
    X_j in the model's type (``wide``: left in float32), the
    sublayer's input ahead of its norm; mix = (H_post [rows, n],
    H_res [n, n, rows]) for ``hc_post``). One stream: (xc, None),
    in the model's type unless ``wide``.
    """
    if config.hc_mult == 1:
        return (xc if wide else xc.astype(config.dtype)), None
    b, t, n, d = xc.shape
    rows = b * t
    f32 = jnp.float32
    with jax.named_scope('hc_pre'):
        xs = xc.reshape(rows, n, d).astype(f32)
        x = xs.reshape(rows, n * d)
        m = (x @ lp[f'hc_{sub}_phi'].astype(f32)) * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + config.norm_eps)
        a = lp[f'hc_{sub}_a'].astype(f32)
        bias = lp[f'hc_{sub}_b'].astype(f32)
        h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(
            a[1] * m[:, n:2 * n] + bias[n:2 * n])
        res = jnp.clip(a[2] * m[:, 2 * n:] + bias[2 * n:],
                       *config.hc_clamp)                 # [rows, n n]
    with jax.named_scope('hc_sinkhorn'):
        mat = jnp.exp(res).T.reshape(n, n, rows)         # [i, j, rows]
        for _ in range(config.hc_sinkhorn_iters):
            mat = mat / (mat.sum(axis=1, keepdims=True) + config.hc_eps)
            mat = mat / (mat.sum(axis=0, keepdims=True) + config.hc_eps)
    with jax.named_scope('hc_pre'):
        u = sum(h_pre[:, j, None] * xs[:, j] for j in range(n))
    if not wide:
        u = u.astype(config.dtype)
    return u.reshape(b, t, d), (h_post, mat)


def hc_post(config: llama.LlamaConfig, xc: jax.Array, y: jax.Array,
            mix) -> jax.Array:
    """The streams after a sublayer whose result is ``y`` [B, T, D]:
    X'_i = sum_j H_res[i, j] X_j + H_post[i] y, in float32 as the
    carry is (``mix`` from ``hc_pre``). One stream: xc + y."""
    if mix is None:
        return xc + y
    b, t, n, d = xc.shape
    h_post, h_res = mix
    with jax.named_scope('hc_post'):
        xs = xc.reshape(b * t, n, d).astype(jnp.float32)
        yf = y.reshape(b * t, d).astype(jnp.float32)
        out = jnp.stack(
            [sum(h_res[i, j][:, None] * xs[:, j] for j in range(n)) +
             h_post[:, i, None] * yf for i in range(n)], axis=1)
        return out.reshape(b, t, n, d)


def latent_head(config: llama.LlamaConfig, xc: jax.Array, lp: Params,
                angles: jax.Array):
    """What precedes attention in a LATENT layer of the paged bodies
    (``layer_head``'s counterpart): the streams' mix for the
    attention sublayer, its norm, the query through its rank-
    ``q_lora_rank`` bottleneck and norm (scope ``mla_q``), and the
    token's latent row (scope ``mla_latent``): ``[RMSNorm(c_kv) ;
    RoPE(k_pe) ; 0..]``, what the pool stores (``da.latent_row``). RoPE turns the
    ``qk_rope_head_dim`` values of each query head and the ONE k_pe
    all heads share.

    ``xc`` [B, T, n, D]. Returns (q_nope [B, T, H, nope], q_pe [B, T,
    H, rope], latent [B, T, W], mix for ``latent_tail``)."""
    b, t = xc.shape[:2]
    nh, nope = config.n_heads, config.qk_nope_head_dim
    rank = config.kv_lora_rank
    u, mix = hc_pre(config, xc, lp, 'attn')
    h = llama.norm(config, u, lp['attn_norm'])
    with jax.named_scope('mla_q'):
        cq = llama._rms_norm(_mm(h, lp['wq_a']), lp['q_norm'],
                             config.norm_eps)
        q = _mm(cq, lp['wq_b']).reshape(b, t, nh, config.head_dim)
        q_nope = q[..., :nope]
        q_pe = rope(q[..., nope:], angles, config.rope_interleaved)
    with jax.named_scope('mla_latent'):
        ckv = _mm(h, lp['wkv_a'])
        c = llama._rms_norm(ckv[..., :rank], lp['kv_norm'],
                            config.norm_eps)
        k_pe = rope(ckv[..., None, rank:], angles,
                    config.rope_interleaved)[..., 0, :]
        latent = da.latent_row(c, k_pe)
    return q_nope, q_pe, latent, mix


def latent_tail(config: llama.LlamaConfig, xc: jax.Array,
                attn: jax.Array, lp: Params, mix
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """What follows attention in a latent layer (``layer_tail``'s
    counterpart): the output projection back into the streams, then
    the second sublayer (its own mix, norm, the dense MLP of a leading
    layer or the expert layer) likewise. ``attn`` [B, T, H * vd].
    Returns the streams and the expert layer's tally (None for a
    dense layer)."""
    with jax.named_scope('o_proj'):
        y = _mm(attn, lp['wo'])
    xc = hc_post(config, xc, y, mix)
    u, mix = hc_pre(config, xc, lp, 'mlp', wide=True)
    # The norm in the carry's float32: the router reads it as it is,
    # the products its rounding to the model's type.
    wide = llama.norm(config, u, lp['mlp_norm'])
    with jax.named_scope('mlp'):
        out, routed = _mlp(config, wide.astype(config.dtype), lp,
                           route_on=wide)
    return hc_post(config, xc, out, mix), routed


def _kv_up(config: llama.LlamaConfig, lp: Params):
    """``wkv_b`` by head: (weights [rank, H, nope + vd], plain or
    int8 codes; per-channel scales [H, nope + vd] or None)."""
    w = lp['wkv_b']
    shape = (config.kv_lora_rank, config.n_heads,
             config.qk_nope_head_dim + config.v_head_dim)
    if isinstance(w, dict) and 'q' in w:
        return w['q'].reshape(shape), w['s'].reshape(shape[1:])
    return w.reshape(shape), None


def absorb_query(config: llama.LlamaConfig, q_nope: jax.Array,
                 lp: Params) -> jax.Array:
    """q_nope [..., H, nope] through the key half of ``wkv_b``
    transposed -> [..., H, rank]: the query of the absorbed form,
    scored against the cached c_kv itself. int8 codes are read as
    codes; their per-channel scales (a channel is one of a head's
    nope values here) multiply the query first, which is exact."""
    w, s = _kv_up(config, lp)
    nope = config.qk_nope_head_dim
    if s is not None:
        q_nope = q_nope * s[:, :nope].astype(q_nope.dtype)
    return jnp.einsum('...hn,rhn->...hr', q_nope,
                      w[:, :, :nope].astype(q_nope.dtype))


def value_up(config: llama.LlamaConfig, o_lat: jax.Array,
             lp: Params) -> jax.Array:
    """The probabilities' sum of c_kv, [..., H, rank], through the
    value half of ``wkv_b`` -> [..., H, vd]."""
    w, s = _kv_up(config, lp)
    nope = config.qk_nope_head_dim
    out = jnp.einsum('...hr,rhv->...hv', o_lat,
                     w[:, :, nope:].astype(o_lat.dtype))
    return out if s is None else out * s[:, nope:].astype(out.dtype)


def expand_latent(config: llama.LlamaConfig, c_kv: jax.Array,
                  lp: Params):
    """c_kv [S, rank] -> (k_nope [S, H, nope], v [S, H, vd]): the
    expanded form's per-head keys and values of cached rows."""
    kv = _mm(c_kv, lp['wkv_b']).reshape(
        c_kv.shape[0], config.n_heads, -1)
    return (kv[..., :config.qk_nope_head_dim],
            kv[..., config.qk_nope_head_dim:])


def looped_stack(config: llama.LlamaConfig, cparams: Params,
                 x: jax.Array, layer, adapters=None,
                 last=lambda h: h):
    """The layer stack of the three paged bodies: a scan of the
    caller's ``layer`` over the stacked weights, run
    ``config.loop_passes`` times over the same weights, closed by the
    final norm after EVERY pass (the normed state goes on into the
    next), and the exit gate's choice of the pass that is served.

    ``layer(x, lp, entry, ad) -> (x, rows)`` is one layer on weights
    ``lp`` (and its slice ``ad`` of ``adapters``, None without),
    reading KV entry ``entry`` = pass x n_layers + layer and
    returning its new K/V rows (any pytree: the expert layer's tally
    rides beside them). With layers of several kinds the scan runs
    over periods (``period_scan`` below). ``last(h)`` cuts the normed
    state down to the positions whose logits are wanted (a prefill
    chunk wants one).

    Every pass always runs for every row: a static batch cannot let
    one row leave early. With ``config.exit_threshold`` = q the pass
    served is chosen BY VALUE, per position: lam_t = sigmoid(w . h_t
    + b) on the normed state; p_t = lam_t prod_{j<t} (1 - lam_j);
    the first t < T whose p_1 + ... + p_t >= q, else T (float32; at
    the published q = 1 that is the last pass unless a gate
    saturates). Returns (hidden of the served pass at ``last``'s
    positions, rows [loop_passes * n_layers, ...]).

    One pass, no gate: one scan over the layers and the final norm
    on ``last(x)``, the program every other model traced before this
    function existed.

    ``config.dense_first`` leading layers run ahead of the scan on
    ``cparams['dense_layers']`` (the scan is then over the expert
    layers alone, entries from ``dense_first`` on), and with
    ``config.hc_mult`` > 1 the carry is the token's streams [.., n,
    D], summed ahead of the final norm."""
    kinds = config.layer_kinds
    period = len(kinds)

    layers, experts = cparams['layers'], None
    if config.n_experts:
        # The expert stacks stay whole and out of the scan
        # (``moe.LayerOf`` says why); a layer gets them by index.
        layers, experts = moe.stacked_experts(layers)

    def with_experts(lp, li):
        if experts is None:
            return lp
        return dict(lp, **{name: moe.LayerOf(w, li)
                           for name, w in experts.items()})

    def layer_scan(xc, first_entry):
        def body(c, scanned):
            lp, li, ad = scanned
            return layer(c, with_experts(lp, li), first_entry + li, ad)
        return jax.lax.scan(
            body, xc,
            (layers,
             jnp.arange(config.n_layers - config.dense_first,
                        dtype=jnp.int32), adapters))

    def period_scan(xc):
        """Layers of several kinds (``config.layer_kinds``): the
        scan's body is one period, each of its layers on its kind's
        own block group, so ``layer`` is also told the ``kind`` and
        ``entry`` counts within that kind's group (period i's j-th
        layer of its kind among n: i * n + j). A layer's weights are
        taken out of the stacks by index inside the body, where the
        products read them in place (a period's worth scanned in as
        one slice was a copy of it: 3 GB of expert codes a step).
        The results come back in layer order, [n_layers, ...]."""
        def body(c, pi):
            ys = []
            for j, kind in enumerate(kinds):
                li = pi * period + j
                lp, ad = jax.tree.map(
                    lambda w: jax.lax.dynamic_index_in_dim(
                        w, li, 0, keepdims=False), (layers, adapters))
                c, y = layer(
                    c, with_experts(lp, li),
                    pi * kinds.count(kind) + kinds[:j].count(kind),
                    ad, kind=kind)
                ys.append(y)
            return c, jax.tree.map(lambda *r: jnp.stack(r), *ys)

        xc, ys = jax.lax.scan(
            body, xc,
            jnp.arange(config.n_layers // period, dtype=jnp.int32))
        return xc, jax.tree.map(
            lambda r: r.reshape(config.n_layers, *r.shape[2:]), ys)

    def final_norm(h):
        if config.hc_mult > 1:
            # The streams' sum is what the head reads.
            h = h.sum(axis=-2)
        return llama.norm(config, h.astype(config.dtype),
                          cparams['final_norm'])

    if config.dense_first:
        # Leading dense layers (leaves of their own, ``dense_layers``)
        # ahead of the scan over the expert layers; their KV entries
        # come first. ``layer`` returns (rows, tally) here, the tally
        # None for a dense layer.
        def dense_body(c, scanned):
            lp, li = scanned
            return layer(c, lp, li, None)
        x, (dense_rows, _) = jax.lax.scan(
            dense_body, x,
            (cparams['dense_layers'],
             jnp.arange(config.dense_first, dtype=jnp.int32)))
        x, (rows, routed) = layer_scan(x, config.dense_first)
        rows = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                            dense_rows, rows)
        return final_norm(last(x)), (rows, routed)

    if period > 1:
        if not (config.loop_passes == 1
                and config.exit_threshold is None):
            raise exceptions.NotSupportedError(
                f'{config.name!r}: a looped stack with layers of '
                f'several kinds is not implemented')
        x, rows = period_scan(x)
        return final_norm(last(x)), rows

    passes, q = config.loop_passes, config.exit_threshold
    if passes == 1 and q is None:
        x, rows = layer_scan(x, 0)
        return final_norm(last(x)), rows

    def one_pass(carry, t):
        xc, survive, total, picked, served = carry
        with jax.named_scope('loop_pass'):
            xc, rows = layer_scan(xc, t * config.n_layers)
            xc = final_norm(xc)
        h = last(xc)
        reached = t == passes - 1
        if q is not None:
            with jax.named_scope('exit_gate'):
                lam = jax.nn.sigmoid(
                    (h.astype(jnp.float32) @
                     cparams['exit_gate_w'].astype(jnp.float32)
                     )[..., 0] +
                    cparams['exit_gate_b'].astype(jnp.float32)[0])
                total = total + lam * survive
                survive = survive * (1.0 - lam)
                reached = reached | (total >= q)
        take = ~picked & reached
        served = jnp.where(take[..., None], h, served)
        return (xc, survive, total, picked | take, served), rows

    h0 = last(x)
    lead = h0.shape[:-1]
    (_, _, _, _, served), rows = jax.lax.scan(
        one_pass,
        (x, jnp.ones(lead, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(lead, bool), jnp.zeros_like(h0)),
        jnp.arange(passes, dtype=jnp.int32))
    # [passes, n_layers, ...] -> one row per KV entry.
    rows = jax.tree.map(
        lambda r: r.reshape(-1, *r.shape[2:]), rows)
    return served, rows


def _by_kind(config: llama.LlamaConfig, x):
    """``x`` by kind of layer. A configuration whose layers are all
    of one kind hands the paged bodies one pool 4-tuple and one block
    table, as ever; one with window AND global layers hands a dict
    of each, keyed 'window' / 'global' (``kv_pool.KVBlockPool``: a
    block group a kind). The bodies work on the dict."""
    return x if isinstance(x, dict) else {config.layer_kinds[0]: x}


def _as_given(given, by_kind):
    return by_kind if isinstance(given, dict) else \
        next(iter(by_kind.values()))


def _flat_pools(config: llama.LlamaConfig, kind: str, pools,
                block_size: int):
    """One group's pool 4-tuple [E, NB, bs, ...] as flat [E, NB * bs,
    ...] arrays (write index arithmetic is one-dimensional), with the
    group's block count."""
    k_pool, _, k_scale, _ = pools
    ne, nb, bs = k_pool.shape[:3]
    assert bs == block_size, (bs, block_size)
    assert ne == config.kind_entries(kind), (
        kind, ne, config.kind_entries(kind))
    return tuple(
        None if p is None else p.reshape(ne, nb * bs, *p.shape[3:])
        for p in pools), nb


def _unflat_pools(flat, nb: int):
    return tuple(
        None if p is None else
        p.reshape(p.shape[0], nb, p.shape[1] // nb, *p.shape[2:])
        for p in flat)


def _kind_rows(config: llama.LlamaConfig, rows, kind: str):
    """The new rows of one kind's layers, [E_kind, ...] in that
    group's entry order, out of ``looped_stack``'s [n_layers, ...]
    (layer order)."""
    kinds = config.layer_kinds
    if len(kinds) == 1:
        return rows
    mine = [j for j, k in enumerate(kinds) if k == kind]

    def pick(r):
        r = r.reshape(-1, len(kinds), *r.shape[1:])[:, mine]
        return r.reshape(-1, *r.shape[2:])
    return jax.tree.map(pick, rows)


def _write_rows(group, idx: jax.Array, rows, *module):
    """One group's flat pools [E, NB * bs, ...] with ``rows`` (a
    tuple in the pool tuple's order, [E, len(idx), ...] each) written
    at the flat slots ``idx``: one scatter a pool array, whatever the
    format has (K, V and their scales, K and V, or latent rows).
    ``module``: a latent group's further write (entry, idx, rows) in
    the same scatter (``_write_latent_rows``)."""
    rows = tuple(rows) + (None,) * (len(group) - len(rows))
    if group[1] is None:
        return (_write_latent_rows(group[0], (0, idx, rows[0]),
                                   *([module] if module else [])),
                ) + group[1:]
    return tuple(None if p is None else p.at[:, idx].set(r)
                 for p, r in zip(group, rows))


def _write_latent_rows(flat: jax.Array, *writes) -> jax.Array:
    """A latent group's flat pool [E, NB * bs, W] with each of
    ``writes`` = (entry, idx, rows [n, len(idx), W]) written at the
    flat slots ``idx`` of the entries ``entry`` .. ``entry + n`` (the
    main stack's layers from 0; a next-token-prediction module's one
    entry after them, at slots of its own), as ONE scatter of all
    the rows into the pool laid end to end. The scatter batched over
    E (``flat.at[:, idx]``) makes the v5e's compiler keep the whole
    pool in a layout with E next to the row, a copy of it of 5.8 GB
    at 18,945 blocks; and two scatters one after the other made a
    one-token chunk's program hold a second pool (3.4 GB at 18,561
    blocks, deviceless v5e compile, PR 43)."""
    ne, slots, width = flat.shape
    at = []
    for entry, idx, rows in writes:
        assert entry + rows.shape[0] <= ne, (entry, rows.shape, ne)
        entries = jnp.arange(rows.shape[0], dtype=idx.dtype)
        if entry:
            entries = entries + entry
        at.append((entries[:, None] * slots +
                   idx[None, :]).reshape(-1))
    # (In this order a single write lowers to the text it had before
    # there could be several.)
    pool = flat.reshape(ne * slots, width)
    values = [rows.reshape(-1, width) for _, _, rows in writes]
    if len(writes) > 1:
        at, values = [jnp.concatenate(at)], [jnp.concatenate(values)]
    return pool.at[at[0]].set(values[0]).reshape(flat.shape)


def _routed_sums(routed: jax.Array) -> jax.Array:
    """Per-step tallies [steps, n_layers, held] as the paged bodies
    return them, int32 [2, n_layers, held]: the pairs routed to each
    held expert summed over the steps, and in how many of the steps
    the expert got any."""
    return jnp.stack([routed.sum(axis=0),
                      (routed > 0).sum(axis=0, dtype=jnp.int32)])


def _all_blocks(flat: jax.Array, block_size: int) -> jax.Array:
    """[E, NB * bs, ...] -> every KV entry's blocks as ONE pool
    [E * NB, bs, ...], which entry e (a pass and a layer,
    ``kv_pool.KVBlockPool``) reads through its block table offset by
    e * NB: an entry's slice taken out of the stacked pool first (a
    scanned input, or an index) is a copy of the slice, 75 MB of K
    and of V a layer at 4,561 blocks."""
    return flat.reshape(-1, block_size, *flat.shape[2:])


def _scale_views(k_scale, v_scale, block_tables: jax.Array,
                 block_size: int, walk: bool = False):
    """Every KV entry's K and V scales for the rows' views
    (``decode_attention.gather_scales``), gathered OUTSIDE the layer
    scan as ONE array [E, 2, B, Hkv, S] float32 (201 MB at 32 x 24 x
    8 x 4,096; 2.7 ms of a 48 ms decode step) that the layer body
    indexes by entry; None for a bf16 pool. Where the layers' attention
    walks the pool (``walk``: ``decode_attention.walk_engages``) the
    same values in the walk's layout, [E, 2, B, tiles, tile]
    (``decode_attention.walk_scales``). k_scale/v_scale are the
    flat [E, NB * bs, Hkv] pools. Timed on the v5e (PERF.md, PR 26):
    scale pools read inside the layer scan cost 7-120 ms a step more
    — an array of 37-100 MB that rides the layer loop is placed in
    the compiler's on-chip memory space and evicted and fetched back
    in every layer (two [L, B, Hkv, S] arrays: 55 ms a step), and
    the pools' [.., 16, 8] tail reshapes to blocks by a copy
    (172 ms)."""
    if k_scale is None:
        return None
    gather = da.walk_scales if walk else da.gather_scales
    return jnp.stack([
        gather(sp.reshape(sp.shape[0], -1, block_size, sp.shape[-1]),
               block_tables) for sp in (k_scale, v_scale)], axis=1)


def forward_paged(params: Params, tokens: jax.Array, pools,
                  block_row: jax.Array, start: jax.Array,
                  real_len: jax.Array, config: llama.LlamaConfig,
                  block_size: int, adapters=None, adapter_idx=None,
                  mtp=None):
    """One PREFILL CHUNK of one request, written directly into paged
    KV-pool blocks (serve/kv_pool.py) — the paged engine's
    copy-on-admit removal: no per-request staging cache, no
    row-insert copy.

    tokens [1, T] — positions [start, start + T) of the prompt, with
    only the first ``real_len`` real (the rest pad the chunk to its
    static bucket; their K/V writes are redirected to the scratch
    block and their logits discarded). ``pools`` is the engine's
    cache 4-tuple (k, v, k_scale, v_scale) with k/v
    [entries, num_blocks, block_size, Hkv, hd]; ``block_row`` [MB]
    int32 is THIS request's block table. ``start``/``real_len`` are
    traced scalars — one executable serves every chunk of every
    prompt at a given bucket T.

    Attention per layer: the chunk's queries walk the request's OWN
    blocks, a tile of cached keys [0, start) at a time, then the
    chunk's own exact rows, with a running maximum and sum
    (``da.chunk_attention``): chunk c sees every earlier chunk's keys
    plus itself causally, so chunked prefill is numerically the plain
    prefill, and what a chunk reads follows ``start``, not
    ``max_seq`` (until PR 42 every chunk scored the row's whole
    padded view). No in-layer pool write: the chunk's rows reach the
    pool by ONE merged scatter after the layer scan (``kv_write``).
    The same contract carries the engine's PREFIX-CACHE suffix
    prefill: when admission reuses cached blocks for the leading
    ``start`` tokens (the table points at pinned shared blocks), the
    first chunk simply begins at that offset and the walk reads the
    cached K/V as if this request had prefilled it — no cache-aware
    branch exists in the model code at all.

    Returns (logits [1, vocab] f32 at the chunk's LAST REAL position,
    new pools, routed). Only the final chunk's logits are meaningful
    (they seed greedy decoding); earlier chunks' are computed into
    the same cheap [1, 1, vocab] projection and ignored. ``routed``
    is None for a dense model, else int32 [2, n_layers, experts held]
    (``_routed_sums``, one step): the (token, expert) pairs this
    chunk, padding included, routed to each expert held here.

    A configuration with a next-token-prediction module
    (``config.nextn_layers``) is handed ``mtp`` = (h_prev [1, 1, D],
    hidden) and also runs the module over the chunk's pairs
    (``mtp_module``, EXPANDED as the main layers are). The pair of
    the token at position i is (h_{i-1}, t_i) and its row lies at
    slot i of the module's entry, so the chunk's pairs need the
    state of the position BEFORE the chunk: ``h_prev``, which the
    previous chunk returned (a fourth value, the final-normed state
    of this chunk's last real position; the engine carries it from
    chunk to chunk and hands the last one to ``mtp_first_paged``).
    Position 0 has no pair: its slot stays empty and masked. After a
    PREFIX HIT nothing kept that state, so the engine starts the
    first chunk one token early with ``hidden`` = 1: the chunk's
    first lane recomputes position start, whose rows the shared
    block already holds, as a read-only lane (its writes go to the
    scratch block, as padded lanes' do; the module reads that slot
    from the pool and not from the lane, whose pair lacks its
    state). ``routed`` then counts the module's layer last.

    Two attention forms, by the configuration's kind of cache. Keys
    and values in a pool (float or int8): tiles of pool keys
    (``layer`` below; scope ``prefill_attention``); with window
    layers (``config.sliding_window``) ``pools`` and ``block_row``
    come as dicts by kind of layer (``_by_kind``) and a window
    layer's walk is bounded by the window (scopes
    ``window_attention``, ``global_attention``). A latent
    configuration (``config.kv_lora_rank``) hands one pool tuple
    ``(rows, None, None, None)`` and attends EXPANDED over tiles of
    latent rows (``latent_layer``: ``da.latent_chunk_attention``,
    scope ``mla_expanded_attention``), its carry the token's
    residual streams (``streams``).

    The pools' leading axis ``l`` counts KV entries, one for every
    pass and layer (``kv_pool.KVBlockPool``); a looped configuration
    runs the layers ``config.loop_passes`` times (``looped_stack``).

    The layer is ``layer_head`` -> this body's own attention ->
    ``layer_tail``, as in ``decode_steps_paged`` and
    ``verify_step_paged``; the attention is what each of the three
    keeps of its own. The dense ``_layer_cached`` is still a body of
    its own; ``tests/test_paged_bodies.py`` (the three agree on one
    position; a chunk over tiles equals the chunk over the row's
    whole view) and the engine's token-for-token-equality tests
    against ``greedy_generate`` are the drift alarm. int8 pools:
    within-chunk attention reads the chunk's exact bf16 rows, but a
    LATER chunk reads earlier chunks' int8 codes, so exact equality
    with the dense int8 path holds for single-chunk prompts
    (multi-chunk tracks closely; see the engine docstring caveat).
    """
    by_kind = _by_kind(config, pools)
    tables = _by_kind(config, block_row)
    quantized = next(iter(by_kind.values()))[2] is not None
    windowed = config.sliding_window is not None
    latent = config.kv_lora_rank is not None
    nh, hd = config.n_heads, config.head_dim
    _, t = tokens.shape

    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)
    positions = start + jnp.arange(t)
    angles = llama._rope_frequencies(config, positions)
    x = streams(config, cparams['embed'][tokens])
    if config.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(config.dim), x.dtype)

    # Flat [NB * bs, ...] pool views; the write index vector is
    # chunk-invariant across layers, computed once (a group).
    flat, nbs, gw, scale_views = {}, {}, {}, {}
    for kind, group in by_kind.items():
        flat[kind], nbs[kind] = _flat_pools(config, kind, group,
                                            block_size)
        gw[kind] = da.chunk_write_indices(
            tables[kind], start, real_len, t, block_size)     # [T]
        if mtp is not None:
            # Read-only leading lanes write scratch.
            gw[kind] = jnp.where(
                jnp.arange(t) < mtp[1],
                da.SCRATCH_BLOCK * block_size, gw[kind])
        # This request's scales of every entry, [E, 2, 1, Hkv, S],
        # gathered outside the layer loop (``_scale_views`` says why).
        scale_views[kind] = _scale_views(
            *flat[kind][2:], tables[kind][None], block_size)

    def latent_layer(xc, lp, entry, ad, kind='latent'):
        """A latent layer: the chunk's queries over the cached
        latent rows and its own, EXPANDED a tile of keys at a time
        (``da.latent_chunk_attention``); no in-layer write."""
        del ad
        q_nope, q_pe, rows, mix = latent_head(config, xc, lp, angles)
        with jax.named_scope('mla_expanded_attention'):
            attn = da.latent_chunk_attention(
                q_nope[0], q_pe[0], rows[0],
                _all_blocks(flat[kind][0], block_size),
                tables[kind] + entry * nbs[kind], start,
                llama.attention_scale(config),
                functools.partial(expand_latent, config, lp=lp),
                config.kv_lora_rank)
        xc, routed = latent_tail(config, xc, attn.reshape(1, t, -1),
                                 lp, mix)
        return xc, ((rows[0],), routed)

    def layer(xc, lp, entry, ad, kind=config.layer_kinds[0]):
        """A layer that caches keys and values: the chunk's queries
        over the request's own blocks, a tile of cached keys at a
        time, and over its own exact rows (``da.chunk_attention``);
        no in-layer write and no view of ``max_seq``."""
        q, k, v, (k_rows, v_rows, ks_rows, vs_rows) = layer_head(
            config, xc, lp, ad, adapter_idx, angles, quantized, kind)
        with jax.named_scope(kind + '_attention' if windowed
                             else 'prefill_attention'):
            # This pass's and layer's KV entry is read through the
            # table, offset into ONE pool of every entry's blocks
            # (at one pass the entry is the layer).
            ks_view, vs_view = (None, None) \
                if scale_views[kind] is None \
                else jax.lax.dynamic_index_in_dim(
                    scale_views[kind], entry, 0, keepdims=False,
                    allow_negative_indices=False)[:, 0]
            attn = da.chunk_attention(
                q[0], k[0], v[0],
                *(_all_blocks(p, block_size) for p in flat[kind][:2]),
                tables[kind] + entry * nbs[kind], start, hd ** -0.5,
                ks_view, vs_view,
                window=config.sliding_window
                if kind == 'window' else None)[None]
        xc, routed = layer_tail(config, xc,
                                attn.reshape(1, t, nh * hd), lp)
        return xc, (((k_rows[0], v_rows[0], ks_rows[0], vs_rows[0])
                     if quantized else (k_rows[0], v_rows[0])),
                    routed)

    # Project ONLY the chunk's last real position (start offsets make
    # it real_len - 1 within the chunk) — a full [1, T, vocab] f32
    # materialization is the admission cost this path deletes.
    def last_real(h):
        return jnp.take(h, jnp.maximum(real_len - 1, 0)[None],
                        axis=1)                              # [1,1,D]

    x_last, (rows, routed) = looped_stack(
        config, cparams, x, latent_layer if latent else layer,
        adapters, last=last_real if mtp is None else lambda h: h)
    module_rows = {}
    if mtp is not None:
        # The module over the chunk's pairs (h_{i-1}, t_i), each at
        # RoPE position i - 1, its row at slot i.
        kind = config.layer_kinds[0]
        entry = config.kv_entries - 1
        h_all, x_last = x_last, last_real(x_last)
        h_before = jnp.concatenate(
            [mtp[0].astype(h_all.dtype), h_all[:, :-1]], axis=1)

        def attend(q_nope, q_pe, m_rows, lp):
            with jax.named_scope('mla_expanded_attention'):
                return da.latent_chunk_attention(
                    q_nope[0], q_pe[0], m_rows[0],
                    _all_blocks(flat[kind][0], block_size),
                    tables[kind] + entry * nbs[kind], start,
                    llama.attention_scale(config),
                    functools.partial(expand_latent, config, lp=lp),
                    config.kv_lora_rank, first=1, hidden=mtp[1]
                ).reshape(1, t, -1)

        _, m_rows, m_routed = mtp_module(
            config, cparams, h_before, tokens,
            llama._rope_frequencies(
                config, jnp.maximum(positions - 1, 0)), attend)
        module_rows[kind] = (entry, jnp.where(
            positions == 0, da.SCRATCH_BLOCK * block_size, gw[kind]),
            m_rows)
        routed = jnp.concatenate([routed, m_routed[None]])
    # Persist the chunk's rows with ONE scatter into the (donated)
    # flat pools (a group), the module's among them.
    with jax.named_scope('kv_write'):
        new_pools = {
            kind: _write_rows(group, gw[kind],
                              _kind_rows(config, rows, kind),
                              *module_rows.get(kind, ()))
            for kind, group in flat.items()}
    logits = _head_logits(config, cparams, x_last).astype(jnp.float32)
    new_pools = {kind: _unflat_pools(group, nbs[kind])
                 for kind, group in new_pools.items()}
    out = (logits[:, 0], _as_given(pools, new_pools),
           None if routed is None else _routed_sums(routed[None]))
    return out if mtp is None else out + (x_last,)


def decode_steps_paged(params: Params, tokens: jax.Array,
                       caches, block_tables: jax.Array,
                       pos: jax.Array, active: jax.Array,
                       config: llama.LlamaConfig,
                       num_steps: int, block_size: int,
                       adapters=None, adapter_idx=None,
                       sampling=None, *,
                       view_blocks: Optional[int] = None):
    """Decode ``num_steps`` tokens for every row at PER-ROW
    positions, as one dispatch (inner ``lax.scan``), over the PAGED
    pool: the step the engine (``serve/batching.py``) runs.

    tokens [B] (each row's most recent token); pos [B] = next write
    index per row; active [B] bool — inactive rows still compute
    (static shapes) but their pos does not advance and their writes
    keep landing on the same parked cell, so they cannot corrupt
    anything. Rows read and write through ``block_tables`` [B, MB]
    into the shared pool ``caches`` = (k, v, k_scale, v_scale) with
    k/v [E, num_blocks, block_size, Hkv, hd] (int8 + bf16 scales
    [E, num_blocks, block_size, Hkv] when quantized — int8 KV halves
    the decode loop's dominant HBM stream; E as
    ``kv_pool.KVBlockPool`` defines it). The layers run
    ``config.loop_passes`` times over the same stacked weights
    (``looped_stack``: scopes ``loop_pass``, ``branch_norm``,
    ``exit_gate``), pass t, layer l on entry t * n_layers + l.

    Attention per layer is the gather-based
    ``ops.decode_attention.paged_decode_attention``: row b's logical
    view of positions [0, pos) is gathered out of the pool block by
    block and masked to its own length, so recycled-block garbage
    past the length contributes exactly 0; an int8 pool is read as
    int8. This step's own K/V row reaches attention as an operand:
    there is NO in-layer pool write (until PR 26 there was one, "so
    this step's attention sees the new row"; the chip's trace showed
    it copying the layer's whole pool slice, 2 x 75 MB in every layer
    of every step). The pool is written once a token, after the layer
    scan, through ``da.write_index`` — parked rows (inactive
    lanes) and overrun positions land in the scratch block, never in
    a block another request owns.

    ``view_blocks`` (static; None = the whole table): the dispatch
    reads and writes through the table's first ``view_blocks``
    columns only, cut here, inside the program. Everything below
    takes its shapes from the table (the scale views, the block
    gathers, the two products, the length mask), so the step is the
    same program, narrower: on the v5e the gathers and products over
    the padded view were 36 of a Mistral step's 46 ms at 256 columns
    (PERF.md). The caller (``serve/batching.py:_launch_dispatch``)
    picks the smallest prewarmed width
    (``decode_attention.view_widths``) that holds ``pos +
    num_steps`` of every ACTIVE row. A row past the width (one
    parked in prefill beside short decoding rows) is not read, its
    lane's output is discarded by the caller, and ``write_index``
    sends its write to the scratch block, as it does any position
    past a table's capacity.

    Multi-adapter serving (serve/adapters/): ``adapters`` is the
    resident set's stacked factor dict (leaves ``[L, C+1, ...]``,
    scanned with the layer stack) and ``adapter_idx`` [B] maps each
    row to its slot; row-gathered LoRA deltas attach to the q and v
    projections (``layer_head``). ``adapters=None`` (a
    distinct jit executable — None is an empty pytree) keeps the
    adapterless math byte-identical to before.

    ``sampling`` (ops/sampling/): None keeps the greedy argmax
    executable byte-identical; otherwise a dict of TRACED per-row
    knob arrays (``temps``/``top_ps``/``seeds`` [B]) plus the grammar
    mask table (``mask_table`` [M, V] bool, ``mask_idx`` [B] — row 0
    is all-allowed) and each step's next token is ``sample_rows``
    keyed ``(seed, position)``; ``temperature <= 0`` rows still
    reduce to the argmax.

    The ``jax.named_scope``s here and in the functions this calls
    (``qkv_proj``, ``lora_delta``, ``kv_write``, ``paged_gather``,
    ``kv_dequant``, ``decode_attention``, ``o_proj``, ``mlp``,
    ``sampler``; the verify and prefill twins carry the same) name
    the program's parts in ``op_name=`` of the compiled text, which
    is the only place the chip's trace lets them be looked up; they
    change HLO metadata and nothing else.

    A configuration with window AND global layers hands ``caches``
    and ``block_tables`` as dicts by kind of layer (``_by_kind``): a
    window layer reads ``da.window_view``'s columns of its table, a
    fixed width whatever the context, and masks by the window; a
    global layer reads the first ``view_blocks`` columns of its own.
    Scopes ``window_attention`` / ``global_attention`` there. A
    latent configuration (``config.kv_lora_rank``) hands one pool
    tuple ``(rows, None, None, None)``; its layer is ``latent_layer``
    below: the rows' latent view at the dispatch's width, attended
    ABSORBED (scope ``mla_absorbed_attention``), over a carry of
    residual streams.

    Returns (out_tokens [B, num_steps], caches, new_pos). A model
    with experts returns a fourth value, ``routed``, int32 [2,
    n_layers, experts held] (``_routed_sums``): the (row, expert)
    pairs routed to each expert held here, summed over the
    dispatch's steps and over ALL rows, parked ones too (static
    shapes: every lane computes), and the steps in which each expert
    got any. (A dense model's returns are as they were: the
    benchmark's own tests wrap this function by that arity.)
    """
    by_kind = _by_kind(config, caches)
    tables = dict(_by_kind(config, block_tables))
    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    b = tokens.shape[0]
    bs = block_size
    quantized = next(iter(by_kind.values()))[2] is not None  # static
    if view_blocks is not None:
        for kind in tables:
            if kind != 'window':    # a window layer's width is fixed
                tables[kind] = tables[kind][:, :view_blocks]

    # Flat [NB * bs, ...] pool views — write index math is 1-D
    # flat-slot; attention reads whole blocks (``_all_blocks``).
    flat, nbs = {}, {}
    for kind, group in by_kind.items():
        flat[kind], nbs[kind] = _flat_pools(config, kind, group, bs)
    # The kinds whose attention walks the pool and gathers no view.
    walks = {kind: da.walk_engages(
        bs, nkv, hd, codes=quantized, positions=1,
        window=config.sliding_window if kind == 'window' else None)
        for kind in flat}

    def one_token(carry, _):
        tok, pools, cur = carry
        angles = llama._rope_frequencies(
            config, cur)[:, None]                       # [B, 1, hd/2]
        x = streams(config, cparams['embed'][tok][:, None])
        if config.scale_embeddings:                     # [B, 1, D]
            x = x * jnp.asarray(math.sqrt(config.dim), x.dtype)
        widx, views, scale_views = {}, {}, {}
        for kind, (_, _, ks_all, vs_all) in pools.items():
            widx[kind] = da.write_index(tables[kind], cur, bs)  # [B]
            # What a layer of this kind reads: the table's columns
            # (cut above), or the window's (``da.window_view``).
            views[kind] = (tables[kind], None) if kind != 'window' \
                else da.window_view(tables[kind], cur,
                                    config.sliding_window, bs)
            scale_views[kind] = _scale_views(
                ks_all, vs_all, views[kind][0], bs, walks[kind])

        def latent_layer(xc, lp, entry, ad, kind='latent'):
            """A latent layer: every head scores the rows' one
            shared latent view, ABSORBED (``absorb_query`` before,
            ``value_up`` after ``da.latent_decode_attention``); this
            step's row an operand, written after the layer scan."""
            del ad
            q_nope, q_pe, rows, mix = latent_head(config, xc, lp,
                                                  angles)
            with jax.named_scope('mla_absorbed_attention'):
                view = da.latent_view(
                    _all_blocks(pools[kind][0], bs),
                    views[kind][0] + entry * nbs[kind])
                o_lat = da.latent_decode_attention(
                    absorb_query(config, q_nope[:, 0], lp),
                    q_pe[:, 0], view, cur,
                    llama.attention_scale(config), rows[:, 0])
                attn = value_up(config, o_lat, lp)
            xc, routed = latent_tail(
                config, xc, attn.reshape(b, 1, -1), lp, mix)
            return xc, ((rows[:, 0],), routed)

        def layer(xc, lp, entry, ad, kind=config.layer_kinds[0]):
            # ``entry``: this pass's and layer's KV entry (at one
            # pass, the layer) in its kind's group; ``ad`` is None
            # without adapters.
            q, _, _, rows = layer_head(
                config, xc, lp, ad, adapter_idx, angles, quantized,
                kind)
            # No in-layer write: the layer's pool slice is a scanned
            # input, so ``kc.at[widx].set`` copied the whole slice
            # (75 MB of K and of V at 4,561 blocks, every layer of
            # every step: 6 % of the step in PR 25's chip trace) for
            # B new rows. Attention takes this step's rows as an
            # operand beside the view of positions [0, cur); the one
            # merged scatter after the layer scan persists them.
            new = tuple(None if r is None else r[:, 0] for r in rows)
            kp_all, vp_all = pools[kind][:2]
            view, key_start = views[kind]
            ks_view, vs_view = (None, None) \
                if scale_views[kind] is None \
                else jax.lax.dynamic_index_in_dim(
                    scale_views[kind], entry, 0, keepdims=False,
                    allow_negative_indices=False)
            # A walk reads what a row's length says: a lane that
            # decodes nothing (free, or parked past the table while
            # its prompt is prefilled) reads nothing. Its output is
            # discarded under either form.
            seen = jnp.where(active, cur, 0) if walks[kind] else cur
            with _attention_scope(config, kind):
                attn = da.paged_decode_attention(
                    q[:, 0], _all_blocks(kp_all, bs),
                    _all_blocks(vp_all, bs), view + entry * nbs[kind],
                    seen, hd ** -0.5, k_scale=ks_view,
                    v_scale=vs_view, new=new,
                    **_window_args(config, kind, key_start)
                )[:, None]
            xc, routed = layer_tail(
                config, xc, attn.reshape(b, 1, nh * hd), lp)
            return xc, (new, routed)

        x, (rows, routed) = looped_stack(
            config, cparams, x,
            latent_layer if config.kv_lora_rank is not None
            else layer, adapters)
        # Persist the new rows: one merged scatter per token into the
        # carried (donated) flat pools.
        with jax.named_scope('kv_write'):
            new_pools = {
                kind: _write_rows(group, widx[kind],
                                  _kind_rows(config, rows, kind))
                for kind, group in pools.items()}
        logits = _head_logits(config, cparams, x)
        with jax.named_scope('sampler'):
            if sampling is None:
                nxt = logits[:, -1].argmax(-1).astype(jnp.int32)
            else:
                # Counter-keyed per-row sampling at position ``cur``
                # — the row's draw never depends on batch neighbors
                # (ops/sampling/prng.py batch-invariance contract).
                allowed = sample_lib.gather_masks(
                    sampling['mask_table'], sampling['mask_idx'])
                nxt = sample_lib.sample_rows(
                    logits[:, -1], sampling['temps'],
                    sampling['top_ps'], sampling['seeds'], cur,
                    allowed)
        # Inactive rows: hold the last token and do NOT advance, so
        # their next (scratch-redirected) write stays parked.
        nxt = jnp.where(active, nxt, tok)
        new_cur = jnp.where(active, cur + 1, cur)
        return (nxt, new_pools, new_cur), (nxt, routed)

    (tok, flat, pos), (toks, routed) = jax.lax.scan(
        one_token, (tokens, flat, pos), None, length=num_steps)
    out_caches = {kind: _unflat_pools(group, nbs[kind])
                  for kind, group in flat.items()}
    out = toks.swapaxes(0, 1), _as_given(caches, out_caches), pos
    return out if routed is None else out + (_routed_sums(routed),)


def _attention_scope(config: llama.LlamaConfig, kind: str):
    """``window_attention`` / ``global_attention`` round a layer's
    attention where a stack has window layers; no scope of its own
    otherwise (the programs of the other models keep their text)."""
    if config.sliding_window is None:
        return contextlib.nullcontext()
    return jax.named_scope(kind + '_attention')


def _window_args(config: llama.LlamaConfig, kind: str, key_start):
    if kind != 'window':
        return {}
    return {'window': config.sliding_window, 'key_start': key_start}


def _head_logits(config: llama.LlamaConfig, cparams: Params,
                 x: jax.Array) -> jax.Array:
    """The model's head on the normed state ``x`` [..., D]."""
    if config.tie_embeddings:
        return x @ llama.output_head(cparams, config)
    return _mm(x, cparams['lm_head'])


def _verify_forward(config: llama.LlamaConfig, cparams: Params,
                    tokens: jax.Array, flat, nbs, tables,
                    pos: jax.Array, n_real: jax.Array, width: int,
                    block_size: int, adapters=None, adapter_idx=None):
    """The layer stack of a VERIFY step over flat pools: ``tokens``
    [B, W] at positions pos[b] .. pos[b] + W - 1, the first
    ``n_real[b]`` real. What ``verify_step_paged`` and a round of
    ``mtp_rounds_paged`` share: ``flat`` / ``nbs`` / ``tables`` by
    kind of layer (``_flat_pools``, ``_by_kind``). Returns (the
    final-normed state [B, W, D], the flat pools with the window's
    rows written (padded lanes to scratch), the expert layers' tally
    [n_layers, held] or None).

    Two bodies, by the configuration's kind of cache. Keys and values
    in a pool: ``da.paged_decode_attention`` in its [B, W, ...] form
    with the intra-draft causal mask (query j attends [0, pos + j]).
    A latent configuration (``config.kv_lora_rank``): the rows'
    latent view attended ABSORBED at W query positions a row
    (``da.latent_verify_attention``, scope
    ``mla_absorbed_attention``), over a carry of residual streams.
    Either way there is no in-layer write: the window's own rows go
    to attention as an operand, causally among themselves, beside the
    view of positions [0, pos), and one merged scatter after the
    layer scan persists them. A padded lane's row is seen only by
    padded lanes, whose outputs are ignored."""
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    b = tokens.shape[0]
    bs = block_size
    latent = config.kv_lora_rank is not None
    quantized = next(iter(flat.values()))[2] is not None     # static

    # As in the decode twin: every entry's blocks as one pool read
    # through tables offset by entry * NB, and the scale views of
    # all entries gathered once, outside the layer scan (a group; a
    # window layer through ``da.window_view``'s columns).
    blocks, views, scale_views = {}, {}, {}
    for kind, (kp, vp, ksp, vsp) in flat.items():
        blocks[kind] = (_all_blocks(kp, bs),) if latent else \
            (_all_blocks(kp, bs), _all_blocks(vp, bs))
        views[kind] = (tables[kind], None) if kind != 'window' \
            else da.window_view(tables[kind], pos,
                                config.sliding_window, bs)
        scale_views[kind] = _scale_views(ksp, vsp, views[kind][0], bs)

    positions = pos[:, None] + jnp.arange(width,
                                          dtype=jnp.int32)[None, :]
    angles = llama._rope_frequencies(
        config, positions.reshape(-1)).reshape(b, width, -1)
    x = streams(config, cparams['embed'][tokens])      # [B, W, D]
    if config.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(config.dim), x.dtype)
    wflat = {kind: da.verify_write_indices(
        tables[kind], pos, n_real, width, block_size).reshape(-1)
        for kind in flat}                          # [B * W] a group

    def latent_layer(xc, lp, entry, ad, kind='latent'):
        del ad
        q_nope, q_pe, rows, mix = latent_head(config, xc, lp, angles)
        with jax.named_scope('mla_absorbed_attention'):
            view = da.latent_view(
                blocks[kind][0], views[kind][0] + entry * nbs[kind])
            o_lat = da.latent_verify_attention(
                absorb_query(config, q_nope, lp), q_pe, view, pos,
                llama.attention_scale(config), rows)
            attn = value_up(config, o_lat, lp)
        xc, routed = latent_tail(
            config, xc, attn.reshape(b, width, -1), lp, mix)
        return xc, ((rows.reshape(b * width, -1),), routed)

    def layer(xc, lp, entry, ad, kind=config.layer_kinds[0]):
        q, _, _, (k_rows, v_rows, ks_rows, vs_rows) = layer_head(
            config, xc, lp, ad, adapter_idx, angles, quantized, kind)
        view, key_start = views[kind]
        ks_view, vs_view = (None, None) if scale_views[kind] is None \
            else jax.lax.dynamic_index_in_dim(
                scale_views[kind], entry, 0, keepdims=False,
                allow_negative_indices=False)
        with _attention_scope(config, kind):
            attn = da.paged_decode_attention(
                q, *blocks[kind], view + entry * nbs[kind], pos,
                hd ** -0.5, k_scale=ks_view, v_scale=vs_view,
                new=(k_rows, v_rows, ks_rows, vs_rows),
                **_window_args(config, kind, key_start)
            )                                      # [B, W, Hq, hd]
        xc, routed = layer_tail(
            config, xc, attn.reshape(b, width, nh * hd), lp)
        rows = (k_rows.reshape(b * width, nkv, hd),
                v_rows.reshape(b * width, nkv, hd))
        if quantized:
            rows += (ks_rows.reshape(b * width, nkv),
                     vs_rows.reshape(b * width, nkv))
        return xc, (rows, routed)

    x, (rows, routed) = looped_stack(
        config, cparams, x, latent_layer if latent else layer,
        adapters)
    with jax.named_scope('kv_write'):
        flat = {kind: _write_rows(group, wflat[kind],
                                  _kind_rows(config, rows, kind))
                for kind, group in flat.items()}
    return x, flat, routed


def verify_step_paged(params: Params, tokens: jax.Array,
                      caches, block_tables: jax.Array,
                      pos: jax.Array, n_real: jax.Array,
                      config: llama.LlamaConfig,
                      width: int, block_size: int,
                      adapters=None, adapter_idx=None,
                      sampling=None):
    """Batched multi-token VERIFY forward — the speculative twin of
    ``decode_steps_paged``: instead of scanning ``num_steps`` single
    tokens, ONE forward carries ``width`` = draft_k + 1 query
    positions per row (the row's current token at ``pos[b]`` plus
    its drafted continuation), so one weight read amortizes over up
    to width accepted-and-emitted tokens — the bandwidth-bound
    decode fix.

    tokens [B, W] (row b's positions pos[b]..pos[b]+W-1, only the
    first n_real[b] real — padded lanes write scratch and their
    outputs are ignored); caches/block_tables as in
    ``decode_steps_paged``. Drafted K/V is written into the row's
    blocks UP FRONT (one merged scatter after the layer scan; within
    the forward the window's rows reach attention as an operand, as
    in the decode twin); a rejection later simply rolls the
    host-side ``pos`` back so the stale rows are never attended
    again — no block copying,
    no scatter-undo (the length-masked paged attention makes
    abandoning them free). The layer stack is ``_verify_forward``,
    with a body for pools of keys and values and one for a latent
    pool.

    Returns (preds [B, W] int32, accepted [B] int32, new_pos [B],
    new_tokens [B], caches): ``preds[b, j]`` is the target model's
    token realization after position pos[b]+j — the argmax when
    ``sampling`` is None, else ``sample_lib.verify_targets``'s
    counter-keyed draw with the SAME key plain decode would use at
    that position (``sampling`` also carries per-position grammar
    masks, table [M, W, V] gathered by traced index). ``accepted``
    is ``accept_tokens``'s per-row count (ops/sampling/accept.py
    — the ONE acceptance implementation: the Chen et al. rejection
    rule realized by maximal coupling, traced here so the
    pos/tokens commit costs no extra host round-trips);
    ``new_pos``/``new_tokens`` carry the committed frontier — pos
    advances by accepted+1 for live rows (the ROLLBACK: rejected
    positions simply stay past the new frontier) and parked rows
    (n_real 0) are untouched.
    """
    by_kind = _by_kind(config, caches)
    tables = _by_kind(config, block_tables)
    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)
    flat, nbs = {}, {}
    for kind, group in by_kind.items():
        flat[kind], nbs[kind] = _flat_pools(config, kind, group,
                                            block_size)
    x, flat, _ = _verify_forward(
        config, cparams, tokens, flat, nbs, tables, pos, n_real,
        width, block_size, adapters, adapter_idx)
    logits = _head_logits(config, cparams, x)
    preds, accepted, new_pos, new_tok = _verify_commit(
        tokens, logits, pos, n_real, sampling)
    out_caches = {kind: _unflat_pools(group, nbs[kind])
                  for kind, group in flat.items()}
    return (preds, accepted, new_pos, new_tok,
            _as_given(caches, out_caches))


def _verify_commit(tokens: jax.Array, logits: jax.Array,
                   pos: jax.Array, n_real: jax.Array, sampling):
    """The target's realizations at a verify window's positions, how
    many drafts each row keeps, and the committed frontier:
    (preds [B, W], accepted [B], new_pos [B], new_tok [B])."""
    with jax.named_scope('sampler'):
        if sampling is None:
            preds = logits.argmax(-1).astype(jnp.int32)   # [B, W]
        else:
            # Target realizations drawn with the keys plain decode
            # would use at each position — the maximal-coupling half
            # of the speculative-sampling rule
            # (ops/sampling/accept.py).
            allowed = None if 'mask_table' not in sampling else \
                sample_lib.gather_masks(sampling['mask_table'],
                                        sampling['mask_idx'])
            preds = sample_lib.verify_targets(
                logits, sampling['temps'], sampling['top_ps'],
                sampling['seeds'], pos, allowed)          # [B, W]
        accepted = accept_tokens(tokens, preds, n_real)   # [B]
    live = n_real > 0
    new_pos = jnp.where(live, pos + accepted + 1, pos)
    new_tok = jnp.where(
        live,
        jnp.take_along_axis(preds, accepted[:, None], axis=1)[:, 0],
        tokens[:, 0])
    return preds, accepted, new_pos, new_tok


# ---------------------------------------------------------------------
# The next-token-prediction module as the engine's drafter
# ---------------------------------------------------------------------


def mtp_module(config: llama.LlamaConfig, cparams: Params,
               h: jax.Array, tokens: jax.Array, angles: jax.Array,
               attend):
    """The model's next-token-prediction module (DeepSeek-V3 report,
    section 2.2, depth 1) up to its last norm. For a position i with
    the main stack's final-normed state h_i and the NEXT token
    t_{i+1}:

        x_i = W_eh [ RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i) ]
        y_i = Layer(x_i; RoPE position i, its own latent rows 0..i)
        l'_i = Head(RMSNorm_s(y_i))        (``mtp_logits``)

    l'_i predicts t_{i+2}. Emb and Head are the main model's; Layer
    is one expert layer in the main layers' form (``latent_head``,
    ``latent_tail``) on the leaves ``cparams['mtp']['layers']``, with
    the latent group's LAST cache entry as its own. ``h`` [B, T, D],
    ``tokens`` [B, T] (pair by pair), ``angles`` of the pairs' RoPE
    positions; ``attend(q_nope, q_pe, rows, lp) -> [B, T, H * vd]``
    is the calling body's attention over that entry (absorbed in a
    round, expanded in a prefill chunk). Returns (y [B, T, D] ahead
    of the last norm, the pairs' latent rows [B, T, W], the expert
    layer's tally [held]). Scopes ``mtp_eh_proj``, ``mtp_layer``."""
    mp = cparams['mtp']
    lp = jax.tree.map(lambda a: a[0], mp['layers'])
    with jax.named_scope('mtp_eh_proj'):
        pair = jnp.concatenate(
            [llama.norm(config, cparams['embed'][tokens], mp['enorm']),
             llama.norm(config, h, mp['hnorm'])], axis=-1)
        x = _mm(pair, mp['eh_proj'])
    with jax.named_scope('mtp_layer'):
        q_nope, q_pe, rows, mix = latent_head(config, x, lp, angles)
        attn = attend(q_nope, q_pe, rows, lp)
        y, routed = latent_tail(config, x, attn, lp, mix)
    return y, rows, routed


def mtp_logits(config: llama.LlamaConfig, cparams: Params,
               y: jax.Array) -> jax.Array:
    """l' = Head(RMSNorm_s(y)): the module's logits through the main
    model's head (scope ``mtp_head``)."""
    with jax.named_scope('mtp_head'):
        return _head_logits(
            config, cparams,
            llama.norm(config, y, cparams['mtp']['final_norm']))


def _mtp_step(config: llama.LlamaConfig, cparams: Params,
              h: jax.Array, tokens: jax.Array, pos: jax.Array,
              n_real: jax.Array, flat, nb: int, table: jax.Array,
              block_size: int):
    """The module over the pairs a commit completed, through the
    cache: pair j of row b is (h[b, j], tokens[b, j]) at RoPE position
    pos[b] + j, the first ``n_real[b]`` real. A pair's latent row
    lies in the module's entry at the slot of the token it embeds,
    pos + j + 1 (slot 0 stays empty and masked: a block's rows then
    depend on tokens up to its own end only, which is what lets a
    shared prefix block carry them, ``forward_paged``). Attention is
    ABSORBED over slots [1, pos + 1) of the rows' view and the pairs'
    own rows, causally. ``flat`` the latent group's flat pool tuple,
    ``table`` [B, MB] (cut to the dispatch's width by the caller).
    Returns (y [B, T, D], flat with the real pairs' rows written,
    the tally [held])."""
    b, t = tokens.shape
    entry = config.kv_entries - 1
    positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    angles = llama._rope_frequencies(
        config, positions.reshape(-1)).reshape(b, t, -1)

    def attend(q_nope, q_pe, rows, lp):
        with jax.named_scope('mla_absorbed_attention'):
            view = da.latent_view(_all_blocks(flat[0], block_size),
                                  table + entry * nb)
            o_lat = da.latent_verify_attention(
                absorb_query(config, q_nope, lp), q_pe, view, pos + 1,
                llama.attention_scale(config), rows, first=1)
            return value_up(config, o_lat, lp).reshape(b, t, -1)

    y, rows, routed = mtp_module(config, cparams, h, tokens, angles,
                                 attend)
    widx = da.verify_write_indices(table, pos + 1, n_real, t,
                                   block_size).reshape(-1)
    with jax.named_scope('kv_write'):
        flat = (_write_latent_rows(
            flat[0], (entry, widx, rows.reshape(1, b * t, -1))),
        ) + flat[1:]
    return y, flat, routed


def _draft_tokens(logits: jax.Array, sampling, positions: jax.Array
                  ) -> jax.Array:
    """The module's draft for the token after each row's frontier:
    drawn from ITS logits with the counter key the target will use
    there, ``(seed, positions[b])`` (``positions``: where the
    committed last token stands, whose main logits decide that
    token). ``jax.random.categorical`` is the argmax of logits / T +
    Gumbel noise of the key, so draft and target share the noise and
    agree wherever the noise decides: on independent unit logits
    over 129,280 ids at T = 1, four times in ten (PERF.md section 6,
    PR 43). Greedy rows, and ``sampling`` None, take the argmax."""
    with jax.named_scope('mtp_draft_sample'):
        if sampling is None:
            return logits.argmax(-1).astype(jnp.int32)
        return sample_lib.sample_rows(
            logits, sampling['temps'], sampling['top_ps'],
            sampling['seeds'], positions)


def mtp_rounds_paged(params: Params, tokens: jax.Array,
                     drafts: jax.Array, caches,
                     block_tables: jax.Array, pos: jax.Array,
                     active: jax.Array, grant: jax.Array,
                     config: llama.LlamaConfig, num_rounds: int,
                     block_size: int, sampling=None, *,
                     view_blocks: Optional[int] = None):
    """``num_rounds`` drafting ROUNDS for every row, as one dispatch
    (a ``lax.scan``, as ``decode_steps_paged`` scans steps): what the
    engine runs in place of the decode scan where the model's own
    next-token-prediction module is the drafter
    (``config.nextn_layers``; ``speculative='mtp'``).

    A row's state: ``pos``, its committed last token ``tokens[b]``
    and one pending draft ``drafts[b]`` of the token after it. A
    round, for every ``active`` row:

    1. the main stack on ``[token, draft]`` at positions pos, pos + 1
       through the latent verify body (``_verify_forward``: both
       rows written up front; a row without ``grant`` verifies its
       token alone, the draft lane padded);
    2. ``preds`` = the target's realizations there
       (``_verify_commit``: ``sample.verify_targets`` and the ONE
       acceptance rule), ``accepted`` 0 or 1, and ``accepted + 1``
       tokens are committed: ``pos += accepted + 1``;
    3. the module on the one or two pairs the commit completed,
       ``(h_pos, preds[0])`` and, if the draft was kept, ``(h_pos+1,
       preds[1])`` (``_mtp_step``: its rows into the last entry);
    4. the next draft from the module's logits at the last pair
       (``_draft_tokens``), keyed as the target will be.

    A rejected draft leaves no trace: its main row lies past the
    frontier and is overwritten by the next round's, and its pair was
    never run. ``sampling``: None (every row greedy), or the per-row
    knob arrays of ``decode_steps_paged`` without the grammar table
    (a draft under a grammar mask is not implemented; the engine
    refuses constrained requests on this path). ``view_blocks`` as
    there: the width has to hold ``pos + 2 * num_rounds + 1`` of
    every active row.

    Returns (out_tokens [B, num_rounds, 2], counts [B, num_rounds]:
    the tokens each round committed, 0 for an inactive row; caches;
    new_pos; new tokens [B]; new drafts [B]; routed int32 [2,
    n_layers + 1, held] as ``decode_steps_paged`` gives it, the
    module's layer last, every round carrying 2 B lanes)."""
    if not config.nextn_layers:
        raise exceptions.NotSupportedError(
            f'{config.name!r} has no next-token-prediction module '
            f'(nextn_layers) to draft with')
    kind = config.layer_kinds[0]
    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)
    table = block_tables if view_blocks is None \
        else block_tables[:, :view_blocks]
    flat, nb = _flat_pools(config, kind, caches, block_size)
    n_real = jnp.where(active, jnp.where(grant, 2, 1), 0
                       ).astype(jnp.int32)

    def one_round(carry, _):
        tok, draft, pools, cur = carry
        pair = jnp.stack([tok, draft], axis=1)               # [B, 2]
        h, pools, routed = _verify_forward(
            config, cparams, pair, {kind: pools}, {kind: nb},
            {kind: table}, cur, n_real, 2, block_size)
        preds, accepted, new_cur, new_tok = _verify_commit(
            pair, _head_logits(config, cparams, h), cur, n_real,
            sampling)
        count = jnp.where(active, accepted + 1, 0)
        y, pools, m_routed = _mtp_step(
            config, cparams, h, preds, cur, count, pools[kind], nb,
            table, block_size)
        y_last = jnp.take_along_axis(
            y, accepted[:, None, None], axis=1)[:, 0]       # [B, D]
        new_draft = _draft_tokens(
            mtp_logits(config, cparams, y_last), sampling, new_cur)
        new_draft = jnp.where(active, new_draft, draft)
        tally = jnp.concatenate([routed, m_routed[None]])
        return (new_tok, new_draft, pools, new_cur), \
            (preds, count, tally)

    (tok, draft, flat, pos), (toks, counts, routed) = jax.lax.scan(
        one_round, (tokens, drafts, flat, pos), None,
        length=num_rounds)
    return (toks.swapaxes(0, 1), counts.swapaxes(0, 1),
            _unflat_pools(flat, nb), pos, tok, draft,
            _routed_sums(routed))


def mtp_first_paged(params: Params, h_last: jax.Array,
                    token: jax.Array, caches, block_row: jax.Array,
                    pos: jax.Array, config: llama.LlamaConfig,
                    block_size: int, temperature: jax.Array,
                    top_p: jax.Array, seed: jax.Array):
    """A request's FIRST draft, once its first token is known: the
    module on the pair (``h_last`` [1, 1, D], the final-normed state
    of the last prompt position ``pos``; ``token``, the first
    generated token), its row into slot pos + 1 of the module's
    entry, and the draft of the token after, drawn with key ``(seed,
    pos + 1)`` (``_draft_tokens``; temperature 0 is the argmax). The
    prefill chunks wrote the module's rows of the prompt
    (``forward_paged``); this is the one pair that needs a token the
    prefill had not drawn yet. Returns (draft int32 scalar, caches,
    routed)."""
    kind = config.layer_kinds[0]
    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)
    flat, nb = _flat_pools(config, kind, caches, block_size)
    at = jnp.reshape(pos, (1,)).astype(jnp.int32)
    y, flat, routed = _mtp_step(
        config, cparams, h_last, jnp.reshape(token, (1, 1)), at,
        jnp.ones((1,), jnp.int32), flat, nb, block_row[None],
        block_size)
    sampling = {'temps': jnp.reshape(temperature, (1,)),
                'top_ps': jnp.reshape(top_p, (1,)),
                'seeds': jnp.reshape(seed, (1,))}
    draft = _draft_tokens(mtp_logits(config, cparams, y[:, 0]),
                          sampling, at + 1)
    return draft[0], _unflat_pools(flat, nb), \
        _routed_sums(routed[None, None])


def decode_shardings(config: llama.LlamaConfig, mesh,
                     shard_batch: bool = True,
                     kv_int8: bool = False):
    """(param_shardings, cache_shardings) for sharded serving on a
    mesh — models too big for one chip decode tensor-parallel: params
    follow ``llama.param_sharding_rules`` (heads/ffn over 'tp',
    ZeRO-style over the fsdp group), the KV cache shards its KV-head
    axis over 'tp' and — with ``shard_batch`` — batch over the data
    axes (pass False when the serving batch is smaller than the
    data-parallel degree, e.g. single-request replicas). GSPMD
    propagates the activation shardings; the per-layer all-reduces
    ride ICI exactly as in training."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from skypilot_tpu.parallel.train import sharding_tree

    rules = llama.param_sharding_rules(config)
    param_sh = sharding_tree(rules, mesh)
    batch_axes = ('dp', 'fsdp', 'ep') if shard_batch else None
    kv_spec = NamedSharding(mesh, P(None, batch_axes, None, 'tp',
                                    None))
    scale_spec = NamedSharding(mesh, P(None, batch_axes, None,
                                       'tp')) if kv_int8 else None
    cache_sh = KVCache(k=kv_spec, v=kv_spec,
                       pos=NamedSharding(mesh, P()),
                       k_scale=scale_spec, v_scale=scale_spec)
    return param_sh, cache_sh


def decode_tokens_scan(params: Params, first: jax.Array,
                       cache: KVCache, config: llama.LlamaConfig,
                       num_tokens: int) -> Tuple[jax.Array, KVCache]:
    """Greedy-decode ``num_tokens`` further tokens ENTIRELY on device:
    a single ``lax.scan`` carries (token, cache), so one dispatch
    serves the whole generation. This is the serving hot loop — the
    Python-loop ``greedy_generate`` pays a host round-trip per token,
    on top of the weight-read time of each decode step.

    first: [B] the most recent token per row. Returns
    ([B, num_tokens] generated ids, final cache).
    """

    def body(carry, _):
        tok, kv = carry
        logits, kv = forward_cached(params, tok[:, None], kv, config)
        nxt = logits[:, -1].argmax(-1).astype(jnp.int32)
        return (nxt, kv), nxt

    (_, cache), toks = jax.lax.scan(body, (first, cache), None,
                                    length=num_tokens)
    return toks.swapaxes(0, 1), cache


def _slice_cache(cache: KVCache, window: int) -> KVCache:
    """View of the first ``window`` positions (static size)."""
    return KVCache(
        k=jax.lax.slice_in_dim(cache.k, 0, window, axis=2),
        v=jax.lax.slice_in_dim(cache.v, 0, window, axis=2),
        pos=cache.pos,
        k_scale=(None if cache.k_scale is None else
                 jax.lax.slice_in_dim(cache.k_scale, 0, window,
                                      axis=2)),
        v_scale=(None if cache.v_scale is None else
                 jax.lax.slice_in_dim(cache.v_scale, 0, window,
                                      axis=2)))


def _unslice_cache(full: KVCache, win: KVCache) -> KVCache:
    """Write the window back into the (donated) full cache."""
    zeros5 = (0, 0, 0, 0, 0)
    return KVCache(
        k=jax.lax.dynamic_update_slice(full.k, win.k, zeros5),
        v=jax.lax.dynamic_update_slice(full.v, win.v, zeros5),
        pos=win.pos,
        k_scale=(None if full.k_scale is None else
                 jax.lax.dynamic_update_slice(full.k_scale,
                                              win.k_scale,
                                              (0, 0, 0, 0))),
        v_scale=(None if full.v_scale is None else
                 jax.lax.dynamic_update_slice(full.v_scale,
                                              win.v_scale,
                                              (0, 0, 0, 0))))


def _decode_segment(params: Params, first: jax.Array, cache: KVCache,
                    config: llama.LlamaConfig, n: int, window: int
                    ) -> Tuple[jax.Array, KVCache]:
    """``n`` greedy steps reading only the first ``window`` cache
    rows (one scan dispatch). The window slice-in/out costs two
    window-sized copies per SEGMENT, amortized over its n tokens."""
    win = _slice_cache(cache, window)
    toks, win = decode_tokens_scan(params, first, win, config, n)
    return toks, _unslice_cache(cache, win)


_decode_segment_jit = jax.jit(_decode_segment,
                              static_argnums=(3, 4, 5),
                              donate_argnums=(2,))


def decode_tokens_windowed(params: Params, first: jax.Array,
                           cache: KVCache,
                           config: llama.LlamaConfig,
                           num_tokens: int, start_pos: int,
                           window_block: int = 512
                           ) -> Tuple[jax.Array, KVCache]:
    """Greedy decode with LENGTH-AWARE cache reads: generation is cut
    into segments, each compiled with a STATIC window = the valid
    prefix rounded up to ``window_block`` — so decode attention (and
    the int8 dequant feeding it) streams only ~the written rows from
    HBM instead of all ``max_seq`` (r4 perf notes: the dense cache
    read over max_seq was a named serving wall; a traced-length slice
    inside one jit is impossible under XLA's static shapes, so the
    segmentation carries the length STATICALLY).

    ``start_pos``: positions already in the cache (a static Python
    int — callers know their prompt length). Executable count stays
    tiny: one per distinct (segment_len, window), both multiples of
    ``window_block`` after the first segment.
    """
    max_seq = cache.k.shape[2]
    assert start_pos + num_tokens <= max_seq, (start_pos, num_tokens,
                                               max_seq)
    outs = []
    done = 0
    while done < num_tokens:
        written = start_pos + done
        window = min(max_seq,
                     -(-(written + 1) // window_block) * window_block)
        n = min(num_tokens - done, window - written)
        toks, cache = _decode_segment_jit(params, first, cache,
                                          config, n, window)
        first = toks[:, -1]
        outs.append(toks)
        done += n
    return jnp.concatenate(outs, axis=1), cache


def _filter_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k highest logits per row (static k), -inf the rest."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, _NEG_INF, logits)


def _filter_top_p(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """Nucleus filtering with a DYNAMIC top_p (no recompile per
    request): keep the smallest prefix of the descending-prob order
    whose cumulative probability reaches top_p. The top-1 token is
    always kept (top_p is clamped above 0, so the first token's
    zero preceding mass never reaches it)."""
    top_p = jnp.maximum(jnp.asarray(top_p, jnp.float32), 1e-6)
    sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A token is OUTSIDE the nucleus if the cumulative mass before it
    # already reached top_p.
    outside = (cum - probs) >= top_p
    kth = jnp.where(outside, jnp.inf, sorted_desc).min(-1,
                                                      keepdims=True)
    return jnp.where(logits < kth, _NEG_INF, logits)


def sample_token(logits: jax.Array, key: jax.Array,
                 temperature: jax.Array, top_k: int = 0,
                 top_p: Optional[jax.Array] = None) -> jax.Array:
    """Sample next ids from [B, V] logits. ``temperature``/``top_p``
    are dynamic (traced) so one executable serves every request;
    ``top_k`` is static (0 = off). temperature == 0 -> greedy."""
    filtered = logits.astype(jnp.float32)
    if top_k:
        filtered = _filter_top_k(filtered, top_k)
    if top_p is not None:
        filtered = _filter_top_p(filtered, top_p)
    t_safe = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    sampled = jax.random.categorical(key, filtered / t_safe, axis=-1)
    greedy = logits.argmax(-1)
    return jnp.where(temperature <= 0.0, greedy,
                     sampled).astype(jnp.int32)


def sample_tokens_scan(params: Params, first: jax.Array,
                       cache: KVCache, config: llama.LlamaConfig,
                       num_tokens: int, key: jax.Array,
                       temperature: jax.Array, top_k: int = 0,
                       top_p: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, KVCache]:
    """Sampling analog of ``decode_tokens_scan`` — the whole
    generation is one device-side dispatch; the PRNG key splits per
    step inside the scan."""

    def body(carry, _):
        tok, kv, k_ = carry
        k_, sub = jax.random.split(k_)
        logits, kv = forward_cached(params, tok[:, None], kv, config)
        nxt = sample_token(logits[:, -1], sub, temperature,
                           top_k=top_k, top_p=top_p)
        return (nxt, kv, k_), nxt

    (_, cache, _), toks = jax.lax.scan(body, (first, cache, key),
                                       None, length=num_tokens)
    return toks.swapaxes(0, 1), cache


def sample_generate(params: Params, prompt: jax.Array,
                    config: llama.LlamaConfig, max_new_tokens: int,
                    key: jax.Array, temperature: float = 1.0,
                    top_k: int = 0,
                    top_p: Optional[float] = None,
                    max_seq: Optional[int] = None,
                    cache_sharding: Optional[KVCache] = None,
                    kv_int8: bool = False
                    ) -> jax.Array:
    """Sampled generation: prefill once, then one scan dispatch.
    temperature/top_p are passed as arrays so distinct request values
    reuse one compiled executable. prompt [B, T0] ->
    [B, max_new_tokens]."""
    max_seq = max_seq or config.max_seq_len
    b, t0 = prompt.shape
    assert t0 + max_new_tokens <= max_seq, (t0, max_new_tokens,
                                            max_seq)
    if max_new_tokens <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    cache = init_cache(config, b, max_seq, kv_int8=kv_int8)
    if cache_sharding is not None:
        cache = jax.device_put(cache, cache_sharding)
    temp = jnp.asarray(temperature, jnp.float32)
    # top_p=None skips the nucleus filter entirely — a full-vocab
    # sort per generated token is not free, so don't run it as a
    # mathematical no-op.
    p = None if top_p is None else jnp.asarray(top_p, jnp.float32)

    step = jax.jit(forward_cached, static_argnums=(3, 4, 5),
                   donate_argnums=(2,))
    logits, cache = step(params, prompt, cache, config, True, True)
    key, sub = jax.random.split(key)
    nxt = sample_token(logits[:, -1], sub, temp, top_k=top_k, top_p=p)
    if max_new_tokens == 1:
        return nxt[:, None]
    scan_fn = jax.jit(sample_tokens_scan, static_argnums=(3, 4, 7),
                      donate_argnums=(2,))
    toks, _ = scan_fn(params, nxt, cache, config, max_new_tokens - 1,
                      key, temp, top_k, p)
    return jnp.concatenate([nxt[:, None], toks], axis=1)


def greedy_generate(params: Params, prompt: jax.Array,
                    config: llama.LlamaConfig, max_new_tokens: int,
                    max_seq: Optional[int] = None,
                    eos_id: Optional[int] = None,
                    cache_sharding: Optional[KVCache] = None,
                    kv_int8: bool = False
                    ) -> jax.Array:
    """Greedy decode: prefill the prompt once, then one cached step
    per token. prompt: [B, T0] -> [B, <=max_new_tokens] generated ids
    (rows that hit ``eos_id`` are padded with it thereafter).

    One jitted callable serves both phases — jit caches one
    executable per distinct T (the T0-length prefill and the shared
    T=1 decode step); the cache buffers are donated so generation
    runs in-place in HBM. ``cache_sharding``: a KVCache of
    NamedShardings (``decode_shardings``) pinning the cache layout
    for tensor-parallel serving.
    """
    max_seq = max_seq or config.max_seq_len
    b, t0 = prompt.shape
    assert t0 + max_new_tokens <= max_seq, (t0, max_new_tokens,
                                            max_seq)
    if max_new_tokens <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    cache = init_cache(config, b, max_seq, kv_int8=kv_int8)
    if cache_sharding is not None:
        cache = jax.device_put(cache, cache_sharding)

    step = jax.jit(forward_cached, static_argnums=(3, 4, 5),
                   donate_argnums=(2,))

    logits, cache = step(params, prompt, cache, config, True, True)
    nxt = logits[:, -1].argmax(-1).astype(jnp.int32)
    if eos_id is None:
        # No early exit wanted: run the whole generation as one
        # device-side scan (one dispatch instead of one per token).
        scan_fn = jax.jit(decode_tokens_scan, static_argnums=(3, 4),
                          donate_argnums=(2,))
        toks, _ = scan_fn(params, nxt, cache, config,
                          max_new_tokens - 1)
        return jnp.concatenate([nxt[:, None], toks], axis=1)
    done = nxt == eos_id
    out = [nxt]
    for _ in range(max_new_tokens - 1):
        if eos_id is not None and bool(done.all()):
            break
        logits, cache = step(params, nxt[:, None], cache, config,
                             True)
        nxt = logits[:, -1].argmax(-1).astype(jnp.int32)
        if eos_id is not None:
            # Per-row: once a row emitted EOS it keeps emitting EOS.
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        out.append(nxt)
    return jnp.stack(out, axis=1)
