"""Length-aware decode attention + per-row cache writes (Pallas).

The serving decode hot loop previously attended with a dense masked
einsum over the FULL static cache (``models/decode.py``
``_masked_attention`` / ``serve/batching.py`` ``_attend_rows``): every
generated token read all ``[B, S, Hkv, hd]`` of K and V from HBM and
multiplied most of it by a -inf mask. At S >= 4k batched decode that
masked junk dominates HBM traffic — decode is bandwidth-bound, so it
directly sets TPOT.

This module provides length-aware Pallas alternatives (the reference
delegates serving to vLLM/JetStream, whose paged/flash decode kernels
play this role — ``llm/vllm/service.yaml``). NOTE: on the v5e used
for this repo's benches, XLA's dense path won (see ``_use_pallas``);
the kernels are opt-in via SKYTPU_PALLAS_DECODE=1 and the shipped
serving bandwidth fix is the int8 KV cache (models/decode.py). Both
kernels remain correctness-tested:

- ``decode_attention(q, k, v, lengths)``: a Pallas kernel that
  streams ONLY the valid prefix of each row's cache HBM->VMEM with
  double-buffered async DMA, chunk by chunk (flash-style online
  softmax across chunks), skipping every block past ``lengths[b]``.
  HBM reads scale with the ACTUAL context length, not the cache
  allocation.
- ``cache_write(k_cache, v_cache, k_new, v_new, pos)``: per-row
  scatter of one new K/V position. The previous one-hot
  ``jnp.where`` write (the "JetStream trick" to avoid XLA's scalar
  scatter) rewrote the entire cache every layer — a second full
  bandwidth pass; the Pallas version DMAs exactly one [Hkv*hd] row
  per batch element in place (input/output aliased).

Mosaic alignment note: head_dim is 64 for 1B-class models, and VMEM
lane tiling is 128 — per-head lane slices would be unaligned. The
kernel therefore works on the flattened ``[S, Hkv*hd]`` cache view
(lane dim 512+, aligned) with a BLOCK-DIAGONAL query matrix
``[Hq, Hkv*hd]`` built outside the kernel: ``q_bd @ k_flat.T`` is
exactly the per-head dot (zeros mask the foreign heads), and the
``p @ v_flat`` accumulator carries every head's value block, from
which the caller gathers each query head's own block. The extra MXU
flops are ~Hkv x, but decode attention is HBM-bound — the MXU is
idle either way, and no lane dim is ever sliced.

Both entry points fall back to dense jnp references off-TPU (CPU
tests, virtual meshes) and are numerically tested against them.

The engine's own decode and verify steps go through
``paged_decode_attention`` -> ``view_attention`` (plain XLA, one
compiled program): the int8 pool is gathered block by block and read
AS int8 — codes converted inside the two dots, the K scale applied
to the scores and the V scale to the probabilities, this step's own
row an operand, not a pool write. What the v5e's trace showed of the
form before (PR 25: per-position gathers at 385 GB/s, a bf16 copy of
the whole padded K and V view, a copy of the layer's pool slice for
B new rows; 113.8 ms a step at 24 rows x 4,096) and of this one (PR
26: 48 ms) is in PERF.md. It is still dense over the table width: a
kernel that walks each row's own blocks is what is left (ROADMAP
S2).
"""
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

# KV positions streamed per DMA chunk. 512 keeps the double-buffered
# scratch at 512*Hkv*hd*2B*2bufs*2(k,v) — ~2 MB for 1B-class models —
# well inside a v5e core's ~16 MB more VMEM budget.
_BLOCK_S = 512

# Aligned read-modify-write window (rows) for the cache-write kernel:
# Mosaic requires HBM sublane slices aligned to the memref tiling.
_WRITE_WIN = 8


def _use_pallas(which: str = '') -> bool:
    """Opt-in (SKYTPU_PALLAS_DECODE=1), and only on TPU.

    Measured on v5e (llama3.2-1b, B=16, S=4608, decode): the XLA
    dense masked path sustains ~400 GB/s and 24.8 ms TPOT; these
    kernels measured 26.8-30.8 ms — per-grid-step overhead exceeded
    the bandwidth saved, at every occupancy tested. They stay
    correctness-tested (interpret + on-chip token equality) for
    hardware/toolchains where the tradeoff flips; the default serve
    bandwidth win is the int8 KV cache instead (models/decode.py).
    """
    import os
    if os.environ.get('SKYTPU_PALLAS_DECODE') != '1':
        return False
    if which and os.environ.get(f'SKYTPU_NO_PALLAS_{which}') == '1':
        return False  # per-kernel kill-switch (ATTN / WRITE)
    # No except: a backend that cannot start is JAX's error to raise,
    # not a reason to answer "not on TPU".
    return jax.default_backend() == 'tpu'


# ---------------------------------------------------------------------
# Reference paths (CPU / tests / non-TPU backends)
# ---------------------------------------------------------------------


def _reference_decode_attention(q, k, v, lengths, scale):
    """q [B, Hq, hd]; k/v [B, S, Hkv, hd]; lengths [B] — row b
    attends keys [0, lengths[b])."""
    b, hq, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, hkv, groups, hd)
    logits = jnp.einsum('bhgd,bshd->bhgs', qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s)[None, :] < lengths[:, None]      # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhgs,bshd->bhgd', probs.astype(v.dtype), v)
    return out.reshape(b, hq, hd)


def _reference_cache_write(k_cache, v_cache, k_new, v_new, pos):
    """One-hot full-cache write (reads+writes the whole cache; kept
    as the off-TPU fallback)."""
    hit = jnp.arange(k_cache.shape[1])[None, :] == pos[:, None]
    k_cache = jnp.where(hit[:, :, None, None], k_new[:, None],
                        k_cache)
    v_cache = jnp.where(hit[:, :, None, None], v_new[:, None],
                        v_cache)
    return k_cache, v_cache


# ---------------------------------------------------------------------
# Pallas decode attention
# ---------------------------------------------------------------------


def _decode_attn_kernel(lengths_ref, qbd_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, block_s: int):
    """Grid (B, S // block_s), row-major (the chunk index is the
    FAST axis). Mosaic's BlockSpec pipeline streams the k/v chunks;
    chunks past a row's valid length map to the last valid chunk
    index (see index_map), so their copies are ELIDED — HBM reads
    scale with the actual length. Online softmax accumulates in
    scratch across chunk steps; the output block is written on the
    row's last step."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    i = pl.program_id(1)
    n_i = pl.num_programs(1)
    length = jnp.maximum(lengths_ref[b], 1)
    nblk = pl.cdiv(length, block_s)

    @pl.when(i == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < nblk)
    def _():
        q_bd = qbd_ref[0]                          # [Hq, Hkv*hd]
        kc = k_ref[0]                              # [BS, Hkv*hd]
        vc = v_ref[0]

        # Block-diagonal q makes this the per-head dot for every
        # query head in ONE aligned matmul (docstring note). Operands
        # stay bf16 (native MXU bf16 x bf16 -> f32); only the
        # accumulators are f32.
        logits = jax.lax.dot_general(
            q_bd, kc,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [Hq, BS]

        col = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        logits = jnp.where(col < length, logits, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                # [Hq, BS]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vc.dtype), vc,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [Hq, Hkv*hd]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(i == n_i - 1)
    def _():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=('scale', 'block_s', 'interpret'))
def _decode_attention_pallas(q, k, v, lengths, scale, block_s,
                             interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    dflat = hkv * hd

    # Block-diagonal queries: q_bd[h*G+g, h*hd : (h+1)*hd] = q[h*G+g],
    # zeros elsewhere. Built in XLA (tiny), scaled here so the kernel
    # skips the multiply.
    head_of = jnp.arange(hq) // groups                     # [Hq]
    lane_head = jnp.arange(dflat) // hd                    # [Dflat]
    sel = (head_of[:, None] == lane_head[None, :])         # [Hq, Dflat]
    q_tiled = jnp.tile(q, (1, 1, hkv))                     # [B,Hq,Dflat]
    q_bd = jnp.where(sel[None], q_tiled,
                     jnp.zeros_like(q_tiled)) * jnp.asarray(
                         scale, q.dtype)

    kernel = functools.partial(_decode_attn_kernel, block_s=block_s)

    def kv_index(bi, i, lens):
        # Chunks past this row's valid range repeat the last valid
        # chunk index; the pipeline elides copies whose index did
        # not change, so invalid chunks cost no HBM reads.
        last = jnp.maximum(
            jax.lax.div(jnp.maximum(lens[bi], 1) + block_s - 1,
                        block_s) - 1, 0)
        return (bi, jnp.minimum(i, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // block_s),
        in_specs=[
            pl.BlockSpec((1, hq, dflat), lambda bi, i, _: (bi, 0, 0)),
            pl.BlockSpec((1, block_s, dflat), kv_index),
            pl.BlockSpec((1, block_s, dflat), kv_index),
        ],
        out_specs=pl.BlockSpec((1, hq, dflat),
                               lambda bi, i, _: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),      # running max
            pltpu.VMEM((hq, 1), jnp.float32),      # running denom
            pltpu.VMEM((hq, dflat), jnp.float32),  # accumulator
        ],
    )
    acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dflat), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_bd,
      k.reshape(b, s, dflat), v.reshape(b, s, dflat))
    # Each query head's output is its own head's value block.
    acc = acc.reshape(b, hq, hkv, hd)
    return jnp.take_along_axis(
        acc, head_of[None, :, None, None], axis=2)[:, :, 0]


def _pallas_takes(k: jax.Array) -> bool:
    """Opted in, on TPU, and the [B, S, Hkv, hd] view meets the
    kernel's chunk and lane divisibility."""
    return (_use_pallas('ATTN') and k.shape[1] % _BLOCK_S == 0 and
            k.shape[1] >= 2 * _BLOCK_S and
            (k.shape[2] * k.shape[3]) % 128 == 0)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array,
                     scale: float) -> jax.Array:
    """Single-position decode attention over per-row valid prefixes.

    q [B, Hq, hd]; k/v [B, S, Hkv, hd]; lengths [B] int — row b
    attends keys [0, lengths[b]). Returns [B, Hq, hd] in q.dtype.
    On TPU this streams only ceil(lengths/block) cache chunks from
    HBM; elsewhere (or for lane-unaligned shapes) it falls back to
    the dense masked reference.
    """
    if _pallas_takes(k):
        return _decode_attention_pallas(q, k, v, lengths, scale,
                                        _BLOCK_S)
    return _reference_decode_attention(q, k, v, lengths, scale)


# ---------------------------------------------------------------------
# Paged (block-table-indirected) decode attention
# ---------------------------------------------------------------------


def paged_gather(pool_flat: jax.Array,
                 gather_idx: jax.Array) -> jax.Array:
    """Gather rows' logical KV views out of a flattened pool,
    position by position: pool_flat [num_blocks * block_size, ...]
    indexed by the precomputed flat indices from
    ``kv_pool.read_indices`` ([B, S_pad] -> [B, S_pad, ...]). The
    prefill chunk's form (one row's view); the decode and verify
    steps use ``gather_blocks``. Scoped ``paged_gather`` in the
    compiled program's ``op_name`` metadata."""
    with jax.named_scope('paged_gather'):
        return jnp.take(pool_flat, gather_idx, axis=0)


def gather_blocks(pool: jax.Array,
                  block_tables: jax.Array) -> jax.Array:
    """Gather rows' logical KV views out of a pool, block by block:
    pool [num_blocks, block_size, ...], block_tables [B, MB] ->
    [B, MB * block_size, ...], the same values in the same order as
    ``paged_gather(pool_flat, read_indices(block_tables))``, moved in
    slices of a whole block (16 KB of int8 codes at block 16 x 8
    heads x 128) where the per-position form moves 1 KB: on the v5e
    the 24 x 4,096 view's per-position gather ran at 385 GB/s
    (PR 25's trace), this one at 535-640 (PR 26's). Table entries
    are always pool blocks, so the index is clipped, not checked."""
    b, mb = block_tables.shape
    with jax.named_scope('paged_gather'):
        view = jnp.take(pool, block_tables, axis=0, mode='clip')
    return view.reshape(b, mb * pool.shape[1], *pool.shape[2:])


def gather_scales(scale_pool: jax.Array,
                  block_tables: jax.Array) -> jax.Array:
    """The int8 pool's scales for rows' views, laid out as the
    scores take them: scale_pool [..., num_blocks, block_size, Hkv]
    (any leading dims: the decode step passes every layer's at
    once), block_tables [B, MB] -> float32 [..., B, Hkv, MB *
    block_size]. A block's scales move as one lane-dense row of
    block_size x Hkv values (256 B at 16 x 8) where the per-position
    form moved 16 B slices."""
    *lead, nb, bs, hkv = scale_pool.shape
    b, mb = block_tables.shape
    with jax.named_scope('paged_gather'):
        rows = jnp.take(scale_pool.reshape(*lead, nb, bs * hkv),
                        block_tables, axis=len(lead), mode='clip')
    return jnp.swapaxes(
        rows.reshape(*lead, b, mb * bs, hkv).astype(jnp.float32),
        -1, -2)


def view_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   lengths: jax.Array, scale: float,
                   k_scale: Optional[jax.Array] = None,
                   v_scale: Optional[jax.Array] = None,
                   new=None) -> jax.Array:
    """Dense masked attention of the decode and verify steps over a
    per-row view that may hold int8 codes, read as int8.

    q [B, Hq, hd] (one position a row) or [B, W, Hq, hd] (the verify
    window); k/v [B, S, Hkv, hd], floats, or int8 codes with
    ``k_scale``/``v_scale`` [B, Hkv, S] (``gather_scales``' layout:
    it is the scores', [B, W, Hkv, G, S]); lengths [B].

    ``new`` = (k_new, v_new, ks_new, vs_new): this step's own K/V
    rows ([B, Hkv, hd], or [B, W, Hkv, hd] for a window; scales
    [B, (W,) Hkv] or None), in the view's type, handed over as an
    operand instead of being written into the cache first. Then row
    b attends view positions [0, lengths[b]) and, query j, new rows
    [0, j]: whatever the view holds at lengths[b] and after (stale
    rows of a recycled block) is masked. With ``new=None`` the rows
    are in the view already and query j attends [0, lengths[b] + j),
    as ``_reference_decode_attention`` does for one position.

    An int8 view is never dequantised as a view: the codes are
    converted in the dot's operand (exact in bf16), the K scale
    multiplies the float32 scores and the V scale the float32
    probabilities before their one cast to q's type; both sums
    accumulate in float32. That is no coarser than the product
    ``code x scale`` rounded to bf16 ahead of the dot, which is what
    ran before PR 26 and what the chip's trace showed materialised:
    a bf16 [B, S, Hkv, hd] copy of K and of V in every layer of
    every step, over a quarter of the decode program's time. In the
    program compiled for the v5e both converts now sit inside the
    two ``convolution`` fusions, which take the s8 view as operand.
    """
    single = q.ndim == 3
    if single:
        q = q[:, None]
        if new is not None:
            new = tuple(None if r is None else r[:, None]
                        for r in new)
    b, w, hq, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, w, hkv, groups, hd)
    # One query row a KV head (no grouping, one position: 16 heads on
    # 16 KV heads) makes each of the two dots a matrix-vector
    # product, which the v5e's compiler computes elementwise over a
    # float32 COPY of the whole view (``convert f32[B x MB, bs, Hkv,
    # hd]`` of K and of V in every layer, four times the codes'
    # bytes written and read back: PERF.md, PR 28). A second, zero
    # query row keeps them matrix products that take the codes as
    # codes; its output row is dropped below.
    lone = w * groups == 1
    if lone:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, 1), (0, 0)))

    def scores(keys, key_scale):
        out = jnp.einsum('bwhgd,bshd->bwhgs', qg,
                         keys.astype(q.dtype),
                         preferred_element_type=jnp.float32)
        if key_scale is not None:
            with jax.named_scope('kv_dequant'):
                out = out * key_scale.astype(
                    jnp.float32)[:, None, :, None, :]
        return out * scale

    def weighted(probs, values, value_scale):
        if value_scale is not None:
            with jax.named_scope('kv_dequant'):
                probs = probs * value_scale.astype(
                    jnp.float32)[:, None, :, None, :]
        return jnp.einsum('bwhgs,bshd->bwhgd', probs.astype(q.dtype),
                          values.astype(q.dtype),
                          preferred_element_type=jnp.float32)

    j = jnp.arange(w)
    span = lengths[:, None] + (j[None, :] if new is None else 0)
    seen = jnp.arange(s)[None, None, :] < span[:, :, None]  # [B,W,S]
    logits = jnp.where(seen[:, :, None, None, :],
                       scores(k, k_scale), _NEG_INF)
    top = jnp.max(logits, axis=-1, keepdims=True)
    if new is not None:
        k_new, v_new, ks_new, vs_new = new
        if ks_new is not None:          # [B, W, Hkv] -> [B, Hkv, W]
            ks_new = jnp.swapaxes(ks_new, 1, 2)
            vs_new = jnp.swapaxes(vs_new, 1, 2)
        own = jnp.where((j[None, :] <= j[:, None])[None, :, None,
                                                   None, :],
                        scores(k_new, ks_new), _NEG_INF)
        top = jnp.maximum(top, jnp.max(own, axis=-1, keepdims=True))
        p_own = jnp.exp(own - top)
    p = jnp.exp(logits - top)
    total = jnp.sum(p, axis=-1, keepdims=True)
    if new is not None:
        total = total + jnp.sum(p_own, axis=-1, keepdims=True)
    out = weighted(p / total, v, v_scale)
    if new is not None:
        out = out + weighted(p_own / total, v_new, vs_new)
    if lone:
        out = out[:, :, :, :1]
    out = out.astype(q.dtype).reshape(b, w, hq, hd)
    return out[:, 0] if single else out


def _place_new(view, view_scale, new_rows, new_scale, lengths,
               dtype):
    """The float view with this step's row in place at
    ``lengths[b]``: what the opt-in Pallas kernel, which takes one
    float view and a length, is handed."""
    if view_scale is not None:
        view = view.astype(dtype) * jnp.swapaxes(
            view_scale, 1, 2)[..., None].astype(dtype)
        new_rows = new_rows.astype(dtype) * new_scale[
            ..., None].astype(dtype)
    hit = jnp.arange(view.shape[1])[None, :] == lengths[:, None]
    return jnp.where(hit[:, :, None, None], new_rows[:, None], view)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array,
                           block_tables: jax.Array,
                           lengths: jax.Array, scale: float,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           new=None) -> jax.Array:
    """Decode (q [B, Hq, hd]) and speculative-verify (q [B, W, Hq,
    hd]: the row's current token plus its drafted continuation)
    attention over PAGED caches.

    k_pool/v_pool are a block pool [num_blocks, block_size, Hkv, hd]
    (one layer's, or every layer's end to end with the table offset
    to the layer: a layer's slice taken out of the stacked pool
    first is a copy of the slice); block_tables [B, MB] int32 maps
    row b's logical block i to a pool block. An int8 pool comes with
    ``k_scale``/``v_scale``, the rows' scale views [B, Hkv, MB *
    block_size] as ``gather_scales`` lays them out (which pool it
    is, is decided at trace time from their presence). ``lengths``
    [B] and ``new`` (this step's own rows, not yet in the pool) as
    in ``view_attention``.

    Gather-based: each row's blocks are gathered whole
    (``gather_blocks``) into the contiguous [B, MB * block_size,
    Hkv, hd] view, codes as codes. Positions past a query's span
    gather scratch/stale rows and are masked to -inf before the
    softmax, so rejected-draft garbage and recycled blocks
    contribute exactly 0. The gather cost scales with the TABLE WIDTH
    (the longest admissible request), not the pool allocation and
    not the rows' lengths: a kernel that walks each row's own blocks
    is ROADMAP S2's remainder. The result equals the contiguous-cache
    path's to float32 rounding (the new row's term is summed after
    the view's, not in its place), not bit for bit; the engines'
    token-for-token tests hold both to the same tokens.
    """
    kd = gather_blocks(k_pool, block_tables)     # [B, S_pad, Hkv, hd]
    vd = gather_blocks(v_pool, block_tables)
    with jax.named_scope('decode_attention'):
        if q.ndim == 3 and new is not None and _pallas_takes(kd):
            k_new, v_new, ks_new, vs_new = new
            return decode_attention(
                q, _place_new(kd, k_scale, k_new, ks_new, lengths,
                              q.dtype),
                _place_new(vd, v_scale, v_new, vs_new, lengths,
                           q.dtype), lengths + 1, scale)
        return view_attention(q, kd, vd, lengths, scale, k_scale,
                              v_scale, new)


# ---------------------------------------------------------------------
# Pallas per-row cache write
# ---------------------------------------------------------------------


def _cache_write_kernel(pos_ref, knew_ref, vnew_ref, kwin_ref,
                        vwin_ref, ko_ref, vo_ref):
    """Grid (B,): the BlockSpec pipeline brings in the aligned
    _WRITE_WIN-row cache window containing this row's write position
    (dynamic block index from the prefetched positions), the kernel
    overwrites the target row with a vector select, and the output
    pipeline writes the window back. The rest of the cache is
    preserved by input/output aliasing. ~2*WIN*Hkv*hd elements move
    per row instead of a full-cache pass."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pos_ref[b]
    row = p - (p // _WRITE_WIN) * _WRITE_WIN

    # Extract this row's new K/V from the whole-[B, Dflat] block by
    # masked reduction (dynamic sublane indexing is layout-hostile).
    rowsel = jax.lax.broadcasted_iota(
        jnp.int32, knew_ref.shape, 0) == b          # [B, Dflat]
    knew = jnp.sum(jnp.where(rowsel, knew_ref[:], 0).astype(
        jnp.float32), axis=0).astype(ko_ref.dtype)  # [Dflat]
    vnew = jnp.sum(jnp.where(rowsel, vnew_ref[:], 0).astype(
        jnp.float32), axis=0).astype(vo_ref.dtype)

    sel = jax.lax.broadcasted_iota(
        jnp.int32, kwin_ref.shape, 1) == row        # [1, W, Dflat]
    ko_ref[:] = jnp.where(sel, knew[None, None], kwin_ref[:])
    vo_ref[:] = jnp.where(sel, vnew[None, None], vwin_ref[:])


@functools.partial(jax.jit, static_argnames=('interpret',))
def _cache_write_pallas(k_cache, v_cache, k_new, v_new, pos,
                        interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, hkv, hd = k_cache.shape
    dflat = hkv * hd
    def win_index(bi, pos):
        return (bi, pos[bi] // _WRITE_WIN, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            # New rows land in VMEM whole ([B, Dflat] is tiny); the
            # kernel masks out its own row (a 1-sublane block would
            # violate the (8, 128) block-divisibility rule).
            pl.BlockSpec((b, dflat), lambda i, _: (0, 0)),
            pl.BlockSpec((b, dflat), lambda i, _: (0, 0)),
            pl.BlockSpec((1, _WRITE_WIN, dflat), win_index),
            pl.BlockSpec((1, _WRITE_WIN, dflat), win_index),
        ],
        out_specs=[
            pl.BlockSpec((1, _WRITE_WIN, dflat), win_index),
            pl.BlockSpec((1, _WRITE_WIN, dflat), win_index),
        ],
    )
    ko, vo = pl.pallas_call(
        _cache_write_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, s, dflat), k_cache.dtype),
            jax.ShapeDtypeStruct((b, s, dflat), v_cache.dtype),
        ],
        # Alias indices count ALL inputs incl. the scalar-prefetch
        # arg: pos=0, k_new=1, v_new=2, k_cache=3, v_cache=4.
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(pos.astype(jnp.int32),
      k_new.reshape(b, dflat), v_new.reshape(b, dflat),
      k_cache.reshape(b, s, dflat), v_cache.reshape(b, s, dflat))
    return (ko.reshape(b, s, hkv, hd), vo.reshape(b, s, hkv, hd))


def cache_write(k_cache: jax.Array, v_cache: jax.Array,
                k_new: jax.Array, v_new: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write one new K/V position per row: k/v_cache [B, S, Hkv, hd],
    k/v_new [B, Hkv, hd], pos [B] int (row b writes index pos[b]).
    Returns the updated caches (in-place on TPU via aliasing)."""
    if _use_pallas('WRITE') and (k_cache.shape[2] *
                                 k_cache.shape[3]) % 128 == 0:
        return _cache_write_pallas(k_cache, v_cache, k_new, v_new,
                                   pos)
    return _reference_cache_write(k_cache, v_cache, k_new, v_new,
                                  pos)
