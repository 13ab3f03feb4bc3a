"""The paged KV pool's format on the device, and attention over it.

The format, stated here and nowhere else: a pool is
``[num_blocks, block_size, Hkv, hd]`` a KV entry (int8 codes with
bf16 scales ``[num_blocks, block_size, Hkv]``, or floats), a request
maps its logical positions onto pool blocks through a block-table row
``[MB]``, and logical position p of a row lives at

    flat slot = block_table[p // block_size] * block_size
                + p % block_size

Block 0 is the reserved SCRATCH block, never allocated
(``serve/kv_pool.KVBlockPool`` is the host allocator): parked rows,
padded prefill positions, padded or rejected draft lanes and
positions past a table's capacity all write there, so a stale table
entry can never corrupt a block that was recycled to another request.

- Write side: ``write_index`` (one position a row: the decode step),
  ``verify_write_indices`` (a draft window a row),
  ``chunk_write_indices`` (a prefill chunk of one request).
- Read side: ``read_indices`` + ``paged_gather`` (position by
  position: the prefill chunk's one-row view), ``gather_blocks`` and
  ``gather_scales`` (block by block: the decode and verify steps).
- Attention: the engine's decode and verify steps go through
  ``paged_decode_attention`` -> ``view_attention`` (plain XLA, one
  compiled program): the int8 pool is gathered block by block and
  read AS int8 — codes converted inside the two dots, the K scale
  applied to the scores and the V scale to the probabilities, this
  step's own row an operand, not a pool write. What the v5e's trace
  showed of the form before (PR 25: per-position gathers at 385 GB/s,
  a bf16 copy of the whole padded K and V view, a copy of the layer's
  pool slice for B new rows; 113.8 ms a step at 24 rows x 4,096) and
  of this one (PR 26: 48 ms) is in PERF.md. It is still dense over
  the table width: a kernel that walks each row's own blocks over
  the int8 codes is what is left (ROADMAP S2b).
  ``decode_attention`` is the plain masked form over a contiguous
  float cache that ``models/decode._layer_cached`` calls.
"""
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

# The reserved scratch block (see module docstring).
SCRATCH_BLOCK = 0


# ---------------------------------------------------------------------
# Index arithmetic (pure, shape-static; used inside jitted steps)
# ---------------------------------------------------------------------


def read_indices(block_tables: jax.Array,
                 block_size: int) -> jax.Array:
    """Flat pool-slot indices for every logical position of every
    row: block_tables [..., MB] int32 -> [..., MB * block_size].
    Positions in unallocated tail blocks land in the scratch block —
    callers mask them via their per-row lengths before softmax."""
    offs = jnp.arange(block_size, dtype=jnp.int32)
    flat = (block_tables[..., :, None] * block_size +
            offs[None, :])
    return flat.reshape(*block_tables.shape[:-1], -1)


def write_index(block_tables: jax.Array, pos: jax.Array,
                block_size: int) -> jax.Array:
    """Flat pool-slot index for each row's next write:
    block_tables [B, MB], pos [B] -> [B]. Positions at or past the
    table's capacity are redirected to the scratch block (overrun
    tokens of rows that finished mid-dispatch, parked lanes)."""
    mb = block_tables.shape[-1]
    blk = jnp.minimum(pos // block_size, mb - 1)
    idx = (jnp.take_along_axis(block_tables, blk[:, None],
                               axis=1)[:, 0] * block_size +
           pos % block_size)
    safe = (pos >= 0) & (pos < mb * block_size)
    return jnp.where(safe, idx, SCRATCH_BLOCK * block_size)


def verify_write_indices(block_tables: jax.Array, pos: jax.Array,
                         n_real: jax.Array, width: int,
                         block_size: int) -> jax.Array:
    """Flat pool-slot indices for a speculative VERIFY dispatch:
    row b writes ``width`` consecutive positions starting at
    ``pos[b]`` (its current token plus drafted continuation), of
    which only the first ``n_real[b]`` are real. Padded draft lanes
    (j >= n_real[b]), parked rows (n_real 0) and positions past the
    table capacity all redirect to the scratch block — a rejected or
    padded draft can never touch a block another request owns.
    block_tables [B, MB], pos/n_real [B] -> [B, width]."""
    t = jnp.arange(width, dtype=jnp.int32)
    p = pos[:, None] + t[None, :]                        # [B, W]
    mb = block_tables.shape[-1]
    blk = jnp.minimum(jnp.maximum(p, 0) // block_size, mb - 1)
    idx = (jnp.take_along_axis(block_tables, blk, axis=1) *
           block_size + jnp.maximum(p, 0) % block_size)
    valid = ((t[None, :] < n_real[:, None]) & (p >= 0) &
             (p < mb * block_size))
    return jnp.where(valid, idx, SCRATCH_BLOCK * block_size)


def chunk_write_indices(block_row: jax.Array, start: jax.Array,
                        real_len: jax.Array, chunk: int,
                        block_size: int) -> jax.Array:
    """Flat pool-slot indices for a prefill chunk's ``chunk`` rows
    written at positions [start, start+real_len): block_row [MB].
    Padded positions (t >= real_len) go to the scratch block."""
    t = jnp.arange(chunk, dtype=jnp.int32)
    pos = start + t
    mb = block_row.shape[0]
    blk = jnp.minimum(pos // block_size, mb - 1)
    idx = block_row[blk] * block_size + pos % block_size
    valid = (t < real_len) & (pos < mb * block_size)
    return jnp.where(valid, idx, SCRATCH_BLOCK * block_size)


# ---------------------------------------------------------------------
# Contiguous-cache decode attention
# ---------------------------------------------------------------------


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, scale: float) -> jax.Array:
    """Single-position decode attention over per-row valid prefixes
    of a contiguous float cache: q [B, Hq, hd]; k/v [B, S, Hkv, hd];
    lengths [B] int — row b attends keys [0, lengths[b]). Returns
    [B, Hq, hd] in q.dtype. Dense over S with a length mask."""
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


# ---------------------------------------------------------------------
# Paged (block-table-indirected) decode attention
# ---------------------------------------------------------------------


def paged_gather(pool_flat: jax.Array,
                 gather_idx: jax.Array) -> jax.Array:
    """Gather rows' logical KV views out of a flattened pool,
    position by position: pool_flat [num_blocks * block_size, ...]
    indexed by the precomputed flat indices from
    ``read_indices`` ([B, S_pad] -> [B, S_pad, ...]). The
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
    as ``decode_attention`` does for one position.

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
    is ROADMAP S2b. The result equals the contiguous-cache
    path's to float32 rounding (the new row's term is summed after
    the view's, not in its place), not bit for bit; the engines'
    token-for-token tests hold both to the same tokens.
    """
    kd = gather_blocks(k_pool, block_tables)     # [B, S_pad, Hkv, hd]
    vd = gather_blocks(v_pool, block_tables)
    with jax.named_scope('decode_attention'):
        return view_attention(q, kd, vd, lengths, scale, k_scale,
                              v_scale, new)


