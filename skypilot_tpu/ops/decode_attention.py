"""The paged KV pool's format on the device, and attention over it.

The three formats, stated here and nowhere else. A pool holds, a KV
entry (a pass and a layer, ``serve/kv_pool.BlockGroup``),

- float keys and values: k and v ``[num_blocks, block_size, Hkv,
  hd]`` in the model's type, no scales;
- int8 keys and values: k and v ``[num_blocks, block_size, Hkv, hd]``
  int8 codes with bf16 scales ``[num_blocks, block_size, Hkv]``, one
  a position and head;
- latent rows (a group of kind 'latent', ``config.kv_lora_rank``): ONE
  array ``[num_blocks, block_size, W]`` in the model's type and
  nothing else: a position's ``[c_kv ; k_pe ; 0..]``, the normed
  compressed row all heads' keys and values are products of, the one
  rotated key part they share, and zeros up to W =
  ``latent_pool_width(rank + rope)``, the next multiple of the
  128-lane register (640 for 512 + 64). It has no head axis and no K
  and V pair; the pool tuple's other three members are None.

In all three a request maps its logical positions onto pool blocks
through a block-table row ``[MB]``, and logical position p of a row
lives at

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
- Read side: ``gather_blocks`` and ``gather_scales`` (block by block:
  the decode and verify steps' views; ``read_indices`` is the same
  layout position by position, which the tests hold them to).
- Attention: the engine's decode and verify steps go through
  ``paged_decode_attention``, which reads an int8 pool AS int8 in
  either of two forms that give the same numbers: codes converted
  inside the two dots, the K scale applied to the scores and the V
  scale to the probabilities, this step's own row an operand, not a
  pool write. Which form runs is ``walk_engages``' answer, from the
  platform and the operands alone:
  on a TPU, for ONE query position a row over an int8 pool without a
  window (head width 128, block 16: the decode step of every int8
  serving configuration, and of a stack with window layers its
  global ones), ``walk_attention``, a Pallas kernel that copies a
  tile of each row's OWN blocks from the pool in HBM into VMEM by
  the block table and folds it into a running maximum and sum; what
  it reads follows each row's length, the table's width only bounds
  it, and the scales come as a dense operand (``walk_scales``).
  Everywhere else (the CPU, the verify window, a window layer, a
  float pool) ``gather_blocks`` + ``view_attention`` (plain XLA):
  the pool gathered block by block into a view that is dense over
  the table it is handed, the table's first columns up to a
  prewarmed width that holds the dispatch's longest row
  (``view_widths``, ``view_width``), and over every lane. What the
  v5e's trace showed of the forms before (PR 25: per-position
  gathers at 385 GB/s, a bf16 copy of the whole padded K and V view,
  a copy of the layer's pool slice for B new rows; 113.8 ms a step
  at 24 rows x 4,096; PR 26, the view read as int8: 48 ms; PR 40,
  the walk) is in PERF.md.
  ``decode_attention`` is the plain masked form over a contiguous
  float cache that ``models/decode._layer_cached`` calls.
- Window layers (a query at i sees keys j with 0 <= i - j < W): the
  table stays logical, column c holds positions [c * bs, (c + 1) *
  bs), and the host sets a column to scratch once it lies behind
  every query to come. A decode or verify step reads the
  ``window_blocks`` columns from the first one a row's window still
  touches (``window_view``: a table of fixed width whatever the
  context) and ``view_attention`` masks by the window.
- A prefill chunk of any configuration that caches keys and values
  goes through ``chunk_attention``, which walks key tiles
  (``chunk_tile_blocks``) between the context's first block (the
  window's, in a window layer) and the chunk, and never builds a
  view of ``max_seq``; ``chunk_keys_read`` counts them on the host.
- Latent layers, one layer in two forms that give the same numbers:
  a decode step reads the rows' ``latent_view`` at the prewarmed
  widths through ``latent_decode_attention``, ABSORBED (the key
  up-projection folded into the query, the value up-projection
  applied after the sum: every head scores the one shared row and no
  per-head key or value is built); a prefill chunk goes through
  ``latent_chunk_attention``, EXPANDED (a tile of cached rows at a
  time is multiplied out to per-head keys and values, as the chunk's
  own rows are).
"""
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    callers mask them via their per-row lengths before softmax. No
    step gathers position by position since PR 42: the layout as
    code, which the tests hold the block gathers and tile walks to."""
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


# The eighths of the table a decode dispatch may be cut to. Timed on
# the v5e at Mistral-7B's serving shapes (24 rows x 256 blocks,
# PERF.md, PR 31), ms a step by eighths: 14.4, 17.4, 27.9, 36.3,
# 27.1, 30.3, 33.3, 45.9. Three and four eighths are SLOWER than
# five (there the compiler keeps the 75-100 MB scale view in on-chip
# memory, where at five to seven it keeps a gathered view), so a
# dispatch that needs them takes five. The whole table lies 8 to
# 9.5 ms over the line the other widths are on, so seven eighths is
# in the set: a row in the table's last eighth but one does not put
# every row of its dispatch on the whole table (PERF.md, PR 36, has
# the step by width on each serving shape).
_VIEW_EIGHTHS = (1, 2, 5, 6, 7, 8)


def view_widths(table_blocks: int) -> Tuple[int, ...]:
    """The block-table widths a decode dispatch may read, ascending:
    ``_VIEW_EIGHTHS`` of the table rounded up to whole blocks, the
    last one the whole table (32, 64, 160, 192, 224, 256 blocks of a
    256-block table; 9, 18, 45, 54, 63, 65 of a 65-block one). Each
    is one compiled program, so the set is small and fixed by the
    table alone."""
    step = -(-table_blocks // 8)
    return tuple(sorted({min(k * step, table_blocks)
                         for k in _VIEW_EIGHTHS}))


def view_width(widths: Sequence[int], positions: int,
               block_size: int) -> int:
    """The smallest of ``widths`` (ascending, in blocks) whose
    columns hold ``positions`` logical positions; the widest (the
    whole table) where none does."""
    return next((w for w in widths if w * block_size >= positions),
                widths[-1])


def window_blocks(window: int, block_size: int,
                  table_blocks: int) -> int:
    """Columns a window layer's decode view needs: the blocks that
    ``window - 1`` cached positions can straddle, starting anywhere
    in a block (257 at 4,096 / 16), never more than the table has."""
    return min(table_blocks,
               (window + 2 * block_size - 3) // block_size)


def window_view(block_tables: jax.Array, pos: jax.Array, window: int,
                block_size: int):
    """The part of a window layer's table that a query at ``pos``
    [B] (and the queries after it) can still see: block_tables
    [B, MB] -> (sub-table [B, ``window_blocks``], key_start [B]: the
    absolute position of the sub-table's first slot). Columns past
    the table read the scratch block; the caller's length mask
    covers them."""
    mb = block_tables.shape[-1]
    width = window_blocks(window, block_size, mb)
    first = jnp.maximum(pos - window + 1, 0) // block_size     # [B]
    cols = first[:, None] + jnp.arange(width, dtype=jnp.int32)[None]
    sub = jnp.take_along_axis(block_tables,
                              jnp.minimum(cols, mb - 1), axis=1)
    return (jnp.where(cols < mb, sub, SCRATCH_BLOCK),
            first * block_size)


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


def gather_blocks(pool: jax.Array,
                  block_tables: jax.Array) -> jax.Array:
    """Gather rows' logical KV views out of a pool, block by block:
    pool [num_blocks, block_size, ...], block_tables [B, MB] ->
    [B, MB * block_size, ...], the same values in the same order as
    the flat pool taken at ``read_indices(block_tables)``, moved in
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
                   new=None, window: Optional[int] = None,
                   key_start: Optional[jax.Array] = None
                   ) -> jax.Array:
    """Dense masked attention of the decode and verify steps over a
    per-row view that may hold int8 codes, read as int8.

    ``window`` (a window layer): query j, at position lengths[b] + j,
    sees only keys less than ``window`` positions behind it, its own
    counted; the view's slot s then holds position key_start[b] + s
    (``window_view``), and ``lengths`` stay absolute.

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
    if window is None:
        seen = jnp.arange(s)[None, None, :] < span[:, :, None]
    else:                                                   # [B,W,S]
        key_pos = key_start[:, None, None] + \
            jnp.arange(s)[None, None, :]
        query_pos = (lengths[:, None] + j[None, :])[:, :, None]
        seen = (key_pos < span[:, :, None]) & \
            (query_pos - key_pos < window)
    logits = jnp.where(seen[:, :, None, None, :],
                       scores(k, k_scale), _NEG_INF)
    top = jnp.max(logits, axis=-1, keepdims=True)
    if new is not None:
        k_new, v_new, ks_new, vs_new = new
        if ks_new is not None:          # [B, W, Hkv] -> [B, Hkv, W]
            ks_new = jnp.swapaxes(ks_new, 1, 2)
            vs_new = jnp.swapaxes(vs_new, 1, 2)
        among = j[None, :] <= j[:, None]
        if window is not None:
            among &= j[:, None] - j[None, :] < window
        own = jnp.where(among[None, :, None, None, :],
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
                           new=None, window: Optional[int] = None,
                           key_start: Optional[jax.Array] = None
                           ) -> jax.Array:
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
    in ``view_attention``; so ``window`` and ``key_start``, with
    ``block_tables`` then the sub-table of ``window_view``.

    Two forms, chosen by ``walk_engages`` from the platform and
    these operands. One decode position a row over an int8 pool
    without a window, on a TPU: ``walk_attention``, which reads each
    row's own blocks in the pool where they lie, as far as
    ``lengths[b]`` says (``k_scale``/``v_scale`` then come as
    ``walk_scales`` lays them out; the caller asks the same rule).
    Otherwise gather-based: each row's blocks are gathered whole
    (``gather_blocks``) into the contiguous [B, MB * block_size,
    Hkv, hd] view, codes as codes, at a cost that scales with the
    width of the TABLE HANDED IN and with every lane, not with each
    row's length. Under both, positions past a query's span (scratch
    or stale rows) are masked to -inf before the softmax, so
    rejected-draft garbage and recycled blocks contribute exactly 0,
    and the decode step hands in the first columns that hold its
    longest active row (``view_widths``). The result equals the
    contiguous-cache path's to float32 rounding (the new row's term
    is summed after the view's, not in its place), not bit for bit;
    the engines' token-for-token tests hold all of them to the same
    tokens.
    """
    if walk_engages(*k_pool.shape[1:], codes=k_scale is not None,
                    positions=1 if q.ndim == 3 else q.shape[1],
                    window=window):
        with jax.named_scope('decode_attention'):
            return walk_attention(q, k_pool, v_pool, block_tables,
                                  lengths, scale, k_scale, v_scale,
                                  new)
    kd = gather_blocks(k_pool, block_tables)     # [B, S_pad, Hkv, hd]
    vd = gather_blocks(v_pool, block_tables)
    with jax.named_scope('decode_attention'):
        return view_attention(q, kd, vd, lengths, scale, k_scale,
                              v_scale, new, window, key_start)


# ---------------------------------------------------------------------
# The block walk: one decode position a row over an int8 pool, each
# row's own blocks read from the pool where they lie (Pallas, TPU)
# ---------------------------------------------------------------------

# The pallas_call's name: what a trace's ``device_ops`` and the
# compiled text call the kernel.
WALK_KERNEL_NAME = 'decode_block_walk'

# What the kernel tiles: a 128-lane head, and a block whose rows
# (positions x KV heads) fill whole (32, 128) int8 tiles.
_WALK_HEAD_DIM = 128
_WALK_BLOCK_SIZE = 16
# The kernel's two products take bf16 operands (codes are exact in
# bf16) and sum in float32, whatever the process's default matmul
# precision is set to: Mosaic refuses a higher one for bf16.
_WALK_PRECISION = jax.lax.Precision.DEFAULT


def _on_tpu() -> bool:
    """Whether the default backend is a TPU (the walk is a Mosaic
    kernel; every other backend keeps the gathered view)."""
    return jax.default_backend() == 'tpu'


def _interpret() -> bool:
    """Whether the walk runs in the Pallas interpreter: where a test
    has made ``_on_tpu`` say yes on another backend."""
    return jax.default_backend() != 'tpu'


def walk_engages(block_size: int, n_kv_heads: int, head_dim: int, *,
                 codes: bool, positions: int,
                 window: Optional[int]) -> bool:
    """Whether a step's attention walks the pool (``walk_attention``)
    or gathers a view (``view_attention``), from what the code can
    observe and nothing else: a TPU, an int8 pool (``codes``: scales
    are present), ONE query position a row (``positions``: the verify
    window has several), no window (``window_view`` already hands a
    window layer 257 columns whatever the context), and a block the
    kernel tiles. The model's step asks it to lay the scales out, the
    attention to choose its path, the engine to count; it has no
    other input."""
    return (_on_tpu() and codes and positions == 1 and window is None
            and head_dim == _WALK_HEAD_DIM
            and block_size == _WALK_BLOCK_SIZE
            and (block_size * n_kv_heads) % 32 == 0)


def walk_tile_blocks(n_kv_heads: int) -> int:
    """Blocks a tile of the walk holds: as many as make 4,096 rows
    (positions x KV heads) of codes, 512 KB of K and of V. Timed on
    the v5e on the three serving shapes (PERF.md, PR 40; ms for the
    attention of one step, rows filled as the cells fill them): 8 KV
    heads x 4 query heads at 16 / 32 / 64 blocks 5.77 / 5.71 / 6.11,
    8 x 16 5.52 / 5.32 / 5.40, 16 x 1 (whose block is twice as
    large) 13.3 / 14.9 / 16.1. A tile is scored and summed whole:
    folded in chunks of an eighth it took 1.7 times as long (each
    fold waits for the matrix unit to drain)."""
    return 4096 // (_WALK_BLOCK_SIZE * n_kv_heads)


def walk_scales(scale_pool: jax.Array,
                block_tables: jax.Array) -> jax.Array:
    """The int8 pool's scales for rows' walks, laid out as the
    walk's scores take them: scale_pool [..., num_blocks,
    block_size, Hkv], block_tables [B, MB] -> float32 [..., B, tiles,
    tile_blocks * block_size * Hkv], a tile's scales one lane-dense
    row in the order its codes have (block, position, head); the
    table's tail up to whole tiles reads the scratch block. Dense
    over the table handed in, as ``gather_scales`` is: 1/64 of the
    codes' bytes, gathered by XLA once a step. (The kernel cannot
    copy a block's scales itself: the v5e's compiler lays the scale
    pools out with the ENTRY axis minor, so one entry's 128 scales of
    a block are nowhere contiguous.)"""
    *lead, nb, bs, hkv = scale_pool.shape
    b, mb = block_tables.shape
    tile = walk_tile_blocks(hkv)
    tiles = -(-mb // tile)
    tables = jnp.pad(block_tables, ((0, 0), (0, tiles * tile - mb)),
                     constant_values=SCRATCH_BLOCK)
    with jax.named_scope('paged_gather'):
        rows = jnp.take(scale_pool.reshape(*lead, nb, bs * hkv),
                        tables, axis=len(lead), mode='clip')
    return rows.reshape(*lead, b, tiles, tile * bs * hkv).astype(
        jnp.float32)


def _walk_kernel(tables_ref, lengths_ref, q_ref, ks_ref, vs_ref,
                 k_hbm, v_hbm, acc_ref, top_ref, total_ref, k_buf,
                 v_buf, sems, base_ref, *, scale: float,
                 table_blocks: int, block_size: int,
                 n_kv_heads: int):
    """One row of the walk (grid: the rows, in order). The row's
    table and length are in SMEM; the code pools stay in HBM and the
    kernel copies a tile of the row's own blocks at a time into one
    of two VMEM buffers, the next tile (or the next row's first)
    in flight while this one is folded. Only blocks that hold a
    position under the row's length are copied."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    hq, hd = q_ref.shape[1:]
    groups = hq // n_kv_heads
    tile_blocks = k_buf.shape[1]
    cols = tile_blocks * block_size * n_kv_heads

    def blocks_of(r):
        seen = jnp.minimum(lengths_ref[r], table_blocks * block_size)
        return (seen + block_size - 1) // block_size

    def copies(r, t, slot, act):
        """Start, or wait for, the copies of tile t of row r."""
        def one(j, _):
            blk = tables_ref[r * table_blocks + t * tile_blocks + j]
            for pool, buf, sem in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                act(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, j], sems.at[sem, slot]))
        jax.lax.fori_loop(
            0, jnp.minimum(tile_blocks, blocks_of(r) - t * tile_blocks),
            one, None)

    def start(r, t, slot):
        copies(r, t, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _():
        base_ref[0] = 0
        start(0, 0, 0)

    length = lengths_ref[b]
    n_tiles = (blocks_of(b) + tile_blocks - 1) // tile_blocks
    base = base_ref[0]
    q = q_ref[0]                                           # [Hq, hd]
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0)
    # A column is a (position, KV head) pair of the tile: a query
    # head reads those of its own KV head.
    mine = col % n_kv_heads == head // groups

    def fold(t, carry):
        top, total, acc = carry
        slot = (base + t) % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            start(b, t + 1, 1 - slot)

        @pl.when((t + 1 == n_tiles) & (b + 1 < rows))
        def _():
            start(b + 1, 0, 1 - slot)

        copies(b, t, slot, lambda c: c.wait())
        logits = jax.lax.dot_general(
            q, k_buf[slot].reshape(cols, hd).astype(q.dtype),
            (((1,), (1,)), ((), ())), precision=_WALK_PRECISION,
            preferred_element_type=jnp.float32)
        logits = logits * ks_ref[0, pl.ds(t, 1), :] * scale
        seen = mine & (col < (length - t * tile_blocks * block_size)
                       * n_kv_heads)
        logits = jnp.where(seen, logits, _NEG_INF)
        new_top = jnp.maximum(top, logits.max(-1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        p = jnp.exp(logits - new_top)
        total = total * shrink + p.sum(-1, keepdims=True)
        p = (p * vs_ref[0, pl.ds(t, 1), :]).astype(q.dtype)
        acc = acc * shrink + jax.lax.dot_general(
            p, v_buf[slot].reshape(cols, hd).astype(q.dtype),
            (((1,), (0,)), ((), ())), precision=_WALK_PRECISION,
            preferred_element_type=jnp.float32)
        return new_top, total, acc

    top, total, acc = jax.lax.fori_loop(
        0, n_tiles, fold,
        (jnp.full((hq, 1), _NEG_INF, jnp.float32),
         jnp.zeros((hq, 1), jnp.float32),
         jnp.zeros((hq, hd), jnp.float32)))

    # A row that reads nothing still hands the next row's first tile
    # on; nothing of its own is in flight, so the slot stays.
    @pl.when((n_tiles == 0) & (b + 1 < rows))
    def _():
        start(b + 1, 0, base)

    base_ref[0] = (base + n_tiles) % 2
    acc_ref[0] = acc
    top_ref[0] = top
    total_ref[0] = total


def walk_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   block_tables: jax.Array, lengths: jax.Array,
                   scale: float, k_scale: jax.Array,
                   v_scale: jax.Array, new) -> jax.Array:
    """``view_attention``'s numbers for one query position a row over
    an int8 pool, with no view: q [B, Hq, hd]; k_pool/v_pool
    [num_blocks, 16, Hkv, 128] int8 codes; block_tables [B, MB];
    k_scale/v_scale the rows' scales as ``walk_scales`` lays them out
    (the tile's length is read off them); ``new`` = (k_new, v_new,
    ks_new, vs_new), this step's own rows, as in ``view_attention``.

    A Pallas TPU kernel (``_walk_kernel``) walks each row's own
    blocks in tiles with a running maximum and sum in float32, as
    ``chunk_attention`` folds its tiles; what a row reads is decided
    by ``lengths[b]``, not by the table's width: a tile wholly past
    the length is not copied, a row of length 0 reads nothing. The
    arithmetic is ``view_attention``'s term for term: codes converted
    inside the two products, the K scale on the float32 scores, the V
    scale on the probabilities before their one cast to q's type,
    float32 sums, positions at or past the length masked before the
    maximum. The product runs over a tile's rows as they lie in the
    pool, (position, KV head) pairs, and a query head keeps the
    columns of its own KV head: the codes are never re-laid by head.
    The own row's term is summed with the walk's here, in XLA."""
    b, hq, hd = q.shape
    nb, bs, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    groups = hq // hkv
    tiles, cols = k_scale.shape[1:]
    tile_blocks = cols // (bs * hkv)
    kernel = functools.partial(
        _walk_kernel, scale=scale, table_blocks=mb, block_size=bs,
        n_kv_heads=hkv)
    row = lambda i, *_: (i, 0, 0)
    stat = jax.ShapeDtypeStruct((b, hq, 1), jnp.float32)
    acc, top, total = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, hd), row),
                pl.BlockSpec((1, tiles, cols), row),
                pl.BlockSpec((1, tiles, cols), row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, hq, hd), row),
                       pl.BlockSpec((1, hq, 1), row),
                       pl.BlockSpec((1, hq, 1), row)],
            scratch_shapes=[
                pltpu.VMEM((2, tile_blocks, bs * hkv, hd), jnp.int8),
                pltpu.VMEM((2, tile_blocks, bs * hkv, hd), jnp.int8),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hq, hd), jnp.float32),
                   stat, stat],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
        name=WALK_KERNEL_NAME,
    )(block_tables.reshape(-1), lengths.astype(jnp.int32), q, k_scale,
      v_scale, k_pool.reshape(nb, bs * hkv, hd),
      v_pool.reshape(nb, bs * hkv, hd))
    # This step's own row, summed with the walk's result as
    # ``view_attention`` sums it with the view's.
    k_new, v_new, ks_new, vs_new = new
    qg = q.reshape(b, hkv, groups, hd)
    own = jnp.einsum('bhgd,bhd->bhg', qg, k_new.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    with jax.named_scope('kv_dequant'):
        own = own * ks_new.astype(jnp.float32)[:, :, None]
    own = (own * scale).reshape(b, hq, 1)
    new_top = jnp.maximum(top, own)
    shrink = jnp.exp(top - new_top)
    p_own = jnp.exp(own - new_top)
    total = total * shrink + p_own
    with jax.named_scope('kv_dequant'):
        p_own = (p_own / total).reshape(b, hkv, groups) * \
            vs_new.astype(jnp.float32)[:, :, None]
    out = acc * (shrink / total) + jnp.einsum(
        'bhg,bhd->bhgd', p_own.astype(q.dtype), v_new.astype(q.dtype),
        preferred_element_type=jnp.float32).reshape(b, hq, hd)
    return out.astype(q.dtype)


def chunk_tile_blocks(block_size: int, table_blocks: int) -> int:
    """Blocks a key tile of a prefill chunk's walk holds: 512 key
    positions, never more than the table has, for every shape. Timed
    on the v5e (PERF.md, PR 42; ms a 512-token chunk, tiles of 16 /
    32 / 64 blocks of 16): Mistral-7B (8 KV heads x 4) at ``start``
    256 49.3 / 50.0 / 55.9, at 2,560 57.7 / 56.2 / 62.6; Ouro (16 x
    1) at 128 85.0 / 87.8 / 92.8, at 512 88.8 / 87.8 / 92.8. A tile
    is scored whole: a wide one pays for the keys past ``start`` it
    masks, a narrow one for a rescale of the accumulator a tile."""
    return max(1, min(512 // block_size, table_blocks))


def chunk_keys_read(start: int, chunk: int, block_size: int,
                    table_blocks: int,
                    window: Optional[int] = None) -> int:
    """Key positions a chunk of ``chunk`` rows at ``start`` scores in
    one layer, on the host for the engine's counters: the whole tiles
    ``chunk_attention`` folds, and the chunk's own rows."""
    tile = chunk_tile_blocks(block_size, table_blocks) * block_size
    first = 0 if window is None else max(start - window + 1, 0) // tile
    return (-(-start // tile) - first) * tile + chunk


def _tiled_row(block_row: jax.Array, block_size: int,
               tile_blocks: Optional[int]):
    """(tile_blocks, key positions a tile, the table padded with the
    scratch block to whole tiles) for a chunk's walk."""
    mb = block_row.shape[0]
    tile_blocks = tile_blocks or chunk_tile_blocks(block_size, mb)
    return (tile_blocks, tile_blocks * block_size,
            jnp.pad(block_row, (0, -mb % tile_blocks),
                    constant_values=SCRATCH_BLOCK))


def chunk_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array,
                    block_row: jax.Array, start: jax.Array,
                    scale: float,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    tile_blocks: Optional[int] = None) -> jax.Array:
    """Attention of one request's PREFILL CHUNK over key tiles, for
    any stack that caches keys and values (with window layers or
    without; a float or an int8 pool): q [T, Hq, hd] at positions
    start + t; k_new/v_new [T, Hkv, hd] the chunk's own exact rows,
    an operand as in the decode step (no in-layer pool write);
    k_pool/v_pool a block pool [num_blocks, block_size, Hkv, hd]
    with ``block_row`` [MB] this request's table into it (offset to
    the layer's entry by the caller); an int8 pool comes with the
    request's scales ``k_scale``/``v_scale`` as ``gather_scales``
    lays this one row's out, float32 [Hkv, MB * block_size],
    gathered by the caller OUTSIDE its layer loop. (Taken from the
    scale pools inside the loop they cost a 512-token Mistral chunk
    80 ms: the v5e keeps those pools with the ENTRY axis minor and
    re-laid both, 37 MB each, in every layer; PERF.md, PR 42.)

    Query t sees the cached keys [0, start) and the chunk's rows
    [0, t]; with ``window`` only those less than ``window`` positions
    behind it. The cached part is walked in tiles of ``tile_blocks``
    blocks (``chunk_tile_blocks``) with a running maximum and sum
    (float32), from the tile
    that holds the first position any query of the chunk can see (0
    in a global layer) to the one that holds ``start - 1``: the work
    follows the context a layer reads, and the scores of one tile
    ([Hq, T, tile] float32) are the largest array there is. The view
    of ``max_seq`` that ``models/decode._masked_attention`` takes is
    128 x 512 x 12,288 x 4 B = 3.2 GB a layer at 128 query heads.
    Codes are converted inside the dots and the scales applied to
    scores and probabilities, as ``view_attention`` does. Returns
    [T, Hq, hd] in q's type. Padded query rows (past the chunk's
    real length) give rows the caller discards."""
    t, hq, hd = q.shape
    bs, hkv = k_pool.shape[1:3]
    groups = hq // hkv
    tile_blocks, tile, row = _tiled_row(block_row, bs, tile_blocks)
    # Scales up to whole tiles too: what the padding holds is masked.
    k_scale, v_scale = (
        None if sc is None else
        jnp.pad(sc, ((0, 0), (0, row.shape[0] * bs - sc.shape[1])))
        for sc in (k_scale, v_scale))
    qg = q.reshape(t, hkv, groups, hd)
    q_pos = start + jnp.arange(t, dtype=jnp.int32)            # [T]

    def scores(keys, key_scale):
        out = jnp.einsum('thgd,shd->hgts', qg, keys.astype(q.dtype),
                         preferred_element_type=jnp.float32)
        if key_scale is not None:
            with jax.named_scope('kv_dequant'):
                out = out * key_scale[:, None, None]
        return out * scale

    def weighted(probs, values, value_scale):
        if value_scale is not None:
            with jax.named_scope('kv_dequant'):
                probs = probs * value_scale[:, None, None]
        return jnp.einsum('hgts,shd->hgtd', probs.astype(q.dtype),
                          values.astype(q.dtype),
                          preferred_element_type=jnp.float32)

    def visible(key_pos):                                  # [T, S]
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= q_pos[:, None] - key_pos[None, :] < window
        return seen

    def fold(carry, logits, seen, values, value_scale):
        top, total, acc = carry
        logits = jnp.where(seen[None, None], logits, _NEG_INF)
        new_top = jnp.maximum(top, logits.max(-1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        p = jnp.where(seen[None, None], jnp.exp(logits - new_top), 0.0)
        return (new_top, total * shrink + p.sum(-1, keepdims=True),
                acc * shrink + weighted(p, values, value_scale))

    def one_tile(i, carry):
        cols = jax.lax.dynamic_slice(row, (i * tile_blocks,),
                                     (tile_blocks,))
        with jax.named_scope('paged_gather'):
            kb, vb = (
                jnp.take(pool, cols, axis=0, mode='clip').reshape(
                    tile, *pool.shape[2:])
                for pool in (k_pool, v_pool))
        ks, vs = (
            None if sc is None else
            jax.lax.dynamic_slice_in_dim(sc, i * tile, tile, axis=1)
            for sc in (k_scale, v_scale))                 # [Hkv, tile]
        key_pos = i * tile + jnp.arange(tile, dtype=jnp.int32)
        seen = visible(key_pos) & (key_pos < start)[None, :]
        return fold(carry, scores(kb, ks), seen, vb, vs)

    first = 0 if window is None else \
        jnp.maximum(start - window + 1, 0) // tile
    carry = (jnp.full((hkv, groups, t, 1), _NEG_INF, jnp.float32),
             jnp.zeros((hkv, groups, t, 1), jnp.float32),
             jnp.zeros((hkv, groups, t, hd), jnp.float32))
    carry = jax.lax.fori_loop(first, -(-start // tile), one_tile,
                              carry)
    _, total, acc = fold(carry, scores(k_new, None), visible(q_pos),
                         v_new, None)
    out = (acc / total).astype(q.dtype)                 # [h, g, T, d]
    return jnp.moveaxis(out, 2, 0).reshape(t, hq, hd)


# ---------------------------------------------------------------------
# Latent (MLA) attention over a pool of latent rows
# ---------------------------------------------------------------------


_LANES = 128


def latent_pool_width(width: int) -> int:
    """The row width of a latent pool for ``width`` = rank + rope
    values a token: the next multiple of 128. The v5e tiles an
    array's minor axis by 128 lanes, so a 576-wide row occupies 640
    in memory whatever is declared; declared as 576 the compiler
    avoids the padding by laying the pool out with its BLOCK axis
    minor, and every program that reads blocks then opens and closes
    with a relayout copy of the whole pool (3.9 GB at 18,945 blocks,
    seen in the deviceless v5e compile). The padding lanes hold
    zeros and meet zeros of the query."""
    return -(-width // _LANES) * _LANES


def latent_row(c_kv: jax.Array, k_pe: jax.Array) -> jax.Array:
    """``[c_kv ; k_pe ; 0..]`` [..., W]: a token's row as a latent
    pool stores it."""
    width = c_kv.shape[-1] + k_pe.shape[-1]
    pad = jnp.zeros((*c_kv.shape[:-1], latent_pool_width(width) - width),
                    c_kv.dtype)
    return jnp.concatenate([c_kv, k_pe, pad], axis=-1)


def latent_view(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Rows' logical views of a latent pool, block by block: pool
    [num_blocks, block_size, W], block_tables [B, MB] (cut by the
    caller to a prewarmed width, ``view_widths``) -> [B, MB *
    block_size, W]."""
    return gather_blocks(pool, block_tables)


def latent_decode_attention(q_lat: jax.Array, q_pe: jax.Array,
                            view: jax.Array, lengths: jax.Array,
                            scale: float, new: jax.Array) -> jax.Array:
    """One decode position a row over a latent view, ABSORBED: q_lat
    [B, H, rank] is the head's position-free query part already taken
    through the key up-projection (q_nope W_kb^K^T), q_pe [B, H,
    rope] its rotated part; view [B, S, W] (``latent_view``; W >=
    rank + rope, ``latent_row``) of which row b's positions [0,
    lengths[b]) count; ``new`` [B, W] this step's own latent row, an
    operand and not yet a pool write (as in ``view_attention``). Scores are (q_lat . c_kv +
    q_pe . k_pe) * scale over the ONE row all H heads share, the
    softmax in float32. Returns the probabilities' sum of c_kv, [B,
    H, rank] in q's type: the caller takes it through the value
    up-projection."""
    rank = q_lat.shape[-1]
    q = latent_row(q_lat, q_pe)                          # [B, H, W]
    s = view.shape[1]
    logits = jnp.einsum('bhw,bsw->bhs', q, view,
                        preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(s)[None, :] < lengths[:, None]       # [B, S]
    logits = jnp.where(seen[:, None, :], logits, _NEG_INF)
    own = jnp.einsum('bhw,bw->bh', q, new,
                     preferred_element_type=jnp.float32) * scale
    top = jnp.maximum(jnp.max(logits, axis=-1), own)       # [B, H]
    p = jnp.exp(logits - top[..., None])
    p_own = jnp.exp(own - top)
    total = jnp.sum(p, axis=-1) + p_own
    out = jnp.einsum('bhs,bsc->bhc',
                     (p / total[..., None]).astype(q.dtype),
                     view[..., :rank],
                     preferred_element_type=jnp.float32)
    out = out + (p_own / total)[..., None] * \
        new[:, None, :rank].astype(jnp.float32)
    return out.astype(q.dtype)


def latent_verify_attention(q_lat: jax.Array, q_pe: jax.Array,
                            view: jax.Array, lengths: jax.Array,
                            scale: float, new: jax.Array,
                            first: int = 0) -> jax.Array:
    """``latent_decode_attention`` at SEVERAL query positions a row
    (a verify step's draft window, a drafting round's two): q_lat [B,
    T, H, rank], q_pe [B, T, H, rope] at positions lengths[b] + t;
    ``new`` [B, T, W] the window's own latent rows, operands as
    there. Query t sees the view's slots [``first``, lengths[b]) and
    the window's rows [0, t] (the intra-draft causal mask). ``first``
    (static): slots below it hold nothing and are never seen (a
    next-token-prediction module's entry keeps slot 0 empty).
    Returns [B, T, H, rank] in q's type."""
    rank = q_lat.shape[-1]
    t = q_lat.shape[1]
    q = latent_row(q_lat, q_pe)                       # [B, T, H, W]
    slot = jnp.arange(view.shape[1])[None, :]
    seen = (slot >= first) & (slot < lengths[:, None])    # [B, S]
    logits = jnp.einsum('bthw,bsw->bths', q, view,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(seen[:, None, None, :], logits, _NEG_INF)
    own = jnp.einsum('bthw,bjw->bthj', q, new,
                     preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]  # [T, J]
    own = jnp.where(causal[None, :, None, :], own, _NEG_INF)
    top = jnp.maximum(jnp.max(logits, axis=-1), jnp.max(own, axis=-1))
    p = jnp.exp(logits - top[..., None])
    p_own = jnp.exp(own - top[..., None])
    total = jnp.sum(p, axis=-1) + jnp.sum(p_own, axis=-1)
    out = jnp.einsum('bths,bsc->bthc',
                     (p / total[..., None]).astype(q.dtype),
                     view[..., :rank],
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum('bthj,bjc->bthc', p_own / total[..., None],
                           new[..., :rank].astype(jnp.float32))
    return out.astype(q.dtype)


def latent_chunk_attention(q_nope: jax.Array, q_pe: jax.Array,
                           new: jax.Array, pool: jax.Array,
                           block_row: jax.Array, start: jax.Array,
                           scale: float, expand: Callable, rank: int,
                           tile_blocks: Optional[int] = None,
                           first: int = 0,
                           hidden: Optional[jax.Array] = None):
    """One request's PREFILL CHUNK over a latent pool, EXPANDED:
    q_nope [T, H, nope] and q_pe [T, H, rope] at positions start + t;
    ``new`` [T, W] the chunk's own latent rows (``latent_row``; an
    operand, no in-layer pool write); pool [num_blocks, block_size,
    W] with ``block_row`` [MB] this request's table into it
    (offset to the layer's entry by the caller). ``expand(c_kv [S,
    rank]) -> (k_nope [S, H, nope], v [S, H, vd])`` is the layer's
    key and value up-projection; a row's first ``rank`` values are
    c_kv, the next rope k_pe.

    Query t sees the cached rows [0, start) and the chunk's rows [0,
    t]. The cached part is walked in tiles of ``tile_blocks`` blocks,
    each gathered, multiplied out to per-head keys and values and
    folded into a running maximum and sum (float32), as
    ``chunk_attention`` does: the expanded keys of 18 k positions x
    32 heads x 192 never exist at once, nor a view of ``max_seq``.
    Returns [T, H, vd] in q's type; padded query rows give rows the
    caller discards.

    A next-token-prediction module's entry (``models/decode.py``:
    ``mtp_module``) adds two things. ``first`` (static): slots below
    it hold nothing and no query sees them (but the one standing
    there, so that its sum is not empty). ``hidden`` (traced count):
    the chunk's first ``hidden`` rows are recomputed lanes whose own
    rows are not to be trusted; every other query reads their slots
    from the pool instead, i.e. the cached part ends at ``start +
    hidden``."""
    t, heads, _ = q_nope.shape
    bs, width = pool.shape[1], pool.shape[2]
    rope = q_pe.shape[-1]
    tile_blocks, tile, row = _tiled_row(block_row, bs, tile_blocks)
    q_pos = start + jnp.arange(t, dtype=jnp.int32)

    def fold(carry, rows, seen):
        """Fold latent ``rows`` [S, W], visible to query t where
        ``seen`` [T, S], into the running (top, total, acc); None
        starts it."""
        k_nope, v = expand(rows[:, :rank])
        if carry is None:
            carry = (jnp.full((heads, t, 1), _NEG_INF, jnp.float32),
                     jnp.zeros((heads, t, 1), jnp.float32),
                     jnp.zeros((heads, t, v.shape[-1]), jnp.float32))
        top, total, acc = carry
        logits = (jnp.einsum('thn,shn->hts', q_nope, k_nope,
                             preferred_element_type=jnp.float32) +
                  jnp.einsum('thr,sr->hts', q_pe,
                             rows[:, rank:rank + rope],
                             preferred_element_type=jnp.float32)
                  ) * scale
        logits = jnp.where(seen[None], logits, _NEG_INF)
        new_top = jnp.maximum(top, logits.max(-1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        p = jnp.where(seen[None], jnp.exp(logits - new_top), 0.0)
        return (new_top, total * shrink + p.sum(-1, keepdims=True),
                acc * shrink + jnp.einsum(
                    'hts,shd->htd', p.astype(q_nope.dtype), v,
                    preferred_element_type=jnp.float32))

    def one_tile(i, carry):
        cols = jax.lax.dynamic_slice(row, (i * tile_blocks,),
                                     (tile_blocks,))
        with jax.named_scope('paged_gather'):
            rows = jnp.take(pool, cols, axis=0, mode='clip').reshape(
                tile, width)
        key_pos = i * tile + jnp.arange(tile, dtype=jnp.int32)
        cached = key_pos < cached_end
        if first:
            cached &= key_pos >= first
        seen = jnp.broadcast_to(cached[None, :], (t, tile))
        return fold(carry, rows, seen)

    # The chunk's own rows first (every query sees its own, so the
    # running maximum is finite from the start), then the cached
    # tiles: the sum's order is not the positions', which a softmax
    # does not mind.
    own = q_pos[None, :] <= q_pos[:, None]
    cached_end = start
    if first or hidden is not None:
        # A row is always seen by its own query.
        itself = q_pos[None, :] == q_pos[:, None]
        if hidden is not None:
            cached_end = start + hidden
            own &= (q_pos >= cached_end)[None, :] | itself
        if first:
            own &= (q_pos >= first)[None, :] | itself
    carry = fold(None, new, own)
    _, total, acc = jax.lax.fori_loop(0, -(-cached_end // tile),
                                      one_tile, carry)
    out = (acc / total).astype(q_nope.dtype)             # [H, T, vd]
    return jnp.swapaxes(out, 0, 1)
