"""Paged KV-cache block pool for the continuous-batching engine.

The fixed-slot engine pinned a full ``[L, B, max_seq, Hkv, hd]`` KV
slab per decode slot — a request using 80 of 4608 positions still
reserved all 4608, and admission was bounded by whole free slabs.
``skytpu_batch_kv_cache_used_bytes`` documented exactly that
fragmentation gap. This module is the PagedAttention/vLLM answer,
TPU-native: KV storage is ONE pool of fixed-size blocks

    k/v:    [E, num_blocks, block_size, Hkv, hd]
    scales: [E, num_blocks, block_size, Hkv]      (int8 pool only)

(E = KV entries, one for every pass and layer: see ``BlockGroup``;
a configuration with window AND global layers keeps one such pool a
kind of layer, ``KVBlockPool.groups``; a group of kind 'latent'
keeps ONE array ``[E, num_blocks, block_size, W]`` of latent rows
and no K and V pair) and each request holds a
host-side list of block ids plus a device block-table row that maps its logical positions onto pool slots.
Admission is then bounded by FREE BLOCKS (a token budget), not free
slabs: short requests pack tightly, long ones grow block by block,
and the engine preempts-and-requeues the youngest request instead of
deadlocking when the pool runs dry.

TPU-first design notes:
- All shapes static: the pool, the per-request block tables
  ``[B, max_blocks]`` and the gather/scatter index arithmetic
  (``ops/decode_attention.py``, where the pool's format on the
  device is stated; this module is the host allocator) are
  fixed-shape; occupancy is data.
- Block 0 is a reserved SCRATCH block, never allocated: parked rows
  (inactive decode lanes) and padded prefill positions direct their
  writes there, so stale block-table entries can never corrupt a
  block that has been recycled to another request.
- The pool shards exactly like the dense cache did
  (``decode_shardings``): KV-head axis over 'tp', everything else
  replicated — blocks are shared across requests, so there is no
  batch axis to shard; a latent group has no head axis and is whole
  on every chip. ``pool_shardings`` builds the NamedShardings
  from the same rules→specs idiom as the training partitioner.

Automatic prefix caching (the vLLM/SGLang radix-reuse lineage, block
granular): blocks are REFCOUNTED, and a full block whose content is a
complete token block of some prompt can be REGISTERED under its
chain hash (``serve/prefix_hash.py`` — the hash commits to the whole
token prefix, so hash equality == reuse-safe KV equality). The
free list becomes two tiers:

- ``_free``: refcount-0 UNREGISTERED blocks (content meaningless) —
  handed out first;
- ``_cached``: refcount-0 REGISTERED blocks in LRU order — their
  content is intact and matchable, and they are evicted (oldest
  first, hash unregistered) only when ``_free`` runs dry. A cached
  block is reclaimable capacity, never corrupted-in-place: eviction
  happens only through the allocator, and every table-referenced
  block holds a reference.

Admission matches an incoming prompt's hash chain
(``match``/``pin``), pins the hit blocks (refcount++), and prefills
only the suffix; ``free`` only ever decrements. Shared blocks are
immutable by construction — only FULL blocks are registered, and a
request's writes land strictly past its reused prefix — so the
SCRATCH invariant and the write-index arithmetic are unchanged.
"""
import collections
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu import exceptions
from skypilot_tpu import tpu_logging
from skypilot_tpu.models import llama
from skypilot_tpu.ops.decode_attention import (SCRATCH_BLOCK,
                                               latent_pool_width)
from skypilot_tpu.serve import prefix_hash

logger = tpu_logging.init_logger(__name__)

# Partial-match (COW) index bound: at most this many registered
# children per chain parent are kept discoverable for partial-block
# matching. A hot shared prefix accumulates one divergent child per
# completed suffix — without the cap, every admission under that
# prefix would scan an unbounded sibling list inside the
# single-threaded engine loop. Blocks past the cap still register
# for EXACT full-chain matching (the common win); they just aren't
# COW candidates.
MAX_PARTIAL_CHILDREN = 64


# ---------------------------------------------------------------------
# Pool
# ---------------------------------------------------------------------


class BlockGroup:
    """Device KV block pool + host free-list allocator of the layers
    of ONE kind (``config.layer_kinds``: 'global' layers keep the
    whole context, 'window' layers what a sliding window still
    sees): its own pool arrays, free list, refcounts and prefix
    index. ``KVBlockPool`` is the group a single-kind configuration
    has, and holds the second one where there are two.

    ``caches`` is the engine-facing tuple
    ``(k, v, k_scale, v_scale)`` with k/v
    ``[E, num_blocks, block_size, Hkv, hd]`` (int8 codes + bf16
    scales ``[E, num_blocks, block_size, Hkv]`` when ``kv_int8``;
    scales are None for a bf16 pool) — the same 4-tuple shape the
    decode step functions carry, so the pool arrays are donated
    through jit like the old slabs were. A group of kind 'latent'
    (``config.kv_lora_rank``) holds ``(rows, None, None, None)``
    with rows ``[E, num_blocks, block_size, W]`` in the model's
    type, W = ``config.latent_width`` rounded up to whole 128-lane
    registers (``ops.decode_attention.latent_pool_width``): one row
    a token and entry where K and V of every head would be ``2 x
    n_heads x head size`` (576 values, 640 in memory = 1,280 B,
    against 20,480 at 32 heads of 192 + 128). An int8 latent
    is not implemented: ``kv_int8`` is refused there.

    THE LEADING AXIS, here and wherever the engine's docstrings
    write the pool's shape: E = ``config.kind_entries(kind)``, for a
    single-kind configuration ``config.kv_entries`` =
    ``loop_passes x n_layers`` KV entries. A model whose layers run
    once has one entry a layer (E = L). A looped stack keeps the
    keys and values of pass t, layer l at entry ``t * n_layers + l``
    and shares none between passes, while ``params['layers']`` keeps
    its ``n_layers`` leading axis. A block id names the same slot in
    every entry, so allocation, prefix hashes, copy-on-write and
    preemption are per block and know nothing of E; the bytes a
    block (and so a token) costs scale with it (``token_bytes``).
    """

    def __init__(self, config: llama.LlamaConfig, num_blocks: int,
                 block_size: int, kv_int8: bool = False,
                 shardings=None, kind: Optional[str] = None):
        if block_size < 1:
            raise ValueError(f'block_size must be >= 1: {block_size}')
        if num_blocks < 2:
            # Block 0 is scratch; a pool with zero usable blocks can
            # never admit anything.
            raise ValueError(
                f'num_blocks must be >= 2 (block 0 is reserved '
                f'scratch): {num_blocks}')
        self.config = config
        self.kind = kind or config.layer_kinds[0]
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.kv_int8 = kv_int8
        shape = (config.kind_entries(self.kind), num_blocks,
                 block_size, config.n_kv_heads, config.head_dim)
        if self.kind == 'latent':
            if kv_int8:
                raise exceptions.NotSupportedError(
                    f'{config.name!r}: an int8 latent cache is not '
                    f'implemented (kv_int8 with kv_lora_rank='
                    f'{config.kv_lora_rank})')
            caches = (jnp.zeros(
                shape[:3] + (latent_pool_width(config.latent_width),),
                config.dtype), None, None, None)
        elif kv_int8:
            caches = (jnp.zeros(shape, jnp.int8),
                      jnp.zeros(shape, jnp.int8),
                      jnp.zeros(shape[:-1], jnp.bfloat16),
                      jnp.zeros(shape[:-1], jnp.bfloat16))
        else:
            caches = (jnp.zeros(shape, config.dtype),
                      jnp.zeros(shape, config.dtype), None, None)
        if shardings is not None:
            caches = tuple(
                None if c is None else jax.device_put(c, s)
                for c, s in zip(caches, shardings))
        self.caches: Optional[Tuple] = caches
        # Sized at init: the engine takes ownership of (and donates)
        # the arrays, so live-array introspection is not an option.
        self._nbytes = sum(int(c.nbytes) for c in caches
                           if c is not None)
        # LIFO free list (hot blocks stay cache/HBM-warm); block 0
        # (scratch) is never handed out. Double-free detection moved
        # to the refcount table below — a block with no reference is
        # simply not freeable.
        self._free: List[int] = list(
            range(num_blocks - 1, SCRATCH_BLOCK, -1))
        # Prefix cache (module docstring): refcounts for allocated
        # blocks, LRU over refcount-0 registered blocks, and the
        # hash-chain registry. ``_hash_meta`` keeps (parent, tokens)
        # per registered hash so partial-block matches (copy-on-write
        # at the first divergent token) can compare token prefixes,
        # and ``_by_parent`` indexes registered children per chain
        # parent for that lookup.
        self._refcount: Dict[int, int] = {}
        self._cached: 'collections.OrderedDict[int, bytes]' = \
            collections.OrderedDict()   # block -> hash, oldest first
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        self._hash_meta: Dict[bytes, Tuple[bytes, Tuple[int, ...]]] = {}
        self._by_parent: Dict[bytes, List[bytes]] = {}
        self.evictions = 0      # cached blocks reclaimed by alloc

    # -- capacity ------------------------------------------------------

    @property
    def usable_blocks(self) -> int:
        """Allocatable blocks (total minus the scratch block)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        """RECLAIMABLE blocks: truly free plus refcount-0 cached.
        Cached blocks are capacity — admission may take them (evicting
        their content) — so exhaustion means free + cached == 0."""
        return len(self._free) + len(self._cached)

    @property
    def used_blocks(self) -> int:
        """Blocks currently REFERENCED by admitted requests (cached
        refcount-0 blocks are free_blocks, not used)."""
        return self.usable_blocks - self.free_blocks

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 blocks holding registered (reusable) content."""
        return len(self._cached)

    @property
    def nbytes(self) -> int:
        return self._nbytes

    @property
    def block_bytes(self) -> float:
        """Resident bytes per block (codes + scales)."""
        return self.nbytes / self.num_blocks

    @property
    def token_bytes(self) -> float:
        """Resident bytes one cached token costs, over every KV
        entry (66,560 at 32 entries x 8 heads x 128 int8; 798,720 at
        192 x 16 x 128)."""
        return self.block_bytes / self.block_size

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` positions."""
        return max(1, -(-tokens // self.block_size))

    # -- allocation ----------------------------------------------------

    def try_alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks (refcount 1 each), or None (and no
        change) if fewer are reclaimable — the caller decides between
        waiting and preempting. Truly-free blocks are taken first;
        only then are LRU cached blocks evicted (content
        unregistered), so resident cache survives as long as real
        free capacity lasts."""
        if n < 0:
            raise exceptions.KVBlockError(f'negative alloc: {n}')
        if n > self.free_blocks:
            return None
        out: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, h = self._cached.popitem(last=False)  # LRU oldest
                self._unregister(b, h)
                self.evictions += 1
            self._refcount[b] = 1
            out.append(b)
        return out

    def alloc(self, n: int) -> List[int]:
        blocks = self.try_alloc(n)
        if blocks is None:
            raise exceptions.KVPoolExhaustedError(
                f'KV pool exhausted: need {n} blocks, '
                f'{self.free_blocks} reclaimable of '
                f'{self.usable_blocks} usable')
        return blocks

    def free(self, blocks: List[int]) -> None:
        """Release one reference per block. At refcount 0 a
        registered block parks in the cached LRU (content intact,
        reclaimable); an unregistered one returns to the free list.
        Releasing a block that holds no reference — double free, or
        a block another request still exclusively owns never being
        yours to free — is a typed ``KVBlockError``, checked for the
        WHOLE batch before any state changes (atomic)."""
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise exceptions.KVBlockError(
                    f'freeing invalid block id {b}')
            if self._refcount.get(b, 0) < 1:
                raise exceptions.KVBlockError(
                    f'double free of block {b} (refcount 0)')
        counts: Dict[int, int] = {}
        for b in blocks:
            counts[b] = counts.get(b, 0) + 1
        for b, k in counts.items():
            if self._refcount[b] < k:
                raise exceptions.KVBlockError(
                    f'freeing block {b} {k} times with refcount '
                    f'{self._refcount[b]}')
        for b in blocks:
            rc = self._refcount[b] - 1
            if rc > 0:
                self._refcount[b] = rc
                continue
            del self._refcount[b]
            h = self._block_hash.get(b)
            if h is not None:
                # Most-recent end of the LRU. Callers release a
                # request's chain DEEPEST-FIRST (reversed) so parents
                # end up younger than children and eviction peels
                # chains from the leaves — evicting a parent first
                # would strand its still-cached descendants
                # (unmatchable until their own LRU turn).
                self._cached[b] = h
            else:
                self._free.append(b)

    # -- prefix cache ---------------------------------------------------

    def match(self, hashes: Sequence[bytes]) -> List[int]:
        """Longest registered prefix of the chain: block ids for
        ``hashes[0..k)`` where every link resolves to a live block
        (cached or referenced). Does NOT pin — callers pin before the
        next alloc can evict."""
        out: List[int] = []
        for h in hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            out.append(b)
        return out

    def lookup(self, hashes: Sequence[bytes]) -> List[Optional[int]]:
        """The live block of each link of the chain, None where the
        group has none (a window group keeps only a chain's tail)."""
        return [self._hash_to_block.get(h) for h in hashes]

    def partial_match(self, parent: bytes,
                      tokens: Sequence[int]
                      ) -> Optional[Tuple[int, int]]:
        """Best partial-block hit past the full-block chain: among
        registered blocks whose chain parent is ``parent``, the one
        sharing the longest leading token run with ``tokens``.
        Returns (block_id, shared_tokens) or None. This is the
        copy-on-write seed — the caller copies the block and
        recomputes from the first divergent token."""
        best: Optional[Tuple[int, int]] = None
        for h in self._by_parent.get(parent, ()):
            b = self._hash_to_block.get(h)
            if b is None:
                continue
            _, cached_tokens = self._hash_meta[h]
            d = 0
            for a, c in zip(tokens, cached_tokens):
                if a != c:
                    break
                d += 1
            if d > 0 and (best is None or d > best[1]):
                best = (b, d)
        return best

    def pin(self, blocks: Sequence[int]) -> None:
        """Take a reference on matched blocks: a cached block leaves
        the LRU (refcount 1); an already-referenced block is shared
        (refcount++). Pinning a block that is neither — freed or
        evicted since the match — is a typed error, so a stale match
        can never alias recycled content."""
        for b in blocks:
            if b in self._cached:
                continue
            if self._refcount.get(b, 0) < 1:
                raise exceptions.KVBlockError(
                    f'pin of unallocated block {b} (stale match?)')
        for b in blocks:
            if b in self._cached:
                del self._cached[b]
                self._refcount[b] = 1
            else:
                self._refcount[b] += 1

    def register(self, block: int, block_hash: bytes, parent: bytes,
                 tokens: Sequence[int]) -> bool:
        """Record that ``block`` holds the FULL token block
        ``tokens`` at chain position ``block_hash`` (parent =
        preceding link). First writer wins: if the hash is already
        registered (a concurrent identical prompt prefilled its own
        copy) the existing block stays canonical and this one simply
        remains unregistered (it returns to the plain free list on
        release). Only a current reference holder may register —
        content of an unreferenced block is not the caller's to
        describe."""
        if self._refcount.get(block, 0) < 1:
            raise exceptions.KVBlockError(
                f'register of unreferenced block {block}')
        if block_hash in self._hash_to_block:
            return False
        if block in self._block_hash:
            # Re-registration under a new chain (COW reuse of an
            # already-registered block id cannot happen — new blocks
            # come unregistered from alloc — but keep the invariant
            # explicit).
            return False
        self._hash_to_block[block_hash] = block
        self._block_hash[block] = block_hash
        self._hash_meta[block_hash] = (parent, tuple(
            int(t) for t in tokens))
        siblings = self._by_parent.setdefault(parent, [])
        if len(siblings) < MAX_PARTIAL_CHILDREN:
            # Bounded COW-candidate index (MAX_PARTIAL_CHILDREN):
            # beyond the cap the block is still exact-matchable via
            # the chain, just not a partial-match seed.
            siblings.append(block_hash)
        return True

    def _unregister(self, block: int, block_hash: bytes) -> None:
        del self._hash_to_block[block_hash]
        del self._block_hash[block]
        parent, _ = self._hash_meta.pop(block_hash)
        siblings = self._by_parent.get(parent)
        if siblings is not None:
            try:
                siblings.remove(block_hash)
            except ValueError:
                pass
            if not siblings:
                del self._by_parent[parent]


class KVBlockPool(BlockGroup):
    """The engine's pool: one ``BlockGroup`` a kind of layer.

    Every configuration the repo ran before window layers has one
    kind, and this object IS that group (its arrays, its allocator,
    ``groups`` = {kind: self}). A stack with window AND global layers
    (``config.layer_kinds``) has two: this object is the 'global'
    group, whose blocks a request keeps for its whole context, and
    ``groups['window']`` a second one of ``window_num_blocks`` blocks
    for the window layers' entries, of which a row holds only the
    columns its window still touches (plus the chunk in flight) and
    gives back what falls behind (``serve/batching.py``). A block id
    names a slot of ONE group; a request has a table a group."""

    def __init__(self, config: llama.LlamaConfig, num_blocks: int,
                 block_size: int, kv_int8: bool = False,
                 shardings=None,
                 window_num_blocks: Optional[int] = None):
        kinds = list(dict.fromkeys(config.layer_kinds))
        primary = 'global' if 'global' in kinds else kinds[0]
        super().__init__(config, num_blocks, block_size, kv_int8,
                         shardings, kind=primary)
        self.groups: Dict[str, BlockGroup] = {primary: self}
        if len(kinds) > 1:
            if window_num_blocks is None:
                raise ValueError(
                    f'{config.name!r} has window and global layers: '
                    f'the window group needs window_num_blocks')
            self.groups['window'] = BlockGroup(
                config, window_num_blocks, block_size, kv_int8,
                shardings, kind='window')


def usable_prefix(present: Sequence[bool], window: int,
                  block_size: int) -> int:
    """The longest prefix-cache hit, in blocks, that a WINDOW group
    can still serve: ``present[i]`` says whether the chain's block i
    is matchable in that group. A hit of k blocks is usable only
    while the group holds every block a query at k * block_size or
    later can still see, i.e. blocks [first_block(k * block_size), k)
    (``first_window_block``): the blocks before them were released
    when they fell behind their row's window, and nothing reads
    them again."""
    run = 0     # matchable blocks in a row, ending at block k - 1
    best = 0
    for k in range(1, len(present) + 1):
        run = run + 1 if present[k - 1] else 0
        if run >= k - first_window_block(k * block_size, window,
                                         block_size):
            best = k
    return best


def first_window_block(next_pos: int, window: int,
                       block_size: int) -> int:
    """The first block a window layer still reads once the next
    query stands at ``next_pos``: it sees keys from ``next_pos -
    window + 1`` on (``ops/decode_attention.window_view``)."""
    return max(next_pos - window + 1, 0) // block_size


def copy_pool_block(caches, src: jax.Array, dst: jax.Array):
    """Copy one block's content ``src`` -> ``dst`` across every
    KV entry of the pool 4-tuple — the COPY-ON-WRITE primitive: a
    partial-block prefix hit duplicates the cached block into a
    private one, then prefill overwrites from the first divergent
    token. ``src``/``dst`` are traced int32 scalars, so one jitted
    executable (caches donated) serves every copy."""
    return tuple(None if c is None else c.at[:, dst].set(c[:, src])
                 for c in caches)


# Re-exported for engine convenience (serve/prefix_hash.py is the
# canonical, jax-free home — the LB's affinity policy imports it
# directly).
ROOT_HASH = prefix_hash.ROOT
chain_hashes = prefix_hash.chain_hashes
block_content_hash = prefix_hash.block_hash


def pool_shardings(config: llama.LlamaConfig, mesh,
                   kv_int8: bool = False):
    """NamedShardings for the pool 4-tuple: KV-head axis over 'tp',
    blocks replicated (pool blocks are shared across requests — only
    the head axis has a natural shard dimension, exactly as in
    ``decode.decode_shardings``). A latent group's one array has no
    head axis (all heads read the same row): it is replicated over
    'tp', and the tuple's other three members are None."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if config.kv_lora_rank is not None:
        return (NamedSharding(mesh, P(None, None, None, None)),
                None, None, None)
    kv = NamedSharding(mesh, P(None, None, None, 'tp', None))
    scale = NamedSharding(mesh, P(None, None, None, 'tp')) \
        if kv_int8 else None
    return (kv, kv, scale, scale)
