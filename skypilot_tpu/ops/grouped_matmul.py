"""The grouped product of the serving expert layer as a Pallas TPU
kernel whose row tile follows the pairs a group holds.

``jax.lax.ragged_dot`` is a kernel of the compiler's own on the TPU,
and its row tile follows ``M``, the whole padded array of pairs (256
or 512 rows at the serving shapes, by the ``ragged_dot_tiling``
attribute of the compiled call): every visit of a group multiplies a
whole row tile against that group's codes with the rows outside the
group masked, so a group of 6 pairs costs the matrix unit 512 rows
(PERF.md, PR 44). ``pair_tiled_matmul`` is the same product with a
row tile of ``row_tile`` rows (64 but for the smallest arrays):

- a small metadata step in plain ``jnp`` (``visits``) lists, for
  each NON-EMPTY group, the row tiles it touches; an empty group is
  never visited and costs nothing;
- the expert codes stay in HBM as the WHOLE stack ``[L, G, K, N]``;
  the kernel's block index is (layer, group) from scalar-prefetched
  values, so no layer's slice is copied (``moe.LayerOf``) and the
  ``(L - 1) x G`` empty groups of the flattened form are not walked;
- a visit streams the group's ``[K, tn]`` code tile once (the grid
  runs the column tiles outermost, so consecutive visits of one group
  re-use the tile in VMEM), converts a depth tile of ``tk`` rows at a
  time to the rows' type (exact: codes are integers under 128),
  multiplies with float32 accumulation, and stores the rows of its
  own group; rows past the last group are left as they are.

The depth tile is fixed by ``K`` alone (``depth_tile``: the
compiler's own choice at these shapes) and summed in order, so a
row's result does not depend on ``M``, on its neighbours or on the
row tile.

Why not ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (jax 0.9.0),
whose visit list and masked store this follows: it refuses int8
(``assert_is_supported_dtype`` admits bf16 and float32 alone) and
takes a 3-D ``rhs`` with no layer index, so it would need the
layer's slice dequantised and copied in every layer of every step
(what ``moe.LayerOf`` exists to prevent).
"""
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The pallas_call's name: what a trace's ``device_ops`` and the
# compiled text call the kernel.
KERNEL_NAME = 'grouped_pair_tiles'

_LANES = 128
# Bytes of int8 codes one column tile of a group holds in VMEM (two
# such buffers are in flight).
_CODE_TILE_BYTES = 2 * 1024 * 1024
# Rows the pair array is padded up to a multiple of (the bf16 sublane
# tile), so that a one-token bucket's 8 pairs take the same kernel.
_ROW_QUANTUM = 16


def _on_tpu() -> bool:
    """Whether the default backend is a TPU (the kernel is Mosaic's;
    every other backend keeps ``jax.lax.ragged_dot``)."""
    return jax.default_backend() == 'tpu'


def _interpret() -> bool:
    """Whether the kernel runs in the Pallas interpreter: where a
    test has made ``_on_tpu`` say yes on another backend."""
    return jax.default_backend() != 'tpu'


def row_tile(m: int) -> int:
    """Rows a visit multiplies: 64, or for the smallest prefill
    buckets the most of 32 and 16 that divides ``m`` (a multiple of
    ``_ROW_QUANTUM``). Timed on the v5e (PERF.md, PR 44; ms a product
    at row tiles of 16 / 32 / 64 / 128 / 256, ``jax.lax.ragged_dot``
    in brackets):
    JoyAI's round, 1,536 pairs over 256 experts of 2,048 x 768,
    0.659 / 0.620 / 0.604 / 0.658 / 1.140 (3.636); its 512-token
    chunk, 4,096 pairs, 0.856 / 0.724 / 0.660 / 0.717 / 1.190
    (3.694); command-a's step, 32 held pairs over 16 experts of
    4,096 x 4,096, 0.349 / 0.334 / 0.337 / 0.361 / 0.623 (0.890);
    Xing4.0's step, 256 pairs over 64 experts of 3,584 x 1,024,
    0.361 / 0.342 / 0.332 / 0.367 / 0.640 (0.919). A weight tile's
    load, not the rows pushed through it, is what the matrix unit
    pays up to 128 rows; a wider tile saves the visits that cross a
    tile's edge (each converts the group's codes again) until the
    rows themselves cost. No crossover to ``jax.lax.ragged_dot`` was
    found at any mean of pairs a group, so the predicate has none
    (ms a product, kernel / ``ragged_dot``, every pair held): 16
    experts of 4,096 x 4,096 at 64 / 256 / 512 / 1,024 pairs a group
    0.663 / 1.776, 1.296 / 2.397, 2.099 / 3.454, 3.701 / 5.348 (a
    512-token chunk of command-a puts 256 there, the most any serving
    shape does); 64 experts of 3,584 x 1,024 at 128 / 256
    0.760 / 1.794, 1.143 / 2.132."""
    return next(t for t in (64, 32, _ROW_QUANTUM) if m % t == 0)


def depth_tile(k: int) -> int:
    """Rows of codes converted and multiplied at a time, by ``K``
    alone: 512 where it divides ``K``, else 256, else 128 (what the
    compiler's ``ragged_dot`` takes at the serving shapes)."""
    return next(t for t in (512, 256, _LANES) if k % t == 0)


def column_tile(k: int, n: int) -> int:
    """Columns of a group's codes a visit holds: the widest whole
    number of lane tiles dividing ``n`` whose ``k`` rows of codes fit
    ``_CODE_TILE_BYTES``."""
    lanes = n // _LANES
    fit = max(_CODE_TILE_BYTES // (k * _LANES), 1)
    return _LANES * max(d for d in range(1, lanes + 1)
                        if lanes % d == 0 and d <= fit)


def tiles_engage(k: int, n: int, *, codes: bool, rows_dtype) -> bool:
    """Whether a grouped product over groups of ``[k, n]`` goes
    through ``pair_tiled_matmul`` or stays ``jax.lax.ragged_dot``,
    from what the code can observe and nothing else: a TPU, int8
    expert codes (``codes``), bf16 or float32 rows, ``k`` and ``n``
    whole lane tiles. However many pair rows there are: on a TPU the
    int8 stack has one product. ``moe._grouped`` asks it to choose its
    product, the engine (once, when it is built) to count; it has no
    other input."""
    return (_on_tpu() and codes
            and jnp.dtype(rows_dtype) in (jnp.bfloat16, jnp.float32)
            and k % _LANES == 0 and n % _LANES == 0)


def visits(sizes: jax.Array, m: int, tm: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The kernel's walk over ``sizes`` [G] (rows sorted by group,
    ``sizes.sum() <= m``): (offsets [G + 1], the group of each visit
    [V], its row tile [V], the number of visits [1]), V = m / tm +
    G - 1. A non-empty group is visited once for each row tile of
    ``tm`` rows it touches, groups in order; entries past the count
    repeat valid indices and are never run."""
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        (upto[None, :] <= v[:, None]).sum(-1, dtype=jnp.int32), g - 1)
    tile = jnp.clip(first[group] + v - (upto - tiles)[group], 0,
                    m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, upto[-1:]


def _kernel(layer_ref, offsets_ref, group_ref, tile_ref, x_ref, w_ref,
            o_ref, *, tk: int):
    """One visit of one column tile (grid: column tiles, visits):
    the row tile ``tile_ref[v]`` of the rows times the column tile of
    group ``group_ref[v]``'s codes, stored to the rows of that
    group."""
    del layer_ref
    v = pl.program_id(1)
    tm, k = x_ref.shape
    tn = o_ref.shape[1]
    # bf16 rows on codes that are exact in bf16, summed in float32,
    # whatever the process's default matmul precision is set to
    # (Mosaic refuses a higher one for bf16); float32 rows in full.
    precision = (jax.lax.Precision.DEFAULT
                 if x_ref.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    acc = jnp.zeros((tm, tn), jnp.float32)
    for c in range(0, k, tk):
        acc += jax.lax.dot_general(
            x_ref[:, c:c + tk],
            w_ref[c:c + tk, :].astype(x_ref.dtype),
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
    g = group_ref[v]
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, tn), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    o_ref[...] = jnp.where(mine, acc, o_ref[...])


@jax.jit
def pair_tiled_matmul(xs: jax.Array, stack: jax.Array,
                      layer: jax.Array, sizes: jax.Array) -> jax.Array:
    """``jax.lax.ragged_dot(xs, stack[layer], sizes,
    preferred_element_type=float32)`` for int8 ``stack`` [L, G, K, N]
    and rows ``xs`` [M, K] sorted by group into ``sizes`` [G]: float32
    [M, N]. Rows past ``sizes.sum()`` hold whatever was there.

    Jitted for the sake of start-up alone: an engine's forty programs
    call it three times in each expert layer they trace, and under
    ``jit`` the kernel is traced once for a set of shapes and lowered
    once for a program, where each call paid for its own (PERF.md,
    PR 44: 8 s of command-a-plus's start-up)."""
    rows = xs.shape[0]
    xs = jnp.pad(xs, ((0, -rows % _ROW_QUANTUM), (0, 0)))
    m, k = xs.shape
    n_layers, g, _, n = stack.shape
    tm, tk, tn = row_tile(m), depth_tile(k), column_tile(k, n)
    offsets, group, tile, count = visits(sizes, m, tm)
    layer = jnp.clip(layer, 0, n_layers - 1).astype(jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_kernel, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, count[0]),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, la, of, gr, ti: (ti[v], 0)),
                pl.BlockSpec((None, None, k, tn),
                             lambda j, v, la, of, gr, ti:
                             (la[0], gr[v], 0, j))],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, la, of, gr, ti: (ti[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(layer, offsets, group, tile, xs, stack)[:rows]
