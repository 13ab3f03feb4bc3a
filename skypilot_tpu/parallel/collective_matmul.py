"""Tensor-parallel products whose transfers run beside them.

With the residual stream sharded along the sequence over ``tp``, a
layer's column-parallel products (q/k/v, gate/up) need the whole
sequence and its row-parallel products (``wo``, ``w_down``) leave a
partial sum on every ``tp`` device. GSPMD spells those an all-gather
before and an all-reduce after the product, each run alone: the norm
that follows needs the result at once. Here each product is a
*collective matmul* in ``tp`` steps, written in the program under a
``shard_map`` that is manual over ``tp`` only (``dp``/``fsdp``/``ep``
stay GSPMD's, so the weight all-gathers over ``fsdp`` are placed as
before):

- *gather side*: a device multiplies the sequence block it holds
  while that block travels on to its neighbour by ``ppermute``, then
  multiplies the block that arrived;
- *scatter side*: a device computes the partial result for the block
  its neighbour needs next, sends the running sum on and adds the
  partial for the following block to what arrives; after ``tp`` steps
  it holds the whole sum for its own block.

The backward comes from differentiating this code: the transpose of a
gather-side product is a scatter-side one and the reverse. Sums over
``tp`` accumulate in the product's own output type, as GSPMD's
all-reduce of the same partials does.

Sequence blocks are named by their offset from the device's own,
``d = (block - axis_index) % tp``, which is static: no step needs a
dynamic index unless it reads or writes a whole-sequence array.
"""
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AXIS = 'tp'
_DATA = ('dp', 'fsdp', 'ep')

# Products of a layer built in the overlapped form (q/k/v, wo,
# gate/up, w_down): docs/observability.md.
OVERLAPPED_GAUGE = 'skytpu_train_tp_overlapped_products'
PRODUCTS_PER_LAYER = 4


def overlapped_gauge():
    from skypilot_tpu import metrics as metrics_lib
    return metrics_lib.registry().gauge(
        OVERLAPPED_GAUGE,
        'tp products of a layer that the train step built as '
        'collective matmuls (4: the transfers over tp run beside the '
        'products; 0: GSPMD places all-reduces).')


def _col_spec(leaf) -> P:
    """Column-parallel operand ([D, N] weight, [1, N] scale, [N]
    bias): the last axis over tp."""
    return P(*([None] * (leaf.ndim - 1)), AXIS)


def _row_spec(leaf) -> P:
    """Row-parallel operand ([N, D] weight: the contraction axis over
    tp); a quantized weight's [1, D] scale has that axis collapsed
    and is whole on every device (``train._scale_spec``)."""
    if leaf.shape[-2] == 1:
        return P()
    return P(*([None] * (leaf.ndim - 2)), AXIS, None)


class TpOverlap:
    """The collective products of one mesh, handed to ``llama._layer``
    by ``build_train_step`` when the mesh has ``tp`` > 1."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.tp = mesh.shape[AXIS]
        # [B, T, D] between the blocks, and whole again for the head.
        self.seq_sharding = NamedSharding(mesh, P(_DATA, AXIS, None))
        self.whole_sharding = NamedSharding(mesh, P(_DATA, None, None))
        self._ring = [(i, (i + 1) % self.tp) for i in range(self.tp)]

    def for_sequence(self, seq_len: int):
        """This object if blocks of ``seq_len`` divide over tp, else
        None (the caller then takes GSPMD's path); the gauge follows
        what the step being traced was built as."""
        engaged = seq_len % self.tp == 0
        overlapped_gauge().set(PRODUCTS_PER_LAYER if engaged else 0)
        return self if engaged else None

    # ---- inside the manual region ------------------------------
    def _send(self, x):
        return jax.lax.ppermute(x, AXIS, self._ring)

    def _gathered(self, block):
        """``out[d]`` = the sequence block at offset d from this
        device's own, for every d: the own block, then what each
        further ``ppermute`` brings (step s delivers offset -s)."""
        out = {0: block}
        for s in range(1, self.tp):
            block = self._send(block)
            out[(self.tp - s) % self.tp] = block
        return out

    def _scattered(self, partial_at: Callable[[int], Any]):
        """Sum over tp of ``partial_at(d)``, each device ending with
        the sum for its own block (d = 0): the running sum for offset
        d moves on to the neighbour, for whom it is offset d - 1,
        while this device computes its partial for that offset."""
        acc = partial_at(self.tp - 1)
        for d in range(self.tp - 2, -1, -1):
            acc = jax.tree.map(jnp.add, self._send(acc), partial_at(d))
        return acc

    def _block_start(self, d: int, block_len: int):
        return ((jax.lax.axis_index(AXIS) + d) % self.tp) * block_len

    def _shard_map(self, body, in_specs, out_specs):
        return jax.shard_map(body, mesh=self.mesh,
                             axis_names=frozenset({AXIS}),
                             in_specs=in_specs, out_specs=out_specs)

    # ---- the three forms a layer uses --------------------------
    def gather_apply(self, fn, h, cols, replicated=None):
        """``fn(h_block, cols, replicated)`` over the whole sequence:
        h is [B, T, D] sharded along T; ``cols`` are column-parallel
        operands, ``replicated`` whole on every tp device. fn returns
        arrays [B, t, N_local, ...]; they come back [B, T, N, ...]
        with axis 2 over tp (what the flash call's specs want)."""

        def body(h_blk, cols_l, rep):
            t = h_blk.shape[1]
            out = None
            for d, blk in self._gathered(h_blk).items():
                part = fn(blk, cols_l, rep)
                if out is None:
                    out = jax.tree.map(
                        lambda p: jnp.zeros(
                            (p.shape[0], t * self.tp) + p.shape[2:],
                            p.dtype), part)
                start = self._block_start(d, t)
                out = jax.tree.map(
                    functools.partial(
                        jax.lax.dynamic_update_slice_in_dim,
                        start_index=start, axis=1), out, part)
            return out

        return self._shard_map(
            body, (P(None, AXIS), jax.tree.map(_col_spec, cols), P()),
            P(None, None, AXIS))(h, cols, replicated)

    def scatter_apply(self, fn, x, rows):
        """Sum over tp of ``fn(x_block, rows)``: x is [B, T, N] with
        N over tp, ``rows`` row-parallel operands; returns [B, T, D]
        sharded along T."""

        def body(x_l, rows_l):
            t = x_l.shape[1] // self.tp
            return self._scattered(lambda d: fn(
                jax.lax.dynamic_slice_in_dim(
                    x_l, self._block_start(d, t), t, axis=1), rows_l))

        return self._shard_map(
            body, (P(None, None, AXIS), jax.tree.map(_row_spec, rows)),
            P(None, AXIS))(x, rows)

    def gather_scatter_apply(self, up_fn, down_fn, h, cols, rows):
        """``down_fn(up_fn(h, cols), rows)`` with h and the result
        [B, T, D] sharded along T: a gather-side product feeding a
        scatter-side one block by block (the gated MLP), so nothing
        of the whole sequence is ever assembled."""

        def body(h_blk, cols_l, rows_l):
            mids = {d: up_fn(blk, cols_l)
                    for d, blk in self._gathered(h_blk).items()}
            return self._scattered(
                lambda d: down_fn(mids[d], rows_l))

        return self._shard_map(
            body, (P(None, AXIS), jax.tree.map(_col_spec, cols),
                   jax.tree.map(_row_spec, rows)),
            P(None, AXIS))(h, cols, rows)
