"""Attention ops: XLA reference implementation + Pallas TPU flash
attention (forward AND backward kernels, native GQA).

``flash_attention`` dispatches to Pallas kernels on TPU (block-tiled,
online-softmax, O(seq) memory) and to the XLA reference elsewhere
(tests run the kernels in interpret mode on the CPU backend).

Design notes (TPU-first):
- Kernels operate on a [B, H, T, D] layout so every block DMA is a
  contiguous [rows, D] tile; the caller's transpose from the model's
  [B, T, H, D] is absorbed into the preceding projection's output
  layout by XLA.
- GQA is native: K/V stay at [B, Hkv, S, D] and the kernel grid maps
  query head h to KV head h // (H // Hkv) in the BlockSpec index_map —
  no jnp.repeat, so K/V HBM traffic is 1/group of the naive version.
- MXU dots run in bf16 x bf16 -> f32 (``preferred_element_type``);
  softmax statistics and accumulators are f32. Scaling is applied to
  the f32 logits after the dot so the operands stay bf16.
- Backward is the FlashAttention-2 split: a dQ kernel gridded over
  (B, H, q-blocks) and a dK/dV kernel gridded over (B, Hkv, k-blocks)
  that accumulates over the KV-head's query group in-kernel. Both
  recompute probabilities from the saved (q, k, v, lse) — only
  O(B*H*T) statistics are saved, never the [T, S] matrix.
- Causal masking is bottom-right aligned (q_pos + S - T >= k_pos),
  matching ``dot_product_attention``'s ``tril(k=s-t)`` so cross-length
  decode/prefill attention is consistent between the two paths.

The reference framework has no TPU attention kernel at all (its
compute path is user code / HF Trainer, see BASELINE.md); this module
is the TPU-native replacement for the torch SDPA the reference's
recipes rely on.
"""
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from skypilot_tpu import tpu_logging

logger = tpu_logging.init_logger(__name__)

# Default flash tile sizes; env-overridable for block-size sweeps on
# new chips/shapes without touching call sites (read at import).
_DEFAULT_BLOCK_Q = int(os.environ.get('SKYTPU_FLASH_BLOCK_Q', '512'))
_DEFAULT_BLOCK_K = int(os.environ.get('SKYTPU_FLASH_BLOCK_K', '512'))
_ENV_BLOCK_Q_BWD = os.environ.get('SKYTPU_FLASH_BLOCK_Q_BWD')
_ENV_BLOCK_K_BWD = os.environ.get('SKYTPU_FLASH_BLOCK_K_BWD')
_NEG_INF = -1e30
# The kernels work in the log2 domain: scale*log2(e) is folded into q
# (or k) ONCE per program and the softmax uses exp2 — removing the
# per-score-element `* scale` multiply and the exp->exp2 conversion
# multiply. At head_dim 64 these kernels are VPU-bound on the
# [block_q, block_k] elementwise ops, so every op per score element
# is ~15% of kernel time. The saved lse residual is in the log2
# domain too (internal contract between _fwd/_bwd only).
_LOG2E = 1.4426950408889634
# f32 min sublane tile: statistics (lse/delta) are stored [B, H, 8, T]
# with 8 broadcast sublanes so their (8, block) VMEM tiles satisfy
# Mosaic's (8, 128) f32 minimum.
_STAT_SUBLANES = 8


# pallas_call names: the trace reduction and the lowered-step check
# (recipes/finetune.py) find the kernels by these.
KERNEL_NAMES = ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv')


def _on_tpu() -> bool:
    """Whether the default backend is a TPU. A backend that cannot
    start raises here (JAX's own error): guessing "not on TPU" would
    silently train on the XLA reference path."""
    return jax.default_backend() == 'tpu'


# ---------------------------------------------------------------------
# Reference implementation (XLA). Used on CPU and as the numerics
# oracle in tests.
# ---------------------------------------------------------------------


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = True,
                          scale: Optional[float] = None) -> jax.Array:
    """Plain attention. q: [B,T,H,D]; k,v: [B,S,Hkv,D] -> [B,T,H,D]."""
    _, t, h, d = q.shape
    _, s, hkv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    groups = h // hkv
    if scale is None:
        scale = d ** -0.5
    # Fold query heads into KV groups: [B,T,Hkv,G,D]
    qg = q.reshape(q.shape[0], t, hkv, groups, d)
    logits = jnp.einsum('bthgd,bshd->bhgts', qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhgts,bshd->bthgd', probs.astype(v.dtype), v)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------
# Pallas TPU kernels. Layout: q/o [B, H, T, D]; k/v [B, Hkv, S, D];
# statistics [B, H, 8, T] (f32, sublane-broadcast).
# ---------------------------------------------------------------------


def _causal_bounds(q_idx, block_q, block_k, offset, num_kb):
    """Shared causal block-bound math for the fwd and dQ kernels.

    Returns (n_full, last_kb, relpos): K blocks [0, n_full) are fully
    visible for this q block, [n_full, last_kb) straddle the diagonal
    (mask with ``relpos >= kb * block_k``), and [last_kb, num_kb) are
    fully hidden. ``relpos[r, c] = q_pos(r) + offset - c`` is hoisted
    here so the diagonal loop only pays a scalar shift per block.
    """
    from jax.experimental import pallas as pl

    n_full = jnp.clip(
        (q_idx * block_q + offset + 1 - block_k) // block_k + 1,
        0, num_kb)
    last_kb = jnp.clip(
        pl.cdiv((q_idx + 1) * block_q + offset, block_k), 0, num_kb)
    relpos = (q_idx * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) -
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    return n_full, last_kb, relpos


def _rot_half_matrix(d, dtype):
    """[D, D] constant J with x @ J == concat(-x2, x1) — rotate-half
    as a tiny MXU matmul. Lane-offset slicing/concat on [R, D] blocks
    compiles to expensive lane shuffles on the VPU; a permutation
    matmul is effectively free next to the kernel's main dots."""
    d2 = d // 2
    eye = jnp.eye(d2, dtype=dtype)
    zero = jnp.zeros((d2, d2), dtype=dtype)
    return jnp.concatenate([
        jnp.concatenate([zero, eye], axis=1),
        jnp.concatenate([-eye, zero], axis=1),
    ], axis=0)


def _rot(x, cos, sin):
    """Apply rotate-half RoPE to a [R, D] block.

    cos/sin: [R, D] f32, the half-angle tables duplicated to full
    width (cos = [c, c], sin = [s, s]). Runs on VMEM-resident blocks
    inside the kernels — fusing RoPE here removes the separate f32
    rope/convert passes over HBM that otherwise cost ~5 ms/layer at
    (8, 2048) on v5e.
    """
    # bf16 operands are exact under the default precision (one +-x
    # term per output, f32 accumulate); f32 operands need HIGHEST or
    # the MXU truncates them to bf16. Mosaic rejects fp32 contract
    # precision on bf16 vectors, so pick per dtype.
    prec = (jax.lax.Precision.HIGHEST
            if x.dtype == jnp.float32 else None)
    swap = jnp.dot(x, _rot_half_matrix(x.shape[-1], x.dtype),
                   preferred_element_type=jnp.float32, precision=prec)
    return (x.astype(jnp.float32) * cos + swap * sin).astype(x.dtype)


def _rot_inv(g, cos, sin):
    """Transpose (= inverse) rotation: pull a gradient back through
    ``_rot``. g: [R, D] (any float dtype); cos/sin: [R, D] f32."""
    gf = g.astype(jnp.float32)
    # J^T == -J, so inverse swap is x @ (-J).
    swap = jnp.dot(gf, -_rot_half_matrix(g.shape[-1], jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    return (gf * cos + swap * sin).astype(g.dtype)


def _fwd_kernel(*refs, scale, causal, block_k, seq_q, seq_k,
                fuse_rope=False):
    """One (b, h, q-block) program: stream K/V blocks with online
    softmax. Refs: q [Bq, D]; k/v [S, D]; (cos/sin [T, D/2] when
    fuse_rope); o [Bq, D]; lse [8, Bq].

    Causal masking is applied only to blocks straddling the diagonal;
    fully-visible blocks run a mask-free body and fully-hidden blocks
    are skipped by the loop bound. The iota for the diagonal mask is
    hoisted out of the loop — the VPU (mask/exp/select) is the
    bottleneck of this kernel at head_dim 64, not the MXU.
    """
    from jax.experimental import pallas as pl

    if fuse_rope:
        q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        cos_ref = sin_ref = None

    q = q_ref[...]  # bf16 — stays bf16 for the MXU
    block_q = q.shape[0]
    d = q.shape[-1]
    q_idx = pl.program_id(2)
    offset = seq_k - seq_q  # bottom-right causal alignment
    if fuse_rope:
        q = _rot(q, cos_ref[pl.ds(q_idx * block_q, block_q), :],
                 sin_ref[pl.ds(q_idx * block_q, block_q), :])
    # Fold scale*log2e into q once (one [Bq, D] op) so the streamed
    # loop below never multiplies a [Bq, Bk] score block.
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)

    m = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    num_kb = seq_k // block_k
    if causal:
        n_full, last_kb, relpos = _causal_bounds(
            q_idx, block_q, block_k, offset, num_kb)

    def body(kb, carry, masked):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        if fuse_rope:
            k_blk = _rot(k_blk,
                         cos_ref[pl.ds(kb * block_k, block_k), :],
                         sin_ref[pl.ds(kb * block_k, block_k), :])
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32)  # log2 dom.
        if masked:
            s = jnp.where(relpos >= kb * block_k, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        carry = jax.lax.fori_loop(
            0, n_full, functools.partial(body, masked=False),
            (m, l, acc))
        m, l, acc = jax.lax.fori_loop(
            n_full, last_kb, functools.partial(body, masked=True),
            carry)
    else:
        m, l, acc = jax.lax.fori_loop(
            0, num_kb, functools.partial(body, masked=False),
            (m, l, acc))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[:, None]
    lse = m + jnp.log2(l_safe)  # log2 domain (bwd contract)
    if causal and offset < 0:
        # seq_q > seq_k: rows with q_pos + offset < 0 see NO keys. In
        # a straddling block every logit is _NEG_INF, so m == _NEG_INF
        # and p = exp(0) degenerates to a uniform average — fix up
        # such rows to out = 0 and lse = +BIG (making the backward's
        # exp(s - lse) exactly 0, hence zero gradients). Only compiled
        # in for the t > s case.
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        valid = (q_idx * block_q + row + offset) >= 0
        out = jnp.where(valid, out, 0.0)
        lse = jnp.where(valid[:, 0], lse, -_NEG_INF)
    o_ref[...] = out.astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(
        lse.astype(jnp.float32)[None, :], lse_ref.shape)


def _bwd_dq_kernel(*refs, scale, causal, block_k, seq_q, seq_k,
                   fuse_rope=False):
    """dQ for one (b, h, q-block): recompute P blockwise from lse.
    Refs: q/do/dq [Bq, D]; k/v [S, D]; lse/delta [8, Bq]. With
    fuse_rope the saved q/k are un-rotated: rotate on load, and pull
    the accumulated gradient back through the (orthogonal) rotation
    before writing dq."""
    from jax.experimental import pallas as pl

    if fuse_rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, cos_ref,
         sin_ref, dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
        cos_ref = sin_ref = None

    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[0, :]      # [Bq], log2 domain
    delta = delta_ref[0, :]  # [Bq]
    block_q, d = q.shape
    q_idx = pl.program_id(2)
    offset = seq_k - seq_q
    if fuse_rope:
        cos_q = cos_ref[pl.ds(q_idx * block_q, block_q), :]
        sin_q = sin_ref[pl.ds(q_idx * block_q, block_q), :]
        q = _rot(q, cos_q, sin_q)
    # Same scale*log2e fold as the forward; the deferred `* scale`
    # on ds is applied once to the accumulated dq at the end.
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)

    acc = jnp.zeros((block_q, d), jnp.float32)
    num_kb = seq_k // block_k
    if causal:
        n_full, last_kb, relpos = _causal_bounds(
            q_idx, block_q, block_k, offset, num_kb)

    def body(kb, acc, masked):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        if fuse_rope:
            k_blk = _rot(k_blk,
                         cos_ref[pl.ds(kb * block_k, block_k), :],
                         sin_ref[pl.ds(kb * block_k, block_k), :])
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32)  # log2 dom.
        if masked:
            s = jnp.where(relpos >= kb * block_k, s, _NEG_INF)
        p = jnp.exp2(s - lse[:, None])          # masked -> exp2(-inf)=0
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return acc + jnp.dot(ds.astype(k_blk.dtype), k_blk,
                             preferred_element_type=jnp.float32)

    if causal:
        acc = jax.lax.fori_loop(
            0, n_full, functools.partial(body, masked=False), acc)
        acc = jax.lax.fori_loop(
            n_full, last_kb, functools.partial(body, masked=True), acc)
    else:
        acc = jax.lax.fori_loop(
            0, num_kb, functools.partial(body, masked=False), acc)
    acc = acc * scale
    if fuse_rope:
        acc = _rot_inv(acc, cos_q, sin_q)
    dq_ref[...] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, seq_q, seq_k,
                    fuse_rope=False):
    """dK/dV for one (b, kv-head, k-block, group-member) program.

    Native GQA: the grid's innermost dimension iterates the KV head's
    query-group members; the dk/dv output block index is independent
    of it, so the f32 accumulators stay resident in VMEM across the
    group and the contributions reduce in-place (zeroed at g == 0) —
    no repeated K/V is ever materialized. Refs: q/do [T, D];
    k/v [Bk, D]; lse/delta [8, T]; dk/dv [Bk, D] f32. With fuse_rope
    (un-rotated saved q/k) the dk accumulator lives in rotated space
    and is pulled back through the rotation before the += — the
    rotation is linear, so per-group-member pullback sums correctly.
    """
    from jax.experimental import pallas as pl

    if fuse_rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, cos_ref,
         sin_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref) = refs
        cos_ref = sin_ref = None

    k_blk = k_ref[...]
    v_blk = v_ref[...]
    block_k, d = k_blk.shape
    k_idx = pl.program_id(2)
    g = pl.program_id(3)
    offset = seq_k - seq_q
    if fuse_rope:
        cos_k = cos_ref[pl.ds(k_idx * block_k, block_k), :]
        sin_k = sin_ref[pl.ds(k_idx * block_k, block_k), :]
        k_blk = _rot(k_blk, cos_k, sin_k)
    # Fold scale*log2e into K here (K is resident across the whole
    # q loop; q must stay raw for the dk accumulation dot).
    k2 = (k_blk.astype(jnp.float32) *
          (scale * _LOG2E)).astype(k_blk.dtype)

    @pl.when(g == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    dk_acc = jnp.zeros((block_k, d), jnp.float32)
    dv_acc = jnp.zeros((block_k, d), jnp.float32)
    num_qb = seq_q // block_q

    if causal:
        # q_pos + offset >= k_pos; smallest k_pos in this block is
        # k_idx*block_k, so q blocks strictly before
        # (k_idx*block_k - offset) // block_q contribute nothing, and
        # q blocks whose min q_pos + offset >= max k_pos are fully
        # visible (mask-free body).
        start_qb = jnp.clip((k_idx * block_k - offset) // block_q, 0,
                            num_qb)
        first_full_qb = jnp.clip(
            pl.cdiv((k_idx + 1) * block_k - 1 - offset, block_q), 0,
            num_qb)
        relpos = (offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) -
            (k_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)))

    def body(qb, carry, masked=False):
        dk_acc, dv_acc = carry
        q_blk = q_ref[pl.ds(qb * block_q, block_q), :]
        if fuse_rope:
            q_blk = _rot(q_blk,
                         cos_ref[pl.ds(qb * block_q, block_q), :],
                         sin_ref[pl.ds(qb * block_q, block_q), :])
        do_blk = do_ref[pl.ds(qb * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(qb * block_q, block_q)]
        delta_blk = delta_ref[0, pl.ds(qb * block_q, block_q)]
        s = jnp.dot(q_blk, k2.T,
                    preferred_element_type=jnp.float32)  # log2 dom.
        if masked:
            s = jnp.where(relpos + qb * block_q >= 0, s, _NEG_INF)
        p = jnp.exp2(s - lse_blk[:, None])
        pt = p.astype(do_blk.dtype).T
        dv_new = dv_acc + jnp.dot(
            pt, do_blk, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v_blk.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None])
        dk_new = dk_acc + jnp.dot(
            ds.astype(q_blk.dtype).T, q_blk,
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    if causal:
        carry = jax.lax.fori_loop(
            start_qb, first_full_qb,
            functools.partial(body, masked=True), (dk_acc, dv_acc))
        dk_acc, dv_acc = jax.lax.fori_loop(
            first_full_qb, num_qb,
            functools.partial(body, masked=False), carry)
    else:
        dk_acc, dv_acc = jax.lax.fori_loop(0, num_qb, body,
                                           (dk_acc, dv_acc))

    dk_acc = dk_acc * scale  # deferred from ds (see fold above)
    if fuse_rope:
        dk_acc = _rot_inv(dk_acc, cos_k, sin_k)
    dk_ref[...] += dk_acc
    dv_ref[...] += dv_acc


# ---------------------------------------------------------------------
# pallas_call wrappers. All take q [B, H, T, D], k/v [B, Hkv, S, D].
# ---------------------------------------------------------------------


def _fwd_pallas(q, k, v, cos=None, sin=None, *, scale, causal,
                block_q, block_k, interpret=False):
    from jax.experimental import pallas as pl

    b, h, t, d = q.shape
    _, hkv, s, _ = k.shape
    groups = h // hkv
    block_q = min(block_q, t)
    block_k = min(block_k, s)
    grid = (b, h, t // block_q)
    fuse_rope = cos is not None

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_q=t, seq_k=s,
                               fuse_rope=fuse_rope)
    kv_spec = pl.BlockSpec((None, None, s, d),
                           lambda b, hh, i: (b, hh // groups, 0, 0))
    in_specs = [
        pl.BlockSpec((None, None, block_q, d),
                     lambda b, hh, i: (b, hh, i, 0)),
        kv_spec,
        kv_spec,
    ]
    inputs = [q, k, v]
    if fuse_rope:
        rope_spec = pl.BlockSpec((t, d), lambda b, hh, i: (0, 0))
        in_specs += [rope_spec, rope_spec]
        inputs += [cos, sin]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, hh, i: (b, hh, i, 0)),
            pl.BlockSpec((None, None, _STAT_SUBLANES, block_q),
                         lambda b, hh, i: (b, hh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, _STAT_SUBLANES, t),
                                 jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(*inputs)
    return out, lse


def _bwd_pallas(q, k, v, out, lse, do, cos=None, sin=None, *, scale,
                causal, block_q, block_k, interpret=False):
    from jax.experimental import pallas as pl

    b, h, t, d = q.shape
    _, hkv, s, _ = k.shape
    groups = h // hkv
    block_q = min(block_q, t)
    block_k = min(block_k, s)
    fuse_rope = cos is not None

    # delta[b,h,i] = sum_d dO * O — one fused XLA pass, then sublane-
    # broadcast to the same [B, H, 8, T] layout as lse.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[:, :, None, :],
                             (b, h, _STAT_SUBLANES, t))
    if lse.ndim == 3:
        lse = jnp.broadcast_to(lse[:, :, None, :],
                               (b, h, _STAT_SUBLANES, t))

    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda b, hh, i: (b, hh, i, 0))
    kv_full_spec = pl.BlockSpec((None, None, s, d),
                                lambda b, hh, i: (b, hh // groups, 0,
                                                  0))
    stat_spec = pl.BlockSpec((None, None, _STAT_SUBLANES, block_q),
                             lambda b, hh, i: (b, hh, 0, i))

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_k=block_k,
                                  seq_q=t, seq_k=s,
                                  fuse_rope=fuse_rope)
    dq_in_specs = [q_spec, kv_full_spec, kv_full_spec, q_spec,
                   stat_spec, stat_spec]
    dq_inputs = [q, k, v, do, lse, delta]
    if fuse_rope:
        rope_spec = pl.BlockSpec((t, d),
                                 lambda b, hh, i: (0, 0))
        dq_in_specs += [rope_spec, rope_spec]
        dq_inputs += [cos, sin]
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, t // block_q),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(*dq_inputs)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   seq_q=t, seq_k=s,
                                   fuse_rope=fuse_rope)
    # Grid: group member g innermost so the dk/dv output block index
    # (b, kv_head, j) is constant across g — Pallas keeps the block in
    # VMEM and the kernel accumulates into it.
    qg_spec = pl.BlockSpec((None, None, t, d),
                           lambda b, kvh, j, g: (b, kvh * groups + g,
                                                 0, 0))
    kv_blk_spec = pl.BlockSpec((None, None, block_k, d),
                               lambda b, kvh, j, g: (b, kvh, j, 0))
    statg_spec = pl.BlockSpec((None, None, _STAT_SUBLANES, t),
                              lambda b, kvh, j, g: (b,
                                                    kvh * groups + g,
                                                    0, 0))
    dkv_in_specs = [qg_spec, kv_blk_spec, kv_blk_spec, qg_spec,
                    statg_spec, statg_spec]
    dkv_inputs = [q, k, v, do, lse, delta]
    if fuse_rope:
        rope_g_spec = pl.BlockSpec((t, d),
                                   lambda b, kvh, j, g: (0, 0))
        dkv_in_specs += [rope_g_spec, rope_g_spec]
        dkv_inputs += [cos, sin]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, s // block_k, groups),
        in_specs=dkv_in_specs,
        out_specs=[kv_blk_spec, kv_blk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, s, d), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(*dkv_inputs)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------
# custom VJP wrapper (on the [B, H, T, D] kernel layout).
# ---------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10,
                                                    11))
def _flash_attention(q, k, v, cos, sin, causal, scale, block_q,
                     block_k, block_q_bwd, block_k_bwd, interpret):
    out, _ = _fwd_pallas(q, k, v, cos, sin, scale=scale,
                         causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, cos, sin, causal, scale, block_q,
                    block_k, block_q_bwd, block_k_bwd, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd_pallas(q, k, v, cos, sin, scale=scale,
                           causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    # Residuals are tagged so a surrounding jax.checkpoint with the
    # ``remat_policy()`` policy saves them instead of re-running the
    # forward kernel during backward (q/k/v stay rematerialized — they
    # are cheap MXU projections; with fused RoPE they are saved
    # UN-rotated and the backward kernels re-rotate in VMEM). lse is
    # saved de-duplicated [B,H,T]; the bwd wrapper re-broadcasts the
    # stat sublanes.
    out = checkpoint_name(out, 'flash_attn_out')
    lse = checkpoint_name(lse[:, :, 0, :], 'flash_attn_lse')
    return out, (q, k, v, cos, sin, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, block_q_bwd,
                    block_k_bwd, interpret, residuals, do):
    q, k, v, cos, sin, out, lse = residuals
    dq, dk, dv = _bwd_pallas(q, k, v, out, lse, do, cos, sin,
                             scale=scale, causal=causal,
                             block_q=block_q_bwd, block_k=block_k_bwd,
                             interpret=interpret)
    # cos/sin carry no gradient (positions are not trained); None
    # matches their (possibly-None) primal pytree structure. An
    # XLA pre-rotate-in-bwd variant measured ~7% SLOWER end-to-end
    # than in-kernel rotation (extra full q/k/dq/dk HBM passes).
    dcos = None if cos is None else jnp.zeros_like(cos)
    dsin = None if sin is None else jnp.zeros_like(sin)
    return dq, dk, dv, dcos, dsin


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def remat_policy(base_policy=None):
    """Checkpoint policy that saves the flash-attention kernel's
    outputs (out + lse) so layer-level remat does not re-run the
    forward kernel in backward. Compose with ``jax.checkpoint``:

        jax.checkpoint(layer_fn, policy=attention.remat_policy())

    ``base_policy``: optional policy to OR with (e.g.
    ``jax.checkpoint_policies.save_only_these_names(...)``).
    """
    names_policy = jax.checkpoint_policies.save_only_these_names(
        'flash_attn_out', 'flash_attn_lse')
    if base_policy is None:
        return names_policy
    return jax.checkpoint_policies.save_from_both_policies(
        names_policy, base_policy)


# ---------------------------------------------------------------------
# Public entry.
# ---------------------------------------------------------------------


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate-half RoPE on [B, T, H, D]; angles [T, D/2] f32. XLA
    path — used by the non-Pallas fallback and by callers that keep
    RoPE outside the kernel (ring attention shards)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = _DEFAULT_BLOCK_Q,
                    block_k: int = _DEFAULT_BLOCK_K,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    rope_angles: Optional[jax.Array] = None,
                    force_pallas: bool = False,
                    interpret: bool = False) -> jax.Array:
    """Flash attention. q: [B,T,H,D]; k,v: [B,S,Hkv,D] -> [B,T,H,D].

    On TPU (or with force_pallas) uses the Pallas kernels; elsewhere
    falls back to the XLA reference so the same model code runs in
    CPU tests. ``interpret=True`` runs the kernels in the Pallas
    interpreter (kernel unit tests on CPU).

    ``rope_angles`` ([T, D/2] f32, requires t == s): apply RoPE to
    q and k INSIDE the kernels, on VMEM-resident blocks — callers
    pass un-rotated q/k and skip the separate rope pass over HBM.
    """
    b, t, h, d = q.shape
    _, s, hkv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    if rope_angles is not None:
        assert t == s, ('fused RoPE assumes aligned self-attention '
                        'positions', t, s)
    if scale is None:
        scale = d ** -0.5
    # Separate bwd block sizes are exposed for tuning. Isolated
    # sweeps favored a (256, 512) bwd tile, but in-model (where XLA
    # owns the surrounding layouts) reusing the fwd (512, 512) tile
    # measured ~6% faster end-to-end on v5e at the 1B shapes — trust
    # the end-to-end number.
    if block_q_bwd is None and _ENV_BLOCK_Q_BWD:
        block_q_bwd = int(_ENV_BLOCK_Q_BWD)
    if block_k_bwd is None and _ENV_BLOCK_K_BWD:
        block_k_bwd = int(_ENV_BLOCK_K_BWD)
    if block_q_bwd is None:
        block_q_bwd = block_q
    if block_k_bwd is None:
        block_k_bwd = block_k
    # SKYTPU_NO_FLASH=1: route through the XLA reference attention
    # even on TPU (A/B lever — on some chip/shape points XLA's fused
    # attention beats the Pallas kernels, cf. the decode path where
    # dense XLA won on v5e).
    use_pallas = (force_pallas or _on_tpu()) and \
        os.environ.get('SKYTPU_NO_FLASH', '0') != '1'
    # The kernels want block-divisible sequence lengths.
    if use_pallas and (t % min(block_q, t) == 0 and
                       s % min(block_k, s) == 0 and
                       t % min(block_q_bwd, t) == 0 and
                       s % min(block_k_bwd, s) == 0 and
                       (interpret or (t >= 128 and s >= 128))):
        # [B,T,H,D] -> [B,H,T,D]; XLA folds this into the producing
        # projection's output layout. K/V keep their Hkv heads — GQA
        # is handled inside the kernel grid.
        qr = q.transpose(0, 2, 1, 3)
        kr = k.transpose(0, 2, 1, 3)
        vr = v.transpose(0, 2, 1, 3)
        cos = sin = None
        if rope_angles is not None:
            # Full-width duplicated tables ([T, D] f32) so the kernels
            # never slice/concat half-lanes.
            angles = jnp.concatenate([rope_angles, rope_angles],
                                     axis=-1).astype(jnp.float32)
            cos, sin = jnp.cos(angles), jnp.sin(angles)
        out = _flash_attention(qr, kr, vr, cos, sin, causal, scale,
                               block_q, block_k,
                               min(block_q_bwd, t),
                               min(block_k_bwd, s), interpret)
        return out.transpose(0, 2, 1, 3)
    if use_pallas:
        # Trace-time, so once per compiled shape: on the chip the
        # choice of path must show in the log, not only in the speed.
        logger.warning(
            'flash_attention: XLA reference path for q=%s k=%s — '
            'lengths must be >= 128 and divisible by the blocks '
            '(fwd %dx%d, bwd %dx%d)', q.shape, k.shape, block_q,
            block_k, block_q_bwd, block_k_bwd)
    if rope_angles is not None:
        q = apply_rope(q, rope_angles)
        k = apply_rope(k, rope_angles)
    out = dot_product_attention(q, k, v, causal=causal, scale=scale)
    # Same residual tag as the Pallas path so layer-level remat
    # policies (save_only_these_names('flash_attn_out', ...)) keep
    # the attention output either way; backward recomputes
    # scores/softmax from (recomputed) qkv — the standard memory-
    # efficient trade.
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(out, 'flash_attn_out')
