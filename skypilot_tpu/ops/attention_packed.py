"""EXPERIMENT: head-packed flash-attention forward for head_dim 64.

At head_dim 64 every kernel dot under-fills the 128-wide MXU
contraction (qk^T has K=64; pv has N=64), which perf_notes identifies
as the attention ceiling on v5e. This kernel packs TWO heads per grid
program:

    Q' = [[qA, 0], [0, qB]]   # [2*Bq, 128] block-diagonal
    K' = [kA | kB]            # [Bk, 128]  (kA == kB under GQA pairs)
    S' = Q' @ K'^T            # [2*Bq, Bk] — both heads, K=128 fill
    V' = [vA | vB]            # [Bk, 128]
    A' = P' @ V'              # [2*Bq, 128], N=128 fill
    outA = A'[:Bq, :64]; outB = A'[Bq:, 64:]

Accounting (why this is an EXPERIMENT, not the default): the zero
blocks double the MAC count, so if the MXU executes a K=64 dot at
half throughput (padding the contraction), packed and plain spend the
SAME MXU time — the real wins are fewer grid programs (half the
per-program overhead) and fuller MXU pipelines; the real risks are
the doubled VMEM traffic for K'/V' and the unchanged VPU (softmax)
work, which the fwd kernel already serializes on. bench mode
``python -m skypilot_tpu.ops.attention_packed`` measures packed vs
plain on the attached chip; docs/perf_notes.md records the verdict.

Forward-only, causal, no RoPE fusion (callers rotate beforehand) —
enough surface to measure the hypothesis before committing to the
(3x larger) backward implementation.
"""
import functools

import jax
import jax.numpy as jnp

from skypilot_tpu.ops.attention import (_causal_bounds, _LOG2E,
                                        _NEG_INF, _STAT_SUBLANES)


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       scale, causal, block_k, seq_q, seq_k,
                       shared_kv):
    """One (b, head-pair, q-block) program. Refs: q [2, Bq, D];
    k/v [S, D] when ``shared_kv`` (GQA pair shares the kv head) else
    [2, S, D]; o [2, Bq, D]; lse [2, 8, Bq]."""
    from jax.experimental import pallas as pl

    qA = q_ref[0]
    qB = q_ref[1]
    block_q, d = qA.shape
    q_idx = pl.program_id(2)
    offset = seq_k - seq_q

    fold = scale * _LOG2E
    qA = (qA.astype(jnp.float32) * fold).astype(qA.dtype)
    qB = (qB.astype(jnp.float32) * fold).astype(qB.dtype)
    zeros = jnp.zeros_like(qA)
    # Block-diagonal packed queries: [2*Bq, 2D].
    qp = jnp.concatenate([
        jnp.concatenate([qA, zeros], axis=1),
        jnp.concatenate([zeros, qB], axis=1),
    ], axis=0)

    m = jnp.full((2 * block_q,), _NEG_INF, jnp.float32)
    l = jnp.zeros((2 * block_q,), jnp.float32)
    acc = jnp.zeros((2 * block_q, 2 * d), jnp.float32)

    num_kb = seq_k // block_k
    if causal:
        n_full, last_kb, relpos = _causal_bounds(
            q_idx, block_q, block_k, offset, num_kb)
        relpos2 = jnp.concatenate([relpos, relpos], axis=0)

    def body(kb, carry, masked):
        m, l, acc = carry
        if shared_kv:
            k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
            kp = jnp.concatenate([k_blk, k_blk], axis=1)
            vp = jnp.concatenate([v_blk, v_blk], axis=1)
        else:
            kp = jnp.concatenate(
                [k_ref[0, pl.ds(kb * block_k, block_k), :],
                 k_ref[1, pl.ds(kb * block_k, block_k), :]], axis=1)
            vp = jnp.concatenate(
                [v_ref[0, pl.ds(kb * block_k, block_k), :],
                 v_ref[1, pl.ds(kb * block_k, block_k), :]], axis=1)
        s = jnp.dot(qp, kp.T, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(relpos2 >= kb * block_k, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(vp.dtype), vp,
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
    lse = m + jnp.log2(l_safe)
    outA = out[:block_q, :d]
    outB = out[block_q:, d:]
    o_ref[0] = outA.astype(o_ref.dtype)
    o_ref[1] = outB.astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(
        lse[None, :block_q].astype(jnp.float32),
        (lse_ref.shape[1], block_q))
    lse_ref[1] = jnp.broadcast_to(
        lse[None, block_q:].astype(jnp.float32),
        (lse_ref.shape[1], block_q))


def packed_flash_attention_fwd(q, k, v, *, causal=True, scale=None,
                               block_q=512, block_k=512,
                               interpret=False):
    """[B, H, T, D] q; [B, Hkv, S, D] k/v (layout of
    attention._fwd_pallas). Requires even H and, under GQA, even
    groups so paired q-heads share a kv head. Returns (out, lse)
    shaped like the plain forward."""
    from jax.experimental import pallas as pl

    b, h, t, d = q.shape
    _, hkv, s, _ = k.shape
    groups = h // hkv
    assert h % 2 == 0, h
    scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, t)
    block_k = min(block_k, s)
    shared_kv = groups % 2 == 0
    if not shared_kv:
        assert hkv % 2 == 0, (h, hkv)

    qp = q.reshape(b, h // 2, 2, t, d)
    grid = (b, h // 2, t // block_q)
    kernel = functools.partial(
        _packed_fwd_kernel, scale=scale, causal=causal,
        block_k=block_k, seq_q=t, seq_k=s, shared_kv=shared_kv)
    if shared_kv:
        kv_spec = pl.BlockSpec(
            (None, None, s, d),
            lambda bb, hp, i: (bb, (2 * hp) // groups, 0, 0))
        k_in, v_in = k, v
    else:
        k_in = k.reshape(b, hkv // 2, 2, s, d)
        v_in = v.reshape(b, hkv // 2, 2, s, d)
        kv_spec = pl.BlockSpec((None, None, 2, s, d),
                               lambda bb, hp, i: (bb, hp, 0, 0, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, 2, block_q, d),
                         lambda bb, hp, i: (bb, hp, 0, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, None, 2, block_q, d),
                         lambda bb, hp, i: (bb, hp, 0, i, 0)),
            pl.BlockSpec((None, None, 2, _STAT_SUBLANES, block_q),
                         lambda bb, hp, i: (bb, hp, 0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h // 2, 2, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h // 2, 2, _STAT_SUBLANES, t),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(qp, k_in, v_in)
    return (out.reshape(b, h, t, d),
            lse.reshape(b, h, _STAT_SUBLANES, t))


def bench_main():
    """Micro-bench: packed vs plain forward at the LoRA headline's
    shapes (B8 T2048 32/8 heads hd64). One jitted lax.scan per
    variant so dispatch latency amortizes."""
    import time

    from skypilot_tpu.ops import attention as attn

    b, h, hkv, t, d = 8, 32, 8, 2048, 64
    iters = 20
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, hkv, t, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, hkv, t, d), jnp.bfloat16)

    def loop(fn):
        def body(c, _):
            o = fn(q + c, k, v)
            return c + o[0, 0, 0, 0].astype(jnp.bfloat16) * 1e-9, None
        return jax.jit(lambda: jax.lax.scan(
            body, jnp.bfloat16(0), None, length=iters)[0])

    def plain(q_, k_, v_):
        return attn._fwd_pallas(  # pylint: disable=protected-access
            q_, k_, v_, scale=d ** -0.5, causal=True,
            block_q=512, block_k=512)[0]

    def packed(q_, k_, v_):
        return packed_flash_attention_fwd(
            q_, k_, v_, causal=True, block_q=512, block_k=512)[0]

    flops = 4 * b * h * t * t * d / 2  # causal qk+pv MACs*2 / 2
    for name, fn in (('plain', plain), ('packed', packed)):
        run = loop(fn)
        run().block_until_ready()  # compile
        t0 = time.perf_counter()
        run().block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        print(f'{name}: {dt * 1e3:.3f} ms/fwd  '
              f'{flops / dt / 1e12:.1f} TFLOP/s effective')


if __name__ == '__main__':
    bench_main()
