"""The paged pool's read path and attention over it
(ops/decode_attention.py): block-wise gathers against the flat index
arithmetic, int8 codes read as codes, this step's rows as an operand."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import decode_attention as da


# ---------------------------------------------------------------------
# The int8 read path of the decode / verify steps (PR 26): block-wise
# gather, codes into the dots as codes, the new row as an operand.
# ---------------------------------------------------------------------

_NB, _BS, _HKV, _HD, _HQ = 23, 8, 2, 16, 4
_B, _MB = 3, 6                      # S = 48 positions a row
_S = _MB * _BS


def _pools(seed=0, int8=True):
    """One layer's block pool with a shuffled table whose tails
    point at the scratch block (0), as the engine's do."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (_NB, _BS, _HKV, _HD)
    if int8:
        kp = jax.random.randint(ks[0], shape, -127, 128, jnp.int8)
        vp = jax.random.randint(ks[1], shape, -127, 128, jnp.int8)
        ksc = jax.random.uniform(ks[2], shape[:-1], jnp.float32,
                                 0.004, 0.03).astype(jnp.bfloat16)
        vsc = jax.random.uniform(ks[3], shape[:-1], jnp.float32,
                                 0.004, 0.03).astype(jnp.bfloat16)
    else:
        kp = jax.random.normal(ks[0], shape, jnp.float32)
        vp = jax.random.normal(ks[1], shape, jnp.float32)
        ksc = vsc = None
    perm = 1 + np.random.default_rng(seed).permutation(_NB - 1)
    tables = np.zeros((_B, _MB), np.int32)
    for b, n in enumerate((6, 4, 2)):          # blocks a row owns
        tables[b, :n] = perm[b * 6:b * 6 + n]
    return kp, vp, ksc, vsc, jnp.asarray(tables)


def _dequant(codes, scales):
    if scales is None:
        return codes
    return codes.astype(jnp.float32) * scales.astype(
        jnp.float32)[..., None]


def _reference(q, kd, vd, lengths, scale):
    """Dequantise-then-attend, one query position at a time."""
    if q.ndim == 3:
        return da.decode_attention(q, kd, vd, lengths,
                                              scale)
    return jnp.stack([
        da.decode_attention(q[:, j], kd, vd, lengths + j,
                                       scale)
        for j in range(q.shape[1])], axis=1)


class TestBlockGather:

    @pytest.mark.parametrize('what', ['codes', 'scales', 'floats'])
    def test_equals_read_indices_take(self, what):
        kp, _, ksc, _, tables = _pools(int8=what != 'floats')
        pool = ksc if what == 'scales' else kp
        got = da.gather_blocks(pool, tables)
        flat = pool.reshape(_NB * _BS, *pool.shape[2:])
        want = jnp.take(flat, da.read_indices(tables, _BS),
                        axis=0)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.shape[:2] == (_B, _S)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want))
        if what == 'scales':
            # The scores' layout of the same values, also with every
            # layer's pool at once (leading dims pass through).
            views = da.gather_scales(jnp.stack([pool, pool * 2]),
                                     tables)
            assert views.dtype == jnp.float32
            assert views.shape == (2, _B, _HKV, _S)
            for layer, mult in enumerate((1, 2)):
                np.testing.assert_array_equal(
                    np.asarray(views[layer]),
                    np.asarray(want.astype(jnp.float32) * mult
                               ).transpose(0, 2, 1))


class TestInt8ViewAttention:
    """``view_attention`` on int8 codes against dequantise-then-
    reference in float32."""

    @pytest.mark.parametrize('width', [0, 1, 3])
    @pytest.mark.parametrize('lengths', [
        (1, 1, 1), (_BS, _BS + 1, _BS - 1), (_S, 17, 2)])
    def test_matches_dequantised_reference(self, width, lengths):
        kp, vp, ksc, vsc, tables = _pools(1)
        lengths = jnp.asarray(lengths, jnp.int32)
        if width:                    # query j attends lengths + j
            lengths = jnp.minimum(lengths, _S - width + 1)
        qshape = (_B, width, _HQ, _HD) if width else (_B, _HQ, _HD)
        q = jax.random.normal(jax.random.PRNGKey(7), qshape,
                              jnp.float32)
        k, v = da.gather_blocks(kp, tables), da.gather_blocks(vp,
                                                              tables)
        s_k, s_v = (da.gather_blocks(ksc, tables),
                    da.gather_blocks(vsc, tables))
        got = da.view_attention(
            q, k, v, lengths, _HD ** -0.5,
            da.gather_scales(ksc, tables),
            da.gather_scales(vsc, tables))
        want = _reference(q, _dequant(k, s_k), _dequant(v, s_v),
                          lengths, _HD ** -0.5)
        assert got.shape == q.shape and got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestNewRowAsOperand:
    """Handing this step's rows to attention beside the view equals
    writing them into the pool first; what the pool holds at the
    write position (a recycled block's stale rows) must not leak."""

    @pytest.mark.parametrize('int8', [True, False])
    @pytest.mark.parametrize('width', [0, 1, 3])
    @pytest.mark.parametrize('cur', [
        (0, 0, 0), (_BS - 1, _BS, 3), (_S - 3, 20, _BS + 1)])
    def test_equals_write_then_attend(self, int8, width, cur):
        kp, vp, ksc, vsc, tables = _pools(2, int8)
        w = max(width, 1)
        cur = jnp.asarray(cur, jnp.int32)
        # Every row owns the blocks its window writes into.
        tables = _pools(2, int8)[4].at[:, :].set(
            1 + jnp.arange(_B * _MB, dtype=jnp.int32).reshape(
                _B, _MB))
        ks = jax.random.split(jax.random.PRNGKey(11), 5)
        qshape = (_B, width, _HQ, _HD) if width else (_B, _HQ, _HD)
        q = jax.random.normal(ks[0], qshape, jnp.float32)
        rshape = (_B, w, _HKV, _HD)
        if int8:
            k_new = jax.random.randint(ks[1], rshape, -127, 128,
                                       jnp.int8)
            v_new = jax.random.randint(ks[2], rshape, -127, 128,
                                       jnp.int8)
            ks_new = jax.random.uniform(
                ks[3], rshape[:-1], jnp.float32, 0.004,
                0.03).astype(jnp.bfloat16)
            vs_new = jax.random.uniform(
                ks[4], rshape[:-1], jnp.float32, 0.004,
                0.03).astype(jnp.bfloat16)
        else:
            k_new = jax.random.normal(ks[1], rshape, jnp.float32)
            v_new = jax.random.normal(ks[2], rshape, jnp.float32)
            ks_new = vs_new = None
        # Stale rows at and after the write positions: as large as
        # the type holds, so a leak of any weight shows.
        pos = cur[:, None] + jnp.arange(w)[None, :]        # [B, W]
        widx = da.verify_write_indices(
            tables, cur, jnp.full((_B,), w, jnp.int32), w,
            _BS).reshape(-1)
        at = da.read_indices(tables, _BS)              # [B, S]
        stale_idx = at[jnp.arange(_S)[None, :] >= cur[:, None]]

        def flat(x):
            return x.reshape(_NB * _BS, *x.shape[2:])

        def unflat(x):
            return x.reshape(_NB, _BS, *x.shape[1:])

        big = 127 if int8 else 1e4
        kp = unflat(flat(kp).at[stale_idx].set(big))
        vp = unflat(flat(vp).at[stale_idx].set(big))
        if int8:
            ksc = unflat(flat(ksc).at[stale_idx].set(100.0))
            vsc = unflat(flat(vsc).at[stale_idx].set(100.0))

        def rows(x):
            return x if width else x[:, 0]

        new = (rows(k_new), rows(v_new),
               None if ks_new is None else rows(ks_new),
               None if vs_new is None else rows(vs_new))
        def views(pool):
            return None if pool is None else da.gather_scales(pool,
                                                              tables)

        got = da.paged_decode_attention(
            q, kp, vp, tables, cur, _HD ** -0.5, views(ksc),
            views(vsc), new=new)

        def written(pool, new_rows):
            return unflat(flat(pool).at[widx].set(
                new_rows.reshape(_B * w, *new_rows.shape[2:])))

        want = da.paged_decode_attention(
            q, written(kp, k_new), written(vp, v_new), tables,
            cur + 1, _HD ** -0.5,
            None if ksc is None else views(written(ksc, ks_new)),
            None if vsc is None else views(written(vsc, vs_new)))
        assert bool(jnp.all(pos < _S))
        assert float(jnp.max(jnp.abs(want))) < 50.0   # nothing leaked
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


class TestNoDequantisedView:

    @pytest.mark.parametrize('width', [0, 3])
    def test_no_multiply_of_the_view_s_shape(self, width):
        """An int8 pool is never dequantised as a view: no ``mul``
        in the traced attention has the [B, S, Hkv, hd] shape (the
        scales multiply scores and probabilities, [.., S])."""
        kp, vp, ksc, vsc, tables = _pools(4)
        w = max(width, 1)
        qshape = (_B, width, _HQ, _HD) if width else (_B, _HQ, _HD)
        rshape = (_B, w, _HKV, _HD) if width else (_B, _HKV, _HD)
        q = jnp.zeros(qshape, jnp.bfloat16)
        new = (jnp.zeros(rshape, jnp.int8), jnp.zeros(rshape, jnp.int8),
               jnp.ones(rshape[:-1], jnp.bfloat16),
               jnp.ones(rshape[:-1], jnp.bfloat16))
        jaxpr = jax.make_jaxpr(
            lambda *a: da.paged_decode_attention(
                a[0], a[1], a[2], tables, jnp.asarray([5, 9, 1]),
                0.25, da.gather_scales(a[3], tables),
                da.gather_scales(a[4], tables), new=a[5]))(
                    q, kp, vp, ksc, vsc, new)
        view = (_B, _S, _HKV, _HD)
        shapes = [(e.primitive.name, v.aval.shape)
                  for e in _eqns(jaxpr.jaxpr) for v in e.outvars]
        assert ('gather', (_B, _MB, _BS, _HKV, _HD)) in shapes
        assert not [s for s in shapes if s == ('mul', view)], shapes
        # Floats of the view's shape exist only as the converts the
        # two dots consume.
        floats = [e for e in _eqns(jaxpr.jaxpr) for v in e.outvars
                  if v.aval.shape == view
                  and jnp.issubdtype(v.aval.dtype, jnp.floating)]
        assert {e.primitive.name for e in floats} == {
            'convert_element_type'}
        assert len(floats) == 2
