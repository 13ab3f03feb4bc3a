"""The block walk (ops/decode_attention.walk_attention) and the
prefill chunk's walk over key tiles (chunk_attention) compiled for
a described v5e at the serving shapes, with no chip: what the
TPU's compiler refuses (a slice off the tiling, too much VMEM, a copy
of the whole pool round the kernel) the interpreter's tests cannot
see. Nothing runs; no time comes out of this.

The topology is described inside a fixture, so only the worker that
is given this file loads the TPU's library, and only once a test of
it has started.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.ops import decode_attention as da

_BS, _HD = 16, 128


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform='tpu', topology_name='v5e:2x2')
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


# rows, KV heads, query heads a KV head, pool blocks (all entries),
# the 7/8 width of the table: mistral-7b, ouro-2.6b and the global
# layers of command-a-plus as their cells serve them.
@pytest.mark.parametrize('rows, hkv, groups, blocks, width', [
    (24, 8, 4, 32 * 4561, 224),
    (12, 16, 1, 192 * 661, 63),
    (32, 8, 16, 2 * 6529, 672),
], ids=['mistral', 'ouro', 'command-a-global'])
def test_walk_compiles_for_the_v5e_with_the_pool_in_place(
        monkeypatch, one_chip, rows, hkv, groups, blocks, width):
    monkeypatch.setattr(da, '_on_tpu', lambda: True)
    monkeypatch.setattr(da, '_interpret', lambda: False)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, tables, lengths, ksp, vsp, kn, vn, ksn, vsn):
        return da.paged_decode_attention(
            q, kp, vp, tables, lengths, _HD ** -0.5,
            k_scale=da.walk_scales(ksp, tables),
            v_scale=da.walk_scales(vsp, tables),
            new=(kn, vn, ksn, vsn))

    pool = (blocks, _BS, hkv, _HD)
    compiled = jax.jit(attend).lower(
        arr((rows, hkv * groups, _HD), jnp.bfloat16),
        arr(pool, jnp.int8), arr(pool, jnp.int8),
        arr((rows, width), jnp.int32), arr((rows,), jnp.int32),
        arr(pool[:-1], jnp.bfloat16), arr(pool[:-1], jnp.bfloat16),
        arr((rows, hkv, _HD), jnp.int8), arr((rows, hkv, _HD), jnp.int8),
        arr((rows, hkv), jnp.bfloat16), arr((rows, hkv), jnp.bfloat16)
    ).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' in text and da.WALK_KERNEL_NAME in text
    # The code pools reach the kernel where they lie: no view of them
    # and no copy in another layout is among the temporaries (a
    # pool's codes alone are 0.2 to 4 GB).
    codes = blocks * _BS * hkv * _HD
    assert compiled.memory_analysis().temp_size_in_bytes < codes // 8


def _relaid_in_a_loop(text, dtype, at_least):
    """The copies, reshapes and transposes of ``dtype`` arrays with
    ``at_least`` elements or more that a compiled program makes
    OUTSIDE its entry computation (so inside a loop body, once a
    trip): [(computation, instruction line)]."""
    found, comp, entry = [], None, False
    for line in text.splitlines():
        head = re.match(r'^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$', line)
        if head:
            entry, comp = bool(head.group(1)), head.group(2)
            continue
        op = re.match(r'\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* '
                      r'(copy|reshape|transpose)\(', line)
        if op and not entry and op.group(1) == dtype and math.prod(
                int(d) for d in op.group(2).split(',') if d) >= at_least:
            found.append((comp, line.strip()[:120]))
    return found


# The model, its pool's blocks and its table as the cells serve it.
@pytest.mark.parametrize('model, blocks, table', [
    ('mistral-7b', 4561, 256), ('ouro-2.6b', 661, 65)])
def test_prefill_chunk_compiles_for_the_v5e_and_relays_no_pool_a_layer(
        one_chip, model, blocks, table):
    """A whole 512-token ``forward_paged`` over int8 weights and an
    int8 pool at the serving size. The merged scatter after the layer
    scan makes the v5e keep the scale pools with the ENTRY axis
    minor; read block by block INSIDE the layer loop they were
    re-laid in every layer (80 ms of a Mistral chunk: PERF.md,
    PR 42), so the request's scales are gathered outside it: no loop
    body copies an array the size of a scale pool, and no temporary
    is the size of a row's view of ``max_seq`` scores (268 MB a
    layer until PR 42)."""
    from skypilot_tpu.models import decode, llama, quant
    config = llama.get_config(model)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: arr(x.shape, x.dtype), jax.eval_shape(
            lambda: quant.quantize_params(llama.init_params(
                config, jax.random.PRNGKey(0), dtype=jnp.bfloat16),
                config)))
    pool = (config.kv_entries, blocks, _BS, config.n_kv_heads, _HD)
    caches = (arr(pool, jnp.int8), arr(pool, jnp.int8),
              arr(pool[:-1], jnp.bfloat16), arr(pool[:-1], jnp.bfloat16))
    compiled = jax.jit(
        decode.forward_paged, static_argnums=(6, 7),
        donate_argnums=(2,)).lower(
        params, arr((1, 512), jnp.int32), caches,
        arr((table,), jnp.int32), arr((), jnp.int32),
        arr((), jnp.int32), config, _BS).compile()
    scales = math.prod(pool[:-1])
    assert not _relaid_in_a_loop(compiled.as_text(), 'bf16', scales // 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
