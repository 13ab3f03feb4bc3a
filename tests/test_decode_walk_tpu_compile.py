"""The block walk (ops/decode_attention.walk_attention), the prefill
chunk's walk over key tiles (chunk_attention) and the pair-tiled
grouped product of the expert layers (ops/grouped_matmul) compiled
for a described v5e at the serving shapes, with no chip: what the
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
from skypilot_tpu.ops import grouped_matmul as gm

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


# ---------------------------------------------------------------------
# The pair-tiled grouped product (ops/grouped_matmul)
# ---------------------------------------------------------------------


@pytest.fixture
def tiling(monkeypatch):
    """The TPU's answers to both kernels' rules, and no interpreter."""
    for mod in (gm, da):
        monkeypatch.setattr(mod, '_on_tpu', lambda: True)
        monkeypatch.setattr(mod, '_interpret', lambda: False)


# pairs, in, (layers, experts held), out: the products of a JoyAI
# drafting round (main layers and the module's), of a command-a-plus
# step and of a Xing4.0 step, gate / up and down.
@pytest.mark.parametrize('m, k, stack, n', [
    (1536, 2048, (7, 256), 768), (1536, 768, (7, 256), 2048),
    (1536, 2048, (1, 256), 768), (1536, 768, (1, 256), 2048),
    (256, 4096, (8, 16), 4096),
    (256, 3584, (8, 64), 1024), (256, 1024, (8, 64), 3584),
    (8, 2048, (7, 256), 768),
], ids=['joyai-up', 'joyai-down', 'joyai-module-up',
        'joyai-module-down', 'command-a', 'xing4-up', 'xing4-down',
        'joyai-one-token'])
def test_pair_tiles_compile_for_the_v5e_with_the_stack_in_place(
        tiling, one_chip, m, k, stack, n):
    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert gm.tiles_engage(k, n, codes=True, rows_dtype=jnp.bfloat16)
    compiled = jax.jit(gm.pair_tiled_matmul).lower(
        arr((m, k), jnp.bfloat16), arr((*stack, k, n), jnp.int8),
        arr((), jnp.int32), arr((stack[1],), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' in text and gm.KERNEL_NAME in text
    # The expert stack reaches the kernel where it lies: no slice,
    # no copy and no other layout of it among the temporaries (a
    # layer's codes alone are 0.2 to 0.4 GB).
    assert not re.search(r's8\[[\d,]+\]\S* (copy|dynamic-slice)\(', text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _decode_program(model, one_chip):
    """(the jitted decode program of a configuration as its cell
    serves it, its arguments' shapes)."""
    from skypilot_tpu.models import decode, llama, quant

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    overrides, slots, blocks, max_seq = {
        'joyai-llm-flash': (dict(n_layers=8), 96, (18561,), 3200),
        'command-a-plus': (dict(n_layers=8, vocab_size=32768,
                                experts_held=(0, 16)), 32,
                           (6529, 5541), 12288),
        'xing4.0-29b-a4b': (dict(n_layers=10), 64, (22529,), 20480),
    }[model]
    config = llama.get_config(model, **overrides)
    params = jax.tree.map(
        lambda x: arr(x.shape, x.dtype), jax.eval_shape(
            lambda: quant.quantize_params(llama.init_params(
                config, jax.random.PRNGKey(0), dtype=jnp.bfloat16),
                config)))
    mb = max_seq // _BS
    rows = arr((slots,), jnp.int32)
    lanes = arr((slots,), jnp.bool_)
    if config.kv_lora_rank is None:
        def group(kind, n):
            pool = (config.kind_entries(kind), n, _BS,
                    config.n_kv_heads, config.head_dim)
            return (arr(pool, jnp.int8), arr(pool, jnp.int8),
                    arr(pool[:-1], jnp.bfloat16),
                    arr(pool[:-1], jnp.bfloat16))
        caches = {'global': group('global', blocks[0]),
                  'window': group('window', blocks[1])}
        tables = {k: arr((slots, mb), jnp.int32) for k in caches}
    else:
        caches = (arr((config.kv_entries, blocks[0], _BS,
                       da.latent_pool_width(config.latent_width)),
                      jnp.bfloat16), None, None, None)
        tables = arr((slots, mb), jnp.int32)
    if config.nextn_layers:
        knobs = {'temps': arr((slots,), jnp.float32),
                 'top_ps': arr((slots,), jnp.float32), 'seeds': rows}
        return jax.jit(
            decode.mtp_rounds_paged, static_argnums=(8, 9, 10),
            donate_argnums=(3,)).lower(
                params, rows, rows, caches, tables, rows, lanes, lanes,
                config, 8, _BS, sampling=knobs), config
    return jax.jit(
        decode.decode_steps_paged, static_argnums=(6, 7, 8),
        donate_argnums=(2,)).lower(
            params, rows, caches, tables, rows, lanes, config, 8,
            _BS), config


@pytest.mark.parametrize('model', [
    'joyai-llm-flash', 'command-a-plus', 'xing4.0-29b-a4b'])
def test_the_expert_cells_decode_programs_hold_the_kernel_and_no_ragged_dot(
        tiling, one_chip, model):
    """The drafting rounds of JoyAI-LLM-Flash and the decode steps of
    command-a-plus and Xing4.0 at their cells' rows, pools and depth:
    every grouped product is the pair-tiled kernel (the rule engages
    at all three static shapes), none is the compiler's
    ``ragged-dot-none``, no expert stack is copied or sliced round
    the kernel, and the program fits the chip."""
    lowered, config = _decode_program(model, one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert gm.KERNEL_NAME in text
    assert 'ragged-dot' not in text and 'ragged_dot_tiling' not in text
    held, d, f = config.n_experts_held, config.dim, config.ffn_hidden
    for op in re.finditer(
            r's8\[([\d,]+)\]\S* (?:copy|dynamic-slice)\(', text):
        dims = [int(x) for x in op.group(1).split(',')]
        assert not (dims[-2:] in ([d, f], [f, d]) and
                    math.prod(dims[:-2]) % held == 0), op.group(0)
    analysis = compiled.memory_analysis()
    assert (analysis.argument_size_in_bytes +
            analysis.output_size_in_bytes -
            analysis.alias_size_in_bytes +
            analysis.temp_size_in_bytes) < 15.75e9
