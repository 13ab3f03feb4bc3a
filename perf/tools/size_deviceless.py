"""Size a configuration without the chip: compile the executables a
cell's window drives at the real size for a described v5e
(``jax.experimental.topologies``) and print what one chip holds while
each runs, by the compiler's own ``memory_analysis()``. This is where
``slots``, ``num_blocks`` and the micro-batches of the configuration
files come from. Nothing runs: no time comes out of this.

    JAX_PLATFORMS=cpu python3 -m perf.tools.size_deviceless \
        --config mistral-7b-int8-serve --slots 24,32 --num-blocks 4650

A serving configuration compiles the decode dispatch and the widest
prefill chunk; a training configuration compiles the train step on a
mesh built from the described devices (``--batch`` overrides the
file's).
"""
import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

from perf.lib import harness  # noqa: E402


def _one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2')
    return topo, SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


def _report(name, compiled):
    m = compiled.memory_analysis()
    held = harness.executable_bytes(compiled)
    print(json.dumps({
        'executable': name,
        'arguments': int(m.argument_size_in_bytes),
        'outputs': int(m.output_size_in_bytes),
        'aliased': int(m.alias_size_in_bytes),
        'temporaries': int(m.temp_size_in_bytes),
        'held_bytes': held, 'held_GB': round(held / 1e9, 3)}),
        flush=True)
    return held


def size_serve(config, slots, num_blocks):
    import jax
    import jax.numpy as jnp
    from perf.lib import weights as weights_lib
    from skypilot_tpu.models import decode
    from skypilot_tpu.serve import batching

    _, chip = _one_chip()
    prog = harness.program_config(config)
    model, build = config['model'], config['build']
    params = _shapes(jax.eval_shape(
        lambda: weights_lib.make_weights(
            model, 0, int8=config['weights'] == 'int8',
            dtype=prog.dtype)[0]), chip)
    bs, max_seq = build['block_size'], build['max_seq']
    hd = model['hidden_size'] // model['num_attention_heads']
    pool = (model['num_hidden_layers'], num_blocks, bs,
            model['num_key_value_heads'], hd)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    caches = (arr(pool, jnp.int8), arr(pool, jnp.int8),
              arr(pool[:-1], jnp.bfloat16),
              arr(pool[:-1], jnp.bfloat16))
    mb = max_seq // bs
    step = jax.jit(batching.decode_steps_paged,
                   static_argnums=(6, 7, 8), donate_argnums=(2,))
    held = [_report(f'decode slots={slots} blocks={num_blocks}',
                    step.lower(
        params, arr((slots,), jnp.int32), caches,
        arr((slots, mb), jnp.int32), arr((slots,), jnp.int32),
        arr((slots,), jnp.bool_), prog,
        build.get('steps_per_dispatch', 8), bs).compile())]
    chunk = build.get('prefill_chunk', 512)
    prefill = jax.jit(decode.forward_paged, static_argnums=(6, 7),
                      donate_argnums=(2,))
    held.append(_report(f'prefill chunk={chunk}', prefill.lower(
        params, arr((1, chunk), jnp.int32), caches,
        arr((mb,), jnp.int32), arr((), jnp.int32),
        arr((), jnp.int32), prog, bs).compile()))
    return max(held)


def size_train(config, batch, seq):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.ops import attention
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import train as train_lib
    from perf.drivers import train_step as driver

    # This process's default backend is the CPU: without this the
    # model lowers the XLA reference attention, not the kernels.
    attention._on_tpu = lambda: True  # pylint: disable=protected-access
    topo, _ = _one_chip()
    chips = config['chips']
    prog = harness.program_config(config)
    if 'remat_saves' in config['build']:
        prog = dataclasses.replace(
            prog, remat_saves=config['build']['remat_saves'])
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshConfig(**config['build']['mesh']),
        devices=list(topo.devices)[:chips])
    opt = config['optimizer']
    optimizer = train_lib.default_optimizer(
        learning_rate=opt['lr'], weight_decay=opt['weight_decay'],
        b1=opt['b1'], b2=opt['b2'], grad_clip=opt['grad_clip'])
    state, shardings = driver.state_shapes(config, prog, mesh,
                                           optimizer)
    step_fn = train_lib.build_train_step(
        prog, mesh, shardings, optimizer=optimizer,
        lora_scale=float(config['build']['lora_scale']))
    tokens = jax.ShapeDtypeStruct(
        (batch, seq + 1), jnp.int32,
        sharding=train_lib.batch_sharding(mesh))
    compiled = step_fn.lower(state, {'tokens': tokens}).compile()
    text = compiled.as_text()
    print(json.dumps({
        'all-gather': text.count(' all-gather('),
        'all-reduce': text.count(' all-reduce('),
        'reduce-scatter': text.count(' reduce-scatter('),
        'tpu_custom_call': text.count('tpu_custom_call')}))
    return _report(f'train step batch={batch} seq={seq} chips={chips}',
                   compiled)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True)
    parser.add_argument('--slots', default='')
    parser.add_argument('--num-blocks', type=int, default=0)
    parser.add_argument('--blocks-per-slot', type=int, default=0,
                        help='num_blocks = slots x this + 1 (the '
                        'scratch block)')
    parser.add_argument('--batch', default='')
    parser.add_argument('--seq', type=int, default=2048)
    args = parser.parse_args(argv)
    config = harness.load_json(harness.PERF_DIR, 'configs',
                               args.config + '.json')
    if config['driver'] == 'serve_engine':
        for slots in [int(s) for s in args.slots.split(',') if s] or \
                [config['build']['slots']]:
            blocks = (slots * args.blocks_per_slot + 1
                      if args.blocks_per_slot else
                      args.num_blocks or config['build']['num_blocks'])
            try:
                size_serve(config, slots, blocks)
            except Exception as e:  # pylint: disable=broad-except
                print(f'slots={slots} blocks={blocks}: refused: '
                      f'{type(e).__name__}: {str(e)[:400]}',
                      flush=True)
    else:
        for batch in [int(b) for b in args.batch.split(',') if b] or \
                [config['build']['batch']]:
            try:
                size_train(config, batch, args.seq)
            except Exception as e:  # pylint: disable=broad-except
                print(f'batch={batch}: refused: {type(e).__name__}: '
                      f'{str(e)[:400]}', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
