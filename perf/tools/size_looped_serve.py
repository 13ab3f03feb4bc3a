"""``size_deviceless`` for a looped serving configuration (driver
``serve_looped``): the pool's leading axis counts a KV entry for every
pass and layer (``total_ut_steps`` x ``num_hidden_layers``) and the
weights carry the architecture's own leaves. Compiles the decode
dispatch and the widest prefill chunk for a described v5e and prints
what one chip holds while each runs; nothing runs.

    JAX_PLATFORMS=cpu python3 -m perf.tools.size_looped_serve \
        --config ouro-2.6b-int8-serve --slots 12,16 --blocks-per-slot 55

``--text-dir`` keeps each program's compiled text for a look at its
operations.
"""
import argparse
import os
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

from perf.lib import harness  # noqa: E402
from perf.tools import size_deviceless as plain  # noqa: E402


def size_serve(config, slots, num_blocks, text_dir=''):
    import jax
    import jax.numpy as jnp
    from perf.drivers import serve_looped
    from perf.lib import weights_looped
    from skypilot_tpu.models import decode
    from skypilot_tpu.serve import batching

    _, chip = plain._one_chip()
    prog = serve_looped.program_config(config)
    model, build = config['model'], config['build']
    params = plain._shapes(jax.eval_shape(
        lambda: weights_looped.make_weights(
            model, 0, int8=config['weights'] == 'int8',
            dtype=prog.dtype)[0]), chip)
    bs, max_seq = build['block_size'], build['max_seq']
    pool = (model['total_ut_steps'] * model['num_hidden_layers'],
            num_blocks, bs, model['num_key_value_heads'],
            model['hidden_size'] // model['num_attention_heads'])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    caches = (arr(pool, jnp.int8), arr(pool, jnp.int8),
              arr(pool[:-1], jnp.bfloat16),
              arr(pool[:-1], jnp.bfloat16))
    mb = max_seq // bs
    chunk = build.get('prefill_chunk', 512)
    programs = {
        f'decode slots={slots} blocks={num_blocks}': jax.jit(
            batching.decode_steps_paged, static_argnums=(6, 7, 8),
            donate_argnums=(2,)).lower(
                params, arr((slots,), jnp.int32), caches,
                arr((slots, mb), jnp.int32), arr((slots,), jnp.int32),
                arr((slots,), jnp.bool_), prog,
                build.get('steps_per_dispatch', 8), bs),
        f'prefill chunk={chunk} blocks={num_blocks}': jax.jit(
            decode.forward_paged, static_argnums=(6, 7),
            donate_argnums=(2,)).lower(
                params, arr((1, chunk), jnp.int32), caches,
                arr((mb,), jnp.int32), arr((), jnp.int32),
                arr((), jnp.int32), prog, bs)}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        plain._report(name, compiled)
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(
                    text_dir, name.replace(' ', '_') + '.txt'),
                    'w') as f:
                f.write(compiled.as_text())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True)
    parser.add_argument('--slots', default='')
    parser.add_argument('--blocks-per-slot', type=int, default=0,
                        help='num_blocks = slots x this + 1 (the '
                        'scratch block)')
    parser.add_argument('--text-dir', default='')
    args = parser.parse_args(argv)
    config = harness.load_json(harness.PERF_DIR, 'configs',
                               args.config + '.json')
    for slots in [int(s) for s in args.slots.split(',') if s] or \
            [config['build']['slots']]:
        blocks = (slots * args.blocks_per_slot + 1
                  if args.blocks_per_slot
                  else config['build']['num_blocks'])
        try:
            size_serve(config, slots, blocks, args.text_dir)
        except Exception as e:  # pylint: disable=broad-except
            print(f'slots={slots} blocks={blocks}: refused: '
                  f'{type(e).__name__}: {str(e)[:400]}', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
