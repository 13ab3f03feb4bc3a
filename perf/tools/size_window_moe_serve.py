"""``size_deviceless`` for a serving configuration with window and
global layers and a share of an expert layer (driver
``serve_window_moe``): two block groups, the architecture's own
leaves. Compiles the decode dispatch (the global table whole: its
widest program) and the widest prefill chunk for a described v5e and
prints what one chip holds while each runs; nothing runs.

    JAX_PLATFORMS=cpu python3 -m perf.tools.size_window_moe_serve \
        --config command-a-plus-int8-serve-ep8 --slots 24,32,40

The groups are sized for the traffic as the configuration's
``assumed.sizing`` says: the global group ``--shared-blocks`` (the
shared documents, held once) + slots x ``--own-blocks`` (a row's own
p95 context past its document) + 1 scratch; the window group
``--shared-window-blocks`` (the documents' tails, which the rows
share while their windows still reach them) + slots x (the same own
blocks + 1, and never more than a row can hold: ``window / block +
1``, the chunk in flight, 1) + 1. ``--text-dir`` keeps each
program's compiled text."""
import argparse
import os
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

from perf.lib import harness  # noqa: E402
from perf.tools import size_deviceless as plain  # noqa: E402


def group_blocks(config, slots, shared_blocks, own_blocks,
                 shared_window_blocks):
    build = config['build']
    bs = build['block_size']
    row_cap = (config['model']['sliding_window'] // bs + 1 +
               -(-build['prefill_chunk'] // bs) + 1)
    return (shared_blocks + slots * own_blocks + 1,
            shared_window_blocks +
            slots * min(own_blocks + 1, row_cap) + 1)


def size_serve(config, slots, num_blocks, window_num_blocks,
               text_dir=''):
    import jax
    import jax.numpy as jnp
    from perf.drivers import serve_window_moe
    from perf.lib import weights_cohere2_moe
    from skypilot_tpu.models import decode

    _, chip = plain._one_chip()
    prog = serve_window_moe.program_config(config)
    model, build = config['model'], config['build']
    params = plain._shapes(jax.eval_shape(
        lambda: weights_cohere2_moe.make_weights(
            model, 0, int8=config['weights'] == 'int8',
            dtype=prog.dtype)[0]), chip)
    bs, max_seq = build['block_size'], build['max_seq']

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def group(kind, blocks):
        pool = (prog.kind_entries(kind), blocks, bs, prog.n_kv_heads,
                prog.head_dim)
        return (arr(pool, jnp.int8), arr(pool, jnp.int8),
                arr(pool[:-1], jnp.bfloat16),
                arr(pool[:-1], jnp.bfloat16))

    caches = {'global': group('global', num_blocks),
              'window': group('window', window_num_blocks)}
    mb = max_seq // bs
    tables = {k: arr((slots, mb), jnp.int32) for k in caches}
    row = {k: arr((mb,), jnp.int32) for k in caches}
    chunk = build.get('prefill_chunk', 512)
    name = f'slots={slots} blocks={num_blocks}+{window_num_blocks}'
    programs = {
        f'decode {name}': jax.jit(
            decode.decode_steps_paged, static_argnums=(6, 7, 8),
            donate_argnums=(2,)).lower(
                params, arr((slots,), jnp.int32), caches, tables,
                arr((slots,), jnp.int32), arr((slots,), jnp.bool_),
                prog, build.get('steps_per_dispatch', 8), bs),
        f'prefill chunk={chunk} {name}': jax.jit(
            decode.forward_paged, static_argnums=(6, 7),
            donate_argnums=(2,)).lower(
                params, arr((1, chunk), jnp.int32), caches, row,
                arr((), jnp.int32), arr((), jnp.int32), prog, bs)}
    for pname, lowered in programs.items():
        compiled = lowered.compile()
        plain._report(pname, compiled)
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(
                    text_dir, pname.replace(' ', '_') + '.txt'),
                    'w') as f:
                f.write(compiled.as_text())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True)
    parser.add_argument('--slots', default='')
    parser.add_argument('--shared-blocks', type=int, default=2048)
    parser.add_argument('--own-blocks', type=int, default=140)
    parser.add_argument('--shared-window-blocks', type=int,
                        default=1028)
    parser.add_argument('--text-dir', default='')
    args = parser.parse_args(argv)
    config = harness.load_json(harness.PERF_DIR, 'configs',
                               args.config + '.json')
    for slots in [int(s) for s in args.slots.split(',') if s] or \
            [config['build']['slots']]:
        blocks, window_blocks = group_blocks(
            config, slots, args.shared_blocks, args.own_blocks,
            args.shared_window_blocks)
        try:
            size_serve(config, slots, blocks, window_blocks,
                       args.text_dir)
        except Exception as e:  # pylint: disable=broad-except
            print(f'slots={slots} blocks={blocks}+{window_blocks}: '
                  f'refused: {type(e).__name__}: {str(e)[:400]}',
                  flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
