"""``size_deviceless`` for a serving configuration with a latent
cache (driver ``serve_latent_moe``): one block group of latent rows,
the architecture's own leaves. Compiles the decode dispatch (the
table whole: its widest program) and the widest prefill chunk for a
described v5e and prints what one chip holds while each runs; nothing
runs.

    JAX_PLATFORMS=cpu python3 -m perf.tools.size_latent_moe_serve \
        --config xing4.0-29b-a4b-int8-serve --slots 32,48,64

The group is sized for the traffic as the configuration's
``assumed.sizing`` says: ``--shared-blocks`` (the shared documents,
held once) + slots x ``--own-blocks`` (the most a row owns past its
document: question and output at their longest) + 1 scratch.
``--reference-pad-to N`` compiles the plain reference's comparison at
that padded length instead, beside the run's weights: it has to fit
the chip once the engine's cache is gone. ``--text-dir`` keeps each
program's compiled text."""
import argparse
import os
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

from perf.lib import harness  # noqa: E402
from perf.tools import size_deviceless as plain  # noqa: E402


def group_blocks(slots, shared_blocks, own_blocks):
    return shared_blocks + slots * own_blocks + 1


def _weights(config, chip):
    import jax
    from perf.drivers import serve_latent_moe
    from perf.lib import weights_xing4
    prog = serve_latent_moe.program_config(config)
    return prog, plain._shapes(jax.eval_shape(
        lambda: weights_xing4.make_weights(
            config['model'], 0, int8=config['weights'] == 'int8',
            dtype=prog.dtype)[0]), chip)


def size_serve(config, slots, num_blocks, text_dir=''):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import decode
    from skypilot_tpu.ops import decode_attention as da

    _, chip = plain._one_chip()
    prog, params = _weights(config, chip)
    build = config['build']
    bs, max_seq = build['block_size'], build['max_seq']

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    caches = (arr((prog.kv_entries, num_blocks, bs,
                   da.latent_pool_width(prog.latent_width)),
                  prog.dtype), None, None, None)
    mb = max_seq // bs
    chunk = build.get('prefill_chunk', 512)
    name = f'slots={slots} blocks={num_blocks}'
    programs = {
        f'decode {name}': jax.jit(
            decode.decode_steps_paged, static_argnums=(6, 7, 8),
            donate_argnums=(2,)).lower(
                params, arr((slots,), jnp.int32), caches,
                arr((slots, mb), jnp.int32),
                arr((slots,), jnp.int32), arr((slots,), jnp.bool_),
                prog, build.get('steps_per_dispatch', 8), bs),
        f'prefill chunk={chunk} {name}': jax.jit(
            decode.forward_paged, static_argnums=(6, 7),
            donate_argnums=(2,)).lower(
                params, arr((1, chunk), jnp.int32), caches,
                arr((mb,), jnp.int32), arr((), jnp.int32),
                arr((), jnp.int32), prog, bs)}
    for pname, lowered in programs.items():
        compiled = lowered.compile()
        plain._report(pname, compiled)
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(
                    text_dir, pname.replace(' ', '_') + '.txt'),
                    'w') as f:
                f.write(compiled.as_text())


def size_reference(config, pad_to, served=2048):
    """The reference's comparison of one request (``served`` logit
    rows, the control's second pass with it) beside the weights."""
    import jax
    import jax.numpy as jnp
    from perf.reference import xing4_block_f32 as reference

    _, chip = plain._one_chip()
    _, params = _weights(config, chip)
    model = config['model']
    fmt = config['control']['weight_format']

    def gaps(w, toks, positions):
        ref = reference.logits_at(w, toks, positions, model)
        low = reference.logits_at(w, toks, positions, model, fmt)
        return ref.max(-1), low.argmax(-1)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    plain._report(
        f'reference pad_to={pad_to} served={served}',
        jax.jit(gaps).lower(params, arr((pad_to,), jnp.int32),
                            arr((served,), jnp.int32)).compile())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True)
    parser.add_argument('--slots', default='')
    parser.add_argument('--shared-blocks', type=int, default=8192)
    parser.add_argument('--own-blocks', type=int, default=224)
    parser.add_argument('--reference-pad-to', type=int, default=0)
    parser.add_argument('--text-dir', default='')
    args = parser.parse_args(argv)
    config = harness.load_json(harness.PERF_DIR, 'configs',
                               args.config + '.json')
    if args.reference_pad_to:
        size_reference(config, args.reference_pad_to)
        return 0
    for slots in [int(s) for s in args.slots.split(',') if s] or \
            [config['build']['slots']]:
        blocks = group_blocks(slots, args.shared_blocks,
                              args.own_blocks)
        try:
            size_serve(config, slots, blocks, args.text_dir)
        except Exception as e:  # pylint: disable=broad-except
            print(f'slots={slots} blocks={blocks}: refused: '
                  f'{type(e).__name__}: {str(e)[:2000]}', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
