"""``size_deviceless`` for a serving configuration with a latent
cache whose next-token-prediction module drafts (driver
``serve_latent_mtp``): one block group of latent rows with the
module's entry in it, the architecture's own leaves. Compiles the
drafting rounds under their live sampled signature (the table whole:
their widest program), the widest prefill chunk with the module's
carry, and the first draft, for a described v5e, and prints what one
chip holds while each runs; nothing runs.

    JAX_PLATFORMS=cpu python3 -m perf.tools.size_latent_mtp_serve \
        --config joyai-llm-flash-int8-serve-mtp --slots 64,80,96

The group is sized for the traffic as the configuration's
``assumed.sizing`` says: slots x ``--own-blocks`` (the most a row
owns: prompt and output at their longest, and the one position more
the engine keeps) + ``--shared-blocks`` (the shared instructions,
held once) + 1 scratch. ``--view-blocks N`` compiles the rounds at a
narrower width of the table; ``--chunks 1,2,4,..`` the prefill
program of each of those buckets (a small bucket's program was laid
out otherwise than the widest and held a copy of the pool, PERF.md
section 6, PR 43); ``--reference-pad-to N`` compiles the
plain reference's comparison at that padded length instead, beside
the run's weights: it has to fit the chip once the engine's cache is
gone. ``--text-dir`` keeps each program's compiled text."""
import argparse
import os
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

from perf.lib import harness  # noqa: E402
from perf.tools import size_deviceless as plain  # noqa: E402


def group_blocks(slots, shared_blocks, own_blocks):
    return slots * own_blocks + shared_blocks + 1


def _weights(config, chip):
    import jax
    from perf.drivers import serve_latent_mtp
    from perf.lib import weights_joyai
    prog = serve_latent_mtp.program_config(config)
    return prog, plain._shapes(jax.eval_shape(
        lambda: weights_joyai.make_weights(
            config['model'], 0, int8=config['weights'] == 'int8',
            dtype=prog.dtype)[0]), chip)


def size_serve(config, slots, num_blocks, view_blocks=None,
               text_dir='', chunks=()):
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
    chunks = chunks or (build.get('prefill_chunk', 512),)
    rows = arr((slots,), jnp.int32)
    knobs = {'temps': arr((slots,), jnp.float32),
             'top_ps': arr((slots,), jnp.float32), 'seeds': rows}
    name = f'slots={slots} blocks={num_blocks}'
    programs = {
        f'rounds view={view_blocks or mb} {name}': jax.jit(
            decode.mtp_rounds_paged, static_argnums=(8, 9, 10),
            static_argnames=('view_blocks',),
            donate_argnums=(3,)).lower(
                params, rows, rows, caches,
                arr((slots, mb), jnp.int32), rows,
                arr((slots,), jnp.bool_), arr((slots,), jnp.bool_),
                prog, build.get('steps_per_dispatch', 8), bs,
                sampling=knobs if build.get('sampling', True)
                else None, view_blocks=view_blocks),
        **{f'prefill chunk={chunk} {name}': jax.jit(
            decode.forward_paged, static_argnums=(6, 7),
            donate_argnums=(2,)).lower(
                params, arr((1, chunk), jnp.int32), caches,
                arr((mb,), jnp.int32), arr((), jnp.int32),
                arr((), jnp.int32), prog, bs,
                mtp=(arr((1, 1, prog.dim), prog.dtype),
                     arr((), jnp.int32))) for chunk in chunks},
        f'first draft {name}': jax.jit(
            decode.mtp_first_paged, static_argnums=(6, 7),
            donate_argnums=(3,)).lower(
                params, arr((1, 1, prog.dim), prog.dtype),
                arr((), jnp.int32), caches, arr((mb,), jnp.int32),
                arr((), jnp.int32), prog, bs, arr((), jnp.float32),
                arr((), jnp.float32), arr((), jnp.int32))}
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
    """The reference's comparison of one sampled request (``served``
    logit rows with their Gumbel noise, the control's second pass
    with it) beside the weights."""
    import jax
    import jax.numpy as jnp
    from perf.reference import joyai_mtp_block_f32 as reference

    _, chip = plain._one_chip()
    _, params = _weights(config, chip)
    model = config['model']
    fmt = config['control']['weight_format']

    def gaps(w, toks, positions, seed):
        noise = reference.gumbel_noise(seed, positions,
                                       model['vocab_size'])
        ref = reference.logits_at(w, toks, positions, model) + noise
        low = reference.logits_at(w, toks, positions, model,
                                  fmt) + noise
        return ref.max(-1), low.argmax(-1)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    plain._report(
        f'reference pad_to={pad_to} served={served}',
        jax.jit(gaps).lower(params, arr((pad_to,), jnp.int32),
                            arr((served,), jnp.int32),
                            arr((), jnp.uint32)).compile())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True)
    parser.add_argument('--slots', default='')
    parser.add_argument('--shared-blocks', type=int, default=32)
    parser.add_argument('--own-blocks', type=int, default=193)
    parser.add_argument('--view-blocks', type=int, default=0)
    parser.add_argument('--chunks', default='',
                        help='prefill buckets to compile (the engine '
                        'has one program a power of two up to '
                        'prefill_chunk); default: the widest')
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
            size_serve(config, slots, blocks,
                       args.view_blocks or None, args.text_dir,
                       tuple(int(c) for c in args.chunks.split(',')
                             if c))
        except Exception as e:  # pylint: disable=broad-except
            print(f'slots={slots} blocks={blocks}: refused: '
                  f'{type(e).__name__}: {str(e)[:2000]}', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
