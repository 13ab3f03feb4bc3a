"""Driver of a training configuration: the program's own
``parallel.build_train_step`` on a ``TrainState`` laid out and sharded
as ``init_qlora_state`` / ``init_train_state`` lay it out, fed a fresh
batch from the host each step as ``recipes/finetune.py`` does.

Set-up builds ONE object - the compiled step with its state - drives
it from the seed through its first steps, and hands that same object
to the window. The plain reference follows those first steps after the
window, once the program's state is freed."""
import time
from typing import Any, Dict, List

import numpy as np

from perf.lib import harness
from perf.lib import loadgen
from perf.lib import weights as weights_lib


def _mesh_and_config(config, chips: int):
    """The program's mesh and model config for this configuration."""
    import dataclasses
    import jax
    from skypilot_tpu.parallel import mesh as mesh_lib
    prog = harness.program_config(config)
    if 'remat_saves' in config['build']:
        prog = dataclasses.replace(
            prog, remat_saves=config['build']['remat_saves'])
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshConfig(**config['build']['mesh']),
        devices=jax.devices()[:chips])
    return prog, mesh


def _weight_shardings(config, prog, mesh):
    """The program's own sharding rules for the base weights and the
    LoRA factors, on this mesh: ``(param_shardings,
    lora_shardings)``."""
    from skypilot_tpu.parallel import lora as lora_lib
    from skypilot_tpu.parallel import train as train_lib
    if config['weights'] == 'int8':
        rules = train_lib.quantized_sharding_rules(prog)
    else:
        from skypilot_tpu.models import llama
        rules = llama.param_sharding_rules(prog)
    return (train_lib.sharding_tree(rules, mesh),
            train_lib.sharding_tree(
                lora_lib.lora_sharding_rules(prog), mesh))


def _make_weights(config, prog, mesh, seed):
    """Benchmark-made weights and LoRA factors, sharded over the mesh
    by the program's own rules: ``(params, lora, param_shardings,
    lora_shardings)``."""
    import jax.numpy as jnp
    param_sh, lora_sh = _weight_shardings(config, prog, mesh)
    params, lora = weights_lib.make_weights(
        config['model'], seed, int8=config['weights'] == 'int8',
        lora_rank=int(config['build']['lora_rank']),
        dtype=jnp.dtype(config['build']['param_dtype']),
        shardings=(param_sh, lora_sh))
    return params, lora, param_sh, lora_sh


def _state_shardings(lora, lora_sh, param_sh, optimizer, mesh):
    """Shardings of the whole ``TrainState``; ``lora`` may be arrays
    or shapes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from skypilot_tpu.parallel import train as train_lib
    opt_sh = train_lib.opt_state_shardings(
        jax.eval_shape(lambda: lora), lora_sh,
        jax.eval_shape(optimizer.init, lora), mesh)
    return train_lib.TrainState(
        step=NamedSharding(mesh, P()), params=param_sh,
        opt_state=opt_sh, lora=lora_sh)


def _build_state(config, prog, mesh, optimizer, seed):
    """The train state from benchmark-made weights, with the
    program's own shardings for it: ``(state, state_shardings)``."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.parallel import train as train_lib

    params, lora, param_sh, lora_sh = _make_weights(config, prog, mesh,
                                                    seed)
    shardings = _state_shardings(lora, lora_sh, param_sh, optimizer,
                                 mesh)
    opt_state = jax.jit(optimizer.init,
                        out_shardings=shardings.opt_state)(lora)
    step0 = jax.device_put(jnp.zeros((), jnp.int32), shardings.step)
    state = train_lib.TrainState(step=step0, params=params,
                                 opt_state=opt_state, lora=lora)
    return state, shardings


def state_shapes(config, prog, mesh, optimizer):
    """The same state as shapes with their shardings, for a compile
    against a described chip (``tools/size_deviceless.py``): nothing
    is made."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.parallel import train as train_lib
    param_sh, lora_sh = _weight_shardings(config, prog, mesh)
    params, lora = jax.eval_shape(
        lambda: weights_lib.make_weights(
            config['model'], 0, int8=config['weights'] == 'int8',
            lora_rank=int(config['build']['lora_rank']),
            dtype=jnp.dtype(config['build']['param_dtype'])))
    shardings = _state_shardings(lora, lora_sh, param_sh, optimizer,
                                 mesh)
    state = train_lib.TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        opt_state=jax.eval_shape(optimizer.init, lora), lora=lora)
    return jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sh),
        state, shardings), shardings


def _adam_mu(opt_state):
    """The first-moment tree inside the optimizer's state."""
    import optax
    import jax
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(
            x, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise harness.HarnessError(
            f'expected one Adam state in the optimizer, found '
            f'{len(found)}')
    return found[0].mu


def _host_f32(tree) -> Dict[str, np.ndarray]:
    import jax
    return {k: np.asarray(jax.device_get(v)).astype(np.float32)
            for k, v in tree.items()}


def worst_leaf_gap(program: Dict[str, np.ndarray],
                   reference: Dict[str, np.ndarray]) -> float:
    """The widest gap between the program's norm and the reference's
    over the leaves (one per factor per layer), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger - some gradients are all but zero."""
    prog_n, ref_n = [], []
    for name in sorted(reference):
        p = program[name].reshape(program[name].shape[0], -1)
        r = reference[name].reshape(reference[name].shape[0], -1)
        prog_n.extend(np.linalg.norm(p, axis=1))
        ref_n.extend(np.linalg.norm(r, axis=1))
    prog_n, ref_n = np.asarray(prog_n), np.asarray(ref_n)
    scale = np.maximum(ref_n, np.median(ref_n))
    return float(np.max(np.abs(prog_n - ref_n) / scale))


def readings(program: Dict[str, Any], ref: Dict[str, Any]
             ) -> Dict[str, float]:
    """The numbers compared, each under the name of its limit:
    ``program`` and ``ref`` carry ``losses``, ``first_grad`` and
    ``change`` (the latter two as host float32 trees)."""
    return {
        'loss_rel_gap': max(
            abs(p - r) / r for p, r in zip(program['losses'],
                                           ref['losses'])),
        'first_grad_norm_worst_leaf_gap': worst_leaf_gap(
            program['first_grad'], ref['first_grad']),
        'param_change_norm_worst_leaf_gap': worst_leaf_gap(
            program['change'], ref['change'])}


def control_readings(loaded: Dict[str, Any], seed: int, seconds: float,
                     rehearse: bool) -> Dict[str, Dict[str, float]]:
    """The control of this kind of cell, at the cell's own size: the
    reference's first steps at the configuration's ``control``
    precision, held against the reference at the stated precision."""
    del seconds
    config, traffic = loaded['config'], loaded['traffic']
    chips = loaded['cell']['chips']
    harness.require_devices(chips, rehearse)
    model, build = config['model'], config['build']
    reference = harness.reference_for(config)
    prog, mesh = _mesh_and_config(config, chips)
    params, lora, _, _ = _make_weights(config, prog, mesh, seed)
    gen = loadgen.generator_for(traffic['kind'])
    batches = [gen(traffic, seed, i, int(build['batch']),
                   model['vocab_size'])
               for i in range(int(config['reference_steps']))]

    def follow(weight_format):
        out = reference.follow_training(
            params, lora, batches, model, config['optimizer'],
            float(build['lora_scale']), weight_format=weight_format)
        return {'losses': out['losses'],
                'first_grad': _host_f32(out['first_grad']),
                'change': _host_f32(out['change'])}

    sound = follow(None)
    control = follow(config['control']['weight_format'])
    return {'control': readings(control, sound)}


def run(loaded: Dict[str, Any], seed: int, seconds: float, trace: bool,
        rehearse: bool, t_process_start: float) -> Dict[str, Any]:
    """One run of a training cell."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.parallel import train as train_lib
    from skypilot_tpu.utils import jax_runtime

    config, traffic, cell = (loaded['config'], loaded['traffic'],
                             loaded['cell'])
    device = harness.require_devices(cell['chips'], rehearse)
    jax_runtime.configure_compile_cache()
    compiles = harness.CompileCounter()
    build, model, chips = config['build'], config['model'], \
        cell['chips']
    prog, mesh = _mesh_and_config(config, chips)
    opt = config['optimizer']
    optimizer = train_lib.default_optimizer(
        learning_rate=opt['lr'], weight_decay=opt['weight_decay'],
        b1=opt['b1'], b2=opt['b2'], grad_clip=opt['grad_clip'])
    state, shardings = _build_state(config, prog, mesh, optimizer,
                                    seed)
    step_fn = train_lib.build_train_step(
        prog, mesh, shardings, optimizer=optimizer,
        lora_scale=float(build['lora_scale']))
    bshard = train_lib.batch_sharding(mesh)
    batch, seq = int(build['batch']), int(traffic['seq_len'])
    gen = loadgen.generator_for(traffic['kind'])

    def feed(i: int):
        with harness.annotate('make_batch'):
            rows = gen(traffic, seed, i, batch, model['vocab_size'])
        with harness.annotate('feed_batch'):
            return rows, {'tokens': jax.device_put(rows, bshard)}

    # ---- set-up: the first steps, which the reference follows
    lora0 = _host_f32(state.lora)
    n_ref = int(config['reference_steps'])
    ref_batches, losses, first_grad = [], [], None
    for i in range(n_ref):
        rows, dev_batch = feed(i)
        ref_batches.append(rows)
        state, metrics = step_fn(state, dev_batch)
        losses.append(float(metrics['loss']))
        if i == 0:
            # The gradient as the optimizer got it, from its state
            # after one step: mu = (1 - b1) g.
            first_grad = {k: v / (1.0 - opt['b1']) for k, v in
                          _host_f32(_adam_mu(state.opt_state)).items()}
    change = {k: v - lora0[k]
              for k, v in _host_f32(state.lora).items()}

    tracer = harness.TraceWindow(cell['name']) if trace and \
        not rehearse else None
    trace_steps = int(traffic['trace_steps'])
    # ---- the window: whole steps; it closes with the first step
    # that ends at or after ``seconds``.
    nxt = feed(n_ref)
    jax.block_until_ready(nxt[1])
    t_open = time.perf_counter()
    setup_s = t_open - t_process_start
    compiles.open()
    ends: List[float] = []
    i = n_ref
    while True:
        if tracer is not None and len(ends) == 1:
            tracer.start()
        with harness.annotate('step_call'):
            state, metrics = step_fn(state, nxt[1])
        i += 1
        nxt = feed(i)
        with harness.annotate('wait_step'):
            losses.append(float(metrics['loss']))
        ends.append(time.perf_counter())
        if tracer is not None and len(ends) == 1 + trace_steps:
            tracer.stop()
        if ends[-1] - t_open >= seconds:
            break
    if tracer is not None and tracer.seconds is None:
        tracer.stop()
    compiles.close()
    window_s = ends[-1] - t_open
    tokens_per_step = batch * seq
    rate = len(ends) * tokens_per_step / window_s / chips
    step_ms = np.diff([t_open] + ends) * 1e3
    harness.say(
        f'window {window_s:.2f} s: {len(ends)} steps of '
        f'{tokens_per_step} tokens on {chips} chip(s); step ms p50 '
        f'{np.median(step_ms):.1f} max {step_ms.max():.1f}; '
        f'compilations inside the window {compiles.inside}')
    harness.say('losses: first ' +
                ' '.join(f'{x:.4f}' for x in losses[:6]) +
                f'; all {len(losses)} in [{min(losses):.4f}, '
                f'{max(losses):.4f}]')
    # The step as the window ran it, found again in the compile cache,
    # for the compiler's account of what a chip holds while it runs.
    peak = None
    if not rehearse:
        t_mem = time.perf_counter()
        peak = harness.memory_peak_bytes(
            [step_fn.lower(state, nxt[1]).compile()]
            if hasattr(step_fn, 'lower') else [])
        harness.say(f'memory read in {time.perf_counter() - t_mem:.1f}'
                    ' s (after the window)')

    # ---- correct: the first steps against the plain reference, on
    # the weights as data, once the program's own state is freed.
    weights = state.params
    del state, nxt, metrics
    reference = harness.reference_for(config)
    t_ref = time.perf_counter()
    ref = reference.follow_training(
        weights, {k: jnp.asarray(v) for k, v in lora0.items()},
        ref_batches, model, opt, float(build['lora_scale']))
    ref_s = time.perf_counter() - t_ref
    results: List[Dict[str, Any]] = []
    limits = config['limits']
    ok = harness.compared('compilations_in_window', compiles.inside,
                          0, results)
    band = max(abs(x - np.log(model['vocab_size'])) for x in losses) \
        if all(np.isfinite(losses)) else float('inf')
    ok &= harness.compared('loss_off_ln_vocab_max', band,
                           limits['loss_off_ln_vocab_max'], results)
    got = readings(
        {'losses': losses, 'first_grad': first_grad, 'change': change},
        {'losses': ref['losses'],
         'first_grad': _host_f32(ref['first_grad']),
         'change': _host_f32(ref['change'])})
    for name, value in got.items():
        ok &= harness.compared(name, value, limits[name], results)
    harness.say(f'reference followed {n_ref} steps in {ref_s:.1f} s '
                f'(not counted in setup_s)')

    # Rows x heads are split over the chips (rows over fsdp, heads
    # over tp): a chip's share of attention is batch / chips rows of
    # all heads.
    facts = {'batch': batch, 'batch_per_chip': batch / chips,
             'seq': seq, 'chips': chips, 'frozen_base': True,
             'steps_in_window': len(ends)}
    failed = int(sum(1 for x in losses[n_ref:] if not np.isfinite(x)))
    return {'correct': bool(ok), 'attempted': len(ends),
            'failed': failed,
            'e2e': {'setup_s': setup_s, 'train_tok_s_chip': rate},
            'device': device, 'memory_peak_bytes': peak,
            'registry': None,
            'tracer': tracer if tracer is not None and tracer.seconds
            else None,
            'facts': facts, 'model': model, 'compared': results}
