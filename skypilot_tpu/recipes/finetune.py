"""Llama finetuning recipe (flagship).

TPU-native port of the reference's ``llm/llama-3_1-finetuning``
(torchtune LoRA on Llama-3.1) and
``examples/tpu/v6e/train-llama3-8b.yaml`` (HF Trainer FSDP): one
process per TPU host, ``jax.distributed`` bootstrap from the env
contract, (dp, fsdp, tp) mesh over all chips, LoRA or full finetune,
orbax async checkpointing for spot resumption, step callbacks for
``x bench``.

Data: a tokenized ``.npy``/``.bin`` file of uint16/int32 token ids
(``--data``), or synthetic tokens (``--synthetic``) for benchmarking.

Run (single host or any slice — same command, reference parity with
the v6e README):
    python -m skypilot_tpu.recipes.finetune \
        --model llama3.1-8b --seq 2048 --batch 8 --steps 100 \
        --lora-rank 16 --checkpoint-dir /checkpoints
"""
import argparse
import contextlib
import json
import os
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument('--model', default='llama3.2-1b')
    p.add_argument('--seq', type=int, default=2048)
    p.add_argument('--batch', type=int, default=8,
                   help='GLOBAL batch size')
    p.add_argument('--steps', type=int, default=100)
    p.add_argument('--lr', type=float, default=3e-4)
    p.add_argument('--lora-rank', type=int, default=16)
    p.add_argument('--full-ft', action='store_true',
                   help='full finetune instead of LoRA')
    p.add_argument('--tp', type=int, default=1)
    p.add_argument('--dp', type=int, default=1)
    p.add_argument('--ep', type=int, default=1,
                   help='expert-parallel degree (MoE models)')
    p.add_argument('--sp', type=int, default=1,
                   help='sequence-parallel degree (ring attention)')
    p.add_argument('--pp', type=int, default=1,
                   help='pipeline-parallel degree (GPipe schedule)')
    p.add_argument('--microbatches', type=int, default=None,
                   help='pipeline microbatches (default 2*pp)')
    p.add_argument('--data', default=None,
                   help='tokenized dataset (.npy of token ids)')
    p.add_argument('--synthetic', action='store_true', default=None)
    # Default from the env contract: a managed job declares its
    # checkpoint base once (task env SKYTPU_CHECKPOINT_DIR), the
    # recipe picks it up here AND the jobs controller reads the same
    # env to report "resuming at step N" on recovery.
    p.add_argument('--checkpoint-dir',
                   default=os.environ.get('SKYTPU_CHECKPOINT_DIR'))
    p.add_argument('--checkpoint-interval', type=int, default=50)
    # Elastic resume (docs/resilience.md): when the latest committed
    # checkpoint was saved from a DIFFERENT device count (a
    # NEXT_BEST_SHAPE recovery landed on a smaller slice), re-plan
    # the mesh for the devices actually here (auto_mesh_config
    # already does) and rescale the global batch to keep the
    # per-device batch constant. The checkpoint engine re-shards the
    # saved shards onto the new mesh on restore.
    p.add_argument('--elastic', action='store_true', default=True)
    p.add_argument('--no-elastic', dest='elastic',
                   action='store_false')
    p.add_argument('--elastic-scale-lr', action='store_true',
                   help='scale the learning rate linearly with the '
                        'device ratio on an elastic resize')
    p.add_argument('--param-dtype', default='bf16',
                   choices=['bf16', 'f32'])
    p.add_argument('--log-every', type=int, default=10)
    return p.parse_args()


def _elastic_design(lineage_dir, n_now, global_batch):
    """The job's DESIGNED shape reference: device count + global
    batch of the FIRST launch, persisted as ``design.json`` in the
    checkpoint lineage (atomic write; ignored by the step scanners).

    Rescaling must reference the design, not the last checkpoint's
    device count: ``--batch`` re-parses as the designed value on
    every relaunch, so scaling it by now/saved would double the
    per-device batch on a scale-back-up (8 -> 4 -> 8) and halve it
    on consecutive step-downs. The first launch always runs at the
    designed shape (NEXT_BEST_SHAPE only resizes recoveries), so
    recording (devices, batch) when the file is absent on a
    non-resized run captures the design exactly."""
    path = os.path.join(lineage_dir, 'design.json')
    try:
        with open(path, encoding='utf-8') as f:
            return json.load(f)
    except (OSError, ValueError):
        pass
    doc = {'device_count': n_now, 'global_batch': global_batch}
    if os.environ.get('SKYTPU_ELASTIC_RESIZED'):
        # Resized relaunch of a PRE-elastic lineage (no design file):
        # the design is unknown — best effort is the last
        # checkpoint's device count, and the guess is not persisted.
        from skypilot_tpu import checkpoint as checkpoint_lib
        saved = checkpoint_lib.saved_device_count(lineage_dir)
        if saved:
            doc['device_count'] = saved
        return doc
    try:
        os.makedirs(lineage_dir, exist_ok=True)
        tmp = f'{path}.{os.getpid()}'
        with open(tmp, 'w', encoding='utf-8') as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only mount: run with the in-memory design
    return doc


def data_iterator(args, vocab_size, rng):
    if args.data:
        tokens = np.load(args.data, mmap_mode='r')
        n = len(tokens) - (args.seq + 1)
        while True:
            starts = rng.integers(0, n, size=args.batch)
            yield np.stack([
                np.asarray(tokens[s:s + args.seq + 1], np.int32)
                for s in starts
            ])
    else:
        while True:
            yield rng.integers(0, vocab_size,
                               size=(args.batch, args.seq + 1),
                               dtype=np.int32)


def _flash_kernel_census(step_fn, state, batch) -> dict:
    """How many times each Pallas flash kernel appears in the lowered
    train step (the module XLA compiles; the layer scan holds each
    once). All zero means attention lowered to the XLA reference —
    the log says so instead of leaving it to the step time."""
    from skypilot_tpu.ops import attention as attention_ops
    text = step_fn.lower(state, batch).as_text()
    return {name: text.count(f'kernel_name = "{name}"')
            for name in attention_ops.KERNEL_NAMES}


def main():
    # The start-up log's stages (docs/observability.md, "Start-up
    # and compilation") are entered on this stack; ``_train`` closes
    # it after the first step, and a start-up that raises closes it
    # here.
    with contextlib.ExitStack() as starting:
        _train(starting)


def _train(starting: contextlib.ExitStack):
    args = parse_args()

    from skypilot_tpu.utils import jax_runtime

    # ``train.start`` stays open until the first step, the one that
    # compiles, has returned.
    starting.enter_context(jax_runtime.stage('train.start'))
    with jax_runtime.stage('train.start.backend'):
        jax_runtime.configure_compile_cache()
        from skypilot_tpu import callbacks
        from skypilot_tpu.parallel import distributed
        distributed.initialize()  # no-op single-host

        import jax
        import jax.numpy as jnp

        device = jax_runtime.device_facts()
    if jax.process_index() == 0:
        print(jax_runtime.device_line(device), flush=True)

    from skypilot_tpu.models import llama
    from skypilot_tpu.parallel import (MeshConfig, auto_mesh_config,
                                       build_train_step,
                                       init_train_state,
                                       instrument_train_step,
                                       make_mesh)
    from skypilot_tpu.parallel.train import default_optimizer

    config = llama.get_config(args.model, max_seq_len=args.seq)
    # Multi-slice jobs (SKYTPU_NUM_SLICES from the gang driver) get
    # the hybrid mesh: dp spans slices so only its gradient
    # all-reduce crosses DCN; fsdp/tp/sp collectives stay on ICI.
    from skypilot_tpu.parallel import mesh as mesh_lib
    num_slices = mesh_lib.num_slices_from_env()
    mesh_cfg = auto_mesh_config(tp=args.tp, dp=args.dp, ep=args.ep,
                                sp=args.sp, pp=args.pp,
                                num_slices=num_slices)
    mesh = make_mesh(mesh_cfg, num_slices=num_slices)
    if jax.process_index() == 0:
        print(f'devices={jax.device_count()} mesh={mesh_cfg} '
              f'slices={num_slices} model={args.model} '
              f'params={config.num_params() / 1e9:.2f}B')

    # Elastic resume: a checkpoint saved from more (or fewer) devices
    # than are visible now means a resize happened between launches.
    # Rescale the global batch by the device ratio BEFORE building
    # the optimizer/iterator so per-device batch (and therefore HBM
    # footprint and per-example numerics) stays what the job was
    # tuned for; the restore below re-shards the saved state onto
    # this mesh.
    if args.elastic and args.checkpoint_dir:
        import math as math_mod

        from skypilot_tpu.data import checkpoint as ckpt_facade
        design = _elastic_design(
            ckpt_facade.task_checkpoint_dir(args.checkpoint_dir),
            jax.device_count(), args.batch)
        n_design = design['device_count']
        n_now = jax.device_count()
        if n_design and n_design != n_now:
            data_n = math_mod.prod(
                getattr(mesh_cfg, a) for a in mesh_lib.data_axes())
            scaled = max(data_n,
                         design['global_batch'] * n_now // n_design
                         // data_n * data_n)
            if jax.process_index() == 0:
                resized = os.environ.get('SKYTPU_ELASTIC_RESIZED')
                print(f'elastic resume: designed for {n_design} '
                      f'chips, running on {n_now}'
                      f'{f" ({resized})" if resized else ""}; '
                      f'global batch {args.batch} -> {scaled}')
            args.batch = scaled
            if args.elastic_scale_lr:
                args.lr = args.lr * n_now / n_design

    with jax_runtime.stage('train.start.state'):
        param_dtype = jnp.bfloat16 if args.param_dtype == 'bf16' \
            else jnp.float32
        optimizer = default_optimizer(learning_rate=args.lr)
        state, shardings = init_train_state(
            config, mesh, jax.random.PRNGKey(0), optimizer=optimizer,
            param_dtype=param_dtype,
            lora_rank=None if args.full_ft else args.lora_rank)
        step_fn = build_train_step(config, mesh, shardings,
                                   optimizer=optimizer,
                                   pipeline_microbatches=args.microbatches)
        # Step-time / tokens-per-sec / goodput buckets / MFU land in the
        # process metrics registry and are published to the host agent's
        # /metrics (textfile bridge) so the driver scrapes them
        # cluster-wide. The accelerator for the MFU peak arrives via the
        # SKYTPU_ACCELERATOR env stamp (runtime/env_contract.py).
        step_fn = instrument_train_step(
            step_fn, tokens_per_step=args.batch * args.seq,
            model_config=config, full_finetune=args.full_ft)
        from skypilot_tpu.metrics import publish as publish_lib
        publisher = publish_lib.start_publisher('train')

        ckpt = None
        start_step = 0
        if args.checkpoint_dir:
            from skypilot_tpu.data.checkpoint import CheckpointManager
            ckpt = CheckpointManager(
                args.checkpoint_dir,
                save_interval_steps=args.checkpoint_interval)
            state, start_step = ckpt.restore_or(state)
            if jax.process_index() == 0 and start_step:
                info = ckpt.last_restore or {}
                reshard = ' (resharded onto the current mesh)' \
                    if info.get('resharded') else ''
                print(f'resumed from checkpoint at step {start_step}'
                      f'{reshard}')
    # Recovery relaunch: price the dead time since the controller
    # observed the failure into the goodput `recovery_stall` bucket
    # (no-op outside a managed-job recovery).
    from skypilot_tpu.metrics import goodput as goodput_lib
    goodput_lib.note_recovery_stall_from_env()

    callbacks.init(total_steps=args.steps)
    rng = np.random.default_rng(jax.process_index())
    batches = data_iterator(args, config.vocab_size, rng)
    tokens_per_step = args.batch * args.seq
    t_start = time.time()
    starting.enter_context(jax_runtime.stage('train.start.first_step'))
    for step in range(start_step, args.steps):
        batch_np = next(batches)
        batch = {'tokens': jnp.asarray(batch_np)}
        if step == start_step and jax.process_index() == 0:
            wq = state.params['layers']['wq']
            print('train_step ' + json.dumps({
                'kernels': _flash_kernel_census(step_fn.inner, state,
                                                batch),
                'wq': wq.shape,
                'wq_shard': wq.addressable_shards[0].data.shape,
                'wq_devices': len(wq.sharding.device_set),
            }), flush=True)
        callbacks.step_begin()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics['loss'])
        callbacks.step_end()
        if step == start_step:
            # From here a lowering lands inside a step: counted, and
            # logged with the program's name.
            starting.close()
            jax_runtime.mark_ready()
        if ckpt is not None:
            ckpt.maybe_save(step, state)
        if jax.process_index() == 0 and \
                (step % args.log_every == 0 or
                 step == args.steps - 1):
            dt = time.time() - t_start
            done = step - start_step + 1
            tps = done * tokens_per_step / dt
            print(f'step {step} loss={float(metrics["loss"]):.4f} '
                  f'grad_norm={float(metrics["grad_norm"]):.3f} '
                  f'tokens/s={tps:.0f} '
                  f'tokens/s/chip={tps / jax.device_count():.0f}')
    if ckpt is not None:
        ckpt.wait()
        ckpt.close()
    publisher.close()
    starting.close()  # still open after a run of no steps
    if jax.process_index() == 0:
        runtime = dict(jax_runtime.runtime_facts(),
                       startup=jax_runtime.startup_seconds())
        print(f'runtime {json.dumps(runtime)}')
        print('finetune done.')


if __name__ == '__main__':
    main()
