"""Sharded train-step builder.

Given a model config and a mesh, produce a jitted
``train_step(state, batch) -> (state, metrics)`` whose params/opt
state live sharded per ``models.llama.param_sharding_rules`` (FSDP/TP)
and whose batch is sharded over the data axes. XLA inserts the
all-gathers (FSDP weight gathering) and reduce-scatters (gradients)
over ICI.

This is the in-tree replacement for the reference's FSDP recipes
(``llm/llama-3_1-finetuning/lora.yaml``,
``examples/tpu/v6e/train-llama3-8b.yaml`` — torch FSDP via HF
accelerate), redesigned as pjit sharding rather than wrapper classes.
"""
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import collective_matmul

Params = llama.Params


@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Params
    opt_state: Any
    # When LoRA-finetuning, params are frozen and only `lora` trains.
    lora: Optional[Params] = None


jax.tree_util.register_dataclass(
    TrainState, data_fields=['step', 'params', 'opt_state', 'lora'],
    meta_fields=[])


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      b1: float = 0.9, b2: float = 0.95,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=b1, b2=b2, eps=1e-8,
                    weight_decay=weight_decay,
                    mu_dtype=jnp.float32),
    )


def sharding_tree(rules: Params, mesh: Mesh):
    """PartitionSpec tree -> NamedSharding tree (shared helper; also
    used by models/decode.decode_shardings)."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), rules,
        is_leaf=lambda x: isinstance(x, P))


_sharding_tree = sharding_tree


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(('dp', 'fsdp', 'ep'), None))


def opt_state_shardings(trainable_shape, trainable_shardings,
                        opt_state_shape, mesh):
    """Match opt-state leaves (Adam mu/nu mirror the trainable tree)
    to their param's sharding by TREE PATH, not shape: wq and wo
    share a shape but have transposed shardings, so shape matching
    would pin wo's moments to wq's layout and reshard every step."""
    trainable_by_path = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainable_shape)[0]:
        shard = trainable_shardings
        for path_key in path:
            shard = shard[path_key.key]
        trainable_by_path[tuple(str(k) for k in path)] = (
            leaf.shape, shard)

    def opt_sharding_for(path, shape_leaf):
        opt_path = tuple(str(k) for k in path)
        # The params-shaped subtree sits at some suffix of the opt
        # path (e.g. opt_state[1].mu['layers']['wq'] ends with the
        # param path ('layers', 'wq')).
        for ppath, (pshape, shard) in trainable_by_path.items():
            if (len(ppath) <= len(opt_path)
                    and opt_path[-len(ppath):] == ppath
                    and pshape == shape_leaf.shape):
                return shard
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(
        opt_sharding_for, opt_state_shape)


def plan_train_state(config: llama.LlamaConfig, mesh,
                     optimizer: Optional[
                         optax.GradientTransformation] = None,
                     param_dtype=jnp.float32,
                     lora_rank: Optional[int] = None,
                     key: Optional[jax.Array] = None,
                     lora_key: Optional[jax.Array] = None):
    """Shape-and-sharding plan for the train state WITHOUT allocating
    anything: returns (init_fn, state_shape, state_shardings).

    Works with a concrete ``Mesh`` or an ``AbstractMesh`` (the latter
    for compile-only validation of target-scale configs — e.g. does
    the 8B config shard onto a 16-device v5p mesh — without hardware).
    """
    if optimizer is None:
        optimizer = default_optimizer()
    if key is None:
        key = jax.random.PRNGKey(0)
    use_pp = mesh.shape.get('pp', 1) > 1
    if use_pp:
        from skypilot_tpu.parallel import pipeline as pipeline_lib
        pipeline_lib.validate_pipeline_config(config, mesh,
                                              lora_rank=lora_rank)
    rules = llama.param_sharding_rules(config, pipeline=use_pp)
    param_shardings = _sharding_tree(rules, mesh)

    def _init() -> TrainState:
        params = llama.init_params(config, key, dtype=param_dtype)
        lora_p = None
        if lora_rank is not None:
            from skypilot_tpu.parallel import lora as lora_lib
            lora_p = lora_lib.init_lora(
                config, lora_key if lora_key is not None else key,
                rank=lora_rank, dtype=param_dtype)
            opt_state = optimizer.init(lora_p)
        else:
            opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32),
                          params=params, opt_state=opt_state,
                          lora=lora_p)

    # Derive shardings for the full state via eval_shape: params use
    # the rules; anything param-shaped in opt_state mirrors the
    # sharding of the matching trainable leaf; scalars replicate.
    state_shape = jax.eval_shape(_init)
    trainable_shardings = param_shardings
    if lora_rank is not None:
        from skypilot_tpu.parallel import lora as lora_lib
        lora_shardings = _sharding_tree(
            lora_lib.lora_sharding_rules(config, pipeline=use_pp),
            mesh)
        trainable_shardings = lora_shardings

    trainable_shape = (state_shape.lora if lora_rank is not None
                       else state_shape.params)
    opt_shardings = opt_state_shardings(
        trainable_shape, trainable_shardings,
        state_shape.opt_state, mesh)
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()),
        params=param_shardings,
        opt_state=opt_shardings,
        lora=(trainable_shardings if lora_rank is not None else None),
    )

    return _init, state_shape, state_shardings


def init_train_state(config: llama.LlamaConfig, mesh: Mesh,
                     key: jax.Array,
                     optimizer: Optional[
                         optax.GradientTransformation] = None,
                     param_dtype=jnp.float32,
                     lora_rank: Optional[int] = None,
                     lora_key: Optional[jax.Array] = None
                     ) -> Tuple[TrainState, Any]:
    """Initialize params DIRECTLY sharded on the mesh (out_shardings on
    the init closure — no host-memory detour, required for 8B+).

    Returns (state, state_shardings) — the latter feeds
    ``build_train_step``.
    """
    init, _, state_shardings = plan_train_state(
        config, mesh, optimizer=optimizer, param_dtype=param_dtype,
        lora_rank=lora_rank, key=key, lora_key=lora_key)
    init_fn = jax.jit(init, out_shardings=state_shardings)
    state = init_fn()
    return state, state_shardings


def _scale_spec(spec: P) -> P:
    """Sharding for a quantized weight's per-output-channel scale
    (shape = weight shape with the contraction axis collapsed to 1):
    same spec with that size-1 axis unsharded."""
    parts = list(spec)
    if len(parts) >= 2:
        parts[-2] = None
    return P(*parts)


def quantized_sharding_rules(config: llama.LlamaConfig,
                             pipeline: bool = False) -> Params:
    """``llama.param_sharding_rules`` mapped onto an int8-quantized
    tree: {'q','s'} pairs for the big matmuls + lm_head (matching
    ``quant.init_quantized``'s structure), originals elsewhere."""
    from skypilot_tpu.models import quant as quant_mod
    rules = llama.param_sharding_rules(config, pipeline=pipeline)
    out = dict(rules)
    layers = dict(rules['layers'])
    for name in quant_mod._LAYER_MATMULS:  # pylint: disable=protected-access
        if name in layers:
            layers[name] = {'q': layers[name],
                            's': _scale_spec(layers[name])}
    out['layers'] = layers
    if 'lm_head' in rules:
        out['lm_head'] = {'q': rules['lm_head'],
                          's': _scale_spec(rules['lm_head'])}
    return out


def init_qlora_state(config: llama.LlamaConfig, mesh: Mesh,
                     key: jax.Array, lora_rank: int = 16,
                     optimizer: Optional[
                         optax.GradientTransformation] = None,
                     lora_key: Optional[jax.Array] = None
                     ) -> Tuple[TrainState, TrainState]:
    """QLoRA train state: int8-quantized FROZEN base (streamed init —
    the bf16 tree never fully materializes, so 8B fits a 16 GB chip)
    + bf16 LoRA adapters and optimizer state, all mesh-sharded.
    Matches the reference's flagship finetune recipe
    (``llm/llama-3_1-finetuning/lora.yaml``) at 8B scale on hardware
    where a bf16 base cannot fit; the forward runs the int8 base
    through ``llama.matmul`` (in-register dequant on the MXU path).

    Returns (state, state_shardings) — feed both to
    ``build_train_step`` exactly like ``init_train_state``."""
    from skypilot_tpu.models import quant as quant_mod
    from skypilot_tpu.parallel import lora as lora_lib
    if optimizer is None:
        optimizer = default_optimizer()
    use_pp = mesh.shape.get('pp', 1) > 1
    qshard = _sharding_tree(quantized_sharding_rules(
        config, pipeline=use_pp), mesh)
    params = quant_mod.init_quantized(config, key)
    params = jax.device_put(params, qshard)

    lora_shardings = _sharding_tree(
        lora_lib.lora_sharding_rules(config, pipeline=use_pp), mesh)

    def _init_trainable():
        lora_p = lora_lib.init_lora(
            config, lora_key if lora_key is not None else key,
            rank=lora_rank, dtype=jnp.bfloat16)
        return lora_p, optimizer.init(lora_p)

    lora_shape, opt_shape = jax.eval_shape(_init_trainable)
    opt_shardings = opt_state_shardings(lora_shape, lora_shardings,
                                        opt_shape, mesh)
    lora_p, opt_state = jax.jit(
        _init_trainable,
        out_shardings=(lora_shardings, opt_shardings))()
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt_state, lora=lora_p)
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()), params=qshard,
        opt_state=opt_shardings, lora=lora_shardings)
    return state, state_shardings


def make_ring_attention_impl(mesh: Mesh, axis_name: str = 'sp'):
    """attn_impl for sequence parallelism: ring attention under
    shard_map, composing with the auto-sharded jit around it. q/k/v
    are [B, T, H, D] with T sharded on 'sp' and H on 'tp'."""
    from jax import shard_map

    from skypilot_tpu.ops import ring_attention as ring

    spec = P(('dp', 'fsdp', 'ep'), axis_name, 'tp', None)
    fn = shard_map(
        functools.partial(ring.ring_attention, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

    def impl(q, k, v, angles):
        # RoPE outside the ring (elementwise in T, shards cleanly);
        # the single-chip path fuses it into the Pallas kernels
        # instead.
        from skypilot_tpu.ops import attention as attention_ops
        q = attention_ops.apply_rope(q, angles)
        k = attention_ops.apply_rope(k, angles)
        return fn(q, k, v)

    return impl


def make_flash_attention_impl(mesh: Mesh):
    """attn_impl for a multi-device mesh without sequence parallelism:
    the default fused-RoPE flash attention under shard_map, batch over
    the data axes and heads over 'tp'. XLA cannot partition a Mosaic
    custom call, so inside the auto-sharded jit the Pallas kernels
    must be handed per-device blocks explicitly; attention is
    independent per (batch row, KV head), so no collective is needed.
    KV heads shard over 'tp' with their query groups — the same split
    ``param_sharding_rules`` gives wq/wk/wv."""
    from jax import shard_map

    tp = mesh.shape['tp']
    spec = P(('dp', 'fsdp', 'ep'), None, 'tp', None)
    # check_vma off: pallas_call's outputs carry no varying-axes
    # annotation for the checker to follow.
    fn = shard_map(llama.default_attn_impl(), mesh=mesh,
                   in_specs=(spec, spec, spec, P()), out_specs=spec,
                   check_vma=False)

    def impl(q, k, v, angles):
        if k.shape[2] % tp:
            raise ValueError(
                f'n_kv_heads={k.shape[2]} is not divisible by tp={tp}: '
                'flash attention shards KV heads over the tp axis')
        return fn(q, k, v, angles)

    return impl


def build_train_step(config: llama.LlamaConfig, mesh: Mesh,
                     state_shardings: TrainState,
                     optimizer: Optional[
                         optax.GradientTransformation] = None,
                     lora_scale: float = 2.0,
                     donate: bool = True,
                     pipeline_microbatches: Optional[int] = None,
                     pipeline_schedule: str = 'gpipe'
                     ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                   Tuple[TrainState, Dict[str, jax.Array]]]:
    """The full training step: loss → grad → optimizer update, jitted
    with explicit in/out shardings.

    When the mesh has an ``sp`` axis > 1, activations shard their
    sequence dim over it and attention runs as ring attention
    (long-context: per-device memory stays O(T / sp)). Any other
    multi-device mesh runs flash attention under shard_map
    (``make_flash_attention_impl``). A ``pp`` axis
    > 1 runs the layer stack as a GPipe pipeline
    (``parallel/pipeline.py``) with ``pipeline_microbatches``
    microbatches (default 2*pp).

    When the mesh has ``tp`` > 1 (and neither ``sp`` nor ``pp``) and
    the config is dense, the residual stream between the blocks is
    sharded along the sequence over ``tp`` and each layer's four tp
    products are collective matmuls whose transfers run beside them
    (``parallel/collective_matmul.py``), in place of all-reduces that
    run alone; a batch whose T does not divide by tp takes the
    all-reduce path. Chosen by the mesh's shape alone; the gauge
    ``skytpu_train_tp_overlapped_products`` says which was built."""
    if optimizer is None:
        optimizer = default_optimizer()
    is_lora = state_shardings.lora is not None

    use_sp = mesh.shape.get('sp', 1) > 1
    use_pp = mesh.shape.get('pp', 1) > 1
    if use_sp:
        attn_impl = make_ring_attention_impl(mesh)
    elif mesh.size > 1 and not use_pp:
        attn_impl = make_flash_attention_impl(mesh)
    else:
        attn_impl = None  # one device: the model's own default
    act_sharding = NamedSharding(
        mesh, P(('dp', 'fsdp', 'ep'), 'sp', None)) if use_sp else None
    tp_overlap = None
    if (mesh.shape.get('tp', 1) > 1 and not use_sp and not use_pp
            and not config.n_experts):
        tp_overlap = collective_matmul.TpOverlap(mesh)
    collective_matmul.overlapped_gauge().set(
        collective_matmul.PRODUCTS_PER_LAYER if tp_overlap else 0)

    pp_loss = None
    pp_vg = None
    if use_pp:
        from skypilot_tpu.parallel import pipeline as pipeline_lib
        pipeline_lib.validate_pipeline_config(config, mesh)
        if pipeline_schedule == '1f1b':
            # 1F1B interleaves fwd/bwd so activation memory is O(pp)
            # rather than O(num_micro); it computes (loss, grads)
            # itself (the schedule IS the backward pass — see
            # pipeline.build_pipeline_value_and_grad).
            pp_vg = pipeline_lib.build_pipeline_value_and_grad(
                config, mesh, num_micro=pipeline_microbatches,
                lora=is_lora, lora_scale=lora_scale)
        elif pipeline_schedule == 'gpipe':
            pp_loss = pipeline_lib.build_pipeline_loss(
                config, mesh, num_micro=pipeline_microbatches,
                lora=is_lora, lora_scale=lora_scale)
        else:
            raise ValueError(
                f'unknown pipeline_schedule {pipeline_schedule!r} '
                "(choose 'gpipe' or '1f1b')")

    def step_fn(state: TrainState, batch: Dict[str, jax.Array]):
        if is_lora:
            def loss_of(lora_p):
                if pp_loss is not None:
                    return pp_loss(state.params, lora_p, batch)
                return llama.loss_fn(
                    jax.lax.stop_gradient(state.params), batch, config,
                    lora=lora_p, lora_scale=lora_scale,
                    attn_impl=attn_impl,
                    activation_sharding=act_sharding, mesh=mesh,
                    tp_overlap=tp_overlap)

            if pp_vg is not None:
                loss, grads = pp_vg(state.params, state.lora, batch)
            else:
                loss, grads = jax.value_and_grad(loss_of)(state.lora)
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.lora)
            new_lora = optax.apply_updates(state.lora, updates)
            new_state = TrainState(step=state.step + 1,
                                   params=state.params,
                                   opt_state=new_opt, lora=new_lora)
        else:
            def loss_of(params):
                if pp_loss is not None:
                    return pp_loss(params, batch)
                return llama.loss_fn(
                    params, batch, config, attn_impl=attn_impl,
                    activation_sharding=act_sharding, mesh=mesh,
                    tp_overlap=tp_overlap)

            if pp_vg is not None:
                loss, grads = pp_vg(state.params, batch)
            else:
                loss, grads = jax.value_and_grad(loss_of)(
                    state.params)
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = TrainState(step=state.step + 1,
                                   params=new_params,
                                   opt_state=new_opt, lora=None)
        grad_norm = optax.global_norm(grads)
        metrics = {'loss': loss, 'grad_norm': grad_norm}
        return new_state, metrics

    bshard = batch_sharding(mesh)
    metrics_sharding = {'loss': NamedSharding(mesh, P()),
                        'grad_norm': NamedSharding(mesh, P())}
    return jax.jit(
        step_fn,
        # bshard is a pytree prefix: every leaf of the batch dict
        # (tokens, loss_mask, ...) shards batch-dim over (dp, fsdp).
        in_shardings=(state_shardings, bshard),
        out_shardings=(state_shardings, metrics_sharding),
        donate_argnums=(0,) if donate else (),
    )


def instrument_train_step(step_fn: Callable,
                          tokens_per_step: Optional[int] = None,
                          model_config=None,
                          accelerator: Optional[str] = None,
                          full_finetune: bool = False
                          ) -> Callable:
    """Wrap a ``train_step(state, batch)`` so every call records
    step time and token throughput into the process metrics registry
    (``skytpu_train_step_seconds`` / ``skytpu_train_tokens_total`` /
    ``skytpu_train_tokens_per_sec`` — docs/observability.md).

    Returned separately from ``build_train_step`` on purpose: the
    bare jit object keeps its ``.trace``/``.lower`` surface for
    compile-only validation, and the wrapper stays a thin host-side
    shim the loop opts into (``recipes/finetune.py`` does).

    Timing is the interval between successive calls — in a loop that
    syncs per step (fetching the loss), that IS the step time; in a
    free-running async loop it converges to true step time once
    device backpressure throttles dispatch. The first call (compile)
    records nothing.

    Tokens per step default to ``batch['tokens'].shape`` minus the
    shifted label column, matching ``llama.loss_fn``'s convention.

    Tracing: when the loop runs inside a trace (a managed job's task
    gets the ``SKYTPU_TRACE_CONTEXT`` stamp from the gang driver),
    every step emits a ``train.step`` span covering the SAME interval
    the ``skytpu_train_step_seconds`` histogram observed — metrics
    and traces agree by construction. The step span stays the ambient
    context until the next call, so a checkpoint save submitted
    between steps nests under it as a ``ckpt.save`` child. The final
    step's span closes on the next call only (a loop that stops never
    reports its last interval to the histogram either).

    Goodput & MFU (docs/observability.md, Compute plane): every
    inter-step interval feeds the process goodput accountant — the
    first interval as ``compile``, the rest as ``compute`` minus any
    blocking time the checkpoint subsystem noted inside it. With
    ``model_config`` (param count) and a resolvable accelerator
    (``accelerator`` arg or the ``SKYTPU_ACCELERATOR`` env stamp →
    catalog peak FLOPs), each compute step also updates
    ``skytpu_mfu_ratio``. ``full_finetune`` selects 6N vs 4N
    FLOPs/token (frozen-base LoRA skips the base weight-grad).

    On-demand profiling: the wrapper polls the host profile dir for
    a trigger (armed by the agent's ``POST /profile`` / ``xsky
    profile``) and, when armed, captures the next N steps with
    ``jax.profiler`` and writes the op-time summary for the agent to
    serve back (utils/profiling.py).
    """
    from skypilot_tpu import trace as trace_lib
    from skypilot_tpu.metrics import goodput as goodput_lib
    from skypilot_tpu.utils import profiling as profiling_lib
    fams = goodput_lib.train_metrics()
    step_hist = fams['step_seconds']
    tokens_total = fams['tokens_total']
    steps_total = fams['steps_total']
    tok_s = fams['tokens_per_sec']
    acct = goodput_lib.accountant()
    profiler = profiling_lib.StepProfiler('train')
    model_armed = [False]
    if model_config is not None and tokens_per_step is not None:
        acct.set_model_info(model_config.num_params(), tokens_per_step,
                            n_chips=jax.device_count(),
                            accelerator=accelerator,
                            full_finetune=full_finetune)
        model_armed[0] = True
    last_call: List[Optional[float]] = [None]
    # Open train.step span state: (context, parent, start_wall,
    # ambient-token, step_index). The span's identity is
    # pre-allocated (trace.child_context) so children recorded while
    # it is ambient parent correctly; it is EMITTED when the next
    # call closes the interval.
    open_step: List[Optional[tuple]] = [None]
    step_idx = [0]

    def _tokens_in(batch) -> int:
        if tokens_per_step is not None:
            return tokens_per_step
        try:
            tokens = batch['tokens']
            return int(tokens.shape[0] * (tokens.shape[1] - 1))
        except Exception:  # pylint: disable=broad-except
            return 0

    def wrapper(state, batch):
        now = time.perf_counter()
        now_wall = time.time()
        n_tokens = _tokens_in(batch)
        if model_config is not None and not model_armed[0] \
                and n_tokens:
            # tokens_per_step was derived from the first batch.
            acct.set_model_info(model_config.num_params(), n_tokens,
                                n_chips=jax.device_count(),
                                accelerator=accelerator,
                                full_finetune=full_finetune)
            model_armed[0] = True
        if last_call[0] is not None:
            dt = now - last_call[0]
            step_hist.observe(dt)
            acct.observe_step(dt, compile_step=(step_idx[0] == 1))
            if dt > 0 and n_tokens:
                tok_s.set(n_tokens / dt)
            prev = open_step[0]
            if prev is not None:
                ctx, parent, start_wall, token, idx = prev
                trace_lib.reset_current(token)
                # SAME dt as the histogram observation above.
                trace_lib.emit_span(ctx, parent, 'train.step',
                                    start_wall, start_wall + dt,
                                    attrs={'step': idx,
                                           'tokens': n_tokens})
                open_step[0] = None
        last_call[0] = now
        parent = trace_lib.current()
        if parent is not None:
            ctx = trace_lib.child_context(parent)
            token = trace_lib.set_current(ctx)
            open_step[0] = (ctx, parent, now_wall, token,
                            step_idx[0])
        step_idx[0] += 1
        steps_total.inc()
        if n_tokens:
            tokens_total.inc(n_tokens)
        profiler.on_step()
        return step_fn(state, batch)

    # Identity copy done BY HAND, not functools.wraps: wraps()
    # silently skips attributes the target lacks, so wrapping a
    # callable object (older jit wrappers, partials, mocks) used to
    # leave the wrapper named 'wrapper' with this function's
    # docstring gone. Fall back through __wrapped__ → the callable →
    # its type.
    target = getattr(step_fn, '__wrapped__', step_fn)
    wrapper.__name__ = getattr(
        target, '__name__', type(step_fn).__name__)
    wrapper.__qualname__ = getattr(
        target, '__qualname__', wrapper.__name__)
    wrapper.__doc__ = getattr(target, '__doc__', None)
    wrapper.__module__ = getattr(
        target, '__module__', wrapper.__module__)
    wrapper.__wrapped__ = step_fn
    wrapper.inner = step_fn
    return wrapper
