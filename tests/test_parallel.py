"""Sharded training tests on the virtual 8-device CPU mesh.

This is the rebuild's answer to the reference's biggest testing gap
(SURVEY.md §4.5): distributed behavior unit-tested without hardware.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import (MeshConfig, auto_mesh_config,
                                   build_train_step, init_train_state,
                                   make_mesh)
from skypilot_tpu.parallel import mesh as mesh_lib


@pytest.fixture(scope='module')
def tiny_config():
    return llama.get_config('tiny')


class TestMesh:

    def test_auto_mesh_defaults_to_fsdp(self):
        cfg = auto_mesh_config(8)
        assert cfg.fsdp == 8
        assert cfg.num_devices == 8

    def test_auto_mesh_tp(self):
        cfg = auto_mesh_config(8, tp=4)
        assert cfg.tp == 4 and cfg.fsdp == 2

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            auto_mesh_config(8, tp=3)

    def test_make_mesh(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        assert mesh.shape == {'pp': 1, 'dp': 2, 'fsdp': 2, 'ep': 1,
                              'tp': 2, 'sp': 1}

    def test_batch_size_per_device(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
        assert mesh_lib.batch_size_per_device(16, mesh) == 2
        with pytest.raises(ValueError):
            mesh_lib.batch_size_per_device(7, mesh)


class TestShardedTraining:

    def _run_steps(self, mesh_config, tiny_config, n_steps=3,
                   lora_rank=None):
        mesh = make_mesh(mesh_config)
        state, shardings = init_train_state(
            tiny_config, mesh, jax.random.PRNGKey(0),
            lora_rank=lora_rank)
        step = build_train_step(tiny_config, mesh, shardings)
        # Contract: tokens are [B, T+1]; the forward runs on the first
        # T=32 positions (sp-divisible).
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                    tiny_config.vocab_size)
        losses = []
        for _ in range(n_steps):
            state, metrics = step(state, {'tokens': tokens})
            losses.append(float(metrics['loss']))
        return state, losses

    def test_fsdp8_loss_decreases(self, tiny_config):
        _, losses = self._run_steps(MeshConfig(fsdp=8), tiny_config)
        assert losses[-1] < losses[0], losses

    def test_fsdp_params_actually_sharded(self, tiny_config):
        mesh = make_mesh(MeshConfig(fsdp=8))
        state, _ = init_train_state(tiny_config, mesh,
                                    jax.random.PRNGKey(0))
        # lm_head [d, vocab] shards d over fsdp.
        shard_shape = state.params['lm_head'].sharding.shard_shape(
            state.params['lm_head'].shape)
        assert shard_shape[0] == tiny_config.dim // 8

    def test_tp_fsdp_matches_pure_fsdp(self, tiny_config):
        """Same seed, different mesh layouts → same loss trajectory
        (SPMD correctness of the sharding rules)."""
        _, fsdp_losses = self._run_steps(MeshConfig(fsdp=8),
                                         tiny_config)
        _, mixed_losses = self._run_steps(
            MeshConfig(dp=2, fsdp=2, tp=2), tiny_config)
        np.testing.assert_allclose(fsdp_losses, mixed_losses,
                                   rtol=2e-3)

    def test_lora_only_trains_adapters(self, tiny_config):
        mesh = make_mesh(MeshConfig(fsdp=8))
        state, shardings = init_train_state(
            tiny_config, mesh, jax.random.PRNGKey(0), lora_rank=4)
        step = build_train_step(tiny_config, mesh, shardings)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                    tiny_config.vocab_size)
        # Copy to host BEFORE the step: donate_argnums invalidates the
        # input state's buffers.
        params_before = jax.tree.map(np.asarray, state.params)
        lora_before = jax.tree.map(np.asarray, state.lora)
        state2, metrics = step(state, {'tokens': tokens})
        assert np.isfinite(metrics['loss'])
        # Base params unchanged, adapters changed.
        params_after = jax.tree.map(np.asarray, state2.params)
        for b, a in zip(jax.tree.leaves(params_before),
                        jax.tree.leaves(params_after)):
            np.testing.assert_array_equal(b, a)
        assert any(
            not np.array_equal(b, np.asarray(a))
            for b, a in zip(jax.tree.leaves(lora_before),
                            jax.tree.leaves(state2.lora)))

    def test_lora_loss_decreases(self, tiny_config):
        _, losses = self._run_steps(MeshConfig(fsdp=8), tiny_config,
                                    n_steps=4, lora_rank=4)
        assert losses[-1] < losses[0], losses


class TestSequenceParallel:
    """Long-context: sp axis shards the sequence; attention runs as
    ring attention under shard_map inside the jitted step."""

    def test_sp_matches_fsdp_loss(self, tiny_config):
        helper = TestShardedTraining()
        _, base = helper._run_steps(MeshConfig(fsdp=8), tiny_config)
        _, sp = helper._run_steps(MeshConfig(fsdp=4, sp=2),
                                  tiny_config)
        np.testing.assert_allclose(base, sp, rtol=2e-3)

    def test_sp_with_tp(self, tiny_config):
        helper = TestShardedTraining()
        _, losses = helper._run_steps(
            MeshConfig(fsdp=2, tp=2, sp=2), tiny_config)
        assert losses[-1] < losses[0], losses

    def test_sp_lora(self, tiny_config):
        helper = TestShardedTraining()
        _, losses = helper._run_steps(MeshConfig(fsdp=4, sp=2),
                                      tiny_config, n_steps=4,
                                      lora_rank=4)
        assert losses[-1] < losses[0], losses


def _tp_groups(mesh):
    """Device-id groups that differ only along 'tp'."""
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    tp_axis = mesh.axis_names.index('tp')
    return {frozenset(g) for g in
            np.moveaxis(ids, tp_axis, -1).reshape(-1, ids.shape[tp_axis])
            .tolist()}


def _replica_groups(line):
    """An HLO collective's replica groups as a set of frozensets, from
    either spelling: ``{{0,1},{2,3}}`` or the iota form
    ``[2,2]<=[4]`` / ``[2,2]<=[2,2]T(1,0)``."""
    import re
    m = re.search(r'replica_groups=\{(\{[0-9,{} ]*\})\}', line)
    if m:
        return {frozenset(int(i) for i in g.split(','))
                for g in re.findall(r'\{([0-9, ]+)\}', m.group(1))}
    m = re.search(r'replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\]'
                  r'(?:T\(([0-9,]+)\))?', line)
    assert m, line
    dims = [int(i) for i in m.group(1).split(',')]
    src = [int(i) for i in m.group(2).split(',')]
    ids = np.arange(int(np.prod(src))).reshape(src)
    if m.group(3):
        ids = ids.transpose([int(i) for i in m.group(3).split(',')])
    return {frozenset(g) for g in ids.reshape(dims).tolist()}


def _loop_bodies(hlo_text):
    """The computations of a compiled step other than its entry, as
    lists of lines: the layer scans' bodies are among them, the
    embedding lookup and the optimizer are not."""
    import re
    bodies, cur = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r'^(ENTRY )?(%[\w.\-]+) \(.*\{$', line)
        if m:
            cur = None if m.group(1) else bodies.setdefault(
                m.group(2), [])
        elif line.startswith('}'):
            cur = None
        elif cur is not None:
            cur.append(line)
    return bodies


def _tp_activation_all_reduces(lines, mesh, min_elements):
    """The all-reduces among ``lines`` whose groups are the tp groups
    and whose result holds at least ``min_elements``: what GSPMD
    places after a row-parallel product of [B, T, D] activations."""
    import re
    found = []
    for line in lines:
        if not re.search(r' all-reduce(-start)?\(', line):
            continue
        shapes = re.findall(r'[a-z0-9]+\[([0-9,]*)\]',
                            line.split(' all-reduce')[0])
        biggest = max(int(np.prod([int(i) for i in s.split(',') if i]
                                  or [1])) for s in shapes)
        if biggest >= min_elements and \
                _replica_groups(line) == _tp_groups(mesh):
            found.append(line.strip()[:200])
    return found


def _gauge_value():
    from skypilot_tpu.parallel import collective_matmul
    return collective_matmul.overlapped_gauge().value


class TestTpOverlap:
    """tp > 1 without sp / pp on a dense config: the residual stream
    is sharded along the sequence over tp and the four tp products of
    a layer are collective matmuls (parallel/collective_matmul.py).
    Chosen by the mesh's shape alone; everything else takes GSPMD's
    all-reduce path."""

    BATCH, SEQ = 8, 32
    MESHES = {
        'fsdp2_tp2': (MeshConfig(fsdp=2, tp=2), 4),
        'dp2_fsdp2_tp2': (MeshConfig(dp=2, fsdp=2, tp=2), 8),
        'fsdp1_tp4': (MeshConfig(fsdp=1, tp=4), 4),
    }

    @pytest.fixture(scope='class')
    def config(self):
        import dataclasses

        # 4 KV heads so that tp=4 divides them; remat on with a saved
        # MLP name, so the names inside the collective MLP are met.
        return dataclasses.replace(
            llama.get_config('tiny'), n_kv_heads=4, remat=True,
            remat_saves='attn+mlp_up')

    def _mesh(self, name):
        mesh_config, n = self.MESHES[name]
        return make_mesh(mesh_config, devices=jax.devices()[:n])

    def _trajectory(self, config, mesh, lora_rank):
        """Losses of three AdamW steps, and the first gradient: one
        step of plain SGD at rate 1 moves every trainable leaf by
        exactly minus its gradient."""
        import dataclasses

        import optax
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (self.BATCH, self.SEQ + 1),
            0, config.vocab_size)
        state, shardings = init_train_state(
            config, mesh, jax.random.PRNGKey(0), lora_rank=lora_rank,
            lora_key=jax.random.PRNGKey(7))
        step = build_train_step(config, mesh, shardings)
        losses = []
        for _ in range(3):
            state, metrics = step(state, {'tokens': tokens})
            losses.append(float(metrics['loss']))
        # LoRA's B factors start at zero, which zeroes the A factors'
        # gradient: take the gradient after the three steps.
        sgd = optax.sgd(1.0)
        trainable = state.lora if lora_rank else state.params
        before = jax.tree.map(np.asarray, trainable)
        no_state = sgd.init(trainable)  # no leaves: its own sharding
        sgd_step = build_train_step(
            config, mesh,
            dataclasses.replace(shardings, opt_state=no_state),
            optimizer=sgd, donate=False)
        after, _ = sgd_step(
            dataclasses.replace(state, opt_state=no_state),
            {'tokens': tokens})
        after = after.lora if lora_rank else after.params
        grads = jax.tree.map(lambda b, a: b - np.asarray(a), before,
                             after)
        return losses, grads

    def _assert_same(self, got, want):
        losses, grads = got
        want_losses, want_grads = want
        np.testing.assert_allclose(losses, want_losses, rtol=2e-3)
        for path, g in jax.tree_util.tree_leaves_with_path(grads):
            w = want_grads
            for key in path:
                w = w[key.key]
            scale = np.abs(w).max()
            assert scale > 0, path
            np.testing.assert_allclose(
                g, w, rtol=2e-3, atol=2e-3 * scale,
                err_msg=jax.tree_util.keystr(path))

    @pytest.fixture(scope='class')
    def pure_fsdp(self, config):
        mesh = make_mesh(MeshConfig(fsdp=8))
        return {rank: self._trajectory(config, mesh, rank)
                for rank in (None, 4)}

    @pytest.fixture(scope='class')
    def overlapped(self, config):
        """The overlapped path's trajectory for a (mesh, rank) case,
        computed once for the two tests that hold it against
        something."""
        done = {}

        def get(mesh_name, lora_rank):
            if (mesh_name, lora_rank) not in done:
                done[mesh_name, lora_rank] = self._trajectory(
                    config, self._mesh(mesh_name), lora_rank)
                assert _gauge_value() == 4
            return done[mesh_name, lora_rank]

        return get

    @pytest.mark.parametrize('lora_rank', [None, 4],
                             ids=['full', 'lora'])
    @pytest.mark.parametrize('mesh_name', list(MESHES))
    def test_matches_pure_fsdp(self, overlapped, pure_fsdp, mesh_name,
                               lora_rank):
        self._assert_same(overlapped(mesh_name, lora_rank),
                          pure_fsdp[lora_rank])

    @pytest.mark.parametrize('lora_rank', [None, 4],
                             ids=['full', 'lora'])
    @pytest.mark.parametrize('mesh_name', list(MESHES))
    def test_matches_all_reduce_path(self, config, overlapped,
                                     mesh_name, lora_rank,
                                     monkeypatch):
        """The same mesh with GSPMD's all-reduces (the path a
        sequence that tp does not divide takes), steered here in the
        test: the program has no option for it."""
        from skypilot_tpu.parallel import collective_matmul
        got = overlapped(mesh_name, lora_rank)
        monkeypatch.setattr(collective_matmul.TpOverlap,
                            'for_sequence', lambda self, t: None)
        self._assert_same(got, self._trajectory(
            config, self._mesh(mesh_name), lora_rank))

    def test_int8_base_with_biases(self, config):
        """QLoRA on a tp mesh: {'q', 's'} pairs (the row-parallel
        scale whole on every device) and column-parallel biases go
        through the collective products."""
        import dataclasses

        import optax

        from skypilot_tpu.parallel import init_qlora_state
        config = dataclasses.replace(config, qkv_bias=True)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (self.BATCH, self.SEQ + 1), 0,
                                    config.vocab_size)

        def losses(mesh):
            opt = optax.adam(1e-2)
            state, shardings = init_qlora_state(
                config, mesh, jax.random.PRNGKey(0), lora_rank=4,
                optimizer=opt)
            step = build_train_step(config, mesh, shardings,
                                    optimizer=opt)
            out = []
            for _ in range(3):
                state, metrics = step(state, {'tokens': tokens})
                out.append(float(metrics['loss']))
            return out

        want = losses(make_mesh(MeshConfig(fsdp=8)))
        assert _gauge_value() == 0
        got = losses(self._mesh('fsdp1_tp4'))
        assert _gauge_value() == 4
        np.testing.assert_allclose(got, want, rtol=2e-3)

    def test_indivisible_sequence_takes_all_reduce_path(self, config):
        mesh = self._mesh('fsdp2_tp2')
        state, shardings = init_train_state(config, mesh,
                                            jax.random.PRNGKey(0))
        step = build_train_step(config, mesh, shardings)
        assert _gauge_value() == 4  # by the mesh, until a batch is seen
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (self.BATCH, 32), 0,
                                    config.vocab_size)  # T = 31
        lowered = step.lower(state, {'tokens': tokens})
        assert _gauge_value() == 0
        text = lowered.as_text()
        assert 'collective_permute' not in text
        assert 'manual_axes={"tp"}' not in text
        _, metrics = step(state, {'tokens': tokens})
        base_mesh = make_mesh(MeshConfig(fsdp=8))
        base_state, base_sh = init_train_state(config, base_mesh,
                                               jax.random.PRNGKey(0))
        _, base = build_train_step(config, base_mesh, base_sh)(
            base_state, {'tokens': tokens})
        np.testing.assert_allclose(float(metrics['loss']),
                                   float(base['loss']), rtol=2e-3)

    def test_sp_mesh_takes_ring_path(self, config):
        mesh = make_mesh(MeshConfig(fsdp=2, tp=2, sp=2))
        state, shardings = init_train_state(config, mesh,
                                            jax.random.PRNGKey(0))
        step = build_train_step(config, mesh, shardings)
        assert _gauge_value() == 0
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (self.BATCH, self.SEQ + 1), 0,
                                    config.vocab_size)
        text = step.lower(state, {'tokens': tokens}).as_text()
        # The ring's shard_map is manual over every axis; none is
        # manual over tp alone.
        assert 'manual_axes={"tp"}' not in text
        assert _gauge_value() == 0

    def test_moe_takes_all_reduce_path(self):
        config = llama.get_config('tiny-moe')
        mesh = self._mesh('fsdp2_tp2')
        _, shardings = init_train_state(config, mesh,
                                        jax.random.PRNGKey(0))
        build_train_step(config, mesh, shardings)
        assert _gauge_value() == 0

    def _step_text(self, config, mesh, compiled):
        state, shardings = init_train_state(
            config, mesh, jax.random.PRNGKey(0), lora_rank=4)
        step = build_train_step(config, mesh, shardings)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (self.BATCH, self.SEQ + 1), 0,
                                    config.vocab_size)
        lowered = step.lower(state, {'tokens': tokens})
        return lowered.compile().as_text() if compiled \
            else lowered.as_text()

    def test_compiled_step_has_permutes_and_no_tp_all_reduce(
            self, config, monkeypatch):
        from skypilot_tpu.parallel import collective_matmul
        mesh = self._mesh('fsdp2_tp2')
        # A chip's sequence block of its [B / fsdp, T, D] activation.
        block = self.BATCH // 2 * self.SEQ // 2 * config.dim
        text = self._step_text(config, mesh, compiled=True)
        assert _gauge_value() == 4
        layer_bodies = [
            lines for lines in _loop_bodies(text).values()
            if any(' collective-permute' in line for line in lines)]
        assert len(layer_bodies) >= 2  # forward scan, backward scan
        for lines in layer_bodies:
            assert _tp_activation_all_reduces(lines, mesh,
                                              block) == []
        # The same reader finds them in the all-reduce path: wo and
        # w_down forward, and the input-gradient sums backward.
        monkeypatch.setattr(collective_matmul.TpOverlap,
                            'for_sequence', lambda self, t: None)
        old = _loop_bodies(self._step_text(config, mesh,
                                           compiled=True))
        assert sum(len(_tp_activation_all_reduces(lines, mesh,
                                                  2 * block))
                   for lines in old.values()) >= 4

    @pytest.mark.parametrize('mesh_config,n_devices', [
        (MeshConfig(), 1), (MeshConfig(fsdp=8), 8)],
        ids=['one_device', 'pure_fsdp'])
    def test_mesh_without_tp_builds_the_old_step(self, config,
                                                 mesh_config,
                                                 n_devices):
        mesh = make_mesh(mesh_config,
                         devices=jax.devices()[:n_devices])
        text = self._step_text(config, mesh, compiled=False)
        assert _gauge_value() == 0
        assert 'collective_permute' not in text
        assert 'manual_axes={"tp"}' not in text
        assert 'collective-permute' not in self._step_text(
            config, mesh, compiled=True)


class TestMultiSlice:
    """Multi-slice (DCN) support: megascale env contract + hybrid
    mesh (SURVEY 2.11-2.12: multi-slice = k slices x barrier at JAX
    init; dp is the only axis whose collectives cross DCN)."""

    def test_env_contract_single_slice_has_no_megascale(self):
        from skypilot_tpu.runtime import env_contract
        env = env_contract.build_env(0, ['10.0.0.1', '10.0.0.2'])
        assert 'MEGASCALE_NUM_SLICES' not in env

    def test_env_contract_multislice(self):
        from skypilot_tpu.runtime import env_contract
        ips = ['10.0.0.1', '10.0.0.2', '10.0.1.1', '10.0.1.2']
        env = env_contract.build_env(2, ips, num_slices=2)
        # Host rank 2 is host 0 of slice 1 (slice-major ranks).
        assert env['SKYTPU_SLICE_ID'] == '1'
        assert env['SKYTPU_NUM_SLICES'] == '2'
        assert env['MEGASCALE_SLICE_ID'] == '1'
        assert env['MEGASCALE_NUM_SLICES'] == '2'
        assert env['MEGASCALE_COORDINATOR_ADDRESS'].startswith(
            '10.0.0.1:')
        # jax.distributed still spans ALL hosts.
        assert env['SKYTPU_NUM_NODES'] == '4'
        assert env['SKYTPU_COORDINATOR_ADDRESS'].startswith(
            '10.0.0.1:')

    def test_hybrid_mesh_builds_and_trains(self, tiny_config):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2),
                         num_slices=2)
        assert mesh.shape['dp'] == 2
        state, shardings = init_train_state(tiny_config, mesh,
                                            jax.random.PRNGKey(0))
        step = build_train_step(tiny_config, mesh, shardings)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                    tiny_config.vocab_size,
                                    dtype=jnp.int32)
        _, metrics = step(state, {'tokens': tokens})
        assert float(metrics['loss']) > 0

    def test_dp_must_divide_by_slices(self):
        with pytest.raises(ValueError, match='num_slices'):
            make_mesh(MeshConfig(dp=1, fsdp=8), num_slices=2)


class TestQLora:
    """int8-frozen-base LoRA (QLoRA): the training forward runs over
    the quantized base via llama.matmul, gradients flow only to the
    bf16 adapters, and the int8 codes never change."""

    def test_qlora_step_trains_adapters_only(self):
        import numpy as np

        import optax

        from skypilot_tpu.models import llama
        from skypilot_tpu.parallel import (MeshConfig,
                                           build_train_step,
                                           init_qlora_state,
                                           make_mesh)

        config = llama.get_config('tiny')
        mesh = make_mesh(MeshConfig(fsdp=len(jax.devices())))
        opt = optax.adam(1e-2)
        state, shardings = init_qlora_state(
            config, mesh, jax.random.PRNGKey(0), lora_rank=4,
            optimizer=opt)
        # Base is quantized: int8 codes + bf16 scales for the big
        # matmuls and the lm_head.
        assert state.params['layers']['wq']['q'].dtype == jnp.int8
        assert state.params['lm_head']['q'].dtype == jnp.int8
        base_codes = np.asarray(state.params['layers']['wq']['q'])

        step = build_train_step(config, mesh, shardings,
                                optimizer=opt)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17),
                                    0, config.vocab_size, jnp.int32)
        batch = {'tokens': tokens}
        losses = []
        for _ in range(6):
            state, metrics = step(state, batch)
        # Same batch every step: the adapters must overfit it.
            losses.append(float(metrics['loss']))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        assert float(metrics['grad_norm']) > 0.0
        # The frozen base is bit-identical after training.
        np.testing.assert_array_equal(
            base_codes, np.asarray(state.params['layers']['wq']['q']))

    def test_qlora_forward_close_to_dequant_forward(self):
        """The quantized-base forward must equal the forward over the
        DEQUANTIZED base to quantization error (sanity that matmul's
        scale placement is right in the training path)."""
        import numpy as np

        from skypilot_tpu.models import llama, quant

        config = llama.get_config('tiny')
        params = llama.init_params(config, jax.random.PRNGKey(0),
                                   dtype=jnp.bfloat16)
        qparams = quant.quantize_params(params, config)

        def dequant(leaf):
            if isinstance(leaf, dict) and 'q' in leaf:
                return (leaf['q'].astype(jnp.float32) *
                        leaf['s'].astype(jnp.float32)
                        ).astype(jnp.bfloat16)
            return leaf

        deq = jax.tree.map(dequant, qparams,
                           is_leaf=lambda x: isinstance(x, dict)
                           and 'q' in x)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9),
                                    0, config.vocab_size, jnp.int32)
        lq = llama.forward(qparams, tokens, config)
        ld = llama.forward(deq, tokens, config)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                                   atol=2e-2, rtol=2e-2)
