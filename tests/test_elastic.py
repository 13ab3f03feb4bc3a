"""Elastic training (ROADMAP item 4): survive slice preemption by
RESIZING the mesh, not just waiting for the same shape back.

Covers the whole vertical: window-level shard re-assembly
(checkpoint/format.py), re-shard-on-restore across real jax meshes
(checkpoint/native.py), mesh re-planning + batch rescale
(parallel/mesh.py), the NEXT_BEST_SHAPE recovery strategy with
optimizer pricing and the `recovery.resize` fault site
(jobs/recovery_strategy.py), the controller's RESUME@step/new-mesh
bookkeeping, goodput `recovery_stall` pricing, the `--bench elastic`
row, and the local-fake e2e: one "slice" of a 2-host managed job is
killed mid-training and the job finishes on the survivor with loss
continuity asserted across the resize.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from skypilot_tpu import core, exceptions, provision, state
from skypilot_tpu.jobs import recovery_strategy
from skypilot_tpu.jobs import state as jobs_state
from skypilot_tpu.resources import Resources
from skypilot_tpu.task import Task


@pytest.fixture
def cleanup_clusters():
    yield
    for record in state.get_clusters():
        try:
            core.down(record['name'], purge=True)
        except exceptions.SkyTpuError:
            pass


@pytest.fixture
def fast_poll(monkeypatch):
    monkeypatch.setenv('SKYTPU_JOBS_POLL_SECONDS', '1')
    from skypilot_tpu.jobs import controller as controller_mod
    monkeypatch.setattr(controller_mod,
                        'JOB_STATUS_CHECK_GAP_SECONDS', 1.0)
    yield


# ---------------------------------------------------------------------
# format.assemble_region: the re-partitioning primitive
# ---------------------------------------------------------------------


class TestAssembleRegion:

    def _step_dir(self, tmp_path, rows=16, cols=8, shards=4):
        """A committed-looking step dir: one leaf split into
        row-range shards (the fsdp layout)."""
        from skypilot_tpu.checkpoint import format as format_lib
        rng = np.random.default_rng(0)
        arr = rng.standard_normal((rows, cols)).astype(np.float32)
        d = str(tmp_path / 'step_00000001')
        os.makedirs(d)
        entry = format_lib.leaf_entry(arr.dtype, arr.shape,
                                      sharding=f'fsdp{shards}')
        step = rows // shards
        for j in range(shards):
            lo, hi = j * step, (j + 1) * step
            fname = f'h0_00000_{j}.bin'
            size, crc = format_lib.write_shard_file(d, fname,
                                                    arr[lo:hi])
            entry['shards'].append({'file': fname,
                                    'index': [[lo, hi], [0, cols]],
                                    'nbytes': size, 'checksum': crc})
        return d, entry, arr

    def test_full_region_equals_assemble_leaf(self, tmp_path):
        from skypilot_tpu.checkpoint import format as format_lib
        d, entry, arr = self._step_dir(tmp_path)
        full = format_lib.assemble_leaf(d, 'w', entry)
        np.testing.assert_array_equal(full, arr)

    def test_aligned_window_single_read(self, tmp_path):
        """A window that IS an old shard takes the zero-copy fast
        path and still equals the source."""
        from skypilot_tpu.checkpoint import format as format_lib
        d, entry, arr = self._step_dir(tmp_path)
        win = format_lib.assemble_region(d, 'w', entry,
                                         [[4, 8], [0, 8]])
        np.testing.assert_array_equal(win, arr[4:8])

    def test_straddling_window_re_packs(self, tmp_path):
        """The elastic case: a 4->2 re-partition window straddles two
        saved shards and must splice them exactly."""
        from skypilot_tpu.checkpoint import format as format_lib
        d, entry, arr = self._step_dir(tmp_path)
        win = format_lib.assemble_region(d, 'w', entry,
                                         [[2, 10], [0, 8]])
        np.testing.assert_array_equal(win, arr[2:10])
        # Column sub-window too (2-d re-partitions).
        win = format_lib.assemble_region(d, 'w', entry,
                                         [[6, 14], [2, 6]])
        np.testing.assert_array_equal(win, arr[6:14, 2:6])

    def test_incomplete_coverage_is_typed_error(self, tmp_path):
        from skypilot_tpu.checkpoint import format as format_lib
        d, entry, _ = self._step_dir(tmp_path)
        entry['shards'] = entry['shards'][:2]  # lose half the rows
        with pytest.raises(format_lib.CheckpointRestoreError,
                           match='cover'):
            format_lib.assemble_region(d, 'w', entry,
                                       [[0, 16], [0, 8]])
        # A window fully inside the surviving shards still assembles.
        win = format_lib.assemble_region(d, 'w', entry,
                                         [[0, 8], [0, 8]])
        assert win.shape == (8, 8)

    def test_bad_region_is_typed_error(self, tmp_path):
        from skypilot_tpu.checkpoint import format as format_lib
        d, entry, _ = self._step_dir(tmp_path)
        with pytest.raises(format_lib.CheckpointRestoreError,
                           match='outside'):
            format_lib.assemble_region(d, 'w', entry,
                                       [[0, 99], [0, 8]])
        with pytest.raises(format_lib.CheckpointRestoreError,
                           match='rank'):
            format_lib.assemble_region(d, 'w', entry, [[0, 16]])

    def test_region_overlap(self):
        from skypilot_tpu.checkpoint import format as format_lib
        assert format_lib.region_overlap([[0, 4]], [[2, 8]]) == [[2, 4]]
        assert format_lib.region_overlap([[0, 4]], [[4, 8]]) is None
        assert format_lib.region_overlap(
            [[0, 4], [0, 8]], [[2, 6], [4, 12]]) == [[2, 4], [4, 8]]


# ---------------------------------------------------------------------
# Re-shard on restore across real meshes (8 -> 4 devices)
# ---------------------------------------------------------------------


class TestReshardRestore:

    def _save(self, tmp_path, mesh, spec_tree, value_tree):
        import jax

        from skypilot_tpu.checkpoint import NativeCheckpointManager
        from jax.sharding import NamedSharding
        placed = {
            k: jax.device_put(v, NamedSharding(mesh, spec_tree[k]))
            for k, v in value_tree.items()
        }
        mgr = NativeCheckpointManager(str(tmp_path), process_index=0,
                                      process_count=1)
        mgr.save(7, placed)
        mgr.wait()
        mgr.close()
        return placed

    def test_restore_onto_smaller_mesh(self, tmp_path):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from skypilot_tpu.checkpoint import NativeCheckpointManager
        from skypilot_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh8 = make_mesh(MeshConfig(fsdp=8))
        specs = {'w': P('fsdp', None), 'b': P()}
        rng = np.random.default_rng(1)
        values = {'w': rng.standard_normal((16, 4)).astype(np.float32),
                  'b': rng.standard_normal((4,)).astype(np.float32)}
        self._save(tmp_path, mesh8, specs, values)

        # The surviving "slice": a 4-device mesh, same fsdp intent.
        mesh4 = make_mesh(MeshConfig(fsdp=4),
                          devices=jax.devices()[:4])
        template = {
            k: jax.device_put(np.zeros_like(values[k]),
                              NamedSharding(mesh4, specs[k]))
            for k in values
        }
        mgr = NativeCheckpointManager(str(tmp_path), process_index=0,
                                      process_count=1)
        restored, next_step = mgr.restore_or(template)
        assert next_step == 8
        for k in values:
            np.testing.assert_array_equal(np.asarray(restored[k]),
                                          values[k])
            # Placed with the TEMPLATE's (new-mesh) sharding.
            assert restored[k].sharding == template[k].sharding
        info = mgr.last_restore
        assert info is not None and info['resharded']
        assert info['saved_device_count'] == 8
        assert info['bytes_read'] > 0

    def test_same_mesh_restore_not_flagged(self, tmp_path):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from skypilot_tpu.checkpoint import NativeCheckpointManager
        from skypilot_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(fsdp=8))
        specs = {'w': P('fsdp', None)}
        values = {'w': np.arange(32, dtype=np.float32).reshape(16, 2)}
        self._save(tmp_path, mesh, specs, values)
        template = {'w': jax.device_put(
            np.zeros_like(values['w']),
            NamedSharding(mesh, specs['w']))}
        mgr = NativeCheckpointManager(str(tmp_path), process_index=0,
                                      process_count=1)
        restored, _ = mgr.restore_or(template)
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      values['w'])
        assert mgr.last_restore is not None
        assert not mgr.last_restore['resharded']

    def test_saved_device_count_in_manifest(self, tmp_path):
        import jax

        from skypilot_tpu import checkpoint as checkpoint_lib
        from skypilot_tpu.checkpoint import NativeCheckpointManager
        import jax.numpy as jnp
        mgr = NativeCheckpointManager(str(tmp_path / 'dev'),
                                      process_index=0, process_count=1)
        mgr.save(0, {'w': jnp.ones(3, jnp.float32)})
        mgr.wait()
        mgr.close()
        assert checkpoint_lib.saved_device_count(
            str(tmp_path / 'dev')) == jax.device_count()
        assert checkpoint_lib.saved_device_count(
            str(tmp_path / 'nope')) is None
        # A host-only tree records no count: the process saving it
        # need not run a backend at all, and is never made to.
        mgr = NativeCheckpointManager(str(tmp_path / 'host'),
                                      process_index=0, process_count=1)
        mgr.save(0, {'w': np.ones(3, np.float32)})
        mgr.wait()
        mgr.close()
        assert checkpoint_lib.saved_device_count(
            str(tmp_path / 'host')) is None


# ---------------------------------------------------------------------
# Mesh re-planning + batch rescale
# ---------------------------------------------------------------------


class TestReplanMesh:

    def test_dp_shrinks_first_fsdp_preserved(self):
        from skypilot_tpu.parallel.mesh import (MeshConfig,
                                                replan_mesh_config)
        cfg = MeshConfig(dp=2, fsdp=4)
        new = replan_mesh_config(cfg, 4)
        assert (new.dp, new.fsdp) == (1, 4)  # per-device memory kept

    def test_fsdp_shrinks_when_it_must(self):
        from skypilot_tpu.parallel.mesh import (MeshConfig,
                                                replan_mesh_config)
        new = replan_mesh_config(MeshConfig(dp=1, fsdp=8), 4)
        assert (new.dp, new.fsdp) == (1, 4)
        new = replan_mesh_config(MeshConfig(dp=2, fsdp=4), 2)
        assert (new.dp, new.fsdp) == (1, 2)

    def test_model_axes_preserved_and_gate(self):
        from skypilot_tpu.parallel.mesh import (MeshConfig,
                                                replan_mesh_config)
        cfg = MeshConfig(dp=2, fsdp=2, tp=2)
        new = replan_mesh_config(cfg, 4)
        assert new.tp == 2 and new.num_devices == 4
        with pytest.raises(ValueError, match='model-parallel'):
            replan_mesh_config(MeshConfig(tp=2, sp=2), 6)

    def test_grow_back_up(self):
        from skypilot_tpu.parallel.mesh import (MeshConfig,
                                                replan_mesh_config)
        new = replan_mesh_config(MeshConfig(dp=1, fsdp=4), 8)
        assert (new.dp, new.fsdp) == (2, 4)

    def test_rescale_global_batch(self):
        from skypilot_tpu.parallel.mesh import (MeshConfig,
                                                rescale_global_batch,
                                                replan_mesh_config)
        old = MeshConfig(dp=2, fsdp=4)
        new = replan_mesh_config(old, 4)
        assert rescale_global_batch(16, old, new) == 8
        with pytest.raises(ValueError, match='divisible'):
            rescale_global_batch(17, old, new)

    def test_describe(self):
        from skypilot_tpu.parallel.mesh import (MeshConfig,
                                                describe_config)
        assert describe_config(MeshConfig(dp=2, fsdp=4)) == \
            '8c:dp2.fsdp4'
        assert describe_config(MeshConfig()) == '1c'


# ---------------------------------------------------------------------
# NEXT_BEST_SHAPE strategy
# ---------------------------------------------------------------------


class TestNextBestShape:

    @pytest.fixture(autouse=True)
    def _no_sleeps(self, monkeypatch):
        self.sleeps = []
        monkeypatch.setattr(
            recovery_strategy.LAUNCH_RETRY_POLICY, 'sleeper',
            self.sleeps.append)
        yield

    def _strategy_env(self, monkeypatch):
        from skypilot_tpu import core as core_lib
        launched = []

        def fake_launch(task, cluster_name, **kwargs):
            res = next(iter(task.resources))
            launched.append(recovery_strategy.shape_desc({res}))
            return len(launched), None

        monkeypatch.setattr(recovery_strategy.execution, 'launch',
                            fake_launch)
        monkeypatch.setattr(core_lib, 'down',
                            lambda name, purge=False: None)
        return launched

    def _tpu_task(self):
        task = Task(name='et', run='echo x')
        task.set_resources(Resources(
            cloud='gcp', accelerators='tpu-v5e-8', use_spot=True,
            job_recovery={'strategy': 'NEXT_BEST_SHAPE'}))
        return task

    def test_registered_and_valid_spec(self):
        s = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        assert s.NAME == 'NEXT_BEST_SHAPE'
        # Round-trips through Resources validation + YAML.
        res = next(iter(self._tpu_task().resources))
        assert res.spot_recovery == 'NEXT_BEST_SHAPE'
        rt = next(iter(Resources.from_yaml_config(
            res.to_yaml_config())))
        assert rt.spot_recovery == 'NEXT_BEST_SHAPE'

    def test_downsize_ladder_tpu(self):
        res = Resources(cloud='gcp', accelerators='tpu-v5e-8')
        rungs = recovery_strategy.downsize_ladder({res})
        names = [next(iter(r)).accelerator for r in rungs]
        # v5e-2 is not a cataloged size: the ladder halves PAST it to
        # the next certified shape.
        assert names == ['tpu-v5e-4', 'tpu-v5e-1']

    def test_downsize_ladder_local_hosts(self):
        res = Resources(cloud='local')
        res._extra_config = {'num_hosts': 4}  # pylint: disable=protected-access
        rungs = recovery_strategy.downsize_ladder({res})
        hosts = [next(iter(r))._extra_config['num_hosts']  # pylint: disable=protected-access
                 for r in rungs]
        assert hosts == [2, 1]
        assert recovery_strategy.shape_desc(rungs[-1]) == '1xhost'

    def test_same_shape_comes_back_no_resize(self, monkeypatch,
                                             faults):
        launched = self._strategy_env(monkeypatch)
        strategy = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        task = self._tpu_task()
        job_id = strategy.recover(task, 'c1', 'us-central1')
        assert job_id is not None
        assert strategy.resized_to is None
        assert launched == ['tpu-v5e-8']

    def test_steps_down_when_shape_gone(self, monkeypatch, faults):
        monkeypatch.setenv(
            recovery_strategy.SAME_SHAPE_ATTEMPTS_ENV, '2')
        launched = self._strategy_env(monkeypatch)
        # Same shape unobtainable for exactly the bounded wait.
        faults.arm('provision.launch', 'error', 1.0, count=2)
        strategy = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        task = self._tpu_task()
        job_id = strategy.recover(task, 'c1', 'us-central1')
        assert job_id is not None
        assert strategy.resized_to == 'tpu-v5e-4'
        assert launched == ['tpu-v5e-4']
        # The relaunched task knows it was resized...
        assert task.envs[recovery_strategy.ELASTIC_RESIZED_ENV] == \
            'tpu-v5e-8->tpu-v5e-4'
        # ...but keeps its DESIGNED shape for future recoveries
        # (scale-back-up is one preemption away).
        assert next(iter(task.resources)).accelerator == 'tpu-v5e-8'

    def test_resize_fault_site_skips_a_rung(self, monkeypatch,
                                            faults):
        monkeypatch.setenv(
            recovery_strategy.SAME_SHAPE_ATTEMPTS_ENV, '1')
        launched = self._strategy_env(monkeypatch)
        faults.arm('provision.launch', 'error', 1.0, count=1)
        # The first DOWNSIZED shape is "gone too": the drill drives
        # the step-down one rung further.
        faults.arm('recovery.resize', 'error', 1.0, count=1)
        strategy = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        job_id = strategy.recover(self._tpu_task(), 'c1', None)
        assert job_id is not None
        assert strategy.resized_to == 'tpu-v5e-1'
        assert launched == ['tpu-v5e-1']

    def test_exhausted_ladder_returns_none(self, monkeypatch, faults):
        monkeypatch.setenv(
            recovery_strategy.SAME_SHAPE_ATTEMPTS_ENV, '1')
        launched = self._strategy_env(monkeypatch)
        faults.arm('provision.launch', 'error', 1.0)  # unlimited
        strategy = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        task = self._tpu_task()
        assert strategy.recover(task, 'c1', None) is None
        assert launched == []
        # Task resources untouched after a failed recovery.
        assert next(iter(task.resources)).accelerator == 'tpu-v5e-8'

    def test_optimizer_prices_the_rung(self, monkeypatch, faults):
        """The downsized rung goes through the optimizer: the pinned
        best_resources (cheapest feasible region) is what launches."""
        monkeypatch.setenv(
            recovery_strategy.SAME_SHAPE_ATTEMPTS_ENV, '1')
        regions = []
        from skypilot_tpu import core as core_lib

        def fake_launch(task, cluster_name, **kwargs):
            res = next(iter(task.resources))
            regions.append(res.region)
            return 1, None

        monkeypatch.setattr(recovery_strategy.execution, 'launch',
                            fake_launch)
        monkeypatch.setattr(core_lib, 'down',
                            lambda name, purge=False: None)
        faults.arm('provision.launch', 'error', 1.0, count=1)
        strategy = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        strategy.recover(self._tpu_task(), 'c1', None)
        # The optimizer pinned a concrete region for the rung.
        assert len(regions) == 1 and regions[0] is not None

    def test_preempted_region_blocklisted_for_rungs(
            self, monkeypatch, faults):
        """The region whose capacity just evaporated must not be
        where the downsized rung lands: it is blocklisted at region
        granularity (accelerator-agnostic — rungs carry DOWNSIZED
        names the exact-match blocklist would otherwise miss)."""
        monkeypatch.setenv(
            recovery_strategy.SAME_SHAPE_ATTEMPTS_ENV, '1')
        regions = []
        from skypilot_tpu import core as core_lib
        from skypilot_tpu.catalog import tpu_catalog

        def fake_launch(task, cluster_name, **kwargs):
            regions.append(next(iter(task.resources)).region)
            return 1, None

        monkeypatch.setattr(recovery_strategy.execution, 'launch',
                            fake_launch)
        monkeypatch.setattr(core_lib, 'down',
                            lambda name, purge=False: None)
        # Preempt in whatever region the optimizer would otherwise
        # pick as cheapest for the downsized shape — the rung MUST
        # land elsewhere.
        cheapest = min(
            tpu_catalog.get_regions('tpu-v5e-4', True),
            key=lambda r: tpu_catalog.get_hourly_cost(
                'tpu-v5e-4', True, r, None))
        faults.arm('provision.launch', 'error', 1.0, count=1)
        strategy = recovery_strategy.get_strategy('NEXT_BEST_SHAPE')
        job_id = strategy.recover(self._tpu_task(), 'c1', cheapest)
        assert job_id is not None
        blocked = {(b.region, b.accelerator)
                   for b in strategy.blocked_resources}
        assert (cheapest, None) in blocked
        assert regions == [r for r in regions if r != cheapest]
        assert regions[0] is not None


class TestElasticDesignReference:
    """The batch rescale references the DESIGNED shape (design.json
    in the lineage), not the last checkpoint's device count — the
    reference that makes scale-back-up and consecutive step-downs
    both correct."""

    def test_first_run_records_design(self, tmp_path, monkeypatch):
        from skypilot_tpu.recipes import finetune
        monkeypatch.delenv('SKYTPU_ELASTIC_RESIZED', raising=False)
        design = finetune._elastic_design(str(tmp_path), 8, 16)  # pylint: disable=protected-access
        assert design == {'device_count': 8, 'global_batch': 16}
        assert (tmp_path / 'design.json').exists()
        # A later (resized) relaunch reads the SAME design even
        # though it runs on fewer devices with the same argv batch.
        monkeypatch.setenv('SKYTPU_ELASTIC_RESIZED', '8->4')
        again = finetune._elastic_design(str(tmp_path), 4, 16)  # pylint: disable=protected-access
        assert again['device_count'] == 8
        # Scale-back-up: designed 8, running 8 again -> ratio 1, no
        # rescale (the now/saved reference would have DOUBLED it).
        back = finetune._elastic_design(str(tmp_path), 8, 16)  # pylint: disable=protected-access
        assert back['device_count'] == 8

    def test_pre_elastic_lineage_falls_back_to_manifest(
            self, tmp_path, monkeypatch):
        from skypilot_tpu.checkpoint import NativeCheckpointManager
        from skypilot_tpu.recipes import finetune
        import jax.numpy as jnp
        mgr = NativeCheckpointManager(str(tmp_path), process_index=0,
                                      process_count=1)
        mgr.save(0, {'w': jnp.ones(3, jnp.float32)})
        mgr.wait()
        mgr.close()
        (tmp_path / 'design.json').unlink(missing_ok=True)
        monkeypatch.setenv('SKYTPU_ELASTIC_RESIZED', '8->4')
        design = finetune._elastic_design(str(tmp_path), 4, 16)  # pylint: disable=protected-access
        # Best effort: the manifest's saved device count; the guess
        # is NOT persisted as the design.
        import jax
        assert design['device_count'] == jax.device_count()
        assert not (tmp_path / 'design.json').exists()


# ---------------------------------------------------------------------
# Goodput: the recovery_stall bucket and the elastic-vs-wait contrast
# ---------------------------------------------------------------------


class TestRecoveryStallAccounting:

    def test_note_from_env(self, monkeypatch):
        from skypilot_tpu.metrics import goodput as goodput_lib
        goodput_lib.reset_accountant()
        monkeypatch.setenv(goodput_lib.ENV_RECOVERY_DETECTED_AT,
                           f'{time.time() - 3.0:.3f}')
        stall = goodput_lib.note_recovery_stall_from_env()
        assert stall == pytest.approx(3.0, abs=1.0)
        snap = goodput_lib.accountant().snapshot()
        assert snap['recovery_stall'] == pytest.approx(stall)
        # Consumed: a second call (fork/exec) cannot double-count.
        assert goodput_lib.note_recovery_stall_from_env() is None
        goodput_lib.reset_accountant()

    def test_not_a_recovery_is_noop(self, monkeypatch):
        from skypilot_tpu.metrics import goodput as goodput_lib
        monkeypatch.delenv(goodput_lib.ENV_RECOVERY_DETECTED_AT,
                           raising=False)
        assert goodput_lib.note_recovery_stall_from_env() is None

    def test_controller_stamps_detected_at(self, tmp_path):
        import yaml

        from skypilot_tpu.jobs.controller import JobsController
        task = Task(name='st', run='echo x')
        task.set_resources(Resources(cloud='local'))
        dag_yaml = tmp_path / 'd.yaml'
        with open(dag_yaml, 'w', encoding='utf-8') as f:
            yaml.safe_dump_all([task.to_yaml_config()], f)
        job_id = jobs_state.add_job('st', str(dag_yaml), 'inproc')
        ctrl = JobsController(job_id, str(dag_yaml))
        before = time.time()
        ctrl._prepare_relaunch(task, 0)  # pylint: disable=protected-access
        stamp = float(task.envs['SKYTPU_RECOVERY_DETECTED_AT'])
        assert before - 1 <= stamp <= time.time() + 1

    def test_elastic_stall_smaller_than_same_shape_wait(self):
        """The goodput contrast the tentpole exists for: with the
        same capacity outage (same-shape gone for 2 attempts), the
        same-shape-wait baseline stalls through the full backoff
        ladder while NEXT_BEST_SHAPE bounds the stall at its one
        same-shape attempt and resizes. Timelines are priced with the
        strategy's OWN retry policy (delay_for — deterministic
        envelope, no real sleeps) and booked into two accountants."""
        from skypilot_tpu.metrics.goodput import GoodputAccountant
        from skypilot_tpu.metrics.registry import Registry
        policy = recovery_strategy.LAUNCH_RETRY_POLICY
        outage_attempts = 2

        # Baseline: wait out the outage at the same shape — every
        # failed attempt burns its backoff delay before capacity
        # returns on attempt 3.
        wait_stall = sum(
            policy.base_delay * (2 ** k)  # jitter envelope upper edge
            for k in range(outage_attempts))
        # Elastic: one bounded same-shape attempt (no backoff after
        # the last attempt of a launch() call), then the step-down
        # launches a smaller shape immediately.
        elastic_stall = 0.0

        base_acct = GoodputAccountant(registry=Registry())
        elastic_acct = GoodputAccountant(registry=Registry())
        relaunch_cost = 1.0  # identical on both arms
        base_acct.note('recovery_stall', relaunch_cost + wait_stall)
        elastic_acct.note('recovery_stall',
                          relaunch_cost + elastic_stall)
        base_bucket = base_acct.snapshot()['recovery_stall']
        elastic_bucket = elastic_acct.snapshot()['recovery_stall']
        assert elastic_bucket < base_bucket
        assert base_bucket - elastic_bucket == \
            pytest.approx(wait_stall)


# ---------------------------------------------------------------------
# bench --bench elastic
# ---------------------------------------------------------------------


def _load_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'bench_under_test',
        os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


class TestElasticBench:

    def test_elastic_row_records_mb_per_sec(self, monkeypatch):
        monkeypatch.setenv('BENCH_ELASTIC_MB', '2')
        bench = _load_bench()
        result = bench.elastic_main()
        assert result['metric'] == 'elastic_resize_restore_mb_per_sec'
        assert result['unit'] == 'MB/s'
        assert result['value'] > 0
        d = result['detail']
        assert d['saved_shards'] == 8 and d['target_shards'] == 4
        assert d['full_restore_mb_per_sec'] > 0
        # The row lands in bench_runs (the perf-gate history).
        from skypilot_tpu.benchmark import benchmark_state as bs
        run_id = bs.record_bench_run(result)
        assert run_id is not None
        rows = bs.bench_runs('elastic_resize_restore_mb_per_sec')
        assert len(rows) == 1 and rows[0]['value'] == result['value']

    def test_env_failure_is_typed_and_never_recorded(self):
        bench = _load_bench()
        # Classification: a backend that cannot start (JAX's own
        # message, as this sandbox gives it with no chip) and the
        # agent-connectivity class are env failures; a plain
        # assertion is not.
        no_chip = RuntimeError(
            "Unable to initialize backend 'tpu': UNKNOWN: TPU "
            'initialization failed: No jellyfish device found.')
        assert bench._is_env_failure(no_chip)  # pylint: disable=protected-access
        assert bench._is_env_failure(  # pylint: disable=protected-access
            ConnectionRefusedError('connection refused'))
        # Code-under-test failures must NOT be reclassified as
        # harness problems, even when their messages smell networky:
        # they belong in the bench_error row the gate can see.
        assert not bench._is_env_failure(  # pylint: disable=protected-access
            AssertionError('loss did not decrease'))
        assert not bench._is_env_failure(  # pylint: disable=protected-access
            RuntimeError('decode deadline exceeded for request 3'))
        assert not bench._is_env_failure(  # pylint: disable=protected-access
            TimeoutError('replica read timed out'))
        # The typed row: distinct exit code, null value.
        rc = bench._emit_env_error(no_chip)  # pylint: disable=protected-access
        assert rc == bench.ENV_ERROR_EXIT_CODE == 4
        # record_bench_run refuses the typed row — an env failure can
        # never seed bench_runs history.
        from skypilot_tpu.benchmark import benchmark_state as bs
        assert bs.record_bench_run(
            {'metric': 'bench_env_error', 'value': None,
             'unit': 'env_error'}) is None
        assert bs.check_regression(
            {'metric': 'bench_env_error', 'value': None}) == []
        assert bs.bench_runs('bench_env_error') == []


# ---------------------------------------------------------------------
# The local-fake e2e: kill one "slice" of a 2-host managed job
# mid-training; it must finish on the survivor, resized, with loss
# continuity across the resize.
# ---------------------------------------------------------------------

_TRAINER = '''
import json, os, sys, time
sys.path.insert(0, @REPO@)  # repo root (script runs from tmpdir)
# Force the CPU platform the way tests/conftest.py does.
os.environ['JAX_PLATFORMS'] = 'cpu'

rank = int(os.environ.get('SKYTPU_NODE_RANK', '0'))
if rank != 0:
    # The second "slice": parks until preempted. It never exists on
    # the resized relaunch.
    time.sleep(120)
    sys.exit(0)

import numpy as np
import jax
jax.config.update('jax_platforms', 'cpu')
from skypilot_tpu.data.checkpoint import CheckpointManager
from skypilot_tpu.metrics import goodput as goodput_lib

log_path = os.environ['ELASTIC_LOSS_LOG']
stall_path = os.environ['ELASTIC_STALL_LOG']
resized = os.environ.get('SKYTPU_ELASTIC_RESIZED', '')
stall = goodput_lib.note_recovery_stall_from_env()
if stall is not None:
    with open(stall_path, 'a') as f:
        snap = goodput_lib.accountant().snapshot()
        f.write(json.dumps({'stall': stall,
                            'bucket': snap['recovery_stall'],
                            'resized': resized}) + '\\n')

ckpt = CheckpointManager(os.environ['SKYTPU_CHECKPOINT_DIR'],
                         save_interval_steps=1, process_index=0,
                         process_count=1)
state = {'w': np.full(4, 16.0, np.float32)}
state, start = ckpt.restore_or(state)
total = 6
for step in range(start, total):
    # One deterministic "train step": loss strictly decreases, and a
    # restored w reproduces the exact loss trajectory — the loss-
    # continuity assertion across the resize.
    loss = float((state['w'] ** 2).mean())
    with open(log_path, 'a') as f:
        f.write(f'{step} {loss:.6f} {"resized" if resized else "full"}\\n')
    state = {'w': state['w'] * 0.5}
    ckpt.maybe_save(step, state)
    if not resized and step >= 2:
        # First (2-host) run: park FOREVER so only the preemption can
        # end it — it must never finish at the designed shape.
        ckpt.wait()
        while True:
            time.sleep(5)
ckpt.wait()
ckpt.close()
'''


class TestElasticManagedJobE2E:

    def test_resize_resume_on_surviving_slice(self, tmp_path,
                                              monkeypatch, faults,
                                              fast_poll,
                                              cleanup_clusters):
        import yaml

        from skypilot_tpu.jobs.controller import JobsController
        from skypilot_tpu.resilience import faults as faults_lib

        # One bounded same-shape attempt, then step down.
        monkeypatch.setenv(
            recovery_strategy.SAME_SHAPE_ATTEMPTS_ENV, '1')
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        script = tmp_path / 'trainer.py'
        script.write_text(_TRAINER.replace('@REPO@',
                                           repr(repo_root)))
        ckpt_base = tmp_path / 'ckpt'
        loss_log = tmp_path / 'loss.log'
        stall_log = tmp_path / 'stall.log'

        task = Task(name='el2', run=f'python3 {script}')
        res = Resources(
            cloud='local',
            job_recovery={'strategy': 'NEXT_BEST_SHAPE'})
        res._extra_config = {'num_hosts': 2}  # pylint: disable=protected-access
        task.set_resources(res)
        task.update_envs({
            'SKYTPU_CHECKPOINT_DIR': str(ckpt_base),
            'ELASTIC_LOSS_LOG': str(loss_log),
            'ELASTIC_STALL_LOG': str(stall_log),
        })
        dag_yaml = str(tmp_path / 'dag.yaml')
        with open(dag_yaml, 'w', encoding='utf-8') as f:
            yaml.safe_dump_all([task.to_yaml_config()], f)
        job_id = jobs_state.add_job('el2', dag_yaml, 'inproc')
        ctrl = JobsController(job_id, dag_yaml)
        cluster_name = f'el2-{job_id}-0'
        lineage = ckpt_base / f'managed-{job_id}-0'

        def committed_steps():
            if not lineage.is_dir():
                return []
            return [d for d in os.listdir(lineage)
                    if d.startswith('step_') and
                    os.path.exists(lineage / d / 'COMMITTED')]

        def preempt_one_slice():
            deadline = time.time() + 90
            while time.time() < deadline:
                rec = jobs_state.get_job(job_id)
                crec = state.get_cluster_from_name(cluster_name)
                if (rec is not None and crec is not None and
                        rec['status'] ==
                        jobs_state.ManagedJobStatus.RUNNING and
                        len(committed_steps()) >= 2):
                    # Same-shape capacity "gone" for exactly the
                    # bounded wait: the one same-shape relaunch
                    # attempt fails, then the 1-host rung launches.
                    faults_lib.arm('provision.launch', 'error', 1.0,
                                   count=1)
                    handle = crec['handle']
                    provision.terminate_instances(
                        'local', handle.region,
                        handle.cluster_name_on_cloud)
                    return
                time.sleep(0.5)

        killer = threading.Timer(1.0, preempt_one_slice)
        killer.start()
        try:
            final = ctrl.run()
        finally:
            killer.cancel()
        assert final == jobs_state.ManagedJobStatus.SUCCEEDED

        rec = jobs_state.get_job(job_id)
        assert rec['recovery_count'] >= 1
        # The resize landed in job state: RESUME@step/new-mesh.
        assert rec['resume_mesh'] == '1xhost'
        assert rec['resume_step'] is not None

        # Loss continuity across the resize: the resumed run must
        # pick up EXACTLY where the checkpoint left off (a silent
        # fresh start would re-log steps 0..2 in the resized phase)
        # and the loss trajectory must stay on the checkpointed
        # curve (each step quarters the quadratic loss) straight
        # through the resize boundary.
        by_step = {}
        steps_by_phase = {'full': set(), 'resized': set()}
        for line in loss_log.read_text().splitlines():
            step_s, loss_s, phase = line.split()
            step_i, loss = int(step_s), float(loss_s)
            steps_by_phase[phase].add(step_i)
            by_step[step_i] = loss
        assert steps_by_phase['full'] == {0, 1, 2}
        assert steps_by_phase['resized'] == {3, 4, 5}, (
            'resumed run did not continue from the checkpoint',
            steps_by_phase)
        losses = [by_step[s] for s in range(6)]
        assert all(b < a for a, b in zip(losses, losses[1:])), losses
        for s in range(1, 6):
            # w halves per step -> loss quarters, INCLUDING across
            # the preemption/resize boundary at 2->3: the restored
            # state is bit-for-bit the saved one.
            assert by_step[s] == pytest.approx(by_step[s - 1] / 4,
                                               rel=1e-5)

        # The recovery stall was priced into the goodput bucket by
        # the RESIZED run.
        stalls = [json.loads(line) for line in
                  stall_log.read_text().splitlines()]
        assert stalls and stalls[-1]['resized']
        assert stalls[-1]['bucket'] >= stalls[-1]['stall'] > 0

        # RESUME@step/new-mesh is visible in `xsky jobs queue`.
        from click.testing import CliRunner

        from skypilot_tpu import cli as cli_mod
        out = CliRunner().invoke(cli_mod.cli, ['jobs', 'queue'])
        assert out.exit_code == 0, out.output
        assert f'/{rec["resume_mesh"]}' in out.output
        assert str(rec['resume_step']) in out.output
