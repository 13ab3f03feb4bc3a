"""The native checkpoint engine.

Save path (``maybe_save``):

1. snapshot: every addressable shard of every leaf is copied to host
   (``replica_id == 0`` shards only, so replicated leaves are
   written once per save, not once per device);
2. the snapshot is handed to the :class:`writer.AsyncWriter`
   (bounded queue — backpressure, not unbounded host RAM);
3. writer thread: shard files + per-host manifest land in
   ``step_N.tmp/`` (fsynced), the ``checkpoint.save`` fault site
   fires (a drill can tear the write HERE, between shards and
   commit), rank 0 merges host manifests and atomically commits,
   then retention GC runs.

Multi-host coordination: each process writes only the shards it can
address, into the SAME shared directory (checkpoints live on a
mounted bucket — the shared medium is the filesystem). Rank 0 waits
for every per-host manifest to land before committing, so a
checkpoint is only ever visible with all hosts' shards present. A
host that dies mid-save simply never produces its manifest; the
barrier times out, nothing is committed, and the previous committed
step keeps serving restores.

Restore: template-driven (``restore_or``) places each leaf back on
device with the template's sharding via
``jax.make_array_from_callback`` (each process materializes only its
addressable portion), or template-free (``restore_latest_raw``) into
nested host arrays with optional top-level subtree selection — the
serve warm-start path skips the optimizer moments entirely.
"""
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from skypilot_tpu import tpu_logging
from skypilot_tpu.checkpoint import commit as commit_lib
from skypilot_tpu.checkpoint import format as format_lib
from skypilot_tpu.checkpoint import retention as retention_lib
from skypilot_tpu.checkpoint import writer as writer_lib
from skypilot_tpu.checkpoint.format import (CheckpointError,
                                            CheckpointRestoreError)

logger = tpu_logging.init_logger(__name__)

BARRIER_POLL_SECONDS = 0.05


def _tree_util():
    import jax
    return jax.tree_util


def _device_count_of(flat) -> Optional[int]:
    """``jax.device_count()`` when the ``(path, leaf)`` list holds
    device-placed leaves: the process that owns them already runs a
    backend. None for host-only trees — the count means nothing
    there, and a process that only checkpoints numpy arrays should
    not start a backend to learn it."""
    if any(getattr(leaf, 'sharding', None) is not None
           for _, leaf in flat):
        import jax
        return jax.device_count()
    return None


def saved_device_count(lineage_dir: str) -> Optional[int]:
    """Device count recorded in the latest COMMITTED checkpoint under
    ``lineage_dir`` (jax-free manifest peek). None when there is no
    committed step or the manifest predates elastic resume — callers
    must treat that as "unknown", not as 0."""
    lineage_dir = os.path.expanduser(lineage_dir)
    step = commit_lib.latest_committed_step(lineage_dir)
    if step is None:
        return None
    step_dir = os.path.join(lineage_dir,
                            commit_lib.step_dir_name(step))
    try:
        manifest = format_lib.read_manifest(step_dir)
    except CheckpointRestoreError:
        return None
    count = manifest.get('device_count')
    return int(count) if count is not None else None


class NativeCheckpointManager:
    """Dependency-free async sharded checkpointing (stdlib+numpy+jax).

    Drop-in for the facade surface of ``data/checkpoint.py``:
    ``maybe_save`` / ``latest_step`` / ``restore_or`` /
    ``restore_latest_raw`` / ``wait`` / ``close``.
    """

    def __init__(self, path: str, save_interval_steps: int = 100,
                 max_to_keep: Optional[int] = 3,
                 keep_period: Optional[int] = None,
                 queue_depth: int = 2,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 barrier_timeout: float = 600.0):
        self.path = os.path.expanduser(path)
        os.makedirs(self.path, exist_ok=True)
        self._interval = max(1, int(save_interval_steps))
        self._max_to_keep = max_to_keep
        self._keep_period = keep_period
        self._barrier_timeout = barrier_timeout
        if process_index is None or process_count is None:
            import jax
            process_index = jax.process_index()
            process_count = jax.process_count()
        self._proc = process_index
        self._nprocs = process_count
        self._metrics = writer_lib.ckpt_metrics()
        self._last_submitted: Optional[int] = None
        # Global device count captured at snapshot time (rank 0
        # writes it into the merged manifest) and details of the most
        # recent restore (step, bytes read, whether the template's
        # shardings differed from the saved ones — the elastic-resume
        # signal; see restore()).
        self._snapshot_device_count: Optional[int] = None
        self.last_restore: Optional[Dict[str, Any]] = None
        # Torn writes from a crashed/preempted predecessor are swept
        # before the FIRST save (rank 0), not in __init__: a manager
        # constructed only to restore (a serve replica warm-starting
        # against a lineage another process is still training into)
        # must never run destructive GC. Readers don't need the sweep
        # — torn dirs carry no marker and are invisible to them.
        self._orphans_swept = False
        self._writer = writer_lib.AsyncWriter(
            self._write_step, queue_depth=queue_depth,
            # An abandoned (drill-preempted) step must stay
            # retryable: clear the same-step dedup for it.
            on_abandoned=self._forget_submitted)

    # -- save -----------------------------------------------------------

    def should_save(self, step: int) -> bool:
        return step % self._interval == 0

    def _forget_submitted(self, step: int) -> None:
        if self._last_submitted == step:
            self._last_submitted = None

    def maybe_save(self, step: int, state: Any,
                   force: bool = False) -> bool:
        # Surface a parked write error FIRST — and forget the failed
        # step, so a retry of that same step is not silently dropped
        # by the dedup below.
        try:
            self._writer.raise_pending_error()
        except BaseException:
            self._last_submitted = None
            raise
        if not force and not self.should_save(step):
            return False
        step = int(step)
        if step == self._last_submitted:
            return False
        # Goodput: only the LOOP-BLOCKING portion of a save counts
        # against the checkpoint bucket — the device->host snapshot
        # and any submit backpressure. The async background write
        # overlaps compute and costs no goodput (that overlap is the
        # whole point of the async writer). A save that raises still
        # blocked the loop for its duration — note in finally.
        t0 = time.monotonic()
        try:
            payload = self._snapshot(state)
            self._writer.submit(step, payload)
        finally:
            from skypilot_tpu.metrics import goodput as goodput_lib
            goodput_lib.note('checkpoint_save',
                             time.monotonic() - t0)
        self._last_submitted = step
        return True

    def save(self, step: int, state: Any) -> bool:
        return self.maybe_save(step, state, force=True)

    def wait(self) -> None:
        t0 = time.monotonic()
        try:
            self._writer.wait()
        except BaseException:
            # The failed step must stay retryable: forget it so the
            # same-step dedup in maybe_save doesn't swallow a retry.
            self._last_submitted = None
            raise
        finally:
            from skypilot_tpu.metrics import goodput as goodput_lib
            goodput_lib.note('checkpoint_save',
                             time.monotonic() - t0)

    def close(self) -> None:
        try:
            self._writer.close()
        except BaseException:
            self._last_submitted = None
            raise

    # -- read side ------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return commit_lib.latest_committed_step(self.path)

    def all_steps(self) -> List[int]:
        return commit_lib.committed_steps(self.path)

    def restore_or(self, state: Any) -> Tuple[Any, int]:
        """Restore the latest committed checkpoint into the template
        ``state`` (same tree structure, each leaf placed with the
        template's sharding); returns ``(state, next_step)``."""
        step = self.latest_step()
        if step is None:
            self._metrics['restores_total'].labels(
                outcome='empty').inc()
            return state, 0
        try:
            restored = self.restore(step, state)
        except Exception:
            self._metrics['restores_total'].labels(
                outcome='error').inc()
            raise
        self._metrics['restores_total'].labels(outcome='ok').inc()
        return restored, step + 1

    def restore(self, step: int, state: Any) -> Any:
        # Span is a no-op outside a trace; inside one (preemption
        # resume under a managed job) the restore cost shows in the
        # recovery waterfall. The goodput accountant gets the same
        # interval (restore blocks the loop by definition).
        from skypilot_tpu import trace as trace_lib
        from skypilot_tpu.metrics import goodput as goodput_lib
        t0 = time.monotonic()
        try:
            with trace_lib.span('ckpt.restore', attrs={'step': step}):
                return self._restore_traced(step, state)
        finally:
            goodput_lib.note('restore', time.monotonic() - t0)

    def _restore_traced(self, step: int, state: Any) -> Any:
        """Template-driven restore, re-sharding on the fly: each leaf
        is placed with the TEMPLATE's sharding, and each device's
        window is assembled from only the saved shard files that
        overlap it (``format.assemble_region``). The saved and
        restoring meshes therefore never need to match — an 8-chip
        checkpoint restores onto a 4-chip mesh by re-partitioning the
        saved shards against the new ``PartitionSpec`` tree (elastic
        resume, docs/checkpointing.md)."""
        step_dir = os.path.join(self.path,
                                commit_lib.step_dir_name(step))
        manifest = format_lib.read_manifest(step_dir)
        leaves = manifest['leaves']
        tree_util = _tree_util()
        flat, treedef = tree_util.tree_flatten_with_path(state)
        out = []
        missing = []
        stats = {'bytes_read': 0, 'resharded': False}
        for path, leaf in flat:
            key = format_lib.key_str(path)
            entry = leaves.get(key)
            if entry is None:
                missing.append(key)
                continue
            out.append(self._place_leaf(step_dir, key, entry, leaf,
                                        stats))
        if missing:
            raise CheckpointRestoreError(
                f'checkpoint step {step} at {self.path} is missing '
                f'{len(missing)} leaves of the restore template '
                f'(first few: {missing[:5]}); was it saved from a '
                'different model/optimizer configuration?')
        restored = tree_util.tree_unflatten(treedef, out)
        device_count = _device_count_of(flat)
        self.last_restore = {
            'step': step,
            'bytes_read': stats['bytes_read'],
            'resharded': stats['resharded'],
            'saved_device_count': manifest.get('device_count'),
            'device_count': device_count,
        }
        if stats['resharded']:
            self._metrics['reshard_restores_total'].inc()
            logger.info(
                'checkpoint step %d restored RESHARDED onto the '
                'current mesh (%s saved devices -> %s; %.1f MB read)',
                step, manifest.get('device_count', '?'),
                device_count, stats['bytes_read'] / 1e6)
        return restored

    def _place_leaf(self, step_dir: str, key: str,
                    entry: Dict[str, Any], template_leaf: Any,
                    stats: Dict[str, Any]) -> Any:
        """Materialize one leaf against the template's placement.

        Sharded template leaves are built shard-window by
        shard-window (``make_array_from_callback`` asks for each
        addressable window; only overlapping saved shards are read),
        so a process restores only the bytes its devices own. Host
        leaves assemble in full."""
        shape = tuple(entry['shape'])
        if hasattr(template_leaf, 'addressable_shards'):
            import jax
            sharding = template_leaf.sharding
            saved_sharding = entry.get('sharding')
            if saved_sharding is not None and \
                    saved_sharding != str(sharding):
                stats['resharded'] = True
            # Cache per-window reads: replicated axes make jax ask
            # for the SAME window once per device holding a replica.
            window_cache: Dict[tuple, Any] = {}

            def read_window(idx):
                region = tuple(
                    tuple(w) for w in format_lib.normalize_index(
                        idx, shape))
                cached = window_cache.get(region)
                if cached is None:
                    cached = format_lib.assemble_region(
                        step_dir, key, entry,
                        [list(w) for w in region])
                    stats['bytes_read'] += cached.nbytes
                    window_cache[region] = cached
                return cached

            return jax.make_array_from_callback(
                shape, sharding, lambda idx: read_window(idx))
        host = format_lib.assemble_leaf(step_dir, key, entry)
        stats['bytes_read'] += host.nbytes
        if isinstance(template_leaf, np.ndarray):
            return host
        if host.shape == ():
            return type(template_leaf)(host.item())
        return host

    def restore_latest_raw(self, keys: Optional[Sequence[str]] = None
                           ) -> Optional[Any]:
        """Template-free restore of the latest committed step: host
        (numpy) arrays in the saved tree structure. ``keys`` selects
        top-level subtrees (e.g. ``('params', 'lora')``) — unselected
        subtrees (the optimizer moments, 2/3 of the bytes at 8B
        scale) are never read from storage."""
        step = self.latest_step()
        if step is None:
            self._metrics['restores_total'].labels(
                outcome='empty').inc()
            return None
        step_dir = os.path.join(self.path,
                                commit_lib.step_dir_name(step))
        try:
            manifest = format_lib.read_manifest(step_dir)
            flat: Dict[str, np.ndarray] = {}
            for key, entry in manifest['leaves'].items():
                top = key.split('/', 1)[0]
                if keys is not None and top not in keys:
                    continue
                flat[key] = format_lib.assemble_leaf(step_dir, key,
                                                     entry)
        except Exception:
            self._metrics['restores_total'].labels(
                outcome='error').inc()
            raise
        if not flat:
            # Nothing matched the subtree selection: to the caller
            # this is "no usable checkpoint" (e.g. serving pointed at
            # a checkpoint with no 'params'), not a success.
            self._metrics['restores_total'].labels(
                outcome='empty').inc()
            logger.warning(
                'checkpoint step %d at %s has no leaves under %s '
                '(top-level keys: %s)', step, self.path, keys,
                sorted({k.split('/', 1)[0]
                        for k in manifest['leaves']}))
            return None
        self._metrics['restores_total'].labels(outcome='ok').inc()
        logger.info('restored checkpoint step %d from %s (%d leaves)',
                    step, self.path, len(flat))
        return format_lib.nest(flat)

    # -- internals ------------------------------------------------------

    def _snapshot(self, state: Any) -> List[Tuple[str, Dict[str, Any],
                                                  List[Tuple[Any,
                                                             np.ndarray]]]]:
        """Device -> host copy of every addressable shard this
        process owns. Returns ``[(key, leaf_entry, [(index, host_np),
        ...]), ...]`` — after this returns, the live state may be
        donated/mutated freely."""
        tree_util = _tree_util()
        flat, _ = tree_util.tree_flatten_with_path(state)
        # Recorded in the merged manifest so a restore onto a
        # different mesh can tell it is a resize (elastic resume).
        self._snapshot_device_count = _device_count_of(flat)
        payload = []
        for path, leaf in flat:
            key = format_lib.key_str(path)
            if hasattr(leaf, 'addressable_shards'):
                entry = format_lib.leaf_entry(
                    leaf.dtype, leaf.shape,
                    sharding=str(getattr(leaf, 'sharding', None)))
                shards = []
                for shard in leaf.addressable_shards:
                    if shard.replica_id != 0:
                        continue
                    index = format_lib.normalize_index(
                        shard.index, leaf.shape)
                    shards.append((index, np.asarray(shard.data)))
                if not shards:
                    continue  # some other host owns this leaf
                payload.append((key, entry, shards))
            else:
                if self._proc != 0:
                    continue  # host-replicated leaf: rank 0 writes it
                arr = np.asarray(leaf)
                entry = format_lib.leaf_entry(arr.dtype, arr.shape)
                payload.append(
                    (key, entry,
                     [(format_lib.full_index(arr.shape), arr)]))
        return payload

    def _write_step(self, step: int, payload) -> Tuple[int, bool]:
        """Writer-thread body: shards -> host manifest -> barrier ->
        merge -> fault site -> commit -> retention. Returns
        ``(nbytes, committed)`` — only rank 0's commit counts as a
        committed step for the metrics gauge."""
        from skypilot_tpu.resilience import faults
        if self._proc == 0 and not self._orphans_swept:
            self._orphans_swept = True
            commit_lib.gc_orphaned_tmp(self.path)
        tmp = os.path.join(self.path, commit_lib.tmp_dir_name(step))
        os.makedirs(tmp, exist_ok=True)
        nbytes = 0
        leaves: Dict[str, Any] = {}
        for i, (key, entry, shards) in enumerate(payload):
            for j, (index, host_arr) in enumerate(shards):
                fname = f'h{self._proc}_{i:05d}_{j}.bin'
                size, crc = format_lib.write_shard_file(tmp, fname,
                                                        host_arr)
                nbytes += size
                entry['shards'].append({
                    'file': fname,
                    'index': index,
                    'nbytes': size,
                    'checksum': crc,
                })
            leaves[key] = entry
        format_lib.write_host_manifest(tmp, self._proc, leaves,
                                       self._nprocs)
        if self._proc != 0:
            # Non-zero ranks are done: rank 0 owns the commit.
            return nbytes, False
        self._await_host_manifests(tmp, step)
        merged = format_lib.merge_host_manifests(tmp, self._nprocs)
        format_lib.write_manifest(
            tmp, step, merged, self._nprocs,
            device_count=self._snapshot_device_count)
        kind = faults.fire('checkpoint.save')
        if kind == 'preempt':
            # Simulated crash between shard write and commit: leave
            # the torn tmp dir exactly as a dead process would.
            raise writer_lib._AbandonedSave()  # noqa: SLF001
        if kind is not None:
            raise CheckpointError(
                f'[fault:checkpoint.save] injected {kind}')
        commit_lib.commit(self.path, step)
        retention_lib.apply_retention(self.path, self._max_to_keep,
                                      self._keep_period)
        return nbytes, True

    def _await_host_manifests(self, tmp: str, step: int) -> None:
        """Rank 0's pre-commit barrier: every process's manifest must
        be visible in the shared step dir before the merge. This is a
        filesystem barrier on purpose — the checkpoint dir IS the
        shared medium (a mounted bucket), and a host that died
        mid-save simply never produces its manifest: the barrier
        times out and the previous committed step stays authoritative."""
        deadline = time.monotonic() + self._barrier_timeout
        pending = set(range(1, self._nprocs))
        while pending:
            pending = {
                p for p in pending
                if not os.path.exists(os.path.join(
                    tmp, format_lib.HOST_MANIFEST_FMT.format(proc=p)))
            }
            if not pending:
                return
            if time.monotonic() >= deadline:
                raise CheckpointError(
                    f'checkpoint step {step}: hosts {sorted(pending)} '
                    f'never wrote their manifests within '
                    f'{self._barrier_timeout:.0f}s; leaving the step '
                    'uncommitted')
            time.sleep(BARRIER_POLL_SECONDS)
