"""Compute observability plane (PR 7): goodput/MFU accounting,
device-memory telemetry through the agents, the textfile metrics
bridge, on-demand profiling, and `xsky top`.

Acceptance coverage:
- goodput buckets sum to within 5% of measured wall clock in a loop
  interleaving real train steps, a checkpoint save (with an injected
  checkpoint.save fault), and a simulated recovery stall;
- fake memory_stats() devices drive the HBM gauges end to end
  through a REAL agent scrape (py and, when built, C++);
- a profile armed via the agent endpoint captures a real
  jax.profiler trace on the CPU backend and renders a non-empty
  op-time table;
- `xsky top --once` renders a 2-host fleet snapshot (host, HBM,
  train, serve, breaker columns) from two live fake agents.
"""
import json
import os
import time

import pytest

from skypilot_tpu import metrics as metrics_lib
from skypilot_tpu.metrics import device as device_lib
from skypilot_tpu.metrics import exposition
from skypilot_tpu.metrics import goodput as goodput_lib
from skypilot_tpu.metrics import publish as publish_lib
from skypilot_tpu.utils import profiling as profiling_lib


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


@pytest.fixture(autouse=True)
def _fresh_accountant():
    goodput_lib.reset_accountant()
    yield
    goodput_lib.reset_accountant()


class FakeDevice:
    def __init__(self, used=100, limit=1000, peak=500):
        self._stats = {'bytes_in_use': used, 'bytes_limit': limit,
                       'peak_bytes_in_use': peak}

    def memory_stats(self):
        return self._stats


class StatlessDevice:
    """CPU-backend shape: memory_stats() is None."""

    def memory_stats(self):
        return None


# ---------------------------------------------------------------------
# Goodput accountant
# ---------------------------------------------------------------------


class TestGoodputAccounting:

    def test_partition_and_ratio(self):
        acct = goodput_lib.accountant()
        acct.observe_step(10.0, compile_step=True)
        acct.observe_step(2.0)
        acct.note('checkpoint_save', 0.5)
        acct.observe_step(2.5)  # 0.5 carved out -> 2.0 compute
        snap = acct.snapshot()
        assert snap['compile'] == pytest.approx(10.0)
        assert snap['compute'] == pytest.approx(4.0)
        assert snap['checkpoint_save'] == pytest.approx(0.5)
        total = sum(snap.values())
        assert total == pytest.approx(14.5)
        ratio = metrics_lib.registry().gauge(
            'skytpu_goodput_ratio').value
        assert ratio == pytest.approx(4.0 / 14.5)

    def test_claim_larger_than_interval_never_negative(self):
        t = time.monotonic()
        acct = goodput_lib.accountant()
        acct.note('restore', 5.0, noted_at=t)
        # Interval [t-2, t] lies wholly inside the 5s restore window
        # -> fully claimed, compute never goes negative.
        acct.observe_step(2.0, now=t)
        snap = acct.snapshot()
        assert snap['compute'] == pytest.approx(0.0, abs=1e-9)
        assert snap['restore'] == pytest.approx(5.0)
        # A LATER interval ([t, t+3]) does not overlap the restore
        # window at all — it keeps its full compute measure.
        acct.observe_step(3.0, now=t + 3.0)
        assert acct.snapshot()['compute'] == pytest.approx(3.0)

    def test_unknown_bucket_rejected(self):
        with pytest.raises(ValueError):
            goodput_lib.note('napping', 1.0)

    def test_claim_outside_intervals_never_docks_compute(self):
        """A pre-loop restore (ends long before the first observed
        interval starts) counts in its bucket but must not be carved
        out of compile/compute it never interrupted."""
        acct = goodput_lib.accountant()
        acct.note('restore', 5.0,
                  noted_at=time.monotonic() - 100.0)
        acct.observe_step(2.0, compile_step=True)
        acct.observe_step(1.5)
        snap = acct.snapshot()
        assert snap['restore'] == pytest.approx(5.0)
        assert snap['compile'] == pytest.approx(2.0)
        assert snap['compute'] == pytest.approx(1.5)

    def test_mfu_math(self):
        acct = goodput_lib.accountant()
        acct.set_model_info(int(1e9), 1000, n_chips=2,
                            peak_flops_per_chip_value=3e12,
                            full_finetune=True)
        acct.observe_step(0.1, compile_step=True)
        acct.observe_step(1.0)  # 6e12 flops / (1s * 2 * 3e12) = 1.0
        mfu = metrics_lib.registry().gauge('skytpu_mfu_ratio').value
        assert mfu == pytest.approx(1.0)

    def test_mfu_absent_without_peak(self, monkeypatch):
        monkeypatch.delenv(goodput_lib.ENV_ACCELERATOR,
                           raising=False)
        assert goodput_lib.peak_flops_per_chip() is None
        assert goodput_lib.peak_flops_per_chip('tpu-v5p-8') == \
            pytest.approx(459e12)
        assert goodput_lib.peak_flops_per_chip('not-a-tpu') is None

    def test_accelerator_env_stamp(self, monkeypatch):
        monkeypatch.setenv(goodput_lib.ENV_ACCELERATOR, 'tpu-v6e-8')
        assert goodput_lib.peak_flops_per_chip() == \
            pytest.approx(918e12)


class TestGoodputEndToEnd:
    """Acceptance: buckets sum to within 5% of measured wall clock
    with real train steps, a checkpoint save whose write is killed
    by an injected checkpoint.save fault, and a simulated recovery
    stall."""

    def test_buckets_sum_to_wall_clock(self, tmp_path, faults):
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.checkpoint.native import \
            NativeCheckpointManager
        from skypilot_tpu.models import llama
        from skypilot_tpu.parallel import (MeshConfig,
                                           build_train_step,
                                           init_train_state,
                                           instrument_train_step,
                                           make_mesh)
        config = llama.get_config('tiny')
        mesh = make_mesh(MeshConfig(fsdp=len(jax.devices())))
        state, shardings = init_train_state(
            config, mesh, jax.random.PRNGKey(0))
        step = instrument_train_step(
            build_train_step(config, mesh, shardings),
            tokens_per_step=8 * 16, model_config=config,
            full_finetune=True)
        batch = {'tokens': jnp.zeros((8, 17), jnp.int32)}
        ckpt = NativeCheckpointManager(str(tmp_path / 'ckpt'),
                                       save_interval_steps=1)
        faults.arm('checkpoint.save', 'error', 1.0, count=1)

        acct = goodput_lib.accountant()
        t0 = time.perf_counter()
        state, m = step(state, batch)      # compile step
        jax.block_until_ready(m['loss'])
        for _ in range(3):
            state, m = step(state, batch)
            jax.block_until_ready(m['loss'])
        # Blocking checkpoint work between steps (the injected fault
        # kills the background write; the blocked time still counts).
        ckpt.maybe_save(1, state)
        with pytest.raises(Exception):
            ckpt.wait()
        state, m = step(state, batch)
        jax.block_until_ready(m['loss'])
        # Simulated recovery stall.
        stall = 0.15
        time.sleep(stall)
        goodput_lib.note('recovery_stall', stall)
        state, m = step(state, batch)
        jax.block_until_ready(m['loss'])
        # Closing call: the final step's interval is observed at the
        # NEXT call, exactly like the step-seconds histogram.
        state, m = step(state, batch)
        wall = time.perf_counter() - t0
        ckpt.close()

        snap = acct.snapshot()
        total = sum(snap.values())
        assert snap['compile'] > 0
        assert snap['compute'] > 0
        assert snap['checkpoint_save'] > 0
        assert snap['recovery_stall'] == pytest.approx(stall)
        # The last call's own execution is outside the accounted
        # window (never closed) — compare against the wall clock up
        # to that closing call.
        assert total == pytest.approx(wall, rel=0.05), (snap, wall)

    def test_restore_noted(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.checkpoint.native import \
            NativeCheckpointManager
        ckpt = NativeCheckpointManager(str(tmp_path / 'ckpt'),
                                       save_interval_steps=1)
        state = {'w': jnp.ones((4,))}
        ckpt.save(3, state)
        ckpt.wait()
        acct = goodput_lib.accountant()
        before = acct.snapshot()['restore']
        restored, nxt = ckpt.restore_or({'w': jnp.zeros((4,))})
        assert nxt == 4
        assert jax.numpy.allclose(restored['w'], 1.0)
        assert acct.snapshot()['restore'] > before
        ckpt.close()


# ---------------------------------------------------------------------
# Device memory + textfile bridge + agent scrape
# ---------------------------------------------------------------------


class TestDeviceMemory:

    def test_fake_devices_drive_gauges(self):
        rows = device_lib.sample_device_memory(
            [FakeDevice(100, 1000, 500), FakeDevice(7, 9, 8)])
        assert [r['device'] for r in rows] == [0, 1]
        fam = metrics_lib.registry().gauge(
            'skytpu_device_hbm_used_bytes', labelnames=('device',))
        assert fam.labels(device='0').value == 100
        assert fam.labels(device='1').value == 7

    def test_statless_backend_is_noop(self):
        assert device_lib.sample_device_memory([StatlessDevice()]) \
            == []

    def test_real_cpu_backend_is_graceful(self):
        # conftest forces the CPU platform: memory_stats() is None
        # there today; if jax ever grows CPU stats this still must
        # not raise.
        device_lib.sample_device_memory()


class TestTextfileBridge:

    def test_publish_and_read_with_proc_label(self, tmp_path):
        d = str(tmp_path / 'metrics.d')
        device_lib.sample_device_memory([FakeDevice()])
        pub = publish_lib.MetricsPublisher('train', directory=d)
        pub.publish_once()
        text = publish_lib.read_textfiles(d)
        fams = exposition.parse_text(text)
        assert 'skytpu_device_hbm_used_bytes' in fams
        sample = fams['skytpu_device_hbm_used_bytes'].samples[0]
        labels = dict(sample.labels)
        assert labels['proc'].startswith('train-')
        assert labels['device'] == '0'
        pub.close()
        assert not os.path.exists(pub.path)

    def test_header_dedup_across_publishers(self, tmp_path):
        d = str(tmp_path / 'metrics.d')
        metrics_lib.registry().gauge('skytpu_goodput_ratio').set(0.5)
        a = publish_lib.MetricsPublisher('a', directory=d)
        b = publish_lib.MetricsPublisher('b', directory=d)
        a.publish_once()
        b.publish_once()
        text = publish_lib.read_textfiles(d)
        assert text.count('# TYPE skytpu_goodput_ratio gauge') == 1
        fams = exposition.parse_text(text)
        procs = {dict(s.labels)['proc']
                 for s in fams['skytpu_goodput_ratio'].samples}
        assert len(procs) == 2

    def test_stale_files_skipped_and_swept(self, tmp_path):
        d = tmp_path / 'metrics.d'
        d.mkdir()
        stale = d / 'dead-1.prom'
        stale.write_text('# TYPE x gauge\nx 1\n')
        old = time.time() - 3600
        os.utime(stale, (old, old))
        assert publish_lib.read_textfiles(str(d)) == ''
        assert not stale.exists()


@pytest.fixture(params=['py', 'cpp'])
def live_agent(request, tmp_path, monkeypatch):
    """A real agent of each implementation with the shared metrics/
    profile dirs pinned (env is inherited by the spawned agent)."""
    from skypilot_tpu.runtime import agent_client
    from skypilot_tpu.runtime.agent_client import AgentClient
    if request.param == 'cpp' and \
            agent_client.resolve_agent_binary() is None:
        pytest.skip('C++ agent not built')
    monkeypatch.setenv('SKYTPU_METRICS_DIR',
                       str(tmp_path / 'metrics.d'))
    monkeypatch.setenv('SKYTPU_PROFILE_DIR',
                       str(tmp_path / 'profiles'))
    port = _free_port()
    # The runtime dir is the agent's LIVENESS ANCHOR — it must exist
    # or the agent self-terminates within seconds (lifecycle.md).
    rt = tmp_path / 'rt'
    rt.mkdir()
    proc = agent_client.start_local_agent(
        port, runtime_dir=str(rt),
        use_cpp=(request.param == 'cpp'))
    client = AgentClient('127.0.0.1', port)
    client.wait_healthy(timeout=15)
    yield client
    proc.terminate()
    proc.wait(timeout=5)


class TestAgentScrapeEndToEnd:
    """Fake memory_stats() devices → gauges → textfile publisher →
    a REAL agent's /metrics (py and C++) → driver-side parse."""

    def test_hbm_gauges_through_agent_scrape(self, live_agent,
                                             tmp_path):
        # Private registry: the process-global one accumulates
        # series across tests (by design), which would change the
        # published sample counts here.
        reg = metrics_lib.Registry()
        device_lib.sample_device_memory(
            [FakeDevice(used=11, limit=101, peak=51)], registry=reg)
        pub = publish_lib.MetricsPublisher(
            'train', directory=str(tmp_path / 'metrics.d'),
            registry=reg)
        pub.publish_once()
        fams = exposition.parse_text(live_agent.metrics())
        # Agent's own gauges still there...
        assert 'skytpu_agent_uptime_seconds' in fams
        # ...plus the published compute series.
        used = fams['skytpu_device_hbm_used_bytes'].samples
        assert len(used) == 1
        assert used[0].value == 11
        assert dict(used[0].labels)['proc'].startswith('train-')
        assert fams['skytpu_device_hbm_limit_bytes'] \
            .samples[0].value == 101
        pub.close()
        # After close the series vanish from the next scrape.
        fams2 = exposition.parse_text(live_agent.metrics())
        assert 'skytpu_device_hbm_used_bytes' not in fams2

    def test_scrape_appends_host_history(self, live_agent,
                                         tmp_path):
        """Both agents append each /metrics scrape's own gauges to
        the bounded on-host history (docs/observability.md, Alerts &
        SLOs): one jsonl line per scrape under
        <runtime_dir>/metrics_history/host.jsonl, readable by the
        driver-side HistoryStore."""
        from skypilot_tpu.metrics.history import HistoryStore
        live_agent.metrics()
        store = HistoryStore('host', base=str(tmp_path / 'rt'))
        deadline = time.time() + 5
        while time.time() < deadline and store.point_count() == 0:
            time.sleep(0.2)
        assert store.point_count() >= 1
        uptime = store.latest('skytpu_agent_uptime_seconds')
        assert uptime is not None and uptime >= 0
        # Min-interval downsampling: an immediate re-scrape (well
        # inside the agents' 5 s default) adds no line.
        before = store.point_count()
        live_agent.metrics()
        assert store.point_count() == before

    def test_profile_arm_round_trip(self, live_agent, tmp_path):
        resp = live_agent.profile(steps=7)
        assert resp['ok'] and resp['steps'] == 7
        assert resp['dir'] == str(tmp_path / 'profiles')
        trigger = json.loads(
            (tmp_path / 'profiles' / 'trigger.json').read_text())
        assert trigger['steps'] == 7
        # Re-arm overwrites (idempotent).
        live_agent.profile(steps=3)
        assert profiling_lib.consume_trigger(
            str(tmp_path / 'profiles')) == 3
        # Consumed: nothing left.
        assert profiling_lib.consume_trigger(
            str(tmp_path / 'profiles')) is None


# ---------------------------------------------------------------------
# On-demand profiling through an instrumented loop
# ---------------------------------------------------------------------


class TestOnDemandProfiling:

    def test_agent_armed_capture_writes_summary(self, live_agent,
                                                tmp_path,
                                                monkeypatch):
        """Acceptance: armed via the agent endpoint, a real
        jax.profiler capture on the CPU backend produces a non-empty
        op-time table, fetched back through the agent."""
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.parallel import instrument_train_step
        resp = live_agent.profile(steps=2)
        remote_dir = resp['dir']

        step_fn = jax.jit(
            lambda s, b: (s, {'loss': (b['tokens'] @ s).sum()}))
        wrapped = instrument_train_step(step_fn)
        s = jnp.ones((8, 8))
        batch = {'tokens': jnp.ones((4, 8))}
        for _ in range(6):
            s2, m = wrapped(s, batch)
            jax.block_until_ready(m['loss'])
        summary_raw = live_agent.read_file(
            os.path.join(remote_dir, profiling_lib.LATEST_SUMMARY))
        assert summary_raw, 'no summary written by the armed loop'
        payload = json.loads(summary_raw)
        assert payload['kind'] == 'train'
        assert payload['steps'] == 2
        assert payload['rows'], 'op-time table is empty'
        table = profiling_lib.format_summary_payload(payload)
        assert 'total ms' in table
        assert payload['rows'][0]['name'] in table

    def test_batching_engine_checks_trigger(self, tmp_path,
                                            monkeypatch):
        """The decode loop consumes a trigger too (kind='decode')."""
        monkeypatch.setenv('SKYTPU_PROFILE_DIR',
                           str(tmp_path / 'profiles'))
        import jax

        from skypilot_tpu.models import llama
        from skypilot_tpu.serve.batching import BatchingEngine
        profiling_lib.write_trigger(steps=2)
        config = llama.get_config('tiny')
        params = llama.init_params(config, jax.random.PRNGKey(0))
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2)
        try:
            out = engine.generate([1, 2, 3], 9)
            assert len(out) == 9
            deadline = time.time() + 20
            payload = None
            while time.time() < deadline:
                payload = profiling_lib.load_summary()
                if payload is not None:
                    break
                engine.generate([1, 2, 3], 5)
        finally:
            engine.close()
        assert payload is not None, 'decode loop never profiled'
        assert payload['kind'] == 'decode'
        assert payload['rows']

    def test_diff_summaries(self):
        old = {'rows': [{'name': 'fusion', 'total_ms': 10.0,
                         'count': 1, 'category': ''},
                        {'name': 'gone', 'total_ms': 2.0,
                         'count': 1, 'category': ''}]}
        new = {'rows': [{'name': 'fusion', 'total_ms': 15.0,
                         'count': 1, 'category': ''},
                        {'name': 'fresh', 'total_ms': 1.0,
                         'count': 1, 'category': ''}]}
        deltas = profiling_lib.diff_summaries(old, new, top=5)
        by_name = {d['name']: d for d in deltas}
        assert by_name['fusion']['delta_ms'] == pytest.approx(5.0)
        assert by_name['fusion']['delta_pct'] == pytest.approx(50.0)
        assert by_name['gone']['delta_ms'] == pytest.approx(-2.0)
        assert by_name['fresh']['delta_pct'] is None
        text = profiling_lib.format_diff(deltas)
        assert 'fusion' in text and '+50.0%' in text

    def test_broken_trigger_dropped_not_retried(self, tmp_path):
        d = tmp_path / 'profiles'
        d.mkdir()
        (d / 'trigger.json').write_text('{"steps": ')
        assert profiling_lib.consume_trigger(str(d)) is None
        assert not (d / 'trigger.json').exists()


# ---------------------------------------------------------------------
# Batching engine KV gauges
# ---------------------------------------------------------------------


class TestKvCacheGauges:

    def test_allocated_and_used_bytes(self):
        import jax

        from skypilot_tpu.models import llama
        from skypilot_tpu.serve.batching import BatchingEngine
        config = llama.get_config('tiny')
        params = llama.init_params(config, jax.random.PRNGKey(0))
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2)
        try:
            kv_bytes = engine._metrics['kv_bytes'].value  # pylint: disable=protected-access
            assert kv_bytes == engine._cache_bytes > 0  # pylint: disable=protected-access
            q = engine.submit([1, 2, 3], 24)
            seen_used = 0.0
            deadline = time.time() + 30
            while time.time() < deadline:
                seen_used = max(
                    seen_used,
                    engine._metrics['kv_used'].value)  # pylint: disable=protected-access
                if q.empty() is False and seen_used > 0:
                    pass
                tok = None
                try:
                    tok = q.get(timeout=0.05)
                except Exception:  # pylint: disable=broad-except
                    continue
                if tok is None:
                    break
            assert seen_used > 0
            # Used never exceeds allocated.
            assert seen_used <= kv_bytes
        finally:
            engine.close()


# ---------------------------------------------------------------------
# Framework callback adapters
# ---------------------------------------------------------------------


class TestFrameworkCallbacks:

    def test_flax_hook_feeds_metrics_and_goodput(self):
        from skypilot_tpu.framework_callbacks import FlaxTrainHook
        hook = FlaxTrainHook(tokens_per_step=128)
        fams = goodput_lib.train_metrics()
        steps_before = fams['steps_total'].value
        tokens_before = fams['tokens_total'].value
        for step in range(3):
            hook.on_step_begin(step)
            time.sleep(0.01)
            hook.on_step_end(step)
        with hook.checkpoint_save():
            time.sleep(0.02)
        assert fams['steps_total'].value == steps_before + 3
        assert fams['tokens_total'].value == tokens_before + 3 * 128
        assert fams['tokens_per_sec'].value > 0
        snap = goodput_lib.accountant().snapshot()
        assert snap['compile'] > 0        # first step
        assert snap['compute'] > 0        # the rest
        assert snap['checkpoint_save'] >= 0.02

    def test_between_bracket_save_not_double_counted(self):
        """A save BETWEEN the adapters' begin->end brackets lands in
        the checkpoint bucket without docking the next brackets'
        compute (the brackets never contained the save time)."""
        from skypilot_tpu.framework_callbacks import FlaxTrainHook
        hook = FlaxTrainHook(tokens_per_step=10)
        hook.on_step_begin(0)
        time.sleep(0.03)
        hook.on_step_end(0)          # compile bracket
        with hook.checkpoint_save():
            time.sleep(0.05)          # outside any bracket
        hook.on_step_begin(1)
        time.sleep(0.03)
        hook.on_step_end(1)          # compute bracket
        snap = goodput_lib.accountant().snapshot()
        assert snap['checkpoint_save'] >= 0.05
        # The compute bracket keeps its full measure — the old
        # carve-from-next-interval accounting zeroed it.
        assert snap['compute'] >= 0.025

    def test_hf_callback_protocol(self):
        from skypilot_tpu.framework_callbacks import SkyTpuHFCallback
        cb = SkyTpuHFCallback(tokens_per_step=64)
        fams = goodput_lib.train_metrics()
        steps_before = fams['steps_total'].value
        # The Trainer calls with (args, state, control) positionals
        # and keyword soup — the adapter must tolerate both.
        cb.on_train_begin(None, None, None, model=None)
        for _ in range(2):
            cb.on_step_begin(None, None, None)
            time.sleep(0.01)
            cb.on_step_end(None, None, None, logs={})
        time.sleep(0.02)
        cb.on_save(None, None, None)
        assert fams['steps_total'].value == steps_before + 2
        snap = goodput_lib.accountant().snapshot()
        assert snap['checkpoint_save'] >= 0.02
        # on_save without a bracketing step end is a no-op.
        before = goodput_lib.accountant().snapshot()['checkpoint_save']
        cb.on_save(None, None, None)
        assert goodput_lib.accountant().snapshot()[
            'checkpoint_save'] == before

    def test_mfu_armed_from_env_chips(self, monkeypatch):
        from skypilot_tpu.framework_callbacks import FlaxTrainHook
        monkeypatch.setenv('SKYTPU_NUM_CHIPS_PER_NODE', '4')
        monkeypatch.setenv('SKYTPU_NUM_NODES', '2')
        hook = FlaxTrainHook(tokens_per_step=1000,
                             param_count=int(1e9))
        acct = goodput_lib.accountant()
        assert acct._n_chips == 8  # pylint: disable=protected-access
        del hook


# ---------------------------------------------------------------------
# xsky top
# ---------------------------------------------------------------------


@pytest.fixture
def two_host_cluster(tmp_path, monkeypatch):
    """Two REAL local agents registered in the state DB as one
    cluster (what `xsky top` scrapes), with host 0 carrying
    published compute series (train/MFU/goodput/HBM/batch)."""
    from skypilot_tpu import state as state_lib
    from skypilot_tpu.backends.backend import ClusterHandle
    from skypilot_tpu.runtime import agent_client
    metrics_dir = str(tmp_path / 'h0-metrics.d')
    procs, hosts = [], []
    for i in range(2):
        port = _free_port()
        env_dir = metrics_dir if i == 0 else \
            str(tmp_path / 'h1-metrics.d')
        monkeypatch.setenv('SKYTPU_METRICS_DIR', env_dir)
        # Liveness anchor: the runtime dir must exist or the agent
        # self-terminates.
        (tmp_path / f'h{i}').mkdir(exist_ok=True)
        procs.append(agent_client.start_local_agent(
            port, runtime_dir=str(tmp_path / f'h{i}')))
        hosts.append({'ip': '127.0.0.1',
                      'external_ip': '127.0.0.1',
                      'agent_port': port,
                      'runtime_dir': str(tmp_path / f'h{i}')})
    monkeypatch.delenv('SKYTPU_METRICS_DIR', raising=False)
    handle = ClusterHandle(
        cluster_name='topfleet', cluster_name_on_cloud='topfleet',
        provider='local', region='local', zone=None,
        launched_resources=None, hosts=hosts)
    for i in range(2):
        handle.agent_client(i).wait_healthy(timeout=15)
    state_lib.add_or_update_cluster('topfleet', handle,
                                    requested_resources=None,
                                    ready=True)
    # Host 0's compute series: train + goodput + MFU + HBM + batch.
    # A PRIVATE registry — the process-global one carries series
    # from other tests, which would pollute the published sums.
    reg = metrics_lib.Registry()
    goodput_lib.train_metrics(reg)['tokens_per_sec'].set(12345.0)
    reg.gauge('skytpu_mfu_ratio', '').set(0.42)
    reg.gauge('skytpu_goodput_ratio', '').set(0.9)
    reg.gauge('skytpu_batch_decode_tokens_per_sec', '').set(777.0)
    reg.gauge('skytpu_batch_slots_occupied', '').set(3)
    reg.gauge('skytpu_batch_slots_total', '').set(8)
    reg.gauge('skytpu_batch_kv_cache_bytes', '').set(1 << 30)
    reg.gauge('skytpu_batch_kv_cache_used_bytes', '').set(1 << 29)
    reg.gauge('skytpu_batch_kv_blocks_used', '').set(5)
    reg.gauge('skytpu_batch_kv_blocks_total', '').set(16)
    reg.counter('skytpu_batch_preemptions_total', '').inc(2)
    device_lib.sample_device_memory(
        [FakeDevice(used=2 << 30, limit=16 << 30, peak=3 << 30)],
        registry=reg)
    pub = publish_lib.MetricsPublisher('train',
                                       directory=metrics_dir,
                                       registry=reg)
    pub.publish_once()
    yield handle
    pub.close()
    for p in procs:
        p.terminate()
    for p in procs:
        p.wait(timeout=5)


class TestXskyTop:

    def test_once_renders_two_host_fleet(self, two_host_cluster):
        from click.testing import CliRunner

        from skypilot_tpu import cli as cli_mod
        from skypilot_tpu.resilience import policy as policy_lib
        # Series other tests on this worker left in the
        # process-global registry: a ``CircuitBreaker(target=...)``
        # built directly exports its state, and ``reset_breakers``
        # (conftest) drops only the series of breakers it registered.
        gauge = policy_lib._breaker_gauge()  # pylint: disable=protected-access
        for labels, _ in list(gauge.collect()):
            target = dict(labels)['target']
            if target not in policy_lib._breakers:  # pylint: disable=protected-access
                gauge.remove(target=target)
        # A driver-side breaker so the breaker line has content.
        policy_lib.breaker_for('10.0.0.9:8790')
        result = CliRunner().invoke(
            cli_mod.cli, ['top', '--once'], catch_exceptions=False)
        assert result.exit_code == 0, result.output
        out = result.output
        # Fleet snapshot: cluster + both hosts.
        assert 'topfleet' in out
        assert out.count('127.0.0.1') >= 2
        # Column content: HBM, train tok/s, MFU, goodput, serve,
        # block-pool utilization/KV, breakers.
        assert 'HBM' in out and '2.0GiB/16.0GiB' in out
        assert '12345' in out
        assert '42.0%' in out and '90.0%' in out
        assert '777' in out
        # Paged-KV block pool replaced the slot-occupancy-only view:
        # used/total blocks + the preemption count.
        assert 'BLOCKS' in out and '5/16' in out
        assert 'PREEMPT' in out
        assert '512.0MiB/1.0GiB' in out
        # The fixture's own AgentClients register per-host breakers
        # too — assert presence + all-closed, not an exact count.
        import re as re_mod
        assert re_mod.search(r'breakers: \d+ \(0 not closed\)', out)

    def test_snapshot_structure_and_quantiles(self,
                                              two_host_cluster):
        from skypilot_tpu.metrics import top as top_lib
        snap = top_lib.snapshot(['topfleet'])
        assert len(snap['clusters']) == 1
        hosts = snap['clusters'][0]['hosts']
        # Same IP for both fake hosts -> merged under one host label
        # is NOT what we want to assert; the scraper labels by ip so
        # both agents share 'host'=127.0.0.1 and rows merge. Assert
        # the merged row carries the published series.
        merged = {k: v for h in hosts for k, v in h.items()}
        assert merged['train_tok_s'] == 12345.0
        assert merged['hbm_limit'] == 16 << 30
        assert merged['kv_bytes'] == 1 << 30

    def test_quantile_from_buckets(self):
        from skypilot_tpu.metrics import top as top_lib
        samples = [
            exposition.Sample('h_bucket', (('le', '0.1'),), 5),
            exposition.Sample('h_bucket', (('le', '1'),), 9),
            exposition.Sample('h_bucket', (('le', '+Inf'),), 10),
        ]
        assert top_lib.quantile_from_buckets(samples, 0.5) == 0.1
        assert top_lib.quantile_from_buckets(samples, 0.9) == 1.0
        assert top_lib.quantile_from_buckets(samples, 0.99) == \
            float('inf')
        assert top_lib.quantile_from_buckets([], 0.5) is None

    def test_unreachable_cluster_degrades(self, tmp_path):
        from skypilot_tpu import state as state_lib
        from skypilot_tpu.backends.backend import ClusterHandle
        from skypilot_tpu.metrics import top as top_lib
        dead = ClusterHandle(
            cluster_name='deadc', cluster_name_on_cloud='deadc',
            provider='local', region='local', zone=None,
            launched_resources=None,
            hosts=[{'ip': '127.0.0.1', 'external_ip': '127.0.0.1',
                    'agent_port': _free_port(),
                    'runtime_dir': str(tmp_path)}])
        state_lib.add_or_update_cluster('deadc', dead,
                                        requested_resources=None,
                                        ready=True)
        snap = top_lib.snapshot(['deadc'], timeout=2)
        # Unreachable hosts degrade to an empty host list (scraper
        # semantics), not an exception.
        assert snap['clusters'][0]['name'] == 'deadc'
        text = top_lib.render(snap)
        assert 'deadc' in text


# ---------------------------------------------------------------------
# Bench profile summaries + `xsky bench diff` op deltas
# ---------------------------------------------------------------------


class TestBenchOpTimeDeltas:

    @staticmethod
    def _run(value, rows):
        return {'metric': 'm_tok_s', 'value': value,
                'unit': 'tokens/s', 'vs_baseline': 1.0,
                'detail': {'op_time_summary': rows}}

    def test_delta_between_best_and_latest(self):
        from skypilot_tpu.benchmark import benchmark_state
        rows_best = [{'name': 'fusion', 'total_ms': 10.0,
                      'count': 2, 'category': 'fusion'}]
        rows_latest = [{'name': 'fusion', 'total_ms': 14.0,
                        'count': 2, 'category': 'fusion'}]
        benchmark_state.record_bench_run(self._run(100.0, rows_best))
        benchmark_state.record_bench_run(
            self._run(90.0, rows_latest))
        deltas = benchmark_state.op_time_delta('m_tok_s')
        assert deltas and deltas[0]['name'] == 'fusion'
        assert deltas[0]['delta_ms'] == pytest.approx(4.0)

    def test_no_delta_without_summaries(self):
        from skypilot_tpu.benchmark import benchmark_state
        benchmark_state.record_bench_run(
            {'metric': 'bare', 'value': 1.0, 'unit': 'tokens/s',
             'vs_baseline': 1.0, 'detail': {}})
        benchmark_state.record_bench_run(
            {'metric': 'bare', 'value': 0.5, 'unit': 'tokens/s',
             'vs_baseline': 1.0, 'detail': {}})
        assert benchmark_state.op_time_delta('bare') is None

    def test_cli_bench_diff_shows_deltas(self):
        from click.testing import CliRunner

        from skypilot_tpu import cli as cli_mod
        from skypilot_tpu.benchmark import benchmark_state
        rows_best = [{'name': 'attn_kernel', 'total_ms': 10.0,
                      'count': 2, 'category': ''}]
        rows_latest = [{'name': 'attn_kernel', 'total_ms': 20.0,
                        'count': 2, 'category': ''}]
        benchmark_state.record_bench_run(self._run(100.0, rows_best))
        benchmark_state.record_bench_run(
            self._run(80.0, rows_latest))
        result = CliRunner().invoke(cli_mod.cli, ['bench', 'diff'])
        # 20% regression -> exit 1, but the deltas still render.
        assert result.exit_code == 1
        assert 'Top op-time deltas for m_tok_s' in result.output
        assert 'attn_kernel' in result.output
        assert '+100.0%' in result.output
