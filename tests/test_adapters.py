"""Multi-tenant LoRA multiplexing (serve/adapters/ + the engine's
mixed-adapter gather path): registry resolution/validation, the
LRU resident set with refcount pinning, async cold-load admission,
and the subsystem's exactness contract — a mixed-adapter batch is
token-for-token what each adapter emits running alone, and
base-model rows match an adapter-less engine exactly.
"""
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.checkpoint.native import NativeCheckpointManager
from skypilot_tpu.models import llama
from skypilot_tpu.serve import prefix_hash
from skypilot_tpu.serve.adapters import (AdapterRegistry,
                                         ResidentAdapterSet)
from skypilot_tpu.serve.batching import BatchingEngine


@pytest.fixture(scope='module')
def setup():
    # Restricted vocab: greedy output loops, so the default-on
    # speculative path actually drafts/accepts during these runs —
    # the exactness tests cover the adapters x speculation
    # composition for free.
    config = dataclasses.replace(llama.get_config('tiny'),
                                 vocab_size=61)
    params = llama.init_params(config, jax.random.PRNGKey(0))
    return config, params


def _shapes(params):
    wq = params['layers']['wq']
    wv = params['layers']['wv']
    if isinstance(wq, dict):
        wq, wv = wq['q'], wv['q']
    return (int(wq.shape[0]), int(wq.shape[1]),
            int(wq.shape[2]), int(wv.shape[2]))


def _write_adapter(base_dir, adapter_id, shapes, rank=4, seed=0,
                   step=1, scale=0.05):
    """One committed native-checkpoint lineage holding a q/v LoRA
    subtree — the artifact the finetune recipe emits."""
    num_layers, dim, q_out, v_out = shapes
    rng = np.random.default_rng(seed)
    factors = {}
    for name, out in (('wq', q_out), ('wv', v_out)):
        factors[f'{name}_a'] = rng.standard_normal(
            (num_layers, dim, rank)).astype(np.float32) * scale
        factors[f'{name}_b'] = rng.standard_normal(
            (num_layers, rank, out)).astype(np.float32) * scale
    mgr = NativeCheckpointManager(
        os.path.join(str(base_dir), adapter_id),
        process_index=0, process_count=1)
    mgr.save(step, {'lora': factors})
    mgr.wait()
    return factors


def _drain(q, timeout=120):
    toks = []
    while True:
        t = q.get(timeout=timeout)
        if t is None:
            return toks
        assert not isinstance(t, BaseException), t
        toks.append(int(t))


# ---------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------


class TestRegistry:

    def test_round_trip_spec_and_host_load(self, setup, tmp_path):
        config, params = setup
        shapes = _shapes(params)
        factors = _write_adapter(tmp_path, 'tenant-a', shapes,
                                 rank=4, seed=1)
        reg = AdapterRegistry(base_dir=str(tmp_path))
        assert reg.list_ids() == ['tenant-a']
        spec = reg.spec('tenant-a')
        assert spec.rank == 4
        assert spec.num_layers == shapes[0]
        assert spec.step == 1
        assert len(spec.content_hash) == 64
        host = reg.load_host('tenant-a')
        assert sorted(host) == ['wq_a', 'wq_b', 'wv_a', 'wv_b']
        np.testing.assert_allclose(host['wq_a'], factors['wq_a'],
                                   rtol=1e-6)
        # DEFAULT_SCALE (alpha/rank) folded into B at host load.
        np.testing.assert_allclose(host['wq_b'],
                                   factors['wq_b'] * 2.0, rtol=1e-6)

    def test_new_step_changes_content_hash(self, setup, tmp_path):
        config, params = setup
        shapes = _shapes(params)
        _write_adapter(tmp_path, 'a', shapes, seed=1, step=1)
        reg = AdapterRegistry(base_dir=str(tmp_path))
        h1 = reg.spec('a').content_hash
        _write_adapter(tmp_path, 'a', shapes, seed=2, step=2)
        spec2 = reg.spec('a')
        assert spec2.step == 2
        assert spec2.content_hash != h1

    def test_unknown_and_escaping_ids_are_typed(self, tmp_path):
        reg = AdapterRegistry(base_dir=str(tmp_path))
        with pytest.raises(exceptions.AdapterNotFoundError):
            reg.spec('nope')
        # Ids are path components; separators must not escape the
        # base dir.
        with pytest.raises(exceptions.AdapterNotFoundError):
            reg.lineage_dir('../outside')
        with pytest.raises(exceptions.AdapterNotFoundError):
            reg.lineage_dir('..')

    def test_empty_lineage_is_not_found(self, tmp_path):
        os.makedirs(tmp_path / 'empty')
        reg = AdapterRegistry(base_dir=str(tmp_path))
        with pytest.raises(exceptions.AdapterNotFoundError):
            reg.spec('empty')

    def test_non_lora_checkpoint_is_manifest_error(self, setup,
                                                   tmp_path):
        # A committed checkpoint that is a MODEL, not an adapter.
        mgr = NativeCheckpointManager(str(tmp_path / 'model'),
                                      process_index=0,
                                      process_count=1)
        mgr.save(1, {'w': np.zeros((2, 2), np.float32)})
        mgr.wait()
        reg = AdapterRegistry(base_dir=str(tmp_path))
        with pytest.raises(exceptions.AdapterManifestError,
                           match='missing'):
            reg.spec('model')

    def test_inconsistent_rank_is_manifest_error(self, setup,
                                                 tmp_path):
        config, params = setup
        num_layers, dim, q_out, v_out = _shapes(params)
        bad = {
            'wq_a': np.zeros((num_layers, dim, 4), np.float32),
            'wq_b': np.zeros((num_layers, 4, q_out), np.float32),
            'wv_a': np.zeros((num_layers, dim, 8), np.float32),
            'wv_b': np.zeros((num_layers, 8, v_out), np.float32),
        }
        mgr = NativeCheckpointManager(str(tmp_path / 'bad'),
                                      process_index=0,
                                      process_count=1)
        mgr.save(1, {'lora': bad})
        mgr.wait()
        reg = AdapterRegistry(base_dir=str(tmp_path))
        with pytest.raises(exceptions.AdapterManifestError,
                           match='rank'):
            reg.spec('bad')

    def test_explicit_registration_outside_base_dir(self, setup,
                                                    tmp_path):
        config, params = setup
        shapes = _shapes(params)
        _write_adapter(tmp_path / 'elsewhere', 'x', shapes)
        reg = AdapterRegistry(base_dir=None)
        reg.register('x', str(tmp_path / 'elsewhere' / 'x'))
        assert reg.spec('x').rank == 4


# ---------------------------------------------------------------------
# Resident set: LRU, pinning, async loads
# ---------------------------------------------------------------------


class TestResidentSet:

    def _resident(self, setup, tmp_path, capacity=2, n=3, bucket=16):
        config, params = setup
        shapes = _shapes(params)
        for i in range(n):
            _write_adapter(tmp_path, f't{i}', shapes,
                           rank=4 + 4 * (i % 2), seed=i)
        reg = AdapterRegistry(base_dir=str(tmp_path))
        return ResidentAdapterSet(reg, capacity, shapes,
                                  rank_bucket=bucket)

    def _load(self, rs, adapter_id, timeout=30):
        rs.ensure_loading(adapter_id)
        deadline = time.time() + timeout
        while time.time() < deadline:
            ready, evicted, _ = rs.poll()
            if adapter_id in ready:
                return evicted
            failure = rs.take_failure(adapter_id)
            assert failure is None, failure
            time.sleep(0.01)
        raise AssertionError(f'{adapter_id} never became resident')

    def test_slots_and_zero_identity(self, setup, tmp_path):
        rs = self._resident(setup, tmp_path)
        assert rs.slot(None) == 0          # base-model slot
        assert rs.slot('t0') is None
        assert self._load(rs, 't0') == []
        assert rs.slot('t0') in (1, 2)
        # Slot 0 stays all-zeros whatever is loaded.
        assert float(jnp.abs(rs.buffers()['wq_a'][:, 0]).max()) == 0

    def test_rank_padding_is_zero_fill(self, setup, tmp_path):
        rs = self._resident(setup, tmp_path, bucket=16)
        self._load(rs, 't0')               # rank 4
        slot = rs.slot('t0')
        a = rs.buffers()['wq_a'][:, slot]
        assert float(jnp.abs(a[..., 4:]).max()) == 0
        assert float(jnp.abs(a[..., :4]).max()) > 0

    def test_lru_evicts_coldest_unpinned(self, setup, tmp_path):
        rs = self._resident(setup, tmp_path, capacity=2, n=3)
        self._load(rs, 't0')
        self._load(rs, 't1')
        # Touch t0 (pin/unpin cycles it to the warm end): t1 is now
        # the coldest and must be the victim.
        rs.pin('t0')
        rs.unpin('t0')
        evicted = self._load(rs, 't2')
        assert evicted == ['t1']
        assert rs.resident_ids() == ['t0', 't2']

    def test_pinned_is_never_evicted(self, setup, tmp_path):
        rs = self._resident(setup, tmp_path, capacity=2, n=3)
        self._load(rs, 't0')
        self._load(rs, 't1')
        rs.pin('t1')                       # in-flight: untouchable
        rs.pin('t0')
        rs.unpin('t0')                     # evictable again
        evicted = self._load(rs, 't2')
        assert evicted == ['t0']
        assert 't1' in rs.resident_ids()

    def test_all_pinned_parks_the_load(self, setup, tmp_path):
        rs = self._resident(setup, tmp_path, capacity=1, n=2)
        self._load(rs, 't0')
        rs.pin('t0')
        rs.ensure_loading('t1')
        deadline = time.time() + 30
        while time.time() < deadline:
            ready, _, _ = rs.poll()
            assert ready == []             # parked, not an error
            if rs.slot('t1') is None and not rs._loading:  # pylint: disable=protected-access
                break
            time.sleep(0.01)
        # The moment the pin drops, the parked load installs.
        rs.unpin('t0')
        ready, evicted, _ = rs.poll()
        assert ready == ['t1'] and evicted == ['t0']

    def test_over_rank_is_capacity_error(self, setup, tmp_path):
        config, params = setup
        shapes = _shapes(params)
        _write_adapter(tmp_path, 'wide', shapes, rank=32)
        reg = AdapterRegistry(base_dir=str(tmp_path))
        rs = ResidentAdapterSet(reg, 2, shapes, rank_bucket=16)
        with pytest.raises(exceptions.AdapterCapacityError,
                           match='rank'):
            rs.check_fits('wide')

    def test_failed_load_surfaces_via_take_failure(self, setup,
                                                   tmp_path):
        rs = self._resident(setup, tmp_path)
        rs.registry.register('ghost', str(tmp_path / 'missing'))
        rs.ensure_loading('ghost')
        deadline = time.time() + 30
        failure = None
        while time.time() < deadline and failure is None:
            rs.poll()
            failure = rs.take_failure('ghost')
            time.sleep(0.01)
        assert isinstance(failure, exceptions.AdapterNotFoundError)

    def test_preload_over_capacity_raises(self, setup, tmp_path):
        rs = self._resident(setup, tmp_path, capacity=2, n=3)
        # All three preloads pin nothing, so the LRU absorbs the
        # overflow silently only for ASYNC loads; the synchronous
        # preload path fits because eviction is allowed...
        rs.preload(['t0', 't1', 't2'])
        assert rs.resident_count() == 2
        # ...but pins block it entirely.
        rs.pin('t1')
        rs.pin('t2')
        with pytest.raises(exceptions.AdapterCapacityError):
            rs.preload(['t0'])


# ---------------------------------------------------------------------
# Engine: mixed-adapter exactness + lifecycle
# ---------------------------------------------------------------------


def _engine(params, config, registry, capacity=4, preload=None,
            **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('max_seq', 96)
    kw.setdefault('steps_per_dispatch', 3)
    kw.setdefault('block_size', 8)
    kw.setdefault('prefill_chunk', 16)
    kw.setdefault('max_num_batched_tokens', 128)
    return BatchingEngine(params, config,
                          adapter_registry=registry,
                          adapter_capacity=capacity,
                          adapter_preload=preload, **kw)


@pytest.fixture(scope='module')
def tenants(setup, tmp_path_factory):
    """Two adapters (different ranks, exercising in-batch rank
    mixing) + a registry over them."""
    config, params = setup
    base = tmp_path_factory.mktemp('adapters')
    shapes = _shapes(params)
    _write_adapter(base, 'tenant-a', shapes, rank=4, seed=1)
    _write_adapter(base, 'tenant-b', shapes, rank=8, seed=2)
    return AdapterRegistry(base_dir=str(base))


class TestEngineExactness:

    PROMPTS = [[7, 3, 9, 4] * 4, [5, 5, 2, 8] * 4, [1, 2, 3, 4] * 4]

    def _solo(self, params, config, registry, prompt, adapter,
              max_new, **kw):
        engine = _engine(params, config, registry,
                         preload=[adapter] if adapter else None,
                         **kw)
        try:
            return _drain(engine.submit(prompt, max_new,
                                        adapter=adapter))
        finally:
            engine.close()

    def test_mixed_batch_matches_each_alone(self, setup, tenants):
        """The tentpole bar: [tenant-a, base, tenant-b] decoding in
        ONE batch — with prefix caching and speculation at their
        defaults (on) — emits per request exactly what a dedicated
        engine emits for that adapter alone. The same prompt rides
        under both adapters, so any cross-adapter KV aliasing in the
        prefix cache would show up as divergence here."""
        config, params = setup
        adapters = ['tenant-a', None, 'tenant-b', 'tenant-b']
        prompts = self.PROMPTS + [self.PROMPTS[0]]
        want = [self._solo(params, config, tenants, p, a, 24)
                for p, a in zip(prompts, adapters)]
        engine = _engine(params, config, tenants,
                         preload=['tenant-a', 'tenant-b'])
        try:
            queues = [engine.submit(p, 24, adapter=a)
                      for p, a in zip(prompts, adapters)]
            got = [_drain(q) for q in queues]
        finally:
            engine.close()
        for i, (w, g) in enumerate(zip(want, got)):
            assert g == w, (i, adapters[i], g, w)
        # Sanity: the adapters actually change the math (otherwise
        # every exactness assert above is vacuous).
        assert want[0] != want[1]

    def test_base_rows_match_adapterless_engine(self, setup,
                                                tenants):
        """An engine with multiplexing ON serves base-model requests
        bit-identically to an engine with the subsystem absent (the
        slot-0 zero gather, and the adapter-less executable)."""
        config, params = setup
        plain = BatchingEngine(params, config, slots=2, max_seq=96,
                               steps_per_dispatch=3, block_size=8,
                               prefill_chunk=16)
        try:
            want = _drain(plain.submit(self.PROMPTS[0], 24))
        finally:
            plain.close()
        engine = _engine(params, config, tenants,
                         preload=['tenant-a'])
        try:
            got = _drain(engine.submit(self.PROMPTS[0], 24))
        finally:
            engine.close()
        assert got == want

    def test_exact_across_preempt_resume(self, setup, tenants):
        """A pool sized to force preemption: the preempted adapter
        request resumes (prompt + generated recompute) and still
        matches its solo run token-for-token.

        The sizing has to hold whatever speculation does. Admission
        gives each 16-token prompt 3 blocks of 8; a row asks for its
        5th block past 32 tokens while the other still holds at
        least 3: 8 > the 7 usable of ``num_blocks=8``, so the pool
        runs dry while both are resident, and one request alone (6
        blocks for 16 + 28 tokens) still fits. Nine usable blocks
        did not force it: at this 61-token vocabulary tenant-b's
        greedy output is one token repeated, its drafts are accepted
        whole (8, 8, 5), and it retires holding 6 blocks while the
        other row still holds 3."""
        config, params = setup
        want = [self._solo(params, config, tenants, p, a, 28)
                for p, a in zip(self.PROMPTS[:2],
                                ['tenant-a', 'tenant-b'])]
        engine = _engine(params, config, tenants,
                         preload=['tenant-a', 'tenant-b'],
                         slots=2, num_blocks=8)
        try:
            queues = [engine.submit(p, 28, adapter=a)
                      for p, a in zip(self.PROMPTS[:2],
                                      ['tenant-a', 'tenant-b'])]
            got = [_drain(q) for q in queues]
            preempted = [e for e in engine.events
                         if e[0] == 'preempt']
        finally:
            engine.close()
        assert got == want
        assert preempted, 'pool never ran dry — the test is not ' \
                          'exercising preempt-resume'


class TestColdLoadAdmission:

    def test_cold_load_admits_and_counts(self, setup, tenants):
        """No preload: the first tenant-a request parks while the
        checkpoint loads on the side thread, then admits and
        completes exactly; the second request hits warm. Metrics and
        events record the load."""
        config, params = setup
        engine = _engine(params, config, tenants, capacity=2)
        try:
            m = engine._adapter_metrics  # pylint: disable=protected-access
            loads0 = m['loads'].value
            req = engine.submit_request(self.prompt(), 16,
                                        adapter='tenant-a')
            got = _drain(req.out)
            assert req.adapter_hit is False    # waited on the load
            warm = engine.submit_request(self.prompt(), 16,
                                         adapter='tenant-a')
            got2 = _drain(warm.out)
            assert warm.adapter_hit is True
            assert got2 == got
            assert m['loads'].value == loads0 + 1
            assert m['resident'].value >= 1
            assert any(e[0] == 'adapter_load' and 'tenant-a' in e[1]
                       for e in engine.events)
        finally:
            engine.close()
        # The cold and warm paths agree with a dedicated engine.
        solo = TestEngineExactness()._solo(  # pylint: disable=protected-access
            params, config, tenants, self.prompt(), 'tenant-a', 16)
        assert got == solo

    def prompt(self):
        return [9, 1, 4, 4] * 4

    def test_unknown_adapter_fails_typed_at_submit(self, setup,
                                                   tenants):
        config, params = setup
        engine = _engine(params, config, tenants)
        try:
            q = engine.submit(self.prompt(), 8, adapter='nope')
            tok = q.get(timeout=30)
            assert isinstance(tok, exceptions.AdapterNotFoundError)
            assert q.get(timeout=30) is None
        finally:
            engine.close()

    def test_over_rank_adapter_fails_typed(self, setup, tenants,
                                           tmp_path):
        config, params = setup
        shapes = _shapes(params)
        _write_adapter(tmp_path, 'wide', shapes, rank=32)
        reg = AdapterRegistry(base_dir=str(tmp_path))
        engine = _engine(params, config, reg, capacity=2)
        try:
            q = engine.submit(self.prompt(), 8, adapter='wide')
            tok = q.get(timeout=30)
            assert isinstance(tok, exceptions.AdapterCapacityError)
        finally:
            engine.close()

    def test_adapterless_engine_refuses_adapters(self, setup):
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=96,
                                steps_per_dispatch=3, block_size=8)
        try:
            q = engine.submit(self.prompt(), 8, adapter='any')
            tok = q.get(timeout=30)
            assert isinstance(tok, exceptions.AdapterCapacityError)
        finally:
            engine.close()

    def test_failed_cold_load_fails_the_waiter(self, setup,
                                               tenants, tmp_path):
        """check_fits passes (the spec reads fine at submit) but the
        shard files vanish before the async load: the parked request
        gets a typed AdapterError, not a hang."""
        import shutil

        config, params = setup
        shapes = _shapes(params)
        _write_adapter(tmp_path, 'doomed', shapes, rank=4)
        reg = AdapterRegistry(base_dir=str(tmp_path))
        engine = _engine(params, config, reg, capacity=2)
        try:
            reg.spec('doomed')             # prime the spec cache
            shutil.rmtree(tmp_path / 'doomed')
            q = engine.submit(self.prompt(), 8, adapter='doomed')
            tok = q.get(timeout=60)
            assert isinstance(tok, exceptions.AdapterError), tok
        finally:
            engine.close()


class TestReplicaE2E:

    def test_cold_load_admission_through_serve_model(
            self, setup, tmp_path, monkeypatch):
        """A REAL serve_model replica (random-init tiny, batching
        engine on): the first adapter POST cold-loads and answers
        with X-Skytpu-Adapter-Loads: 1, the repeat answers Hits: 1,
        an unknown adapter answers 404 — the full HTTP body ->
        engine submit -> adapter-wait -> admission path."""
        import http.client
        import json as json_mod
        import socket
        import sys
        import threading

        from skypilot_tpu.recipes import serve_model

        config, params = setup
        _write_adapter(tmp_path, 'tenant-e2e', _shapes(params),
                       rank=4, seed=7)
        sock = socket.socket()
        sock.bind(('127.0.0.1', 0))
        port = sock.getsockname()[1]
        sock.close()
        monkeypatch.setattr(sys, 'argv', [
            'serve_model', '--model', 'tiny', '--port', str(port),
            '--slots', '2', '--adapter-dir', str(tmp_path),
            '--adapter-capacity', '2'])
        # main() never returns; the daemon thread dies with the
        # test process (the replica has no shutdown RPC by design).
        threading.Thread(target=serve_model.main,
                         daemon=True).start()

        def request(method, path, body=None):
            conn = http.client.HTTPConnection('127.0.0.1', port,
                                              timeout=120)
            try:
                conn.request(method, path,
                             body=json_mod.dumps(body)
                             if body else None)
                resp = conn.getresponse()
                return (resp.status, dict(resp.getheaders()),
                        json_mod.loads(resp.read() or b'{}'))
            finally:
                conn.close()

        deadline = time.time() + 300
        while True:
            try:
                status, _, _ = request('GET', '/')
                if status == 200:
                    break
            except OSError:
                pass
            assert time.time() < deadline, 'replica never ready'
            time.sleep(1.0)

        body = {'prompt_ids': [5, 9, 2, 7] * 4,
                'max_new_tokens': 8, 'adapter': 'tenant-e2e'}
        status, headers, out = request('POST', '/generate', body)
        assert status == 200, out
        assert out['output_ids']
        assert headers[prefix_hash.ADAPTER_LOADS_HEADER] == '1'
        assert headers[prefix_hash.ADAPTER_HITS_HEADER] == '0'
        status, headers, warm = request('POST', '/generate', body)
        assert status == 200
        assert headers[prefix_hash.ADAPTER_HITS_HEADER] == '1'
        # Same adapter, same prompt: deterministic greedy output.
        assert warm['output_ids'] == out['output_ids']
        # Base requests carry no adapter headers at all.
        status, headers, base = request(
            'POST', '/generate', {'prompt_ids': [5, 9, 2, 7] * 4,
                                  'max_new_tokens': 8})
        assert status == 200
        assert prefix_hash.ADAPTER_HITS_HEADER not in headers
        assert base['output_ids'] != out['output_ids']
        status, _, err = request(
            'POST', '/generate', dict(body, adapter='ghost'))
        assert status == 404, err
        status, _, err = request(
            'POST', '/generate', dict(body, adapter='../escape'))
        assert status == 404, err


# ---------------------------------------------------------------------
# Prefix isolation + routing
# ---------------------------------------------------------------------


class TestAdapterPrefixIsolation:

    def test_adapter_root_salts_chains(self):
        toks = list(range(64))
        base = prefix_hash.chain_hashes(toks, 16)
        a = prefix_hash.chain_hashes(
            toks, 16, root=prefix_hash.adapter_root('a'))
        b = prefix_hash.chain_hashes(
            toks, 16, root=prefix_hash.adapter_root('b'))
        # Same tokens, three disjoint chains — cross-tenant KV can
        # never alias by construction.
        assert len({base[0], a[0], b[0]}) == 3
        assert prefix_hash.adapter_root(None) == prefix_hash.ROOT
        assert prefix_hash.adapter_root('a') == \
            prefix_hash.adapter_root('a')

    def test_request_prefix_key_includes_adapter(self):
        import json as json_mod

        from skypilot_tpu.serve import load_balancer as lb
        ids = list(range(80))
        base_key = lb.request_prefix_key(
            json_mod.dumps({'prompt_ids': ids}).encode())
        a_key = lb.request_prefix_key(
            json_mod.dumps({'prompt_ids': ids,
                            'adapter': 'a'}).encode())
        b_key = lb.request_prefix_key(
            json_mod.dumps({'prompt_ids': ids,
                            'adapter': 'b'}).encode())
        assert len({base_key, a_key, b_key}) == 3
        # Short adapter prompts still route by adapter (affinity to
        # wherever the adapter is warm); short base prompts stay
        # keyless (least-load).
        assert lb.request_prefix_key(
            json_mod.dumps({'prompt_ids': [1, 2],
                            'adapter': 'a'}).encode()) == \
            prefix_hash.adapter_root('a')
        assert lb.request_prefix_key(
            json_mod.dumps({'prompt_ids': [1, 2]}).encode()) is None

    def test_adapter_keys_rendezvous_and_survive_drain(self):
        """Adapter-rooted keys behave like any rendezvous key: a
        drained endpoint's tenants re-target, everyone else's
        placement is undisturbed (no full reshuffle on drain)."""
        from skypilot_tpu.serve.load_balancer import \
            PrefixAffinityPolicy
        policy = PrefixAffinityPolicy()
        eps = [f'http://10.0.0.{i}:8080' for i in range(4)]
        keys = {t: prefix_hash.adapter_root(f'tenant-{t}')
                for t in range(32)}
        owners = {t: policy.select(eps, key=k)
                  for t, k in keys.items()}
        assert len(set(owners.values())) == len(eps)
        gone = eps[2]
        rest = [e for e in eps if e != gone]
        for t, k in keys.items():
            moved = policy.select(rest, key=k)
            if owners[t] != gone:
                assert moved == owners[t]
            else:
                assert moved in rest


# ---------------------------------------------------------------------
# Spec knobs + HTTP error mapping
# ---------------------------------------------------------------------


class TestAdapterKnobs:

    def test_round_trip_and_env(self):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        spec = SkyServiceSpec.from_yaml_config({
            'engine': {'adapters': {'dir': '~/adapters',
                                    'capacity': 4,
                                    'preload': ['a', 'b']}},
        })
        assert spec.engine_adapter_capacity == 4
        out = spec.to_yaml_config()
        assert out['engine']['adapters'] == {
            'dir': '~/adapters', 'capacity': 4,
            'preload': ['a', 'b']}
        env = SkyServiceSpec.from_yaml_config(out).engine_env()
        assert env['SKYTPU_ENGINE_ADAPTER_DIR'] == '~/adapters'
        assert env['SKYTPU_ENGINE_ADAPTER_CAPACITY'] == '4'
        assert env['SKYTPU_ENGINE_ADAPTER_PRELOAD'] == 'a,b'
        bare = SkyServiceSpec.from_yaml_config({})
        assert bare.engine_adapter_dir is None
        assert 'SKYTPU_ENGINE_ADAPTER_DIR' not in bare.engine_env()

    def test_validation(self):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        with pytest.raises(exceptions.InvalidSpecError):
            # dir without capacity: half a configuration.
            SkyServiceSpec(engine_adapter_dir='/x')
        with pytest.raises(exceptions.InvalidSpecError):
            SkyServiceSpec(engine_adapter_dir='/x',
                           engine_adapter_capacity=0)
        with pytest.raises(exceptions.InvalidSpecError):
            SkyServiceSpec(engine_adapter_dir='/x',
                           engine_adapter_capacity=2,
                           engine_adapter_preload=['a', 'b', 'c'])
        with pytest.raises(exceptions.InvalidSpecError):
            # Commas would corrupt the comma-joined env stamp.
            SkyServiceSpec(engine_adapter_dir='/x',
                           engine_adapter_capacity=2,
                           engine_adapter_preload=['a,b'])

    def test_schema_fields(self):
        from skypilot_tpu.utils import schemas
        props = schemas.SERVICE_SCHEMA['properties']['engine'][
            'properties']['adapters']['properties']
        assert props['capacity'] == {'type': 'integer',
                                     'minimum': 1}
        assert set(props) == {'dir', 'capacity', 'preload'}

    def test_http_error_mapping(self):
        """The replica's typed-error translation (serve_model's
        Handler is nested in main(), so this is a source-level
        wiring check): adapter refusals answer 404/413 and are
        checked BEFORE the overload branches — client-shaped
        errors must never trip the 5xx page."""
        import inspect

        from skypilot_tpu.recipes import serve_model
        src = inspect.getsource(serve_model)
        body = src.split('def _engine_error', 1)[1]
        body = body.split('def ', 1)[0]
        nf = body.index('AdapterNotFoundError')
        cap = body.index('AdapterCapacityError')
        over = body.index('EngineOverloadedError')
        assert nf < cap < over
        assert '404' in body[nf:cap]
        assert '413' in body[cap:over]


# ---------------------------------------------------------------------
# xsky top rendering
# ---------------------------------------------------------------------


class TestTopAdaptersColumn:

    def test_host_and_service_cells(self):
        from skypilot_tpu.metrics import top as top_lib
        snap = {
            'at': time.time(),
            'clusters': [{'name': 'c', 'status': 'UP',
                          'alerts_firing': 0,
                          'hosts': [
                              {'host': 'h0', 'adapters_resident': 3,
                               'adapters_capacity': 8},
                              {'host': 'h1'}]}],
            'services': [{'name': 's', 'status': 'READY',
                          'adapter_hit_ratio': 0.75,
                          'alerts_firing': 0},
                         {'name': 'plain', 'status': 'READY',
                          'alerts_firing': 0}],
            'alerts': [], 'breakers': [], 'watchdogs': [],
        }
        text = top_lib.render(snap)
        assert 'ADAPTERS' in text and 'ADPT-HIT%' in text
        assert '3/8' in text           # resident/capacity
        assert '75.0%' in text         # warm-hit ratio
        # Hosts/services without the gauges degrade to '-'.
        h1_row = next(l for l in text.splitlines() if ' h1 ' in l)
        assert '3/8' not in h1_row


# ---------------------------------------------------------------------
# Alert rule wiring
# ---------------------------------------------------------------------


class TestAdapterThrashRule:

    def test_rule_shape(self):
        from skypilot_tpu.alerts import builtin
        rule = {r.id: r for r in builtin.fleet_rules()}[
            'adapter-thrash']
        assert rule.metric == 'skytpu_batch_adapter_evictions_total'
        assert rule.kind == 'rate'
