"""Paged KV cache: block-pool allocator, index math, token-budget
admission, chunked prefill, preempt-and-requeue, typed pool
exhaustion, and the paged-engine numerics contract (serve/kv_pool.py,
ops/decode_attention.py, serve/batching.py, the paged steps of
models/decode.py)."""
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.models import decode, llama
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.batching import BatchingEngine


@pytest.fixture(scope='module')
def setup():
    config = llama.get_config('tiny')
    params = llama.init_params(config, jax.random.PRNGKey(0))
    return config, params


def _reference(params, config, prompt_ids, max_new, max_seq=64,
               kv_int8=False):
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    out = decode.greedy_generate(params, prompt, config,
                                 max_new_tokens=max_new,
                                 max_seq=max_seq, kv_int8=kv_int8)
    return [int(t) for t in out[0]]


# ---------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------


class TestKVBlockPool:

    def test_alloc_free_roundtrip(self, setup):
        config, _ = setup
        pool = kv_pool.KVBlockPool(config, num_blocks=9, block_size=8)
        assert pool.usable_blocks == 8
        assert pool.free_blocks == 8
        a = pool.alloc(3)
        b = pool.alloc(5)
        assert pool.free_blocks == 0
        assert pool.used_blocks == 8
        # Block 0 (scratch) is never handed out.
        assert kv_pool.SCRATCH_BLOCK not in a + b
        assert sorted(a + b) == list(range(1, 9))
        pool.free(a)
        assert pool.free_blocks == 3
        pool.free(b)
        assert pool.free_blocks == 8

    def test_try_alloc_exhaustion_is_atomic(self, setup):
        config, _ = setup
        pool = kv_pool.KVBlockPool(config, num_blocks=4, block_size=8)
        assert pool.try_alloc(4) is None      # only 3 usable
        assert pool.free_blocks == 3          # nothing leaked
        got = pool.try_alloc(3)
        assert len(got) == 3

    def test_alloc_raises_typed(self, setup):
        config, _ = setup
        pool = kv_pool.KVBlockPool(config, num_blocks=3, block_size=8)
        with pytest.raises(exceptions.KVPoolExhaustedError):
            pool.alloc(5)

    def test_double_free_rejected(self, setup):
        config, _ = setup
        pool = kv_pool.KVBlockPool(config, num_blocks=4, block_size=8)
        got = pool.alloc(1)
        pool.free(got)
        with pytest.raises(ValueError):
            pool.free(got)
        with pytest.raises(ValueError):
            pool.free([kv_pool.SCRATCH_BLOCK])

    def test_int8_pool_has_scales_and_bytes(self, setup):
        config, _ = setup
        pool = kv_pool.KVBlockPool(config, num_blocks=4, block_size=8,
                                   kv_int8=True)
        k, v, ks, vs = pool.caches
        assert k.dtype == jnp.int8 and v.dtype == jnp.int8
        assert ks.dtype == jnp.bfloat16 and vs.dtype == jnp.bfloat16
        assert pool.nbytes == sum(int(c.nbytes) for c in pool.caches)
        assert pool.block_bytes * pool.num_blocks == pool.nbytes


    def test_a_module_adds_an_entry_to_the_latent_group(self):
        """A next-token-prediction module's layer keeps a cache
        entry of its own, the latent group's last: n_layers + 1
        entries, the bytes a token costs with it."""
        plain = llama.get_config('tiny-latent-moe')
        mtp = llama.get_config('tiny-latent-mtp')
        pools = {c.name: kv_pool.KVBlockPool(c, 8, 4)
                 for c in (plain, mtp)}
        rows = pools[mtp.name].caches[0]
        assert rows.shape[0] == mtp.n_layers + 1 == mtp.kv_entries
        assert pools[plain.name].caches[0].shape[0] == plain.n_layers
        assert pools[mtp.name].token_bytes * plain.n_layers == \
            pools[plain.name].token_bytes * (mtp.n_layers + 1)


class TestIndexMath:

    def test_read_indices_flatten_blocks(self):
        bt = jnp.asarray([[3, 1, 0], [2, 0, 0]], jnp.int32)
        got = da.read_indices(bt, 4)
        want = [[12, 13, 14, 15, 4, 5, 6, 7, 0, 1, 2, 3],
                [8, 9, 10, 11, 0, 1, 2, 3, 0, 1, 2, 3]]
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_write_index_and_overrun_scratch(self):
        bt = jnp.asarray([[3, 1], [2, 0]], jnp.int32)
        pos = jnp.asarray([5, 2], jnp.int32)   # row0 block1 off1
        got = da.write_index(bt, pos, 4)
        np.testing.assert_array_equal(np.asarray(got), [4 + 1, 8 + 2])
        # Positions past the table capacity park in scratch.
        over = da.write_index(bt, jnp.asarray([8, 9], jnp.int32),
                                   4)
        np.testing.assert_array_equal(np.asarray(over), [0, 0])

    def test_chunk_write_indices_pad_to_scratch(self):
        row = jnp.asarray([5, 2], jnp.int32)
        got = da.chunk_write_indices(
            row, jnp.asarray(3, jnp.int32), jnp.asarray(2, jnp.int32),
            chunk=4, block_size=4)
        # start=3: positions 3,4 real -> block5 off3, block2 off0;
        # padded positions -> scratch slot 0.
        np.testing.assert_array_equal(np.asarray(got),
                                      [23, 8, 0, 0])


# ---------------------------------------------------------------------
# Paged engine numerics (the contract the tentpole must not bend)
# ---------------------------------------------------------------------


class TestPagedNumerics:

    def test_chunked_prefill_matches_single_stream(self, setup):
        """A prompt spanning several prefill chunks AND several KV
        blocks must decode token-for-token like the plain
        single-request path."""
        config, params = setup
        prompt = [(i * 7) % 250 + 1 for i in range(40)]
        want = _reference(params, config, prompt, 10)
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=3, block_size=8,
                                prefill_chunk=8,
                                max_num_batched_tokens=16)
        try:
            got = engine.generate(prompt, 10)
            assert got == want, (got, want)
        finally:
            engine.close()

    def test_int8_kv_paged_matches_int8_plain(self, setup):
        """int8-KV paged engine == int8-KV single-request greedy,
        EXACTLY: quantization is per-(position, head), so the paged
        layout changes nothing about the codes or scales."""
        config, params = setup
        cases = [([1, 2, 3], 7), ([9, 8, 7, 6, 2], 6), ([5, 4], 8)]
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2, kv_int8=True)
        try:
            queues = [engine.submit(p, m) for p, m in cases]
            for (prompt, max_new), q in zip(cases, queues):
                toks = []
                while True:
                    t = q.get(timeout=120)
                    if t is None:
                        break
                    assert not isinstance(t, BaseException), t
                    toks.append(t)
                want = _reference(params, config, prompt, max_new,
                                  kv_int8=True)
                assert toks == want, (prompt, toks, want)
        finally:
            engine.close()

    def test_moe_paged_below_capacity(self):
        """MoE config with capacity slack: paged engine must equal
        single-request greedy (routing is per token; paged storage
        is invisible to the expert dispatch)."""
        config = llama.get_config('tiny-moe')
        params = llama.init_params(config, jax.random.PRNGKey(0))
        prompt = [7, 3, 5, 11, 2]
        want = _reference(params, config, prompt, 6)
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2, block_size=8)
        try:
            got = engine.generate(prompt, 6)
            assert got == want, (got, want)
        finally:
            engine.close()

    @pytest.mark.parametrize('kv_int8', [False, True],
                             ids=['bf16', 'int8'])
    def test_paged_rows_match_uniform_decode(self, setup, kv_int8):
        """``decode_steps_paged`` at per-row positions, through
        tables that lay the dense cache's rows out contiguously,
        against ``forward_cached``'s shared-position decode: at
        EQUAL positions the two must pick the same tokens (bf16), or
        track them (int8 KV is lossy against the bf16 reference)."""
        config, params = setup
        prompts = jnp.asarray([[1, 2, 3, 4], [9, 8, 7, 6]], jnp.int32)
        want = decode.greedy_generate(params, prompts, config,
                                      max_new_tokens=5, max_seq=32)
        cache = decode.init_cache(config, 2, max_seq=32,
                                  kv_int8=kv_int8)
        logits, cache = decode.forward_cached(params, prompts, cache,
                                              config, True)
        first = logits[:, -1].argmax(-1).astype(jnp.int32)
        # Row b's [32] slab becomes blocks [b*4+1 .. b*4+4] of 8
        # (block 0 stays scratch).
        bs, per_row = 8, 4

        def as_pool(slab):
            if slab is None:
                return None
            blocks = slab.reshape(slab.shape[0], 2 * per_row, bs,
                                  *slab.shape[3:])
            return jnp.concatenate(
                [jnp.zeros_like(blocks[:, :1]), blocks], axis=1)

        pools = tuple(as_pool(c) for c in (
            cache.k, cache.v, cache.k_scale, cache.v_scale))
        tables = 1 + jnp.arange(2 * per_row, dtype=jnp.int32).reshape(
            2, per_row)
        toks, pools, pos = decode.decode_steps_paged(
            params, first, pools, tables,
            jnp.asarray([4, 4], jnp.int32), jnp.asarray([True, True]),
            config, 4, bs)
        np.testing.assert_array_equal(np.asarray(pos), [8, 8])
        got = np.asarray(jnp.concatenate([first[:, None], toks],
                                         axis=1))
        if not kv_int8:
            np.testing.assert_array_equal(got, np.asarray(want))
            return
        assert pools[0].dtype == jnp.int8
        # On a random-init model the int8-vs-bf16 logit gap at the
        # argmax is often within one quantization step, so the
        # winning token can flip on BLAS/thread-count differences
        # and one early flip then diverges the whole row: a LOOSE
        # agreement, not token equality, which is a property of
        # trained models with real logit margins.
        assert (got == np.asarray(want)).mean() >= 1 / 3, (got, want)
        assert np.all((got >= 0) & (got < config.vocab_size))


# ---------------------------------------------------------------------
# Admission, preemption, typed failure
# ---------------------------------------------------------------------


class TestPoolPressure:

    def test_preempt_and_requeue_preserves_tokens(self, setup):
        """A pool too small for the concurrent mix must preempt (not
        deadlock, not fail unrelated requests) and still produce
        token-for-token-correct output for EVERY request."""
        config, params = setup
        # 6 usable blocks of 8 = 48 token-slots; three requests that
        # want ~(5+12)+1 tokens each cannot all fit once they grow.
        engine = BatchingEngine(params, config, slots=3, max_seq=64,
                                steps_per_dispatch=4, block_size=8,
                                num_blocks=7)
        try:
            cases = [([1, 2, 3, 4, 5], 12), ([6, 7, 8, 9, 1], 12),
                     ([2, 4, 6, 8, 3], 12)]
            queues = [engine.submit(p, m) for p, m in cases]
            for (prompt, max_new), q in zip(cases, queues):
                toks = []
                while True:
                    t = q.get(timeout=120)
                    if t is None:
                        break
                    assert not isinstance(t, BaseException), t
                    toks.append(t)
                assert toks == _reference(params, config, prompt,
                                          max_new), prompt
            assert engine.pool.free_blocks == engine.pool.usable_blocks
        finally:
            engine.close()

    def test_oversized_request_fails_typed_not_fail_all(self, setup):
        """A request the pool can NEVER hold fails alone with
        KVPoolExhaustedError; a concurrent request keeps decoding to
        completion (the engine must never _fail_all on pool
        exhaustion)."""
        config, params = setup
        # usable = 2 blocks of 8 = 16 token-slots; max_seq 64 allows
        # submitting prompts the pool can never hold.
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2, block_size=8,
                                num_blocks=3)
        try:
            ok_q = engine.submit([1, 2, 3], 4)
            with pytest.raises(exceptions.KVPoolExhaustedError):
                engine.generate(list(range(1, 41)), 8)
            toks = []
            while True:
                t = ok_q.get(timeout=120)
                if t is None:
                    break
                assert not isinstance(t, BaseException), t
                toks.append(t)
            assert toks == _reference(params, config, [1, 2, 3], 4)
            # The engine loop is still alive and serving.
            assert engine.generate([5, 6], 3) == _reference(
                params, config, [5, 6], 3)
        finally:
            engine.close()

    def test_growth_failure_in_decode_is_typed(self, setup):
        """A lone request that outgrows the whole pool mid-decode
        (admission fit, growth cannot) fails typed, not hang."""
        config, params = setup
        # usable = 2 blocks of 8 = 16 slots; prompt 12 admits
        # (needs 2 blocks) but position 16 can never be written.
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=4, block_size=8,
                                num_blocks=3)
        try:
            with pytest.raises(exceptions.KVPoolExhaustedError):
                engine.generate(list(range(1, 13)), 20)
        finally:
            engine.close()

    def test_churn_leaves_zero_leaked_blocks(self, setup):
        """Admit/retire >= 100 mixed-length requests through a small
        pool: every request completes (no preemption starvation) and
        every block is free at the end."""
        config, params = setup
        engine = BatchingEngine(params, config, slots=4, max_seq=64,
                                steps_per_dispatch=4, block_size=8,
                                num_blocks=13,
                                max_num_batched_tokens=32)
        rng = np.random.default_rng(7)
        try:
            queues = []
            for i in range(100):
                plen = int(rng.integers(1, 30))
                prompt = [int(x) for x in
                          rng.integers(1, config.vocab_size,
                                       size=plen)]
                max_new = int(rng.integers(1, 6))
                queues.append((engine.submit(prompt, max_new),
                               max_new))
            for i, (q, max_new) in enumerate(queues):
                toks = []
                while True:
                    t = q.get(timeout=300)
                    if t is None:
                        break
                    assert not isinstance(t, BaseException), (i, t)
                    toks.append(t)
                assert 1 <= len(toks) <= max_new, (i, toks)
            deadline = time.time() + 10
            while engine.pool.free_blocks != \
                    engine.pool.usable_blocks and \
                    time.time() < deadline:
                time.sleep(0.05)
            assert engine.pool.free_blocks == \
                engine.pool.usable_blocks, 'leaked KV blocks'
            assert all(not b for b in engine.slot_blocks)
        finally:
            engine.close()


# ---------------------------------------------------------------------
# Chunked-prefill interleaving (the p99-TTFT lever)
# ---------------------------------------------------------------------


class TestChunkedPrefillInterleaving:

    def test_decode_dispatches_between_prompt_chunks(self, setup):
        """While a long prompt prefills chunk by chunk, decode
        dispatches for already-running requests must land BETWEEN
        its chunks — one 8k prompt must not stall every in-flight
        decode."""
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2, block_size=8,
                                prefill_chunk=8,
                                max_num_batched_tokens=8)
        try:
            # A short request first, decoding for a while.
            q_short = engine.submit([1, 2, 3], 20)
            first_short = q_short.get(timeout=120)  # admitted,
            #                                         decoding
            # Now a long prompt: 40 tokens = 5 chunks of 8, budget 8
            # = one chunk per scheduler iteration.
            long_prompt = [(i * 3) % 250 + 1 for i in range(40)]
            q_long = engine.submit(long_prompt, 4)
            outs = {'short': [first_short]}
            for name, q in (('short', q_short), ('long', q_long)):
                toks = outs.setdefault(name, [])
                while True:
                    t = q.get(timeout=120)
                    if t is None:
                        break
                    assert not isinstance(t, BaseException), t
                    toks.append(t)
            # BOTH requests' outputs must survive the interleaving
            # token-for-token — in particular, the decode dispatches
            # running BETWEEN the long prompt's chunks must not
            # touch its already-prefilled blocks (parked lanes write
            # to scratch, not position 0 of their first block).
            assert outs['long'] == _reference(params, config,
                                              long_prompt, 4)
            assert outs['short'] == _reference(params, config,
                                               [1, 2, 3], 20)
            events = list(engine.events)
            # Identify the long request's prefill chunks: total==40.
            chunk_idx = [i for i, e in enumerate(events)
                         if e[0] == 'prefill_chunk' and e[3] == 40]
            assert len(chunk_idx) == 5, events
            decode_between = [
                i for i, e in enumerate(events)
                if e[0] == 'decode'
                and chunk_idx[0] < i < chunk_idx[-1]]
            assert decode_between, (
                'no decode dispatch interleaved with the long '
                f'prompt\'s prefill chunks: {events}')
            # And the interleaving preserved both outputs' numerics:
            assert engine.generate([1, 2, 3], 5) == _reference(
                params, config, [1, 2, 3], 5)
        finally:
            engine.close()


# ---------------------------------------------------------------------
# Metrics + lint satellites
# ---------------------------------------------------------------------


class TestBlockGauges:

    def test_blocks_total_used_and_preemptions(self, setup):
        config, params = setup
        engine = BatchingEngine(params, config, slots=2, max_seq=64,
                                steps_per_dispatch=2, block_size=8)
        try:
            m = engine._metrics  # pylint: disable=protected-access
            assert m['kv_blocks_total'].value == \
                engine.pool.usable_blocks > 0
            seen_used = 0.0
            q = engine.submit([1, 2, 3, 4], 16)
            while True:
                t = q.get(timeout=120)
                seen_used = max(seen_used, m['kv_blocks_used'].value)
                if t is None:
                    break
            assert seen_used >= 1
            # kv_cache_used_bytes is real block accounting now —
            # gauges refresh once per scheduler iteration, so wait
            # for the post-retirement sweep.
            want = (engine.pool.used_blocks *
                    engine.pool.block_bytes)
            deadline = time.time() + 10
            while m['kv_used'].value != want and \
                    time.time() < deadline:
                time.sleep(0.05)
                want = (engine.pool.used_blocks *
                        engine.pool.block_bytes)
            assert m['kv_used'].value == want
        finally:
            engine.close()


class TestNoFullSlabKVAllocationLint:
    """The serve data plane must not allocate full per-slot KV slabs
    ([L, B, S, ...]-style jnp.zeros over n_layers) anywhere outside
    the block pool — that is exactly the fragmentation the paged
    rebuild removed. models/decode.init_cache (the single-request
    path) is intentionally out of scope."""

    def test_no_layer_kv_zeros_outside_kv_pool(self):
        import skypilot_tpu
        serve_dir = os.path.join(
            os.path.dirname(skypilot_tpu.__file__), 'serve')
        offenders = []
        for fn in sorted(os.listdir(serve_dir)):
            if not fn.endswith('.py') or fn == 'kv_pool.py':
                continue
            text = open(os.path.join(serve_dir, fn),
                        encoding='utf-8').read()
            for match in re.finditer(r'jnp\.zeros\(', text):
                window = text[match.start():match.start() + 200]
                if 'n_layers' in window:
                    line = text[:match.start()].count('\n') + 1
                    offenders.append(f'{fn}:{line}')
        assert not offenders, (
            'full-slab KV allocation outside serve/kv_pool.py '
            f'(use the block pool): {offenders}')


class TestServeContinuousBench:

    @pytest.mark.slow
    def test_paged_beats_static_on_open_loop_load(self, tmp_path,
                                                  monkeypatch):
        """The acceptance bench: mixed short/long open-loop load,
        paged vs static-slot arms at equal KV HBM and decode width —
        paged must win tokens/s AND p99 TTFT, and the row must land
        in bench_runs where --assert-no-regress sees it."""
        import importlib.util
        import skypilot_tpu
        root = os.path.dirname(os.path.dirname(
            skypilot_tpu.__file__))
        spec = importlib.util.spec_from_file_location(
            'bench', os.path.join(root, 'bench.py'))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        monkeypatch.setenv('SKYTPU_STATE_DIR', str(tmp_path))
        result = bench.serve_continuous_main()
        assert result['unit'] == 'tokens/s'
        detail = result['detail']
        assert detail['tokens_per_sec_speedup'] > 1.0, detail
        assert detail['p99_ttft_speedup'] > 1.0, detail
        assert detail['paged']['tokens'] == \
            detail['static']['tokens']
        from skypilot_tpu.benchmark import benchmark_state
        run_id = benchmark_state.record_bench_run(result)
        assert run_id is not None
        assert not benchmark_state.check_regression(result)
        rows = benchmark_state.bench_diff()
        assert any(r['metric'] == result['metric'] for r in rows)
