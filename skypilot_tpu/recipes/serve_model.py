"""Model serving replica (stdlib HTTP).

Port of the reference's serving recipes (``llm/vllm/service.yaml``,
JetStream on v6e): a replica process exposing ``/`` (readiness) and
``/generate`` (greedy, sampled and grammar-constrained decode — the
latter two on the batching engine only) over the in-tree Llama
implementation.
Runs under ``x serve up`` — the service spec's port arrives via
``SKYTPU_REPLICA_PORT``.

    python -m skypilot_tpu.recipes.serve_model --model tiny
"""
import argparse
import contextlib
import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from skypilot_tpu import trace as trace_lib


def main():
    # The start-up log's stages (docs/observability.md, "Start-up
    # and compilation") are entered on this stack; ``_serve`` closes
    # it at ``ready``, and a start-up that raises closes it here.
    with contextlib.ExitStack() as starting:
        _serve(starting)


def _serve(starting: contextlib.ExitStack):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        '--model', default='tiny',
        help='a preset of models/llama.py (CONFIGS). Stacks only the '
             'batching engine runs need --slots N: ouro-2.6b (looped), '
             'command-a-plus (window and global layers, a share of '
             'the experts) and xing4.0-29b-a4b (latent attention, '
             'four residual streams, dense layers before the expert '
             'layers: bf16 latent cache, so no --kv-int8) and '
             'joyai-llm-flash (latent attention and a '
             'next-token-prediction module: --speculative mtp makes '
             'it the drafter)')
    parser.add_argument('--port', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_REPLICA_PORT', '8080')))
    parser.add_argument('--max-new-tokens', type=int, default=32)
    parser.add_argument('--tp', type=int, default=1,
                        help='tensor-parallel degree for models too '
                             'big for one chip (shards params + KV '
                             'cache over the tp mesh axis)')
    parser.add_argument('--quant', choices=['none', 'int8'],
                        default='none',
                        help='weight-only quantization (halves '
                             'decode weight bandwidth)')
    parser.add_argument('--kv-int8', action='store_true',
                        help='int8 KV cache for the batching engine '
                             '(halves decode HBM traffic; measured '
                             'TPOT 24.8->16.6 ms at S=4.6k b=16 on '
                             'v5e)')
    parser.add_argument('--slots', type=int, default=0,
                        help='enable continuous batching with this '
                             'many concurrent decode rows (greedy, '
                             'sampled and grammar-constrained '
                             'requests all share one batch; sampled '
                             'and structured decoding REQUIRE the '
                             'engine — there is no serial sampling '
                             'path)')
    # Engine knobs default from the SKYTPU_ENGINE_* env stamps the
    # replica manager injects from the service YAML's `engine:`
    # section (SkyServiceSpec.engine_env) — explicit flags win.
    parser.add_argument('--block-size', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_BLOCK_SIZE', '16')),
                        help='paged-KV block granularity in tokens '
                             '(service YAML: engine.block_size)')
    parser.add_argument('--num-blocks', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_NUM_BLOCKS', '0')),
                        help='KV pool size in blocks; 0 sizes the '
                             'pool so every row reaches max_seq (no '
                             'preemption). Smaller oversubscribes: '
                             'admission bounds by actual usage and '
                             'the engine preempts-and-requeues on '
                             'exhaustion (engine.num_blocks)')
    parser.add_argument('--window-num-blocks', type=int, default=0,
                        help='blocks of the WINDOW layers\' block '
                        'group of a model with window and global '
                        'layers (the window is the configuration\'s: '
                        'a row holds there what its window still '
                        'sees plus the chunk in flight); 0 = that '
                        'much for every row')
    parser.add_argument('--experts-held', default='',
                        help='FIRST:COUNT - this replica\'s share of '
                        'the routed experts under expert parallelism '
                        '(it routes over all of them and computes '
                        'its own part); empty = all of them')
    parser.add_argument('--max-seq', type=int, default=0,
                        help='positions a decode row can reach (the '
                             'block table\'s width: a decode dispatch '
                             'reads it up to its longest row, in '
                             'eighths); 0 takes the model\'s '
                             'max_seq_len')
    parser.add_argument('--max-batched-tokens', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_MAX_BATCHED_TOKENS',
                            '2048')),
                        help='per-iteration prefill token budget — '
                             'bounds how much prompt work runs '
                             'between decode dispatches '
                             '(engine.max_num_batched_tokens)')
    parser.add_argument('--prefix-caching', choices=['on', 'off'],
                        default=('on' if os.environ.get(
                            'SKYTPU_ENGINE_PREFIX_CACHING', '1')
                            not in ('0', 'off', 'false') else 'off'),
                        help='automatic prefix caching on the paged '
                             'KV pool: repeat prompt prefixes skip '
                             'their prefill (token-exact under '
                             'greedy decoding; engine.prefix_caching '
                             'in the service YAML)')
    parser.add_argument('--speculative', choices=['on', 'off', 'mtp'],
                        default={'0': 'off', 'off': 'off',
                                 'false': 'off', 'mtp': 'mtp'}.get(
                            os.environ.get(
                                'SKYTPU_ENGINE_SPECULATIVE', '1'),
                            'on'),
                        help='speculative decoding on the paged '
                             'engine: self-speculative n-gram '
                             'drafting + batched multi-token verify '
                             '(token-exact under greedy decoding; '
                             'engine.speculative in the service '
                             'YAML). mtp: the model\'s own '
                             'next-token-prediction module drafts on '
                             'the device, one token a round (a model '
                             'with nextn_layers; token-exact for '
                             'greedy and sampled rows)')
    parser.add_argument('--draft-k', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_DRAFT_K', '8')),
                        help='max drafted tokens per row per verify '
                             'dispatch (engine.draft_k; 0 disables '
                             'speculation)')
    # Overload-control knobs (service YAML `overload:` section,
    # stamped as SKYTPU_ENGINE_OVERLOAD_* by the replica manager):
    # 0 = unbounded/none, the pre-overload-control behavior.
    parser.add_argument('--max-queued-requests', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_OVERLOAD_MAX_QUEUED_'
                            'REQUESTS', '0')),
                        help='bounded admission: refuse (429) past '
                             'this many queued requests '
                             '(overload.max_queued_requests; 0 = '
                             'unbounded)')
    parser.add_argument('--max-queued-tokens', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_OVERLOAD_MAX_QUEUED_'
                            'TOKENS', '0')),
                        help='bounded admission: refuse (429) past '
                             'this many queued prompt tokens '
                             '(overload.max_queued_tokens; 0 = '
                             'unbounded)')
    parser.add_argument('--default-timeout-s', type=float,
                        default=float(os.environ.get(
                            'SKYTPU_ENGINE_OVERLOAD_DEFAULT_'
                            'TIMEOUT_S', '0')),
                        help='deadline stamped on requests that '
                             'carry none; expired requests abort '
                             'typed with 504 '
                             '(overload.default_timeout_s; 0 = no '
                             'default deadline)')
    # Multi-tenant LoRA multiplexing (serve/adapters/): one base
    # model + per-tenant adapters sharing the batched engine. The
    # service YAML's `engine.adapters:` section stamps these as
    # SKYTPU_ENGINE_ADAPTER_* (SkyServiceSpec.engine_env).
    parser.add_argument('--adapter-dir',
                        default=os.environ.get(
                            'SKYTPU_ENGINE_ADAPTER_DIR', ''),
                        help='adapter registry base dir: every '
                             'subdirectory holding a committed LoRA '
                             'checkpoint is a servable adapter named '
                             'by the subdirectory '
                             '(engine.adapters.dir)')
    parser.add_argument('--adapter-capacity', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ENGINE_ADAPTER_CAPACITY', '0')),
                        help='device-resident adapter slots (LRU '
                             'with in-flight pinning; 0 disables '
                             'adapter serving; '
                             'engine.adapters.capacity)')
    parser.add_argument('--preload-adapters',
                        default=os.environ.get(
                            'SKYTPU_ENGINE_ADAPTER_PRELOAD', ''),
                        help='comma-separated adapter ids to load '
                             'before readiness — their first '
                             'requests pay no cold load '
                             '(engine.adapters.preload)')
    # Sampling subsystem (serve/sampling/): per-request temperature/
    # top_p/seed ride the shared batch as traced arrays under the
    # batch-invariance contract; response_format adds grammar-
    # constrained structured decoding. Service YAML `engine.sampling:`
    # stamps these as SKYTPU_ENGINE_SAMPLING*.
    parser.add_argument('--sampling', choices=['on', 'off'],
                        default=('on' if os.environ.get(
                            'SKYTPU_ENGINE_SAMPLING', '1')
                            not in ('0', 'off', 'false') else 'off'),
                        help='batch-invariant sampled decode on the '
                             'engine: per-request temperature/top_p/'
                             'seed as traced per-row arrays, '
                             'counter-keyed (seed, position) PRNG '
                             '(engine.sampling.enabled; off pins the '
                             'replica to the greedy-only '
                             'executables)')
    parser.add_argument('--grammar-vocab',
                        default=os.environ.get(
                            'SKYTPU_ENGINE_SAMPLING_GRAMMAR_VOCAB',
                            ''),
                        help='path to a JSON list mapping token id '
                             '-> token string (null for ids with no '
                             'text); enables response_format '
                             'grammar-constrained decoding '
                             '(engine.sampling.grammar_vocab; empty '
                             '= structured requests are refused)')
    parser.add_argument('--checkpoint-dir', default=None,
                        help='restore the latest finetune checkpoint '
                             'from this dir (a TrainState as saved by '
                             'recipes/finetune; LoRA adapters are '
                             'merged into the base). Point at the '
                             'task-id subdir, e.g. a mounted bucket '
                             'path.')
    args = parser.parse_args()
    trace_lib.set_component('replica')
    if args.quant == 'int8' and args.tp > 1:
        # Reject before the (expensive) sharded init, not after.
        parser.error('--quant int8 with --tp > 1 is not supported yet')
    if args.slots > 0 and args.tp > 1:
        parser.error('--slots (continuous batching) with --tp > 1 is '
                     'not supported yet: the engine cache is '
                     'unsharded and would replicate per device')

    from skypilot_tpu.utils import jax_runtime

    # ``replica.start`` stays open to ``ready``.
    starting.enter_context(jax_runtime.stage('replica.start'))
    with jax_runtime.stage('replica.start.backend'):
        jax_runtime.configure_compile_cache()

        import jax
        import jax.numpy as jnp

        from skypilot_tpu import exceptions
        from skypilot_tpu.models import decode, llama

        device = jax_runtime.device_facts()
    print(jax_runtime.device_line(device), flush=True)
    if args.experts_held:
        try:
            first, count = map(int, args.experts_held.split(':'))
            config = llama.get_config(args.model,
                                      experts_held=(first, count))
        except ValueError as e:
            parser.error(f'--experts-held {args.experts_held}: {e}')
    else:
        config = llama.get_config(args.model)
    if args.speculative == 'mtp' and not (args.slots > 0
                                          and config.nextn_layers):
        # The engine would refuse while it is built, after the
        # weights are made.
        parser.error(
            f'--speculative mtp: --model {args.model} has no '
            f'next-token-prediction module (nextn_layers) or the '
            f'batching engine is off (--slots 0)')
    if not config.plain_stack and args.slots <= 0:
        # The serial path is the dense layer body, which refuses
        # such a stack on the first request: say so at start-up.
        parser.error(
            f'--model {args.model} (loop passes '
            f'{config.loop_passes}, KV entries {config.kv_entries}, '
            f'sliding window {config.sliding_window}, experts held '
            f'{config.experts_held}, latent rank '
            f'{config.kv_lora_rank}, residual streams '
            f'{config.hc_mult}) is a stack only the batching '
            f'engine implements: pass --slots N')
    with jax_runtime.stage('replica.start.weights'):
        ckpt_params = None
        if args.checkpoint_dir:
            from skypilot_tpu.data.checkpoint import CheckpointManager
            ckpt = CheckpointManager(args.checkpoint_dir,
                                     use_task_namespace=False)
            raw = ckpt.restore_latest_raw(keys=('params', 'lora'))
            if raw is None:
                # Name the RESOLVED directory and list what is actually
                # there: finetune checkpoints are task-id namespaced
                # (data/checkpoint.task_checkpoint_dir), so the committed
                # steps usually live one subdirectory below the
                # --checkpoint-dir the user passed.
                resolved = ckpt.path
                try:
                    entries = sorted(os.listdir(resolved))
                except OSError:
                    entries = []
                listing = ', '.join(entries[:20]) if entries else '(empty)'
                raise SystemExit(
                    f'no committed checkpoint found in {resolved} '
                    f'(from --checkpoint-dir {args.checkpoint_dir}); the '
                    f'directory contains: {listing}. Finetune runs '
                    'namespace checkpoints by task id — point '
                    '--checkpoint-dir at the task-id subdirectory that '
                    'holds the step_* dirs.')
            ckpt_params = raw['params']
            if raw.get('lora') is not None:
                # Serve merged weights — no adapter math in the hot
                # loop. Merged ON HOST: the tp/int8 paths below exist
                # precisely because the full tree must not land on one
                # device.
                from skypilot_tpu.parallel import lora as lora_lib
                ckpt_params = lora_lib.merge_lora_host(ckpt_params,
                                                       raw['lora'])
            # Serve at the compute dtype: a training checkpoint is
            # usually fp32 masters — serving those doubles weight HBM.
            import numpy as np
            ckpt_params = jax.tree.map(
                lambda x: np.asarray(x).astype(config.dtype), ckpt_params)
        cache_sh = None
        if args.tp > 1:
            from skypilot_tpu.parallel import auto_mesh_config, make_mesh
            mesh = make_mesh(auto_mesh_config(tp=args.tp))
            # Single-request replica: cache batch stays replicated.
            param_sh, cache_sh = decode.decode_shardings(
                config, mesh, shard_batch=False)
            if ckpt_params is not None:
                # Host->device transfer lands directly sharded.
                params = jax.device_put(ckpt_params, param_sh)
            else:
                # Init DIRECTLY sharded (out_shardings on the jitted
                # init) — materializing the full pytree on one device
                # first would OOM for exactly the models --tp exists for.
                params = jax.jit(
                    lambda: llama.init_params(config,
                                              jax.random.PRNGKey(0)),
                    out_shardings=param_sh)()
        elif args.quant == 'int8':
            from skypilot_tpu.models import quant
            if ckpt_params is not None:
                # Leaf-streamed: each (host) leaf transfers + quantizes
                # alone, so the bf16 tree never fully sits in HBM.
                params = quant.quantize_params_streamed(ckpt_params,
                                                        config)
            else:
                params = quant.init_quantized(config,
                                              jax.random.PRNGKey(0))
        elif ckpt_params is not None:
            params = jax.tree.map(jnp.asarray, ckpt_params)
        else:
            params = llama.init_params(config, jax.random.PRNGKey(0))

    lock = threading.Lock()
    engine = None
    if args.slots > 0:
        from skypilot_tpu.serve.batching import BatchingEngine
        adapter_registry = None
        if args.adapter_dir and args.adapter_capacity > 0:
            from skypilot_tpu.serve.adapters import AdapterRegistry
            adapter_registry = AdapterRegistry(
                base_dir=args.adapter_dir)
        preload = [a for a in
                   (s.strip() for s in
                    args.preload_adapters.split(','))
                   if a] if args.preload_adapters else None
        grammar_vocab = None
        if args.grammar_vocab:
            # Structured decoding needs token TEXT to walk grammars:
            # a JSON list indexed by token id (null = no text, never
            # legal under a grammar). Refuse a malformed file at
            # startup, not on the first constrained request.
            with open(args.grammar_vocab) as f:
                grammar_vocab = json.load(f)
            if not isinstance(grammar_vocab, list):
                raise SystemExit(
                    f'--grammar-vocab {args.grammar_vocab} must hold '
                    f'a JSON list (token id -> string or null), got '
                    f'{type(grammar_vocab).__name__}')
        with jax_runtime.stage('replica.start.engine'):
            engine = BatchingEngine(
                params, config, slots=args.slots,
                max_seq=args.max_seq or None, kv_int8=args.kv_int8,
                block_size=args.block_size,
                num_blocks=args.num_blocks or None,
                window_num_blocks=args.window_num_blocks or None,
                max_num_batched_tokens=args.max_batched_tokens,
                prefix_caching=args.prefix_caching == 'on',
                speculative='mtp' if args.speculative == 'mtp'
                else args.speculative == 'on',
                draft_k=args.draft_k,
                max_queued_requests=args.max_queued_requests or None,
                max_queued_tokens=args.max_queued_tokens or None,
                default_timeout_s=args.default_timeout_s or None,
                adapter_registry=adapter_registry,
                adapter_capacity=args.adapter_capacity,
                adapter_preload=preload,
                sampling=args.sampling == 'on',
                grammar_vocab=grammar_vocab)

    # Publish this replica's registry (batching queue/TTFT/KV-cache
    # gauges + device HBM) to the host agent's /metrics via the
    # textfile bridge, so `xsky metrics`/`xsky top` see the serving
    # data plane, not just host gauges. Daemon thread; the stale-file
    # TTL cleans up after a crash.
    from skypilot_tpu.metrics import publish as publish_lib
    publish_lib.start_publisher('replica')

    def generate(prompt_ids, max_new, eos_id=None):
        """Greedy generation. Sampled and grammar-constrained decode
        live ONLY on the batching engine (submit_request with
        temperature/top_p/seed/response_format) — the old serial
        sampling fallback is gone: it allocated a whole extra
        [L, 1, S] KV cache next to the engine's resident one and
        broke batch invariance by keying randomness off a per-request
        split chain instead of (seed, position)."""
        if engine is not None:
            # Continuous batching: no lock — concurrent requests
            # share the decode batch (the engine clamps max_new
            # itself and retires rows at eos_id).
            return engine.generate(prompt_ids, max_new,
                                   eos_id=eos_id)
        # Engine-off replica (--slots 0): greedy-only serial path.
        # KV-cache decode: prefill once, then ONE device-side scan for
        # the whole generation (decode.decode_tokens_scan). The scan
        # length is a static compile parameter, so requested lengths
        # are bucketed to powers of two and truncated — otherwise
        # every distinct client max_new_tokens would pay a full-model
        # recompile while holding the serve lock.
        tokens = jnp.asarray([prompt_ids], jnp.int32)
        max_new = min(max_new,
                      config.max_seq_len - tokens.shape[1])
        if max_new <= 0:
            return []
        bucket = 1
        while bucket < max_new:
            bucket *= 2
        bucket = min(bucket, config.max_seq_len - tokens.shape[1])
        with lock:
            # Deliberately NOT passing eos_id down: it would
            # switch greedy_generate to its per-token loop (one
            # host round-trip per token, lock held); the scan
            # decodes the full bucket and the host-side
            # truncation below yields identical output.
            out = decode.greedy_generate(params, tokens, config,
                                         max_new_tokens=bucket,
                                         cache_sharding=cache_sh)
        out = [int(t) for t in out[0][:max_new]]
        if eos_id is not None and eos_id in out:
            out = out[:out.index(eos_id) + 1]
        return out

    class Handler(BaseHTTPRequestHandler):
        protocol_version = 'HTTP/1.1'

        def log_message(self, fmt, *largs):
            pass

        def _json(self, obj, code=200, extra_headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header('Content-Type', 'application/json')
            self.send_header('Content-Length', str(len(body)))
            for k, v in (extra_headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _engine_error(self, err):
            """Answer a typed engine failure as an HTTP error
            instead of raising through the handler (which tears the
            connection down mid-handshake). Client-shaped refusals
            map to non-5xx codes so they never trip the LB's
            replica-5xx-rate page: 413 for the pool-can-never-hold-
            this-prompt case, 429 (+ Retry-After from the engine's
            drain-rate estimate) for bounded-admission shedding,
            504 for an expired end-to-end deadline. Anything else
            (engine death pushed onto every queue by _fail_all) IS
            a replica fault and answers 500 so the 5xx alert sees
            it."""
            from skypilot_tpu import exceptions
            from skypilot_tpu.serve.sampling import GrammarError
            if isinstance(err, GrammarError):
                # The grammar compiler refused the client's
                # response_format (unsupported construct, bad
                # schema, no grammar vocab on this replica): their
                # request shape, not a replica fault.
                self._json({'error': str(err)}, 400)
                return
            if isinstance(err, exceptions.AdapterNotFoundError):
                # Client named an adapter this replica cannot
                # resolve: their error, not a replica fault.
                self._json({'error': str(err)}, 404)
                return
            if isinstance(err, exceptions.AdapterCapacityError):
                # This engine can NEVER serve the adapter (no
                # adapter subsystem, or rank over the gather
                # bucket) — same never-fits shape as the
                # prompt-exceeds-pool 413.
                self._json({'error': str(err)}, 413)
                return
            if isinstance(err, exceptions.EngineOverloadedError):
                retry_after = max(1, int(round(
                    getattr(err, 'retry_after_s', 1.0))))
                self._json({'error': str(err)}, 429,
                           extra_headers={'Retry-After':
                                          str(retry_after)})
                return
            if isinstance(err, exceptions.DeadlineExceededError):
                self._json({'error': str(err)}, 504)
                return
            code = 413 if isinstance(
                err, exceptions.KVPoolExhaustedError) else 500
            self._json({'error': str(err)}, code)

        @staticmethod
        def _prefix_headers(req):
            """Per-request prefix-cache accounting as response
            headers — the LB folds these into its per-endpoint
            block-hit-rate (serve/load_balancer.py)."""
            from skypilot_tpu.serve import prefix_hash
            headers = {
                prefix_hash.PREFIX_HITS_HEADER:
                    str(req.prefix_hit_blocks),
                prefix_hash.PREFIX_MISSES_HEADER:
                    str(req.prefix_miss_blocks),
            }
            if req.adapter is not None:
                # Adapter residency accounting: hit = the adapter
                # was device-resident at admission; load = this
                # request waited on a cold load. The LB folds these
                # into its per-endpoint adapter hit rate, which its
                # affinity policy is trying to maximize.
                hit = req.adapter_hit is True
                headers[prefix_hash.ADAPTER_HITS_HEADER] = \
                    str(int(hit))
                headers[prefix_hash.ADAPTER_LOADS_HEADER] = \
                    str(int(not hit))
            return headers

        def do_GET(self):  # noqa: N802
            if self.path == '/':
                # Readiness, plus what this replica runs on, what
                # it has compiled and allocated so far, and where
                # its start-up went — a probe reply alone shows
                # whether it is the chip.
                self._json({'status': 'ok', 'model': args.model,
                            'device': device,
                            'runtime': jax_runtime.runtime_facts(),
                            'startup': jax_runtime.startup_seconds()})
            else:
                self._json({'error': 'not found'}, 404)

        def do_POST(self):  # noqa: N802
            if self.path != '/generate':
                self._json({'error': 'not found'}, 404)
                return
            length = int(self.headers.get('Content-Length', '0'))
            try:
                body = json.loads(self.rfile.read(length))
                prompt_ids = [int(t) % config.vocab_size
                              for t in body['prompt_ids']]
                max_new = min(int(body.get('max_new_tokens',
                                           args.max_new_tokens)), 512)
                # Sampling knobs: typed 400s that NAME the offending
                # field — the engine enforces the same bounds
                # (submit_request), but refusing here answers before
                # a queue slot is taken.
                temperature = body.get('temperature')
                if temperature is not None:
                    if isinstance(temperature, bool) or \
                            not isinstance(temperature, (int, float)):
                        raise ValueError(
                            f'temperature must be a number, got '
                            f'{temperature!r}')
                    temperature = float(temperature)
                    if temperature < 0.0:
                        raise ValueError(
                            f'temperature must be >= 0, got '
                            f'{temperature}')
                top_p = body.get('top_p')
                if top_p is not None:
                    if isinstance(top_p, bool) or \
                            not isinstance(top_p, (int, float)):
                        raise ValueError(
                            f'top_p must be a number, got {top_p!r}')
                    top_p = float(top_p)
                    if not 0.0 < top_p <= 1.0:
                        raise ValueError(
                            f'top_p must be in (0, 1], got {top_p}')
                seed = body.get('seed')
                if seed is not None and (isinstance(seed, bool)
                                         or not isinstance(seed, int)):
                    raise ValueError(
                        f'seed must be an integer, got {seed!r}')
                response_format = body.get('response_format')
                if response_format is not None and \
                        not isinstance(response_format, dict):
                    raise ValueError(
                        f'response_format must be an object, got '
                        f'{type(response_format).__name__}')
                eos_id = body.get('eos_id')
                if eos_id is not None:
                    eos_id = int(eos_id)
                # Fair-share QoS key: the engine splits its prefill
                # token budget across tenants by weighted deficit
                # round-robin.
                tenant = body.get('tenant')
                if tenant is not None:
                    tenant = str(tenant)
                # LoRA adapter to decode under (None = base model);
                # resolved/validated by the engine, which answers
                # unknown ids 404 and never-fits adapters 413.
                adapter = body.get('adapter')
                if adapter is not None:
                    adapter = str(adapter)
                # Priority class (overload control): shedding takes
                # batch first, preemption takes lowest-priority-
                # youngest, prefill weights interactive ahead.
                priority = str(body.get('priority', 'interactive'))
                from skypilot_tpu.serve import batching as b_lib
                if priority not in b_lib.PRIORITIES:
                    raise ValueError(
                        f'priority must be one of '
                        f'{b_lib.PRIORITIES}, got {priority!r}')
            except (ValueError, KeyError, TypeError) as e:
                self._json({'error': f'bad request: {e}'}, 400)
                return
            # End-to-end deadline: the X-Skytpu-Deadline header (the
            # LB's remaining-budget stamp, already decremented for
            # the proxy hop) wins over the body's timeout_s — both
            # are seconds-from-now, re-anchored on THIS process's
            # clock so LB and replica clocks never need to agree.
            from skypilot_tpu.serve import overload as overload_lib
            import time as time_mod
            budget_s = overload_lib.parse_timeout_s(
                self.headers.get(overload_lib.DEADLINE_HEADER))
            if budget_s is None:
                budget_s = overload_lib.parse_timeout_s(
                    body.get('timeout_s'))
            deadline = (time_mod.time() + budget_s
                        if budget_s is not None else None)
            stream = bool(body.get('stream'))
            # Adopt the LB's traceparent hop (attach(None) is a
            # barrier: an untraced request must not inherit this
            # replica process's own launch-time trace context).
            ctx = trace_lib.parse_traceparent(
                self.headers.get(trace_lib.TRACEPARENT_HEADER))
            with trace_lib.attach(ctx), \
                    trace_lib.span('replica.generate',
                                   attrs={'prompt_len':
                                          len(prompt_ids),
                                          'max_new': max_new}):
                self._generate_response(prompt_ids, max_new,
                                        temperature, top_p, seed,
                                        eos_id, stream, tenant,
                                        deadline, priority, adapter,
                                        response_format)

        def _generate_response(self, prompt_ids, max_new, temperature,
                               top_p, seed, eos_id, stream,
                               tenant=None, deadline=None,
                               priority='interactive', adapter=None,
                               response_format=None):
            use_engine = engine is not None
            sampled = ((temperature is not None and temperature > 0.0)
                       or response_format is not None)
            if sampled and not use_engine:
                # There is no serial sampling path anymore: sampled
                # and grammar-constrained decode run ONLY on the
                # batching engine's shared batch.
                self._json({'error': 'sampled/structured decoding '
                            '(temperature > 0 or response_format) '
                            'requires the batching engine — start '
                            'the replica with --slots > 0'}, 400)
                return
            if adapter is not None and not use_engine:
                # Adapter decode lives on the batched engine's
                # gather path only.
                self._json({'error': 'adapter requests require the '
                            'batching engine (--slots > 0)'}, 400)
                return
            if sampled and seed is None:
                # Unseeded sampled requests draw a fresh seed at the
                # HTTP edge (host-side, never inside jit — the
                # serve-jit-prng lint): identical requests must not
                # return identical "samples", while a client-pinned
                # seed stays bitwise reproducible.
                seed = int.from_bytes(os.urandom(4), 'little')
            submit_kwargs = dict(
                eos_id=eos_id, tenant=tenant, deadline=deadline,
                priority=priority, adapter=adapter,
                temperature=temperature if temperature is not None
                else 0.0,
                top_p=top_p if top_p is not None else 1.0,
                seed=seed if seed is not None else 0,
                response_format=response_format)
            if stream and use_engine:
                # SSE: tokens leave as the engine produces them (per
                # decode dispatch), so client TTFT is prefill-bound,
                # not completion-bound. The serve LB passes chunked
                # bodies through unbuffered (load_balancer.py
                # _stream_response), end to end.
                import queue as queue_mod
                req = engine.submit_request(prompt_ids, max_new,
                                            **submit_kwargs)
                q = req.out
                # Hold the status line for the FIRST queue item:
                # admission (which fills the prefix-cache stats the
                # headers carry) strictly precedes the first token,
                # so in the common case this costs no TTFT — and a
                # typed failure can be answered as a real HTTP error
                # instead of a 200 event stream. BOUNDED wait: under
                # a queueing collapse the first token can take
                # longer than the LB's 120 s upstream timeout, and
                # the status line must never be what times out —
                # past the bound, send headers without the stats and
                # stream as before.
                _pending = object()
                try:
                    first = q.get(timeout=90)
                except queue_mod.Empty:
                    first = _pending
                if isinstance(first, BaseException):
                    self._engine_error(first)
                    return
                self.send_response(200)
                self.send_header('Content-Type', 'text/event-stream')
                self.send_header('Cache-Control', 'no-cache')
                self.send_header('Transfer-Encoding', 'chunked')
                if first is not _pending:
                    for k, v in self._prefix_headers(req).items():
                        self.send_header(k, v)
                self.end_headers()

                def chunk(data: bytes):
                    self.wfile.write(f'{len(data):x}\r\n'.encode())
                    self.wfile.write(data + b'\r\n')
                    self.wfile.flush()

                try:
                    tok = q.get() if first is _pending else first
                    while True:
                        if tok is None:
                            chunk(b'data: [DONE]\n\n')
                            break
                        if isinstance(tok, BaseException):
                            # Mid-stream typed failure: the 200 is
                            # gone — surface it as an SSE error
                            # event, then end the stream. One-line
                            # payload: a newline in the message
                            # (XLA errors are multi-line) would
                            # terminate the SSE event early and
                            # leak the tail as bogus data lines.
                            msg = ' '.join(str(tok).split())
                            chunk(f'event: error\ndata: '
                                  f'{msg}\n\n'.encode())
                            tok = q.get()
                            continue
                        chunk(f'data: {tok}\n\n'.encode())
                        tok = q.get()
                    self.wfile.write(b'0\r\n\r\n')
                    self.wfile.flush()
                except OSError:
                    # Client went away mid-stream: CANCEL the
                    # request — the engine frees its KV blocks at
                    # the next iteration boundary (the same reclaim
                    # path as preemption) instead of burning decode
                    # until max_tokens for nobody — then drain the
                    # queue so this handler thread unblocks on the
                    # sentinel. Bounded get()s: the sentinel may
                    # already have been consumed above, and a bare
                    # get() would then block forever.
                    engine.cancel(req.id)
                    try:
                        while q.get(timeout=30) is not None:
                            pass
                    except queue_mod.Empty:
                        pass
                return
            if use_engine:
                req = engine.submit_request(prompt_ids, max_new,
                                            **submit_kwargs)
                out = []
                err = None
                while True:
                    tok = req.out.get()
                    if tok is None:
                        break
                    if isinstance(tok, BaseException):
                        err = tok
                        continue
                    out.append(tok)
                if err is not None:
                    self._engine_error(err)
                    return
                self._json({'output_ids': out},
                           extra_headers=self._prefix_headers(req))
                return
            out = generate(prompt_ids, max_new, eos_id=eos_id)
            if stream:
                self._stream_burst(out)
                return
            self._json({'output_ids': out})

        def _stream_burst(self, out):
            # No engine: stream-compatible response with the whole
            # generation as one event burst.
            self.send_response(200)
            self.send_header('Content-Type', 'text/event-stream')
            payload = b''.join(f'data: {t}\n\n'.encode()
                               for t in out) + b'data: [DONE]\n\n'
            self.send_header('Content-Length', str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

    # Warm the decode compiles before declaring readiness — the first
    # request would otherwise pay them. max_new=2 so the batching
    # engine's decode step compiles too (a 1-token request retires at
    # admission without ever dispatching it). Sampled warmup is
    # engine-gated: sampled decode only exists on the engine, and its
    # sampled executable is a SECOND compile (the greedy one stays
    # byte-identical to the pre-sampling engine).
    with jax_runtime.stage('replica.start.warm'):
        generate([1, 2, 3], 2)
        if engine is not None and engine.sampling:
            req = engine.submit_request([1, 2, 3], 2, temperature=1.0,
                                        top_p=0.9, seed=0)
            while req.out.get() is not None:
                pass
    server = ThreadingHTTPServer(('0.0.0.0', args.port), Handler)
    starting.close()
    # From here a lowering lands inside a request: counted, and
    # logged with the program's name.
    jax_runtime.mark_ready()
    print(f'serve_model ready on :{args.port} (model {args.model}, '
          f'platform={device["platform"]} '
          f'device_kind={device["device_kind"]!r} '
          f'devices={device["device_count"]})', flush=True)
    server.serve_forever()


if __name__ == '__main__':
    main()
