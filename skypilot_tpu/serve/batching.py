"""Continuous batching for serving (iteration-level scheduling) over
a PAGED KV cache.

The reference delegates serving to engines like vLLM/JetStream whose
core tricks are exactly these: concurrent requests share ONE decode
batch (new requests admitted between decode iterations, finished ones
retired immediately), and KV storage is a pool of fixed-size blocks
mapped per-request through block tables (PagedAttention) — so
admission is bounded by a TOKEN budget (free blocks), not by whole
free slots, and short requests never reserve long-request HBM.

TPU-first design:
- All shapes static: the engine owns a block pool
  ``[E, num_blocks, block_size, Hkv, hd]`` (E KV entries, a pass and
  a layer each: ``kv_pool.KVBlockPool`` says what that axis is) plus
  per-request block-table rows ``[B, max_blocks]``; decode is one
  jitted step for every batch/occupancy composition (block tables and
  occupancy are data, not shape).
- Decode runs ``steps_per_dispatch`` tokens per dispatch as a small
  ``lax.scan`` — admission happens between dispatches; the scan
  amortizes host->device dispatch latency without giving up
  iteration-level scheduling.
- Prefill is CHUNKED and writes DIRECTLY into the request's allocated
  blocks (``models/decode.forward_paged``): long prompts prefill in
  fixed-size chunks interleaved with decode dispatches, so one 8k
  prompt cannot stall every in-flight decode (the p99-TTFT lever),
  and there is no staging cache or row-insert copy on admission. A
  chunk attends tiles of the request's own blocks up to its offset,
  not a view of ``max_seq`` (the ``prefill_keys_*`` counters).
- Pool exhaustion PREEMPTS the youngest request (blocks freed, the
  request requeued at the front; resume re-prefills prompt+generated,
  which under greedy decoding reproduces the continuation exactly) —
  never a deadlock, never an engine-wide failure. A request that can
  never fit the pool fails alone with a typed
  ``exceptions.KVPoolExhaustedError``.
- AUTOMATIC PREFIX CACHING (default on): admission matches the
  prompt's block hash chain against refcounted cached blocks, pins
  hits and prefills only the suffix (copy-on-write past the first
  divergent token mid-block); completed prompts register their full
  blocks. Cached content is exactly what re-prefilling would write,
  so greedy outputs stay token-for-token identical (bf16 KV; under
  int8 KV a hit shifts the suffix's prefill-chunk boundary, so the
  int8 chunk caveat below applies across the hit boundary too) — a
  preempted request's resume also re-admits through the matcher,
  collapsing its re-prefill to ~the tokens generated since
  preemption.
- Numerics contract: batched outputs EQUAL single-request greedy
  decoding (tested token-for-token, bf16 and int8 KV; the paged
  gather view is masked so recycled-block garbage contributes exactly
  0). int8 caveat: equality vs the plain int8 path holds for prompts
  within ONE prefill chunk — a later chunk attends earlier chunks'
  int8 codes where whole-prompt prefill attends exact bf16
  (``forward_paged`` attends only the CURRENT chunk's rows exact, as
  an operand), so multi-chunk int8 prompts track rather than equal the
  dense path; quantization error still never enters within-chunk
  attention. MoE caveat: equality holds while expert capacity does
  not bind — the engine's power-of-two chunk padding enters the
  capacity denominator (cap = ceil(k*T*cf/E)), so a low
  ``moe_capacity_factor`` can drop different tokens than an unpadded
  prefill would.
"""
import collections
import itertools
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu import exceptions
from skypilot_tpu import metrics as metrics_lib
from skypilot_tpu import tpu_logging
from skypilot_tpu import trace as trace_lib
from skypilot_tpu.models import llama
from skypilot_tpu.models import moe
from skypilot_tpu.models.decode import (decode_steps_paged,
                                        forward_paged,
                                        mtp_first_paged,
                                        mtp_rounds_paged,
                                        verify_step_paged)
from skypilot_tpu.ops import decode_attention as da
from skypilot_tpu.ops.sampling import sample as sample_lib
from skypilot_tpu.resilience import faults as faults_lib
from skypilot_tpu.serve import kv_pool as kv_pool_lib
from skypilot_tpu.serve import prefix_hash
from skypilot_tpu.serve.sampling import grammar as grammar_lib
from skypilot_tpu.utils import jax_runtime

logger = tpu_logging.init_logger(__name__)

Params = Dict[str, Any]

# Trailing window for the exported prefix hit-rate gauge — matches
# the prefix-hit-ratio-low alert rule's evaluation window, so a
# regression is visible to the rule within one window.
PREFIX_RATIO_WINDOW_SECONDS = 900.0

# Trailing window for the exported speculative accept-rate gauge —
# matches the spec-accept-rate-low alert rule's window for the same
# reason as the prefix-ratio window above.
SPEC_RATIO_WINDOW_SECONDS = 900.0

# Self-speculative n-gram drafting (prompt lookup): longest suffix
# n-gram tried first down to a bigram minimum (unigram anchors
# propose near-noise and poison the acceptance window), and the
# history scan is bounded so an 8k prompt cannot turn every
# proposal into an O(prompt) walk on the single-threaded engine
# loop.
SPEC_MAX_NGRAM = 6
SPEC_MIN_NGRAM = 2
SPEC_MATCH_WINDOW = 1024

# Adaptive per-request draft length: trailing acceptance window size
# (verify rounds), the shrink/grow thresholds, and how many emitted
# tokens a collapsed (k=0) request waits before re-probing with a
# short draft — adversarial (low-repeat) traffic converges to
# plain decode with only this counter as overhead. While OTHER rows
# keep a verify dispatch alive anyway, collapsed rows re-probe for
# free inside it (their ride-along lanes exist either way); the
# cooldown gates only the case where the probe itself would force a
# verify dispatch.
SPEC_WINDOW_ROUNDS = 8
SPEC_SHRINK_BELOW = 0.4
SPEC_COLLAPSE_BELOW = 0.15
SPEC_GROW_ABOVE = 0.8
SPEC_REPROBE_TOKENS = 16
# Re-probe cooldowns back off exponentially (doubling per failed
# probe, capped at 2**SPEC_BACKOFF_MAX_EXP * SPEC_REPROBE_TOKENS)
# so a genuinely low-repeat request's total probing overhead is a
# vanishing fraction of its stream, while a regime change is still
# caught within a few hundred tokens.
SPEC_BACKOFF_MAX_EXP = 4
SPEC_PROBE_K = 2
# Probe-mode proposals (a collapsed or nearly-collapsed request
# testing the water, k <= SPEC_PROBE_K) demand a LONG n-gram match:
# repetitive streams produce one instantly, while low-repeat text
# essentially never does — so re-entry into speculation is
# immediate exactly when it will pay, and an adversarial stream's
# probes stop costing verify dispatches at all. A request with no
# verify history yet gets a milder (trigram) bar: it has no failure
# evidence against it, but a first full-k draft on bigram evidence
# alone whiffs too often to be worth a dispatch.
SPEC_PROBE_MIN_NGRAM = 4
SPEC_FIRST_MIN_NGRAM = 3
# A verify dispatch must carry at least this many drafted tokens:
# below it, displacing the multi-step decode scan cannot pay for
# itself and the batch takes the plain path instead.
SPEC_MIN_DISPATCH_TOKENS = 4


# ---------------------------------------------------------------------
# Speculative decoding: n-gram drafting (the batched multi-token
# verify is ``models/decode.verify_step_paged``)
# ---------------------------------------------------------------------


def propose_ngram_draft(tokens: List[int], k: int,
                        max_ngram: int = SPEC_MAX_NGRAM,
                        min_ngram: int = SPEC_MIN_NGRAM,
                        window: int = SPEC_MATCH_WINDOW) -> List[int]:
    """Self-speculative prompt-lookup drafting: find the most recent
    EARLIER occurrence of the longest n-gram ending at the current
    suffix of ``tokens`` (the request's own prompt + generated
    stream) and propose up to ``k`` tokens that followed it
    historically. No second model: summarization/extraction-shaped
    traffic — and greedy decode's own repetition — make the
    continuation of a repeated n-gram an excellent draft. The scan
    is bounded to the trailing ``window`` tokens so proposal cost
    cannot grow with prompt length. Returns [] when nothing matches
    (not a rejection — the row just decodes plainly)."""
    if k <= 0 or len(tokens) < 2:
        return []
    import array
    lo = max(0, len(tokens) - window)
    hist = list(tokens[lo:])
    # SEQUENTIAL drafting: each drafted token re-anchors the n-gram
    # lookup on the suffix INCLUDING the tokens drafted so far, so
    # the draft can hop between historical sources mid-run (a
    # single k-token continuation copy breaks at the first source
    # divergence — measured ~0.5 acceptance where the re-anchoring
    # predictor measures 0.9+ on the same stream). The history is
    # a flat int32 byte string searched with C-speed
    # ``bytearray.rfind`` (a Python scan here would cost ~100s of
    # µs per row per dispatch — exactly the adversarial overhead
    # the adaptive controller is supposed to bound); the most
    # recent earlier occurrence wins, since recent context predicts
    # the continuation best.
    buf = bytearray(array.array('i', hist).tobytes())
    item = array.array('i', [0]).itemsize
    out: List[int] = []
    for _ in range(k):
        n_hist = len(hist)
        nxt = None
        for n in range(min(max_ngram, n_hist - 1),
                       min_ngram - 1, -1):
            pat = array.array('i', hist[-n:]).tobytes()
            # The match must END at or before the last-but-one
            # token (an occurrence strictly earlier than the
            # suffix itself, with a token after it to propose).
            idx = buf.rfind(pat, 0, (n_hist - 1) * item)
            while idx != -1 and idx % item:
                # Byte-level hits straddling item boundaries are
                # not token matches — keep searching earlier.
                idx = buf.rfind(pat, 0, idx + len(pat) - 1)
            if idx != -1:
                nxt = hist[idx // item + n]
                break
        if nxt is None:
            break
        out.append(nxt)
        hist.append(nxt)
        buf += array.array('i', [nxt]).tobytes()
    return out


def update_spec_k(cur_k: int, window, draft_k: int) -> int:
    """Adaptive per-request draft length from a trailing
    acceptance-rate window of (proposed, accepted) verify rounds:
    shrink (halve, to 0) while the trailing rate sits under
    ``SPEC_SHRINK_BELOW``, grow (double, capped at ``draft_k``)
    above ``SPEC_GROW_ABOVE`` — adversarial low-repeat traffic
    converges to plain decode, repeat-heavy traffic rides the full
    draft length."""
    proposed = sum(p for p, _ in window)
    if proposed <= 0:
        return cur_k
    rate = sum(a for _, a in window) / proposed
    if proposed >= 8 and rate < SPEC_COLLAPSE_BELOW:
        # Near-nothing accepted over real evidence: collapse to
        # plain decode NOW instead of halving down — every
        # intermediate verify would emit ~1 token for a whole
        # dispatch.
        return 0
    if rate < SPEC_SHRINK_BELOW:
        return cur_k // 2
    if rate > SPEC_GROW_ABOVE and cur_k < draft_k:
        return min(draft_k, max(1, cur_k * 2))
    return cur_k


# ---------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------


# Priority classes layered on the tenant DRR (overload control):
# shedding takes batch first, pool-exhaustion preemption takes the
# lowest-priority-youngest row, and the prefill budget weights
# interactive classes ahead of batch ones (docs/resilience.md,
# Overload control).
PRIORITIES = ('interactive', 'batch')
PRIORITY_PREFILL_WEIGHTS = {'interactive': 4.0, 'batch': 1.0}

_REQ_SEQ = itertools.count(1)


class _Request:
    def __init__(self, prompt_ids: List[int], max_new: int,
                 eos_id: Optional[int] = None,
                 tenant: Optional[str] = None,
                 deadline: Optional[float] = None,
                 priority: str = 'interactive',
                 adapter: Optional[str] = None,
                 temperature: float = 0.0,
                 top_p: float = 1.0,
                 seed: int = 0,
                 response_format: Optional[dict] = None):
        self.prompt_ids = prompt_ids
        self.max_new = max_new
        self.eos_id = eos_id
        # Sampling knobs (serve/sampling/): temperature 0 = greedy
        # (bitwise the pre-sampling engine); every random draw this
        # request ever sees is keyed (seed, absolute position) and
        # nothing else — the batch-invariance contract. The compiled
        # grammar (``response_format`` -> ``grammar``, filled at
        # submit) walks host-side; ``grammar_state`` tracks the DFA
        # state after every EMITTED token, recomputed from
        # ``generated`` at (re-)admission so preempt-resume lands in
        # the identical state.
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.response_format = response_format
        self.grammar = None
        self.grammar_state = None
        # Multi-tenant LoRA (serve/adapters/): the adapter this
        # request decodes under (None = base model). ``adapter_hit``
        # is filled at admission — True when the adapter was already
        # device-resident (no cold load stood between submit and
        # admission), False when this request waited on a cold load;
        # None for base-model requests. serve_model surfaces it as
        # the X-Skytpu-Adapter-* response headers the LB folds into
        # its per-endpoint adapter hit rate.
        self.adapter = adapter
        self.adapter_hit: Optional[bool] = None
        # Fair-share QoS key (None = the default tenant): the
        # admission loop splits the per-iteration prefill token
        # budget by weighted deficit round-robin over this field.
        self.tenant = tenant
        # Overload-control state: ``id`` is the handle
        # ``BatchingEngine.cancel`` takes (serve_model holds it
        # across the streaming response), ``deadline`` is an
        # ABSOLUTE epoch second (None = no deadline) enforced at
        # admission and between decode iterations, ``priority``
        # picks the shed/preempt/prefill class.
        self.id = next(_REQ_SEQ)
        self.deadline = deadline
        self.priority = priority
        self.cancelled = False
        # Prefix-cache accounting, filled at admission (cumulative
        # across re-admissions after preemption): whole KV blocks
        # reused from the cache vs freshly prefilled. serve_model
        # surfaces these as X-Skytpu-Prefix-* response headers, which
        # the LB rolls into its per-endpoint block-hit-rate.
        self.prefix_hit_blocks = 0
        self.prefix_miss_blocks = 0
        # Admission-time hash chain, stashed so _register_prefix
        # does not recompute it at prefill finish (an 8k prompt is
        # ~500 sha256 calls — once per admission is enough on the
        # single-threaded engine loop).
        self.chain_hashes: List[bytes] = []
        self.chain_t0 = -1
        # Speculative-decoding state (engine-managed): current draft
        # length (None until admission seeds it from the engine's
        # draft_k), trailing (proposed, accepted) verify window the
        # adaptive controller reads, and the emitted-token cooldown
        # before a collapsed (k=0) request re-probes. ONLY emitted
        # (accepted) tokens ever enter ``generated`` — drafted
        # tokens live in the dispatch alone, so preemption resume
        # and prefix registration hash exactly what the client saw.
        self.spec_k: Optional[int] = None
        self.spec_window: 'collections.deque' = collections.deque(
            maxlen=SPEC_WINDOW_ROUNDS)
        self.spec_cooldown = 0
        self.spec_fail_streak = 0
        self.out: 'queue.Queue' = queue.Queue()
        self.submitted_at = time.time()
        # Tokens already EMITTED to the client — preemption resume
        # state: a requeued request re-prefills prompt + generated
        # (greedy decoding reproduces the continuation exactly) and
        # keeps emitting from where it left off.
        self.generated: List[int] = []
        self.admitted_once = False
        self.preemptions = 0
        # Trace context captured at submit (the engine loop runs on
        # its own thread — contextvars don't cross it): queue-wait /
        # prefill / TTFT / decode-chunk spans are emitted under the
        # SUBMITTING request's trace. None = untraced request, spans
        # cost nothing.
        self.trace_ctx = trace_lib.current()


def _engine_metrics():
    """The engine's metric families (get-or-create: several engines
    in one process share them; see docs/observability.md)."""
    reg = metrics_lib.registry()
    return {
        'queue_wait': reg.histogram(
            'skytpu_batch_queue_wait_seconds',
            'submit() to admission (first prefill chunk).'),
        'ttft': reg.histogram(
            'skytpu_batch_ttft_seconds',
            'submit() to first generated token.'),
        'tokens': reg.counter(
            'skytpu_batch_decode_tokens_total',
            'Generated tokens emitted to clients.'),
        'requests': reg.counter(
            'skytpu_batch_requests_total',
            'Requests admitted into the decode batch.'),
        'tok_s': reg.gauge(
            'skytpu_batch_decode_tokens_per_sec',
            'Decode throughput of the latest dispatch '
            '(active rows * steps / wall time).'),
        'occupancy': reg.gauge(
            'skytpu_batch_slots_occupied',
            'Decode rows currently holding a request.'),
        'slots': reg.gauge(
            'skytpu_batch_slots_total',
            'Fixed decode row count of the engine.'),
        'kv_bytes': reg.gauge(
            'skytpu_batch_kv_cache_bytes',
            'Resident KV block-pool allocation of the engine '
            '(codes + scales) — the HBM the pool pins whether or '
            'not its blocks are allocated.'),
        'kv_used': reg.gauge(
            'skytpu_batch_kv_cache_used_bytes',
            'Bytes of KV blocks currently allocated to admitted '
            'requests — real block accounting (allocated blocks x '
            'bytes/block), not a slot-occupancy estimate.'),
        'kv_blocks_total': reg.gauge(
            'skytpu_batch_kv_blocks_total',
            'Allocatable KV blocks in the pool (excludes the '
            'reserved scratch block).'),
        'kv_blocks_used': reg.gauge(
            'skytpu_batch_kv_blocks_used',
            'KV blocks currently allocated to admitted requests.'),
        'preemptions': reg.counter(
            'skytpu_batch_preemptions_total',
            'Requests preempted (blocks reclaimed, request '
            'requeued) because the KV pool ran out of free blocks.'),
        'kv_cached': reg.gauge(
            'skytpu_batch_kv_cache_cached_bytes',
            'Bytes of refcount-0 prefix-cache blocks — RECLAIMABLE '
            'capacity holding reusable KV content. A pool reading '
            'full on kv_cache_bytes but mostly cached here is '
            'healthy, not exhausted.'),
        'prefix_hits': reg.counter(
            'skytpu_batch_prefix_hits_total',
            'KV blocks reused from the prefix cache at admission '
            '(prefill skipped for their tokens).'),
        'prefix_misses': reg.counter(
            'skytpu_batch_prefix_misses_total',
            'KV blocks freshly allocated and prefilled at admission '
            '(no cache hit).'),
        'prefix_cached_blocks': reg.gauge(
            'skytpu_batch_prefix_cached_blocks',
            'Refcount-0 blocks currently holding registered '
            '(reusable) prefix-cache content.'),
        'spec_proposed': reg.counter(
            'skytpu_batch_spec_proposed_total',
            'Draft tokens proposed by the self-speculative n-gram '
            'drafter and carried into a verify dispatch.'),
        'spec_accepted': reg.counter(
            'skytpu_batch_spec_accepted_total',
            'Proposed draft tokens accepted by verification — the '
            'argmax match for greedy rows, the speculative-'
            'sampling rule for sampled rows (each accepted draft '
            'is one decode forward the engine did not have to '
            'run).'),
        'spec_tokens_per_forward': reg.gauge(
            'skytpu_batch_spec_tokens_per_forward',
            'Tokens emitted per row by the latest verify dispatch '
            '(accepted drafts + the bonus token; 1.0 == plain '
            'decode, draft_k+1 == full acceptance).'),
        'spec_accept_rate': reg.histogram(
            'skytpu_batch_spec_accept_rate',
            'Per-row accepted/proposed fraction of each verify '
            'round, labeled by decode mode — sampled rows accept '
            'by the speculative-sampling rule '
            '(ops/sampling/accept.py), greedy rows by argmax '
            'match. A sampled-mode distribution sitting far below '
            'greedy on the same traffic means drafts are being '
            'rejected by randomness, not by model disagreement.',
            ('mode',),
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0)),
        'sampled_requests': reg.counter(
            'skytpu_batch_sampled_requests_total',
            'Admitted requests decoding with temperature > 0 '
            '(counter-keyed sampled decode, serve/sampling/).'),
        'constrained_requests': reg.counter(
            'skytpu_batch_constrained_requests_total',
            'Admitted requests decoding under a response_format '
            'grammar (structured decoding, serve/sampling/'
            'grammar.py).'),
        'shed': reg.counter(
            'skytpu_batch_shed_total',
            'Requests refused typed at submit() by bounded '
            'admission, by reason: which overload knob tripped '
            '(max_queued_requests / max_queued_tokens) or '
            'priority_evict (a queued batch request shed to make '
            'room for an arriving interactive one).',
            ('reason',)),
        'cancelled': reg.counter(
            'skytpu_batch_cancelled_total',
            'Requests cancelled by the client (broken connection) '
            '— their KV blocks reclaimed at the next iteration '
            'boundary through the preemption release path.'),
        'deadline_exceeded': reg.counter(
            'skytpu_batch_deadline_exceeded_total',
            'Requests aborted typed because their end-to-end '
            'deadline expired at admission or between decode '
            'iterations (serve_model answers 504).'),
        'loop_hang': reg.counter(
            'skytpu_batch_loop_hang_total',
            'close() observed the engine loop thread still alive '
            'after its join timeout — a wedged dispatch is holding '
            'the loop (likely a hung device call).'),
        'queued_requests': reg.gauge(
            'skytpu_batch_queued_requests',
            'Requests waiting in the pending (pre-admission) '
            'queue.'),
        'queued_tokens': reg.gauge(
            'skytpu_batch_queued_tokens',
            'Prompt + resume tokens held by the pending queue — '
            'the currency of the max_queued_tokens admission '
            'bound.'),
        # The scheduler iteration as the unit of account. Unlabelled
        # on purpose: each is one row of the docs table and one
        # number to a reader that sums a family over its labels.
        'iterations': reg.counter(
            'skytpu_batch_iterations_total',
            'Scheduler passes that did work (ran a prefill chunk '
            'or a decode / verify dispatch).'),
        'iteration_seconds': reg.counter(
            'skytpu_batch_iteration_seconds_total',
            'Wall time of the passes that did work, loop top to '
            'the end of the gauges; passes that end in wake.wait '
            'are left out.'),
        'host_gap_seconds': reg.counter(
            'skytpu_batch_host_gap_seconds_total',
            'Time inside those passes from the return of a '
            'blocking device_get (decode, verify, first token) to '
            'the next enqueue of a prefill, decode, verify, '
            'first-token or block-copy program. A lower bound of '
            'device idle: launch latency, the return of device_get '
            'and the small block-table updates enqueued inside '
            'the gap are not in it (a half to three quarters of a '
            'trace\'s idle on the v5e).'),
        'prefill_chunks': reg.counter(
            'skytpu_batch_prefill_chunks_total',
            'Prefill-chunk dispatches (forward_paged calls).'),
        'prefill_tokens': reg.counter(
            'skytpu_batch_prefill_tokens_total',
            'Real prompt (or resume) tokens in those chunks.'),
        'prefill_bucket_tokens': reg.counter(
            'skytpu_batch_prefill_bucket_tokens_total',
            'Tokens those chunks were charged, bucket padding '
            'included: attempts, against the useful count in '
            'prefill_tokens_total.'),
        'prefill_keys_read': reg.counter(
            'skytpu_batch_prefill_keys_read_total',
            'Key positions those chunks\' attention scored, a kind '
            'of layer (ops/decode_attention.chunk_keys_read): the '
            'key tiles up to a chunk\'s start, and its bucket.'),
        'prefill_keys_view': reg.counter(
            'skytpu_batch_prefill_keys_view_total',
            'Key positions the row\'s whole view held for those '
            'chunks and kinds (max_seq each), which a chunk scored '
            'until PR 42; prefill_keys_read_total over it is the '
            'share that is left.'),
        'loop_passes': reg.counter(
            'skytpu_batch_loop_passes_total',
            'Passes over the layer stack run for the tokens in '
            'decode_tokens_total (config.loop_passes for each: every '
            'pass runs for every row); over that family it is the '
            'passes a token costs, 1 for a model whose layers run '
            'once.'),
        'kv_token_bytes': reg.gauge(
            'skytpu_batch_kv_token_bytes',
            'Resident KV bytes one cached token costs, codes and '
            'scales over every KV entry (passes x layers).'),
        'decode_dispatches': reg.counter(
            'skytpu_batch_decode_dispatches_total',
            'Decode and verify dispatches (decode_steps_paged + '
            'verify_step_paged calls); decode_tokens_total over it '
            'is the tokens a dispatch yields.'),
        'kv_window_blocks_total': reg.gauge(
            'skytpu_batch_kv_window_blocks_total',
            'Allocatable blocks of the WINDOW layers\' block group '
            '(a model with window and global layers keeps one group '
            'a kind; skytpu_batch_kv_blocks_total counts both).'),
        'kv_window_blocks_used': reg.gauge(
            'skytpu_batch_kv_window_blocks_used',
            'Window-group blocks currently referenced by admitted '
            'requests: at most window / block_size + 1 a row plus '
            'the chunk or dispatch in flight.'),
        'kv_window_released': reg.counter(
            'skytpu_batch_kv_window_blocks_released_total',
            'Window-group blocks given back because they fell behind '
            'their row\'s window (a decrement where shared).'),
        'moe_routed_pairs': reg.counter(
            'skytpu_batch_moe_routed_pairs_total',
            '(token, expert) pairs the routers chose, held here or '
            'not: tokens computed (every lane of a dispatch, every '
            'slot of a chunk bucket) x experts per token x expert '
            'layers.'),
        'moe_held_pairs': reg.counter(
            'skytpu_batch_moe_held_pairs_total',
            'Of those, the pairs whose expert this chip holds: what '
            'the grouped products computed. A layer that drops '
            'tokens moves held / routed off experts_held / '
            'n_experts.'),
        'moe_tiled_pairs': reg.counter(
            'skytpu_batch_moe_tiled_pairs_total',
            'Of the held pairs, those whose three grouped products '
            'went through the pair-tiled kernel '
            '(ops/grouped_matmul.tiles_engage, by the static shapes '
            'of the dispatch\'s or chunk\'s program).'),
        'moe_busiest_pairs': reg.counter(
            'skytpu_batch_moe_busiest_expert_pairs_total',
            'Pairs of the busiest held expert, summed over layers '
            'and dispatches (or chunks): over held pairs / experts '
            'held it is the load imbalance.'),
        'moe_experts_hit': reg.counter(
            'skytpu_batch_moe_experts_hit_total',
            'Held experts that got at least one pair, counted a '
            'layer and step: the expert weights a step had to read.'),
        'moe_experts_held': reg.counter(
            'skytpu_batch_moe_experts_held_total',
            'Held experts, counted a layer and step (the denominator '
            'of the above).'),
        'mla_absorbed_row_steps': reg.counter(
            'skytpu_batch_mla_absorbed_row_steps_total',
            'Decode steps of active rows that went through the '
            'absorbed form of latent attention (a model with '
            'kv_lora_rank): rows of a dispatch x its steps.'),
        'mla_absorbed_context': reg.counter(
            'skytpu_batch_mla_absorbed_context_tokens_total',
            'Positions those row-steps attended, the step\'s own '
            'counted: over the row-steps it is the mean context a '
            'latent decode step reads.'),
        'mtp_rounds': reg.counter(
            'skytpu_batch_mtp_row_rounds_total',
            'Drafting rounds of active rows (a model whose own '
            'next-token-prediction module drafts on the device, '
            'decode.mtp_rounds_paged): rows of a dispatch x its '
            'rounds. A round verifies the row\'s token and its one '
            'pending draft and commits one or two tokens.'),
        'mtp_tokens': reg.counter(
            'skytpu_batch_mtp_round_tokens_total',
            'Tokens those rounds committed (what the device '
            'emitted, before a row\'s budget or EOS cut it): over '
            'the row-rounds it is the tokens a round yields.'),
        'mtp_positions': reg.counter(
            'skytpu_batch_mtp_module_positions_total',
            'Pairs the module computed for real in those rounds and '
            'in first drafts (one a committed token); prefill chunks '
            'count theirs under mla_expanded_tokens_total.'),
        'mla_expanded_tokens': reg.counter(
            'skytpu_batch_mla_expanded_tokens_total',
            'Real prompt tokens prefilled through the expanded form '
            'of latent attention (chunk padding not counted).'),
        'decode_view_blocks': reg.counter(
            'skytpu_batch_decode_view_blocks_total',
            'Block-table columns those dispatches read: the '
            'prewarmed width that held the dispatch\'s longest '
            'active row (the whole table for a verify dispatch or a '
            'signature that was not prewarmed).'),
        'decode_table_blocks': reg.counter(
            'skytpu_batch_decode_table_blocks_total',
            'Block-table columns those dispatches had (max_seq / '
            'block_size each); decode_view_blocks_total over it is '
            'the share of the table a dispatch read.'),
        'decode_walk_blocks': reg.counter(
            'skytpu_batch_decode_walk_blocks_total',
            'Blocks a KV entry\'s attention read on decode '
            'dispatches whose program walks each row\'s own blocks '
            'in the pool (ops/decode_attention.walk_engages): the '
            'sum over the ACTIVE rows of ceil((slot_len + steps) / '
            'block_size). A dispatch that gathers a view moves '
            'neither this nor the next.'),
        'decode_walk_lane_blocks': reg.counter(
            'skytpu_batch_decode_walk_lane_blocks_total',
            'Blocks the gathered view of those dispatches would '
            'have held an entry: slots x the dispatch\'s width. '
            'decode_walk_blocks_total over it is the share of the '
            'dense view the walk still reads.'),
    }


def _adapter_metrics():
    """Adapter-serving metric families (serve/adapters/), registered
    ONLY by engines built with an adapter registry — an engine
    serving no adapters must not export fake zero series (the
    hit-ratio-gauge precedent in _engine_metrics)."""
    reg = metrics_lib.registry()
    return {
        'resident': reg.gauge(
            'skytpu_batch_adapters_resident',
            'LoRA adapters currently device-loaded in the stacked '
            'gather buffers (slot 0, the base-model identity, not '
            'counted).'),
        'capacity': reg.gauge(
            'skytpu_batch_adapters_capacity',
            'Adapter slots in the stacked gather buffers (fixed at '
            'engine build; resident == capacity means the next cold '
            'load must evict).'),
        'loads': reg.counter(
            'skytpu_batch_adapter_loads_total',
            'Adapter cold loads completed and installed into a '
            'device slot (each one had requests waiting on it or '
            'was an operator preload).'),
        'evictions': reg.counter(
            'skytpu_batch_adapter_evictions_total',
            'Resident adapters evicted (LRU over refcount-0 '
            'adapters only — a pinned, in-flight adapter is never '
            'evicted) to make room for a cold load. A high rate '
            'with a steady working set is thrash: capacity is too '
            'small for the adapter mix (the adapter-thrash alert).'),
        'load_seconds': reg.histogram(
            'skytpu_batch_adapter_load_seconds',
            'Cold-load wall time: ensure_loading kick to device '
            'install — the latency a cold-adapter request pays on '
            'top of normal queueing (its TTFT floor).'),
    }


class BatchingEngine:
    """Paged-KV continuous batching around ``decode_steps_paged``.

    ``submit()`` returns a Queue yielding generated token ids (ints)
    then ``None`` (a typed exception object precedes the ``None`` if
    the request failed). A background thread admits pending requests
    into free decode rows when the block pool has room, runs chunked
    prefill interleaved with whole-batch decode dispatches
    (``steps_per_dispatch`` tokens each), retires rows the moment
    they hit their budget (freeing their blocks), and
    preempts-and-requeues the youngest request when the pool runs
    dry.

    Knobs (service YAML ``service: engine:`` maps onto these):
    - ``slots``: decode batch width (concurrent requests).
    - ``block_size``: KV block granularity in tokens.
    - ``num_blocks``: pool size; default sizes the pool so every row
      can reach ``max_seq`` (no preemption unless oversubscribed).
    - ``window_num_blocks``: a model with window AND global layers
      keeps a second block group for the window layers' entries
      (``kv_pool.KVBlockPool``), of which a row holds only what its
      window still sees plus the chunk in flight; default: that much
      for every row.
    - ``max_num_batched_tokens``: per-scheduler-iteration prefill
      token budget — bounds how much prompt work can run between two
      decode dispatches (the chunked-prefill interleaving lever).
      With multiple tenants the budget splits by weighted deficit
      round-robin over the request ``tenant`` field.
    - ``prefill_chunk``: max tokens per prefill dispatch.
    - ``prefix_caching``: automatic block-granular prefix caching
      (default on): admission matches the prompt's hash chain,
      reuses hit blocks and prefills only the suffix — token-exact
      under greedy decoding (kv_pool.py module docstring).
    - ``speculative``: self-speculative n-gram decoding (default
      on): rows with a prompt-lookup draft verify draft_k+1 tokens
      in ONE forward (``verify_step_paged``); the acceptance rule
      (ops/sampling/accept.py — argmax match for greedy rows,
      maximal-coupling speculative sampling for sampled ones)
      keeps outputs token-for-token equal to plain decode, and an
      adaptive per-request controller collapses the draft length to
      0 on low-repeat traffic (the batch then takes the plain scan
      path). A verify row costs draft+1 of the per-iteration token
      budget, so speculation degrades before it can starve prefill.
      ``'mtp'``: the model's own next-token-prediction module
      (``config.nextn_layers``) drafts ON THE DEVICE: every decode
      dispatch is ``steps_per_dispatch`` drafting rounds
      (``decode.mtp_rounds_paged``), each verifying a row's token and
      its one pending draft and committing one or two tokens, so a
      dispatch emits between ``steps`` and ``2 x steps`` tokens a
      row. The n-gram drafter, ``draft_k`` and the adaptive
      controller are bypassed (their thresholds price a drafter that
      costs a dispatch); a round costs a row 2 of the token budget.
      Outputs stay token-for-token those of plain decode.
    - ``draft_k``: max drafted tokens per row per verify (the
      static verify width is draft_k + 1).
    - ``tenant_weights``: optional per-tenant weights for the
      fair-share budget split (absent tenants weigh 1.0).
    - ``max_queued_requests`` / ``max_queued_tokens``: bounded
      admission (service YAML ``service: overload:``): past either
      bound ``submit()`` refuses with a typed
      ``EngineOverloadedError`` carrying a drain-rate Retry-After
      (None = unbounded, the pre-overload-control behavior). An
      arriving interactive request sheds a queued batch request
      instead of being refused itself.
    - ``default_timeout_s``: deadline stamped on requests that
      carry none (None = no default). Expired requests abort typed
      (``DeadlineExceededError``) at admission or between decode
      iterations, blocks reclaimed.
    - ``sampling``: sampled decode + structured decoding
      (serve/sampling/, default on): per-request
      temperature/top_p/seed ride the jitted steps as traced
      per-row arrays under the batch-invariance contract — a
      request's output depends only on its own (seed, position)
      draws, never on batch neighbors, slot assignment, or
      preempt-resume. While every admitted row is greedy, the
      greedy executables stay byte-identical to sampling=False.
    - ``grammar_vocab``: per-token-id decoded strings (None entries
      = never-legal ids), required to serve ``response_format``
      grammars; must match the model vocab size.
    """

    @jax_runtime.stage('engine.build')
    def __init__(self, params: Params, config: llama.LlamaConfig,
                 slots: int = 8, max_seq: Optional[int] = None,
                 steps_per_dispatch: int = 8,
                 kv_int8: bool = False,
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 window_num_blocks: Optional[int] = None,
                 max_num_batched_tokens: Optional[int] = 2048,
                 prefill_chunk: int = 512,
                 prefix_caching: bool = True,
                 speculative=True,
                 draft_k: int = 8,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 max_queued_requests: Optional[int] = None,
                 max_queued_tokens: Optional[int] = None,
                 default_timeout_s: Optional[float] = None,
                 adapter_registry=None,
                 adapter_capacity: int = 0,
                 adapter_rank_bucket: int = 16,
                 adapter_preload: Optional[List[str]] = None,
                 sampling: bool = True,
                 grammar_vocab: Optional[List[Optional[str]]]
                 = None):
        self.params = params
        self.config = config
        self.slots = slots
        self.max_seq = max_seq or config.max_seq_len
        # Block-aligned: the table maps whole blocks.
        self.max_seq = -(-self.max_seq // block_size) * block_size
        self.block_size = block_size
        self.max_blocks_per_req = self.max_seq // block_size
        if num_blocks is None:
            # Default: capacity for every row to reach max_seq — the
            # no-preemption regime matching the old fixed slabs (+1
            # for the reserved scratch block). Oversubscribe by
            # passing a smaller num_blocks: admission then bounds by
            # actual usage and preemption handles the tail.
            num_blocks = slots * self.max_blocks_per_req + 1
        self.steps = steps_per_dispatch
        self.kv_int8 = kv_int8
        self.prefill_chunk = max(1, prefill_chunk)
        self.max_batched_tokens = max_num_batched_tokens
        # Automatic prefix caching (kv_pool.py module docstring):
        # admission matches the prompt's hash chain, pins hit blocks
        # and prefills only the suffix; completed full prompt blocks
        # register into the cache. Exact under greedy decoding —
        # cached K/V is precisely what re-prefilling the same prefix
        # would write.
        self.prefix_caching = prefix_caching
        # Speculative decoding (module docstring + the functions
        # above): drafting/acceptance are host-side; the device-side
        # verify width is STATIC at draft_k + 1 (shorter drafts pad
        # to scratch), so speculation adds exactly one executable.
        self._mtp = speculative == 'mtp'
        if self._mtp and not config.nextn_layers:
            raise exceptions.NotSupportedError(
                f'{config.name!r} has no next-token-prediction '
                f'module to draft with: speculative=\'mtp\' needs a '
                f'model with nextn_layers')
        self.speculative = (bool(speculative) and not self._mtp
                            and draft_k > 0)
        self.draft_k = max(0, draft_k)
        # Sampling subsystem (serve/sampling/): sampled decode +
        # structured decoding are compiled into the SAME executables
        # lazily — while every admitted row is greedy-unconstrained,
        # ``_sampling_args`` returns None and the greedy executables
        # stay byte-identical to a sampling-off engine. The mask
        # table ([slots + 1, V] bool, row 0 all-allowed) is the
        # device half of the grammar pipeline: host-side DFA walks
        # refresh one row per constrained request per emitted token,
        # the jitted steps gather rows by traced index.
        self.sampling = bool(sampling)
        self._grammar_vocab = (tuple(grammar_vocab)
                               if grammar_vocab else None)
        if self._grammar_vocab is not None and \
                len(self._grammar_vocab) != config.vocab_size:
            raise ValueError(
                f'grammar_vocab has {len(self._grammar_vocab)} '
                f'entries but the model vocab is '
                f'{config.vocab_size}')
        self._mask_table = jnp.ones(
            (slots + 1, config.vocab_size), bool) \
            if self.sampling else None
        # Engine-local cumulatives + trailing window for the
        # windowed accept-rate gauge (same shape as the prefix
        # hit-ratio window below).
        self._spec_proposed_local = 0
        self._spec_accepted_local = 0
        self._spec_window: 'collections.deque' = collections.deque()
        self._spec_ratio_gauge = None
        # Prefill tokens spent in the CURRENT scheduler iteration —
        # the verify dispatch budgets its draft grants against the
        # remainder (a verify row costs drafted+1 budget tokens).
        self._prefill_spent_iter = 0
        # Per-tenant weighted deficit round-robin over the prefill
        # token budget (fair-share QoS): deficits accrue a weighted
        # share of max_num_batched_tokens per scheduler iteration.
        self.tenant_weights = dict(tenant_weights or {})
        self._tenant_deficit: Dict[str, float] = {}
        self._tenant_rr = 0
        # Trailing-window hit-rate state (engine-local cumulatives —
        # the counter FAMILIES are process-global and shared across
        # engines): snapshots of (ts, hits, misses), ~1/s, pruned to
        # PREFIX_RATIO_WINDOW_SECONDS. The exported ratio gauge is a
        # WINDOWED rate, so a warm replica whose hits collapse (LB
        # policy misconfigured away from affinity) trips the
        # prefix-hit-ratio-low alert within the window instead of
        # being averaged away by days of cumulative history.
        self._prefix_hits_local = 0
        self._prefix_misses_local = 0
        self._prefix_window: 'collections.deque' = collections.deque()
        # Window AND global layers: a second block group, the window
        # layers' (``self.wpool``; None for every other model). A row
        # holds there at most the blocks its window can touch and
        # those of the chunk or dispatch in flight (``_window_cap``).
        two_kinds = len(set(config.layer_kinds)) > 1
        self._window_cap = 0
        if two_kinds:
            self._window_cap = min(
                self.max_blocks_per_req,
                da.window_blocks(config.sliding_window, block_size,
                                 self.max_blocks_per_req) + 1 +
                -(-max(self.prefill_chunk, steps_per_dispatch)
                  // block_size))
            if window_num_blocks is None:
                window_num_blocks = slots * self._window_cap + 1
        with jax_runtime.stage('engine.build.pool'):
            self.pool = kv_pool_lib.KVBlockPool(
                config, num_blocks, block_size, kv_int8=kv_int8,
                window_num_blocks=window_num_blocks if two_kinds else None)
            self.wpool = self.pool.groups['window'] if two_kinds else None
            # The engine owns the device arrays (they are donated through
            # every jitted step); the pool keeps only the allocator. One
            # 4-tuple, or with two groups a dict of them by kind, as the
            # paged bodies take them (``models/decode._by_kind``).
            if two_kinds:
                self.caches = {kind: g.caches
                               for kind, g in self.pool.groups.items()}
            else:
                self.caches = self.pool.caches
            for group in self.pool.groups.values():
                group.caches = None
            # The block tables live on the host and are written in place
            # (``_set_table_row``); a device program is handed a copy
            # (``_tables``). A row's update is then a numpy assignment,
            # where an eager ``.at[row].set`` of a device array cost a
            # list conversion and a dispatch of its own, many a pass.
            self.block_tables = np.full(
                (slots, self.max_blocks_per_req),
                kv_pool_lib.SCRATCH_BLOCK, np.int32)
            # The window group's table: the same logical columns, of
            # which only those a row's window still touches hold a block
            # (the rest read scratch); ``slot_wblocks`` is its host side,
            # column -> block.
            self.wblock_tables = (self.block_tables.copy() if two_kinds
                                  else None)
            self.slot_wblocks: List[Dict[int, int]] = [
                {} for _ in range(slots)]
            self.pos = jnp.zeros((slots,), jnp.int32)
            self.tokens = jnp.zeros((slots,), jnp.int32)
            # The module's pending draft of each row's next token,
            # beside its last token (``speculative='mtp'``).
            self.drafts = jnp.zeros((slots,), jnp.int32)
        # A prefilling row's carry for the module: the final-normed
        # state of its last prefilled position (a device array; None
        # ahead of the first chunk), and whether its next chunk
        # opens with a read-only lane (``forward_paged``).
        self._mtp_h: List[Any] = [None] * slots
        self._mtp_hidden = [0] * slots
        # Rows the dispatch in flight granted a draft (what its
        # rounds proposed, a round each).
        self._rounds_granted = 0
        # Host-side per-row bookkeeping.
        self.slot_req: List[Optional[_Request]] = [None] * slots
        self.slot_left = [0] * slots
        self.slot_len = [0] * slots          # written prompt+generated
        self.slot_blocks: List[List[int]] = [[] for _ in range(slots)]
        self.slot_off = [0] * slots          # prompt tokens prefilled
        self.slot_total = [0] * slots        # prompt length this pass
        self.slot_seq = [0] * slots          # admission order
        self._admit_seq = 0
        self._prefill_t0: List[Optional[float]] = [None] * slots
        self._prefill_chunks = [0] * slots
        self.pending: 'collections.deque[_Request]' = \
            collections.deque()
        self._pending_lock = threading.Lock()
        # Multi-tenant LoRA multiplexing (serve/adapters/): the
        # device-resident adapter set, each row's CURRENT gather slot
        # (0 = the all-zeros base-model identity), and the requests
        # parked waiting for a cold load to land (engine-loop-only
        # state — _poll_adapter_loads re-queues them the iteration
        # their weights arrive).
        self._adapters = None
        self._adapter_metrics = None
        self.slot_adapter = [0] * slots
        self._adapter_wait: List[_Request] = []
        if adapter_registry is not None and adapter_capacity > 0:
            if config.kv_lora_rank is not None:
                raise exceptions.NotSupportedError(
                    f'{config.name!r}: LoRA adapters attach to wq and '
                    f'wv, which a latent-attention layer does not '
                    f'have')
            from skypilot_tpu.serve.adapters import ResidentAdapterSet
            wq = params['layers']['wq']
            wv = params['layers']['wv']
            if isinstance(wq, dict):     # int8-quantized leaves
                wq, wv = wq['q'], wv['q']
            self._adapters = ResidentAdapterSet(
                adapter_registry, adapter_capacity,
                (wq.shape[0], wq.shape[1],
                 wq.shape[2], wv.shape[2]),
                rank_bucket=adapter_rank_bucket)
            self._adapter_metrics = _adapter_metrics()
            self._adapter_metrics['capacity'].set(adapter_capacity)
            if adapter_preload:
                # Synchronous, before the loop starts: a preload
                # list names adapters the operator expects live at
                # ready time — anything unusable raises HERE.
                self._adapters.preload(adapter_preload)
                self._adapter_metrics['loads'].inc(
                    self._adapters.resident_count())
        # Overload control (docs/resilience.md, Overload control):
        # bounded admission + default deadline. _queued_tokens
        # mirrors the pending queue's token content (updated under
        # _pending_lock wherever the deque mutates); _admit_times
        # feeds the drain-rate Retry-After estimate; _cancel_ids
        # holds ids handed to cancel() until the loop's sweep acts
        # on them at the next iteration boundary.
        self.max_queued_requests = max_queued_requests
        self.max_queued_tokens = max_queued_tokens
        self.default_timeout_s = default_timeout_s
        self._queued_tokens = 0
        self._admit_times: 'collections.deque' = collections.deque(
            maxlen=256)
        self._cancel_ids: set = set()
        # Scheduler event log (bounded) — the chunked-prefill
        # interleaving contract is asserted against this in tests.
        self.events: 'collections.deque' = collections.deque(
            maxlen=4096)
        # The iteration account (skytpu_batch_iteration*/host_gap):
        # _gap_open is the perf_counter instant since which the
        # device is known to have nothing queued (None while work is
        # in flight or the loop is parked).
        self._iter_n = 0
        self._gap_open: Optional[float] = None
        self.wake = threading.Event()
        self._stop = False
        # Set on engine DEATH (never on clean close): submits after
        # the loop died get this pushed ahead of their sentinel.
        self._death_exc: Optional[BaseException] = None
        self._step_fn = jax.jit(decode_steps_paged,
                                static_argnums=(6, 7, 8),
                                static_argnames=('view_blocks',),
                                donate_argnums=(2,))
        self._verify_fn = jax.jit(verify_step_paged,
                                  static_argnums=(6, 7, 8),
                                  donate_argnums=(2,))
        self._prefill_fn = jax.jit(forward_paged,
                                   static_argnums=(6, 7),
                                   donate_argnums=(2,))
        self._rounds_fn = jax.jit(mtp_rounds_paged,
                                  static_argnums=(8, 9, 10),
                                  static_argnames=('view_blocks',),
                                  donate_argnums=(3,))
        self._mtp_first_fn = jax.jit(mtp_first_paged,
                                     static_argnums=(6, 7),
                                     donate_argnums=(3,))
        # First-token selection from the final prefill chunk's
        # logits for sampled/constrained rows — keyed at position
        # t0 - 1 (the last prompt token's index), so the
        # prompt/decode boundary is invisible to the (seed,
        # position) contract. Greedy rows keep the host argmax.
        self._first_fn = jax.jit(sample_lib.sample_first)
        # COW primitive: duplicate a cached block before diverging
        # writes (src/dst traced — one executable for every copy).
        self._copy_fn = jax.jit(kv_pool_lib.copy_pool_block,
                                donate_argnums=(0,))
        if self.prefix_caching and self.wpool is None:
            # Prewarm the copy executable (scratch onto itself is a
            # no-op) so the FIRST partial-block hit in production
            # does not pay the compile inside a request's TTFT. (With
            # a window group a hit is whole blocks only: no copy.)
            with jax_runtime.stage('engine.build.prewarm_copy'):
                scratch = jnp.asarray(kv_pool_lib.SCRATCH_BLOCK,
                                      jnp.int32)
                self.caches = self._copy_fn(self.caches, scratch,
                                            scratch)
        if self.speculative:
            # Prewarm the verify executable (n_real 0 everywhere:
            # every write lands in scratch, outputs discarded) — the
            # first live draft must not pay the compile inside a
            # request's decode window (same rationale as the COW
            # prewarm above; the verify width is static, so this is
            # THE executable).
            # Under the live call's own keywords (``_launch_verify``
            # passes ``sampling=``, None while every row is greedy): a
            # call that leaves the keyword out is another signature,
            # and the first live verify lowered the step anew.
            with jax_runtime.stage('engine.build.prewarm_verify'):
                *_, self.caches = self._verify_fn(
                    self.params,
                    np.zeros((slots, self.draft_k + 1), np.int32),
                    self.caches, self._tables(), self.pos,
                    np.zeros((slots,), np.int32), self.config,
                    self.draft_k + 1, self.block_size,
                    *self._adapter_args(), sampling=None)
        # Prewarm the decode executable at every width of the table
        # a dispatch may read (``_view_blocks``), through the call
        # the live dispatch makes: a width first met inside a
        # request's decode window would stall it for a compilation.
        # No row is active, so every write lands in scratch and the
        # outputs are discarded.
        self._view_widths = da.view_widths(self.max_blocks_per_req)
        # Whether those programs walk each row's own blocks in the
        # pool (the rule the step itself asks): what the two
        # decode_walk counters are counted under.
        self._walks = da.walk_engages(
            self.block_size, config.n_kv_heads, config.head_dim,
            codes=kv_int8, positions=1, window=None)
        with jax_runtime.stage('engine.build.prewarm_decode') as warm:
            for width in self._view_widths:
                with jax_runtime.stage(
                        'engine.build.prewarm_decode.width',
                        width=width):
                    if self._mtp:
                        # The rounds are this engine's only decode
                        # program, under one signature (sampled rows
                        # and greedy ones ride the same knob arrays).
                        self._enqueue_rounds(
                            [False] * slots, [False] * slots, width)
                        continue
                    _, self.caches, *_ = self._step_fn(
                        self.params, self.tokens, self.caches,
                        self._tables(), self.pos,
                        jnp.zeros((slots,), bool), self.config,
                        self.steps, self.block_size,
                        *self._adapter_args(),
                        sampling=None, view_blocks=width)
            if self._mtp:
                # A request's first draft and, for sampled rows, its
                # first token: one executable each, every write to
                # scratch.
                self._first_draft(
                    jnp.zeros((1, 1, config.dim), config.dtype), 0,
                    np.full((self.max_blocks_per_req,),
                            kv_pool_lib.SCRATCH_BLOCK, np.int32), 0,
                    0.0, 1.0, 0)
                if self.sampling:
                    self._first_fn(
                        jnp.zeros((1, config.vocab_size), jnp.float32),
                        jnp.asarray(1.0, jnp.float32),
                        jnp.asarray(1.0, jnp.float32),
                        jnp.asarray(0, jnp.int32),
                        jnp.asarray(0, jnp.int32), None)
            jax.block_until_ready(self.caches)
        logger.info('Decode step prewarmed at %d table widths %s '
                    '(blocks) in %.1f s.', len(self._view_widths),
                    list(self._view_widths), warm.seconds)
        self._metrics = _engine_metrics()
        # Lazily created on first real traffic (MFU-gauge precedent):
        # an engine with caching off must not export a fake 0 ratio.
        self._hit_ratio_gauge = None
        self._metrics['slots'].set(slots)
        groups = self.pool.groups.values()
        self._cache_bytes = sum(g.nbytes for g in groups)
        self._metrics['kv_bytes'].set(self._cache_bytes)
        self._metrics['kv_blocks_total'].set(
            sum(g.usable_blocks for g in groups))
        self._metrics['kv_token_bytes'].set(
            sum(g.token_bytes for g in groups))
        if self.wpool is not None:
            self._metrics['kv_window_blocks_total'].set(
                self.wpool.usable_blocks)
        # Pairs the expert layers routed, as device arrays until the
        # next emit adds them to the counters (``_count_routed``),
        # and which grouped product every program of this engine
        # takes for them (static: platform, types, widths).
        self._routed_pending: list = []
        self._pairs_tiled = bool(self.config.n_experts) and \
            moe.pairs_tiled(self.params['layers'],
                            self.params['embed'].dtype)
        from skypilot_tpu.utils import profiling as profiling_lib
        self._profiler = profiling_lib.StepProfiler('decode')
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    # -- client API -----------------------------------------------------

    def submit(self, prompt_ids: List[int], max_new: int,
               eos_id: Optional[int] = None,
               tenant: Optional[str] = None,
               deadline: Optional[float] = None,
               priority: str = 'interactive',
               adapter: Optional[str] = None,
               temperature: float = 0.0,
               top_p: float = 1.0,
               seed: int = 0,
               response_format: Optional[dict] = None
               ) -> 'queue.Queue':
        """Returns a Queue yielding generated ids then None. With
        ``eos_id``, the row retires the moment it emits that id
        (the EOS itself is emitted, matching greedy_generate). A
        request the pool can never hold yields a typed
        ``KVPoolExhaustedError`` before its None; a refused
        (bounded-admission) request a typed ``EngineOverloadedError``
        and an expired one a typed ``DeadlineExceededError``.
        ``temperature > 0`` samples with counter-keyed randomness
        ((seed, position) — batch-invariant, serve/sampling/);
        ``response_format`` ({'type': 'json_schema'|'regex', ...})
        constrains decoding to the grammar (requires the engine's
        ``grammar_vocab`` and a ``eos_id``; a bad grammar yields a
        typed ``GrammarError`` before the None)."""
        return self.submit_request(prompt_ids, max_new,
                                   eos_id=eos_id, tenant=tenant,
                                   deadline=deadline,
                                   priority=priority,
                                   adapter=adapter,
                                   temperature=temperature,
                                   top_p=top_p, seed=seed,
                                   response_format=response_format
                                   ).out

    def submit_request(self, prompt_ids: List[int], max_new: int,
                       eos_id: Optional[int] = None,
                       tenant: Optional[str] = None,
                       deadline: Optional[float] = None,
                       priority: str = 'interactive',
                       adapter: Optional[str] = None,
                       temperature: float = 0.0,
                       top_p: float = 1.0,
                       seed: int = 0,
                       response_format: Optional[dict] = None
                       ) -> _Request:
        """``submit`` returning the request object itself: ``.out``
        is the token queue, ``.id`` is the handle ``cancel()``
        takes, and after admission (i.e. by the first token)
        ``.prefix_hit_blocks``/``.prefix_miss_blocks`` carry the
        prefix-cache accounting serve_model exports as response
        headers. ``deadline`` is an absolute epoch second (None
        falls back to the engine's ``default_timeout_s``)."""
        if priority not in PRIORITIES:
            raise ValueError(f'priority must be one of {PRIORITIES},'
                             f' got {priority!r}')
        # Knob validation raises at the call site (caller bugs, the
        # ``priority`` precedent) — serve_model validates the HTTP
        # body itself so a bad field answers a typed 400 naming it.
        if not isinstance(seed, int) or isinstance(seed, bool):
            raise ValueError(
                f'seed must be an integer, got {seed!r}')
        # The PRNG keys on uint32(seed) (ops/sampling/prng.py), so
        # any Python int is taken mod 2**32 — stored as the int32
        # two's-complement of that value because the per-row knob
        # arrays pack as int32 (an unmasked 2**31+ seed would
        # OverflowError INSIDE the scheduler thread and kill the
        # engine; seeds < 2**31 keep their bit pattern, so existing
        # outputs are unchanged).
        seed &= 0xFFFFFFFF
        if seed >= 1 << 31:
            seed -= 1 << 32
        temperature = float(temperature)
        top_p = float(top_p)
        if temperature < 0.0:
            raise ValueError(
                f'temperature must be >= 0, got {temperature}')
        if not 0.0 < top_p <= 1.0:
            raise ValueError(
                f'top_p must be in (0, 1], got {top_p}')
        if not self.sampling and (temperature > 0.0
                                  or response_format is not None):
            raise ValueError(
                'this engine was built with sampling=False and '
                'cannot serve sampled or constrained requests')
        if deadline is None and self.default_timeout_s is not None:
            deadline = time.time() + self.default_timeout_s
        max_new = min(max_new,
                      self.max_seq - len(prompt_ids) - 1)
        req = _Request(list(prompt_ids), max(0, max_new),
                       eos_id=eos_id, tenant=tenant,
                       deadline=deadline, priority=priority,
                       adapter=adapter, temperature=temperature,
                       top_p=top_p, seed=seed,
                       response_format=response_format)
        if response_format is not None:
            # Compile (cached by grammar hash) synchronously: a bad
            # grammar must refuse typed at submit, before any KV is
            # touched — the adapter-refusal precedent. serve_model
            # maps GrammarError to 400.
            try:
                if self._mtp:
                    raise grammar_lib.GrammarError(
                        'this engine drafts with the model\'s '
                        'next-token-prediction module, and a draft '
                        'under a grammar mask is not implemented')
                if self._grammar_vocab is None:
                    raise grammar_lib.GrammarError(
                        'this engine serves no structured decoding '
                        '(start it with a grammar_vocab to serve '
                        'response_format requests)')
                if eos_id is None:
                    raise grammar_lib.GrammarError(
                        'response_format requires an eos_id (the '
                        'grammar decides completion by allowing '
                        'EOS only at accepting states)')
                req.grammar = grammar_lib.compile_grammar(
                    response_format, self._grammar_vocab, eos_id)
            except grammar_lib.GrammarError as e:
                self._fail_request(
                    req, f'response_format refused: {e}', exc=e)
                return req
        if adapter is not None:
            # Typed refusal at submit for adapters this engine can
            # NEVER serve: no adapter subsystem at all, an unknown
            # id, or a rank over the gather bucket (serve_model maps
            # AdapterNotFoundError to 404, AdapterCapacityError to
            # 413). Residency is NOT required here — a known adapter
            # cold-loads asynchronously and the request is admitted
            # the iteration its weights land.
            try:
                if self._adapters is None:
                    raise exceptions.AdapterCapacityError(
                        'this engine serves no adapters (start it '
                        'with an adapter registry and capacity >= 1 '
                        'to serve LoRA requests)')
                self._adapters.check_fits(adapter)
            except exceptions.AdapterError as e:
                self._fail_request(
                    req, f'adapter {adapter!r} refused: {e}', exc=e)
                return req
        if req.deadline is not None and time.time() >= req.deadline:
            # Already past its deadline at submit: refusing NOW is
            # strictly better than queueing work whose answer nobody
            # is waiting for (the admission-time deadline check,
            # taken at its earliest possible point).
            self._metrics['deadline_exceeded'].inc()
            self._fail_request(
                req, 'deadline expired before admission',
                exc=exceptions.DeadlineExceededError(
                    'deadline expired before admission'))
            return req
        if req.max_new == 0 or self._stop:
            # A DEAD engine (not a clean close / zero-budget
            # request) fails post-death submits typed: serve_model
            # answers the exception 500, which the replica-5xx-rate
            # page needs — a bare sentinel would read as a clean
            # empty 200 from a replica that can never serve again.
            if self._stop and self._death_exc is not None:
                req.out.put(self._death_exc)
            req.out.put(None)
            return req
        if self.pool.blocks_for(len(prompt_ids) + 1) > \
                self.pool.usable_blocks:
            # This prompt alone exceeds the whole pool: fail THIS
            # request, typed, immediately — transient exhaustion is
            # handled by preempt-and-requeue instead.
            self._fail_request(
                req, f'prompt of {len(prompt_ids)} tokens needs '
                f'{self.pool.blocks_for(len(prompt_ids) + 1)} KV '
                f'blocks but the pool has only '
                f'{self.pool.usable_blocks} usable '
                f'(block_size={self.block_size})')
            return req
        cost = len(req.prompt_ids)
        victim = None
        with self._pending_lock:
            reason = self._shed_reason(cost)
            if reason is not None and req.priority == 'interactive':
                # Shedding takes batch first: an arriving
                # interactive request evicts the YOUNGEST queued
                # batch request rather than being refused itself.
                victim = self._evict_queued_batch()
                if victim is not None:
                    reason = None
            if reason is not None:
                retry_after = self._retry_after_locked()
            else:
                self.pending.append(req)
                self._queued_tokens += cost
        if victim is not None:
            self._metrics['shed'].labels(
                reason='priority_evict').inc()
            self._fail_request(
                victim, 'shed from the pending queue to admit an '
                'interactive request',
                exc=exceptions.EngineOverloadedError(
                    'shed from the pending queue to admit an '
                    'interactive request',
                    retry_after_s=self._retry_after()))
        if reason is not None:
            self._metrics['shed'].labels(reason=reason).inc()
            self._fail_request(
                req, f'pending queue full ({reason})',
                exc=exceptions.EngineOverloadedError(
                    f'pending queue full ({reason})',
                    retry_after_s=retry_after))
            return req
        self.wake.set()
        # close()/death may have stopped the loop between the _stop
        # check above and the append — the exited loop will never
        # drain this request, so sentinel it here (a double None
        # from racing _drain_all is harmless: consumers stop at the
        # first; same typed-death rule as the early return above).
        if self._stop:
            if self._death_exc is not None:
                req.out.put(self._death_exc)
            req.out.put(None)
        return req

    def generate(self, prompt_ids: List[int], max_new: int,
                 eos_id: Optional[int] = None,
                 tenant: Optional[str] = None) -> List[int]:
        """Blocking convenience: collect the full generation. Raises
        the typed error if the request failed."""
        q = self.submit(prompt_ids, max_new, eos_id=eos_id,
                        tenant=tenant)
        out: List[int] = []
        while True:
            tok = q.get()
            if tok is None:
                return out
            if isinstance(tok, BaseException):
                raise tok
            out.append(tok)

    def cancel(self, request_id) -> None:
        """Tear down an in-flight or queued request: its KV blocks
        are freed at the next iteration boundary through the exact
        reclaim path preemption uses, and its token queue gets the
        None sentinel so any residual reader unblocks. Accepts the
        ``_Request`` from ``submit_request`` or its ``.id``.
        Cancelling an unknown or already-finished request is a
        no-op — the client is gone either way."""
        if isinstance(request_id, _Request):
            request_id.cancelled = True
        else:
            with self._pending_lock:
                self._cancel_ids.add(request_id)
        self.wake.set()

    def close(self):
        self._stop = True
        self.wake.set()
        self.thread.join(timeout=10)
        if self.thread.is_alive():
            # A wedged dispatch (hung device call) is holding the
            # loop past the join timeout: returning silently would
            # hide a live thread still mutating engine state. Count
            # + log so operators see it (satellite of ISSUE 17).
            self._metrics['loop_hang'].inc()
            logger.error(
                'Batching engine loop thread still alive after '
                'close() join timeout — a dispatch is likely '
                'wedged; the daemon thread dies with the process.')

    # -- scheduling helpers ---------------------------------------------

    @staticmethod
    def _queue_cost(req: _Request) -> int:
        """Tokens this PENDING request will prefill when admitted —
        prompt plus any resume (preempted-and-requeued) tokens; the
        currency of the max_queued_tokens bound. Stable while the
        request sits in the queue (``generated`` only grows while
        admitted), so append/pop accounting stays symmetric."""
        return len(req.prompt_ids) + len(req.generated)

    def _pop_pending(self) -> Optional[_Request]:
        with self._pending_lock:
            try:
                req = self.pending.popleft()
            except IndexError:
                return None
            self._queued_tokens -= self._queue_cost(req)
            return req

    def _push_front(self, req: _Request) -> None:
        with self._pending_lock:
            self.pending.appendleft(req)
            self._queued_tokens += self._queue_cost(req)

    def _adapter_args(self, idx: Optional[List[int]] = None) -> tuple:
        """Trailing ``(adapters, adapter_idx)`` args for the jitted
        decode/prefill/verify steps. EMPTY when adapter serving is
        off — the calls then hit the ``adapters=None`` defaults and
        the adapterless executables stay byte-identical to an engine
        built without a registry (no gather, no numeric change).
        ``idx`` defaults to the whole batch's per-row slots; prefill
        passes its single row's ``[slot]``."""
        if self._adapters is None:
            return ()
        if idx is None:
            idx = self.slot_adapter
        return (self._adapters.buffers(),
                jnp.asarray(idx, jnp.int32))

    def _sampling_needed(self) -> bool:
        return self.sampling and any(
            r is not None and (r.temperature > 0.0
                               or r.grammar is not None)
            for r in self.slot_req)

    def _knob_arrays(self):
        """Per-slot ``temps`` / ``top_ps`` / ``seeds`` as the jitted
        steps take them — empty rows get greedy-neutral values;
        their lanes are inactive/parked so the draws are never
        emitted."""
        reqs = self.slot_req
        return {
            'temps': jnp.asarray(
                [r.temperature if r is not None else 0.0
                 for r in reqs], jnp.float32),
            'top_ps': jnp.asarray(
                [r.top_p if r is not None else 1.0 for r in reqs],
                jnp.float32),
            'seeds': jnp.asarray(
                [r.seed if r is not None else 0 for r in reqs],
                jnp.int32)}

    def _sampling_args(self):
        """Traced ``sampling`` kwarg for the jitted decode steps —
        None while every admitted row is greedy-unconstrained, so
        the greedy executables stay byte-identical to a
        sampling-off engine (the ``_adapter_args`` precedent).
        Knobs are per-row DATA: one sampled executable serves every
        request mix; constrained rows point ``mask_idx`` at their
        slot's row of the persistent device mask table."""
        if not self._sampling_needed():
            return None
        idx = [i + 1 if self.slot_req[i] is not None
               and self.slot_req[i].grammar is not None else 0
               for i in range(self.slots)]
        return dict(self._knob_arrays(),
                    mask_table=self._mask_table,
                    mask_idx=jnp.asarray(idx, jnp.int32))

    def _verify_sampling_args(self, toks: List[List[int]],
                              n_real: List[int]):
        """``sampling`` kwarg for the verify step: same knobs, but
        grammar masks are PER-POSITION ([M, W, V]) — row r's mask
        at lane j is the DFA state after consuming its drafts
        1..j, walked host-side along the (grammar-filtered) draft
        path. With no constrained row active the table collapses
        to the shared all-allowed row ([1, W, V], every index 0)."""
        if not self._sampling_needed():
            return None
        w = self.draft_k + 1
        con = [i for i in range(self.slots)
               if self.slot_req[i] is not None
               and self.slot_req[i].grammar is not None]
        if not con:
            table = np.ones((1, w, self.config.vocab_size), bool)
            idx = [0] * self.slots
        else:
            table = np.ones(
                (self.slots + 1, w, self.config.vocab_size), bool)
            idx = [0] * self.slots
            for i in con:
                req = self.slot_req[i]
                idx[i] = i + 1
                if n_real[i] <= 0:
                    continue
                st = req.grammar_state
                table[i + 1, 0] = req.grammar.allowed(st)
                for j in range(1, n_real[i]):
                    st = req.grammar.advance(st, toks[i][j])
                    table[i + 1, j] = req.grammar.allowed(st)
        return dict(self._knob_arrays(),
                    mask_table=jnp.asarray(table),
                    mask_idx=jnp.asarray(idx, jnp.int32))

    def _refresh_mask_row(self, row: int) -> None:
        """Push the row's current grammar mask into the device mask
        table (the host half of the structured-decoding pipeline —
        one [V] upload per constrained row per emitted token)."""
        req = self.slot_req[row]
        if req is None or req.grammar is None:
            return
        self._mask_table = self._mask_table.at[row + 1].set(
            jnp.asarray(req.grammar.allowed(req.grammar_state)))

    def _filter_draft_grammar(self, req: _Request,
                              draft: List[int]) -> List[int]:
        """Truncate an n-gram draft at the first token the request's
        grammar disallows — a disallowed draft could never be
        emitted (the verify mask forces the target realization off
        it), so carrying it would only burn verify lanes."""
        st = req.grammar_state
        out: List[int] = []
        for t in draft:
            if not req.grammar.allowed(st)[t]:
                break
            st = req.grammar.advance(st, t)
            out.append(t)
        return out

    def _shed_reason(self, cost: int) -> Optional[str]:
        """Which admission bound a ``cost``-token arrival would
        trip (None = admit). Caller holds ``_pending_lock``. An
        empty queue always admits regardless of the token bound —
        one oversized request must degrade to FIFO progress, not a
        permanent typed refusal (the DRR budget has the same
        first-chunk overdraft rule)."""
        n_q = len(self.pending)
        if self.max_queued_requests is not None \
                and n_q >= self.max_queued_requests:
            return 'max_queued_requests'
        if self.max_queued_tokens is not None and n_q > 0 \
                and self._queued_tokens + cost > \
                self.max_queued_tokens:
            return 'max_queued_tokens'
        return None

    def _evict_queued_batch(self) -> Optional[_Request]:
        """Remove and return the YOUNGEST queued batch-priority
        request (None if the queue holds only interactive ones).
        Caller holds ``_pending_lock``."""
        for idx in range(len(self.pending) - 1, -1, -1):
            cand = self.pending[idx]
            if cand.priority == 'batch':
                del self.pending[idx]
                self._queued_tokens -= self._queue_cost(cand)
                return cand
        return None

    def _retry_after_locked(self) -> float:
        """Retry-After estimate from the recent admission drain
        rate: queue depth / admissions-per-second over the trailing
        30 s, clamped to [1, 60]. Caller holds ``_pending_lock``."""
        now = time.time()
        times = [t for t in self._admit_times if t > now - 30.0]
        if len(times) >= 2 and now > times[0]:
            rate = len(times) / (now - times[0])
            est = (len(self.pending) + 1) / max(rate, 1e-6)
        else:
            est = 1.0
        return min(60.0, max(1.0, est))

    def _retry_after(self) -> float:
        with self._pending_lock:
            return self._retry_after_locked()

    def _fail_request(self, req: _Request, msg: str,
                      exc: Optional[BaseException] = None) -> None:
        """Typed per-request failure: the REQUEST fails; every other
        in-flight request keeps decoding (never ``_fail_all``).
        ``exc`` overrides the default ``KVPoolExhaustedError``
        (deadline / overload refusals carry their own types)."""
        logger.warning('Batching engine failing request: %s', msg)
        req.out.put(exc if exc is not None
                    else exceptions.KVPoolExhaustedError(msg))
        req.out.put(None)

    def _set_table_row(self, row: int) -> None:
        blocks = self.slot_blocks[row]
        table = self.block_tables[row]
        table[:len(blocks)] = blocks
        table[len(blocks):] = kv_pool_lib.SCRATCH_BLOCK

    def _set_wtable_row(self, row: int) -> None:
        held = self.slot_wblocks[row]
        table = self.wblock_tables[row]
        table[:] = kv_pool_lib.SCRATCH_BLOCK
        if held:
            table[list(held)] = list(held.values())

    def _tables(self, row: Optional[int] = None):
        """The block tables as the paged bodies take them: one array
        (one row of it for a prefill chunk), or with a window group a
        dict of the two by kind. Copies: a program's transfer may
        read its argument after the call returns, and the engine
        writes the tables in place."""
        pick = (np.copy if row is None
                else (lambda t: t[row].copy()))
        if self.wpool is None:
            return pick(self.block_tables)
        return {'global': pick(self.block_tables),
                'window': pick(self.wblock_tables)}

    def _free_window(self, row: int, cols) -> None:
        """Give the row's window-group blocks at ``cols`` back (a
        decrement where a block is shared), deepest first."""
        held = self.slot_wblocks[row]
        cols = sorted(cols, reverse=True)
        if cols:
            self.wpool.free([held.pop(c) for c in cols])
            self._set_wtable_row(row)

    def _release_behind(self, row: int, next_pos: int) -> None:
        """Release the row's window-group blocks that lie behind the
        window of every query to come, the next standing at
        ``next_pos``."""
        if self.wpool is None:
            return
        first = kv_pool_lib.first_window_block(
            next_pos, self.config.sliding_window, self.block_size)
        behind = [c for c in self.slot_wblocks[row] if c < first]
        if behind:
            self._metrics['kv_window_released'].inc(len(behind))
            self._free_window(row, behind)

    def _ensure_window(self, row: int, lo: int, hi: int) -> bool:
        """Window-group blocks for the row's positions [lo, hi), the
        chunk or dispatch about to be written; exhaustion preempts
        as ``_ensure_blocks`` does. False if the row itself went."""
        if self.wpool is None or hi <= lo:
            return True
        held = self.slot_wblocks[row]
        cols = [c for c in range(lo // self.block_size,
                                 (hi - 1) // self.block_size + 1)
                if c not in held]
        if not cols:
            return True
        got = self._alloc_or_preempt(
            row, self.wpool, len(cols),
            f'request needs {len(cols)} more window-group KV blocks '
            f'but that group has only {self.wpool.usable_blocks} '
            f'usable')
        if got is None:
            return False
        held.update(zip(cols, got))
        self._set_wtable_row(row)
        return True

    def _alloc_or_preempt(self, row: int, group, n: int,
                          hopeless: str) -> Optional[List[int]]:
        """``n`` blocks of ``group`` for ``row``, preempting the
        youngest request for as long as the group is dry. None if the
        row itself was preempted, or failed with ``hopeless`` because
        it is the only admitted request and still cannot grow."""
        while True:
            got = group.try_alloc(n)
            if got is not None:
                return got
            victim = self._pick_victim()
            if victim is None:
                req = self.slot_req[row]
                self._release_row(row)
                self._fail_request(req, hopeless)
                return None
            self._preempt(victim)
            if victim == row:
                return None

    def _release_row(self, row: int) -> None:
        req = self.slot_req[row]
        if self._adapters is not None and req is not None \
                and req.adapter is not None \
                and self.slot_adapter[row] != 0:
            # Drop the admission-time pin: the last in-flight row of
            # an adapter makes it evictable again (still resident —
            # the warm end of the LRU, so repeat traffic re-pins it
            # without a cold load).
            self._adapters.unpin(req.adapter)
        self.slot_adapter[row] = 0
        if self.slot_blocks[row]:
            # One decrement per held block — shared (pinned) prefix
            # blocks stay alive for their other holders. DEEPEST
            # first: released chains enter the cached LRU leaf-first,
            # so eviction peels chains from the tail instead of
            # orphaning descendants by evicting their parent.
            self.pool.free(list(reversed(self.slot_blocks[row])))
        if self.slot_wblocks[row]:
            self._free_window(row, list(self.slot_wblocks[row]))
        self.slot_blocks[row] = []
        self.slot_req[row] = None
        self.slot_left[row] = 0
        self._set_table_row(row)  # stale entries must not alias
        #                           blocks recycled to other rows

    def _retire(self, row: int) -> None:
        self._release_row(row)

    def _preempt(self, row: int) -> None:
        """Reclaim the row's blocks and requeue its request at the
        FRONT of the pending queue (it keeps its original submit
        time, so it ages toward never-preempted oldest)."""
        req = self.slot_req[row]
        assert req is not None
        req.preemptions += 1
        self._metrics['preemptions'].inc()
        self.events.append(('preempt', row, len(req.generated)))
        logger.info(
            'KV pool exhausted: preempting request in row %d '
            '(%d blocks reclaimed, %d tokens generated so far; '
            'resume recomputes from prompt+generated).',
            row, len(self.slot_blocks[row]), len(req.generated))
        self._release_row(row)
        self._push_front(req)

    def _pick_victim(self) -> Optional[int]:
        """The LOWEST-PRIORITY-YOUNGEST admitted row: every batch-
        class row is preempted before any interactive one, and
        within a class the youngest goes first (latest original
        submit time; admission order breaks ties). The oldest
        request of the highest admitted class is thereby never
        preempted while any other row exists — preempted requests
        keep their submit time, so they age into that protection
        and cannot starve."""
        rows = [i for i in range(self.slots)
                if self.slot_req[i] is not None]
        if len(rows) <= 1:
            return None
        return max(rows, key=lambda i: (
            PRIORITIES.index(self.slot_req[i].priority),
            self.slot_req[i].submitted_at, self.slot_seq[i]))

    def _ensure_blocks(self, row: int, target_tokens: int) -> bool:
        """Grow the row's allocation to cover ``target_tokens``
        positions, preempting the youngest request on exhaustion.
        Returns False if the row itself was preempted or failed."""
        need = self.pool.blocks_for(target_tokens)
        extra = need - len(self.slot_blocks[row])
        if extra <= 0:
            return True
        got = self._alloc_or_preempt(
            row, self.pool, extra,
            f'request needs {need} KV blocks but the pool has only '
            f'{self.pool.usable_blocks} usable '
            f'(block_size={self.block_size})')
        if got is None:
            return False
        self.slot_blocks[row].extend(got)
        self._set_table_row(row)
        return True

    # -- engine loop ----------------------------------------------------

    def _match_prefix(self, req: _Request, tokens_all: List[int],
                      t0: int):
        """Prefix-cache lookup for an admission: returns
        (pinned_blocks, cow, cached_tokens, window_hit) where
        ``pinned_blocks`` are the full-block chain hits (already
        pinned), ``cow`` is an optional (src_block, shared_tokens)
        partial hit past them and ``window_hit`` the window group's
        pinned blocks by column ({} without that group). Reuse is
        capped at t0 - 1 tokens: the LAST prompt token is always
        recomputed so its logits seed decoding.
        The computed chain is stashed on the request for
        ``_register_prefix`` to reuse."""
        if not self.prefix_caching or t0 < 2:
            return [], None, 0, {}
        if req.chain_t0 == t0 and req.chain_hashes:
            # Re-admission of a request requeued by
            # _unwind_admission (pool momentarily full): the token
            # stream is unchanged, so the stashed chain is still
            # valid — don't re-hash the whole prompt on every
            # scheduler iteration while waiting for blocks. A
            # preemption resume has grown ``generated`` (t0
            # changed) and recomputes.
            hashes = req.chain_hashes
        else:
            # Adapter-salted root: KV content depends on the
            # adapter (the v projection carries its LoRA delta), so
            # per-adapter chains must never alias each other or the
            # base model's (prefix_hash.adapter_root).
            hashes = kv_pool_lib.chain_hashes(
                tokens_all, self.block_size,
                root=prefix_hash.adapter_root(req.adapter))
            req.chain_hashes = hashes
            req.chain_t0 = t0
        matched = self.pool.match(hashes)
        max_reuse_blocks = (t0 - 1) // self.block_size
        matched = matched[:max_reuse_blocks]
        if self.wpool is not None:
            # A hit is usable only as far as the window group still
            # holds what a query after it can see; whole blocks.
            at = self.wpool.lookup(hashes[:len(matched)])
            k = kv_pool_lib.usable_prefix(
                [b is not None for b in at],
                self.config.sliding_window, self.block_size)
            first = kv_pool_lib.first_window_block(
                k * self.block_size, self.config.sliding_window,
                self.block_size)
            window_hit = {c: at[c] for c in range(first, k)}
            self.pool.pin(matched[:k])
            self.wpool.pin(list(window_hit.values()))
            return matched[:k], None, k * self.block_size, window_hit
        cached_tokens = len(matched) * self.block_size
        parent = hashes[len(matched) - 1] if matched \
            else prefix_hash.adapter_root(req.adapter)
        cow = None
        rest = tokens_all[cached_tokens:
                          min(cached_tokens + self.block_size,
                              t0 - 1)]
        if rest:
            cow = self.pool.partial_match(parent, rest)
        if matched:
            self.pool.pin(matched)
        return matched, cow, cached_tokens, {}

    def _unwind_admission(self, req: _Request, blocks: List[int],
                          window_hit: Optional[Dict[int, int]] = None
                          ) -> None:
        """Admission could not complete (pool momentarily full):
        release whatever was pinned/allocated — exactly once — and
        requeue the request at the front to retry after
        retirements free capacity."""
        if blocks:
            self.pool.free(list(reversed(blocks)))
        if window_hit:
            self.wpool.free([window_hit[c] for c in
                             sorted(window_hit, reverse=True)])
        self._push_front(req)

    def _poll_adapter_loads(self) -> None:
        """Engine-loop tick for the adapter subsystem: install
        completed cold loads into device slots, account
        loads/evictions/latency, fail requests whose load failed
        (typed), sweep cancelled/expired waiters, and re-queue the
        requests whose adapter just became resident — at the FRONT,
        preserving their order (they already waited once)."""
        if self._adapters is None:
            return
        ready, evicted, durations = self._adapters.poll()
        if ready:
            self._adapter_metrics['loads'].inc(len(ready))
            for s in durations:
                self._adapter_metrics['load_seconds'].observe(s)
            self.events.append(('adapter_load', tuple(ready)))
        if evicted:
            self._adapter_metrics['evictions'].inc(len(evicted))
            self.events.append(('adapter_evict', tuple(evicted)))
        if not self._adapter_wait:
            return
        now = time.time()
        failures: Dict[str, BaseException] = {}
        still_waiting: List[_Request] = []
        admit: List[_Request] = []
        for req in self._adapter_wait:
            if req.cancelled:
                self._metrics['cancelled'].inc()
                req.out.put(None)
                continue
            if req.deadline is not None and now >= req.deadline:
                self._metrics['deadline_exceeded'].inc()
                self._fail_request(
                    req, 'deadline expired waiting for adapter '
                    'cold load',
                    exc=exceptions.DeadlineExceededError(
                        'deadline expired waiting for adapter '
                        f'{req.adapter!r} to load'))
                continue
            if req.adapter not in failures:
                exc = self._adapters.take_failure(req.adapter)
                if exc is not None:
                    failures[req.adapter] = exc if isinstance(
                        exc, exceptions.AdapterError) else \
                        exceptions.AdapterError(
                            f'adapter {req.adapter!r} failed to '
                            f'load: {exc!r}')
            if req.adapter in failures:
                self._fail_request(
                    req, f'adapter {req.adapter!r} cold load '
                    'failed', exc=failures[req.adapter])
                continue
            if self._adapters.slot(req.adapter) is not None:
                admit.append(req)
            else:
                # Not resident, not failed: either still loading or
                # its parked install lost a slot race — re-kick
                # (idempotent) and keep waiting.
                self._adapters.ensure_loading(req.adapter)
                still_waiting.append(req)
        self._adapter_wait = still_waiting
        for req in reversed(admit):
            self._push_front(req)

    def _admit_pending(self) -> None:
        """Token-budget admission: a request is admitted when a
        decode row is free AND the pool has blocks for its whole
        prompt (+1 for the first generated token) — free blocks, not
        free slots, are the admission currency. With prefix caching,
        the prompt's hash chain is matched first: hit blocks are
        PINNED (refcount++) and only the suffix past them is
        prefilled — repeat prefixes skip their prefill entirely."""
        for row in range(self.slots):
            if self._stop:
                return
            if self.slot_req[row] is not None:
                continue
            req = self._pop_pending()
            if req is None:
                return
            if req.cancelled:
                # Client gone before admission: sentinel only (no
                # typed error — nobody is reading) and never touch
                # the pool.
                self._metrics['cancelled'].inc()
                req.out.put(None)
                continue
            if req.deadline is not None and \
                    time.time() >= req.deadline:
                # Cannot start before its deadline: refuse typed
                # NOW instead of burning prefill on an answer the
                # client has already given up on.
                self._metrics['deadline_exceeded'].inc()
                self._fail_request(
                    req, 'deadline expired before admission',
                    exc=exceptions.DeadlineExceededError(
                        'deadline expired before admission'))
                continue
            if req.adapter is not None and \
                    self._adapters.slot(req.adapter) is None:
                # Cold adapter: kick the async host load and park
                # the request aside — admission (and everything
                # behind it in the queue) keeps flowing while the
                # weights stream in; _poll_adapter_loads re-queues
                # it at the front the iteration they land.
                if req.adapter_hit is None:
                    req.adapter_hit = False
                self._adapters.ensure_loading(req.adapter)
                self._adapter_wait.append(req)
                continue
            tokens_all = req.prompt_ids + req.generated
            t0 = len(tokens_all)
            need = self.pool.blocks_for(t0 + 1)
            if need > self.pool.usable_blocks:
                # Can never fit (a preempted request that grew past a
                # small pool): typed per-request failure.
                self._fail_request(
                    req, f'request of {t0} tokens needs {need} KV '
                    f'blocks but the pool has only '
                    f'{self.pool.usable_blocks} usable')
                continue
            matched, cow, cached_tokens, window_hit = \
                self._match_prefix(req, tokens_all, t0)
            blocks = list(matched)
            if cow is not None:
                # Copy-on-write: duplicate the partially-matching
                # cached block into a private one; prefill resumes at
                # the first divergent token, overwriting the rest.
                src, shared = cow
                self.pool.pin([src])     # eviction-proof during copy
                got = self.pool.try_alloc(1)
                if got is None:
                    self.pool.free([src])
                    self._unwind_admission(req, blocks)
                    return
                self._mark_enqueue()
                self.caches = self._copy_fn(
                    self.caches, jnp.asarray(src, jnp.int32),
                    jnp.asarray(got[0], jnp.int32))
                self.pool.free([src])
                blocks.append(got[0])
                cached_tokens += shared
            extra = need - len(blocks)
            got = self.pool.try_alloc(extra) if extra > 0 else []
            # The window group allocates as the row advances
            # (``_ensure_window``); admission asks that it could hold
            # the row at its widest: what its window touches plus the
            # chunk in flight, less what the hit brought.
            short_of_window = self.wpool is not None and (
                self.wpool.free_blocks <
                min(need, self._window_cap) - len(window_hit))
            if got is None or short_of_window:
                # Not enough free blocks yet: wait for retirements
                # (in-flight rows make progress every iteration, so
                # this cannot deadlock).
                self._unwind_admission(req, blocks + (got or []),
                                       window_hit)
                return
            blocks.extend(got)
            if self.prefix_caching:
                # Accounting over PROMPT blocks only — the +1 block
                # reserved for the first generated token is never
                # prefilled, so counting it as a miss would cap a
                # fully-cached short prompt at 50%. A COW partial
                # hit still counts as a miss (the block is copied
                # and partially re-prefilled).
                hit = len(matched)
                miss = max(0, self.pool.blocks_for(t0) - hit)
                self._metrics['prefix_hits'].inc(hit)
                self._metrics['prefix_misses'].inc(miss)
                self._prefix_hits_local += hit
                self._prefix_misses_local += miss
                req.prefix_hit_blocks += hit
                req.prefix_miss_blocks += miss
            if not req.admitted_once:
                # First admission only: a preempted request's
                # re-admission delay is service disruption, not
                # queueing — re-observing from the original submit
                # time would count its own prefill/decode service as
                # queue wait and poison the p99.
                t_admit = time.time()
                self._metrics['queue_wait'].observe(
                    t_admit - req.submitted_at)
                trace_lib.record_span('batch.queue_wait',
                                      req.submitted_at, t_admit,
                                      req.trace_ctx,
                                      attrs={'slot': row})
                req.admitted_once = True
                self._metrics['requests'].inc()
                if self.sampling and req.temperature > 0.0:
                    self._metrics['sampled_requests'].inc()
                if req.grammar is not None:
                    self._metrics['constrained_requests'].inc()
            # Drain-rate sample for the Retry-After estimate: every
            # admission (including re-admissions) moves the queue.
            self._admit_times.append(time.time())
            if req.adapter is not None:
                # Pin for the row's lifetime: a pinned adapter is
                # never LRU-evicted, so the gather slot stays valid
                # until _release_row unpins. No eviction can slip in
                # between the residency check above and this pin —
                # evictions only happen in _poll_adapter_loads /
                # preload, on this same loop thread.
                self.slot_adapter[row] = \
                    self._adapters.pin(req.adapter)
                if req.adapter_hit is None:
                    # Never waited on a cold load: resident at
                    # first admission.
                    req.adapter_hit = True
            else:
                self.slot_adapter[row] = 0
            self.slot_req[row] = req
            self.slot_blocks[row] = blocks
            if self.wpool is not None:
                self.slot_wblocks[row] = window_hit
                self._set_wtable_row(row)
            # Cache-hit tokens are ALREADY in the row's blocks —
            # prefill starts at the suffix (the whole TTFT win).
            self.slot_off[row] = cached_tokens
            self._mtp_h[row] = None
            self._mtp_hidden[row] = 0
            if self._mtp and cached_tokens:
                # The module's pair at the first new token needs the
                # state of the last cached position, which nothing
                # kept: the first chunk recomputes that position as
                # a read-only lane (``forward_paged``).
                self.slot_off[row] = cached_tokens - 1
                self._mtp_hidden[row] = 1
            self.slot_total[row] = t0
            self.slot_left[row] = 0
            self.slot_len[row] = 0
            self._prefill_t0[row] = None
            self._prefill_chunks[row] = 0
            self._admit_seq += 1
            self.slot_seq[row] = self._admit_seq
            self._set_table_row(row)
            if req.grammar is not None:
                # Re-derive the DFA state from the EMITTED stream
                # (empty on first admission): a preempt-resume walks
                # the identical tokens, so the resumed request
                # constrains from the identical state — the grammar
                # half of resume reproducibility.
                st = req.grammar.start
                for t in req.generated:
                    st = req.grammar.advance(st, t)
                req.grammar_state = st
                self._refresh_mask_row(row)
            self.events.append(('admit', row, cached_tokens, t0))
            # Park the lane OUT OF RANGE until prefill finishes:
            # decode dispatches treat the row as inactive but still
            # write (static shapes), and write_index redirects
            # past-capacity positions to the scratch block. Parking
            # INSIDE the row's range would aim the parked write at
            # table[0] — a real allocated block whose position 0 the
            # first prefill chunk has already filled.
            self.pos = self.pos.at[row].set(self.max_seq)

    def _chunk_bucket(self, remaining: int) -> int:
        """Static chunk length for a prefill dispatch: the smallest
        power of two >= the real chunk, capped at ``prefill_chunk``
        — compile count stays O(log prefill_chunk)."""
        real = min(remaining, self.prefill_chunk)
        bucket = 1
        while bucket < real:
            bucket *= 2
        return min(bucket, self.prefill_chunk)

    def _tenant_weight(self, tenant: str) -> float:
        w = self.tenant_weights.get(tenant, 1.0)
        return w if w > 0 else 1.0

    def _class_weight(self, key: tuple) -> float:
        """Weight of a ``(tenant, priority)`` DRR class: the
        tenant's configured fair-share weight times the priority
        prefill weight (interactive ahead of batch)."""
        tenant, priority = key
        return (self._tenant_weight(tenant) *
                PRIORITY_PREFILL_WEIGHTS.get(priority, 1.0))

    def _run_prefill_row(self, row: int) -> int:
        """One prefill chunk for ``row``; returns the bucket tokens
        charged (0 if the row has nothing left)."""
        req = self.slot_req[row]
        t0 = self.slot_total[row]
        off = self.slot_off[row]
        if off >= t0:
            return 0
        bucket = self._chunk_bucket(t0 - off)
        real = min(t0 - off, bucket)
        if not self._ensure_window(row, off, off + real):
            return 0
        with trace_lib.phase('engine.prefill_chunk', row=row,
                             bucket=bucket, real=real, offset=off):
            if self._prefill_t0[row] is None:
                self._prefill_t0[row] = time.time()
            # Slice the chunk straight out of prompt_ids/generated
            # (the logical prompt is their concatenation, and
            # generated is static while this row prefills) —
            # concatenating the whole prompt per chunk would copy
            # O(prompt) on the engine loop for every chunk of a long
            # prompt.
            n_p = len(req.prompt_ids)
            if off + real <= n_p:
                chunk = req.prompt_ids[off:off + real]
            elif off >= n_p:
                chunk = req.generated[off - n_p:off - n_p + real]
            else:
                chunk = (req.prompt_ids[off:] +
                         req.generated[:off + real - n_p])
            padded = chunk + [0] * (bucket - real)
            self._mark_enqueue()
            chunk_tokens = np.asarray([padded], np.int32)
            mtp = {}
            if self._mtp:
                h_prev = self._mtp_h[row]
                if h_prev is None:
                    h_prev = jnp.zeros((1, 1, self.config.dim),
                                       self.config.dtype)
                mtp = {'mtp': (h_prev, jnp.asarray(
                    self._mtp_hidden[row], jnp.int32))}
                self._mtp_hidden[row] = 0
            logits, self.caches, routed, *h_last = self._prefill_fn(
                self.params, chunk_tokens, self.caches,
                self._tables(row),
                jnp.asarray(off, jnp.int32),
                jnp.asarray(real, jnp.int32),
                self.config, self.block_size,
                *self._adapter_args([self.slot_adapter[row]]), **mtp)
            if h_last:
                self._mtp_h[row] = h_last[0]
        self._metrics['prefill_chunks'].inc()
        self._metrics['prefill_tokens'].inc(real)
        self._metrics['prefill_bucket_tokens'].inc(bucket)
        kinds = self.config.layer_kinds
        self._metrics['prefill_keys_read'].inc(sum(
            da.chunk_keys_read(
                off, bucket, self.block_size, self.max_blocks_per_req,
                self.config.sliding_window if kind == 'window'
                else None) for kind in kinds))
        self._metrics['prefill_keys_view'].inc(
            len(kinds) * self.max_seq)
        if self.config.kv_lora_rank is not None:
            self._metrics['mla_expanded_tokens'].inc(real)
        if routed is not None:
            self._routed_pending.append((routed, bucket, 1))
        self.slot_off[row] = off + real
        if self.wpool is not None:
            # The window group publishes a prompt's blocks as their
            # chunks complete, since it gives them back long before
            # the prompt ends: a released block then stays matchable
            # in that group's cache until it is evicted.
            self._register_window(row)
            self._release_behind(row, off + real)
        self._prefill_chunks[row] += 1
        self.events.append(('prefill_chunk', row, off + real, t0))
        if self.slot_off[row] >= t0:
            with trace_lib.phase('engine.first_token', row=row):
                self._finish_prefill(row, logits)
        return bucket

    def _run_prefill_chunks(self) -> bool:
        """Run prefill chunks for admitted-but-unprefilled rows
        within this iteration's token budget. Chunks beyond the
        budget wait for the NEXT iteration — a decode dispatch runs
        in between, which is exactly the chunked-prefill
        interleaving contract.

        The budget is split across TENANTS by weighted deficit
        round-robin (fair-share QoS): each tenant with pending
        prefill accrues a weighted share of the budget per
        iteration and spends it in admission order; unspent deficit
        carries over, so one tenant's long prompts cannot starve
        another's TTFT. A second, deficit-blind pass keeps the
        scheduler work-conserving (leftover budget is never idled
        while any prefill is pending, and the free capacity is not
        charged against future shares)."""
        budget = self.max_batched_tokens or float('inf')
        self._prefill_spent_iter = 0
        rows = sorted(
            (i for i in range(self.slots)
             if self.slot_req[i] is not None
             and self.slot_off[i] < self.slot_total[i]),
            key=lambda i: self.slot_seq[i])
        if not rows:
            return False
        # The DRR class is (tenant, priority): priorities weight
        # the split WITHIN the existing tenant fair-share machinery
        # (PRIORITY_PREFILL_WEIGHTS puts interactive prefill ahead
        # of batch), instead of bolting a second scheduler on top.
        by_tenant: Dict[tuple, List[int]] = {}
        for i in rows:
            req_i = self.slot_req[i]
            by_tenant.setdefault(
                (req_i.tenant or '', req_i.priority),
                []).append(i)
        # Interactive classes ahead of batch ones for the same
        # tenant; the rotation below still round-robins fairly
        # across iterations.
        tenants = sorted(by_tenant,
                         key=lambda k: (k[0],
                                        PRIORITIES.index(k[1])))
        metered = budget != float('inf')
        if metered:
            total_w = sum(self._class_weight(t) for t in tenants)
            for t in tenants:
                quantum = budget * self._class_weight(t) / total_w
                # Cap banked credit at two full budgets so a
                # long-idle-then-bursty tenant cannot monopolize one
                # iteration with accumulated deficit.
                self._tenant_deficit[t] = min(
                    self._tenant_deficit.get(t, 0.0) + quantum,
                    2.0 * budget)
            # A tenant with nothing pending banks no credit.
            for t in list(self._tenant_deficit):
                if t not in by_tenant:
                    del self._tenant_deficit[t]
        # Rotate the service order so equal-deficit tenants take
        # turns going first.
        start = self._tenant_rr % len(tenants)
        self._tenant_rr += 1
        order = tenants[start:] + tenants[:start]
        spent = 0.0
        ran_any = False
        for deficit_blind in (False, True):
            for t in order:
                for row in by_tenant[t]:
                    while (self.slot_req[row] is not None
                           and self.slot_off[row] <
                           self.slot_total[row]
                           and not self._stop):
                        if spent >= budget:
                            return ran_any
                        if metered and not deficit_blind:
                            bucket = self._chunk_bucket(
                                self.slot_total[row] -
                                self.slot_off[row])
                            if self._tenant_deficit.get(t, 0.0) \
                                    < bucket and ran_any:
                                # Deficit exhausted: this tenant
                                # waits (credit carries over) while
                                # others run. The very first chunk
                                # of an iteration may overdraft so
                                # a budget smaller than one chunk
                                # still makes progress.
                                break
                        charged = self._run_prefill_row(row)
                        if charged <= 0:
                            break
                        spent += charged
                        self._prefill_spent_iter = int(spent)
                        if metered and not deficit_blind:
                            self._tenant_deficit[t] = \
                                self._tenant_deficit.get(t, 0.0) \
                                - charged
                        ran_any = True
            if not metered:
                break
        return ran_any

    def _register_window(self, row: int) -> None:
        """Publish in the WINDOW group the row's prompt blocks that
        are complete (wholly below ``slot_off``) and still held."""
        req = self.slot_req[row]
        if not (self.prefix_caching and req.chain_hashes
                and req.chain_t0 == self.slot_total[row]):
            return
        tokens_all = req.prompt_ids + req.generated
        full = min(self.slot_off[row] // self.block_size,
                   len(req.chain_hashes))
        root = prefix_hash.adapter_root(req.adapter)
        for col, block in self.slot_wblocks[row].items():
            if col < full:
                self.wpool.register(
                    block, req.chain_hashes[col],
                    req.chain_hashes[col - 1] if col else root,
                    tokens_all[col * self.block_size:
                               (col + 1) * self.block_size])

    def _register_prefix(self, row: int) -> None:
        """Publish the row's FULL prompt blocks into the prefix
        cache: each complete block's content now equals its chain
        hash's token block, so future prompts sharing the prefix can
        pin them. The trailing partial block (still written by
        decode) is never registered — registered blocks are
        immutable from here on (all later writes land past t0)."""
        if not self.prefix_caching:
            return
        req = self.slot_req[row]
        t0 = self.slot_total[row]
        tokens_all = (req.prompt_ids + req.generated)[:t0]
        if req.chain_t0 == t0 and req.chain_hashes:
            # Reuse the admission-time chain (same tokens: generated
            # does not grow between admission and prefill finish).
            hashes = req.chain_hashes
        else:
            hashes = kv_pool_lib.chain_hashes(
                tokens_all, self.block_size,
                root=prefix_hash.adapter_root(req.adapter))
        blocks = self.slot_blocks[row]
        parent = prefix_hash.adapter_root(req.adapter)
        for i, h in enumerate(hashes):
            self.pool.register(
                blocks[i], h, parent,
                tokens_all[i * self.block_size:
                           (i + 1) * self.block_size])
            parent = h

    def _finish_prefill(self, row: int, logits: jax.Array) -> None:
        """Last prompt chunk done: its logits seed greedy decoding —
        the first generated token comes from the prefill itself."""
        req = self.slot_req[row]
        t0 = self.slot_total[row]
        self._register_prefix(row)
        self._release_behind(row, t0)
        if self.sampling and (req.temperature > 0.0
                              or req.grammar is not None):
            # Counter-keyed first token at position t0 - 1 (the
            # index of the last prompt token these logits consumed)
            # — the same key decode would use there, so the
            # prefill/decode boundary is invisible to the (seed,
            # position) contract. Greedy-unconstrained rows keep
            # the host argmax below, byte-identical to before.
            allowed = None
            if req.grammar is not None:
                allowed = jnp.asarray(
                    req.grammar.allowed(req.grammar_state))
            self._mark_enqueue()
            first = int(jax.device_get(self._first_fn(
                logits, jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.top_p, jnp.float32),
                jnp.asarray(req.seed, jnp.int32),
                jnp.asarray(t0 - 1, jnp.int32), allowed)))
        else:
            first = int(jax.device_get(logits)[0].argmax())
        # The int() above synchronizes, so these are real wall times
        # (and the device has nothing queued from here on).
        self._gap_open = time.perf_counter()
        t_first = time.time()
        resumed = bool(req.generated)
        trace_lib.record_span('batch.prefill',
                              self._prefill_t0[row], t_first,
                              req.trace_ctx,
                              attrs={'prompt_len': t0,
                                     'chunks':
                                         self._prefill_chunks[row]})
        if not resumed:
            trace_lib.record_span('batch.first_token',
                                  req.submitted_at, t_first,
                                  req.trace_ctx)
            self._metrics['ttft'].observe(t_first - req.submitted_at)
        self.pos = self.pos.at[row].set(t0)
        self.tokens = self.tokens.at[row].set(first)
        self.slot_len[row] = t0
        self._count_tokens(1)
        req.out.put(first)
        req.generated.append(first)
        if req.grammar is not None:
            req.grammar_state = req.grammar.advance(
                req.grammar_state, first)
        self.slot_left[row] = req.max_new - len(req.generated)
        if self.slot_left[row] <= 0 or first == req.eos_id:
            req.out.put(None)
            self._retire(row)
        elif req.grammar is not None:
            self._refresh_mask_row(row)
        elif self._mtp:
            # The pair (state of the last prompt position, the first
            # token) and the row's first draft.
            self._mark_enqueue()
            draft, routed = self._first_draft(
                self._mtp_h[row], first, self._tables(row), t0 - 1,
                req.temperature, req.top_p, req.seed)
            self.drafts = self.drafts.at[row].set(draft)
            self._routed_pending.append((routed, 1, 1))
            self._metrics['mtp_positions'].inc()
        self._mtp_h[row] = None

    def _spec_k_for(self, req: _Request) -> int:
        """Current draft length for a request (adaptive controller
        state), seeding new requests at the engine draft_k and
        re-probing collapsed ones with a 1-token draft once their
        emitted-token cooldown expires."""
        if req.spec_k is None:
            req.spec_k = self.draft_k
        if req.spec_k == 0 and req.spec_cooldown <= 0:
            req.spec_k = 1
            req.spec_window.clear()
        return req.spec_k

    def _collect_drafts(self, rows: List[int]) -> Dict[int, List[int]]:
        """Propose n-gram drafts for this dispatch's decode rows
        under what remains of the per-iteration token budget: every
        row costs its 1 base token unconditionally (plain decode was
        never budget-gated), drafts are granted oldest-first from
        the remainder after prefill spending — a verify row costs
        drafted+1 budget tokens, so speculation degrades gracefully
        under load instead of starving prefill."""
        if not rows:
            return {}
        if self.max_batched_tokens is None:
            left = float('inf')
        else:
            left = (self.max_batched_tokens -
                    self._prefill_spent_iter - len(rows))
        def row_cap(row: int, k: int) -> int:
            cap = min(k, self.slot_left[row] - 1,
                      self.max_seq - self.slot_len[row] - 2)
            if left != float('inf'):
                cap = min(cap, int(left))
            return cap

        def draft_stream(req: _Request) -> List[int]:
            # Only the trailing match window ever matters — build
            # just that, not the full prompt+generated concat (an
            # 8k prompt would otherwise be copied per row per
            # dispatch on the engine loop, the exact O(prompt) walk
            # SPEC_MATCH_WINDOW exists to bound).
            tail = req.generated[-SPEC_MATCH_WINDOW:]
            short = SPEC_MATCH_WINDOW - len(tail)
            if short > 0 and req.prompt_ids:
                tail = req.prompt_ids[-short:] + tail
            return tail

        drafts: Dict[int, List[int]] = {}
        min_k = self.draft_k
        for row in sorted(rows, key=lambda i: self.slot_seq[i]):
            if left <= 0:
                break
            req = self.slot_req[row]
            k = self._spec_k_for(req)
            cap = row_cap(row, k)
            if cap <= 0:
                continue
            # Evidence bars: nearly-collapsed requests re-probe on
            # a 4-gram only (their window says drafting loses);
            # first-ever proposals need a trigram (no evidence
            # either way — a repetitive stream produces one within
            # a few tokens, low-repeat text essentially never);
            # established speculators draft on the default bar.
            if k <= SPEC_PROBE_K:
                bar = SPEC_PROBE_MIN_NGRAM
            elif not req.spec_window:
                bar = SPEC_FIRST_MIN_NGRAM
            else:
                bar = SPEC_MIN_NGRAM
            d = propose_ngram_draft(draft_stream(req), cap,
                                    min_ngram=bar)
            if d and req.grammar is not None:
                d = self._filter_draft_grammar(req, d)
            if d:
                drafts[row] = d
                left -= len(d)
                min_k = min(min_k, req.spec_k)
        # Low-value gate: a verify carrying almost no drafted tokens
        # cannot pay for displacing the multi-step decode scan. The
        # threshold relaxes to the smallest drafting row's k so a
        # cooldown re-probe (k=1) is never gated out of existence —
        # it is already rate-limited by the cooldown itself.
        if drafts and sum(map(len, drafts.values())) < \
                min(SPEC_MIN_DISPATCH_TOKENS, min_k):
            return {}
        if drafts:
            # Ride-along probes: the verify dispatch is happening
            # anyway and its lanes are as wide for every row, so
            # collapsed (cooldown) rows re-probe for free inside it
            # instead of waiting out their cooldown at 1 emitted
            # token per dispatch.
            for row in rows:
                req = self.slot_req[row]
                if row in drafts or req.spec_k != 0 or left <= 0:
                    continue
                cap = row_cap(row, SPEC_PROBE_K)
                if cap <= 0:
                    continue
                d = propose_ngram_draft(
                    draft_stream(req), cap,
                    min_ngram=SPEC_PROBE_MIN_NGRAM)
                if d and req.grammar is not None:
                    d = self._filter_draft_grammar(req, d)
                if d:
                    drafts[row] = d
                    left -= len(d)
        return drafts

    def _trim_blocks(self, row: int) -> None:
        """Free the row's whole blocks past its committed frontier
        (keeping coverage for the next write position): a rejected
        draft can leave blocks holding nothing but abandoned rows —
        they are reclaimable pool capacity, not this request's to
        sit on. Trimmed blocks are always this row's own fresh
        allocations (pinned prefix-cache hits cover the prompt
        PREFIX, strictly inside the committed frontier), and the
        table row is re-padded to scratch so the stale entries can
        never alias a recycled block."""
        keep = self.pool.blocks_for(min(self.slot_len[row] + 1,
                                        self.max_seq))
        if self.wpool is not None:
            self._free_window(row, [c for c in self.slot_wblocks[row]
                                    if c >= keep])
        extra = self.slot_blocks[row][keep:]
        if not extra:
            return
        self.pool.free(list(reversed(extra)))
        del self.slot_blocks[row][keep:]
        self._set_table_row(row)

    def _decode_rows(self) -> List[int]:
        return [i for i in range(self.slots)
                if self.slot_req[i] is not None
                and self.slot_off[i] >= self.slot_total[i]]

    def _dispatch_decode(self) -> bool:
        """One whole-batch dispatch over every row whose prefill is
        complete: a VERIFY dispatch (``verify_step_paged``, width
        draft_k+1) when any row carries a live n-gram draft, the
        plain ``steps_per_dispatch`` decode scan otherwise — mixed
        batches verify and 1-token-decode in the same forward
        (draft-less rows just pad their lanes to scratch). With the
        model's own module as the drafter (``speculative='mtp'``)
        every dispatch is ``steps_per_dispatch`` drafting rounds
        (``_launch_rounds``).

        Three phases on the profiler's clock: ``engine.dispatch``
        (host work up to the return of the enqueue),
        ``engine.device_wait`` (the blocking ``device_get``) and
        ``engine.emit`` (tokens to clients, retirement)."""
        ready = self._decode_rows()
        n = self.steps
        if any(self.slot_req[i].grammar is not None for i in ready):
            # Grammar masks advance HOST-side per emitted token — a
            # multi-step scan cannot re-mask between its steps, so
            # any constrained row forces 1-token dispatches (the
            # structured-decoding throughput cost; unconstrained
            # batches keep the full scan).
            n = 1
        with trace_lib.phase('engine.dispatch', rows=len(ready),
                             steps=n):
            launched = self._launch_dispatch(ready, n)
        if launched is None:
            return False
        active_rows, drafts, outputs, t_dispatch = launched
        with trace_lib.phase('engine.device_wait',
                             rows=len(active_rows),
                             kind='verify' if drafts else
                             'rounds' if self._mtp else 'decode'):
            host = jax.device_get(outputs)
        # device_get synchronizes: this is real decode wall time, and
        # the device has nothing queued from here on.
        self._gap_open = time.perf_counter()
        dispatch_s = self._gap_open - t_dispatch
        self._metrics['decode_dispatches'].inc()
        with trace_lib.phase('engine.emit', rows=len(active_rows)):
            if drafts:
                self._finish_verify(active_rows, drafts, host,
                                    dispatch_s)
            elif self._mtp:
                self._finish_rounds(active_rows, host, dispatch_s)
            else:
                self._finish_decode(active_rows, n, host, dispatch_s)
        return True

    def _launch_dispatch(self, ready: List[int], n: int):
        """Everything up to the return of the enqueue: drafts, block
        growth, the ``active`` mask, then ``decode_steps_paged`` or
        ``verify_step_paged``. Returns ``(active_rows, drafts,
        device outputs, enqueue instant)``, or None when no row is
        decode-ready."""
        drafts = self._collect_drafts(ready) \
            if self.speculative else {}
        # Grow allocations for this dispatch's writes up front;
        # exhaustion preempts the youngest request (possibly a row in
        # this very list, which then simply sits the dispatch out —
        # a preempted row's draft dies with it).
        for i in ready:
            if self.slot_req[i] is None:
                # Preempted by an earlier row's growth in this very
                # loop — it sits the dispatch out.
                continue
            # Plain decode writes min(slot_left, n) positions past
            # slot_len; a verify row writes its base token + draft
            # (draft length is pre-capped at slot_left - 1).
            need = min(self.slot_left[i], n)
            if i in drafts:
                need = max(need, len(drafts[i]) + 1)
            if self._mtp:
                # n rounds commit up to 2 n tokens, the last round's
                # draft is written one position on, and the module's
                # rows lie one slot past their pairs.
                need = 2 * n + 1
            target = min(self.slot_len[i] + need, self.max_seq)
            if self._ensure_blocks(i, target):
                self._release_behind(i, self.slot_len[i])
                self._ensure_window(i, self.slot_len[i], target)
        active_rows = self._decode_rows()
        if not active_rows:
            return None
        drafts = {i: d for i, d in drafts.items()
                  if self.slot_req[i] is not None}
        if drafts:
            return self._launch_verify(active_rows, drafts)
        # On-demand profiling hook: one "step" per decode dispatch
        # (docs/observability.md, On-demand profiling).
        self._profiler.on_step()
        # Fixed dispatch length: a data-dependent n would compile one
        # executable per distinct remaining-count (observed as
        # multi-second stalls in the tail of a request wave). Rows
        # that finish mid-dispatch just overrun harmlessly — their
        # extra tokens are never emitted and their overrun writes are
        # redirected to unallocated-table/scratch slots.
        is_active = [self.slot_req[i] is not None
                     and self.slot_off[i] >= self.slot_total[i]
                     and self.slot_left[i] > 0
                     for i in range(self.slots)]
        if self._mtp:
            return self._launch_rounds(active_rows, is_active)
        active = jnp.asarray(is_active, bool)
        sampling = self._sampling_args()
        view = self._view_blocks(is_active, n, sampling)
        self._count_view(view)
        t_dispatch = time.perf_counter()
        self._mark_enqueue(t_dispatch)
        toks, self.caches, self.pos, *routed = self._step_fn(
            self.params, self.tokens, self.caches,
            self._tables(), self.pos, active, self.config, n,
            self.block_size, *self._adapter_args(),
            sampling=sampling, view_blocks=view)
        self.tokens = toks[:, -1]
        if routed:
            self._routed_pending.append((routed[0], self.slots, n))
        if self._walks:
            # What the walk reads: each active row's own blocks up
            # to the dispatch's last step, where the view held
            # every lane at the dispatch's width.
            self._metrics['decode_walk_blocks'].inc(sum(
                -(-(self.slot_len[i] + n) // self.block_size)
                for i in range(self.slots) if is_active[i]))
            self._metrics['decode_walk_lane_blocks'].inc(
                self.slots * view)
        if self.config.kv_lora_rank is not None:
            # Step k of a row at length L attends L + k cached
            # positions and its own.
            lengths = [self.slot_len[i] for i in range(self.slots)
                       if is_active[i]]
            self._metrics['mla_absorbed_row_steps'].inc(
                n * len(lengths))
            self._metrics['mla_absorbed_context'].inc(
                n * sum(lengths) + len(lengths) * n * (n + 1) // 2)
        for i in active_rows:
            if self.slot_left[i] > 0:
                self.slot_len[i] = min(self.slot_len[i] + n,
                                       self.max_seq)
        return active_rows, drafts, toks, t_dispatch

    def _view_blocks(self, active: List[bool], n: int,
                     sampling) -> int:
        """The block-table columns an ``n``-step decode dispatch
        reads: the smallest prewarmed width that holds ``slot_len +
        n`` positions of every ACTIVE row (a row still in prefill
        may be longer: it is not read, and its parked write lands in
        scratch under any width). A signature the constructor did
        not prewarm (sampled or constrained rows) keeps the whole
        table, its one compilation as before: a narrower program is
        never compiled inside a request's decode window."""
        if sampling is not None or n != self.steps:
            return self.max_blocks_per_req
        longest = max((self.slot_len[i]
                       for i in range(self.slots) if active[i]),
                      default=0)
        return da.view_width(self._view_widths, longest + n,
                             self.block_size)

    def _count_view(self, view_blocks: int) -> None:
        self._metrics['decode_view_blocks'].inc(view_blocks)
        self._metrics['decode_table_blocks'].inc(
            self.max_blocks_per_req)

    def _finish_decode(self, active_rows: List[int], n: int,
                       host_toks, dispatch_s: float) -> None:
        """The emission tail of a plain decode dispatch."""
        self._count_routed()
        if dispatch_s > 0:
            self._metrics['tok_s'].set(
                len(active_rows) * n / dispatch_s)
        self.events.append(('decode', len(active_rows)))
        # Per-chunk decode spans: one `batch.decode` per traced
        # request per dispatch, all sharing the dispatch's wall
        # window — a request's TTFT decomposes as queue_wait +
        # prefill + its decode chunks in the waterfall.
        t_chunk_end = time.time()
        t_chunk_start = t_chunk_end - dispatch_s
        emitted = 0
        for i in active_rows:
            emitted += self._emit_tokens(i, host_toks[i][:n],
                                         t_chunk_start, t_chunk_end)
        if emitted:
            self._count_tokens(emitted)

    def _count_routed(self) -> None:
        """Add what the expert layers routed since the last emit to
        the ``skytpu_batch_moe_*`` counters. Each entry is one
        dispatch's or chunk's tally (``decode._routed_sums``: pairs
        and hit steps, [n_layers, experts held] each) with the
        tokens a step carried (every lane of a dispatch and every
        slot of a chunk bucket computes, real or not) and its steps;
        nothing else reads them."""
        pending, self._routed_pending = self._routed_pending, []
        if not pending:
            return
        top_k = self.config.moe_top_k
        m = self._metrics
        for (pairs, hit_steps), tokens, steps in \
                jax.device_get(pending):
            layers, held = pairs.shape
            m['moe_routed_pairs'].inc(tokens * steps * top_k * layers)
            held_pairs = int(pairs.sum())
            m['moe_held_pairs'].inc(held_pairs)
            if self._pairs_tiled:
                m['moe_tiled_pairs'].inc(held_pairs)
            m['moe_busiest_pairs'].inc(int(pairs.max(axis=1).sum()))
            m['moe_experts_hit'].inc(int(hit_steps.sum()))
            m['moe_experts_held'].inc(layers * held * steps)

    def _count_tokens(self, n: int) -> None:
        """``n`` tokens handed to clients, with the passes over the
        layer stack that each cost."""
        self._metrics['tokens'].inc(n)
        self._metrics['loop_passes'].inc(n * self.config.loop_passes)

    def _emit_tokens(self, row: int, toks, t_start: float,
                     t_end: float) -> int:
        """Shared emission tail for decode AND verify dispatches:
        push tokens to the client in order until EOS or the
        request's budget (EOS retires the row NOW — anything the
        device computed past it in this dispatch is discarded with
        the row's blocks/table at retirement), record the
        per-request ``batch.decode`` span, tick the speculation
        re-probe cooldown, and retire the row when done. Returns
        the number of tokens emitted."""
        req = self.slot_req[row]
        done = False
        row_emitted = 0
        for t in toks:
            if self.slot_left[row] <= 0:
                break
            req.out.put(int(t))
            req.generated.append(int(t))
            if req.grammar is not None:
                # Host half of structured decoding: walk the DFA
                # over the emitted stream (device-side masks made
                # the token legal; a None state falls back to
                # unconstrained rather than poisoning the row).
                req.grammar_state = req.grammar.advance(
                    req.grammar_state, int(t))
            row_emitted += 1
            self.slot_left[row] -= 1
            if int(t) == req.eos_id:
                done = True
                break
        if row_emitted:
            trace_lib.record_span(
                'batch.decode', t_start, t_end, req.trace_ctx,
                attrs={'tokens': row_emitted, 'slot': row})
        # Collapsed-speculation rows re-probe after a cooldown of
        # emitted tokens (_spec_k_for).
        req.spec_cooldown = max(0, req.spec_cooldown - row_emitted)
        if done or self.slot_left[row] <= 0:
            req.out.put(None)
            self._retire(row)
        elif row_emitted and req.grammar is not None:
            self._refresh_mask_row(row)
        return row_emitted

    def _round_sampling(self):
        """``sampling`` of the drafting rounds: the per-row knob
        arrays whatever the mix (a greedy row rides at temperature 0
        and reduces to the argmax), so that the rounds have ONE
        signature and the constructor prewarmed it; None for an
        engine built with sampling off. No grammar table: a
        constrained request is refused at submit on this path."""
        return self._knob_arrays() if self.sampling else None

    def _enqueue_rounds(self, active: List[bool], grant: List[bool],
                        view: int):
        """``mtp_rounds_paged`` on the engine's state, through the
        one call the constructor's prewarm and the live dispatch
        share. Returns the device's (tokens, counts, routed)."""
        toks, counts, self.caches, self.pos, self.tokens, \
            self.drafts, routed = self._rounds_fn(
                self.params, self.tokens, self.drafts, self.caches,
                self._tables(), self.pos, jnp.asarray(active, bool),
                jnp.asarray(grant, bool), self.config, self.steps,
                self.block_size, sampling=self._round_sampling(),
                view_blocks=view)
        return toks, counts, routed

    def _first_draft(self, h_last, token: int, table_row, pos: int,
                     temperature: float, top_p: float, seed: int):
        """``mtp_first_paged`` for one row (prewarm and live alike).
        Returns (draft, routed), both on the device."""
        draft, self.caches, routed = self._mtp_first_fn(
            self.params, h_last, jnp.asarray(token, jnp.int32),
            self.caches, table_row, jnp.asarray(pos, jnp.int32),
            self.config, self.block_size,
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(seed, jnp.int32))
        return draft, routed

    def _launch_rounds(self, active_rows: List[int],
                       is_active: List[bool]):
        """One dispatch of ``steps_per_dispatch`` drafting rounds
        over every decode-ready row (``mtp_rounds_paged``). A round
        costs a row 2 of the per-iteration token budget: every row's
        own token is free, as in plain decode, and drafts are granted
        oldest first from what prefill left; a row without a grant
        verifies its token alone. The host-side ``spec_k`` controller
        is not consulted: its thresholds price a drafter that costs
        a dispatch, and this one rides the round."""
        n = self.steps
        left = float('inf') if self.max_batched_tokens is None else (
            self.max_batched_tokens - self._prefill_spent_iter -
            len(active_rows))
        grant = [False] * self.slots
        for i in sorted(active_rows, key=lambda i: self.slot_seq[i]):
            if left <= 0:
                break
            grant[i] = is_active[i]
            left -= 1
        longest = max((self.slot_len[i] for i in range(self.slots)
                       if is_active[i]), default=0)
        view = da.view_width(self._view_widths, longest + 2 * n + 1,
                             self.block_size)
        self._count_view(view)
        t_dispatch = time.perf_counter()
        self._mark_enqueue(t_dispatch)
        toks, counts, routed = self._enqueue_rounds(is_active, grant,
                                                    view)
        # Every lane of both query positions computes, in the main
        # layers and in the module's.
        self._routed_pending.append((routed, 2 * self.slots, n))
        self._rounds_granted = sum(
            1 for i in active_rows if grant[i])
        return active_rows, {}, (toks, counts), t_dispatch

    def _finish_rounds(self, active_rows: List[int], host,
                       dispatch_s: float) -> None:
        """Commit and emit a dispatch of drafting rounds: per row
        the tokens its rounds committed, in order (one or two a
        round), up to its budget or EOS."""
        host_toks, host_counts = host
        self._count_routed()
        n = host_counts.shape[1]
        t_chunk_end = time.time()
        t_chunk_start = t_chunk_end - dispatch_s
        m = self._metrics
        emitted = committed = 0
        row_steps = context = 0
        for i in active_rows:
            counts = host_counts[i]
            total = int(counts.sum())
            if not total:
                continue
            # Round r of a row that stood at L: two main positions
            # (contexts L + 1 and L + 2, their own counted) and the
            # module's one or two (L + 1, L + 2 less its empty slot).
            before = self.slot_len[i] + np.concatenate(
                [[0], np.cumsum(counts)[:-1]])
            row_steps += 2 * n + total
            context += int((2 * before + 3).sum() +
                           (counts * before + counts *
                            (counts - 1) // 2).sum())
            committed += total
            self.slot_len[i] = min(self.slot_len[i] + total,
                                   self.max_seq)
            kept = np.arange(2)[None, :] < counts[:, None]
            emitted += self._emit_tokens(
                i, host_toks[i][kept], t_chunk_start, t_chunk_end)
        proposed = n * self._rounds_granted
        accepted = committed - int((host_counts > 0).sum())
        m['mtp_rounds'].inc(n * len(active_rows))
        m['mtp_tokens'].inc(committed)
        m['mtp_positions'].inc(committed)
        m['mla_absorbed_row_steps'].inc(row_steps)
        m['mla_absorbed_context'].inc(context)
        if proposed:
            m['spec_proposed'].inc(proposed)
            self._spec_proposed_local += proposed
        if accepted:
            m['spec_accepted'].inc(accepted)
        self._spec_accepted_local += accepted
        m['spec_tokens_per_forward'].set(
            committed / max(1, n * len(active_rows)))
        if dispatch_s > 0:
            m['tok_s'].set(emitted / dispatch_s)
        self.events.append(('decode', len(active_rows)))
        self.events.append(('rounds', len(active_rows), proposed,
                            accepted))
        if emitted:
            self._count_tokens(emitted)

    def _launch_verify(self, active_rows: List[int],
                       drafts: Dict[int, List[int]]):
        """One speculative VERIFY dispatch: every decode-ready row
        rides the same ``verify_step_paged`` forward — rows with a
        draft verify draft+1 positions, draft-less rows decode their
        1 base token (their padded lanes write scratch). Drafted K/V
        went into the rows' blocks up front; a rejection at draft
        position a simply rolls the row's ``pos`` forward by only
        a+1 (the accepted span), so the abandoned rows are never
        attended again, and whole blocks past the committed frontier
        are returned to the pool (``_trim_blocks``). Emission is
        ``preds[0..a]`` — exactly what plain greedy decode would
        have produced, one forward at a time. This half enqueues
        the forward; ``_finish_verify`` commits and emits."""
        w = self.draft_k + 1
        toks = [[0] * w for _ in range(self.slots)]
        n_real = [0] * self.slots
        for i in active_rows:
            req = self.slot_req[i]
            d = drafts.get(i, ())
            # generated[-1] is the row's current input token — the
            # host mirror of self.tokens[i] (every emission path
            # appends it before the next dispatch).
            toks[i][0] = req.generated[-1]
            toks[i][1:1 + len(d)] = d
            n_real[i] = 1 + len(d)
        self._profiler.on_step()
        self._count_view(self.max_blocks_per_req)
        t_dispatch = time.perf_counter()
        self._mark_enqueue(t_dispatch)
        preds, accepted, self.pos, self.tokens, self.caches = \
            self._verify_fn(
                self.params, np.asarray(toks, np.int32),
                self.caches, self._tables(), self.pos,
                np.asarray(n_real, np.int32), self.config, w,
                self.block_size, *self._adapter_args(),
                sampling=self._verify_sampling_args(toks, n_real))
        return active_rows, drafts, (preds, accepted), t_dispatch

    def _finish_verify(self, active_rows: List[int],
                       drafts: Dict[int, List[int]], host,
                       dispatch_s: float) -> None:
        """Commit and emit a verify dispatch: per row the accepted
        span of ``preds``, the adaptive draft length, the blocks past
        the committed frontier back to the pool."""
        host_preds, host_acc = host
        t_chunk_end = time.time()
        t_chunk_start = t_chunk_end - dispatch_s
        emitted = 0
        proposed_total = 0
        accepted_total = 0
        for i in active_rows:
            req = self.slot_req[i]
            d = drafts.get(i, [])
            preds_i = host_preds[i]
            a = int(host_acc[i])
            if d:
                proposed_total += len(d)
                accepted_total += a
                self._metrics['spec_accept_rate'].labels(
                    mode='sampled' if (self.sampling
                                       and req.temperature > 0.0)
                    else 'greedy').observe(a / len(d))
                req.spec_window.append((len(d), a))
                new_k = update_spec_k(req.spec_k, req.spec_window,
                                      self.draft_k)
                if new_k != req.spec_k:
                    grew = new_k > req.spec_k
                    req.spec_k = new_k
                    if new_k == 0:
                        # Backed-off cooldown: repeated failed
                        # probes stretch the next one out
                        # exponentially, so adversarial traffic's
                        # probing overhead vanishes relative to
                        # its stream length.
                        req.spec_cooldown = (
                            SPEC_REPROBE_TOKENS *
                            (2 ** min(req.spec_fail_streak,
                                      SPEC_BACKOFF_MAX_EXP)))
                        req.spec_fail_streak += 1
                        req.spec_window.clear()
                    elif grew and new_k >= 2:
                        # A probe caught a regime change: the
                        # request speculates again — forget the
                        # backoff.
                        req.spec_fail_streak = 0
            # Committed KV: the base token + a accepted drafts. The
            # device already advanced pos/tokens by exactly this —
            # the rollback IS that arithmetic: rejected positions
            # sit past the new frontier, never attended
            # (length-masked attention), never emitted, never in
            # ``generated``.
            self.slot_len[i] = min(self.slot_len[i] + a + 1,
                                   self.max_seq)
            emitted += self._emit_tokens(i, preds_i[:a + 1],
                                         t_chunk_start, t_chunk_end)
            if self.slot_req[i] is not None and a < len(d):
                self._trim_blocks(i)
        if dispatch_s > 0:
            self._metrics['tok_s'].set(emitted / dispatch_s)
        if proposed_total:
            self._metrics['spec_proposed'].inc(proposed_total)
            self._spec_proposed_local += proposed_total
        if accepted_total:
            self._metrics['spec_accepted'].inc(accepted_total)
        self._spec_accepted_local += accepted_total
        self._metrics['spec_tokens_per_forward'].set(
            emitted / max(1, len(active_rows)))
        # 'decode' first for the interleaving contract (a verify IS
        # this iteration's decode dispatch); 'verify' carries the
        # speculation accounting the spec tests assert.
        self.events.append(('decode', len(active_rows)))
        self.events.append(('verify', len(drafts), proposed_total,
                            accepted_total))
        if emitted:
            self._count_tokens(emitted)

    def _sweep_overload(self) -> None:
        """Iteration-boundary enforcement of cancellation and
        deadlines: a cancelled row frees its KV blocks through the
        EXACT reclaim path preemption uses (``_release_row``) and
        gets its sentinel; an expired row additionally gets the
        typed ``DeadlineExceededError`` serve_model maps to 504.
        The pending queue is swept under the same rules so queued
        requests cannot outlive their client or their deadline."""
        now = time.time()
        cancel_ids = ()
        if self._cancel_ids:
            with self._pending_lock:
                cancel_ids, self._cancel_ids = self._cancel_ids, \
                    set()
        for row in range(self.slots):
            req = self.slot_req[row]
            if req is None:
                continue
            if req.id in cancel_ids:
                req.cancelled = True
            if req.cancelled:
                self.events.append(('cancel', row,
                                    len(req.generated)))
                self._metrics['cancelled'].inc()
                self._release_row(row)
                req.out.put(None)
            elif req.deadline is not None and now >= req.deadline:
                self.events.append(('deadline', row,
                                    len(req.generated)))
                self._metrics['deadline_exceeded'].inc()
                self._release_row(row)
                self._fail_request(
                    req, 'deadline expired mid-decode',
                    exc=exceptions.DeadlineExceededError(
                        'deadline expired after '
                        f'{len(req.generated)} generated tokens'))
        # Requests parked waiting on an adapter cold load sit in
        # neither a slot nor the pending queue — mark them here;
        # _poll_adapter_loads (right after this sweep) drops them.
        for req in self._adapter_wait:
            if req.id in cancel_ids:
                req.cancelled = True
        dropped: List[_Request] = []
        with self._pending_lock:
            if self.pending:
                kept: 'collections.deque[_Request]' = \
                    collections.deque()
                for req in self.pending:
                    if req.id in cancel_ids:
                        req.cancelled = True
                    if req.cancelled or (
                            req.deadline is not None
                            and now >= req.deadline):
                        dropped.append(req)
                    else:
                        kept.append(req)
                if dropped:
                    self.pending = kept
                    self._queued_tokens = sum(
                        self._queue_cost(r) for r in kept)
        for req in dropped:
            if req.cancelled:
                self._metrics['cancelled'].inc()
                req.out.put(None)
            else:
                self._metrics['deadline_exceeded'].inc()
                self._fail_request(
                    req, 'deadline expired while queued',
                    exc=exceptions.DeadlineExceededError(
                        'deadline expired while queued'))

    def _set_gauges(self) -> None:
        self._metrics['occupancy'].set(sum(
            1 for r in self.slot_req if r is not None))
        with self._pending_lock:
            queued_reqs = len(self.pending)
            queued_toks = self._queued_tokens
        self._metrics['queued_requests'].set(queued_reqs)
        self._metrics['queued_tokens'].set(queued_toks)
        # used = REFERENCED blocks only; cached (refcount-0,
        # reclaimable) bytes are split out so a full-looking pool
        # that is mostly reusable cache reads as healthy
        # (docs/observability.md).
        groups = self.pool.groups.values()
        self._metrics['kv_blocks_used'].set(
            sum(g.used_blocks for g in groups))
        self._metrics['kv_used'].set(
            sum(g.used_blocks * g.block_bytes for g in groups))
        self._metrics['kv_cached'].set(
            sum(g.cached_blocks * g.block_bytes for g in groups))
        self._metrics['prefix_cached_blocks'].set(
            sum(g.cached_blocks for g in groups))
        if self.wpool is not None:
            self._metrics['kv_window_blocks_used'].set(
                self.wpool.used_blocks)
        if self._adapters is not None:
            self._adapter_metrics['resident'].set(
                self._adapters.resident_count())
        if self.prefix_caching:
            now = time.time()
            win = self._prefix_window
            if not win or now - win[-1][0] >= 1.0:
                win.append((now, self._prefix_hits_local,
                            self._prefix_misses_local))
            horizon = now - PREFIX_RATIO_WINDOW_SECONDS
            while len(win) > 1 and win[1][0] <= horizon:
                win.popleft()
            d_hits = self._prefix_hits_local - win[0][1]
            d_total = d_hits + (self._prefix_misses_local -
                                win[0][2])
            if d_total <= 0 and self._hit_ratio_gauge is not None:
                # No admissions in the whole trailing window: DROP
                # the series rather than re-export the last value
                # forever — a frozen low ratio on an idle replica
                # would keep prefix-hit-ratio-low firing with no
                # traffic behind it (absent data correctly no-fires
                # threshold rules). One unregister per idle
                # transition; traffic re-creates it lazily.
                metrics_lib.registry().unregister(
                    'skytpu_batch_prefix_hit_ratio')
                self._hit_ratio_gauge = None
            if d_total > 0:
                # Re-resolve via get-or-create on EVERY write (a
                # dict lookup): the family is process-global, and a
                # sibling engine's idle sweep may have unregistered
                # it — a cached reference would keep set()ing a
                # detached object while the series silently vanished
                # from /metrics. Still lazy: only a caching engine
                # with traffic in-window exports a ratio (no fake
                # 0%). The series is UNLABELED and therefore
                # last-writer-wins: it assumes the production
                # layout of one engine per replica process
                # (serve_model builds exactly one) — two engines
                # with live traffic in one process would flap it.
                # Sibling engines only arise in tests, where at
                # most one has in-window traffic at a time.
                self._hit_ratio_gauge = \
                    metrics_lib.registry().gauge(
                        'skytpu_batch_prefix_hit_ratio',
                        'Fraction of prompt KV blocks served '
                        'from the prefix cache at admission '
                        'over the trailing window (a windowed '
                        'rate, not a since-boot cumulative — '
                        'the prefix-hit-ratio-low alert needs '
                        'regressions visible within its '
                        'window).')
                self._hit_ratio_gauge.set(d_hits / d_total)
        if self.speculative:
            # Trailing-window speculative accept rate — the same
            # windowed-rate / lazy-register / idle-unregister
            # contract as the prefix hit ratio above (the
            # spec-accept-rate-low rule must see a collapse within
            # one window, and an idle or spec-off replica must not
            # export a frozen ratio that keeps it firing).
            now = time.time()
            win = self._spec_window
            if not win or now - win[-1][0] >= 1.0:
                win.append((now, self._spec_proposed_local,
                            self._spec_accepted_local))
            horizon = now - SPEC_RATIO_WINDOW_SECONDS
            while len(win) > 1 and win[1][0] <= horizon:
                win.popleft()
            d_prop = self._spec_proposed_local - win[0][1]
            d_acc = self._spec_accepted_local - win[0][2]
            if d_prop <= 0 and self._spec_ratio_gauge is not None:
                metrics_lib.registry().unregister(
                    'skytpu_batch_spec_accept_ratio')
                self._spec_ratio_gauge = None
            if d_prop > 0:
                # Get-or-create on every write (sibling-engine idle
                # sweeps may unregister the process-global family);
                # unlabeled, one-engine-per-process assumption as
                # the prefix ratio documents.
                self._spec_ratio_gauge = \
                    metrics_lib.registry().gauge(
                        'skytpu_batch_spec_accept_ratio',
                        'Accepted/proposed draft tokens over the '
                        'trailing window (a windowed rate — the '
                        'spec-accept-rate-low alert needs '
                        'collapses visible within its window). '
                        'LAZY: only exported by a speculative '
                        'engine that proposed drafts in-window.')
                self._spec_ratio_gauge.set(d_acc / d_prop)

    def _fail_all(self, exc: BaseException) -> None:
        """Fail-stop for ENGINE death (an unexpected loop exception):
        unblock every waiter — a silently dead loop thread would hang
        all current AND future requests forever — and push the FATAL
        exception ahead of each sentinel, so clients see a failure
        (serve_model answers it 500, which the replica-5xx-rate page
        needs to notice a dead engine) instead of a silently
        truncated 200. Pool exhaustion never comes here: it preempts
        or fails the one request."""
        logger.error('Batching engine died: %r', exc)
        self._drain_all(exc=exc)

    def _drain_all(self, exc: Optional[BaseException] = None) -> None:
        """Put the None sentinel on every active slot queue and every
        still-pending request so no waiter blocks past loop exit.
        ``exc`` (engine death only — a clean close() drains without
        it) precedes each sentinel as the typed failure. The death
        exception is also stashed so requests submitted AFTER the
        drain fail typed too (submit_request) — a dead replica must
        answer 500, not a clean-looking empty 200, or the
        replica-5xx-rate page never notices it."""
        if exc is not None:
            self._death_exc = exc
        self._stop = True
        for i, req in enumerate(self.slot_req):
            if req is not None:
                if exc is not None:
                    req.out.put(exc)
                req.out.put(None)
                self.slot_req[i] = None
        waiting, self._adapter_wait = self._adapter_wait, []
        for req in waiting:
            if exc is not None:
                req.out.put(exc)
            req.out.put(None)
        while True:
            req = self._pop_pending()
            if req is None:
                return
            if exc is not None:
                req.out.put(exc)
            req.out.put(None)

    def _loop(self) -> None:
        try:
            self._loop_inner()
            # Normal exit (close() while requests are in flight):
            # drain exactly like the failure path, or blocked
            # generate()/submit() waiters hang forever on queues that
            # will never see their None sentinel.
            self._drain_all()
        except BaseException as e:  # pylint: disable=broad-except
            self._fail_all(e)

    def _mark_enqueue(self, now: Optional[float] = None) -> None:
        """A device program is about to be enqueued: close the host
        gap that the last blocking ``device_get`` opened, and count
        it. (A parked loop has no gap open, so adds nothing.)"""
        if self._gap_open is not None:
            self._metrics['host_gap_seconds'].inc(
                (time.perf_counter() if now is None else now)
                - self._gap_open)
            self._gap_open = None

    def _loop_inner(self) -> None:
        while not self._stop:
            if not self._iterate():
                with trace_lib.phase('engine.idle_wait'):
                    self.wake.wait(timeout=0.5)
                self.wake.clear()

    def _iterate(self) -> bool:
        """One pass of the scheduler: sweep, admit, prefill chunks
        under the token budget, one decode / verify dispatch, gauges.
        Returns whether it did work (ran a chunk or a dispatch). The
        ``engine.*`` phases (docs/observability.md) partition the
        pass on the profiler's clock; the ``skytpu_batch_iteration*``
        / ``host_gap`` counters account for it on the host's, and
        count only passes that did work."""
        t_top = time.perf_counter()
        self._iter_n += 1
        with trace_lib.phase('engine.iteration', n=self._iter_n,
                             queued=len(self.pending)):
            if faults_lib.fire('serve.stall'):
                # Chaos drill (docs/resilience.md): stall the
                # scheduler iteration regardless of armed kind so
                # in-flight deadlines can be driven to expiry
                # deterministically — the sweep right below must
                # then abort them typed and reclaim their blocks.
                time.sleep(float(os.environ.get(
                    'SKYTPU_SERVE_STALL_SECONDS', '1.0')))
            with trace_lib.phase('engine.sweep'):
                self._sweep_overload()
                self._poll_adapter_loads()
            with trace_lib.phase('engine.admit',
                                 queued=len(self.pending)):
                self._admit_pending()
            with trace_lib.phase('engine.prefill'):
                progressed = self._run_prefill_chunks()
            ran = self._dispatch_decode()
            with trace_lib.phase('engine.gauges'):
                self._set_gauges()
            t_end = time.perf_counter()
        if not (progressed or ran):
            # Parking in wake.wait: an idle engine accrues nothing.
            self._gap_open = None
            return False
        if self._gap_open is not None:
            # The gap runs on into the next pass: account for this
            # pass's part of it now.
            self._mark_enqueue(t_end)
            self._gap_open = t_end
        self._metrics['iterations'].inc()
        self._metrics['iteration_seconds'].inc(t_end - t_top)
        return True
