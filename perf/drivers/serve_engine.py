"""Driver of a served configuration: the program's own paged
``BatchingEngine``, built with the arguments ``recipes/serve_model``
passes, under open-loop load through ``submit_request`` - the call the
HTTP handler makes. In-process, because only the process that holds
the chip can seed the weights, compare logits and trace it."""
import gc
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from perf.lib import harness
from perf.lib import loadgen
from perf.lib import stats
from perf.lib import weights as weights_lib


class _Tracked:
    """One request under load: when it was due, and when each of its
    tokens came back."""

    def __init__(self, spec: Dict[str, Any], due_abs: float,
                 stamps: List[float]):
        self.spec = spec
        self.due_abs = due_abs
        self.stamps = stamps  # every request's token instants
        self.req = None
        self.submitted_late_s = 0.0
        self.tokens: List[int] = []
        self.times: List[float] = []
        self.error: Optional[BaseException] = None
        self.done = threading.Event()

    def consume(self) -> None:
        """Read the request's queue to its end (a typed error object
        may precede the sentinel), stamping each token."""
        while True:
            item = self.req.out.get()
            if item is None:
                break
            if isinstance(item, BaseException):
                self.error = item
                continue
            now = time.perf_counter()
            self.tokens.append(int(item))
            self.times.append(now)
            self.stamps.append(now)
        self.done.set()


def _collect(req) -> List[int]:
    out = []
    while True:
        item = req.out.get()
        if item is None:
            return out
        if isinstance(item, BaseException):
            raise item
        out.append(int(item))


def _warm_up(engine, vocab: int, chunk: int, seed: int) -> None:
    """Every executable the mix can reach, before the window: one
    prefill per chunk bucket (1, 2, 4, ... ``chunk``), a prompt of
    two chunks, a repeat that hits the prefix cache inside a block
    (the copy-on-write path), decode dispatches on many rows at
    once, and a live speculative verify. All greedy, as the mix is.

    The engine prewarms its verify step with another call signature
    than its live dispatch uses, so the first live draft lowers it
    anew. Greedy streams of seeded weights repeat themselves within
    some tens of tokens, which is what makes the engine draft: the
    waves run until the program's own counter of proposed draft
    tokens moves (at most three), so that it happens here and not
    inside the window."""
    from skypilot_tpu import metrics as metrics_lib
    rng = np.random.default_rng([int(seed), 0x7761])
    lengths, bucket = [], 1
    while bucket <= chunk:
        lengths.append(bucket)
        bucket *= 2
    lengths.append(chunk + 8)
    prompts = [rng.integers(0, vocab, size=n).tolist()
               for n in lengths]
    # A repeat of the two-chunk prompt cut inside a block.
    prompts.append(prompts[-1][:chunk - 8] +
                   rng.integers(0, vocab, size=24).tolist())
    proposed = {f.name: f for f in metrics_lib.registry().families()
                }['skytpu_batch_spec_proposed_total']
    before = proposed.value
    for _ in range(3):
        for req in [engine.submit_request(p, 32) for p in prompts]:
            _collect(req)
        if not engine.speculative or proposed.value > before:
            return
        prompts = [rng.integers(0, vocab, size=48).tolist()
                   for _ in range(engine.slots)]
    harness.say('warm-up: no live verify dispatch came about; the '
                'first draft of the window will lower it')


class Served:
    """The system under test, built once: the program's engine on
    benchmark-made weights, every shape of the mix warmed."""

    def __init__(self, loaded: Dict[str, Any], seed: int,
                 rehearse: bool):
        import jax
        from skypilot_tpu.serve.batching import BatchingEngine
        from skypilot_tpu.utils import jax_runtime

        config = loaded['config']
        self.device = harness.require_devices(
            loaded['cell']['chips'], rehearse)
        jax_runtime.configure_compile_cache()
        self.prog = harness.program_config(config)
        self.model = config['model']
        self.build = config['build']
        self.traffic = loaded['traffic']
        self.params, _ = weights_lib.make_weights(
            self.model, seed, int8=config['weights'] == 'int8',
            dtype=self.prog.dtype)
        self.engine = BatchingEngine(self.params, self.prog,
                                     **self.build)
        _warm_up(self.engine, self.model['vocab_size'],
                 self.engine.prefill_chunk, seed)
        jax.block_until_ready(self.engine.caches)

    def close(self) -> None:
        """Stop the engine and free its cache; the weights stay, as
        data for the reference."""
        self.engine.close()
        del self.engine.caches


def _whole_burst_edges(stamps: List[float], t_from: float,
                       seconds: float, gap_s: float):
    """The window over whole bursts of emission: the engine hands
    tokens back a dispatch at a time, so a window cut at fixed
    instants counts a burst more or less by chance (2-3 % of a
    minute's tokens). It opens with the first burst that starts at or
    after ``t_from`` and closes with the first that starts
    ``seconds`` or more later; a burst is a run of tokens less than
    ``gap_s`` apart. ``(open, close)``, or None while the closing
    burst has not come."""
    times = sorted(stamps)
    starts = [t for prev, t in zip([-math.inf] + times, times)
              if t - prev > gap_s]
    opened = next((t for t in starts if t >= t_from), None)
    if opened is None:
        return None
    closed = next((t for t in starts if t >= opened + seconds), None)
    return None if closed is None else (opened, closed)


def drive(served: Served, requests: List[Dict[str, Any]],
          seconds: float, on_open=None, on_close=None, tracer=None
          ) -> Dict[str, Any]:
    """Offer ``requests`` to the engine on their schedule through the
    lead-in and the window, stamp every token, and cancel what is
    left when the window has closed. The window is ``seconds`` from
    the lead-in's end, or, where the mix says ``"window_edges":
    "bursts"``, the whole bursts of emission from then on (see
    ``_whole_burst_edges``)."""
    engine, traffic = served.engine, served.traffic
    by_bursts = traffic.get('window_edges') == 'bursts'
    t_lead_end = time.perf_counter() + float(traffic['lead_s'])
    t_nominal_close = t_lead_end + seconds
    stamps: List[float] = []
    tracked = [_Tracked(r, t_lead_end + r['due_s'], stamps)
               for r in requests if r['due_s'] < seconds]
    threads: List[threading.Thread] = []
    trace_from = t_lead_end + float(traffic['trace_start_s'])
    trace_until = trace_from + float(traffic['trace_seconds'])
    state = {'opened': False, 'trace': 'idle'}
    # The host's load average where the window opens and closes: a
    # stall of seconds that the program's logs cannot explain shows
    # here if the machine's other tenants caused it.
    loadavg = {}
    # Python's own collections, timed: the engine's thread and the
    # consumers share this interpreter, and a collection stops both.
    collections: List[tuple] = []  # (start, seconds, generation)
    gc_started = 0.0

    def on_gc(phase: str, info: Dict[str, Any]) -> None:
        nonlocal gc_started
        now = time.perf_counter()
        if phase == 'start':
            gc_started = now
        else:
            collections.append((gc_started, now - gc_started,
                                info['generation']))

    gc.callbacks.append(on_gc)

    def tick(now: float) -> None:
        """Open the window and run the profiler at their instants;
        called from the one scheduling thread."""
        if not state['opened'] and now >= t_lead_end:
            state['opened'] = True
            loadavg['open'] = os.getloadavg()
            if on_open is not None:
                on_open()
        if tracer is not None:
            if state['trace'] == 'idle' and now >= trace_from:
                tracer.start()
                state['trace'] = 'on'
            elif state['trace'] == 'on' and now >= trace_until:
                # Writing the trace takes seconds: off this thread,
                # so that arrivals keep their schedule.
                th = threading.Thread(target=tracer.stop)
                th.start()
                threads.append(th)
                state['trace'] = 'done'

    for item in tracked:
        while True:
            now = time.perf_counter()
            tick(now)
            wait = item.due_abs - now
            if wait <= 0:
                break
            with harness.annotate('between_arrivals'):
                time.sleep(min(wait, 0.02))
        with harness.annotate('submit'):
            item.req = engine.submit_request(item.spec['prompt'],
                                             item.spec['max_new'])
        item.submitted_late_s = time.perf_counter() - item.due_abs
        th = threading.Thread(target=item.consume, daemon=True)
        th.start()
        threads.append(th)
    edges = None
    gap_s = float(traffic.get('burst_gap_s', 0.02))
    while True:
        now = time.perf_counter()
        tick(now)
        if now >= t_nominal_close:
            if not by_bursts:
                edges = (t_lead_end, t_nominal_close)
                break
            # A burst's stamps come from as many threads as it has
            # rows: read the edges once its first has aged.
            found = _whole_burst_edges(list(stamps), t_lead_end,
                                       seconds, gap_s)
            if found is not None and now - found[1] > 5 * gap_s:
                edges = found
                break
            if now > t_nominal_close + 30.0:
                raise harness.HarnessError(
                    'no burst of tokens closed the window within 30 s '
                    'of its nominal end: the engine stopped emitting')
        with harness.annotate('collect'):
            time.sleep(0.01)
    loadavg['close'] = os.getloadavg()
    gc.callbacks.remove(on_gc)
    if state['trace'] == 'on':
        tracer.stop()
    if on_close is not None:
        on_close()
    t_open, t_close = edges
    for item in tracked:
        if not item.done.is_set():
            engine.cancel(item.req)
    for item in tracked:
        if not item.done.wait(timeout=60):
            raise harness.HarnessError(
                'a cancelled request did not end within 60 s')
    for th in threads:
        th.join(timeout=60)
    vocab = served.model['vocab_size']

    def whole(i: _Tracked) -> bool:
        return (i.error is None and
                len(i.tokens) == i.spec['max_new'] and
                all(0 <= t < vocab for t in i.tokens))

    # A request that the harness cancelled after the window did not
    # fail; one that ended by itself short, long, outside the
    # vocabulary or in a typed error did.
    failed = [i for i in tracked
              if not whole(i) and not i.req.cancelled]
    complete = [i for i in tracked if whole(i)]
    inside = sorted(t for t in stamps if t_open <= t < t_close)
    gaps = [(b - a, a - t_open) for a, b in zip(inside, inside[1:])]
    return {'t_open': t_open, 't_close': t_close, 'tracked': tracked,
            'complete': complete, 'failed': failed,
            'loadavg': loadavg,
            'longest_token_gap': max(gaps, default=(0.0, 0.0)),
            'collections': [(sec, gen, t0 - t_open)
                            for t0, sec, gen in collections
                            if t_open <= t0 < t_close],
            'finished_in_window': [
                i for i in complete
                if t_open <= i.times[-1] < t_close],
            'first_in_window': [
                i for i in tracked
                if i.times and t_open <= i.times[0] < t_close],
            'window_tokens': sum(1 for t in stamps
                                 if t_open <= t < t_close)}


def summarize(drove: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers of one window: tokens emitted inside it over its
    length; per-request time per output token over the requests that
    finished inside it, and its median over them; time to first
    token, from the due instant, over the requests whose first token
    fell inside it; how late the generator sent them. Medians and
    90th percentiles are linearly interpolated; the per-layer metrics
    and earlier lines of the output carry the rest."""
    window_s = drove['t_close'] - drove['t_open']
    finished, first = drove['finished_in_window'], \
        drove['first_in_window']
    tpot = [x for x in (stats.tpot_ms(i.times[0], i.times[-1],
                                      len(i.times)) for i in finished)
            if x is not None]
    ttft = [(i.times[0] - i.due_abs) * 1e3 for i in first]
    late = [i.submitted_late_s * 1e3 for i in first]
    out = {'window_s': window_s, 'tpot_ms': tpot, 'ttft_ms': ttft,
           'late_ms': late,
           'e2e': {'out_tok_s': drove['window_tokens'] / window_s}}
    if tpot:
        out['e2e']['tpot_p50_ms'] = stats.percentile(tpot, 50)
    fifths = [0] * 5
    for item in drove['tracked']:
        for t in item.times:
            if drove['t_open'] <= t < drove['t_close']:
                fifths[min(4, int((t - drove['t_open']) /
                                  window_s * 5))] += 1
    waiting = sum(1 for i in drove['tracked'] if not i.times)
    harness.say(
        f'window {window_s:.3f} s: submitted {len(drove["tracked"])} '
        f'complete {len(drove["complete"])} finished in window '
        f'{len(finished)} failed {len(drove["failed"])} without '
        f'first token at close {waiting}; tokens in window '
        f'{drove["window_tokens"]} ({out["e2e"]["out_tok_s"]:.2f}/s; '
        f'by fifths {fifths}); requests finished per second '
        f'{len(finished) / window_s:.4f}')
    gap_s, gap_at = drove['longest_token_gap']
    gcs = drove['collections']
    gc_s, gc_gen, gc_at = max(gcs, default=(0.0, -1, 0.0))
    harness.say(
        f'stalls: longest gap between two bursts of tokens '
        f'{gap_s:.3f} s at +{gap_at:.1f} s; longest Python garbage '
        f'collection {gc_s:.3f} s (generation {gc_gen}) at '
        f'+{gc_at:.1f} s, {len(gcs)} collections of '
        f'{sum(c[0] for c in gcs):.3f} s in all; host load average '
        f'(1, 5, 15 min) at the opening {drove["loadavg"].get("open")}'
        f' at the close {drove["loadavg"].get("close")}')
    if ttft and tpot:
        harness.say(
            'ttft ms p50 %.1f p90 %.1f max %.1f (n=%d); tpot ms p50 '
            '%.2f p90 %.2f max %.2f mean %.2f (n=%d); generator late '
            'ms p50 %.2f p90 %.2f max %.2f' % (
                stats.percentile(ttft, 50), stats.percentile(ttft, 90),
                max(ttft), len(ttft), stats.percentile(tpot, 50),
                stats.percentile(tpot, 90), max(tpot),
                sum(tpot) / len(tpot), len(tpot),
                stats.percentile(late, 50), stats.percentile(late, 90),
                max(late)))
    return out


def run(loaded: Dict[str, Any], seed: int, seconds: float, trace: bool,
        rehearse: bool, t_process_start: float) -> Dict[str, Any]:
    """One run of a serving cell."""
    from skypilot_tpu import metrics as metrics_lib

    config, cell = loaded['config'], loaded['cell']
    compiles = harness.CompileCounter()
    served = Served(loaded, seed, rehearse)
    requests = loadgen.generator_for(served.traffic['kind'])(
        served.traffic, seed, seconds, served.model['vocab_size'])
    past = int(served.build['max_seq']) * 3 // 4
    harness.say(f'offered (the same under every seed; decode '
                f'row-steps past position {past}): '
                f'{loadgen.offered(requests, past)}')
    registry = None
    if trace:
        from perf.lib.registry_delta import RegistryWindow
        registry = RegistryWindow(
            metrics_lib.registry(), config['sampled_gauges'])
    tracer = harness.TraceWindow(cell['name']) if trace and \
        not rehearse else None

    def on_open():
        compiles.open()
        if registry is not None:
            registry.open()

    def on_close():
        compiles.close()
        if registry is not None:
            registry.close()

    drove = drive(served, requests, seconds, on_open, on_close, tracer)
    # The lead-in (load before the window, so that it opens on a
    # running system) is set-up.
    summed = summarize(drove)
    e2e = summed['e2e']
    e2e['setup_s'] = drove['t_open'] - t_process_start
    harness.say(f'compilations inside the window {compiles.inside}')
    peak = harness.memory_peak_bytes()
    served.close()

    # ---- correct: the served tokens against the plain reference
    results: List[Dict[str, Any]] = []
    t_ref = time.perf_counter()
    gaps = check_served(loaded, served.params, served.model, drove,
                        seed, weight_format=None)
    ref_s = time.perf_counter() - t_ref
    ok = harness.compared('compilations_in_window', compiles.inside,
                          0, results)
    ok &= harness.compared('requests_ended_wrong',
                           len(drove['failed']), 0, results)
    ok &= harness.compared('served_tokens_missing', gaps['missing'],
                           0, results)
    ok &= harness.compared(
        'served_logit_gap_max', gaps['served'],
        config['limits']['served_logit_gap_max'], results)
    harness.say(f'reference check took {ref_s:.1f} s '
                f'(not counted in setup_s)')
    facts = {'ttft_ms': summed['ttft_ms'],
             'tpot_ms': summed['tpot_ms'],
             'late_ms': summed['late_ms'],
             'steps_per_dispatch': served.engine.steps,
             'slots': served.engine.slots,
             'block_size': served.engine.block_size,
             'experts_held': config.get('experts_held'),
             'kv_bytes': 1 if served.build.get('kv_int8') else 2,
             'weight_bytes': 1 if config['weights'] == 'int8' else 2}
    return {'correct': bool(ok), 'attempted': len(drove['tracked']),
            'failed': len(drove['failed']), 'e2e': e2e,
            'device': served.device, 'memory_peak_bytes': peak,
            'registry': registry,
            'tracer': tracer if tracer is not None and tracer.seconds
            else None,
            'facts': facts, 'model': served.model, 'compared': results}


def check_sample(config: Dict[str, Any], drove: Dict[str, Any],
                 seed: int) -> List[int]:
    """Which requests ``correct`` compares, as indices of the mix's
    order (of ``drove['tracked']``): of the requests that finished
    inside the window with the last ``check_tokens`` of their tokens
    all stamped inside it, the longest (prompt + served tokens, ties
    by the mix's order) and ``check_requests`` - 1 more by the seed.
    Where fewer than ``check_requests`` such requests are, every
    request that finished inside the window stands for them, and
    ``check_served`` counts what is missing. In a backlog most of
    these are requests of the later waves (an index of ``slots`` or
    more): they took a freed slot and recycled blocks and met a warm
    prefix cache."""
    cap, n = int(config['check_tokens']), int(config['check_requests'])
    tracked = drove['tracked']
    inside = {id(i) for i in drove['finished_in_window']}
    finished = [j for j, i in enumerate(tracked) if id(i) in inside]
    if not finished:
        raise harness.HarnessError(
            'no request finished inside the window: nothing to compare '
            'with the reference')
    late = [j for j in finished if len(tracked[j].tokens) >= cap and
            tracked[j].times[-cap] >= drove['t_open']]
    among = late if len(late) >= n else finished
    harness.say(f'reference: {len(finished)} requests finished inside '
                f'the window, {len(late)} of them with their last '
                f'{cap} tokens inside it')
    longest = max(among, key=lambda j: (
        len(tracked[j].spec['prompt']) + len(tracked[j].tokens), -j))
    rest = [j for j in among if j != longest]
    rng = np.random.default_rng([int(seed), 0x6368])
    return [longest] + [rest[k] for k in
                        rng.permutation(len(rest))[:n - 1]]


def check_served(loaded: Dict[str, Any], params, model,
                 drove: Dict[str, Any], seed: int,
                 weight_format: Optional[str]) -> Dict[str, float]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the LAST ``check_tokens`` tokens of each
    request of ``check_sample``: its longest context, and a count of
    tokens that does not grow with the program's speed (a maximum over
    more tokens reads higher: PERF.md section 6, PR 39 and PR 45). The
    reference follows the whole request. ``missing`` is how many of
    ``check_requests`` x ``check_tokens`` tokens stamped inside the
    window were not there to compare. A request's
    ``spec['reference']``, where the driver put one, holds what else
    the reference needs to follow it (a sampled row's temperature and
    seed). With ``weight_format`` also the control's reading
    (``lower``): the same for the tokens the lower precision would put
    first."""
    config = loaded['config']
    reference = harness.reference_for(config)
    cap = int(config['check_tokens'])
    picks = check_sample(config, drove, seed)
    widest = {'served': 0.0, 'lower': 0.0}
    summed = {'served': 0.0, 'lower': 0.0}
    counts, inside = [], 0
    sizes = []
    for item in (drove['tracked'][j] for j in picks):
        total = len(item.spec['prompt']) + len(item.tokens)
        sizes.append(total)
        pad_to = next(b for b in config['check_pad_to'] if b >= total)
        served, lower = reference.served_token_gaps(
            params, model, list(item.spec['prompt']), item.tokens,
            pad_to, weight_format=weight_format,
            **item.spec.get('reference', {}))
        for name, gaps in (('served', served), ('lower', lower)):
            widest[name] = max(widest[name], float(gaps[-cap:].max()))
            summed[name] += float(gaps[-cap:].sum())
        counts.append(len(item.tokens[-cap:]))
        inside += sum(1 for t in item.times[-cap:]
                      if drove['t_open'] <= t < drove['t_close'])
    widest['missing'] = float(max(
        0, int(config['check_requests']) * cap - inside))
    harness.say(
        f'reference: requests {picks} of the mix\'s order, the longest '
        f'first, sequences {sizes}; '
        f'tokens compared {counts} (the last {cap} of each), {inside} '
        f'of them stamped inside the window; mean gap a token served '
        f'{summed["served"] / sum(counts):.4g}' + (
            f', lower {summed["lower"] / sum(counts):.4g}'
            if weight_format else ''))
    return widest


def control_readings(loaded: Dict[str, Any], seed: int, seconds: float,
                     rehearse: bool) -> Dict[str, Dict[str, float]]:
    """The control of this kind of cell, at the cell's own size: a
    short window at the cell's own load, then over the same requests
    and tokens (``check_served``) the program's reading (``sound``)
    and the reading of the reference computed at the configuration's
    ``control`` precision (``control``), each under the name of the
    limit it is held to."""
    served = Served(loaded, seed, rehearse)
    requests = loadgen.generator_for(served.traffic['kind'])(
        served.traffic, seed, seconds, served.model['vocab_size'])
    drove = drive(served, requests, seconds)
    served.close()
    gaps = check_served(
        loaded, served.params, served.model, drove, seed,
        weight_format=loaded['config']['control']['weight_format'])
    return {'sound': {'served_logit_gap_max': gaps['served'],
                      'served_tokens_missing': gaps['missing']},
            'control': {'served_logit_gap_max': gaps['lower']}}
