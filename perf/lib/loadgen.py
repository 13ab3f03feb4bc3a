"""Traffic from a data file and a seed: one general generator per
``kind``. A mix is a file of parameters under ``perf/traffic/``; a
later PR adds a mix by adding a file, and a kind by adding a module
``perf/lib/traffic_<kind>.py`` that ``generator_for`` finds by name.

Every seed gets the SAME multiset of sizes and inter-arrival gaps (the
distribution's quantiles), dealt into blocks that each span the whole
range, in another order: the seed changes order and token ids, never
the amount of work."""
import importlib
import json
import math
import os
import statistics
from typing import Any, Dict, List

import numpy as np

TRAFFIC_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    'traffic')


def load_traffic(name: str, traffic_dir: str = TRAFFIC_DIR
                 ) -> Dict[str, Any]:
    """Parameters of mix ``name``; ``extends`` names a mix whose
    parameters this one overrides (the two rates of one mix)."""
    with open(os.path.join(traffic_dir, name + '.json')) as f:
        spec = json.load(f)
    if 'extends' in spec:
        base = load_traffic(spec.pop('extends'), traffic_dir)
        base.update(spec)
        spec = base
    return spec


def generator_for(kind: str):
    """The generator of a traffic kind: ``generate_<kind>`` here, or
    ``generate`` in ``perf/lib/traffic_<kind>.py``."""
    fn = globals().get('generate_' + kind)
    if fn is not None:
        return fn
    return importlib.import_module('perf.lib.traffic_' + kind).generate


def _lognormal_quantiles(n: int, median: float, sigma: float,
                         lo: int, hi: int) -> np.ndarray:
    """The n mid-quantiles of a clipped lognormal, as integers."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo,
                   hi).astype(np.int64)


def _exponential_quantiles(n: int, mean: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (mean / gaps.mean())  # keep the offered rate exact


def _dealt(values: np.ndarray, block: int,
           rng: np.random.Generator) -> np.ndarray:
    """Values dealt into blocks of about ``block`` that each span the
    whole sorted range, shuffled inside each block and the blocks
    shuffled: any stretch of the schedule carries about the same work
    under every seed. The sorted values are dealt a round at a time,
    each round in the opposite direction to the last (as cards are
    dealt in a snake draft), so that no block collects every round's
    largest."""
    ordered = np.sort(values)
    n_blocks = max(1, math.ceil(len(ordered) / block))
    blocks: List[List[Any]] = [[] for _ in range(n_blocks)]
    for start in range(0, len(ordered), n_blocks):
        hand = ordered[start:start + n_blocks]
        if (start // n_blocks) % 2:
            hand = hand[::-1]
        for b, v in enumerate(hand):
            blocks[b].append(v)
    out = []
    for i in rng.permutation(n_blocks):
        b = np.asarray(blocks[i])
        rng.shuffle(b)
        out.append(b)
    return np.concatenate(out)


def _zipf_ids(n: int, n_ids: int, s: float) -> np.ndarray:
    """``n`` draws over ``n_ids`` ids with Zipf(``s``) shares, made
    exact: each id gets its share of ``n`` by largest remainder, and
    the ids are spread evenly along the index (so along the sorted
    lengths they are paired with)."""
    w = 1.0 / np.arange(1, n_ids + 1) ** s
    want = n * w / w.sum()
    counts = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - counts))[:n - counts.sum()]:
        counts[i] += 1
    # Id k's j-th copy sits at (j + 0.5) / counts[k] of the range.
    where = np.concatenate([(np.arange(c) + 0.5) / c for c in counts])
    ids = np.repeat(np.arange(n_ids), counts)
    return ids[np.argsort(where, kind='stable')]


def _requests(spec: Dict[str, Any], n: int, due: np.ndarray,
              rng: np.random.Generator, vocab_size: int
              ) -> List[Dict[str, Any]]:
    """``n`` requests due at ``due`` (sorted): the n quantile
    mid-points of the prompt-length and of the output-length
    distribution, dealt by the seed; every prompt starts with one of
    ``shared_prompts`` system prompts of ``shared_len`` tokens
    (Zipf ``shared_zipf_s``), counted inside its length, and goes on
    with a body of its own."""
    block = int(spec['deal_block'])
    p, o = spec['prompt_len'], spec['output_len']
    lengths = _lognormal_quantiles(n, p['median'], p['sigma'],
                                   p['min'], p['max'])
    n_sys, shared_len = int(spec['shared_prompts']), \
        int(spec['shared_len'])
    if lengths.min() < shared_len + 1:
        raise ValueError('the shortest prompt has to outlast the '
                         'system prompt')
    sys_id = _zipf_ids(n, n_sys, float(spec['shared_zipf_s']))
    order = _dealt(np.arange(n), block, rng)
    prompt_len, shared = lengths[order], sys_id[order]
    out_len = _dealt(_lognormal_quantiles(
        n, o['median'], o['sigma'], o['min'], o['max']), block, rng)
    systems = rng.integers(0, vocab_size, size=(n_sys, shared_len))
    requests = []
    for i in range(n):
        body = rng.integers(0, vocab_size,
                            size=int(prompt_len[i]) - shared_len)
        requests.append({
            'due_s': float(due[i]),
            'prompt': np.concatenate([systems[shared[i]],
                                      body]).tolist(),
            'max_new': int(out_len[i]), 'shared': int(shared[i])})
    return requests


def generate_open_loop(spec: Dict[str, Any], seed: int,
                       seconds: float, vocab_size: int
                       ) -> List[Dict[str, Any]]:
    """Open-loop requests over ``lead_s + seconds``: each a dict with
    ``due_s`` (relative to the window's opening; negative inside the
    lead-in), ``prompt`` (token ids), ``max_new`` and ``shared`` (its
    system prompt). N = rate x (lead + window) arrivals whose gaps
    are the N exponential quantile mid-points at mean 1 / rate, dealt
    by the seed: every seed offers the same number of requests, the
    same multiset of lengths and of gaps."""
    rng = np.random.default_rng([int(seed), 0x7261])
    rate, lead = float(spec['rate_rps']), float(spec['lead_s'])
    n = int(math.floor(rate * (lead + seconds)))
    gaps = _dealt(_exponential_quantiles(n, 1.0 / rate),
                  int(spec['deal_block']), rng)
    return _requests(spec, n, np.cumsum(gaps) - lead, rng, vocab_size)


def generate_backlog(spec: Dict[str, Any], seed: int, seconds: float,
                     vocab_size: int) -> List[Dict[str, Any]]:
    """``n_requests`` requests, all due when the lead-in starts: the
    engine is never short of work. ``n_requests`` is fixed in the mix
    so that the queue outlasts lead-in and window."""
    del seconds
    rng = np.random.default_rng([int(seed), 0x626b])
    n = int(spec['n_requests'])
    return _requests(spec, n, np.full(n, -float(spec['lead_s'])), rng,
                     vocab_size)


def generate_train_steps(spec: Dict[str, Any], seed: int,
                         step: int, batch: int, vocab_size: int
                         ) -> np.ndarray:
    """Batch ``step`` of a training feed: uniform random token ids
    ``[batch, seq_len + 1]`` (inputs and shifted targets), every row
    different, a function of (seed, step) alone."""
    rng = np.random.default_rng([int(seed), 0x7366, int(step)])
    return rng.integers(0, vocab_size,
                        size=(batch, int(spec['seq_len']) + 1),
                        dtype=np.int32)
