"""Traffic from a data file and a seed: one general generator per
``kind``. A mix is a file of parameters under ``perf/traffic/``; a
later PR adds a mix by adding a file, and a kind by adding a module
``perf/lib/traffic_<kind>.py`` that ``generator_for`` finds by name.

Every seed gets the SAME schedule: the same (prompt length, output
length, system prompt) triples and the same inter-arrival gaps (the
distributions' quantiles), dealt into blocks that each span the whole
range, in the same order. The seed draws the token ids (and the run's
weights), never the amount of work nor when it falls due. Which
output a prompt gets and where the pair stands in the schedule are
the mix's: a decode step costs what its dispatch's longest row makes
it cost, so a seed that paired the longest prompts with the longest
outputs, or sent them together, held more work than one that did
not."""
import hashlib
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


def _snake_blocks(n: int, block: int) -> List[np.ndarray]:
    """Ranks 0 .. n-1 dealt into blocks of about ``block`` that each
    span the whole range: a round at a time, each round in the
    opposite direction to the last (as cards are dealt in a snake
    draft), so that no block collects every round's largest. No seed
    has a say; every block's ranks ascend."""
    n_blocks = max(1, math.ceil(n / block))
    blocks: List[List[int]] = [[] for _ in range(n_blocks)]
    for start in range(0, n, n_blocks):
        hand = list(range(start, min(n, start + n_blocks)))
        if (start // n_blocks) % 2:
            hand = hand[::-1]
        for b, rank in enumerate(hand):
            blocks[b].append(rank)
    return [np.asarray(b, dtype=np.int64) for b in blocks]


def _stride(m: int) -> int:
    """The whole number nearest ``m`` / 1.618 that shares no factor
    with ``m``: stepping by it visits every residue, and no two
    neighbours land near each other."""
    for s in sorted(range(1, max(2, m)),
                    key=lambda s: abs(s - m / 1.618034)):
        if math.gcd(s, m) == 1:
            return s
    return 1


def _dealt_order(blocks: List[np.ndarray]) -> np.ndarray:
    """The blocks' members as one index array: the schedule's order,
    with no seed in it. Block ``(k x stride) mod n_blocks`` comes
    ``k``-th: neighbouring blocks differ by where in each round of
    the deal their members sit (block 0 holds the longest of the last
    round), so this spreads any run of them evenly over the schedule.
    Inside the ``k``-th block the ``(j x stride + k) mod m``-th member
    comes ``j``-th, so that no two blocks open with the same round.

    Why the seed has no say in the order (PERF.md section 6, PR 34):
    on a program whose decode step costs what its dispatch's longest
    row makes it cost, the order IS work. With the members shuffled
    by the seed inside each block, the same seed run twice read the
    same to 0.3-1.4 % and six seeds spread by 6.5 %; a seed that also
    shuffled the blocks spread a backlog cell's rate by 7-8 % in a row
    simulator fed with the measured step times. The seed keeps the
    token ids and the weights."""
    n_blocks = len(blocks)
    stride = _stride(n_blocks)
    out = []
    for k in range(n_blocks):
        members = blocks[k * stride % n_blocks]
        m = len(members)
        out.append(members[(np.arange(m) * _stride(m) + k) % m])
    return np.concatenate(out)


def _dealt(values: np.ndarray, block: int) -> np.ndarray:
    """Values dealt into blocks of about ``block`` that each span the
    whole sorted range (``_snake_blocks``), in the schedule's order
    (``_dealt_order``): any stretch of the schedule carries the same
    work, to within a block."""
    return np.sort(values)[_dealt_order(
        _snake_blocks(len(values), block))]


def _paired_ranks(blocks: List[np.ndarray]) -> np.ndarray:
    """For every prompt rank the rank of the output it gets, by a
    rule with no seed in it. Sorted prompts and sorted outputs are
    dealt into the same blocks; inside block ``b`` the ``j``-th
    prompt takes the ``(j x stride + b) mod m``-th output. A block
    keeps one prompt and one output from every round of the deal
    (every tenth of either range, at ``deal_block`` 10), and as ``b``
    runs over the blocks each tenth of the prompts meets every tenth
    of the outputs equally often: independent in the large, as the
    mixes say, with nothing left to chance."""
    out_rank = np.empty(sum(len(b) for b in blocks), dtype=np.int64)
    for b, ranks in enumerate(blocks):
        m = len(ranks)
        out_rank[ranks] = ranks[(np.arange(m) * _stride(m) + b) % m]
    return out_rank


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
    distribution, paired by ``_paired_ranks`` and the pairs dealt by
    ``_dealt_order``; every prompt starts with one of ``shared_prompts``
    system prompts of ``shared_len`` tokens (Zipf ``shared_zipf_s``,
    spread evenly along the sorted prompt lengths), counted inside
    its length, and goes on with a body of its own. The sequence of
    (prompt length, output length, system prompt) is a function of
    the mix and ``n`` alone; the seed draws the token ids."""
    p, o = spec['prompt_len'], spec['output_len']
    lengths = _lognormal_quantiles(n, p['median'], p['sigma'],
                                   p['min'], p['max'])
    outputs = _lognormal_quantiles(n, o['median'], o['sigma'],
                                   o['min'], o['max'])
    n_sys, shared_len = int(spec['shared_prompts']), \
        int(spec['shared_len'])
    if lengths.min() < shared_len + 1:
        raise ValueError('the shortest prompt has to outlast the '
                         'system prompt')
    sys_id = _zipf_ids(n, n_sys, float(spec['shared_zipf_s']))
    blocks = _snake_blocks(n, int(spec['deal_block']))
    out_of = outputs[_paired_ranks(blocks)]
    order = _dealt_order(blocks)
    prompt_len, shared, out_len = \
        lengths[order], sys_id[order], out_of[order]
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


def offered(requests: List[Dict[str, Any]], past: int = 0
            ) -> Dict[str, Any]:
    """What a schedule offers, whatever its order: the count, the
    prompt and output tokens, a digest of the multiset of (prompt
    length, output length, system prompt), and the decode row-steps
    spent past position ``past`` (a step there makes its whole
    dispatch read the table that far)."""
    triples = sorted((len(r['prompt']), r['max_new'], r['shared'])
                     for r in requests)
    return {
        'n': len(triples),
        'prompt_tokens': sum(t[0] for t in triples),
        'output_tokens': sum(t[1] for t in triples),
        'row_steps_past': sum(
            max(0, min(t[1], t[0] + t[1] - past)) for t in triples),
        'triples_sha1': hashlib.sha1(
            repr(triples).encode()).hexdigest()[:12]}


def generate_open_loop(spec: Dict[str, Any], seed: int,
                       seconds: float, vocab_size: int
                       ) -> List[Dict[str, Any]]:
    """Open-loop requests over ``lead_s + seconds``: each a dict with
    ``due_s`` (relative to the window's opening; negative inside the
    lead-in), ``prompt`` (token ids), ``max_new`` and ``shared`` (its
    system prompt). N = rate x (lead + window) arrivals whose gaps
    are the N exponential quantile mid-points at mean 1 / rate, dealt
    as the lengths are: every seed offers the same requests at the
    same instants."""
    rng = np.random.default_rng([int(seed), 0x7261])
    rate, lead = float(spec['rate_rps']), float(spec['lead_s'])
    n = int(math.floor(rate * (lead + seconds)))
    gaps = _dealt(_exponential_quantiles(n, 1.0 / rate),
                  int(spec['deal_block']))
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
