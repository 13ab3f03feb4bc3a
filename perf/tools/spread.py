"""Spread of each metric over sets of runs, as the bounds are set
from it: for each directory given (one set of runs, one ``*.log`` per
run, the result line last) the values by metric, their median, the
quartile spread as a share of the median, and the same without the
run farthest from the median where that narrows it.

    python3 -m perf.tools.spread chiprun_out/p24_2/setA chiprun_out/p24_2/setB

``--judge`` takes the first two sets as the two sides of pairs on
the same seeds (the parent and the change, or one tree against
itself) and says of each end-to-end metric what the
``choosing-metrics`` guide's section 6.5 says: ``worse`` where the
second side's median is worse than the first's by more than the
metric's bound in ``BENCHMARK.json``, ``unresolved`` where either
side's trimmed spread is wider than that bound, else ``unchanged``.

Earlier lines of a run that read ``name <number>`` pairs can be pulled
out too: ``--line 'tpot ms' --field p90`` reads the number after
``p90`` on the first line containing ``tpot ms``.
"""
import argparse
import glob
import json
import os
import re
import statistics
import sys

from perf.lib import stats


def _values(directory, line, field):
    out = {}
    for path in sorted(glob.glob(os.path.join(directory, '*.log'))):
        lines = open(path).read().strip().splitlines()
        if not lines or not lines[-1].startswith('{'):
            print(f'{path}: no result line', file=sys.stderr)
            continue
        result = json.loads(lines[-1])
        if not result['correct']:
            print(f'{path}: correct is false', file=sys.stderr)
        for name, m in result['metrics'].items():
            out.setdefault(name, []).append(m['value'])
        if line:
            hit = next(x for x in lines if line in x)
            found = re.search(
                re.escape(field) + r' ([-+0-9.eE]+)',
                hit[hit.index(line):])
            out.setdefault(f'{line} {field}', []).append(
                float(found.group(1)))
    return out


def _judge(first, second) -> None:
    from perf.lib import harness
    bench = harness.load_json(harness.REPO_DIR, 'BENCHMARK.json')
    for m in bench['end_to_end']:
        a, b = first.get(m['name']), second.get(m['name'])
        if not a or not b:
            continue
        med_a, med_b = statistics.median(a), statistics.median(b)
        sign = 1 if m['better'] == 'lower' else -1
        worse_by = sign * (med_b - med_a) / med_a
        # Set-up is judged by its median alone, never by its spread.
        spread = 0.0 if m['name'] == 'setup_s' else max(
            stats.trimmed_spread(a), stats.trimmed_spread(b))
        verdict = ('worse' if worse_by > m['bound'] else
                   'unresolved' if spread > m['bound'] else 'unchanged')
        print(json.dumps({
            'metric': m['name'], 'bound': m['bound'],
            'medians': [med_a, med_b], 'worse_by': worse_by,
            'wider_trimmed_spread': spread, 'verdict': verdict}))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('sets', nargs='+')
    parser.add_argument('--line', default='')
    parser.add_argument('--field', default='')
    parser.add_argument('--judge', action='store_true')
    args = parser.parse_args(argv)
    if args.judge:
        _judge(*(_values(d, '', '') for d in args.sets[:2]))
    for directory in args.sets:
        for name, values in _values(directory, args.line,
                                    args.field).items():
            print(json.dumps({
                'set': directory, 'metric': name, 'n': len(values),
                'values': values,
                'median': statistics.median(values),
                'spread_pct': 100 * stats.quartile_spread(values),
                'trimmed_spread_pct':
                    100 * stats.trimmed_spread(values)}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
